"""spmd_wrapper: the seconds of set-up that the backend spent COMPILING
the programs ``hvd.spmd`` built (``hvd/spmd/build/compile`` rows: the
persistent cache missed) or LOADING them from it (``/load`` rows: it
hit). See ``setup_build_trace_s``; ``setup_build_cache_misses`` says
which it was."""

from benchmark.layer_metrics import setup_build_trace_s as build


def read(run):
    return build.part_s(run, "compile", "load")
