"""spmd_wrapper: the seconds of set-up that JAX spent LOWERING inside
``hvd/spmd/build`` spans (a Pallas kernel is lowered to Mosaic there, a
call site at a time): the ``hvd/spmd/build/lower`` rows. See
``setup_build_trace_s``."""

from benchmark.layer_metrics import setup_build_trace_s as build


def read(run):
    return build.part_s(run, "lower")
