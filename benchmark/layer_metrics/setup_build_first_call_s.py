"""spmd_wrapper: what is left of set-up's ``hvd/spmd/build`` spans when
tracing, lowering and the backend's compile or load are taken out — the
dispatch of the built program and whatever it waits for. The program
keeps no row of it: it is this remainder. See ``setup_build_trace_s``."""

from benchmark import scopes
from benchmark.layer_metrics import setup_build_trace_s as build


def read(run):
    parts = build.part_s(run, "trace", "lower", "compile", "load")
    if parts is None:
        return None
    spans = build.setup_spans(run, scopes.record())
    return sum((e - s) / 1e9 for n, s, e, _ in spans
               if n == build.BUILD) - parts
