"""spmd_wrapper: the host's seconds of set-up in ``hvd/replicate``,
``hvd/rank_stack`` and ``hvd/broadcast`` spans, less the ``hvd/spmd/build``
spans nested in them (the broadcast's program is a build:
``setup_spmd_build_s`` holds it). Host time: the device's copies end at
the runner's wait. The small programs of the lifts inside these spans
are in ``setup_other_programs_s`` too (on a cold cache their compiles are
most of this reading)."""

from benchmark import scopes
from benchmark.layer_metrics import setup_build_trace_s as build

PLACE = ("hvd/replicate", "hvd/rank_stack", "hvd/broadcast")


def read(run):
    spans = build.setup_spans(run, scopes.record())
    if not spans or not any(s[0] in PLACE for s in spans):
        return None
    return sum((e - s) * (1 if n in PLACE else -1) / 1e9
               for n, s, e, parent in spans
               if (n in PLACE and parent not in PLACE)
               or (n == build.BUILD and parent in PLACE))
