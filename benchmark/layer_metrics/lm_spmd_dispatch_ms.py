"""spmd_wrapper: what one call of the wrapper costs the host — the median
of the window's ``hvd/spmd/dispatch`` spans, in ms."""

import statistics

from benchmark import scopes


def read(run):
    spans = scopes.window_dispatches(run, scopes.record())
    if not spans:
        return None
    return statistics.median((e - s) / 1e6 for _, s, e, _ in spans)
