"""spmd_wrapper: programs built inside the measured window —
``hvd/spmd/build`` spans that began after the window's first dispatch: a
compile nobody saw. Should read 0."""

from benchmark import scopes


def read(run):
    rec = scopes.record()
    spans = scopes.window_dispatches(run, rec)
    if not spans:
        return None
    first = spans[0][1]
    return sum(1 for n, s, _, _ in rec["spans"]
               if n == "hvd/spmd/build" and s > first)
