"""spmd_wrapper: the ``hvd/init`` span — the program's own share of
set-up before any array is placed (the native control plane's g++ build in
a checkout's first run included)."""

from benchmark import scopes


def read(run):
    return scopes.span_seconds(scopes.record(), "hvd/init")
