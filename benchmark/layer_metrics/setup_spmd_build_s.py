"""spmd_wrapper: the sum of the ``hvd/spmd/build`` spans — trace, lower,
compile or load, and first call of every program ``hvd.spmd`` built."""

from benchmark import scopes


def read(run):
    return scopes.span_seconds(scopes.record(), "hvd/spmd/build")
