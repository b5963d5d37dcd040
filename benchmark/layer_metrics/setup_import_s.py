"""spmd_wrapper: ``import horovod_tpu`` from its first line to its last
(the record's ``hvd/import`` row): every ``parallel/`` module, ``tune``,
``training`` and what they import in turn — JAX itself only where the
caller had not imported it. None where the program keeps no such row."""

from benchmark import scopes


def read(run):
    return scopes.span_seconds(scopes.record(), "hvd/import")
