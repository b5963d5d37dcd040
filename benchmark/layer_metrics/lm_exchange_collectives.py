"""gradient_exchange: collectives the device runs a step under
``hvd.exchange`` — the capture's events, joined to the program's scopes
(``scopes.exchange_collectives_per_step``). The plan may ask for more:
XLA's combiner merges small buckets."""

from benchmark import scopes


def read(run):
    return scopes.exchange_collectives_per_step(run) if run.chips > 1 \
        else None
