"""gradient_exchange: gigabytes the exchange plan moves a step, a rank —
the step program's ``exchange.wire_bytes``, counted where the buckets are
planned (``ops/fusion.py``)."""

from benchmark import scopes


def read(run):
    wire = scopes.step_counter("exchange.wire_bytes")
    return wire / 1e9 if wire and run.chips > 1 else None
