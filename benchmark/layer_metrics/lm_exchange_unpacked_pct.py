"""gradient_exchange: the share of the exchange plan's wire bytes that is
reduced in the leaves' own shapes, with no flat fusion buffer built — the
step program's ``exchange.unpacked_bytes`` over its ``exchange.wire_bytes``,
both counted where the buckets are planned (``ops/fusion.py``)."""

from benchmark import scopes


def read(run):
    wire = scopes.step_counter("exchange.wire_bytes")
    unpacked = scopes.step_counter("exchange.unpacked_bytes")
    if not wire or unpacked is None or run.chips <= 1:
        return None
    return 100.0 * unpacked / wire
