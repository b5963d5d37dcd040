"""gradient_exchange: the share of the exchange plan's wire bytes whose
plain sum is lowered as a ring of ``collective-permute``s, which this
compiler issues asynchronously (the backward's fusions run between a
``-start`` and its ``-done``), and not as an ``all-reduce``, which it does
not — the step program's ``exchange.async_bytes``, counted where the
lowering decides (``ops/strategy.py``), over its ``exchange.wire_bytes``.
A program without the counter (a parent of PR 30) reads nothing."""

from benchmark import scopes


def read(run):
    wire = scopes.step_counter("exchange.wire_bytes")
    ring = scopes.step_counter("exchange.async_bytes")
    if not wire or ring is None or run.chips <= 1:
        return None
    return 100.0 * ring / wire
