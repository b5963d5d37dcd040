"""kernels: the routed experts' grouped matrix products' share of their
roofline, read with ``configs/trinity_mini.json``'s keys — the least time
the chip could take for the three products of every expert layer, forward
and both transposes, at the pairs the step MEASURED
(``flops_window_moe.py``: 16 groups of 2048 x 1024; ``moe.local_pairs`` of
the program's record) over the device time under the ``experts`` scope
(``named_events.py``: forward, the transposes, and the elementwise passes
between the products): the same work whatever implements it. None where
the record has no such counter or scope."""

from benchmark import flops_window_moe, named_events, scopes


def read(run):
    pairs = scopes.step_counter("moe.local_pairs")
    experts_ms = named_events.ms_per_step(run, "experts")
    if pairs is None or experts_ms is None:
        return None
    least, _ = run.flops.roofline_seconds(
        flops_window_moe.experts_step_flops(run.config, pairs),
        flops_window_moe.experts_step_bytes(run.config, pairs), run.peaks)
    return 100.0 * least / (experts_ms / 1e3)
