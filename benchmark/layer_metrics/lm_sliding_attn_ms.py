"""model_step: device ms a step under the ``sliding_attn`` scope — the
windowed attention layers (``models/transformer.py``'s ``'sliding'``
kind): their projections, q/k norms, rotary embedding, output gate and
kernels, forward and backward (``named_events.py``); the busiest device.
None where the program names no such scope (a program without attention
kinds, or a model without this one)."""

from benchmark import named_events


def read(run):
    return named_events.ms_per_step(run, "sliding_attn")
