"""model_step: device ms a step under the ``full_attn`` scope — the
full-causal attention layers that take no position encoding
(``models/transformer.py``'s ``'full'`` kind): their projections, q/k
norms, output gate and kernels, forward and backward
(``named_events.py``); the busiest device. None where the program names
no such scope (a program without attention kinds, or a model without
this one)."""

from benchmark import named_events


def read(run):
    return named_events.ms_per_step(run, "full_attn")
