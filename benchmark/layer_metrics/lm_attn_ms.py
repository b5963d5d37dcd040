"""model_step: device ms a step under the ``attn`` scope — the attention
mixers, their projections, q/k norms, rotary embedding and kernels,
forward and backward, every attention layer (``named_events.py``); the
busiest device. None where the program names no such scope."""

from benchmark import named_events


def read(run):
    return named_events.ms_per_step(run, "attn")
