"""model_step: device ms a step under the ``conv`` scope — the gated
short-convolution mixers, their two projections and the gate-and-tap pass
between, forward and backward, every conv layer (``named_events.py``); the
busiest device. None where the program names no such scope."""

from benchmark import named_events


def read(run):
    return named_events.ms_per_step(run, "conv")
