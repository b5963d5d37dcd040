"""model_step: device ms a step of block forwards run AGAIN inside the
backward — the program's recomputation rule (``models/transformer.py``:
a stack run more than once keeps only each block application's input).
JAX names what a checkpoint recomputes ``.../checkpoint/
rematted_computation/...`` under ``transpose(jvp(hvd.model))``: the events
of the ``backward`` phase that carry that mark (``named_events.py``); the
busiest device."""

from benchmark import named_events


def read(run):
    return named_events.ms_per_step(run, "rematted_computation", "backward")
