"""model_step: device ms a step under the ``head`` scope — the vocabulary
projection with its cross-entropy, forward and backward, every application
of a looped model's one head (``named_events.py``); the busiest device."""

from benchmark import named_events


def read(run):
    return named_events.ms_per_step(run, "head")
