"""model_step: device ms a step under the ``mla`` scope, forward and
backward, every application (``named_events.py``); the busiest device.
None where the program names no such scope."""

from benchmark import named_events


def read(run):
    return named_events.ms_per_step(run, "mla")
