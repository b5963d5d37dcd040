"""model_step: device ms a step under the expert layers' ``dispatch`` and
``combine`` scopes — the pairs' sort, the row gathers into expert order
and back, the masks, and their transposes, forward, run again in the
backward, and transposed (``named_events.py``); the busiest device. None
where the program names no such scopes."""

from benchmark import named_events


def read(run):
    parts = [named_events.ms_per_step(run, name)
             for name in ("dispatch", "combine")]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)
