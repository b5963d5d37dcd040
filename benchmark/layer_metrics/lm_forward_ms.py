"""model_step: device ms a step under ``jvp(...)`` scopes — the loss's
forward as the user differentiates it (``benchmark/scopes.py``); the
busiest device."""

from benchmark import scopes


def read(run):
    return scopes.phase_ms_per_step(run, "forward")
