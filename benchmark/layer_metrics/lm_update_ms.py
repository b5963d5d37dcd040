"""model_step: device ms a step under ``hvd.update`` — the inner
optimizer's pass, where the compiler left it apart; the busiest device."""

from benchmark import scopes


def read(run):
    return scopes.phase_ms_per_step(run, "update")
