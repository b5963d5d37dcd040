"""gradient_exchange: all device time a step under ``hvd.exchange`` —
packing, converts, collectives, unpacking; the worst device."""

from benchmark import scopes


def read(run):
    if run.chips < 2:
        return None
    return scopes.phase_ms_per_step(run, "exchange")
