"""gradient_exchange: collective time a step that no compute hides — the
collectives' spans (``-start`` to ``-done``) minus their overlap with other
operations on the same device; the worst device."""


def read(run):
    exposed = run.trace.exposed_collective_us()
    if not exposed or run.chips < 2:
        return None
    return max(exposed.values()) / 1e3 / run.window["steps"]
