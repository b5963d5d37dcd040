"""device: the instrument's own guard — the share of a device's busy time
(its events' self times) that falls in exactly one phase, neither
``other`` nor ``mixed``; the device where it is lowest."""

from benchmark import scopes


def read(run):
    rec = scopes.record()
    named = scopes.seconds_by(
        run, rec, lambda ev: ev.phase not in ("other", "mixed"))
    busy = scopes.seconds_by(run, rec, lambda ev: True)
    shares = [100.0 * named[d] / busy[d] for d in named if busy[d] > 0]
    return min(shares) if shares else None
