"""device: the share of the traced window in which no operation ran, on
the idlest device."""


def read(run):
    return run.trace.idle_share_pct()
