"""entry: the 95th percentile of a step's time by the host clock, each
step's completion waited for (completion to completion, two in flight)."""


def read(run):
    times = sorted(run.window["step_seconds"][1:])  # the first fills the pipe
    if len(times) < 20:
        return None
    return 1e3 * times[min(len(times) - 1, int(0.95 * len(times)))]
