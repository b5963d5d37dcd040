"""spmd_wrapper: the seconds of set-up that JAX spent TRACING inside
``hvd/spmd/build`` spans — the step's Python, the model's, the kernels'
bodies — from the ``hvd/spmd/build/trace`` rows the program keeps of
``jax.monitoring``'s own events (``core/timeline.py`` ``jax_event``).

Set-up is everything before the window's first dispatch, so the followed
steps' programs and the broadcast's are in it. The four parts — this,
``setup_build_lower_s``, ``setup_build_backend_s`` and
``setup_build_first_call_s`` (what is left of the spans: no row) — add up
to ``setup_spmd_build_s``. None where the record holds no such row (a
parent of PR 36)."""

from benchmark import scopes

BUILD = "hvd/spmd/build"


def setup_spans(run, rec):
    """The record's rows that began before the window's first dispatch;
    None where the record holds no dispatch."""
    window = scopes.window_dispatches(run, rec)
    if not window:
        return None
    return [s for s in rec["spans"] if s[1] < window[0][1]]


def part_s(run, *parts):
    """Seconds of set-up's ``hvd/spmd/build/<part>`` rows (the program
    drops a tracing nested in another: rows of a kind never overlap)."""
    spans = setup_spans(run, scopes.record())
    if not spans or not any(s[0].startswith(BUILD + "/") for s in spans):
        return None
    names = [f"{BUILD}/{part}" for part in parts]
    return sum((e - s) / 1e9 for n, s, e, _ in spans if n in names)


def read(run):
    return part_s(run, "trace")
