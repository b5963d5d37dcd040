"""spmd_wrapper: backend compiles (or loads from the persistent cache)
after the window's first dispatch began, in a build span or not. Should
read 0. ``lm_window_builds`` counts the build spans; this also sees the
compile no span holds: ``hvd.spmd`` keys its programs by shape and dtype,
and ``jax.jit`` compiles again on its own for another sharding,
committedness or weak type, inside a dispatch.

Inside a build the compiles are rows with stamps. Outside, the program
keeps sums with the stamp of their last event: where that lies in the
window, every program compiled inside a dispatch counts (any of them is
a fault, set-up's too) and the others as one — at least one was. None
where the record has no such sums (a parent of PR 36)."""

from benchmark import scopes


def read(run):
    rec = scopes.record()
    window = scopes.window_dispatches(run, rec)
    if not window or "compiles" not in rec:
        return None
    first = window[0][1]
    built = sum(1 for n, s, _, _ in rec["spans"] if s > first and n in (
        "hvd/spmd/build/compile", "hvd/spmd/build/load"))

    def late(where):
        sums = rec["compiles"][where]
        return sums["programs"] if sums["last_ns"] > first else 0

    return built + late("in_dispatch") + min(late("after_dispatch"), 1)
