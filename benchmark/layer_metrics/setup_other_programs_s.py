"""spmd_wrapper: tracing, lowering and compile-or-load seconds of the JAX
programs that no ``hvd/spmd/build`` span holds — the seed's weights,
``opt.init``, placement's lifts, the readings — as the program sums them
under its record's ``compiles``, by where they fell: before the session's
first dispatch, after it, and after it inside a dispatch. The sums carry
no stamps but their last, so this is all of them up to ``hvd.shutdown()``:
set-up's where ``lm_window_compiles`` reads 0. None where the record has
no such sums (a parent of PR 36)."""

from benchmark import scopes


def read(run):
    sums = (scopes.record() or {}).get("compiles")
    if not sums:
        return None
    return sum(s[k] for s in sums.values()
               for k in ("trace_s", "lower_s", "backend_s"))
