"""The private corners of JAX this package reads, in one place (validated
on jax 0.9.0): a JAX upgrade that moves one of them breaks here and
nowhere else."""

from __future__ import annotations

import contextlib

from jax._src import config as _config
from jax._src import profiler as _profiler


@contextlib.contextmanager
def named_locations():
    """Context manager under which a lowering writes the whole name stack
    (``jax.named_scope``s, ``jvp(...)``, flax's module paths) into every
    instruction's ``op_name``, and ONE frame of the traceback into its
    location. With ``jax_include_full_tracebacks_in_locations`` off — the
    compact form, which ``benchmark/run.py`` sets to keep Pallas kernels'
    cache keys free of call sites — this JAX nests the stack under the
    primitive's name and XLA keeps only that (``op_name="sin"``). With it
    on and ``jax_traceback_in_locations_limit`` at 1 the names are kept
    and the location is the innermost user frame, as in the compact form:
    the program's persistent-cache key still holds no call stack (an edit
    above the step recompiles nothing). Neither flag is part of ``jit``'s
    cache keys: a program lowered under them is found again, names and
    all, by calls and lowerings outside."""
    with _config.include_full_tracebacks_in_locations(True), \
            _config.traceback_in_locations_limit(1):
        yield


def profiler_session_active() -> bool:
    """True while a ``jax.profiler.start_trace`` capture is running in
    this process (one attribute read: cheap enough for every dispatch)."""
    return _profiler._profile_state.profile_session is not None


def jit_cache_size(jitted) -> int:
    """Entries in a ``jax.jit`` function's call cache; 0 before its first
    call and after ``jax.clear_caches()`` — when asking it for its
    executable again would trace and compile anew."""
    return jitted._cache_size()
