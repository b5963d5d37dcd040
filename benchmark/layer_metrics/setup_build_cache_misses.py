"""spmd_wrapper: programs ``hvd.spmd`` built that JAX's persistent cache
did not hold (``build.cache_misses``, counted by the program where the
cache writes its new entry): 0 on a warm run, and the one number that
tells a cold run from a warm one. None where no program counts it (a
parent of PR 36)."""

from benchmark import scopes


def read(run):
    programs = ((scopes.record() or {}).get("programs") or {}).values()
    misses = [p["counters"]["build.cache_misses"] for p in programs
              if "build.cache_misses" in p.get("counters", {})]
    return sum(misses) if misses else None
