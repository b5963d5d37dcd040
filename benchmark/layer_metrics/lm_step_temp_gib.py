"""spmd_wrapper: XLA's temporaries of the compiled step on one device, in
GiB — ``temp_size_in_bytes`` of the executable's own memory analysis,
which the program reads where it reads the scope map
(``programs[step]["memory"]``): what ``lm_peak_hbm_gib`` (buffers only)
does not hold, and what caps a cell's depth. None where the program keeps
no memory analysis (a parent of PR 36)."""

from benchmark import scopes


def read(run):
    memory = (scopes.step_program(scopes.record()) or {}).get("memory")
    return memory["temp_bytes"] / 2 ** 30 if memory else None
