"""spmd_wrapper: peak device memory of the fullest chip, set-up and window
(``memory_stats()["peak_bytes_in_use"]``, read before the reference runs)."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 2 ** 30
