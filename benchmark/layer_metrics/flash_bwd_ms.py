"""kernels: device ms a step of the backward flash-attention kernel — the
Pallas calls named ``hvd_flash_bwd`` (``scopes.kernel_ms_per_step``); the
busiest device."""

from benchmark import scopes


def read(run):
    return scopes.kernel_ms_per_step(run, "hvd_flash_bwd")
