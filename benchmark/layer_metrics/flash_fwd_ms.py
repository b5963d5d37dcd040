"""kernels: device ms a step of the forward flash-attention kernel — the
Pallas calls named ``hvd_flash_fwd`` (``scopes.kernel_ms_per_step``); the
busiest device."""

from benchmark import scopes


def read(run):
    return scopes.kernel_ms_per_step(run, "hvd_flash_fwd")
