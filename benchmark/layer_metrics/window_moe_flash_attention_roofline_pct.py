"""kernels: the flash-attention kernels' share of their roofline in an LM
whose attention kind is chosen by layer — the least time the chip could
take for one forward and one backward call an attention layer, each
layer's visible pairs counted by its own window (``flops_window_moe.py``:
32 query heads of 128 over 4 KV heads, window 2048 or full causal) over
the device time of the Pallas calls named ``hvd_flash_fwd`` /
``hvd_flash_bwd`` (by name: the grouped products of the expert layers are
custom calls too); the busiest device. None where no such kernel ran."""

from benchmark import flops_window_moe, scopes


def read(run):
    parts = [scopes.kernel_ms_per_step(run, name)
             for name in ("hvd_flash_fwd", "hvd_flash_bwd")]
    if None in parts:
        return None
    rows, t = run.traffic["batch_per_chip"], run.traffic["seq_len"]
    least, _ = run.flops.roofline_seconds(
        flops_window_moe.flash_step_flops(run.config, rows, t),
        flops_window_moe.flash_step_bytes(run.config, rows, t), run.peaks)
    return 100.0 * least / (sum(parts) / 1e3)
