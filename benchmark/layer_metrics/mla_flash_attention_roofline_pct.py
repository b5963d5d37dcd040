"""kernels: the flash-attention kernels' share of their roofline in a
latent-attention LM's step — the least time the chip could take for one
forward and one backward call a block application (``flops_moe.py``: 256-
wide queries, keys and values over the causal pairs) over the device time
of the Pallas calls named ``hvd_flash_fwd`` / ``hvd_flash_bwd`` (by name:
the grouped products of the expert layers are custom calls too); the
busiest device."""

from benchmark import flops_moe, scopes


def read(run):
    parts = [scopes.kernel_ms_per_step(run, name)
             for name in ("hvd_flash_fwd", "hvd_flash_bwd")]
    if None in parts:
        return None
    rows, t = run.traffic["batch_per_chip"], run.traffic["seq_len"]
    least, _ = run.flops.roofline_seconds(
        flops_moe.flash_step_flops(run.config, rows, t),
        flops_moe.flash_step_bytes(run.config, rows, t), run.peaks)
    return 100.0 * least / (sum(parts) / 1e3)
