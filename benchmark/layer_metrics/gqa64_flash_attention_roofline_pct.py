"""kernels: the flash-attention kernels' share of their roofline in a
convolution-and-attention LM's step — the least time the chip could take
for one forward and one backward call an ATTENTION layer
(``flops_conv_moe.py``: 32 query heads of 64 over 8 KV heads, the causal
pairs) over the device time of the Pallas calls named ``hvd_flash_fwd`` /
``hvd_flash_bwd`` (by name: the grouped products of the expert layers are
custom calls too); the busiest device. None where no such kernel ran."""

from benchmark import flops_conv_moe, scopes


def read(run):
    parts = [scopes.kernel_ms_per_step(run, name)
             for name in ("hvd_flash_fwd", "hvd_flash_bwd")]
    if None in parts:
        return None
    rows, t = run.traffic["batch_per_chip"], run.traffic["seq_len"]
    least, _ = run.flops.roofline_seconds(
        flops_conv_moe.flash_step_flops(run.config, rows, t),
        flops_conv_moe.flash_step_bytes(run.config, rows, t), run.peaks)
    return 100.0 * least / (sum(parts) / 1e3)
