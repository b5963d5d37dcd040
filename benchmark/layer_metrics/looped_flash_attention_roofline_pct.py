"""kernels: the flash-attention kernels' share of their roofline in a
looped LM's step — the least time the chip could take for layers x passes
forward and backward calls (``flops_looped.py``; the forward calls the
backward runs again are recomputation: in the time, not in the count)
over the device time of the step's Pallas custom calls; the busiest
device."""

from benchmark import flops_looped


def read(run):
    per_device = run.trace.op_seconds(run.trace.is_pallas_call)
    kernel_s = max(per_device.values(), default=0.0) / run.window["steps"]
    if kernel_s <= 0:
        return None
    rows, t = run.traffic["batch_per_chip"], run.traffic["seq_len"]
    least, _ = run.flops.roofline_seconds(
        flops_looped.flash_step_flops(run.config, rows, t),
        flops_looped.flash_step_bytes(run.config, rows, t), run.peaks)
    return 100.0 * least / kernel_s
