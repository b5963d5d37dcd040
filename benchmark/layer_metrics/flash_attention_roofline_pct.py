"""kernels: the flash-attention kernels' share of their roofline — the
least time the chip could take for one step's attention (forward and
backward at these shapes: the larger of operations over the bf16 peak and
bytes over the HBM peak; compute bounds it at T=8192, D=128) over the
device time of the step's Pallas custom calls (``custom-call`` events whose
target is ``tpu_custom_call``). The kernels carry no name yet, and the LM step has no other custom
call; the busiest device."""


def read(run):
    per_device = run.trace.op_seconds(run.trace.is_pallas_call)
    kernel_s = max(per_device.values(), default=0.0) / run.window["steps"]
    if kernel_s <= 0:
        return None
    rows, t = run.traffic["batch_per_chip"], run.traffic["seq_len"]
    least, _ = run.flops.roofline_seconds(
        run.flops.flash_step_flops(run.config, rows, t),
        run.flops.flash_step_bytes(run.config, rows, t), run.peaks)
    return 100.0 * least / kernel_s
