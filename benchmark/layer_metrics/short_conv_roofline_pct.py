"""kernels: the gated short convolution's share of its roofline — the
least time the chip could take for every conv layer's gate-and-tap pass,
forward and backward, with the taps fused into one pass over the three
streams each way (``flops_conv_moe.py``: HBM traffic bounds it, 11 streams
of (T, E) bfloat16 a layer) over the device time under the ``conv/gate``
scope (everything between a conv layer's two projections: both gatings
and the taps, forward, computed again in the backward, and transposed);
the busiest device. A pass that writes shifted copies, or its streams in
float32, reads lower. None where the program names no such scope."""

from benchmark import flops_conv_moe, named_events, scopes


def read(run):
    # ``gate`` under ``conv``: a gated MLP's ``gate`` matrix is another
    # scope, and carries no ``conv``.
    per_device = scopes.seconds_by(
        run, scopes.record(), lambda ev: named_events.carries(ev, "conv")
        and named_events.carries(ev, "gate"))
    gate_s = max(per_device.values(), default=0.0) / run.window["steps"]
    if gate_s <= 0:
        return None
    rows, t = run.traffic["batch_per_chip"], run.traffic["seq_len"]
    least, _ = run.flops.roofline_seconds(
        flops_conv_moe.conv_gate_step_flops(run.config, rows, t),
        flops_conv_moe.conv_gate_step_bytes(run.config, rows, t), run.peaks)
    return 100.0 * least / gate_s
