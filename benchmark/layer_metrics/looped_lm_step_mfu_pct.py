"""model_step: a looped LM's whole step as a share of the chip's bf16 peak
— model operations a step from shapes (``flops_looped.py``: every pass of
the stack, three MLP matrices, a head a pass; forward and backward; no
recomputation, no update) over the window's seconds a step."""

from benchmark import flops_looped


def read(run):
    per_chip = flops_looped.step_flops(
        run.config, run.traffic["batch_per_chip"], run.traffic["seq_len"])
    step_s = run.window["seconds"] / run.window["steps"]
    return 100.0 * per_chip / step_s / run.peaks["bf16_flops_per_s"]
