"""model_step: the whole step's share of the chip's bf16 peak — model
operations a step from shapes (forward and backward; no recomputation, no
update) over the window's seconds a step."""


def read(run):
    rows = run.traffic["batch_per_chip"]
    per_chip = run.flops.lm_step_flops(run.config, rows,
                                       run.traffic["seq_len"])
    step_s = run.window["seconds"] / run.window["steps"]
    return 100.0 * per_chip / step_s / run.peaks["bf16_flops_per_s"]
