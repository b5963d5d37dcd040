"""model_step: the whole step's share of the chip's bf16 peak — 3 x the
forward operations of the convolutions and the dense head, from shapes,
over the window's seconds a step."""


def read(run):
    per_chip = run.flops.resnet_step_flops(run.config,
                                           run.traffic["batch_per_chip"])
    step_s = run.window["seconds"] / run.window["steps"]
    return 100.0 * per_chip / step_s / run.peaks["bf16_flops_per_s"]
