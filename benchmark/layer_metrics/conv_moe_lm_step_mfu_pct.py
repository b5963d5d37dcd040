"""model_step: a convolution-and-attention expert-layer LM's whole step as
a share of the chip's bf16 peak — model operations a step from shapes
(``flops_conv_moe.py``: the mixers by ``layer_types``, the routed experts
at the pairs the step MEASURED, ``moe.local_pairs`` of the program's
record, a uniform router's where the record has none) over the window's
seconds a step."""

from benchmark import flops_conv_moe, scopes


def read(run):
    per_chip = flops_conv_moe.step_flops(
        run.config, run.traffic["batch_per_chip"], run.traffic["seq_len"],
        scopes.step_counter("moe.local_pairs"))
    step_s = run.window["seconds"] / run.window["steps"]
    return 100.0 * per_chip / step_s / run.peaks["bf16_flops_per_s"]
