"""model_step: the whole step of an expert-layer LM whose attention kind is
chosen by layer, as a share of the chip's bf16 peak — model operations a
step from shapes (``flops_window_moe.py``: each layer by its kind's
window and its gate, the routed experts at the pairs the step MEASURED,
``moe.local_pairs`` of the program's record, a uniform router's where the
record has none) over the window's seconds a step."""

from benchmark import flops_window_moe, scopes


def read(run):
    per_chip = flops_window_moe.step_flops(
        run.config, run.traffic["batch_per_chip"], run.traffic["seq_len"],
        scopes.step_counter("moe.local_pairs"))
    step_s = run.window["seconds"] / run.window["steps"]
    return 100.0 * per_chip / step_s / run.peaks["bf16_flops_per_s"]
