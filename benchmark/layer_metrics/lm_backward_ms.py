"""model_step: device ms a step of the backward — ``transpose(jvp(...))``
scopes, custom-VJP rules included; the busiest device. A fusion is the
backward's where its matmul is (a weight gradient fused with the AdamW
pass it feeds counts here, whole: ``scopes.phase_of``)."""

from benchmark import scopes


def read(run):
    return scopes.phase_ms_per_step(run, "backward")
