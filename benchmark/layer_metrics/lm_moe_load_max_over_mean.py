"""model_step: the fullest held expert's pairs over the mean held
expert's, a step — ``moe.max_expert_pairs`` over ``moe.local_pairs`` /
(``model.moe_layers`` x ``model.experts_held``), the program's counters. 1
is a perfectly even load; None where the record lacks a counter."""

from benchmark import scopes


def read(run):
    most, pairs, layers, held = (scopes.step_counter(name) for name in (
        "moe.max_expert_pairs", "moe.local_pairs", "model.moe_layers",
        "model.experts_held"))
    if None in (most, pairs, layers, held) or not pairs:
        return None
    return most / (pairs / (layers * held))
