"""model_step: (token, choice) pairs a step, a rank, routed to the experts
held here, all expert layers together — the step's own count, which the
runner writes into the program's record as ``moe.local_pairs``. None where
the record has no such counter."""

from benchmark import scopes


def read(run):
    return scopes.step_counter("moe.local_pairs")
