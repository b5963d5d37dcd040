"""model_step: block applications a step, a rank, whose backward reads the
attention kernel's output and log-sum-exp back and does not run the forward
kernel again — the step program's ``model.kept_attention_outputs``, counted
where the model is traced (``models/transformer.py``): layers x passes of a
looped stack whose attention is the Pallas kernel. None where the program
has no such counter."""

from benchmark import scopes


def read(run):
    return scopes.step_counter("model.kept_attention_outputs")
