"""model_step: block applications a step, a rank — layers x passes, the
step program's ``model.block_applications`` counted where the model is
traced (``models/transformer.py``)."""

from benchmark import scopes


def read(run):
    return scopes.step_counter("model.block_applications")
