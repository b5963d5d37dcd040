"""kernels: the scores the flash kernels compute a step over the scores
the mask leaves visible, in per cent — the step program's
``flash.scores_computed`` over its ``flash.scores_visible``, counted where
the model is traced (``models/transformer.py``) by the classification the
kernels themselves run (``ops/flash_attention.score_counts``): 100 for a
kernel that computes nothing masked; 112.5 by construction at T = 8192 full
causal while a block pair on the diagonal is computed whole. None where
the program counts neither (a parent of PR 35)."""

from benchmark import scopes


def read(run):
    visible = scopes.step_counter("flash.scores_visible")
    computed = scopes.step_counter("flash.scores_computed")
    if not visible or computed is None:
        return None
    return 100.0 * computed / visible
