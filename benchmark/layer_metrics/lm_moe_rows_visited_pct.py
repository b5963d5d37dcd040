"""model_step: the upper bound of the share of the expert layers' buffer
rows that the passes between the grouped products visit a step — the
routed pairs plus one row block a layer (a device-sized pass runs whole
blocks: its last one runs past the routed rows by less than a block), over
the buffers' rows: ``100 x (moe.local_pairs + model.moe_layers x
model.moe_row_block) / (model.moe_layers x model.moe_pair_capacity)``.
A layer whose gathers and elementwise passes walk the whole worst-case
buffer visits 100 %; its program counts no ``model.moe_row_block`` (a
parent of PR 32) and reads nothing."""

from benchmark import scopes


def read(run):
    pairs = scopes.step_counter("moe.local_pairs")
    layers = scopes.step_counter("model.moe_layers")
    block = scopes.step_counter("model.moe_row_block")
    capacity = scopes.step_counter("model.moe_pair_capacity")
    if pairs is None or block is None or not layers or not capacity:
        return None
    return 100.0 * (pairs + layers * block) / (layers * capacity)
