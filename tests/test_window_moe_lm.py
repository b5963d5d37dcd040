"""The windowed-and-full-attention expert-layer LM (an attention kind by
layer: ``'sliding'`` layers with a window and rotary beside ``'full'``
layers with neither; q/k-normed grouped-query heads wider than
``hidden / heads``; a sigmoid output gate; the embedding's multiplier;
sandwich norms; a leading dense layer; expert layers holding a share of
the experts beside a shared expert) against its plain reference
``benchmark/reference/train_window_moe_lm.py``, and what the kinds
promise: each mechanism can be seen, the shares of a layer add up to the
uncut layer, the kernels' score counters follow each layer's own window,
the combinations that cannot run say why, and a sliding layer takes its
window round the ring."""

import copy
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.core import timeline
from horovod_tpu.models import transformer
from horovod_tpu.ops import flash_attention
from horovod_tpu.parallel import sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # benchmark/ is a namespace package of ROOT


def _load(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "window_moe_" + parts[-1][:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _load("reference", "train_window_moe_lm.py")
RUNNER = _load("runners", "train_window_moe_lm.py")
SEEDED = _load("seeded.py")

# A small Trinity-Mini: sliding, full, sliding (the first with the dense
# SwiGLU), 4 query heads of 16 over 2 KV heads at hidden 32 (heads twice
# as wide as hidden / heads), window 16 at T = 64, two expert layers of 4
# held of 32 experts at top-8 beside a shared expert, route scale 2.826,
# share 1 of 8; float32.
SMALL = {"hidden_size": 32, "head_dim": 16, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 48,
         "moe_intermediate_size": 24, "num_experts": 4,
         "num_experts_per_tok": 8, "num_shared_experts": 1,
         "route_scale": 2.826, "route_norm": True, "score_func": "sigmoid",
         "n_group": 1, "hidden_act": "silu", "num_dense_layers": 1,
         "num_hidden_layers": 3,
         "layer_types": ["sliding_attention", "full_attention",
                         "sliding_attention"],
         "sliding_window": 16, "vocab_size": 96, "rope_theta": 10000,
         "rope_scaling": None, "rms_norm_eps": 1e-5, "mup_enabled": True,
         "tie_word_embeddings": False, "max_position_embeddings": 128,
         "initializer_range": 0.1, "embedding_std": 0.3,
         "attention_output_gate": True,
         "published": {"num_experts": 32},
         "expert_share": {"chips": 8, "index": 1}}
OPT = {"name": "adamw", "learning_rate": 3e-3, "b1": 0.9, "b2": 0.95,
       "eps": 1e-8, "weight_decay": 0.1, "moment_dtype": "bfloat16"}
SEED, T = 2147483659, 64
CFG = RUNNER.model_config(SMALL)._replace(dtype=jnp.float32)
MECHANISMS = ("sliding_full", "rotary_everywhere", "no_gate",
              "no_embed_scale", "narrow_heads")


def _tokens(batch, rows=1):
    return SEEDED.lm_tokens(SEED, 0, batch, rows, T, SMALL["vocab_size"])


def _reference(variant="reference"):
    with jax.default_matmul_precision("highest"):
        return REFERENCE.Reference(SMALL, OPT, SEED, SEEDED, variant)


@pytest.fixture(scope="module")
def reference():
    """The seed's reference, built once: no test steps it."""
    return _reference()


def _variant(ref, variant):
    """``ref``'s weights under another variant's programs."""
    other = copy.copy(ref)
    other.variant = variant
    other._p = REFERENCE._programs(json.dumps(SMALL, sort_keys=True),
                                   json.dumps(OPT, sort_keys=True),
                                   variant == "fp8", variant)
    return other


def _gradients(ref, toks):
    """(loss, {leaf: gradient}) of one row by the reference."""
    acc = {}

    def add(name, g, whole):
        acc[name] = g if name not in acc else acc[name] + g

    with jax.default_matmul_precision("highest"):
        return ref._gradients(jnp.asarray(toks, jnp.int32), add), acc


def _exact(q, k, v, causal=True, sm_scale=None, window=None, **_):
    t, reps = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, reps, axis=2), jnp.repeat(v, reps, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (
        sm_scale or q.shape[-1] ** -0.5)
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[None, :] > pos[:, None] - window
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.fixture
def exact_attention(monkeypatch):
    """``hvd.local_attention`` rounds q and k to bfloat16 whatever the
    model's dtype; the tests of what stands around the attention give the
    model a float32 one (as ``tests/test_conv_moe_lm.py`` does). The
    program's own kernels at 128-wide heads are held to the reference by
    the cell's rehearsal and on the chip."""
    monkeypatch.setattr(hvd, "local_attention", _exact)


@pytest.fixture
def one_device():
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    yield
    hvd.shutdown()


@pytest.fixture(scope="module")
def program_gradients(reference):
    """The program's loss, pairs and every leaf's gradient on the
    reference's seeded weights, through ``hvd.spmd`` with the fused head,
    in float32 with exact attention."""
    ref = reference
    toks = _tokens(0)
    loss_fn = transformer.make_loss_fn(CFG, fused_head=True,
                                       with_expert_pairs=True)
    real = hvd.local_attention
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    hvd.local_attention = _exact
    try:
        step = hvd.spmd(lambda p, toks: jax.value_and_grad(
            loss_fn, has_aux=True)(p, toks))
        with jax.default_matmul_precision("highest"):
            (loss, pairs), grads = step(
                hvd.replicate(RUNNER._to_tree(ref.p)),
                hvd.rank_stack([toks]))
        counters = [p["counters"] for p in
                    timeline.record()["programs"].values()][0]
    finally:
        hvd.local_attention = real
        hvd.shutdown()
    grads = RUNNER._by_name(jax.tree.map(lambda a: np.asarray(a[0]), grads),
                            list(ref.p))
    return float(np.asarray(loss)[0]), np.asarray(pairs)[0], grads, counters


# ---------------------------------------------------------------------------
# (a) the program against the plain reference
# ---------------------------------------------------------------------------


def test_the_runner_names_every_leaf_of_the_programs_tree():
    want = jax.eval_shape(lambda: transformer.init_params(CFG))
    got = RUNNER._to_tree({n: jax.ShapeDtypeStruct(shape, jnp.float32)
                           for n, shape, _ in REFERENCE.leaf_specs(SMALL)})
    assert jax.tree.map(lambda a: a.shape, want) \
        == jax.tree.map(lambda a: a.shape, got)
    attn = want["block_1"]["attn"]  # 4 heads of 16 at hidden 32
    assert attn["query"]["kernel"].shape == (32, 4, 16)
    assert attn["gate"]["kernel"].shape == (32, 4, 16)
    assert attn["out"]["kernel"].shape == (4, 16, 32)
    assert attn["q_norm"]["scale"].shape == (16,)
    assert want["block_1"]["moe"]["shared_gate"]["kernel"].shape == (32, 24)
    assert len([k for k in want["block_1"] if k.startswith("RMSNorm")]) == 4


def test_the_cells_parameter_count_agrees_three_ways():
    """The program's tree, the reference's specs and the FLOP count hold
    the same number of parameters at the cell's own sizes."""
    import math

    from benchmark import flops_window_moe

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity_mini.json")) as f:
        cell = json.load(f)
    tree = jax.eval_shape(lambda: transformer.init_params(
        RUNNER.model_config(cell)))
    in_tree = sum(math.prod(a.shape) for a in jax.tree.leaves(tree))
    in_specs = sum(math.prod(shape)
                   for _, shape, _ in REFERENCE.leaf_specs(cell))
    assert in_tree == in_specs == flops_window_moe.params(cell) \
        == cell["parameters_per_chip"] == 705_473_792


def test_loss_and_every_gradient_match_the_reference(program_gradients,
                                                     reference):
    got, pairs, grads, _ = program_gradients
    want, acc = _gradients(reference, _tokens(0)[0])
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert set(acc) == set(grads)  # a gradient reaches every leaf
    for name, g in acc.items():
        scale = float(jnp.max(jnp.abs(g)))
        assert scale > 0, name
        np.testing.assert_allclose(grads[name], g, rtol=1e-4,
                                   atol=2e-5 * scale, err_msg=name)
    assert pairs.shape == (2, SMALL["num_experts"])  # two expert layers
    assert 0 < pairs.sum(axis=1).max() <= T * SMALL["num_experts_per_tok"]


def _forward_loss(ref, toks):
    """The reference's loss of one row, its forward alone."""
    pr, cfg = ref._p, ref.cfg
    with jax.default_matmul_precision("highest"):
        x = pr.embed(ref.p["embed"], jnp.asarray(toks, jnp.int32))
        for i in range(cfg["num_hidden_layers"]):
            x = pr.fwd[cfg["layer_types"][i]](ref._layer(i), x)
        h = pr.norm(ref.p["ln_f"], x)
        return float(pr.loss(ref.p["head"], h, jnp.asarray(toks), False)[0])


@pytest.mark.parametrize("variant", MECHANISMS)
def test_each_mechanism_can_be_seen(variant, program_gradients, reference):
    """Take one mechanism out of the reference alone and it no longer
    agrees with the program: the loss moves by more than a hundred times
    the agreement's 2e-6."""
    got = program_gradients[0]
    toks = _tokens(0)[0]
    assert abs(_forward_loss(reference, toks) - got) <= 2e-6 * got
    gap = abs(_forward_loss(_variant(reference, variant), toks) - got) / got
    assert gap > 100 * 2e-6, gap


def test_a_reference_step_trains_every_leaf_the_routers_too():
    """Every leaf moves in a reference step, the routers included, as every
    leaf of the program's tree takes its optimizer update."""
    ref = _reference()
    start = {n: np.asarray(a) for n, a in ref.p.items()}
    with jax.default_matmul_precision("highest"):
        ref.step(list(_tokens(0)))
    for name, p0 in start.items():
        assert np.any(np.asarray(ref.p[name]) != p0), name


def test_the_count_and_the_model_read_one_mapping_of_the_kinds():
    """Each published kind's window and rotary in the FLOP count are what
    the program's attention kind that runs it does."""
    flops = _load("flops_window_moe.py")
    for published, kind in flops.KINDS.items():
        program = transformer.ATTENTION_KINDS[kind["program"]]
        assert (program.windowed, program.rotary) == (
            kind["windowed"], kind["rotary"]), published
    assert RUNNER.model_config(SMALL).layer_types == (
        "sliding", "full", "sliding")


def test_the_plan_counts_the_layers_by_kind(program_gradients):
    counters = program_gradients[3]
    assert counters["model.block_applications"] == 3
    assert counters["model.attention_layers"] == 3
    assert counters["model.windowed_attention_layers"] == 2
    assert counters["model.full_attention_layers"] == 1
    assert counters["model.rotary_attention_layers"] == 2
    assert counters["model.gated_attention_layers"] == 3
    assert counters["model.moe_layers"] == 2
    assert counters["model.experts_held"] == 4
    assert counters["model.experts_total"] == 32
    assert counters["model.moe_pair_capacity"] == T * 8


# ---------------------------------------------------------------------------
# (b) the share test with a shared expert, at top-8 and scale 2.826
# ---------------------------------------------------------------------------


def _layer_inputs(total=32, tokens=64, e=32, f=24, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    lp = {"wr": normal(keys[0], e, total),
          "eg": 0.2 * normal(keys[1], total, e, f),
          "eu": 0.2 * normal(keys[2], total, e, f),
          "ed": 0.2 * normal(keys[3], total, f, e),
          "sg": 0.2 * normal(keys[4], e, f),
          "su": 0.2 * normal(keys[5], e, f),
          "sd": 0.2 * normal(keys[6], f, e)}
    return lp, normal(keys[7], tokens, e)


def _program_layer(lp, x, first, held):
    """The program's expert layer holding experts ``first .. first +
    held`` of ``lp``'s, beside the shared expert."""
    cfg = CFG._replace(moe=CFG.moe._replace(
        total=lp["wr"].shape[1], held=held, first=first))
    part = slice(first, first + held)
    params = {"router": lp["wr"], "wg": lp["eg"][part],
              "wu": lp["eu"][part], "wd": lp["ed"][part],
              "shared_gate": {"kernel": lp["sg"]},
              "shared_up": {"kernel": lp["su"]},
              "shared_down": {"kernel": lp["sd"]}}
    with jax.default_matmul_precision("highest"):
        out, sown = transformer.MoE(cfg).apply(
            {"params": params}, x[None], mutable=[transformer.EXPERT_PAIRS])
    return out[0], sown[transformer.EXPERT_PAIRS]["pairs"][0]


@pytest.mark.parametrize("shares", [8, 1])
def test_the_shares_of_a_layer_add_up_with_the_shared_expert_once(shares):
    """Eight shares of 4 experts of 32 at top-8 (the configuration's ratio,
    1 of 8): their outputs, less the shared expert that each of them
    computes alike (counted once), are the uncut reference layer, and
    every pair of the batch is some share's."""
    lp, x = _layer_inputs()
    with jax.default_matmul_precision("highest"):
        want = REFERENCE.moe(lp, x, SMALL, False, first=0)
        shared = REFERENCE._gated(x, lp["sg"], lp["su"], lp["sd"], False)
    held = 32 // shares
    total, pairs = -(shares - 1) * shared, 0
    for share in range(shares):
        out, took = _program_layer(lp, x, first=held * share, held=held)
        total, pairs = total + out, pairs + int(took.sum())
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)
    assert pairs == x.shape[0] * SMALL["num_experts_per_tok"]


# ---------------------------------------------------------------------------
# (c) the kernels' score counters follow each layer's window and width
# ---------------------------------------------------------------------------


def test_the_score_counters_sum_layer_by_layer(monkeypatch):
    """``flash.scores_visible`` / ``_computed`` of a mixed stack are the
    sum of each attention layer's own: a sliding layer at its window, a
    full layer at none, every one at the decoupled head width (here 16
    for hidden / heads = 8)."""
    monkeypatch.setattr(sequence, "local_attention_impl", lambda t: "flash")
    t = 16
    cfg = transformer.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, num_kv_heads=1,
        embed_dim=16, head_dim=16, mlp_dim=32, max_seq_len=t, window=5,
        dtype=jnp.float32, layer_types=("sliding", "full"),
        attn_gate=True)
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    try:
        loss_fn = transformer.make_loss_fn(cfg)
        step = hvd.spmd(jax.value_and_grad(loss_fn))
        step(hvd.replicate(transformer.init_params(cfg)),
             hvd.rank_stack([np.arange(t, dtype=np.int32)[None] % 64]))
        [counters] = [p["counters"] for p in
                      timeline.record()["programs"].values()]
    finally:
        hvd.shutdown()
    counts = lambda w: flash_attention.score_counts(t, t, 16, window=w)
    calls = cfg.num_heads  # one row
    assert counters["flash.scores_visible"] == calls * (
        counts(5)[0] + counts(None)[0])
    assert counters["flash.scores_computed"] == calls * (
        counts(5)[1] + counts(None)[1])
    assert counts(5)[0] < counts(None)[0]


# ---------------------------------------------------------------------------
# (d) what raises, and the ring
# ---------------------------------------------------------------------------


def _forward(cfg):
    toks = jnp.asarray(_tokens(0))[:, :8]
    params = jax.eval_shape(lambda: transformer.init_params(
        cfg._replace(decode=False, attention="local")))
    return jax.eval_shape(lambda p: transformer.Transformer(cfg).apply(
        {"params": p}, toks), params)


PLAIN = CFG._replace(layer_types=None, head_dim=None, attn_gate=False,
                     moe=None, embed_dim=64)


@pytest.mark.parametrize("strategy", ["decode", "ulysses"])
@pytest.mark.parametrize("change, says", [
    (dict(layer_types=("sliding", "attention", "attention")),
     "a 'sliding' layer"),
    (dict(layer_types=("full", "attention", "attention")), "a 'full' layer"),
    (dict(attn_gate=True), "the output gate"),
    (dict(head_dim=32), "head_dim=32"),
], ids=["sliding", "full", "gate", "head_dim"])
def test_what_serving_and_ulysses_do_not_run_raises(strategy, change, says):
    cfg = PLAIN._replace(**change)
    cfg = cfg._replace(decode=True) if strategy == "decode" \
        else cfg._replace(attention="ulysses")
    with pytest.raises(ValueError, match=f"{says}.*ROADMAP M4 \\(b\\)"):
        _forward(cfg)


def test_a_sliding_layer_needs_a_window_and_latent_attention_no_kinds():
    with pytest.raises(ValueError, match="needs the configuration's window"):
        _forward(CFG._replace(window=None))
    mla = PLAIN._replace(mla=transformer.MLAConfig(
        q_rank=16, kv_rank=12, nope_dim=6, rope_dim=4, v_dim=8),
        qk_norm=False, layer_types=("full", "full", "full"))
    with pytest.raises(ValueError, match="an attention kind of its own"):
        _forward(mla)


def test_a_sliding_layer_takes_its_window_round_the_ring(exact_attention):
    """Under ``'ring'`` a sliding layer passes its own window (and a full
    layer none): the sharded forward is the local one, which differs from
    the same stack with a window as long as the sequence."""
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:2])
    cfg = PLAIN._replace(num_layers=2, layer_types=("sliding", "full"),
                         window=6, max_seq_len=32)
    params = transformer.init_params(cfg)
    toks = jnp.asarray(_tokens(1))[:, :32]
    try:
        want = transformer.Transformer(cfg).apply({"params": params}, toks)
        full = transformer.Transformer(cfg._replace(window=32)).apply(
            {"params": params}, toks)
        ring = cfg._replace(attention="ring")

        @hvd.spmd
        def f(params, shards):
            return transformer.Transformer(ring).apply(
                {"params": params}, shards,
                shard_offset=hvd.rank() * shards.shape[1])

        got = jnp.concatenate(list(f(hvd.replicate(params),
                                     jnp.stack(jnp.split(toks, 2, axis=1)))),
                              axis=1)
    finally:
        hvd.shutdown()
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)
    assert float(jnp.max(jnp.abs(want - full))) > 0.1
