"""The latent-attention / expert-layer LM (MLA, a sigmoid-routed expert
layer holding a share of the experts beside a shared expert, leading dense
layers, the multi-token-prediction module) against its plain reference
``benchmark/reference/train_moe_lm.py``, and what ties the chip's share
to the model: the shares of a layer add up to the uncut layer, no token is
dropped whatever the imbalance, and a configuration without the new
fields still computes the old model."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.core import timeline
from horovod_tpu.models import transformer
from horovod_tpu.ops import moe as moe_ops
from horovod_tpu.ops import optim
from horovod_tpu.parallel import sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # benchmark/ is a namespace package of ROOT


def _load(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "moe_" + parts[-1][:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _load("reference", "train_moe_lm.py")
RUNNER = _load("runners", "train_moe_lm.py")
SEEDED = _load("seeded.py")

# A small GLM-4.7-Flash: every mechanism of the configuration (a dense
# layer, two expert layers, the MTP module), unequal nope / rope / value
# widths, share 1 of 4 of the experts; float32 compute.
SMALL = {"hidden_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 12,
         "qk_nope_head_dim": 6, "qk_rope_head_dim": 4, "v_head_dim": 12,
         "intermediate_size": 48, "moe_intermediate_size": 24,
         "n_routed_experts": 4, "n_shared_experts": 1,
         "routed_scaling_factor": 1.8, "num_experts_per_tok": 3,
         "first_k_dense_replace": 1, "num_hidden_layers": 3,
         "num_nextn_predict_layers": 1, "vocab_size": 96,
         "rope_theta": 1000000, "rms_norm_eps": 1e-5, "hidden_act": "silu",
         "attention_bias": False, "norm_topk_prob": True,
         "rope_scaling": None, "n_group": 1, "topk_group": 1,
         "max_position_embeddings": 64, "initializer_range": 0.1,
         "embedding_std": 1.0,
         "mtp_loss_weight": 0.3,
         "published": {"n_routed_experts": 16},
         "expert_share": {"chips": 4, "index": 1}}
OPT = {"name": "adamw", "learning_rate": 3e-3, "b1": 0.9, "b2": 0.95,
       "eps": 1e-8, "weight_decay": 0.1, "moment_dtype": "bfloat16"}
SEED, T = 2147483659, 32
CFG = RUNNER.model_config(SMALL)._replace(dtype=jnp.float32)


def _tokens(batch, rows=1):
    return SEEDED.lm_tokens(SEED, 0, batch, rows, T, SMALL["vocab_size"])


def _reference(variant="reference", cfg=SMALL):
    with jax.default_matmul_precision("highest"):
        return REFERENCE.Reference(cfg, OPT, SEED, SEEDED, variant)


def _gradients(ref, toks):
    """(loss, {leaf: gradient}) of one row by the reference."""
    acc = {}

    def add(name, g, whole):
        acc[name] = g if name not in acc else acc[name] + g

    with jax.default_matmul_precision("highest"):
        return ref._gradients(jnp.asarray(toks, jnp.int32), add), acc


def _exact(q, k, v, causal=True, sm_scale=None, **_):
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (
        sm_scale or q.shape[-1] ** -0.5)
    pos = jnp.arange(t)
    p = jax.nn.softmax(
        jnp.where(pos[None, :] <= pos[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.fixture
def exact_attention(monkeypatch):
    """``hvd.local_attention`` rounds q and k to bfloat16 whatever the
    model's dtype; the tests of what stands AROUND the attention give the
    model a float32 one (as ``tests/test_looped_lm.py`` does). The
    program's own attention is held to the reference below, and by the
    cell's rehearsal."""
    monkeypatch.setattr(hvd, "local_attention", _exact)


@pytest.fixture
def one_device():
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    yield
    hvd.shutdown()


# ---------------------------------------------------------------------------
# (a) the program against the plain reference, through hvd.spmd
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "unfused"])
def test_loss_and_every_gradient_match_the_reference(
        fused, exact_attention, one_device):
    ref = _reference()
    toks = _tokens(0)
    want, acc = _gradients(ref, toks[0])
    loss_fn = transformer.make_loss_fn(CFG, fused_head=fused,
                                       with_expert_pairs=True)
    step = hvd.spmd(lambda p, toks: jax.value_and_grad(
        loss_fn, has_aux=True)(p, toks))
    with jax.default_matmul_precision("highest"):
        (got, pairs), grads = step(hvd.replicate(RUNNER._to_tree(ref.p)),
                                   hvd.rank_stack([toks]))
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=2e-6)
    assert set(acc) == set(ref.p)  # a gradient reaches every leaf
    grads = RUNNER._by_name(jax.tree.map(lambda a: a[0], grads), list(acc))
    for name, g in acc.items():
        scale = float(jnp.max(jnp.abs(g)))
        np.testing.assert_allclose(grads[name], g, rtol=1e-4,
                                   atol=2e-5 * scale, err_msg=name)
    # two expert layers and the MTP module's, this share's four experts
    pairs = np.asarray(pairs)[0]
    assert pairs.shape == (3, SMALL["n_routed_experts"])
    assert (pairs.sum(axis=1) <= T * SMALL["num_experts_per_tok"]).all()
    assert pairs.sum() > 0


def test_three_adamw_steps_through_hvd_match_the_reference(
        exact_attention, one_device):
    """The benchmark's own path (hvd.init -> DistributedOptimizer ->
    hvd.spmd) on one device, float32 moments so that only the order of
    the arithmetic differs; the step's counters; the measured ones."""
    ref = _reference()
    start = {n: np.asarray(a) for n, a in ref.p.items()}
    opt = hvd.DistributedOptimizer(optim.adamw(
        OPT["learning_rate"], b1=OPT["b1"], b2=OPT["b2"], eps=OPT["eps"],
        weight_decay=OPT["weight_decay"], moment_dtype=jnp.float32))
    loss_fn = transformer.make_loss_fn(CFG, fused_head=True,
                                       with_expert_pairs=True)

    def train_step(p, s, toks):
        (loss, pairs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, toks)
        updates, s = opt.update(grads, s, p)
        return (optax.apply_updates(p, updates), s, hvd.allreduce(loss),
                pairs)

    step = hvd.spmd(train_step, donate_argnums=(0, 1))
    params = RUNNER._to_tree(ref.p)
    ps, ss = hvd.replicate(params), hvd.replicate(opt.init(params))
    with jax.default_matmul_precision("highest"):
        for k in range(3):
            ps, ss, loss, pairs = step(ps, ss, hvd.rank_stack([_tokens(k)]))
            want, _ = ref.step(list(_tokens(k)))
            np.testing.assert_allclose(np.asarray(loss)[0], want, rtol=1e-5)
    now = RUNNER._by_name(jax.tree.map(lambda a: np.asarray(a[0]), ps),
                          list(start))
    timeline.session().count_measured("moe.local_pairs",
                                      float(np.asarray(pairs).sum()))
    [program] = [p for p in timeline.record()["programs"].values()
                 if p["dispatches"] == 3]
    for name, p0 in start.items():
        moved = np.asarray(ref.p[name]) - p0
        gap = np.linalg.norm((now[name] - p0) - moved)
        assert gap <= 2e-3 * np.linalg.norm(moved), name
    counters = program["counters"]
    assert counters["model.block_applications"] == 4  # 3 and the MTP's
    assert counters["model.recomputed_blocks"] == 0
    assert counters["model.head_applications"] == 2
    assert counters["model.moe_layers"] == 3  # two layers and the MTP's
    assert counters["model.moe_kept_products"] == 3  # each of them
    assert counters["model.experts_held"] == 4
    assert counters["model.experts_total"] == 16
    assert counters["model.moe_pair_capacity"] == T * 3
    assert counters["moe.local_pairs"] == float(np.asarray(pairs).sum())


@pytest.mark.parametrize("variant", ["dropped_tokens", "no_mtp",
                                     "half_batch"])
def test_a_planted_fault_is_another_model(variant):
    toks = _tokens(0)[0]
    want, good = _gradients(_reference(), toks)
    got, bad = _gradients(_reference(variant), toks)
    assert abs(float(got) - float(want)) > 1e-4 * float(want)
    if variant == "no_mtp":  # nothing reaches the MTP module's leaves
        assert float(jnp.max(jnp.abs(bad["mtp.weh"]))) == 0.0
        assert float(jnp.max(jnp.abs(good["mtp.weh"]))) > 0.0


# ---------------------------------------------------------------------------
# (b) the share test, (c) no token dropped
# ---------------------------------------------------------------------------


def _layer_inputs(total=16, tokens=64, e=32, f=24, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    lp = {"wr": normal(keys[0], e, total), "eg": 0.2 * normal(keys[1], total, e, f),
          "eu": 0.2 * normal(keys[2], total, e, f),
          "ed": 0.2 * normal(keys[3], total, f, e),
          "sg": 0.2 * normal(keys[4], e, f), "su": 0.2 * normal(keys[5], e, f),
          "sd": 0.2 * normal(keys[6], f, e)}
    return lp, normal(keys[7], tokens, e)


def _program_layer(lp, x, first, held, shared):
    """The program's expert layer (``transformer.MoE``) holding experts
    ``first .. first + held`` of ``lp``'s."""
    total = lp["wr"].shape[1]
    cfg = CFG._replace(moe=CFG.moe._replace(
        total=total, held=held, first=first, shared_experts=int(shared)))
    part = slice(first, first + held)
    params = {"router": lp["wr"], "wg": lp["eg"][part],
              "wu": lp["eu"][part], "wd": lp["ed"][part]}
    if shared:
        params.update({f"shared_{n}": {"kernel": lp["s" + n[0]]}
                       for n in ("gate", "up", "down")})
    with jax.default_matmul_precision("highest"):
        out, sown = transformer.MoE(cfg).apply(
            {"params": params}, x[None], mutable=[transformer.EXPERT_PAIRS])
    return out[0], sown[transformer.EXPERT_PAIRS]["pairs"][0]


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The routed parts that the 4 shares of a layer give, plus the shared
    expert counted once, are the uncut reference layer with all 16
    experts; every pair of the batch is some share's."""
    lp, x = _layer_inputs()
    with jax.default_matmul_precision("highest"):
        want = REFERENCE.moe(lp, x, SMALL, False, first=0)
    total, pairs = 0.0, 0
    for share in range(4):
        out, took = _program_layer(lp, x, first=4 * share, held=4,
                                   shared=share == 0)
        total, pairs = total + out, pairs + int(took.sum())
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    assert pairs == x.shape[0] * SMALL["num_experts_per_tok"]
    # ... and one share alone is that share's part by the reference
    with jax.default_matmul_precision("highest"):
        part = REFERENCE.moe({**lp, **{n: lp[n][4:8] for n in
                                       ("eg", "eu", "ed")}}, x, SMALL,
                             False, first=4, shared=False)
    np.testing.assert_allclose(
        _program_layer(lp, x, 4, 4, False)[0], part, rtol=1e-4, atol=1e-5)


def test_the_sliced_heads_logits_side_by_side_are_the_uncut_heads(
        exact_attention):
    """A model holding 1/4 of the vocabulary gives, for tokens of its
    slice, the uncut model's logits of its columns."""
    shares, v = 4, SMALL["vocab_size"]
    full = CFG._replace(vocab_size=shares * v)
    params = transformer.init_params(full, seed=1)
    toks = jnp.asarray(_tokens(1))  # ids of slice 0
    want = transformer.Transformer(full).apply({"params": params}, toks)
    got = []
    for s in range(shares):
        mine = dict(params)
        mine["Embed_0"] = {"embedding": params["Embed_0"]["embedding"][:v]}
        mine["lm_head"] = {
            "kernel": params["lm_head"]["kernel"][:, s * v:(s + 1) * v]}
        got.append(transformer.Transformer(CFG).apply({"params": mine},
                                                      toks))
    np.testing.assert_allclose(jnp.concatenate(got, -1), want, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("favoured", [(4, 5, 6, 7), (5,)],
                         ids=["all_here", "one_expert"])
def test_no_token_is_dropped_whatever_the_imbalance(favoured):
    """A bias that sends every token's choices to the held experts (all
    three to experts 4-7; then one of the three to expert 5 for every
    token): the layer still equals the reference, and the experts took
    every pair."""
    lp, x = _layer_inputs(tokens=128)
    bias = jnp.zeros((16,)).at[jnp.asarray(favoured)].set(10.0)
    first, held, k = 4, 4, SMALL["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        want = REFERENCE.moe(
            {**lp, **{n: lp[n][first:first + held] for n in
                      ("eg", "eu", "ed")}}, x, SMALL, False, bias=bias,
            first=first, shared=False)
        idx, gates = moe_ops.route(x, lp["wr"], bias, k, 1.8)
        got, pairs = moe_ops.routed_experts(
            x, idx, gates, lp["eg"][first:first + held],
            lp["eu"][first:first + held], lp["ed"][first:first + held],
            first=first)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    if len(favoured) == held:
        assert int(pairs.sum()) == x.shape[0] * k  # the buffer is full
    else:
        assert int(pairs[1]) == x.shape[0]  # every token, eight times the mean
    # ... and the gradient through the buffer, full or a third full, is
    # the reference's.
    mine = {n: lp[n][first:first + held] for n in ("eg", "eu", "ed")}
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda x, w: jnp.sum(REFERENCE.moe(
            {**lp, **w}, x, SMALL, False, bias=bias, first=first,
            shared=False) ** 2), argnums=(0, 1))(x, mine)
        got = jax.grad(lambda x, w: jnp.sum(moe_ops.routed_experts(
            x, *moe_ops.route(x, lp["wr"], bias, k, 1.8), w["eg"], w["eu"],
            w["ed"], first=first)[0] ** 2), argnums=(0, 1))(x, mine)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=2e-5 * float(jnp.max(jnp.abs(b))))


def _routed_case(routed, tokens=64, k=3, total=16, first=4, held=4, e=24,
                 f=8, seed=7):
    """A layer's inputs with exactly ``routed`` of the ``tokens x k`` pairs
    held here (experts ``first .. first + held``), every token's choices
    distinct: ``(x, idx, gates, wg, wu, wd)``, float32."""
    rng = np.random.default_rng(seed)
    mine = np.zeros(tokens * k, bool)
    mine[rng.permutation(tokens * k)[:routed]] = True
    here = np.arange(first, first + held)
    away = np.setdiff1d(np.arange(total), here)
    idx = np.empty((tokens, k), np.int32)
    for t, row in enumerate(mine.reshape(tokens, k)):
        idx[t, row] = rng.permutation(here)[:row.sum()]
        idx[t, ~row] = rng.permutation(away)[:k - row.sum()]
    normal = lambda *shape: jnp.asarray(
        rng.standard_normal(shape), jnp.float32)
    gates = jnp.abs(normal(tokens, k)) + 0.1
    return (normal(tokens, e), jnp.asarray(idx), gates,
            0.3 * normal(held, e, f), 0.3 * normal(held, e, f),
            0.3 * normal(held, f, e))


def _dense_part(x, idx, gates, wg, wu, wd, first=4):
    """The routed part with no buffer: every pair through its own
    expert's matrices, the pairs held elsewhere weighed with zero."""
    out = 0.0
    for j in range(idx.shape[1]):
        local = idx[:, j] - first
        mine = (local >= 0) & (local < wg.shape[0])
        at = jnp.clip(local, 0, wg.shape[0] - 1)
        h = jax.nn.silu(jnp.einsum("te,tef->tf", x, wg[at])) \
            * jnp.einsum("te,tef->tf", x, wu[at])
        out = out + jnp.where(mine, gates[:, j], 0.0)[:, None] \
            * jnp.einsum("tf,tfe->te", h, wd[at])
    return out


def _part_and_gradients(fn, case):
    """``fn``'s routed part of ``case`` and the gradient of a weighted
    sum of it with respect to x, the gates and the three matrices."""
    x, idx, gates, wg, wu, wd = case
    probe = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
    with jax.default_matmul_precision("highest"):
        out = fn(x, idx, gates, wg, wu, wd)
        grads = jax.grad(
            lambda x, gates, wg, wu, wd: jnp.sum(
                probe * fn(x, idx, gates, wg, wu, wd)),
            argnums=(0, 1, 2, 3, 4))(x, gates, wg, wu, wd)
    return (out, *grads)


def _program_part(x, idx, gates, wg, wu, wd):
    return moe_ops.routed_experts(x, idx, gates, wg, wu, wd, first=4)[0]


@pytest.fixture
def rounds_of_32(monkeypatch):
    """``ops/moe.ROW_BLOCK`` = 32 for one test: a 192-row buffer is six
    rounds. The traces JAX keeps of the layer's functions were made under
    one block size and know no other: dropped before and after."""
    monkeypatch.setattr(moe_ops, "ROW_BLOCK", 32)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("routed", [0, 1, 32, 33, 192],
                         ids=["none", "one_row", "one_block",
                              "one_block_and_a_row", "full_buffer"])
def test_the_routed_part_at_every_extent(rounds_of_32, routed):
    """The passes between the grouped products get their extent from the
    routed-row count: the part and every gradient are the dense
    reference's with no row routed here, one, exactly one round's block,
    one row more, and the whole 192-row worst-case buffer."""
    case = _routed_case(routed)
    assert moe_ops.row_block(case[1].size) == 32
    assert "slice_sizes=(32, 24)" in str(jax.make_jaxpr(_program_part)(*case))
    pairs = moe_ops.routed_experts(*case, first=4)[1]
    assert int(pairs.sum()) == routed
    for got, want in zip(_part_and_gradients(_program_part, case),
                         _part_and_gradients(_dense_part, case)):
        np.testing.assert_allclose(
            got, want, rtol=1e-4,
            atol=2e-5 * max(float(jnp.max(jnp.abs(want))), 1e-3))


def _poisoned(grouped_matmul):
    """``grouped_matmul`` as a kernel whose grid follows the groups may
    leave it: NaN in every row past the last group, of the product and of
    its transpose; what such rows of a cotangent hold is ignored."""
    def rows(a, sizes):
        return (jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None]

    def product(x, w, sizes):
        @jax.custom_vjp
        def f(x, w):
            return jnp.where(rows(x, sizes), grouped_matmul(x, w, sizes),
                             jnp.nan)

        def fwd(x, w):
            return f(x, w), (x, w)

        def bwd(res, g):
            x, w = res
            back = jax.vjp(lambda x, w: grouped_matmul(x, w, sizes), x, w)[1]
            dx, dw = back(jnp.where(rows(g, sizes), g, 0))
            return jnp.where(rows(dx, sizes), dx, jnp.nan), dw
        f.defvjp(fwd, bwd)
        return f(x, w)
    return product


@pytest.mark.parametrize("routed", [0, 33, 192],
                         ids=["none", "a_block_and_a_row", "full_buffer"])
def test_no_pass_reads_a_row_nobody_was_routed_to(monkeypatch, rounds_of_32,
                                                 routed):
    """What replaces the masks: with NaN left in every buffer row past the
    routed ones, by every grouped product and every transpose of one, and
    NaN in every row of a buffer before a pass lands its blocks in it (on
    a TPU nothing clears one), the part and its gradients stay finite and
    equal."""
    case = _routed_case(routed)
    want = _part_and_gradients(_program_part, case)
    monkeypatch.setattr(moe_ops, "grouped_matmul",
                        _poisoned(moe_ops.grouped_matmul))
    monkeypatch.setattr(moe_ops, "_buffer", lambda rows, width, dtype:
                        jnp.full((rows, width), jnp.nan, dtype))
    got = _part_and_gradients(_program_part, case)
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_array_equal(a, b)


def _buffer_sized_values(jaxpr, rows, inside=False, found=None):
    """``[(primitive, shape, inside a device-sized loop or kernel)]`` of
    every equation of ``jaxpr`` and its sub-jaxprs whose result is a
    matrix of ``rows`` rows."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        sized = name in ("while", "ragged_dot_general", "pallas_call")
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            if len(shape) == 2 and shape[0] == rows:
                found.append((name, shape, inside))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _buffer_sized_values(sub, rows, inside or sized, found)
    return found


def test_nothing_outside_a_device_sized_pass_makes_a_buffer():
    """At the cell's N = 8,192 tokens and k = 4: outside the loops whose
    trip count the routed rows give and the grouped products, the only
    (N k, ·) matrices of the layer, forward, second forward and
    backward, are the buffers' initialisations — no gather, mask,
    activation, sum or cast walks the 32,768 rows."""
    n, k, e, f = 8192, 4, 24, 8
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((n, e), jnp.float32), ((n, k), jnp.int32), ((n, k), jnp.float32),
        ((4, e, f), jnp.float32), ((4, e, f), jnp.float32),
        ((4, f, e), jnp.float32))]
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, idx, gates, wg, wu, wd: jnp.sum(_program_part(
            x, idx, gates, wg, wu, wd)), argnums=(0, 2, 3, 4, 5)))(*shapes)
    made = _buffer_sized_values(jaxpr.jaxpr, n * k)
    assert any(inside for _, _, inside in made)
    # (index arithmetic makes (N k, 1)- and (N k, held)-shaped integers)
    outside = {(name, shape[1]) for name, shape, inside in made
               if not inside and shape[1] in (e, f, 2 * f)}
    assert {name for name, _ in outside} <= {
        "broadcast_in_dim",            # a buffer's one initialisation
        "while", "ragged_dot_general", "pallas_call",  # the passes' results
        "custom_vjp_call", "custom_jvp_call", "pjit", "jit", "checkpoint",
        "remat", "remat2", "closed_call", "custom_vjp_call_jaxpr"}, outside


def test_the_backward_keeps_the_gate_and_up_product_alone(capsys):
    """Of the routed part's (N k, .) buffers the backward keeps one: the
    gate-and-up product, (N k, 2F). Not the gathered rows (N k, E), not
    the activation (N k, F), not the down product (N k, E): those are
    gathered, computed again or never read back."""
    x, idx, gates, wg, wu, wd = _routed_case(100)
    jax.ad_checkpoint.print_saved_residuals(
        lambda x, gates, wg, wu, wd: jnp.sum(moe_ops.routed_experts(
            x, idx, gates, wg, wu, wd, first=4)[0]), x, gates, wg, wu, wd)
    saved = [line.split()[0] for line in
             capsys.readouterr().out.strip().splitlines()]
    assert saved.count("f32[192,16]") == 1, saved  # gate | up, once
    assert not [s for s in saved if s.startswith("f32[192,")
                and s != "f32[192,16]"], saved


def test_the_backward_runs_each_grouped_product_forward_once():
    """In the jaxpr of the layer's gradient the gate-and-up product
    (N k, E) x (E, 2F) and the down product (N k, F) x (F, E) each appear
    once, beside their four transposes: 6 grouped products. (Before the
    product was kept, the backward ran both forward again: 8.)"""
    case = _routed_case(100)
    x, idx, gates, wg, wu, wd = case
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, gates, wg, wu, wd: jnp.sum(_program_part(
            x, idx, gates, wg, wu, wd)), argnums=(0, 1, 2, 3, 4)))(
        x, gates, wg, wu, wd)

    def products(jaxpr, found):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "ragged_dot_general":
                found.append((eqn.invars[0].aval.shape,
                              eqn.outvars[0].aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                products(sub, found)
        return found
    found = products(jaxpr.jaxpr, [])
    assert len(found) == 6, found
    assert found.count(((192, 24), (192, 16))) == 1  # x_s [wg | wu]
    assert found.count(((192, 8), (192, 24))) == 1  # (gate h) wd


def test_the_gates_and_the_routers_gradient_match_a_dense_form():
    """With the gates applied before the down product, each gate's
    cotangent is ``<d(gate h), h>``: the gradient with respect to the
    gates, and through :func:`route` the router's and the tokens', is the
    float32 dense form's — pairs held elsewhere (gate zero, gradient zero)
    and tokens none of whose choices is held here among them."""
    tokens, e, f, total, first, held, k = 64, 24, 8, 16, 4, 4, 3
    rng = np.random.default_rng(11)
    normal = lambda *shape: jnp.asarray(
        rng.standard_normal(shape), jnp.float32)
    x, wr = normal(tokens, e), 0.3 * normal(e, total)
    wg, wu, wd = (0.3 * normal(held, e, f), 0.3 * normal(held, e, f),
                  0.3 * normal(held, f, e))
    bias = jnp.zeros((total,))
    probe = jnp.cos(jnp.arange(tokens * e, dtype=jnp.float32)).reshape(
        tokens, e)
    idx, gates = moe_ops.route(x, wr, bias, k, 1.8)
    mine = (idx >= first) & (idx < first + held)
    assert bool(jnp.any(~mine))  # pairs held elsewhere
    assert bool(jnp.any(~jnp.any(mine, axis=1)))  # a token with none here
    assert bool(jnp.any(mine))

    def through_gates(part):
        return lambda gates: jnp.sum(
            probe * part(x, idx, gates, wg, wu, wd))

    def through_router(part):
        return lambda x, wr: jnp.sum(
            probe * part(x, *moe_ops.route(x, wr, bias, k, 1.8), wg, wu, wd))

    with jax.default_matmul_precision("highest"):
        got_gates = jax.grad(through_gates(_program_part))(gates)
        want_gates = jax.grad(through_gates(_dense_part))(gates)
        got = jax.grad(through_router(_program_part), argnums=(0, 1))(x, wr)
        want = jax.grad(through_router(_dense_part), argnums=(0, 1))(x, wr)
    assert not bool(jnp.any(jnp.where(mine, 0.0, got_gates)))
    for a, b in ((got_gates, want_gates), *zip(got, want)):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=2e-5 * float(jnp.max(jnp.abs(b))))


# ---------------------------------------------------------------------------
# (d) latent attention against the reference
# ---------------------------------------------------------------------------


def _mla_case(cfg_dict, t, seed=5):
    """(program output, reference output) of one MLA on seeded weights."""
    mcfg = RUNNER.model_config(cfg_dict)._replace(dtype=jnp.float32)
    specs = [(n.split(".")[1], shape, init) for n, shape, init in
             REFERENCE._block_specs(cfg_dict, "l0", "dense")
             if n.split(".")[1] in ("wqa", "lnq", "wqb", "wkva", "lnkv",
                                    "wkvb", "wo")]
    lp = jax.jit(lambda k: SEEDED.leaves(k, specs))(SEEDED.key(seed))
    params = {}
    for name, leaf in lp.items():
        *parents, last = RUNNER._BLOCK[name][1:]
        node = params
        for key in parents:
            node = node.setdefault(key, {})
        node[last] = leaf
    x = jax.random.normal(jax.random.PRNGKey(seed),
                          (t, cfg_dict["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = transformer.Attention(mcfg).apply(
            {"params": params}, x[None], jnp.arange(t))[0]
        want = REFERENCE.mla(lp, x, cfg_dict, False)
    return got, want


def test_mla_with_unequal_widths_matches_the_reference_on_the_xla_path():
    """nope 6, rope 4, value 12: the value heads are wider than the
    queries' and keys', which are padded. ``local_attention`` rounds q
    and k to bfloat16: 2e-2 of the largest output."""
    got, want = _mla_case(SMALL, T)
    np.testing.assert_allclose(got, want,
                               atol=2e-2 * float(jnp.max(jnp.abs(want))))
    wider_qk = dict(SMALL, qk_nope_head_dim=12, v_head_dim=6)
    got, want = _mla_case(wider_qk, T)
    np.testing.assert_allclose(got, want,
                               atol=2e-2 * float(jnp.max(jnp.abs(want))))


def test_mla_through_the_flash_kernel_at_256_wide_heads(monkeypatch):
    """The published head widths (192 + 64 / 256) through the Pallas
    kernel, interpreted off the TPU, at its D > 128 default blocks."""
    monkeypatch.setattr(sequence, "local_attention_impl", lambda t: "flash")
    wide = dict(SMALL, num_attention_heads=2, num_key_value_heads=2,
                qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256)
    got, want = _mla_case(wide, 128)
    np.testing.assert_allclose(got, want,
                               atol=2e-2 * float(jnp.max(jnp.abs(want))))


# ---------------------------------------------------------------------------
# (e) what raises, (f) the defaults
# ---------------------------------------------------------------------------


def _forward(cfg, **kwargs):
    toks = jnp.asarray(_tokens(0))
    params = jax.eval_shape(lambda: transformer.init_params(
        cfg._replace(decode=False, attention="local", window=None,
                     recurrent_steps=1, exit_gate=False)))
    return jax.eval_shape(lambda p: transformer.Transformer(cfg).apply(
        {"params": p}, toks, **kwargs), params)


@pytest.mark.parametrize("change, says", [
    (dict(decode=True), "ROADMAP M5"),
    (dict(attention="ring"), "attention='local' only"),
    (dict(attention="ulysses"), "attention='local' only"),
    (dict(window=8), "window"),
    (dict(recurrent_steps=2), "looped model"),
    (dict(recurrent_steps=2, moe=None), "looped model"),
], ids=["mla_decode", "mla_ring", "mla_ulysses", "mla_window",
        "experts_looped", "mtp_looped"])
def test_combinations_that_raise(change, says):
    with pytest.raises(ValueError, match=says):
        _forward(CFG._replace(**change))


def test_mtp_under_sequence_parallelism_raises():
    with pytest.raises(ValueError, match="sequence parallelism"):
        transformer.make_loss_fn(CFG, sp_rank=lambda: 0)
    with pytest.raises(ValueError, match="expert layers"):
        transformer.make_loss_fn(CFG._replace(moe=None),
                                 with_expert_pairs=True)


def test_a_share_that_is_no_share_raises():
    with pytest.raises(hvd.HorovodError, match="not a share"):
        _forward(CFG._replace(moe=CFG.moe._replace(first=14)))


# What the parent commit's models gave (seed 0's init, these tokens):
# the new fields' defaults must reproduce them.
OLD = {"plain": (dict(), 5.083866119384766, 27),
       "gqa_swiglu": (dict(num_kv_heads=2, ffn="swiglu", sandwich_norm=True,
                           rope_theta=1e6), 5.0577521324157715, 36),
       "looped": (dict(ffn="swiglu", recurrent_steps=3, exit_gate=True),
                  5.060669898986816, 32)}


@pytest.mark.parametrize("name", sorted(OLD))
def test_defaults_give_the_old_models(name):
    change, loss, leaves = OLD[name]
    cfg = transformer.TransformerConfig(
        vocab_size=96, num_layers=3, num_heads=4, embed_dim=32, mlp_dim=48,
        max_seq_len=64, dtype=jnp.float32)._replace(**change)
    params = transformer.init_params(cfg)
    assert len(jax.tree.leaves(params)) == leaves
    names = {jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(params)}
    assert not any(w in n for n in names
                   for w in ("moe", "mtp", "q_a", "kv_a"))
    got = transformer.make_loss_fn(cfg, fused_head=True)(
        params, jnp.asarray(_tokens(0, rows=2)))
    np.testing.assert_allclose(got, loss, rtol=1e-6)
