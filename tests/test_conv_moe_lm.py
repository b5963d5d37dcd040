"""The convolution-and-attention expert-layer LM (a mixer by layer: gated
short convolutions beside q/k-normed grouped-query attention; a leading
dense layer; expert layers holding a share of the experts with NO shared
expert) against its plain reference
``benchmark/reference/train_conv_moe_lm.py``, and what the mixer slot
promises: the convolution is causal and keeps packed documents apart, the
shares of a layer without a shared expert add up to the uncut layer, the
combinations that cannot run say why, and the plan's counters tell the
kinds of layer apart."""

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
from horovod_tpu.ops import optim, short_conv
from horovod_tpu.parallel import sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # benchmark/ is a namespace package of ROOT


def _load(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "conv_moe_" + parts[-1][:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _load("reference", "train_conv_moe_lm.py")
RUNNER = _load("runners", "train_conv_moe_lm.py")
SEEDED = _load("seeded.py")

# A small LFM2-MoE: both mixers (the dense leading layer a conv layer, then
# attention, conv, conv), grouped-query heads with q/k norms, three expert
# layers with no shared expert, share 1 of 4 of the experts; float32.
SMALL = {"hidden_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 48,
         "moe_intermediate_size": 24, "num_experts": 4,
         "num_experts_per_tok": 3, "routed_scaling_factor": 1,
         "num_dense_layers": 1, "num_hidden_layers": 4,
         "layer_types": ["conv", "full_attention", "conv", "conv"],
         "conv_L_cache": 3, "conv_bias": False, "vocab_size": 96,
         "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
         "norm_eps": 1e-5, "norm_topk_prob": True, "use_expert_bias": True,
         "max_position_embeddings": 64, "initializer_range": 0.1,
         "embedding_std": 1.0, "conv_init_bound": 0.5773502691896258,
         "published": {"num_experts": 16},
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
    t, reps = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, reps, axis=2), jnp.repeat(v, reps, axis=2)
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
    model a float32 one (as ``tests/test_moe_lm.py`` does). The program's
    own attention at 64-wide heads is held to the reference below, and by
    the cell's rehearsal."""
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


def test_the_runner_names_every_leaf_of_the_programs_tree():
    want = jax.eval_shape(lambda: transformer.init_params(CFG))
    got = RUNNER._to_tree({n: jax.ShapeDtypeStruct(shape, jnp.float32)
                           for n, shape, _ in REFERENCE.leaf_specs(SMALL)})
    assert jax.tree.map(lambda a: a.shape, want) \
        == jax.tree.map(lambda a: a.shape, got)
    block = want["block_1"]  # the attention layer's, with its two norms
    assert block["attn"]["q_norm"]["scale"].shape == (8,)
    assert block["attn"]["k_norm"]["scale"].shape == (8,)
    assert "shared_gate" not in block["moe"]  # no shared expert
    assert want["block_2"]["conv"]["taps"].shape == (32, 3)


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
        assert scale > 0, name
        np.testing.assert_allclose(grads[name], g, rtol=1e-4,
                                   atol=2e-5 * scale, err_msg=name)
    pairs = np.asarray(pairs)[0]  # three expert layers, four held experts
    assert pairs.shape == (3, SMALL["num_experts"])
    assert (pairs.sum(axis=1) <= T * SMALL["num_experts_per_tok"]).all()
    assert pairs.sum() > 0


def test_three_adamw_steps_through_hvd_match_the_reference(
        exact_attention, one_device):
    """The benchmark's own path (hvd.init -> DistributedOptimizer ->
    hvd.spmd) on one device, float32 moments so that only the order of
    the arithmetic differs; then the plan's counters."""
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
    [program] = [p for p in timeline.record()["programs"].values()
                 if p["dispatches"] == 3]
    for name, p0 in start.items():
        moved = np.asarray(ref.p[name]) - p0
        gap = np.linalg.norm((now[name] - p0) - moved)
        assert gap <= 2e-3 * np.linalg.norm(moved), name
    counters = program["counters"]
    assert counters["model.block_applications"] == 4
    assert counters["model.conv_layers"] == 3
    assert counters["model.attention_layers"] == 1
    assert counters["model.recomputed_blocks"] == 0
    assert counters["model.kept_attention_outputs"] == 0  # a plain stack
    assert counters["model.head_applications"] == 1
    assert counters["model.moe_layers"] == 3
    assert counters["model.moe_kept_products"] == 3
    assert counters["model.experts_held"] == 4
    assert counters["model.experts_total"] == 16
    assert counters["model.moe_pair_capacity"] == T * 3
    assert counters["model.moe_row_block"] == 32  # gcd(96, ROW_BLOCK)


@pytest.mark.parametrize("variant", ["conv_ahead", "no_qk_norm",
                                     "dropped_tokens", "half_batch"])
def test_a_planted_fault_is_another_model(variant):
    toks = _tokens(0)[0]
    want, good = _gradients(_reference(), toks)
    got, bad = _gradients(_reference(variant), toks)
    assert abs(float(got) - float(want)) > 1e-6 * float(want)
    if variant == "no_qk_norm":  # nothing reaches the two scales
        assert float(jnp.max(jnp.abs(bad["l1.lnq"]))) == 0.0
        assert float(jnp.max(jnp.abs(good["l1.lnq"]))) > 0.0
    if variant == "conv_ahead":  # position t's loss sees token t + 1
        leak = np.asarray(_tokens(0)[0]).copy()
        leak[-1] = (leak[-1] + 1) % SMALL["vocab_size"]
        with jax.default_matmul_precision("highest"):
            state = lambda ref, row: ref._p.fwd(
                ref._layer(0), ref._p.embed(ref.p["embed"],
                                            jnp.asarray(row)))
            same = state(_reference(), toks)[:-1] \
                == state(_reference(), leak)[:-1]
            seen = state(_reference(variant), toks)[:-1] \
                == state(_reference(variant), leak)[:-1]
        assert bool(jnp.all(same)) and not bool(jnp.all(seen))


# ---------------------------------------------------------------------------
# (b) the gated short convolution
# ---------------------------------------------------------------------------


def _conv_inputs(b=2, t=16, e=8, taps=3, seed=1):
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(ka, (b, t, 3 * e), jnp.float32),
            jax.random.normal(kb, (e, taps), jnp.float32))


def test_the_short_convolution_is_the_sum_it_says():
    """out_t = C_t * sum_j w[:, j] * (B u)_{t - (K - 1) + j}, position by
    position, and the reference's own form of it."""
    bcu, w = _conv_inputs()
    got = short_conv.gated_short_conv(bcu, w)
    b, c, u = np.split(np.asarray(bcu), 3, axis=-1)
    z, want = b * u, np.zeros_like(b)
    for t in range(z.shape[1]):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += np.asarray(w)[:, j] * z[:, t - 2 + j]
    np.testing.assert_allclose(got, c * want, rtol=1e-5, atol=1e-6)
    e = w.shape[0]
    lp = {"win": jnp.eye(3 * e), "taps": w, "wout": jnp.eye(e)}
    with jax.default_matmul_precision("highest"):
        ref = REFERENCE.short_conv(lp, bcu[0], False)
    # (the reference splits what ITS projection gives; an identity
    # projection of 3E inputs makes that ``bcu`` itself)
    np.testing.assert_allclose(got[0], ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_the_convolution_is_causal_bit_for_bit(taps):
    """Changing position t's input leaves every output before t
    bit-identical, and changes t's own."""
    bcu, w = _conv_inputs(taps=taps)
    out = short_conv.gated_short_conv(bcu, w)
    for t in (0, 5, 15):
        other = bcu.at[:, t].add(1.0)
        moved = short_conv.gated_short_conv(other, w)
        np.testing.assert_array_equal(out[:, :t], moved[:, :t])
        assert not np.array_equal(out[:, t], moved[:, t])
        # ... and reaches taps - 1 positions on, no further
        np.testing.assert_array_equal(out[:, t + taps:], moved[:, t + taps:])


def test_the_conv_block_of_the_model_is_causal(exact_attention):
    """The same through the model: a later token changes no earlier
    logit, with conv and attention layers in one stack."""
    params = transformer.init_params(CFG, seed=2)
    toks = jnp.asarray(_tokens(1))
    logits = transformer.Transformer(CFG).apply({"params": params}, toks)
    for t in (1, 17, T - 1):
        other = toks.at[0, t].set((toks[0, t] + 1) % SMALL["vocab_size"])
        moved = transformer.Transformer(CFG).apply({"params": params},
                                                   other)
        np.testing.assert_array_equal(logits[:, :t], moved[:, :t])
        assert not np.array_equal(logits[:, t], moved[:, t])


def test_a_tap_from_another_document_contributes_nothing():
    """With ``segment_ids`` each packed document is convolved as if it
    stood alone: its first positions see zeros, not its neighbour."""
    bcu, w = _conv_inputs(b=1, t=16)
    segs = jnp.asarray([[0] * 5 + [1] * 1 + [2] * 10])
    got = short_conv.gated_short_conv(bcu, w, segs)
    alone = jnp.concatenate(
        [short_conv.gated_short_conv(bcu[:, a:b], w)
         for a, b in ((0, 5), (5, 6), (6, 16))], axis=1)
    np.testing.assert_array_equal(got, alone)
    assert not np.array_equal(got, short_conv.gated_short_conv(bcu, w))
    # ... and so are its transposes
    loss = lambda x, s: jnp.sum(short_conv.gated_short_conv(x, w, s) ** 2)
    g = jax.grad(loss)(bcu, segs)
    g_alone = jnp.concatenate(
        [jax.grad(loss)(bcu[:, a:b], None)
         for a, b in ((0, 5), (5, 6), (6, 16))], axis=1)
    np.testing.assert_allclose(g, g_alone, rtol=1e-6, atol=1e-6)


def test_the_backward_keeps_the_three_streams_alone(capsys):
    """Between the two projections nothing but the input is saved for the
    backward (``jax.checkpoint``): z and c are two products away."""
    bcu, w = _conv_inputs()
    jax.ad_checkpoint.print_saved_residuals(
        lambda x: jnp.sum(short_conv.gated_short_conv(x, w)), bcu)
    saved = [line.split()[0] for line in
             capsys.readouterr().out.strip().splitlines()]
    assert saved.count("f32[2,16,24]") == 1  # the three streams, once
    assert not [s for s in saved if s.startswith("f32[2,16,")
                and s != "f32[2,16,24]"], saved  # no z, no c


def test_the_model_hands_its_segments_to_the_convolution(exact_attention):
    """Two documents packed into one row give the second the logits it has
    alone at the row's start ... but for rotary positions, which attention
    sees: so a stack of conv layers only."""
    convs = CFG._replace(num_layers=2, layer_types=("conv", "conv"),
                         moe=None)
    params = transformer.init_params(convs, seed=3)
    toks = jnp.asarray(_tokens(2))
    segs = jnp.asarray([[0] * 12 + [1] * (T - 12)])
    model = transformer.Transformer(convs)
    packed = model.apply({"params": params}, toks, segment_ids=segs)
    alone = model.apply({"params": params}, toks[:, 12:])
    np.testing.assert_allclose(packed[:, 12:], alone, rtol=1e-5, atol=1e-5)
    unpacked = model.apply({"params": params}, toks)
    assert not np.allclose(unpacked[:, 12:14], alone[:, :2], atol=1e-3)


# ---------------------------------------------------------------------------
# (c) the share test without a shared expert, (d) q/k norms
# ---------------------------------------------------------------------------


def _layer_inputs(total=16, tokens=64, e=32, f=24, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    lp = {"wr": normal(keys[0], e, total),
          "eg": 0.2 * normal(keys[1], total, e, f),
          "eu": 0.2 * normal(keys[2], total, e, f),
          "ed": 0.2 * normal(keys[3], total, f, e)}
    return lp, normal(keys[4], tokens, e)


def _program_layer(lp, x, first, held):
    """The program's expert layer holding experts ``first .. first +
    held`` of ``lp``'s, no shared expert."""
    cfg = CFG._replace(moe=CFG.moe._replace(
        total=lp["wr"].shape[1], held=held, first=first))
    part = slice(first, first + held)
    params = {"router": lp["wr"], "wg": lp["eg"][part],
              "wu": lp["eu"][part], "wd": lp["ed"][part]}
    with jax.default_matmul_precision("highest"):
        out, sown = transformer.MoE(cfg).apply(
            {"params": params}, x[None], mutable=[transformer.EXPERT_PAIRS])
    return out[0], sown[transformer.EXPERT_PAIRS]["pairs"][0]


@pytest.mark.parametrize("shares", [4, 2, 1])
def test_the_shares_of_a_layer_without_a_shared_expert_add_up(shares):
    """The routed parts that the shares of a layer give — nothing counted
    once beside them — are the uncut reference layer with all 16 experts,
    and every pair of the batch is some share's."""
    lp, x = _layer_inputs()
    with jax.default_matmul_precision("highest"):
        want = REFERENCE.moe(lp, x, SMALL, False, first=0)
    held = 16 // shares
    total, pairs = 0.0, 0
    for share in range(shares):
        out, took = _program_layer(lp, x, first=held * share, held=held)
        total, pairs = total + out, pairs + int(took.sum())
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    assert pairs == x.shape[0] * SMALL["num_experts_per_tok"]
    # ... and one share alone is that share's part by the reference
    first = held * (shares - 1)
    with jax.default_matmul_precision("highest"):
        part = REFERENCE.moe(
            {**lp, **{n: lp[n][first:first + held]
                      for n in ("eg", "eu", "ed")}}, x, SMALL, False,
            first=first)
    np.testing.assert_allclose(_program_layer(lp, x, first, held)[0], part,
                               rtol=1e-4, atol=1e-5)


def _attention_case(cfg_dict, t, qk_norm=True, seed=4):
    """(program, reference) of one q/k-normed GQA mixer on random
    weights with norm scales away from one."""
    mcfg = RUNNER.model_config(cfg_dict)._replace(dtype=jnp.float32,
                                                  qk_norm=qk_norm)
    e, h, g = cfg_dict["hidden_size"], cfg_dict["num_attention_heads"], \
        cfg_dict["num_key_value_heads"]
    d = e // h
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    lp = {"wq": normal(keys[0], e, h, d) / e ** 0.5,
          "wk": normal(keys[1], e, g, d) / e ** 0.5,
          "wv": normal(keys[2], e, g, d) / e ** 0.5,
          "wo": normal(keys[3], h, d, e) / e ** 0.5,
          "lnq": 1.0 + 0.3 * normal(keys[4], d),
          "lnk": 1.0 + 0.3 * normal(keys[5], d)}
    params = {"query": {"kernel": lp["wq"]}, "key": {"kernel": lp["wk"]},
              "value": {"kernel": lp["wv"]}, "out": {"kernel": lp["wo"]}}
    if qk_norm:
        params.update(q_norm={"scale": lp["lnq"]},
                      k_norm={"scale": lp["lnk"]})
    x = normal(keys[6], t, e)
    with jax.default_matmul_precision("highest"):
        got = transformer.Attention(mcfg).apply(
            {"params": params}, x[None], jnp.arange(t))[0]
        want = REFERENCE.attention(lp, x, cfg_dict, False, qk_norm=qk_norm)
    return got, want


def test_qk_normed_gqa_matches_the_reference_and_the_norm_matters(
        exact_attention):
    got, want = _attention_case(SMALL, T)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    off, want_off = _attention_case(SMALL, T, qk_norm=False)
    np.testing.assert_allclose(off, want_off, rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(got - off))) \
        > 1e-2 * float(jnp.max(jnp.abs(got)))
    # the switch adds exactly the two scale vectors to the tree
    leaves = lambda on: {jax.tree_util.keystr(p) for p, _ in
                         jax.tree_util.tree_leaves_with_path(
                             transformer.init_params(
                                 CFG._replace(qk_norm=on)))}
    assert {n.split("]")[-3] for n in leaves(True) - leaves(False)} \
        == {"['q_norm'", "['k_norm'"}


def test_gqa_through_the_flash_kernel_at_64_wide_heads(monkeypatch):
    """The published head geometry (64-wide heads, four query heads a KV
    head) through the Pallas kernel, interpreted off the TPU.
    ``local_attention`` rounds q and k to bfloat16: 2e-2 of the largest
    output."""
    monkeypatch.setattr(sequence, "local_attention_impl", lambda t: "flash")
    wide = dict(SMALL, hidden_size=512, num_attention_heads=8,
                num_key_value_heads=2)
    got, want = _attention_case(wide, 256)
    np.testing.assert_allclose(got, want,
                               atol=2e-2 * float(jnp.max(jnp.abs(want))))


# ---------------------------------------------------------------------------
# (e) what raises, (f) the counters of a looped stack
# ---------------------------------------------------------------------------


def _forward(cfg, **kwargs):
    toks = jnp.asarray(_tokens(0))
    params = jax.eval_shape(lambda: transformer.init_params(
        cfg._replace(decode=False, attention="local", mla=None)))
    return jax.eval_shape(lambda p: transformer.Transformer(cfg).apply(
        {"params": p}, toks, **kwargs), params)


MLA = transformer.MLAConfig(q_rank=16, kv_rank=12, nope_dim=6, rope_dim=4,
                            v_dim=8)


@pytest.mark.parametrize("change, says", [
    (dict(decode=True), "not a KV cache"),
    (dict(attention="ring"), "halo of conv_taps - 1 = 2 positions"),
    (dict(attention="ulysses"), "halo of conv_taps - 1 = 2 positions"),
    (dict(layer_types=("conv", "attention")), "names 2 layers' mixers"),
    (dict(layer_types=("conv", "attention", "mamba", "conv")),
     "Unknown mixer 'mamba'"),
    (dict(layer_types=None, mla=MLA), "qk_norm is the GQA path's"),
], ids=["conv_decode", "conv_ring", "conv_ulysses", "pattern_too_short",
        "unknown_kind", "qk_norm_with_mla"])
def test_combinations_that_raise(change, says):
    with pytest.raises(ValueError, match=says):
        _forward(CFG._replace(**change))


def test_a_conv_layer_has_no_decode_cache():
    with pytest.raises(ValueError, match="ROADMAP M6"):
        transformer.init_cache(CFG, 1)
    # ... an all-attention stack with q/k norms still decodes
    attends = CFG._replace(layer_types=None, moe=None)
    cache = transformer.init_cache(attends, 1)
    assert len(jax.tree.leaves(cache)) == 3 * attends.num_layers


def test_decode_with_qk_norms_follows_the_full_forward(exact_attention):
    """q/k norms sit before the rotary embedding on the decode branch
    too: token-by-token decoding gives the full forward's logits."""
    attends = CFG._replace(layer_types=None, moe=None, num_layers=2)
    params = transformer.init_params(attends, seed=5)
    toks = jnp.asarray(_tokens(3))[:, :8]
    full = transformer.Transformer(attends).apply({"params": params}, toks)
    _, last = transformer.prefill(attends, params, toks)
    np.testing.assert_allclose(last, full[:, -1], rtol=2e-4, atol=2e-4)


def test_mixer_of_layer_reads_the_pattern():
    assert [transformer.mixer_of_layer(CFG, i) for i in range(5)] \
        == ["conv", "attention", "conv", "conv", "attention"]  # one past
    plain = CFG._replace(layer_types=None)
    assert {transformer.mixer_of_layer(plain, i) for i in range(4)} \
        == {"attention"}
    assert sorted(transformer.MIXER) == ["attention", "conv", "full",
                                         "sliding"]
