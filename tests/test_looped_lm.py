"""The looped LM (``recurrent_steps`` passes of one weight-shared stack,
sandwich norms, SwiGLU, an exit gate a pass, the multi-exit loss) against
its plain reference ``benchmark/reference/train_looped_lm.py``, and the
pieces it is built from: the per-position fused head, the exit
distribution, weight sharing, the recomputation rule, and that a plain
configuration still computes the old model."""

import collections
import importlib.util
import json
import os
import subprocess
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.core import timeline
from horovod_tpu.models import transformer
from horovod_tpu.ops import optim
from horovod_tpu.parallel import sequence
from horovod_tpu.ops.losses import (fused_cross_entropy,
                                    fused_cross_entropy_per_position)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # benchmark/ is a namespace package of ROOT


def _load(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "looped_" + parts[-1][:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


from test_flash_attention import equations, kernel_calls  # noqa: E402

REFERENCE = _load("reference", "train_looped_lm.py")
PLAIN_REFERENCE = _load("reference", "train_lm.py")
RUNNER = _load("runners", "train_looped_lm.py")
SEEDED = _load("seeded.py")

# A small Ouro: every mechanism of the configuration, float32 compute.
SMALL = {"hidden_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 4, "head_dim": 8, "intermediate_size": 48,
         "num_hidden_layers": 2, "vocab_size": 96, "total_ut_steps": 4,
         "rope_theta": 1000000, "rms_norm_eps": 1e-6, "hidden_act": "silu",
         "sliding_window": None, "max_position_embeddings": 64,
         "initializer_range": 0.1, "exit_entropy_beta": 0.1}
OPT = {"name": "adamw", "learning_rate": 3e-3, "b1": 0.9, "b2": 0.95,
       "eps": 1e-8, "weight_decay": 0.1, "moment_dtype": "bfloat16"}
SEED, T = 2147483659, 32
CFG = RUNNER.model_config(SMALL)._replace(dtype=jnp.float32)
BETA = SMALL["exit_entropy_beta"]


def _weights(seed=SEED, gate_std=None):
    """The seed's weights by the reference's names (float32)."""
    specs = REFERENCE.leaf_specs(SMALL)
    by_name = jax.jit(lambda k: SEEDED.leaves(k, specs))(SEEDED.key(seed))
    if gate_std is not None:  # a gate that says something: exits differ
        by_name["gate_w"] = by_name["gate_w"] * gate_std
    return by_name


def _tokens(batch, rows=1):
    return SEEDED.lm_tokens(SEED, 0, batch, rows, T, SMALL["vocab_size"])


def _tree(by_name):
    return RUNNER._to_tree(by_name)


def _reference(variant="reference"):
    with jax.default_matmul_precision("highest"):
        return REFERENCE.Reference(SMALL, OPT, SEED, SEEDED, variant)


@pytest.fixture
def exact_attention(monkeypatch):
    """``hvd.local_attention`` rounds q and k to bfloat16 whatever the
    model's dtype (parallel/sequence.py), which reads 1e-2 in a float32
    comparison. These tests are of what stands AROUND the attention, so
    they give the model a float32 one; the program's own attention is
    held to the reference by the cell's rehearsal (last test)."""
    def attention(q, k, v, causal=True, window=None):
        t, reps = q.shape[1], q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, reps, axis=2), jnp.repeat(v, reps, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
        pos = jnp.arange(t)
        seen = (pos[None, :] <= pos[:, None]) \
            & (pos[None, :] > pos[:, None] - (window or t))
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    monkeypatch.setattr(hvd, "local_attention", attention)


# ---------------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "unfused"])
def test_loss_and_every_gradient_match_the_reference(fused, exact_attention):
    ref = _reference()
    toks = _tokens(0)
    acc = {}
    with jax.default_matmul_precision("highest"):
        want = ref._gradients(jnp.asarray(toks[0]), T - 1, acc)
        loss_fn = transformer.make_loss_fn(CFG, fused_head=fused,
                                           exit_beta=BETA)
        got, grads = jax.value_and_grad(loss_fn)(_tree(ref.p),
                                                 jnp.asarray(toks))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    grads = RUNNER._by_name(grads, list(acc))
    assert set(acc) == set(ref.p)  # a gradient reaches every leaf
    for name, g in acc.items():
        scale = float(jnp.max(jnp.abs(g)))
        np.testing.assert_allclose(grads[name], g, rtol=1e-4,
                                   atol=2e-5 * scale, err_msg=name)


def test_three_adamw_steps_through_hvd_match_the_reference(exact_attention):
    """The benchmark's own path (hvd.init -> DistributedOptimizer ->
    hvd.spmd) on one device, float32 moments so that only the order of
    the arithmetic differs; and the step's counters."""
    ref = _reference()
    start = {n: np.asarray(a) for n, a in ref.p.items()}
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    opt = hvd.DistributedOptimizer(optim.adamw(
        OPT["learning_rate"], b1=OPT["b1"], b2=OPT["b2"], eps=OPT["eps"],
        weight_decay=OPT["weight_decay"], moment_dtype=jnp.float32))
    loss_fn = transformer.make_loss_fn(CFG, fused_head=True, exit_beta=BETA)

    def train_step(p, s, toks):
        loss, grads = jax.value_and_grad(loss_fn)(p, toks)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s, hvd.allreduce(loss)

    step = hvd.spmd(train_step, donate_argnums=(0, 1))
    params = _tree(ref.p)
    ps, ss = hvd.replicate(params), hvd.replicate(opt.init(params))
    with jax.default_matmul_precision("highest"):
        for k in range(3):
            ps, ss, loss = step(ps, ss, hvd.rank_stack([_tokens(k)]))
            want, _ = ref.step(list(_tokens(k)))
            np.testing.assert_allclose(np.asarray(loss)[0], want, rtol=1e-5)
    now = RUNNER._by_name(jax.tree.map(lambda a: np.asarray(a[0]), ps),
                          list(start))
    [program] = [p for p in timeline.record()["programs"].values()
                 if p["dispatches"] == 3]
    hvd.shutdown()
    for name, p0 in start.items():
        moved = np.asarray(ref.p[name]) - p0
        gap = np.linalg.norm((now[name] - p0) - moved)
        assert gap <= 2e-3 * np.linalg.norm(moved), name
    layers, passes = SMALL["num_hidden_layers"], SMALL["total_ut_steps"]
    counters = program["counters"]
    assert counters["model.block_applications"] == layers * passes
    assert counters["model.recomputed_blocks"] == layers * passes
    assert counters["model.head_applications"] == passes
    # no Pallas kernel in this step (T=32 on the CPU): nothing is named,
    # so nothing of the attention is kept
    assert counters["model.kept_attention_outputs"] == 0


def test_last_pass_only_is_another_model():
    """The reference's planted fault differs where it should: no
    gradient below the last pass's blocks, another loss."""
    toks = jnp.asarray(_tokens(0)[0])
    good, bad = {}, {}
    with jax.default_matmul_precision("highest"):
        want = _reference()._gradients(toks, T - 1, good)
        got = _reference("last_pass_only")._gradients(toks, T - 1, bad)
    assert "embed" not in bad and "gate_w" in good
    assert float(jnp.max(jnp.abs(bad["gate_w"]))) == 0.0
    assert abs(float(got) - float(want)) > 1e-3 * float(want)


# ---------------------------------------------------------------------------
# weight sharing, recomputation, the old model
# ---------------------------------------------------------------------------


def _untied_loss(copies, shared, toks):
    """The looped loss with pass t using ``copies[t]`` (the blocks' and
    the final norm's leaves) — built from the program's own Block."""
    block = transformer.Block(CFG)
    norm = nn.RMSNorm(dtype=CFG.dtype)
    positions = jnp.arange(toks.shape[1])
    x = shared["Embed_0"]["embedding"][toks]
    losses, gates = [], []
    for t, leaves in enumerate(copies):
        for i in range(CFG.num_layers):
            x = block.apply({"params": leaves[f"block_{i}"]}, x, positions)
        x = norm.apply({"params": leaves["RMSNorm_0"]}, x)
        logits = x[:, :-1] @ shared["lm_head"]["kernel"]
        losses.append(optax.softmax_cross_entropy_with_integer_labels(
            logits, toks[:, 1:]))
        if t + 1 < len(copies):
            g = shared["exit_gate"]
            gates.append((x[:, :-1] @ g["kernel"])[..., 0] + g["bias"][0])
    return transformer.exit_loss(jnp.stack(losses), jnp.stack(gates), BETA)


def test_shared_leaf_gradient_is_the_sum_of_the_passes(exact_attention):
    params = _tree(_weights(gate_std=20.0))
    toks = jnp.asarray(_tokens(1, rows=2))
    per_pass = {k: v for k, v in params.items()
                if k.startswith("block_") or k == "RMSNorm_0"}
    shared = {k: v for k, v in params.items() if k not in per_pass}
    copies = [per_pass] * CFG.recurrent_steps
    with jax.default_matmul_precision("highest"):
        want, tied = jax.value_and_grad(transformer.make_loss_fn(
            CFG, exit_beta=BETA))(params, toks)
        got, apart = jax.value_and_grad(_untied_loss)(copies, shared, toks)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    summed = jax.tree.map(lambda *g: sum(g), *apart)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(summed),
            jax.tree.leaves({k: tied[k] for k in per_pass})):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=2e-6 * float(jnp.max(jnp.abs(b))),
            err_msg=jax.tree_util.keystr(path))
    # every pass contributes: none of the four parts is the whole
    first = jax.tree.leaves(apart[0]["block_0"]["attn"])[0]
    assert float(jnp.linalg.norm(first)) > 0
    assert not np.allclose(first, jax.tree.leaves(
        tied["block_0"]["attn"])[0], rtol=1e-2)


def test_recomputation_on_and_off_give_equal_gradients(monkeypatch,
                                                       exact_attention):
    params = _tree(_weights(gate_std=20.0))
    toks = jnp.asarray(_tokens(2, rows=2))
    grad = lambda: jax.value_and_grad(transformer.make_loss_fn(
        CFG, fused_head=True, exit_beta=BETA))(params, toks)
    text = lambda: jax.jit(jax.grad(transformer.make_loss_fn(
        CFG, fused_head=True))).lower(params, toks).as_text(debug_info=True)
    on = grad()
    assert "rematted_computation" in text()  # the rule: a looped stack
    monkeypatch.setattr(transformer.nn, "remat", lambda module, **_: module)
    off = grad()
    assert "rematted_computation" not in text()
    for a, b in zip(jax.tree.leaves(on), jax.tree.leaves(off)):
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=2e-6 * float(jnp.max(jnp.abs(b))))
    # ... and a stack run once keeps what it always kept
    monkeypatch.undo()
    plain = CFG._replace(recurrent_steps=1, exit_gate=False)
    assert "rematted_computation" not in jax.jit(jax.grad(
        transformer.make_loss_fn(plain))).lower(
            transformer.init_params(plain), toks).as_text(debug_info=True)


@pytest.fixture
def kernel_attention(monkeypatch):
    """``hvd.local_attention``'s ``impl='auto'`` resolved to the Pallas
    flash kernel, as on a TPU above T=2048; off the TPU the kernel runs
    interpreted (``ops/flash_attention._resolve``)."""
    monkeypatch.setattr(sequence, "local_attention_impl", lambda t: "flash")


def test_the_backward_reads_the_attention_kernels_output_back(
        monkeypatch, kernel_attention):
    """The recomputation rule with the kernel in the block: the gradient
    holds the forward kernel once a layer (the scan's forward body) where
    a bare ``nn.remat`` holds it twice (and again in the backward body),
    and the backward kernel once either way; the saved output and
    log-sum-exp are the bits the second run gave, so the gradients are
    equal to the bare rule's and to no recomputation's bit for bit."""
    params = _tree(_weights(gate_std=20.0))
    toks = jnp.asarray(_tokens(4, rows=2))
    layers = SMALL["num_hidden_layers"]
    remat = nn.remat
    forms = {"kept": remat,
             "bare": lambda module, **_: remat(module),
             "off": lambda module, **_: module}
    calls, grads = {}, {}
    for name, form in forms.items():
        monkeypatch.setattr(transformer.nn, "remat", form)
        grad = jax.value_and_grad(transformer.make_loss_fn(  # traced anew
            CFG, fused_head=True, exit_beta=BETA))
        calls[name] = kernel_calls(jax.make_jaxpr(grad)(params, toks).jaxpr)
        grads[name] = jax.jit(grad)(params, toks)
    assert calls["kept"] == {"hvd_flash_fwd": layers,
                             "hvd_flash_bwd": layers}
    assert calls["bare"] == {"hvd_flash_fwd": 2 * layers,
                             "hvd_flash_bwd": layers}
    assert calls["off"] == calls["kept"]
    for other in ("bare", "off"):
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(grads["kept"]),
                jax.tree.leaves(grads[other])):
            np.testing.assert_array_equal(
                a, b, err_msg=other + jax.tree_util.keystr(path))


def test_the_passes_scan_stacks_the_two_named_residuals_and_nothing_new(
        kernel_attention):
    """What crosses the ``nn.scan`` x ``nn.remat`` of the looped step,
    with the kernels that follow the mask (PR 35) as before them: a
    layer's input, its attention kernel's output and log-sum-exp — the two
    names, once a layer —, the pass's norms; twelve float arrays stacked a
    pass, no table, no integer."""
    from horovod_tpu.ops import flash_attention as fa

    params = _tree(_weights(gate_std=20.0))
    toks = jnp.asarray(_tokens(4, rows=2))
    layers, heads = SMALL["num_hidden_layers"], SMALL["num_attention_heads"]
    jaxpr = jax.make_jaxpr(jax.grad(transformer.make_loss_fn(
        CFG, fused_head=True, exit_beta=BETA)))(params, toks).jaxpr
    names = [eqn.params["name"] for eqn in equations(jaxpr)
             if eqn.primitive.name == "name"]
    assert sorted(names) == sorted(
        [fa.OUT_RESIDUAL, fa.LSE_RESIDUAL] * layers)
    forward = next(eqn for eqn in equations(jaxpr)
                   if eqn.primitive.name == "scan"
                   and eqn.params["length"] == SMALL["total_ut_steps"])
    stacked = [v.aval for v in forward.outvars[forward.params["num_carry"]:]]
    assert all(a.dtype == jnp.float32 for a in stacked)
    shapes = [a.shape[1:] for a in stacked]
    rows = toks.shape[0]
    assert shapes.count((rows, T, heads, SMALL["head_dim"])) == layers
    assert shapes.count((rows, heads, T)) == layers
    assert len(stacked) == 12


def test_the_looped_step_traces_each_kernel_once(monkeypatch,
                                                 kernel_attention):
    """Set-up's guard: flax's ``nn.scan`` traces its body several times
    and ``nn.remat`` each block again, so a ``pallas_call`` built a call
    site had its kernel's body traced three times a layer (24 times in the
    looped cell, seconds of every start); built once a geometry
    (``ops/flash_attention._fwd_call`` / ``_bwd_call``) it is traced once
    or twice a process, whatever the model around it."""
    from horovod_tpu.ops import flash_attention as fa

    traced = collections.Counter()

    def counting(kernel):
        def counted(*args, **kwargs):
            traced[kernel.__name__] += 1
            return kernel(*args, **kwargs)
        return counted

    monkeypatch.setattr(fa, "_fwd_kernel", counting(fa._fwd_kernel))
    monkeypatch.setattr(fa, "_bwd_fused_kernel",
                        counting(fa._bwd_fused_kernel))
    fa._fwd_call.cache_clear()  # built anew, around the counting bodies
    fa._bwd_call.cache_clear()
    try:
        params = _tree(_weights())
        toks = jnp.asarray(_tokens(4, rows=2))
        jaxpr = jax.make_jaxpr(jax.grad(transformer.make_loss_fn(
            CFG, fused_head=True, exit_beta=BETA)))(params, toks).jaxpr
    finally:
        fa._fwd_call.cache_clear()  # nothing keeps the counting bodies
        fa._bwd_call.cache_clear()
    layers = SMALL["num_hidden_layers"]
    assert kernel_calls(jaxpr) == {"hvd_flash_fwd": layers,
                                   "hvd_flash_bwd": layers}
    # (the forward's once more where JAX keys its trace cache apart: the
    # primal's call beside the VJP rule's; never once a call site)
    assert traced["_bwd_fused_kernel"] == 1
    assert 1 <= traced["_fwd_kernel"] <= 2 < 3 * layers


def test_a_stack_run_once_has_no_checkpoint_and_keeps_nothing_by_name(
        kernel_attention):
    """... whatever its attention: one forward kernel a layer, one
    backward, no ``checkpoint`` in the gradient (the looped stack's has
    one), and the counter 0 (a looped step's: ``tests/test_tracing.py``)."""
    plain = CFG._replace(recurrent_steps=1, exit_gate=False)
    toks = jnp.asarray(_tokens(5, rows=2))
    params = transformer.init_params(plain)
    loss_fn = transformer.make_loss_fn(plain, fused_head=True)
    jaxpr = jax.make_jaxpr(jax.grad(loss_fn))(params, toks).jaxpr
    assert kernel_calls(jaxpr) == {
        "hvd_flash_fwd": plain.num_layers, "hvd_flash_bwd": plain.num_layers}
    primitives = lambda j: {eqn.primitive.name for eqn in equations(j)}
    assert "remat2" not in primitives(jaxpr)  # jax.checkpoint's
    assert "remat2" in primitives(jax.make_jaxpr(jax.grad(
        transformer.make_loss_fn(CFG, fused_head=True)))(
            transformer.init_params(CFG), toks).jaxpr)
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    step = hvd.spmd(lambda p, toks: hvd.allreduce(loss_fn(p, toks)))
    step(hvd.replicate(params), hvd.rank_stack([np.asarray(toks)]))
    [program] = timeline.record()["programs"].values()
    hvd.shutdown()
    assert program["counters"]["model.kept_attention_outputs"] == 0


def _old_loss(cfg, fused):
    """make_loss_fn's body as it was before the looped model (PR 26)."""
    model = transformer.Transformer(cfg)

    def loss(params, tokens):
        if fused:
            hidden = model.apply({"params": params}, tokens,
                                 return_hidden=True)
            w = params["lm_head"]["kernel"].astype(cfg.dtype)
            return fused_cross_entropy(
                hidden[:, :-1].reshape(-1, hidden.shape[-1]), w,
                tokens[:, 1:].reshape(-1), chunk=min(8192, w.shape[1]))
        logits = model.apply({"params": params}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]).mean()

    return loss


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "unfused"])
def test_default_fields_give_the_old_model(fused, exact_attention):
    """One pass, no sandwich norm, the GELU FFN, no gate: the old loss
    and gradients to the bit, the old parameter tree, and the plain LM's
    reference's mathematics."""
    cfg = transformer.TransformerConfig(
        vocab_size=96, num_layers=2, num_heads=4, num_kv_heads=2,
        embed_dim=32, mlp_dim=64, max_seq_len=64, dtype=jnp.float32,
        window=16)
    assert (cfg.ffn, cfg.sandwich_norm, cfg.rope_theta, cfg.recurrent_steps,
            cfg.exit_gate) == ("gelu", False, 10000.0, 1, False)
    params = transformer.init_params(cfg, seed=3)
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "attn",
                                      "Dense_0", "Dense_1"}
    toks = jnp.asarray(_tokens(3, rows=2))
    new = jax.value_and_grad(transformer.make_loss_fn(
        cfg, fused_head=fused))(params, toks)
    old = jax.value_and_grad(_old_loss(cfg, fused))(params, toks)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        np.testing.assert_array_equal(a, b)
    # the plain reference's block on the same weights, one row
    ref_cfg = {"norm_epsilon": 1e-6, "rope_theta": 10000.0,
               "sliding_window": 16}
    with jax.default_matmul_precision("highest"):
        x = params["Embed_0"]["embedding"][toks[0]]
        for i in range(2):
            b = params[f"block_{i}"]
            x = PLAIN_REFERENCE._block(
                {"ln1": b["RMSNorm_0"]["scale"],
                 "ln2": b["RMSNorm_1"]["scale"],
                 "wq": b["attn"]["query"]["kernel"],
                 "wk": b["attn"]["key"]["kernel"],
                 "wv": b["attn"]["value"]["kernel"],
                 "wo": b["attn"]["out"]["kernel"],
                 "w1": b["Dense_0"]["kernel"], "w2": b["Dense_1"]["kernel"]},
                x, ref_cfg, False)
        want = PLAIN_REFERENCE._head_loss(
            {"ln_f": params["RMSNorm_0"]["scale"],
             "head": params["lm_head"]["kernel"]}, x, toks[0], ref_cfg,
            False, T - 1)
        got = transformer.make_loss_fn(cfg, fused_head=fused)(params,
                                                              toks[:1])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_looped_configuration_is_checked():
    toks = jnp.zeros((1, 8), jnp.int32)
    small = dict(vocab_size=32, num_layers=1, num_heads=2, embed_dim=16,
                 mlp_dim=32, max_seq_len=8)
    for bad, match in [(dict(exit_gate=True), "exit_gate needs"),
                       (dict(recurrent_steps=0), "recurrent_steps"),
                       (dict(ffn="relu"), "Unknown ffn"),
                       (dict(recurrent_steps=2, decode=True), "decode")]:
        cfg = transformer.TransformerConfig(**small, **bad)
        with pytest.raises(ValueError, match=match):
            transformer.Transformer(cfg).init(jax.random.PRNGKey(0), toks)
    # a looped model without a gate trains its last pass alone, and
    # its logits are the last pass's
    cfg = transformer.TransformerConfig(**small, recurrent_steps=3,
                                        dtype=jnp.float32)
    params = transformer.init_params(cfg)
    model = transformer.Transformer(cfg)
    logits = model.apply({"params": params}, toks)
    passes, gates = model.apply({"params": params}, toks, return_passes=True)
    assert len(passes) == 3 and gates == ()
    np.testing.assert_array_equal(logits, passes[-1])
    want = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], toks[:, 1:]).mean()
    np.testing.assert_allclose(transformer.make_loss_fn(cfg)(params, toks),
                               want, rtol=1e-6)


# ---------------------------------------------------------------------------
# the per-position fused head
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,e,v,chunk", [
    (24, 16, 64, 64),      # one chunk
    (24, 16, 96, 32),      # unrolled chunks
    (17, 16, 83, 32),      # a remainder chunk, odd rows
    (24, 8, 17 * 4 + 3, 4),  # beyond UNROLL_MAX_CHUNKS: the scan path
], ids=["one", "unrolled", "remainder", "scan"])
def test_per_position_fused_head_matches_optax(n, e, v, chunk):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(n + v), 4)
    x = jax.random.normal(k1, (n, e), jnp.float32)
    w = jax.random.normal(k2, (e, v), jnp.float32) * 0.3
    t = jax.random.randint(k3, (n,), 0, v)
    g = jax.random.normal(k4, (n,), jnp.float32)  # any cotangent a row
    plain = lambda x, w: optax.softmax_cross_entropy_with_integer_labels(
        x @ w, t)
    fused = lambda x, w: fused_cross_entropy_per_position(x, w, t, chunk)
    want, want_vjp = jax.vjp(plain, x, w)
    got, got_vjp = jax.vjp(fused, x, w)
    assert got.shape == (n,) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for a, b in zip(got_vjp(g), want_vjp(g)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    # the mean form is this with a mean on top
    np.testing.assert_allclose(fused_cross_entropy(x, w, t, chunk),
                               jnp.mean(want), rtol=1e-6)


# ---------------------------------------------------------------------------
# the exit distribution and the loss
# ---------------------------------------------------------------------------


def test_exit_distribution_sums_to_one():
    z = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (3, 5, 7))
    logp = transformer.exit_log_probs(z)
    assert logp.shape == (4, 5, 7)
    np.testing.assert_allclose(jnp.exp(logp).sum(0), 1.0, rtol=1e-6)
    lam = jax.nn.sigmoid(z)
    np.testing.assert_allclose(jnp.exp(logp[0]), lam[0], rtol=1e-6)
    np.testing.assert_allclose(jnp.exp(logp[2]),
                               lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-5)
    np.testing.assert_allclose(
        jnp.exp(logp[3]), (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]),
        rtol=1e-5)
    # the reference's own form of the same objective
    ce = jax.random.uniform(jax.random.PRNGKey(1), (4, 5, 7)) + 2.0
    np.testing.assert_allclose(
        transformer.exit_loss(ce, z, 0.3),
        jnp.mean(REFERENCE.exit_objective(ce, lam, 0.3)), rtol=1e-6)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "unfused"])
def test_zero_gate_gives_the_halving_distribution(fused):
    """Gate weight and bias zero: lambda = 1/2, p = (1/2, 1/4, 1/8, 1/8),
    H = 1.75 ln 2, so the loss is sum_t p_t CE_t - 1.2130 beta."""
    by_name = _weights()
    by_name["gate_w"] = jnp.zeros_like(by_name["gate_w"])
    params = _tree(by_name)
    toks = jnp.asarray(_tokens(4, rows=2))
    logits, gates = transformer.Transformer(CFG).apply(
        {"params": params}, toks, return_passes=True)
    assert len(logits) == 4 and len(gates) == 3
    assert all(float(jnp.max(jnp.abs(z))) == 0.0 for z in gates)
    ce = [optax.softmax_cross_entropy_with_integer_labels(
        l[:, :-1], toks[:, 1:]).mean() for l in logits]
    want = sum(p * c for p, c in zip((0.5, 0.25, 0.125, 0.125), ce)) \
        - 1.75 * np.log(2.0) * BETA
    assert 1.75 * np.log(2.0) == pytest.approx(1.2130, abs=5e-5)
    got = transformer.make_loss_fn(CFG, fused_head=fused,
                                   exit_beta=BETA)(params, toks)
    np.testing.assert_allclose(got, want, rtol=1e-5)


# ---------------------------------------------------------------------------
# the benchmark's cell, end to end at its rehearsal size
# ---------------------------------------------------------------------------


def test_the_cells_rehearsal_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "lm_ouro_2_6b_t8k_1chip", "--seed", "3000000019",
         "--seconds", "0.5", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["metrics"] == {}
    limited = [row for row in result["compared"]
               if row["limit"] is not None]
    assert len(limited) >= 5  # the losses and both worst-leaf gaps
