"""The training step named from the inside: ``hvd`` scopes in the compiled
program, ``hvd/*`` spans and each program's plan in the timeline session's
record,
the lazy scope map that never compiles, and the program's own trace reader
(core/xprof.py) on the capture recorded on four chips."""

import collections
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.analysis import hlo
from horovod_tpu.core import timeline, xprof
from horovod_tpu.models import transformer
from horovod_tpu.ops import exchange, optim
from horovod_tpu.parallel import sequence, spmd as spmd_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "benchmark", "tests", "recorded")
CFG = transformer.TransformerConfig(
    vocab_size=128, num_layers=2, num_heads=2, embed_dim=32, mlp_dim=64,
    max_seq_len=16, dtype=jnp.float32)


TAG = "_lm_step.<locals>.train_step/3"  # the wrapper's tag of the step


def _world4():
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:4])


def _lm_step(compression=None, cfg=CFG):
    """A tiny LM step as the benchmark's runner builds it: (step, params,
    optimizer state, tokens), rank-stacked over four CPU devices."""
    params = transformer.init_params(cfg)
    opt = hvd.DistributedOptimizer(optim.adamw(1e-3),
                                   compression=compression)
    loss_fn = transformer.make_loss_fn(cfg, fused_head=True)

    def train_step(p, s, toks):
        loss, grads = jax.value_and_grad(loss_fn)(p, toks)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s, hvd.allreduce(loss)

    step = hvd.spmd(train_step, donate_argnums=(0, 1))
    toks = hvd.rank_stack([
        (np.arange(32, dtype=np.int32).reshape(2, 16) + r) % 128
        for r in range(4)])
    return (step, hvd.replicate(params), hvd.replicate(opt.init(params)),
            toks, params)


@pytest.fixture(scope="module", params=["full", "compact"])
def compiled_texts(request):
    """The optimized text of the default step and of the step built with
    ``compression="bf16"`` (whose buckets are packed: ops/fusion.py),
    under JAX's default locations and under the compact ones
    ``benchmark/run.py`` sets (which alone would leave ``op_name="sin"``:
    utils/jax_compat.named_locations)."""
    # (The other half of named_locations, that no call stack reaches the
    # program's text, is test_lowered_text_holds_no_call_stack.)
    full = request.param == "full"
    jax.config.update("jax_include_full_tracebacks_in_locations", full)
    texts = {}
    try:
        for built in ("default", "bf16"):
            _world4()
            step, ps, ss, toks, _ = _lm_step(
                None if built == "default" else built)
            if full:
                texts[built] = step.lower(ps, ss, toks).compile().as_text()
            else:  # as a run reads it: from the executable the call built
                step(ps, ss, toks)
                texts[built] = timeline.session()._owners[
                    TAG]().executable().as_text()
            hvd.shutdown()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", True)
    return texts


@pytest.fixture(scope="module")
def compiled_text(compiled_texts):
    return compiled_texts["default"]


# The fusion buffer is built only for a bucket whose wire needs one: the
# MEMCPY scopes are the bf16 step's, and the default step has none.
@pytest.mark.parametrize("built,scope", [
    ("default", "jvp(hvd.model)/Transformer/block_0/attn"),
    ("default", "transpose(jvp(hvd.model))/Transformer/block_1/mlp"),
    ("default", "jvp(hvd.model)/head"),
    ("default", "transpose(jvp(hvd.model))/head"),
    ("bf16", "hvd.exchange/MEMCPY_IN_FUSION_BUFFER"),
    ("bf16", "hvd.exchange/MEMCPY_OUT_FUSION_BUFFER"),
    ("default", "hvd.exchange/psum"), ("bf16", "hvd.exchange/psum"),
    ("default", "hvd.update/")])
def test_scopes_in_the_compiled_step(compiled_texts, built, scope):
    text = compiled_texts[built]
    assert f"/shard_map/{scope}" in text
    # flax's own names are kept and not doubled; nothing of the loss is
    # left under an empty scope
    assert "jvp()/" not in text
    assert "hvd.model/hvd.model" not in text


def test_default_step_builds_no_fusion_buffer(compiled_text):
    assert "FUSION_BUFFER" not in compiled_text


def test_program_is_named_after_the_users_function(compiled_text):
    assert compiled_text.startswith("HloModule jit_train_step")


def _count(rec, name):
    return sum(1 for s in rec["spans"] if s[0] == name)


def test_record_after_shutdown_holds_spans_and_programs():
    _world4()
    step, ps, ss, toks, _ = _lm_step()
    n = 4
    for _ in range(n):
        ps, ss, loss = step(ps, ss, toks)
    # builds are the build spans, dispatches the programs' own counts
    rec = timeline.record()
    assert _count(rec, "hvd/spmd/build") == 1
    assert _count(rec, "hvd/spmd/dispatch") == n
    assert {t: p["dispatches"] for t, p in rec["programs"].items()} == {
        TAG: n}
    ps, ss, loss = step(ps, ss, toks[:, :1])  # another shape: a build
    hvd.shutdown()
    rec = timeline.record()
    names = collections.Counter(s[0] for s in rec["spans"])
    assert names["hvd/init"] == 1 and names["hvd/shutdown"] == 1
    assert names["hvd/replicate"] == 2 and names["hvd/rank_stack"] == 1
    assert names["hvd/spmd/build"] == 2
    assert names["hvd/spmd/dispatch"] == n + 1
    assert sorted(rec) == ["compiles", "programs", "spans"]
    # the first call of a program lies inside its build
    parents = [s[3] for s in rec["spans"] if s[0] == "hvd/spmd/dispatch"]
    assert parents == ["hvd/spmd/build"] + [None] * (n - 1) + [
        "hvd/spmd/build"]
    assert all(s[1] <= s[2] for s in rec["spans"])
    assert rec["programs"][TAG]["dispatches"] == n
    assert rec["programs"][TAG + "#2"]["dispatches"] == 1
    import json

    json.dumps(rec)  # plain data
    hvd.init()  # the next world starts a new record
    assert [s[0] for s in timeline.record()["spans"]] == [
        "hvd/import", "hvd/init"]
    hvd.shutdown()


@pytest.mark.parametrize("compression,share", [(None, 1.0), ("bf16", 0.5)])
def test_exchange_wire_bytes_are_the_plan(compression, share):
    """... and ``exchange.unpacked_bytes`` the part of them reduced in the
    leaves' own shapes: all of the default step, none under bf16."""
    _world4()
    step, ps, ss, toks, params = _lm_step(compression)
    step(ps, ss, toks)
    nbytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
    counters = timeline.record()["programs"][TAG]["counters"]
    hvd.shutdown()
    assert counters == {
        "exchange.wire_bytes": int(nbytes * share),
        "exchange.unpacked_bytes": nbytes if compression is None else 0,
        # no leaf of this tiny model is worth a ring of collective-permutes
        # (ops/strategy.py RING_MIN_SLAB_BYTES): all stay all-reduces
        "exchange.async_bytes": 0,
        # the model's own account (models/transformer.py): a plain stack
        # applies each of its CFG.num_layers blocks once, recomputes none
        # and has one head
        "model.block_applications": 2, "model.recomputed_blocks": 0,
        "model.kept_attention_outputs": 0, "model.head_applications": 1,
        # ... and has no expert layer whose gate-and-up product is kept
        "model.moe_kept_products": 0,
        # ... all of them attention (no ``layer_types``: PR 33)
        "model.attention_layers": 2, "model.conv_layers": 0,
        "model.conv_kernel_layers": 0,
        # ... by what their kind does: rotary, no window, no gate
        "model.windowed_attention_layers": 0,
        "model.full_attention_layers": 2,
        "model.rotary_attention_layers": 2,
        "model.gated_attention_layers": 0,
        # the build's own: no persistent cache here, so neither
        "build.cache_hits": 0, "build.cache_misses": 0}


def test_exchange_async_bytes_are_the_leaves_that_go_round_the_ring(
        monkeypatch):
    """``exchange.async_bytes``, counted where the lowering decides
    (ops/strategy.py ``_plain_sum``): of a bucket's leaves, those whose
    plain sum is a ring of collective-permutes — here, with slabs from
    1 MiB, the 2 MiB-slab matrix, not the bias beside it, nor anything
    of a program on one rank."""
    from horovod_tpu.ops import strategy

    monkeypatch.setattr(strategy, "RING_MIN_SLAB_BYTES", 1 << 20)
    for n, ring in ((4, 8 << 20), (1, None)):
        hvd.shutdown()
        hvd.init(devices=jax.devices()[:n])
        grads = {"bias": np.ones((n, 512), np.float32),
                 "kernel": np.ones((n, 4096, 512), np.float32)}

        @hvd.spmd
        def exchange(g):
            return hvd.allreduce_gradients(g, average=False)

        out = exchange(grads)
        [counters] = [p["counters"]
                      for p in timeline.record()["programs"].values()]
        hvd.shutdown()
        np.testing.assert_array_equal(np.asarray(out["kernel"]), float(n))
        assert counters.get("exchange.async_bytes") == ring
        assert counters["exchange.wire_bytes"] == (8 << 20) + 2048


@pytest.mark.parametrize("impl,layer_types,kept", [
    ("flash", None, 6), ("xla", None, 0),
    ("flash", ("conv", "attention"), 3)],
    ids=["flash", "xla", "flash_beside_a_conv_layer"])
def test_a_looped_steps_record_counts_the_kept_attention_outputs(
        monkeypatch, impl, layer_types, kept):
    """A looped stack recomputes every block application, and of each
    ATTENTION one whose mixer is the Pallas kernel (here interpreted) its
    backward reads the kernel's output back; where attention does not go
    through the kernel nothing is named, so nothing is kept, and a
    ``'conv'`` layer has no kernel to keep anything of."""
    monkeypatch.setattr(sequence, "local_attention_impl", lambda t: impl)
    _world4()
    step, ps, ss, toks, _ = _lm_step(cfg=CFG._replace(
        recurrent_steps=3, layer_types=layer_types))
    step(ps, ss, toks)
    counters = timeline.record()["programs"][TAG]["counters"]
    hvd.shutdown()
    convs = 0 if layer_types is None else 3
    assert {k: v for k, v in counters.items() if k.startswith("model.")} == {
        "model.block_applications": 6, "model.recomputed_blocks": 6,
        "model.attention_layers": 6 - convs, "model.conv_layers": convs,
        # (a CPU step: the gate-and-tap pass is the plain form, PR 37)
        "model.conv_kernel_layers": 0,
        "model.windowed_attention_layers": 0,
        "model.full_attention_layers": 6 - convs,
        "model.rotary_attention_layers": 6 - convs,
        "model.gated_attention_layers": 0,
        "model.kept_attention_outputs": kept,
        "model.moe_kept_products": 0,
        "model.head_applications": 1}
    # The kernels' scores (PR 35), a forward and a backward call an
    # attention block application, a row and head: T = 16 is one block,
    # computed whole (256 scores a call) for 136 visible.
    calls = (6 - convs) * toks.shape[1] * CFG.num_heads
    assert {k: v for k, v in counters.items() if k.startswith("flash.")} == (
        {"flash.scores_visible": calls * 2 * 136,
         "flash.scores_computed": calls * 2 * 256} if impl == "flash" else {})


@pytest.mark.parametrize("window", [None, 5])
def test_a_plain_steps_record_counts_the_kernels_scores(monkeypatch, window):
    """``flash.scores_visible`` is the mask counted pair by pair, twice (a
    forward and a backward call), over the step's block applications,
    rows and heads; ``flash.scores_computed`` what
    ``ops/flash_attention.score_counts`` walks."""
    from horovod_tpu.ops import flash_attention

    monkeypatch.setattr(sequence, "local_attention_impl", lambda t: "flash")
    _world4()
    step, ps, ss, toks, _ = _lm_step(cfg=CFG._replace(window=window))
    step(ps, ss, toks)
    counters = timeline.record()["programs"][TAG]["counters"]
    hvd.shutdown()
    t = toks.shape[-1]
    seen = sum(min(p + 1, window or t) for p in range(t))
    calls = CFG.num_layers * toks.shape[1] * CFG.num_heads
    assert counters["flash.scores_visible"] == calls * 2 * seen
    assert counters["flash.scores_computed"] == calls * \
        flash_attention.score_counts(t, t, 16, window=window)[1]


def test_an_expert_layer_steps_record_counts_its_row_block():
    """The plan of the expert layers beside ``model.moe_pair_capacity``:
    ``model.moe_row_block``, the rows a round of the layer's device-sized
    passes takes (``ops/moe.py`` ``row_block``): a pass visits the routed
    rows and less than one such block more, never the whole buffer."""
    from horovod_tpu.ops import moe

    _world4()
    cfg = CFG._replace(moe=transformer.MoEConfig(
        total=8, held=2, top_k=3, expert_dim=16, dense_layers=1))
    step, ps, ss, toks, _ = _lm_step(cfg=cfg)
    step(ps, ss, toks)
    counters = timeline.record()["programs"][TAG]["counters"]
    hvd.shutdown()
    capacity = 2 * 16 * 3  # a rank's tokens x top_k
    assert {k: v for k, v in counters.items() if "moe" in k} == {
        "model.moe_layers": 1, "model.moe_kept_products": 1,
        "model.moe_pair_capacity": capacity,
        "model.moe_row_block": moe.row_block(capacity)}
    assert moe.row_block(capacity) == 32 and moe.row_block(32768) == 512


def _stablehlo_ops(step, *args):
    """``[(op, result type, location's name)]`` of the lowered module's
    one-line operations (an ``all_reduce`` holds a region: not one)."""
    asm = step.lower(*args).compiler_ir().operation.get_asm(
        enable_debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", asm, re.M))
    ops = re.findall(
        r'= "?(stablehlo\.\w+)"?.* (tensor<[^ ]*>) loc\((#loc\d+)\)$',
        asm, re.M)
    return [(op, typ, locs.get(ref, "")) for op, typ, ref in ops]


def _psums(jaxpr, out):
    """The ``psum`` equations of ``jaxpr`` and its sub-jaxprs, in order."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "psum":
            out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _psums(sub, out)
    return out


def test_default_step_reduces_the_gradients_where_they_lie():
    """No flat buffer in the lowered text — no ``concatenate`` and no
    rank-1 ``reshape`` under ``hvd.exchange`` — and in the jaxpr each
    planned bucket is its members' ``psum``s, adjacent, in the plan's
    order and in the leaves' own shapes (this JAX binds one ``psum`` a
    leaf of ``lax.psum``'s tuple), then the loss's own."""
    _world4()
    step, ps, ss, toks, params = _lm_step()
    under = [(op, typ) for op, typ, name in _stablehlo_ops(step, ps, ss, toks)
             if "hvd.exchange" in name]
    assert any(op == "stablehlo.divide" for op, _ in under)  # the average
    assert not [o for o in under if o[0] == "stablehlo.concatenate"]
    assert not [o for o in under if o[0] == "stablehlo.reshape"
                and re.fullmatch(r"tensor<\d+x\w+>", o[1])]
    psums = _psums(jax.make_jaxpr(step)(ps, ss, toks).jaxpr, [])
    plan = exchange.last_plan()
    shapes = [l.shape for l in jax.tree.leaves(params)]
    assert [len(b.indices) for b in plan.buckets] == [len(shapes)]
    assert [e.invars[0].aval.shape for e in psums] == [
        shapes[i] for b in plan.buckets for i in b.indices] + [()]
    hvd.shutdown()


def test_packed_step_still_builds_the_buffer():
    _world4()
    step, ps, ss, toks, _ = _lm_step("bf16")
    under = [op for op, _, name in _stablehlo_ops(step, ps, ss, toks)
             if "hvd.exchange/MEMCPY_IN_FUSION_BUFFER" in name]
    hvd.shutdown()
    assert "stablehlo.concatenate" in under and "stablehlo.reshape" in under


def test_lowered_text_holds_no_call_stack():
    """``hvd.spmd`` lowers with the names in and ONE frame a location,
    whatever the caller set: two call sites give the same text, so the
    compile cache's key holds no call stack (PERF.md, PR 21 and PR 25)."""
    _world4()
    step, ps, ss, toks, _ = _lm_step()

    def text():
        return step.lower(ps, ss, toks).compiler_ir().operation.get_asm(
            enable_debug_info=True)

    def from_deeper():
        return text()

    try:
        for full in (True, False):
            jax.config.update("jax_include_full_tracebacks_in_locations",
                              full)
            here = text()
            assert here == from_deeper()
            assert "hvd.exchange/psum" in here and "callsite(" not in here
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", True)
        hvd.shutdown()


@pytest.fixture
def compile_events():
    """Names of JAX's lowering and backend-compile events while the test
    runs."""
    from jax._src import monitoring

    seen = []

    def listen(name, _secs, **_kw):
        if name.endswith(("jaxpr_to_mlir_module_duration",
                          "backend_compile_duration")):
            seen.append(name)

    monitoring.register_event_duration_secs_listener(listen)
    yield seen
    monitoring.unregister_event_duration_listener(listen)


def test_scope_map_resolves_without_compiling(compile_events):
    _world4()
    step, ps, ss, toks, _ = _lm_step()
    ps, ss, _ = step(ps, ss, toks)
    assert compile_events  # the first call did compile
    del compile_events[:]
    program = timeline.record(scopes=True)["programs"][TAG]
    scopes = program["scopes"]
    hvd.shutdown()
    assert compile_events == []
    # ... and with the text the executable's own memory analysis
    assert sorted(program["memory"]) == [
        "alias_bytes", "argument_bytes", "generated_code_bytes",
        "output_bytes", "temp_bytes"]
    assert all(isinstance(v, int) for v in program["memory"].values())
    nbytes = sum(l.nbytes for l in jax.tree.leaves((ps, ss, toks))) // 4
    assert program["memory"]["argument_bytes"] == nbytes  # a device's
    phases = collections.Counter()
    for op_name, members in scopes.values():
        for name in members or [op_name]:
            for scope in ("transpose(jvp(hvd.model))", "jvp(hvd.model)",
                          "hvd.exchange", "hvd.update"):
                if f"/{scope}/" in name:
                    phases[scope] += 1
                    break
    assert all(phases[s] for s in ("transpose(jvp(hvd.model))",
                                   "jvp(hvd.model)", "hvd.exchange",
                                   "hvd.update")), phases
    names = {v[0] for v in scopes.values()}
    assert any(n.endswith("/shard_map/hvd.exchange/psum") for n in names)
    # the loss's own all-reduce lies outside the exchange
    assert any(n.endswith("/shard_map/psum") for n in names)


def _children(rec, part=None):
    """The rows JAX's events made inside build spans."""
    return [r for r in rec["spans"] if r[0].startswith("hvd/spmd/build/")
            and (part is None or r[0].endswith("/" + part))]


def test_a_build_span_holds_its_parts_as_child_rows():
    """Tracing, lowering and the backend's compile of one ``hvd.spmd``
    program, as JAX's own events time them: rows of the record whose
    parent is the build span and which lie inside it; a second call of
    the same signature builds nothing and adds none."""
    import json

    _world4()
    step, ps, ss, toks, _ = _lm_step()
    ps, ss, _ = step(ps, ss, toks)
    rec = timeline.record()
    (build,) = [r for r in rec["spans"] if r[0] == "hvd/spmd/build"]
    kids = _children(rec)
    assert {r[0].rsplit("/", 1)[1] for r in kids} == {
        "trace", "lower", "compile"}  # no persistent cache: no load
    for name, start, end, parent in kids:
        assert parent == "hvd/spmd/build"
        assert build[1] <= start <= end <= build[2]
    # the tracings nested in the step's own are dropped when it arrives
    assert len([r for r in kids if r[0].endswith("/trace")]) < 8
    assert sum(r[2] - r[1] for r in kids) <= build[2] - build[1]
    ps, ss, _ = step(ps, ss, toks)
    again = timeline.record()
    assert _children(again) == _children(rec)
    json.dumps(again)  # plain data still
    hvd.shutdown()


def test_a_tracing_nested_in_another_counts_once():
    """JAX reports a nested tracing before the one that holds it: the
    row (in a build) or the seconds (outside) of the inner one go when
    the outer one arrives."""
    tl = timeline.Timeline()
    with tl.span("hvd/spmd/build"):
        tl.jax_event("trace", 0.001)
        tl.jax_event("trace", 0.002)
        tl.jax_event("lower", 0.0005)
        tl.jax_event("trace", 0.5)  # holds the two tracings before it
    rows = _children(tl.record())
    assert [r[0].rsplit("/", 1)[1] for r in rows] == ["lower", "trace"]
    assert rows[1][2] - rows[1][1] == 500_000_000
    tl.jax_event("trace", 0.001)
    tl.jax_event("trace", 0.25)
    tl.jax_event("trace", 0.5)
    sums = tl.record()["compiles"]["before_dispatch"]
    assert sums["trace_s"] == pytest.approx(0.5) and sums["programs"] == 0


def test_a_jit_outside_any_build_is_summed_and_makes_no_row():
    """The small programs of a run (weights, placement, readings) are
    seconds by kind and a count, before and after the first dispatch."""
    _world4()
    a, b, ones = jnp.arange(5.0), jnp.arange(7.0), hvd.replicate(jnp.ones(3))
    before = timeline.record()
    jax.jit(lambda x: x * 3 + 1)(a)
    rec = timeline.record()
    assert len(rec["spans"]) == len(before["spans"])
    sums = rec["compiles"]["before_dispatch"]
    was = before["compiles"]["before_dispatch"]
    assert sums["programs"] == was["programs"] + 1
    assert all(sums[k] > was[k] for k in ("trace_s", "lower_s", "backend_s"))
    assert rec["compiles"]["after_dispatch"]["programs"] == 0
    hvd.spmd(lambda x: hvd.allreduce(x))(ones)
    rows = len(timeline.record()["spans"])
    jax.jit(lambda x: x * 5 - 1)(b)
    rec = timeline.record()
    assert len(rec["spans"]) == rows
    assert rec["compiles"]["before_dispatch"] == sums
    late = rec["compiles"]["after_dispatch"]
    assert late["programs"] == 1 and late["backend_s"] > 0
    assert late["last_ns"] > sums["last_ns"]
    assert rec["compiles"]["in_dispatch"]["programs"] == 0
    hvd.shutdown()


@pytest.mark.parametrize("change", [
    lambda x: jax.device_put(x, jax.sharding.NamedSharding(
        hvd.get_group(0).mesh, jax.sharding.PartitionSpec())),
    lambda x: np.asarray(x),
    lambda x: jnp.broadcast_to(jnp.asarray(2.0), x.shape),
], ids=["sharding", "uncommitted", "weak_type"])
def test_a_recompile_under_an_unchanged_signature_is_counted(change):
    """``hvd.spmd`` keys its programs by shape and dtype; ``jax.jit``
    compiles again for what else it keys on. No build span sees that
    compile: it is counted as one inside a dispatch."""
    _world4()
    step = hvd.spmd(lambda x: hvd.allreduce(x) * 2.0)
    x = hvd.replicate(jnp.ones((8,)))
    step(x)
    step(x)
    assert timeline.record()["compiles"]["in_dispatch"]["programs"] == 0
    other = change(x)
    assert spmd_mod._args_signature((other,)) == spmd_mod._args_signature(
        (x,))
    first = [s for s in timeline.record()["spans"]
             if s[0] == "hvd/spmd/dispatch"][0][1]
    step(other)
    rec = timeline.record()
    hidden = rec["compiles"]["in_dispatch"]
    assert hidden["programs"] >= 1 and hidden["backend_s"] > 0
    assert hidden["last_ns"] > first
    # what ``lm_window_builds`` counts saw nothing
    assert not [s for s in rec["spans"]
                if s[0] == "hvd/spmd/build" and s[1] > first]
    assert _count(rec, "hvd/spmd/build") == 1
    hvd.shutdown()


def test_init_shutdown_cycles_leave_one_listener_and_keep_the_import():
    from jax._src import monitoring

    def ours():
        return ([l for l in monitoring.get_event_duration_listeners()
                 if l is timeline._on_duration],
                [l for l in monitoring.get_event_listeners()
                 if l is timeline._on_event])

    rows = []
    for _ in range(2):
        _world4()
        rows.append(timeline.record()["spans"][0])
        assert [len(l) for l in ours()] == [1, 1]
        hvd.shutdown()
        # the record is closed: what JAX does now is not this world's
        closed = timeline.record()["compiles"]
        jax.jit(lambda x: x - 11)(jnp.arange(3.0))
        assert timeline.record()["compiles"] == closed
    assert rows[0] == rows[1] and rows[0][0] == "hvd/import"
    assert rows[0][3] is None and 0 < rows[0][1] < rows[0][2]


def test_a_warm_persistent_cache_makes_the_build_a_load(tmp_path):
    """With JAX's persistent cache on, a program's first build is a miss
    and a ``compile`` row; the same program built by the next world is a
    hit and a ``load`` row, each counted on the program built."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        seen = []
        for _ in range(2):
            _world4()
            x = hvd.replicate(jnp.ones((16,), jnp.float32))
            jax.clear_caches()
            hvd.spmd(lambda x: hvd.allreduce(x) * 7.0 + 3.0)(x)
            rec = timeline.record()
            (tag,) = [t for t in rec["programs"] if "cache_makes" in t]
            build = [r for r in rec["spans"]
                     if r[0] == "hvd/spmd/build"][-1]
            seen.append((
                {r[0].rsplit("/", 1)[1] for r in _children(rec)
                 if r[1] >= build[1]},
                rec["programs"][tag]["counters"]))
            hvd.shutdown()
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert seen[0][0] == {"trace", "lower", "compile"}
    built = [{k: v for k, v in c.items() if k.startswith("build.")}
             for _, c in seen]
    assert built[0] == {"build.cache_hits": 0, "build.cache_misses": 1}
    assert seen[1][0] == {"trace", "lower", "load"}
    assert built[1] == {"build.cache_hits": 1, "build.cache_misses": 0}


def test_build_split_prints_the_records_rows():
    """``tools/build_split.py`` keeps no listener of its own: the parts
    it prints a build are the record's child rows."""
    import json
    import subprocess
    import sys

    tool = os.path.join(ROOT, "tools", "build_split.py")
    with open(tool) as f:
        assert "_listener(" not in f.read()
    out = subprocess.run(
        [sys.executable, tool, "lm_sc2_3b_t8k_1chip", "--rehearse"],
        capture_output=True, text=True, check=True).stdout
    line = json.loads(out.splitlines()[-1])
    assert len(line["builds"]) == 2  # the broadcast's and the step's
    for build in line["builds"]:
        assert sorted(build) == [
            "build_s", "cache_hits", "cache_misses", "cache_retrieval_s",
            "compile_or_load_s", "first_call_and_rest_s", "largest",
            "lower_s", "trace_s"]
        parts = [build[k] for k in ("trace_s", "lower_s",
                                    "compile_or_load_s",
                                    "first_call_and_rest_s")]
        assert all(p > 0 for p in parts)
        assert sum(parts) == pytest.approx(build["build_s"])
        assert build["largest"][0][0] in ("trace", "lower", "compile")


def test_nobody_pays_who_is_not_looking(monkeypatch):
    """No profiler session, no HOROVOD_TIMELINE: the run resolves no
    scope map and never asks a program for its executable."""
    asked = []
    monkeypatch.setattr(spmd_mod._Program, "executable",
                        lambda self: asked.append(self.tag))
    _world4()
    step, ps, ss, toks, _ = _lm_step()
    for _ in range(2):
        ps, ss, _ = step(ps, ss, toks)
    hvd.shutdown()
    assert asked == []
    assert timeline.record()["programs"][TAG]["scopes"] is None


def test_profiled_program_is_resolved_at_shutdown(tmp_path, compile_events):
    """A dispatch under a profiler session pins the program: its map is
    read at ``hvd.shutdown()`` though the wrapper is long gone — and
    after ``jax.clear_caches()`` it gives up rather than compile."""
    _world4()
    step, ps, ss, toks, _ = _lm_step()
    other = hvd.spmd(lambda x: hvd.allreduce(x) + 1.0)
    x = hvd.replicate(jnp.ones((8,)))
    jax.profiler.start_trace(str(tmp_path))
    try:
        ps, ss, loss = step(ps, ss, toks)
        jax.block_until_ready(loss)
    finally:
        jax.profiler.stop_trace()
    x = other(x)  # never profiled: held weakly, dropped with its wrapper
    del step, other
    del compile_events[:]
    hvd.shutdown()
    programs = timeline.record()["programs"]
    assert programs[TAG]["scopes"]
    assert programs["test_profiled_program_is_resolved_at_shutdown."
                    "<locals>.<lambda>/1"]["scopes"] is None

    _world4()
    step, ps, ss, toks, _ = _lm_step()
    ps, ss, _ = step(ps, ss, toks)
    jax.clear_caches()
    del compile_events[:]
    assert timeline.record(scopes=True)["programs"][TAG][
        "scopes"] is None
    assert compile_events == []
    hvd.shutdown()


def test_ring_keeps_setup_and_the_newest_spans():
    tl = timeline.Timeline()
    with tl.span("hvd/broadcast"):
        with tl.span("hvd/spmd/build"):
            pass
    tl.dispatched = True
    for _ in range(timeline.RING_SPANS + 100):
        with tl.span("hvd/spmd/dispatch"):
            pass
    spans = tl.record()["spans"]
    assert [s[0] for s in spans[:2]] == ["hvd/spmd/build", "hvd/broadcast"]
    assert spans[0][3] == "hvd/broadcast" and spans[1][3] is None
    assert len(spans) == 2 + timeline.RING_SPANS
    tl.count_plan("exchange.wire_bytes", 2)  # no program being traced
    assert tl.record()["programs"] == {}


def test_scope_map_joins_fusions_to_their_members():
    text = """HloModule jit_step, is_scheduled=true

%region_0.0 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="hvd.exchange/psum"}
  %b = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%a, %b), metadata={op_name="hvd.exchange/add"}
}

%fused_computation.4 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/shard_map/transpose(jvp(hvd.model))/mlp/mul" stack_frame_id=3}
  ROOT %add.9 = f32[8]{0} add(%mul.1, %p), metadata={op_name="jit(step)/shard_map/hvd.update/add"}
}

ENTRY %main.0_spmd (param.2: f32[8]) -> f32[8] {
  %param.2 = f32[8]{0} parameter(0), metadata={op_name="w"}
  %psum.7 = f32[8]{0} all-reduce(%param.2), channel_id=1, to_apply=%region_0.0, metadata={op_name="jit(step)/shard_map/hvd.exchange/psum"}
  %hvd_flash_fwd.1 = (f32[8]{0}, f32[8]{0}) custom-call(%psum.7), custom_call_target="tpu_custom_call"
  ROOT %fusion.3 = f32[8]{0} fusion(%psum.7), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(step)/shard_map/hvd.update/add"}
}
"""
    scopes = hlo.scope_map(text)
    assert scopes["psum.7"] == ["jit(step)/shard_map/hvd.exchange/psum", []]
    assert scopes["hvd_flash_fwd.1"] == ["", []]
    assert scopes["fusion.3"] == [
        "jit(step)/shard_map/hvd.update/add",
        ["jit(step)/shard_map/transpose(jvp(hvd.model))/mlp/mul",
         "jit(step)/shard_map/hvd.update/add"]]
    assert "mul.1" not in scopes and "add.0" in scopes


# --- the program's reader on the capture recorded on four v5e chips --------

SCHED3 = [[f"HorovodAllreduce_{i}", "ALLREDUCE", "float32", [512, 512], 0,
           -1] for i in range(3)]


def test_xprof_reads_every_plane_and_maps_the_allreduces():
    planes = xprof.device_planes(RECORDED)
    assert sorted(planes) == [f"/device:TPU:{i}" for i in range(4)]
    assert all(len(ev) == 9 for ev in planes.values())
    # on the chip an all-reduce is named %psum_invariant.7: the opcode is
    # read from the instruction's text
    slowest = xprof.slowest_plane(planes)
    assert slowest in planes.values() and xprof.slowest_plane({}) == []
    spans = xprof.map_device_spans(SCHED3, slowest)
    reduces = [s for s in spans if s[1] == "XLA_ALLREDUCE"]
    assert [s[0] for s in reduces] == [r[0] for r in SCHED3]
    assert all(s[3] > 0 for s in reduces)
    # ... and the plane with most collective time is the one written
    total = lambda sp: sum(s[3] for s in sp if s[1] == "XLA_ALLREDUCE")
    assert total(spans) == max(
        total(xprof.map_device_spans(SCHED3, ev)) for ev in planes.values())


def test_device_mode_writes_one_planes_rows(tmp_path, monkeypatch):
    """``_sample_device_step`` on the four-chip capture (the profiler
    stands aside: its capture is the recorded one): the collectives, the
    step and its idle gaps on the ``_device`` row are ONE plane's — every
    row lies inside that plane's ``DEVICE_STEP``."""
    import json
    import shutil
    import types

    monkeypatch.setenv("HOROVOD_TIMELINE_DEVICE", "1")
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: shutil.copy(
            os.path.join(RECORDED, "tiny_dp4.xplane.pb"), d))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    tl = timeline.Timeline()
    path = str(tmp_path / "tl.json")
    tl.start(path)
    prog = types.SimpleNamespace(call=lambda x: x, schedule=SCHED3,
                                 tag="step/1")
    assert spmd_mod._sample_device_step(tl, prog, (jnp.ones(2),)) is not None
    tl.stop()
    events = [e for e in json.loads(open(path).read().rstrip().rstrip(",")
                                    + "]") if e.get("ph") == "X"]
    names = collections.Counter(e["name"] for e in events)
    assert names["XLA_ALLREDUCE"] == 3 and names["DEVICE_STEP"] == 1
    assert any(n.startswith("IDLE [") for n in names)
    step = next(e for e in events if e["name"] == "DEVICE_STEP")
    planes = xprof.device_planes(RECORDED)
    slowest = xprof.slowest_plane(planes)
    assert step["dur"] == pytest.approx(
        max(s + d for _, s, d in slowest) - min(s for _, s, _ in slowest),
        abs=2e-3)
    for e in events:
        assert step["ts"] - 1e-3 <= e["ts"] and \
            e["ts"] + e["dur"] <= step["ts"] + step["dur"] + 2e-3, e


def test_exposed_comm_sees_the_collectives_of_the_capture():
    for events in xprof.device_planes(RECORDED).values():
        reduce_us = sum(d for n, _, d in events
                        if xprof.hlo_base(n) == "all-reduce")
        # nothing runs beside a synchronous all-reduce: all of it exposed
        assert reduce_us > 0
        assert exchange.exposed_comm_ms_of_events(events) == pytest.approx(
            reduce_us / 1e3)


def test_both_hlo_bases_agree_on_the_recorded_names():
    spec = importlib.util.spec_from_file_location(
        "bench_trace", os.path.join(ROOT, "benchmark", "trace.py"))
    bench_trace = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_trace)
    names = {n for ev in xprof.device_planes(RECORDED).values()
             for n, _, _ in ev}
    names |= {
        "fusion.12", "%all-reduce-start.3 = f32[4]", "%all-reduce-done.3",
        "%psum.168 = f32[150994944]{0:T(1024)} all-reduce(f32[150994944]"
        "{0:T(1024)} %bitcast.447), channel_id=3",
        "%shard_map.1704 = (f32[2,1,24,8192,128]{4,3,2,1,0:T(8,128)}, "
        "bf16[1,2,8192,128]{3,2,1,0:T(8,128)(2,1)}) custom-call("
        "bf16[1,24,8192,128]{3,2,1,0} %x), "
        'custom_call_target="tpu_custom_call"',
        "%slice-start.9 = (f32[8], f32[4]) slice-start(f32[8] %x)"}
    assert len(names) >= 9
    for name in names:
        assert xprof.hlo_base(name) == bench_trace.hlo_base(name), name
    assert {xprof.hlo_base(n) for n in names} >= {
        "all-reduce", "fusion", "custom-call", "all-reduce-start"}


def test_idle_gaps_are_named_by_the_span_that_covers_them():
    events = [("%fusion.1 = f32[8] fusion(...)", 0.0, 100.0),
              ("%while.2 = f32[8] while(...)", 150.0, 100.0),
              ("%fusion.3 = f32[8] fusion(...)", 160.0, 20.0),  # nested
              ("%fusion.4 = f32[8] fusion(...)", 260.0, 10.0)]
    host = [("hvd/spmd/dispatch", 90.0, 120.0),
            ("hvd/timeline/wait_sample", 120.0, 400.0)]
    assert xprof.idle_spans(events, host) == [
        ("_device", "IDLE [hvd/timeline/wait_sample]", 100.0, 50.0),
        ("_device", "IDLE [hvd/timeline/wait_sample]", 250.0, 10.0)]
    assert xprof.idle_spans(events, [], k=1) == [
        ("_device", "IDLE [none]", 100.0, 50.0)]


@pytest.mark.parametrize("kwargs", [dict(sharded=True),
                                    dict(sharding="zero2")],
                         ids=["zero1", "zero2"])
def test_sharded_optimizers_trace_their_halves_under_the_scopes(kwargs):
    """The reduce and the gather half of a sharded optimizer are the
    exchange, the inner transformation's pass the update."""
    hvd.shutdown()
    hvd.init()
    opt = hvd.DistributedOptimizer(optax.adam(1e-3), **kwargs)
    params = {"w": jnp.ones((16, 8), jnp.float32),
              "b": jnp.ones((8,), jnp.float32)}

    def step(p, s, x):
        grads = jax.grad(lambda p: jnp.sum((x @ p["w"] + p["b"]) ** 2))(p)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s

    state = jax.eval_shape(opt.init, params)
    stack = lambda t: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((8,) + l.shape, l.dtype), t)
    text = hvd.spmd(step).lower(
        stack(params), stack(state),
        jax.ShapeDtypeStruct((8, 4, 16), jnp.float32)).as_text(
            debug_info=True)
    hvd.shutdown()
    scoped = [ln for ln in text.splitlines() if "hvd.exchange" in ln]
    assert any("reduce_scatter" in ln or "psum_scatter" in ln
               for ln in scoped), scoped[:5]
    assert any("all_gather" in ln for ln in scoped)
    assert "hvd.update/" in text
    assert "hvd.exchange/hvd.update" not in text
    assert "hvd.update/hvd.exchange" not in text
