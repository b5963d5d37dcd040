"""Continuous-batching generation engine over the paged KV cache.

The engine jits exactly TWO fixed-shape executables and reuses them for
the life of the service (the ISSUE's no-retrace acceptance bar):

* ``prefill`` — a ``lax.while_loop`` of one-token steps that ingests
  every newly admitted request's prompt in one compiled call (inactive
  batch slots are masked; their pool writes are redirected to the null
  block). The loop's trip window is DATA, not shape: it runs
  ``[min(skip), max(prompt_len))`` over the admitted rows, so prefix
  hits (and short prompts) save real device iterations — a
  fully-shared system prompt admitted alone costs one step — while the
  executable still compiles exactly once. Shared-span positions inside
  the window are write-masked: their pages are already in the pool,
  mapped from the prefix index, and are never rewritten. Returns the
  first sampled token per admitted row.
* ``decode_step`` — ONE token for every active slot: gather each slot's
  paged-cache view through its block table, run the model's decode path
  (the same :class:`~horovod_tpu.models.transformer.Attention` branch
  ``transformer.generate`` runs — bit-identical greedy tokens at
  fp32/bf16 KV), scatter the fresh K/V back into the pool, sample.

Speculative decoding (``speculate=k`` > 0, ``HOROVOD_SERVE_SPECULATE``)
swaps ``decode_step`` for a draft-and-verify pair WITHOUT breaking the
fixed-executable discipline — the engine then runs exactly two TARGET
executables (``prefill``, ``verify_step``) plus two DRAFT executables
(``draft_prefill``, ``draft_propose``) for its life:

* ``draft_propose`` — a small draft model (its own paged pool, int4 KV
  by default — proposals are guesses, every emitted token is re-scored
  by the target) autoregressively proposes ``k`` tokens per active slot
  in ONE compiled call (a fixed-``k`` ``lax.scan`` of the same paged
  one-token forward).
* ``verify_step`` — the target scores all ``k + 1`` positions (carried
  last token + ``k`` proposals) in ONE batched fixed-shape call: the
  whole (batch, k+1) window runs through the shared paged attend as a
  single wide forward, reading the weights once per step instead of
  once per position (the amortization the speedup comes from); a causal
  visibility mask keeps the logits bit-identical to k+1 sequential
  one-token steps. The accept rule is *accept while the proposal equals the target's own
  (deterministically keyed) choice at that position; emit the target's
  choice at the first mismatch* — so the emitted stream is the target's
  sequential stream, token for token: greedy speculation is
  bit-identical to ``transformer.generate``, and sampled speculation is
  bit-identical to the non-speculative engine (same
  (seed, request, position) keys). Accepted tokens' K/V already sit in
  the pool (the verify scan wrote them); the rejected tail rolls back
  via refcounted page truncation (``BlockPool.truncate``) — whole freed
  blocks are released and a shared partial boundary block would be
  copy-on-write forked (engine tails are private by construction, so
  the fork path is a loud invariant, not a hot path).

Per step a speculating slot may write up to ``k + 1`` cache positions,
so admission backs ``prompt + k + 1`` tokens of page headroom
(serving/scheduler.py) and ``_ensure_block`` guarantees the whole write
window before each verify. Timeline: DRAFT/VERIFY spans and ROLLBACK
ticks join PREFILL/DECODE on the ``serving`` row (docs/timeline.md).

``kv_dtype`` selects the pool storage format at CONSTRUCTION time
(fp32/bf16 raw pages, or int8_block/int4 payloads + bf16 scale planes —
serving/kv_cache.py): it is a trace-time constant baked into both
executables, so quantization adds zero retraces and the two-executable
contract holds across every kv_dtype × prefix-sharing composition.

Batch slots are PADDED to ``max_batch``: admitting, finishing, or
preempting requests changes mask/table/length ARRAYS, never shapes, so
the hot loop compiles once no matter how the in-flight composition
churns (tests/test_serving.py pins the trace count).

The scheduler (serving/scheduler.py) owns admission/fairness/prefix
matching; the block pool (serving/kv_cache.py) owns memory. Timeline:
PREFILL/DECODE spans and ADMIT/EVICT ticks on a ``serving`` row
(docs/timeline.md).

Prefill/decode pool split: pass ``prefill_group=``/``decode_group=``
(subset-group indices from ``hvd.init([[...], [...]])``) and the two
executables are placed on the lead devices of the respective groups —
the fork's overlapping-group machinery (README.md:10) applied to the
serving regime: prefill's compute-bound burst and decode's
bandwidth-bound steady state stop contending for one chip, at the cost
of shipping the written KV across (the disaggregated-serving trade).
"""

from __future__ import annotations

import os
import time
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp

from horovod_tpu.analysis import protocol as _proto
from horovod_tpu.core import resilience as _res
from horovod_tpu.core.state import HorovodError
from horovod_tpu.core import timeline as _timeline
from horovod_tpu.models import transformer
from horovod_tpu.serving import kv_cache as _kv
from horovod_tpu.serving import resilience as _serve_res
from horovod_tpu.serving.resilience import (RequestJournal, Watchdog,
                                            now_ms as _now_ms_clock)
from horovod_tpu.serving.scheduler import (AdmissionError, PrefixIndex,
                                           Request, RequestState, Scheduler)
from horovod_tpu.utils import env as _env


class Engine:
    """Continuous-batching LM serving engine.

    ``config``/``params``: the trained transformer (the parameter tree
    restores from training checkpoints unchanged). ``block_size`` /
    ``max_batch`` / ``kv_dtype`` / ``prefix_cache`` default from
    ``HOROVOD_SERVE_BLOCK_SIZE`` / ``HOROVOD_SERVE_MAX_BATCH`` /
    ``HOROVOD_SERVE_KV_DTYPE`` / ``HOROVOD_SERVE_PREFIX_CACHE`` (typos
    raise — utils/env.py). ``num_blocks`` sizes the shared pool; the
    default backs every slot's worst case (no scarcity); alternatively
    ``pool_bytes`` sizes it by HBM budget (scale planes included), the
    honest equal-bytes comparison across kv_dtypes. ``max_prompt_len``
    fixes the prefill scan's compiled length (longer prompts are
    rejected at submit). ``temperature=0`` is greedy — bit-identical to
    ``transformer.generate`` at fp32/bf16 KV; otherwise per-request
    deterministic sampling keyed by (seed, request, position), stable
    across preemption/recompute.

    ``speculate=k`` (default ``HOROVOD_SERVE_SPECULATE``, 0 = off)
    enables draft-and-verify speculative decoding: ``draft_config`` /
    ``draft_params`` name the draft model (same vocab; omit both for
    self-speculation — the target drafts for itself, which prices pure
    dispatch amortization) and ``draft_kv_dtype`` its pool format
    (default ``HOROVOD_SERVE_DRAFT_KV_DTYPE``, unset = ``int4``). The
    accept/reject rule keeps output bit-identical to the
    non-speculative engine at every temperature (module docstring).

    Resilience (serving/resilience.py): ``deadline_ms`` is the default
    per-request latency budget (``HOROVOD_SERVE_DEADLINE_MS``; per-call
    ``submit(deadline_ms=)`` overrides it; expired requests are evicted
    at step boundaries and infeasible admissions refused up front);
    ``journal`` names a crash-safe request journal
    (``HOROVOD_SERVE_JOURNAL``, a ``*.journal.json`` path) replayed by
    :meth:`recover`; ``watchdog_timeout`` (seconds,
    ``HOROVOD_SERVE_WATCHDOG_TIMEOUT``, 0 = off) arms a heartbeat
    watchdog that raises :class:`~horovod_tpu.serving.resilience.\
EngineStalled` instead of hanging; ``min_accept``
    (``HOROVOD_SERVE_MIN_ACCEPT``, 0 = off) auto-disables speculation
    when the windowed accept rate collapses below it (emitted tokens
    stay bit-identical — speculation is lossless either way).
    """

    def __init__(self, config, params, *,
                 block_size: int | None = None,
                 max_batch: int | None = None,
                 num_blocks: int | None = None,
                 pool_bytes: int | None = None,
                 kv_dtype: str | None = None,
                 prefix_cache: bool | None = None,
                 max_prompt_len: int | None = None,
                 max_queue: int = 1024,
                 temperature: float = 0.0,
                 seed: int = 0,
                 eos_id: int | None = None,
                 prefill_group: int | None = None,
                 decode_group: int | None = None,
                 speculate: int | None = None,
                 draft_config=None,
                 draft_params=None,
                 draft_kv_dtype: str | None = None,
                 deadline_ms: float | None = None,
                 journal: str | None = None,
                 watchdog_timeout: float | None = None,
                 min_accept: float | None = None):
        self.config = config
        if kv_dtype is None:
            kv_dtype = _env.serve_kv_dtype()
        self.kv_dtype = _kv.resolve_kv_dtype(kv_dtype, config.dtype)
        self._cfg = transformer.decode_config(config)._replace(
            kv_dtype=self.kv_dtype)
        self.block_size = (block_size if block_size is not None
                           else _env.serve_block_size())
        self.max_batch = (max_batch if max_batch is not None
                          else _env.serve_max_batch())
        if self.block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {self.block_size}")
        if self.max_batch < 1:
            raise ValueError(
                f"max_batch must be >= 1, got {self.max_batch}")
        self.blocks_per_seq = -(-self._cfg.max_seq_len // self.block_size)
        self.view_len = self.blocks_per_seq * self.block_size
        if pool_bytes is not None:
            if num_blocks is not None:
                raise ValueError(
                    "pass num_blocks or pool_bytes, not both — they are "
                    "two ways of sizing the same pool")
            num_blocks = _kv.num_blocks_for_bytes(
                self._cfg, self.block_size, self.kv_dtype, pool_bytes)
        elif num_blocks is None:
            # No-scarcity default: every slot can hold a max-length
            # sequence. Size it DOWN to overcommit — that is the paged
            # cache's point — and admission control + preemption keep
            # the overcommitted pool correct.
            num_blocks = self.max_batch * self.blocks_per_seq + 1
        self.pool = _kv.BlockPool(num_blocks, self.block_size)

        # Speculative decoding: resolve k and the draft model BEFORE
        # the scheduler, whose admission headroom depends on k.
        if speculate is None:
            # env > tuned > default (tune/apply.py): override() is None
            # unless a TunedConfig is active AND the env doesn't set
            # the knob, so falling through to the env getter covers
            # both the explicit-env and the default (0 = off) cases.
            from horovod_tpu.tune import apply as _tune_apply

            tuned = _tune_apply.override("HOROVOD_SERVE_SPECULATE")
            speculate = (int(tuned) if tuned is not None
                         else _env.serve_speculate())
        self.speculate_k = int(speculate)
        if self.speculate_k < 0:
            raise ValueError(
                f"speculate must be >= 0 (0 disables speculation), got "
                f"{speculate}")
        if self.speculate_k == 0 and (draft_config is not None
                                      or draft_params is not None):
            raise ValueError(
                "draft_config/draft_params were passed but speculate=0 — "
                "set speculate=k (or HOROVOD_SERVE_SPECULATE) to enable "
                "speculative decoding; a silently ignored draft model "
                "would serve without the speedup it was configured for")
        self.draft_kv_dtype = None
        self._draft_cfg = None
        if self.speculate_k:
            if (draft_config is None) != (draft_params is None):
                raise ValueError(
                    "draft_config and draft_params must be passed "
                    "together (a config without weights, or weights "
                    "without their shape story, cannot draft)")
            if draft_config is None:
                # Self-speculation: the target drafts for itself —
                # accept rate 1.0 by construction at matching pool
                # formats, pricing pure per-call dispatch amortization.
                draft_config, draft_params = config, params
            if draft_config.vocab_size != config.vocab_size:
                raise ValueError(
                    f"draft vocab_size ({draft_config.vocab_size}) must "
                    f"match the target's ({config.vocab_size}) — "
                    f"proposals are target token ids")
            if draft_kv_dtype is None:
                draft_kv_dtype = _env.serve_draft_kv_dtype()
            if draft_kv_dtype is None:
                draft_kv_dtype = "int4"
            self.draft_kv_dtype = _kv.resolve_kv_dtype(
                draft_kv_dtype, draft_config.dtype)
            # The draft serves the target's positions and block tables:
            # align its sequence capacity with the target's.
            self._draft_cfg = transformer.decode_config(
                draft_config)._replace(kv_dtype=self.draft_kv_dtype,
                                       max_seq_len=self._cfg.max_seq_len)

        if prefix_cache is None:
            prefix_cache = _env.serve_prefix_cache()
        self.prefix_index = PrefixIndex(self.pool) if prefix_cache else None
        self.scheduler = Scheduler(
            self.pool, self.max_batch, max_queue,
            prefix_index=self.prefix_index,
            headroom_tokens=(self.speculate_k + 1 if self.speculate_k
                             else 0),
            seq_cap=self._cfg.max_seq_len,
            prefill_rate=self._measured_prefill_rate)
        self.max_prompt_len = (max_prompt_len if max_prompt_len is not None
                               else self._cfg.max_seq_len)
        if not 1 <= self.max_prompt_len <= self._cfg.max_seq_len:
            raise ValueError(
                f"max_prompt_len must be in [1, max_seq_len="
                f"{self._cfg.max_seq_len}], got {self.max_prompt_len}")
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.eos_id = eos_id

        self._prefill_device, self._decode_device = self._resolve_groups(
            prefill_group, decode_group)

        # Device state: the paged pool tuple — (k, v) raw pages, or
        # (k, v, k_scale, v_scale) for the quantized formats — plus
        # per-device param copies when the prefill/decode split is on.
        pools = _kv.make_kv_pools(self._cfg, num_blocks, self.block_size,
                                  self.kv_dtype)
        if self._decode_device is not None:
            pools = jax.device_put(pools, self._decode_device)
            self._params_decode = jax.device_put(params, self._decode_device)
            self._params_prefill = jax.device_put(params,
                                                  self._prefill_device)
        else:
            self._params_decode = self._params_prefill = params
        self._pools = tuple(pools)
        self._draft_pools = None
        self._params_draft = None
        if self.speculate_k:
            # The draft pool mirrors the target's allocator: same block
            # ids, same tables, its own (usually int4) page arrays — one
            # allocation/truncation decision governs both pools.
            dpools = _kv.make_kv_pools(self._draft_cfg, num_blocks,
                                       self.block_size,
                                       self.draft_kv_dtype)
            if self._decode_device is not None:
                dpools = jax.device_put(dpools, self._decode_device)
                self._params_draft = jax.device_put(draft_params,
                                                    self._decode_device)
            else:
                self._params_draft = draft_params
            self._draft_pools = tuple(dpools)

        # Host state: fixed-shape numpy mirrors of the batch slots.
        mb = self.max_batch
        self._slots: list[Request | None] = [None] * mb
        self._tables = np.zeros((mb, self.blocks_per_seq), np.int32)
        self._lengths = np.zeros((mb,), np.int32)
        self._plens = np.zeros((mb,), np.int32)
        self._skips = np.zeros((mb,), np.int32)
        self._prompts = np.zeros((mb, self.max_prompt_len), np.int32)
        self._last_tok = np.zeros((mb,), np.int32)
        # Token at cache position L-1 — the draft's catch-up input (its
        # pool runs one write behind the target's after a full accept).
        self._prev_tok = np.zeros((mb,), np.int32)
        self._seeds = np.zeros((mb,), np.int32)

        self._next_id = 0
        self._decode_traces = 0
        self._prefill_traces = 0
        self._verify_traces = 0
        self._draft_traces = 0
        self._draft_prefill_traces = 0
        self.stats = {"steps": 0, "prefill_calls": 0, "decode_calls": 0,
                      "tokens_generated": 0, "preemptions": 0,
                      "finished": 0, "rejected": 0,
                      "prefill_tokens": 0, "prefix_hit_tokens": 0,
                      "prefill_steps": 0,
                      "draft_calls": 0, "verify_calls": 0,
                      "spec_proposed": 0, "spec_accepted": 0,
                      "spec_rollback_tokens": 0, "draft_time_s": 0.0,
                      "deadline_missed": 0, "shed_rejected": 0,
                      "recovered": 0}

        # -- resilience state (serving/resilience.py) ------------------
        self.default_deadline_ms = (float(deadline_ms)
                                    if deadline_ms is not None
                                    else _env.serve_deadline_ms())
        if (self.default_deadline_ms is not None
                and not self.default_deadline_ms > 0):
            raise ValueError(
                f"deadline_ms must be > 0, got {self.default_deadline_ms}")
        self.watchdog = Watchdog(
            watchdog_timeout if watchdog_timeout is not None
            else _env.serve_watchdog_timeout())
        self.min_accept = (float(min_accept) if min_accept is not None
                           else _env.serve_min_accept())
        if not 0.0 <= self.min_accept <= 1.0:
            raise ValueError(
                f"min_accept must be in [0, 1], got {self.min_accept}")
        self._spec_disabled = False     # accept-rate collapse latch
        self._accept_window: deque[float] = deque(maxlen=32)
        self._shedding = False          # pool-pressure load-shed latch
        self._pressure_window: deque[int] = deque(maxlen=16)
        self._prefill_time_s = 0.0      # wall inside _call_prefill
        self._now_ms = _now_ms_clock()  # step-boundary deadline clock
        journal_path = (journal if journal is not None
                        else _env.serve_journal_path())
        self.journal = (RequestJournal(journal_path, self.fingerprint())
                        if journal_path else None)
        self._build_fns()

    # ------------------------------------------------------------------
    # jitted executables
    # ------------------------------------------------------------------

    def _resolve_groups(self, prefill_group, decode_group):
        if prefill_group is None and decode_group is None:
            return None, None
        if prefill_group is None or decode_group is None:
            raise ValueError(
                "prefill_group and decode_group must be set together "
                "(the split maps the two phases onto two subset groups).")
        from horovod_tpu.core import state as _state

        pg = _state.get_group(prefill_group)
        dg = _state.get_group(decode_group)
        return pg.devices[0], dg.devices[0]

    def _build_fns(self):
        cfg = self._cfg
        model = transformer.Transformer(cfg)
        nl, bs, lv = cfg.num_layers, self.block_size, self.view_len
        mb, pmax, vocab = self.max_batch, self.max_prompt_len, cfg.vocab_size
        temp = self.temperature
        base_key = self.seed
        # kv_dtype is a pool-construction-time CONSTANT closed over by
        # both executables — no retrace across any composition.
        quant = _kv.kv_quantized(self.kv_dtype)
        fresh_names = (("k", "v", "k_scale", "v_scale") if quant
                       else ("k", "v"))

        def make_forward(fmodel, fnl, fnames):
            def forward(params, pools, tables, lengths, toks, active):
                """One token for every slot: gather views → model decode
                path → scatter fresh K/V (inactive rows land in the null
                block). ``pools`` is the (k, v[, k_scale, v_scale])
                tuple; scale planes gather/scatter alongside their
                payloads."""
                b = tables.shape[0]
                views = [p[:, tables].reshape(fnl, b, lv, *p.shape[3:])
                         for p in pools]
                kv_views = [tuple(v[l] for v in views)
                            for l in range(fnl)]
                logits, mut = fmodel.apply(
                    {"params": params}, toks[:, None],
                    positions=lengths[:, None], kv_views=kv_views,
                    mutable=["paged_kv"])
                fresh = mut["paged_kv"]
                stacks = [jnp.stack([fresh[f"block_{l}"]["attn"][name][0]
                                     for l in range(fnl)])
                          for name in fnames]
                # Clamp the table-column gather: masked rows inside a
                # speculative window may index past the last column
                # (their write is redirected to the null block below).
                col = jnp.minimum(lengths // bs, tables.shape[1] - 1)
                bi = tables[jnp.arange(b), col]
                bi = jnp.where(active, bi, _kv.NULL_BLOCK)
                off = lengths % bs
                pools = tuple(p.at[:, bi, off].set(s)
                              for p, s in zip(pools, stacks))
                return logits[:, 0], pools
            return forward

        forward = make_forward(model, nl, fresh_names)

        def sample(logits, positions, seeds):
            """Next token from (B, V) logits. Greedy at temperature 0;
            otherwise categorical keyed by (engine seed, request seed,
            position) — deterministic, batch-composition-independent,
            and recompute-stable across preemption."""
            if temp == 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            key = jax.random.PRNGKey(base_key)
            keys = jax.vmap(
                lambda s, p: jax.random.fold_in(jax.random.fold_in(key, s),
                                                p))(seeds, positions)
            return jax.vmap(
                lambda k, lg: jax.random.categorical(k, lg / temp))(
                    keys, logits).astype(jnp.int32)

        def decode_fn(params, pools, tables, lengths, toks, active, seeds):
            self._decode_traces += 1  # trace-time side effect: the
            # no-retrace tests count compilations, not guesses.
            logits, pools = forward(params, pools, tables, lengths,
                                    toks, active)
            nxt = sample(logits, lengths, seeds)
            return pools, nxt

        def prefill_fn(params, pools, tables, prompts, plens, skips,
                       admit, seeds):
            self._prefill_traces += 1
            # Dynamic iteration window [t0, t1): start at the earliest
            # position any admitted row actually needs — its shared-
            # prefix span ends at ``skips`` (those pages are already in
            # the pool via the prefix index), but never past plen-1 (the
            # last prompt position must run to produce the first-token
            # logits even when its write is skipped) — and stop after
            # the longest admitted prompt. A while_loop's trip count is
            # data, not shape, so prefix hits (and short prompts) save
            # REAL prefill iterations inside the one compiled
            # executable; a fully-shared admission costs one step.
            big = jnp.int32(pmax)
            t0 = jnp.min(jnp.where(admit, jnp.minimum(skips, plens - 1),
                                   big))
            t1 = jnp.max(jnp.where(admit, plens, 0))
            t0 = jnp.minimum(t0, t1)

            def cond(carry):
                return carry[0] < t1

            def body(carry):
                t, pools, last = carry
                toks = prompts[:, t]
                # Shared-prefix positions (t < skips) are NOT written:
                # rows whose span is inside the batch window ride it
                # with their pool writes redirected to the null block.
                active = admit & (t >= skips) & (t < plens)
                logits, pools = forward(
                    params, pools, tables,
                    jnp.full((mb,), t, jnp.int32), toks, active)
                last = jnp.where(((t == plens - 1) & admit)[:, None],
                                 logits, last)
                return (t + 1, pools, last)

            init = (t0, pools, jnp.zeros((mb, vocab), jnp.float32))
            _, pools, last = jax.lax.while_loop(cond, body, init)
            first = sample(last, plens - 1, seeds)
            return pools, first, t1 - t0

        # Pools are donated so XLA updates the cache in place instead of
        # double-buffering it every token — on every backend, so the
        # tests run the same aliasing the chip does.
        donate = (1,)
        self._decode = jax.jit(decode_fn, donate_argnums=donate)
        self._prefill = jax.jit(prefill_fn, donate_argnums=donate)

        if not self.speculate_k:
            return
        spec_k = self.speculate_k
        dcfg = self._draft_cfg
        dmodel = transformer.Transformer(dcfg)
        dquant = _kv.kv_quantized(self.draft_kv_dtype)
        draft_forward = make_forward(
            dmodel, dcfg.num_layers,
            ("k", "v", "k_scale", "v_scale") if dquant else ("k", "v"))

        def verify_fn(params, pools, tables, lengths, toks, active,
                      seeds, horizon):
            """ONE wide fixed-shape target call scoring all k+1
            positions of every slot: the whole ``toks`` (B, k+1) window
            — the carried last token then the k draft proposals — runs
            through the shared paged attend as a single (B, W) forward,
            so the weights are read once per step instead of once per
            position (the compute amortization speculation's speedup
            comes from). Every window position's fresh K/V lands in the
            attend view before the one attend; the causal visibility
            mask keeps each query blind to the positions after it, so
            the logits are bit-identical to k+1 sequential one-token
            steps. Row writes past a slot's per-row ``horizon``
            (sequence-capacity guard) are masked to the null block on
            the pool scatter. Returns the target's deterministic choice
            at each position — the host accepts the longest proposal
            prefix that matches them."""
            self._verify_traces += 1
            b = tables.shape[0]
            iidx = jnp.arange(spec_k + 1, dtype=jnp.int32)
            posw = lengths[:, None] + iidx[None, :]          # (B, W)
            views = [p[:, tables].reshape(nl, b, lv, *p.shape[3:])
                     for p in pools]
            kv_views = [tuple(v[l] for v in views) for l in range(nl)]
            logits, mut = model.apply(
                {"params": params}, toks, positions=posw,
                kv_views=kv_views, mutable=["paged_kv"])
            fresh = mut["paged_kv"]
            stacks = [jnp.stack([fresh[f"block_{l}"]["attn"][name][0]
                                 for l in range(nl)])
                      for name in fresh_names]           # (nl, B, W, ..)
            actw = active[:, None] & (iidx[None, :] <= horizon[:, None])
            col = jnp.minimum(posw // bs, tables.shape[1] - 1)
            bi = jnp.take_along_axis(tables, col, axis=1)    # (B, W)
            bi = jnp.where(actw, bi, _kv.NULL_BLOCK)
            off = posw % bs
            pools = tuple(p.at[:, bi, off].set(s)
                          for p, s in zip(pools, stacks))
            choices = jax.vmap(lambda lg, p_: sample(lg, p_, seeds),
                               in_axes=(1, 1), out_axes=0)(logits, posw)
            return pools, choices

        dnl = dcfg.num_layers
        dnames = (("k", "v", "k_scale", "v_scale") if dquant
                  else ("k", "v"))

        def draft_propose_fn(params, pools, tables, lengths, prev, last,
                             active, seeds, horizon):
            """ONE fixed-shape draft call proposing k tokens per slot
            autoregressively (a fixed-k+1 ``lax.scan``). The paged view
            is gathered from the draft pool ONCE and carried through
            the scan — each iteration writes its fresh K/V into the
            carried view (an in-place loop-carry update, not a
            whole-pool re-gather) and all k+1 fresh entries scatter
            back to the pool in one vectorized write after the scan.
            Iteration 0 re-ingests the token at position L-1
            (``prev``): after a full-accept step the draft cache is one
            position short of the target's (the verify writes k+1
            entries, the draft k), so the catch-up write closes the gap
            — and when the position is already cached it rewrites the
            identical, deterministically quantized bits (a no-op).
            Proposals use the SAME (seed, request, position)-keyed
            sampler as the target, so a draft that agrees with the
            target proposes exactly the target's choices — accept rate
            1.0 under self-speculation at any temperature."""
            self._draft_traces += 1
            b = tables.shape[0]
            bidx = jnp.arange(b)
            views = [p[:, tables].reshape(dnl, b, lv, *p.shape[3:])
                     for p in pools]

            def body(carry, i):
                views, tok = carry
                pos = lengths + i - 1
                kv_views = [tuple(v[l] for v in views)
                            for l in range(dnl)]
                logits, mut = dmodel.apply(
                    {"params": params}, tok[:, None],
                    positions=pos[:, None], kv_views=kv_views,
                    mutable=["paged_kv"])
                fresh = mut["paged_kv"]
                stacks = tuple(
                    jnp.stack([fresh[f"block_{l}"]["attn"][nm][0]
                               for l in range(dnl)])
                    for nm in dnames)            # each (dnl, b, ...)
                # Mirror the model's internal view write into the
                # carried view so the NEXT iteration attends over it.
                # Out-of-window iterations (inactive row, or past the
                # row's horizon) may land on a clipped position — their
                # logits are never consumed and their pool write is
                # masked below, so the local corruption is unreadable.
                vpos = jnp.clip(pos, 0, lv - 1)
                views = [v.at[:, bidx, vpos].set(s)
                         for v, s in zip(views, stacks)]
                # A proposal from position p estimates the target's
                # choice AT p — key it identically.
                nxt = sample(logits[:, 0], pos, seeds)
                nxt_in = jnp.where(i == 0, last, nxt)
                return (views, nxt_in), (nxt, stacks)

            (_, _), (raw, ys) = jax.lax.scan(
                body, (views, prev), jnp.arange(spec_k + 1))
            # One vectorized pool scatter for the whole window.
            iidx = jnp.arange(spec_k + 1, dtype=jnp.int32)
            posw = lengths[:, None] + iidx[None, :] - 1      # (B, W)
            actw = active[:, None] & (iidx[None, :]
                                      <= horizon[:, None] + 1)
            col = jnp.clip(posw // bs, 0, tables.shape[1] - 1)
            bi = jnp.take_along_axis(tables, col, axis=1)
            bi = jnp.where(actw, bi, _kv.NULL_BLOCK)
            off = posw % bs
            pools = tuple(p.at[:, bi, off].set(jnp.moveaxis(y, 0, 2))
                          for p, y in zip(pools, ys))
            return pools, raw[1:]  # iteration 0 is the catch-up write

        def draft_prefill_fn(params, pools, tables, prompts, plens,
                             skips, admit):
            """The draft model's prompt ingestion — the same dynamic
            [t0, t1) window as the target prefill (shared-span writes
            skipped: the draft pages of a shared block were written by
            the admission that first prefilled it)."""
            self._draft_prefill_traces += 1
            big = jnp.int32(pmax)
            t0 = jnp.min(jnp.where(admit, jnp.minimum(skips, plens - 1),
                                   big))
            t1 = jnp.max(jnp.where(admit, plens, 0))
            t0 = jnp.minimum(t0, t1)

            def cond(carry):
                return carry[0] < t1

            def body(carry):
                t, pools = carry
                toks = prompts[:, t]
                active = admit & (t >= skips) & (t < plens)
                _, pools = draft_forward(
                    params, pools, tables,
                    jnp.full((mb,), t, jnp.int32), toks, active)
                return (t + 1, pools)

            _, pools = jax.lax.while_loop(cond, body, (t0, pools))
            return pools

        self._verify = jax.jit(verify_fn, donate_argnums=donate)
        self._draft_propose = jax.jit(draft_propose_fn,
                                      donate_argnums=donate)
        self._draft_prefill = jax.jit(draft_prefill_fn,
                                      donate_argnums=donate)

    # ------------------------------------------------------------------
    # request lifecycle
    # ------------------------------------------------------------------

    def fingerprint(self) -> dict:
        """The engine identity a journal is only replayable against:
        any of these fields changing would make 'recompute the same
        tokens' a lie (serving/resilience.py FINGERPRINT_FIELDS)."""
        return {"block_size": self.block_size,
                "kv_dtype": self.kv_dtype,
                "temperature": self.temperature,
                "seed": self.seed,
                "speculate_k": self.speculate_k}

    def _measured_prefill_rate(self) -> float:
        """Measured prefill throughput (tokens/ms) for the scheduler's
        deadline-feasibility gate. 0.0 before any prefill ran — no
        evidence, no refusal (analysis/protocol.py
        ``admission_feasible``)."""
        if self._prefill_time_s <= 0.0:
            return 0.0
        return self.stats["prefill_tokens"] / (self._prefill_time_s * 1e3)

    def submit(self, prompt, max_new_tokens: int, *, tenant: str = "default",
               sample_seed: int | None = None,
               deadline_ms: float | None = None) -> Request:
        """Queue a generation request. Raises :class:`AdmissionError`
        when the bounded queue is full, the engine is shedding load
        under sustained pool pressure, or the request can never be
        served (capacity validation up front — a doomed request must
        not deadlock the queue). ``deadline_ms`` is a relative latency
        budget in milliseconds (default: the engine's
        ``default_deadline_ms``; pass 0/negative to opt a request out
        of any default): past it the request is evicted at the next
        step boundary with whatever it produced."""
        if self._shedding:
            self.stats["shed_rejected"] += 1
            self.stats["rejected"] += 1
            raise AdmissionError(
                "engine is shedding load: sustained pool pressure has "
                "been preempting live work every step — retry later, or "
                "grow num_blocks/pool_bytes (docs/troubleshooting.md)")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = prompt.shape[0]
        if plen < 1:
            raise ValueError("prompt must carry at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if plen > self.max_prompt_len:
            self._reject(
                f"prompt ({plen} tokens) exceeds max_prompt_len="
                f"{self.max_prompt_len} — raise it (engine rebuild) or "
                f"truncate the prompt")
        total = plen + max_new_tokens
        if total > self._cfg.max_seq_len:
            self._reject(
                f"prompt ({plen}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_seq_len ({self._cfg.max_seq_len}) — the KV "
                f"capacity bound transformer.generate enforces too")
        need_blocks = self.pool.blocks_for(total)
        if self.speculate_k:
            # Speculative headroom: an admission (including a preempted
            # re-admission whose prompt grew by its generated prefix)
            # must back up to k+1 write positions past its prompt.
            need_blocks = self.pool.blocks_for(
                min(total + self.speculate_k + 1, self._cfg.max_seq_len))
        if need_blocks > self.pool.capacity:
            self._reject(
                f"request needs {need_blocks} blocks but "
                f"the pool holds {self.pool.capacity}: it can NEVER be "
                f"admitted — grow num_blocks or shrink the request")
        budget = (float(deadline_ms) if deadline_ms is not None
                  else self.default_deadline_ms)
        if budget is not None and budget <= 0:
            budget = None  # explicit opt-out of the engine default
        now = _now_ms_clock()
        req = Request(
            request_id=self._next_id, tenant=tenant, prompt=prompt,
            max_new_tokens=int(max_new_tokens), orig_prompt=prompt.copy(),
            sample_seed=(self._next_id if sample_seed is None
                         else int(sample_seed)),
            deadline_ms=(now + budget if budget is not None else None),
            budget_ms=budget)
        self._next_id += 1
        try:
            self.scheduler.submit(req)
        except AdmissionError:
            self.stats["rejected"] += 1
            raise
        if self.journal is not None:
            # Admissions are flushed IMMEDIATELY (one fsync per submit):
            # an admitted-then-crashed request must replay, so its
            # journal record cannot wait for the next step boundary.
            self.journal.record_admit(
                req.request_id, prompt, tenant=tenant,
                seed=req.sample_seed, max_new=int(max_new_tokens),
                deadline_ms=req.deadline_ms, budget_ms=budget, t=now)
            self.journal.flush(t=now)
        return req

    def _reject(self, msg: str) -> None:
        """Every rejection path — submit-time validation AND queue-full —
        counts into stats['rejected'], so the engine's own accounting
        matches what an external load driver observes."""
        self.stats["rejected"] += 1
        raise AdmissionError(msg)

    # -- internal slot bookkeeping ----------------------------------------

    def _active_slots(self) -> list[int]:
        return [i for i, r in enumerate(self._slots) if r is not None]

    def _install(self, req: Request, slot: int) -> None:
        req.slot = slot
        self._slots[slot] = req
        self._tables[slot] = _kv.padded_table(req.blocks,
                                              self.blocks_per_seq)
        self._lengths[slot] = 0
        self._plens[slot] = req.prompt_len
        self._skips[slot] = req.skip_tokens
        self._prompts[slot] = 0
        self._prompts[slot, :req.prompt_len] = req.prompt
        self._seeds[slot] = req.sample_seed
        self._last_tok[slot] = 0
        self._prev_tok[slot] = int(req.prompt[req.prompt_len - 1])

    def _clear_slot(self, slot: int) -> None:
        self._slots[slot] = None
        self._tables[slot] = _kv.NULL_BLOCK
        self._lengths[slot] = 0
        self._plens[slot] = 0
        self._skips[slot] = 0

    def _finish(self, req: Request, tl) -> None:
        req.state = RequestState.FINISHED
        req.finished_at = time.monotonic()
        self.scheduler.release(req)
        self._clear_slot(req.slot)
        req.slot = None
        self.stats["finished"] += 1
        if self.journal is not None:
            self.journal.record_finish(req.request_id, len(req.output),
                                       t=self._now_ms)
        tl.event("serving", "EVICT", "X")

    def _record_token(self, req: Request, token: int, tl) -> bool:
        """Append a generated token; True when the request just
        finished (max_new reached or EOS sampled)."""
        if self.journal is not None:
            # Buffered (coalesced into one emit run per request per
            # step, flushed once at the step boundary); the index is
            # recorded BEFORE append so monotonicity is structural.
            self.journal.record_emit(req.request_id, len(req.output),
                                     int(token))
        req.output.append(int(token))
        self._last_tok[req.slot] = token
        self.stats["tokens_generated"] += 1
        done = (len(req.output) >= req.max_new_tokens
                or (self.eos_id is not None and int(token) == self.eos_id))
        if done:
            self._finish(req, tl)
        return done

    def _preempt(self, victim: Request, tl) -> None:
        """Recompute-preemption: release the victim's blocks and requeue
        it front-of-line with prompt := prompt + generated-so-far, so
        re-admission rebuilds its KV (identical values per kv_dtype —
        same positions, same params, deterministic quantization) and
        the continuation picks up exactly where it stopped. Pages the
        prefix index holds survive the release, so the re-admission
        often maps its own old prefix straight back in."""
        self.scheduler.release(victim)
        self._clear_slot(victim.slot)
        victim.prompt = np.concatenate(
            [victim.orig_prompt, np.asarray(victim.output, np.int32)])
        self.scheduler.requeue_front(victim)
        self.stats["preemptions"] += 1
        tl.event("serving", "EVICT", "X")

    def _ensure_block(self, req: Request, tl, horizon: int = 0) -> bool:
        """Guarantee the blocks backing cache positions
        ``lengths[slot] .. lengths[slot] + horizon`` exist before the
        step's writes (``horizon=0`` is the plain one-token decode;
        a speculative step writes up to k+1 positions). May evict
        index-only cached pages, then preempt newest-admitted requests
        (recompute policy); returns False when ``req`` itself was
        preempted and must skip this step."""
        slot = req.slot
        need = min(self.pool.blocks_for(
            int(self._lengths[slot]) + 1 + horizon), self.blocks_per_seq)
        while len(req.blocks) < need:
            got = self.pool.alloc(1)
            if got is None and self.prefix_index is not None:
                # Cached prefix pages nobody references are the cheapest
                # memory to reclaim — before preempting live work.
                if self.prefix_index.evict(1):
                    got = self.pool.alloc(1)
            if got is not None:
                req.blocks.extend(got)
                self._tables[slot] = _kv.padded_table(req.blocks,
                                                      self.blocks_per_seq)
                continue
            # Preempt the newest admission whose resumed prompt
            # (original + generated so far) still fits the prefill
            # buffer — it has the least sunk work and CAN be recomputed.
            victims = [r for r in self._slots
                       if r is not None
                       and len(r.orig_prompt) + len(r.output)
                       <= self.max_prompt_len]
            if not victims:
                raise HorovodError(
                    "block pool exhausted and no running request is "
                    "preemptable (resumed prompts would exceed "
                    "max_prompt_len) — grow num_blocks or max_prompt_len")
            victim = max(victims, key=lambda r: r.admitted_seq)
            self._preempt(victim, tl)
            if victim is req:
                return False
        return True

    # ------------------------------------------------------------------
    # resilience: fault injection, deadlines, degradation
    # ------------------------------------------------------------------

    def _maybe_serve_faults(self, step_idx: int, tl) -> None:
        """Serving fault injection (``HOROVOD_FAULT_INJECT`` grammar,
        core/resilience.py): ``engine_crash@step`` exits hard (exit 43;
        the journal is deliberately NOT flushed — the previous step
        boundary's fsync is the durability point the drill replays
        from); ``stuck_decode@step[,ms=M]`` backdates an open watchdog
        stamp and judges it — a deterministic stand-in for a dispatch
        that never returns, so the conviction is loud and immediate,
        never a real hang; ``deadline_storm@step`` force-expires every
        deadline-carrying request so the eviction path fires under
        load."""
        inj = _res.injector()
        f = inj.serve_fault_due("engine_crash", step_idx)
        if f is not None:
            print(f"HOROVOD_FAULT_INJECT: simulating engine crash at "
                  f"serving step {step_idx} ({f.describe()}); exiting "
                  f"{_res.CRASH_EXIT_CODE}.", flush=True)
            os._exit(_res.CRASH_EXIT_CODE)
        f = inj.serve_fault_due("stuck_decode", step_idx)
        if f is not None:
            timeout = (self.watchdog.timeout
                       if self.watchdog.timeout > 0 else 1.0)
            age = f.attrs.get("ms", int(timeout * 2000)) / 1000.0
            self.watchdog.stamp("DECODE", step_idx)
            self.watchdog.backdate(age)
            self.watchdog.check(timeout=timeout)
        f = inj.serve_fault_due("deadline_storm", step_idx)
        if f is not None:
            expired = self._now_ms - 1.0
            for req in self._slots:
                if req is not None and req.deadline_ms is not None:
                    req.deadline_ms = expired
            for req in self.scheduler.pending_requests():
                if req.deadline_ms is not None:
                    req.deadline_ms = expired

    def _evict_expired(self, tl) -> list[Request]:
        """Step-boundary deadline eviction for RUNNING requests: pages
        released, slot cleared, ``DEADLINE`` tick, journal evict
        record. The boundary is the only place eviction is safe (no
        mid-dispatch array mutation), which bounds enforcement
        granularity to one engine step."""
        evicted: list[Request] = []
        for slot in range(self.max_batch):
            req = self._slots[slot]
            if req is None or not _proto.deadline_expired(
                    self._now_ms, req.deadline_ms):
                continue
            req.deadline_missed = True
            req.state = RequestState.FINISHED
            req.finished_at = time.monotonic()
            self.scheduler.release(req)
            self._clear_slot(slot)
            req.slot = None
            self.stats["deadline_missed"] += 1
            if self.journal is not None:
                self.journal.record_evict(req.request_id, "deadline",
                                          t=self._now_ms)
            tl.event("serving", "DEADLINE", "X")
            evicted.append(req)
        return evicted

    def _drain_deadline_dropped(self, tl) -> list[Request]:
        """Queued requests the scheduler's admission gate refused for
        deadline reasons (expired, or prefill infeasible inside the
        remaining budget): account + journal them here so a refusal is
        exactly as observable as an eviction."""
        dropped = self.scheduler.deadline_dropped
        if not dropped:
            return []
        self.scheduler.deadline_dropped = []
        for req in dropped:
            self.stats["deadline_missed"] += 1
            if self.journal is not None:
                self.journal.record_evict(req.request_id, "deadline",
                                          t=self._now_ms)
            tl.event("serving", "DEADLINE", "X")
        return dropped

    def _update_shed_latch(self, preempted: int, tl) -> None:
        """Load shedding under sustained pool pressure: when recent
        steps keep preempting live work (the thrash regime where every
        admission only recomputes), ``submit`` starts refusing with a
        retryable error until a full pressure window passes clean."""
        self._pressure_window.append(preempted)
        if not self._shedding and _serve_res.pool_pressure_high(
                self._pressure_window):
            self._shedding = True
            tl.event("serving", "SHED", "X")
        elif self._shedding and sum(self._pressure_window) == 0:
            self._shedding = False

    # ------------------------------------------------------------------
    # the step loop
    # ------------------------------------------------------------------

    def step(self) -> list[Request]:
        """One continuous-batching step: admit+prefill new requests,
        decode one token for every running one. Returns the requests
        that FINISHED during this step — deadline-evicted ones included
        (they are done, just not complete: check
        ``Request.deadline_missed``)."""
        tl = _timeline.session()
        step_idx = self.stats["steps"]
        self._maybe_serve_faults(step_idx, tl)
        finished: list[Request] = []
        self.stats["steps"] += 1
        self._now_ms = _now_ms_clock()
        preempt_before = self.stats["preemptions"]

        # 0. Deadline pass at the step boundary: evict expired running
        #    requests (pages released) before admission spends pool
        #    blocks on newcomers.
        finished.extend(self._evict_expired(tl))

        # 1. Admission at the step boundary (Orca iteration-level
        #    scheduling): fill free slots from the tenant-fair queue.
        free = [i for i, r in enumerate(self._slots) if r is None]
        admitted = self.scheduler.admit(len(free), now_ms=self._now_ms)
        finished.extend(self._drain_deadline_dropped(tl))
        if admitted:
            admit_mask = np.zeros((self.max_batch,), np.bool_)
            for req in admitted:
                slot = free.pop(0)
                self._install(req, slot)
                admit_mask[slot] = True
                self.stats["prefill_tokens"] += (req.prompt_len
                                                 - req.skip_tokens)
                self.stats["prefix_hit_tokens"] += req.skip_tokens
                tl.event("serving", "ADMIT", "X")
            tl.start_activity("serving", "PREFILL")
            self.watchdog.stamp("PREFILL", step_idx)
            t0 = time.monotonic()
            pools, first, nsteps = self._call_prefill(admit_mask)
            self._pools = tuple(pools)
            if self.speculate_k and not self._spec_disabled:
                # The draft ingests the same prompts into its own pool
                # (same block ids) so proposals start from position 0
                # context. Rides the PREFILL span: it is prompt work.
                self._draft_pools = tuple(
                    self._call_draft_prefill(admit_mask))
            first = np.asarray(first)
            self._prefill_time_s += time.monotonic() - t0
            self.watchdog.clear()
            tl.end_activity("serving", "PREFILL")
            self.stats["prefill_calls"] += 1
            self.stats["prefill_steps"] += int(nsteps)
            for req in admitted:
                slot = req.slot
                self._lengths[slot] = req.prompt_len
                # The prompt's full blocks are now valid pool pages:
                # index them so identical future prefixes share.
                self.scheduler.note_prefilled(req)
                if self._record_token(req, int(first[slot]), tl):
                    finished.append(req)

        # 2. One decode token (or one draft-and-verify burst) for every
        #    running request. Block guarantees run first for ALL slots;
        #    preemption may clear slots mid-loop (including ones already
        #    visited), so the stepped set is whatever survives.
        if self._active_slots() and self.speculate_k:
            finished.extend(self._spec_decode_step(tl))
        elif self._active_slots():
            for slot in range(self.max_batch):
                req = self._slots[slot]
                if req is None:
                    continue  # free, or preempted by an earlier iteration
                self._ensure_block(req, tl)
            stepped = [r for r in self._slots if r is not None]
            if stepped:
                mask = np.zeros((self.max_batch,), np.bool_)
                for req in stepped:
                    mask[req.slot] = True
                tl.start_activity("serving", "DECODE")
                self.watchdog.stamp("DECODE", step_idx)
                pools, nxt = self._decode(
                    self._params_decode, self._pools, self._tables,
                    self._lengths, self._last_tok, mask, self._seeds)
                self._pools = tuple(pools)
                nxt = np.asarray(nxt)
                self.watchdog.clear()
                tl.end_activity("serving", "DECODE")
                self.stats["decode_calls"] += 1
                for req in stepped:
                    slot = req.slot
                    self._lengths[slot] += 1
                    if self._record_token(req, int(nxt[slot]), tl):
                        finished.append(req)

        # 3. Step-boundary bookkeeping: pressure window (load shed
        #    latch) and ONE journal flush — the step's durability point.
        self._update_shed_latch(
            self.stats["preemptions"] - preempt_before, tl)
        if self.journal is not None:
            self.journal.flush(t=self._now_ms)
        return finished

    def _spec_decode_step(self, tl) -> list[Request]:
        """One draft-and-verify burst for every running request: the
        draft proposes k tokens per slot (one compiled call), the target
        scores all k+1 positions (one compiled call), and the host
        accepts the longest proposal prefix matching the target's own
        choices — emitting 1..k+1 tokens per slot per step. Rejected
        tails roll back via refcounted page truncation."""
        k = self.speculate_k
        step_idx = self.stats["steps"] - 1
        finished: list[Request] = []
        hz = 0 if self._spec_disabled else k
        for slot in range(self.max_batch):
            req = self._slots[slot]
            if req is None:
                continue  # free, or preempted by an earlier iteration
            self._ensure_block(req, tl, horizon=hz)
        stepped = [r for r in self._slots if r is not None]
        if not stepped:
            return finished
        mask = np.zeros((self.max_batch,), np.bool_)
        horizon = np.zeros((self.max_batch,), np.int32)
        for req in stepped:
            mask[req.slot] = True
            # Per-row speculation window: never write past the model's
            # sequence capacity (writes beyond are masked on-device).
            remaining = self._cfg.max_seq_len - int(self._lengths[req.slot])
            horizon[req.slot] = min(hz, remaining - 1)

        if self._spec_disabled:
            # Degraded mode (accept-rate collapse): skip the draft call
            # and verify with horizon 0 — the verify executable scores
            # only the carried last token, whose choice is EXACTLY the
            # plain greedy/sampled decode (same positions, same keys),
            # so emitted tokens stay bit-identical with zero rollback.
            # Same fixed executables, so zero retraces either way.
            props = np.zeros((k, self.max_batch), np.int32)
            horizon[:] = 0
        else:
            t0 = time.monotonic()
            tl.start_activity("serving", "DRAFT")
            self.watchdog.stamp("DRAFT", step_idx)
            dpools, props = self._draft_propose(
                self._params_draft, self._draft_pools, self._tables,
                self._lengths, self._prev_tok, self._last_tok, mask,
                self._seeds, horizon)
            self._draft_pools = tuple(dpools)
            props = np.asarray(props)      # (k, B): props[i] = d_{i+1}
            self.watchdog.clear()
            tl.end_activity("serving", "DRAFT")
            self.stats["draft_time_s"] += time.monotonic() - t0
            self.stats["draft_calls"] += 1

        toks = np.zeros((self.max_batch, k + 1), np.int32)
        toks[:, 0] = self._last_tok
        toks[:, 1:] = props.T
        tl.start_activity("serving", "VERIFY")
        self.watchdog.stamp("VERIFY", step_idx)
        pools, choices = self._verify(
            self._params_decode, self._pools, self._tables,
            self._lengths, toks, mask, self._seeds, horizon)
        self._pools = tuple(pools)
        choices = np.asarray(choices)      # (k+1, B): choices[i] = c_i
        self.watchdog.clear()
        tl.end_activity("serving", "VERIFY")
        self.stats["verify_calls"] += 1

        rejected_total = 0
        proposed_step = accepted_step = 0
        for req in stepped:
            slot = req.slot
            h = int(horizon[slot])
            # Accept while the draft's proposal equals the target's own
            # choice: d_{i+1} == c_i. The emitted stream c_0..c_a is
            # then exactly the sequential target stream.
            a = 0
            while a < h and props[a, slot] == choices[a, slot]:
                a += 1
            self.stats["spec_proposed"] += h
            self.stats["spec_accepted"] += a
            proposed_step += h
            accepted_step += a
            done = False
            for i in range(a + 1):
                self._lengths[slot] += 1
                done = self._record_token(req, int(choices[i, slot]), tl)
                if done:
                    finished.append(req)
                    break
            rejected_total += h - a
            if done:
                continue  # _finish already released every block
            # New second-to-last sequence token (draft catch-up input).
            self._prev_tok[slot] = int(
                choices[a - 1, slot] if a >= 1 else toks[slot, 0])
            # Roll back the rejected tail: drop whole freed blocks;
            # stale entries inside kept blocks are overwritten before
            # any attend can see them (writes are sequential and the
            # visibility mask stops at the query position).
            new_len = int(self._lengths[slot])
            if len(req.blocks) > self.pool.blocks_for(new_len):
                _, cow = self.pool.truncate(req.blocks, new_len)
                if cow is not None:
                    raise HorovodError(
                        "speculative rollback forked a shared boundary "
                        "block — engine tail blocks are private by "
                        "construction; the allocator or the prefix "
                        "index violated that invariant")
                self._tables[slot] = _kv.padded_table(
                    req.blocks, self.blocks_per_seq)
        if rejected_total:
            self.stats["spec_rollback_tokens"] += rejected_total
            tl.event("serving", "ROLLBACK", "X")
        if proposed_step:
            # Accept-rate degradation latch: a windowed collapse below
            # min_accept means drafting burns more than it amortizes —
            # auto-disable speculation (DEGRADE tick) rather than keep
            # paying for rejected proposals. Lossless by construction,
            # so outputs do not change; only the speed story does.
            self._accept_window.append(accepted_step / proposed_step)
            if (not self._spec_disabled
                    and _proto.accept_rate_collapsed(self._accept_window,
                                                     self.min_accept)):
                self._spec_disabled = True
                tl.event("serving", "DEGRADE", "X")
        return finished

    def _call_draft_prefill(self, admit_mask: np.ndarray):
        """Run the draft prefill executable (decode-device resident —
        proposals are decode-phase work even under the phase split)."""
        args = (self._params_draft, self._draft_pools, self._tables,
                self._prompts, self._plens, self._skips, admit_mask)
        if self._decode_device is not None:
            args = tuple(jax.device_put(a, self._decode_device)
                         for a in args)
        return self._draft_prefill(*args)

    def _call_prefill(self, admit_mask: np.ndarray):
        """Run the prefill executable, shipping state to the prefill
        device and the written pools back when the phase split is on."""
        args = (self._params_prefill, self._pools, self._tables,
                self._prompts, self._plens, self._skips, admit_mask,
                self._seeds)
        if self._prefill_device is not None:
            args = tuple(jax.device_put(a, self._prefill_device)
                         for a in args)
        pools, first, nsteps = self._prefill(*args)
        if self._decode_device is not None:
            pools = jax.device_put(pools, self._decode_device)
        return pools, first, nsteps

    # ------------------------------------------------------------------
    # convenience drivers
    # ------------------------------------------------------------------

    def has_work(self) -> bool:
        return bool(self._active_slots()) or self.scheduler.has_pending()

    def run_until_idle(self, max_steps: int = 100_000) -> list[Request]:
        """Step until every submitted request finished; returns them in
        completion order."""
        done: list[Request] = []
        steps = 0
        while self.has_work():
            done.extend(self.step())
            steps += 1
            if steps > max_steps:
                raise HorovodError(
                    f"run_until_idle exceeded {max_steps} steps with work "
                    f"still pending — scheduling livelock? "
                    f"(stats: {self.stats})")
        return done

    def generate_batch(self, prompts, max_new_tokens: int,
                       tenant: str = "default") -> list[np.ndarray]:
        """Submit-and-drain convenience: returns each request's full
        sequence (prompt + generated) in SUBMIT order — the layout
        ``transformer.generate`` returns, for direct comparison."""
        reqs = [self.submit(p, max_new_tokens, tenant=tenant)
                for p in prompts]
        self.run_until_idle()
        return [r.full_sequence() for r in reqs]

    # ------------------------------------------------------------------
    # crash recovery
    # ------------------------------------------------------------------

    def recover(self, journal: str | None = None) -> list[Request]:
        """Replay a crash-safe request journal: every admitted request
        that neither finished nor was evicted is resubmitted through
        the recompute-preemption path — ``prompt := original +
        committed tokens`` with its original request id and sampling
        seed — so every continuation is bit-identical to the
        uninterrupted run (greedy, and sampled: the (seed, request,
        position) keys survive). The torn tail a mid-append crash left
        is dropped, never replayed as committed tokens
        (``protocol.journal_committed``); a journal whose engine
        fingerprint mismatches this engine is refused (the replay could
        not be bit-identical). Returns the resumed requests in
        admission order; ``RECOVER`` timeline tick per request."""
        path = journal if journal is not None else (
            self.journal.path if self.journal is not None else None)
        if path is None:
            raise HorovodError(
                "recover() needs a journal: pass journal= or construct "
                "the engine with one (HOROVOD_SERVE_JOURNAL)")
        header, records, committed, _torn = _serve_res.load_journal(path)
        theirs = header.get("engine", {})
        mine = self.fingerprint()
        for field in _serve_res.FINGERPRINT_FIELDS:
            if theirs.get(field) != mine[field]:
                raise HorovodError(
                    f"{path}: journal fingerprint mismatch — {field} was "
                    f"{theirs.get(field)!r} at write time but this engine "
                    f"has {mine[field]!r}; a replay could not be "
                    f"bit-identical, refusing")
        tl = _timeline.session()
        now = _now_ms_clock()
        resumed: list[Request] = []
        for item in _serve_res.replay_plan(records, committed):
            rid = item["rid"]
            orig = np.asarray(item["prompt"], np.int32)
            toks = list(item["committed"])
            prompt = np.concatenate([orig, np.asarray(toks, np.int32)])
            if prompt.shape[0] > self.max_prompt_len:
                raise HorovodError(
                    f"journal request {rid}: resumed prompt "
                    f"({prompt.shape[0]} tokens) exceeds max_prompt_len="
                    f"{self.max_prompt_len} — it cannot be recomputed; "
                    f"grow max_prompt_len on the recovering engine")
            budget = item["budget_ms"]
            req = Request(
                request_id=rid, tenant=item["tenant"], prompt=prompt,
                max_new_tokens=item["max_new"], orig_prompt=orig,
                sample_seed=item["seed"],
                deadline_ms=(now + budget if budget is not None else None),
                budget_ms=budget)
            req.output.extend(toks)
            self._next_id = max(self._next_id, rid + 1)
            self.scheduler.submit(req)
            if self.journal is not None:
                self.journal.record_recover(rid, len(toks), t=now)
            tl.event("serving", "RECOVER", "X")
            self.stats["recovered"] += 1
            resumed.append(req)
        if self.journal is not None:
            self.journal.flush(t=now)
        return resumed

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    def cache_stats(self) -> dict:
        """Pool-level accounting: allocator occupancy, the internal
        fragmentation of the live sequences (shared pages counted once),
        prefix-cache held pages, and the kv_dtype's memory-per-token
        cost (scale planes included)."""
        self.pool.check_invariants()
        active = self._active_slots()
        lengths = [int(self._lengths[i]) for i in active]
        tables = [self._tables[i] for i in active]
        return {
            "num_blocks": self.pool.num_blocks,
            "block_size": self.block_size,
            "kv_dtype": self.kv_dtype,
            "kv_cache_bytes_per_token": _kv.kv_bytes_per_token(
                self._cfg, self.kv_dtype),
            "blocks_used": self.pool.num_used,
            "blocks_free": self.pool.num_free,
            "blocks_shared": self.pool.num_shared,
            "prefix_cached_blocks": (len(self.prefix_index.blocks())
                                     if self.prefix_index else 0),
            "prefix_index_hits": (self.prefix_index.hits
                                  if self.prefix_index else 0),
            "prefix_index_misses": (self.prefix_index.misses
                                    if self.prefix_index else 0),
            "utilization": round(self.pool.utilization(), 4),
            "internal_frag_tokens":
                self.pool.internal_fragmentation(lengths, tables),
            "active_requests": len(lengths),
            "queued_requests": self.scheduler.queued,
            "speculate_k": self.speculate_k,
            "draft_kv_dtype": self.draft_kv_dtype,
            "spec_accept_rate": self.spec_accept_rate,
            "spec_disabled": self._spec_disabled,
            "shedding": self._shedding,
        }

    @property
    def decode_trace_count(self) -> int:
        """How many times the decode executable was traced — 1 for the
        engine's whole life is the fixed-shape contract (0 when
        speculation replaces it with the verify executable)."""
        return self._decode_traces

    @property
    def verify_trace_count(self) -> int:
        """How many times the speculative verify executable was traced
        — 1 for the engine's whole life is the fixed-shape contract
        (0 with speculation off)."""
        return self._verify_traces

    @property
    def draft_trace_count(self) -> int:
        """How many times the draft-propose executable was traced — 1
        for the engine's whole life (0 with speculation off)."""
        return self._draft_traces

    @property
    def draft_prefill_trace_count(self) -> int:
        """How many times the draft prefill executable was traced — 1
        for the engine's whole life (0 with speculation off)."""
        return self._draft_prefill_traces

    @property
    def spec_accept_rate(self) -> float | None:
        """Fraction of draft proposals the target accepted (None before
        any speculative step, or with speculation off) — the number the
        tune knob prices k against (tune/search.py)."""
        proposed = self.stats["spec_proposed"]
        if not proposed:
            return None
        return self.stats["spec_accepted"] / proposed
