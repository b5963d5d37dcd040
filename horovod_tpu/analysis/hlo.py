"""Layer 1 front-end: extract the collective schedule from HLO text.

The paper's correctness contract is an *ordered list of collectives,
identical on every process* (arXiv:1802.05799 §3 — the background
coordinator exists to enforce it dynamically). On TPU the compiled program
IS that schedule: every collective a step executes appears as an HLO
instruction (`all-reduce`, `reduce-scatter`, `all-gather`, `all-to-all`,
`collective-permute`) with its `replica_groups` partition, element type and
shape in program order. This module turns HLO text — freshly lowered from a
jitted step (:func:`step_hlo`, the ``tests/test_strategy.py`` lowering
idiom) or ingested from a dumped ``.hlo`` file — into that schedule as
:class:`CollectiveInstr` records, which ``analysis/schedule.py`` then
verifies statically.

Parsing is plain stdlib regex over the text form (both ``lower(...)
.as_text(dialect="hlo")`` and compiled ``.as_text()`` shapes are handled;
compiled text additionally carries ``metadata={op_name=...}`` from which the
framework's named scopes — QUANTIZE/REDUCE_SCATTER/CROSS_SLICE/ALL_GATHER/
DEQUANTIZE — are recovered). jax is imported only inside the lowering
helpers, so the parser works in jax-less environments (the CI lint job).
"""

from __future__ import annotations

import dataclasses
import re

# Collective opcodes that constitute the schedule. `-start` variants (async
# TPU lowering) count as the op; `-done` completions are skipped so an async
# pair is one schedule entry.
COLLECTIVE_OPCODES = (
    "all-reduce",
    "reduce-scatter",
    "all-gather",
    "all-to-all",
    "collective-permute",
)

# Named scopes the framework stamps around collective phases
# (ops/strategy.py `_phase`, ops/collectives.py `_compressed_psum`).
PHASE_SCOPES = (
    "REDUCE_SCATTER",
    "CROSS_SLICE",
    "ALL_GATHER",
    "QUANTIZE",
    "DEQUANTIZE",
)

_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<iname>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\([^)]*\)|[\w\[\],{}]+)\s+"
    r"(?P<opcode>" + "|".join(COLLECTIVE_OPCODES) + r")"
    r"(?P<async>-start|-done)?\(")
_SHAPE_RE = re.compile(r"(?P<etype>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(?P<body>[\d,{} ]*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(?P<g>\d+),(?P<s>\d+)\]<=\[(?P<w>\d+)\]"
    r"(?P<t>T\(1,0\))?")
_OPNAME_RE = re.compile(r'op_name="(?P<op_name>[^"]*)"')
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<iname>[\w.\-]+)\s*=\s")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?(?P<cname>[\w.\-]+)\s*\(.*\{\s*$")
_CALLS_RE = re.compile(r"\bfusion\(.*\bcalls=%?(?P<callee>[\w.\-]+)")

# HLO element-type byte widths (pred is bit-packed conceptually but moves
# as a byte on the wire).
_ITEMSIZE = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}


@dataclasses.dataclass(frozen=True)
class CollectiveInstr:
    """One collective in the extracted schedule.

    ``replica_groups`` is a tuple of rank tuples, or ``None`` when the op
    names no groups (XLA semantics: all replicas form one group).
    ``wire_bytes`` is the instruction result payload (elements x itemsize)
    — for an all-gather that is the gathered size, for a reduce-scatter the
    shard; the canonical schedule key uses it together with the opcode so
    phase structure, not absolute byte accounting, is what must match.
    ``scope`` is the innermost framework named scope (PHASE_SCOPES) when
    the text carries op metadata, else ``None``.
    """

    opcode: str
    element_type: str
    shape: tuple[int, ...]
    replica_groups: tuple[tuple[int, ...], ...] | None
    wire_bytes: int
    scope: str | None
    op_name: str | None
    instr_name: str
    line: int  # 1-indexed line in the source text

    @property
    def numel(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n

    def key(self, rank_group_size: int | None = None) -> tuple:
        """Canonical identity for schedule comparison: what must agree
        across ranks/topologies-of-equal-shape for the schedule to be
        'the same collective'."""
        gshape = (None if self.replica_groups is None
                  else (len(self.replica_groups),
                        len(self.replica_groups[0])
                        if self.replica_groups else 0))
        base = (self.opcode, self.element_type, self.numel, gshape,
                self.scope)
        return base if rank_group_size is None else base + (rank_group_size,)

    def describe(self) -> str:
        groups = ("all" if self.replica_groups is None
                  else "x".join(str(len(g)) for g in self.replica_groups[:1])
                       + f"*{len(self.replica_groups)}")
        scope = f" scope={self.scope}" if self.scope else ""
        return (f"{self.opcode} {self.element_type}{list(self.shape)} "
                f"groups={groups} {self.wire_bytes}B{scope}")


def _parse_shape(text: str) -> tuple[str, tuple[int, ...]]:
    """First (element_type, dims) in an HLO shape string; tuple shapes
    (variadic all-reduce) report their first element."""
    m = _SHAPE_RE.search(text)
    if not m:
        return "unknown", ()
    dims = tuple(int(d) for d in m.group("dims").split(",") if d != "")
    return m.group("etype"), dims


def _parse_groups(line: str):
    m = _GROUPS_RE.search(line)
    if m:
        body = m.group("body").strip()
        if not body:
            return None
        groups = []
        for grp in re.findall(r"\{([\d, ]*)\}", "{" + body + "}"
                              if "{" not in body else body):
            groups.append(tuple(int(r) for r in grp.replace(" ", "")
                                .split(",") if r != ""))
        return tuple(g for g in groups if g) or None
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # iota form [g,s]<=[w] (optionally transposed): expand explicitly
        g, s, w = int(m.group("g")), int(m.group("s")), int(m.group("w"))
        ranks = list(range(w))
        if m.group("t"):  # T(1,0): column-major fill
            return tuple(tuple(ranks[j * g + i] for j in range(s))
                         for i in range(g))
        return tuple(tuple(ranks[i * s: (i + 1) * s]) for i in range(g))
    return None


def _parse_scope(line: str) -> tuple[str | None, str | None]:
    m = _OPNAME_RE.search(line)
    if not m:
        return None, None
    op_name = m.group("op_name")
    scope = None
    for part in reversed(op_name.split("/")):
        if part in PHASE_SCOPES:
            scope = part
            break
    return scope, op_name


def scope_map(hlo_text: str) -> dict:
    """``{instruction name: [op_name, [its fusion's members' op_names]]}``
    of a COMPILED module's text — the join from a profiler capture's
    device events (named by instruction; this libtpu gives them no
    ``tf_op``) to the ``jax.named_scope``s the program was traced under.
    Every instruction outside a fused computation is a key (``""`` where
    it carries no ``op_name``); a fusion lists the distinct ``op_name``s
    of the computation it calls, in order."""
    bodies: dict = {}  # computation → [(instruction, op_name, callee)]
    body = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m:
            body = bodies.setdefault(m.group("cname"), [])
            continue
        m = _INSTR_RE.match(line) if body is not None else None
        if m:
            name = _OPNAME_RE.search(line)
            calls = _CALLS_RE.search(line)
            body.append((m.group("iname"),
                         name.group("op_name") if name else "",
                         calls.group("callee") if calls else None))
    fused = {c for rows in bodies.values() for _, _, c in rows if c}
    out = {}
    for cname, rows in bodies.items():
        if cname in fused:
            continue
        for iname, op_name, callee in rows:
            members = dict.fromkeys(
                n for _, n, _ in bodies.get(callee, ()) if n)
            out[iname] = [op_name, list(members)]
    return out


def extract_schedule(hlo_text: str) -> list[CollectiveInstr]:
    """The ordered collective schedule of an HLO module's text form.

    Order is textual program order — HLO text prints each computation's
    instructions in (post-scheduling) execution order, which for the
    single-computation step programs this repo emits IS the collective
    issue order every replica follows.
    """
    out: list[CollectiveInstr] = []
    for lineno, line in enumerate(hlo_text.splitlines(), start=1):
        m = _OP_RE.match(line)
        if m is None or m.group("async") == "-done":
            continue
        etype, dims = _parse_shape(m.group("shape"))
        numel = 1
        for d in dims:
            numel *= d
        scope, op_name = _parse_scope(line)
        out.append(CollectiveInstr(
            opcode=m.group("opcode"),
            element_type=etype,
            shape=dims,
            replica_groups=_parse_groups(line),
            wire_bytes=numel * _ITEMSIZE.get(etype, 1),
            scope=scope,
            op_name=op_name,
            instr_name=m.group("iname"),
            line=lineno,
        ))
    return out


_EXPECT_RE = re.compile(r"hvd-lint-expect:\s*(?P<body>.*)")


def parse_expectations(text: str) -> dict[str, str]:
    """``hvd-lint-expect: key=value [key=value ...]`` headers in an ingested
    schedule file — the declared contract (world size, wire dtype, algo)
    the schedule is verified against."""
    out: dict[str, str] = {}
    for line in text.splitlines():
        m = _EXPECT_RE.search(line)
        if not m:
            continue
        for item in m.group("body").split():
            if "=" in item:
                k, v = item.split("=", 1)
                out[k.strip()] = v.strip()
    return out


# ---------------------------------------------------------------------------
# Lowering drivers (jax imported lazily; unavailable in jax-less CLI runs).
# ---------------------------------------------------------------------------


def step_hlo(fn, arg_structs, group: int = 0, compiled: bool = False) -> str:
    """HLO text of ``fn`` traced as one SPMD step over ``group``'s mesh.

    ``fn(*per_rank_args) -> scalar`` is the per-rank step body (collectives
    allowed — a TraceContext is active, the tests/test_strategy.py idiom);
    ``arg_structs`` are per-rank ``jax.ShapeDtypeStruct``s (or arrays).

    The default is the LOWERED (pre-optimization) module: it is the
    framework's truth — wire dtypes and phase structure exactly as
    ops/strategy.py + ops/compression.py emitted them. ``compiled=True``
    returns the backend-optimized text instead, which adds the named-scope
    ``op_name`` metadata and the real scheduled order but lets backend
    passes rewrite the wire (the CPU backend folds bf16 collective
    converts back to f32 — the reason PR 1's wire-dtype proof is an AOT
    TPU test); use it when scopes matter and the backend preserves the
    lowering.
    """
    import jax

    import horovod_tpu as hvd

    size = hvd.get_group(group).size
    structs = [jax.ShapeDtypeStruct((size,) + tuple(a.shape), a.dtype)
               for a in arg_structs]
    # ``hvd.spmd(...).lower`` is the one place the SPMD program is built;
    # it leaves the live process's auto-name counters alone (verifying a
    # step mid-job would otherwise shift this process's later collective
    # names — the exact drift hvd-lint HVD003 exists to catch).
    lowered = hvd.spmd(fn, group=group).lower(*structs)
    if compiled:
        try:
            return lowered.compile().as_text()
        except Exception:  # backend without text support: lowered view
            pass
    return lowered.as_text(dialect="hlo")
