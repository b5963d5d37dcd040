"""Device-topology discovery: where a group's ranks live on the machine.

The reference treats every rank as equidistant — MPI/NCCL hides the
hierarchy inside the transport. On TPU the hierarchy is visible and
enormous: ranks on one slice talk over the ICI torus (tens of GB/s per
link, microsecond latency), ranks on different slices talk over DCN
(data-center network — an order of magnitude less bandwidth, tens of
microseconds of latency). The MLPerf TPU-v3 pod work (arXiv:1909.09756)
and hierarchical-allreduce literature (arXiv:2508.13397) both hang their
gains on exactly this distinction, so the allreduce strategy layer
(ops/strategy.py) needs a truthful map of it.

:func:`discover` builds that map for a :class:`~horovod_tpu.core.state.
Group` from JAX device metadata:

* ``device.slice_index`` — present on multi-slice TPU jobs — marks the
  DCN boundaries; devices sharing a slice_index share an ICI domain.
* Where the attribute is absent (single-slice TPU, CPU simulation, AOT
  topology devices) the world is one slice, unless
  ``HOROVOD_TOPOLOGY_SLICES=N`` overrides discovery with N equal
  contiguous slices (the CPU-simulated-pod / AOT test knob, utils/env.py).

Per-level link constants (latency α, bandwidth β) are *seed* values from
public per-generation specs, good enough to rank algorithms; measured
constants from ``tools/allreduce_bench.py --calibrate`` override them via
the tuning cache (utils/costs.py).
"""

from __future__ import annotations

import dataclasses

import jax

from horovod_tpu.core import state as _state
from horovod_tpu.core.state import HorovodError
from horovod_tpu.utils import env as _env


@dataclasses.dataclass(frozen=True)
class Link:
    """One interconnect level of the α–β model.

    ``alpha_us``: fixed per-collective cost (launch + propagation), µs.
    ``gbps``: achievable ring bus bandwidth per chip, GB/s (the NCCL
    busbw convention the bench reports in, so calibration can overwrite
    these numbers with the measured ones directly).
    """

    alpha_us: float
    gbps: float


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """What the repo knows about one TPU generation: the published bf16
    peak (the MFU denominator) and the ICI seed link of the α–β model."""

    peak_bf16_tflops: float
    ici: Link


# THE chip table, substring-matched on ``device_kind`` (longest key
# first). Peaks are the public per-chip bf16 spec-sheet figures (Google
# Cloud TPU documentation). ICI numbers are ring busbw per chip derived
# from public per-chip aggregate interconnect specs; they only need to be
# right enough to ORDER the algorithms — ``--calibrate`` measures the
# real ones. A TPU that is not in this table is an error, never a
# default: a guessed peak or link rate would print as if measured.
_V5E = ChipSpec(197.0, Link(alpha_us=1.0, gbps=90.0))
_V6E = ChipSpec(918.0, Link(alpha_us=1.0, gbps=180.0))
_V5P = ChipSpec(459.0, Link(alpha_us=1.0, gbps=180.0))
_CHIP_SPECS = {
    "v4": ChipSpec(275.0, Link(alpha_us=1.0, gbps=100.0)),
    "v5 lite": _V5E, "v5e": _V5E, "v5litepod": _V5E,
    "v5p": _V5P, "v5": _V5P,
    "v6e": _V6E, "v6 lite": _V6E,
}
# CPU-simulated meshes: "bandwidth" is host memcpy; the numbers exist so
# the cost model stays total-ordered during harness validation (ICI
# faster than DCN, as on every real TPU topology), nothing more.
_ICI_CPU = Link(alpha_us=5.0, gbps=20.0)
_DCN_SEED = Link(alpha_us=25.0, gbps=12.5)


def chip_spec(device_kind: str) -> ChipSpec:
    """The :class:`ChipSpec` of a TPU ``device_kind``; an unknown kind
    raises — add the chip's published figures to ``_CHIP_SPECS``."""
    kind = device_kind.lower()
    for key in sorted(_CHIP_SPECS, key=len, reverse=True):
        if key in kind:
            return _CHIP_SPECS[key]
    raise HorovodError(
        f"TPU device_kind {device_kind!r} is not in the chip table "
        f"(ops/topology.py _CHIP_SPECS: {sorted(_CHIP_SPECS)}). Add its "
        f"published bf16 peak and ICI seed there — an unknown chip gets "
        f"no default peak or link rate.")


@dataclasses.dataclass(frozen=True)
class Topology:
    """Where one group's ranks live, as the strategy layer consumes it.

    ``slice_of[i]`` is the (renumbered, contiguous) slice id of group
    rank i; ``num_slices``/``local_size`` describe the two-level shape.
    ``local_size`` is None when slices are unequal — the hierarchical
    decomposition then refuses (XLA needs uniform replica_groups).
    """

    group_size: int
    slice_of: tuple[int, ...]
    num_slices: int
    local_size: int | None
    device_kind: str
    ici: Link
    dcn: Link

    @property
    def multi_slice(self) -> bool:
        return self.num_slices > 1

    def slice_members(self) -> list[list[int]]:
        """Group ranks per slice, slice-major, rank-ascending — the
        intra-slice ``axis_index_groups`` building block."""
        out: list[list[int]] = [[] for _ in range(self.num_slices)]
        for r, s in enumerate(self.slice_of):
            out[s].append(r)
        return out


def _ici_link(device_kind: str, platform: str) -> Link:
    if platform != "tpu":
        return _ICI_CPU
    return chip_spec(device_kind).ici


def seed_links(device_kind: str) -> tuple[Link, Link]:
    """``(ici, dcn)`` seed links for a device kind WITHOUT a live mesh —
    the synthetic-topology entry point (tools/cost_model.py), resolving
    through the same table :func:`discover` uses so there is exactly one
    copy of the constants."""
    platform = "cpu" if device_kind.lower() in ("cpu", "host") else "tpu"
    return _ici_link(device_kind, platform), _DCN_SEED


# (group devices, override) -> Topology. Trace-time selection runs per
# fusion bucket; the metadata walk should run once per group, not once
# per bucket. Keyed on the device tuple itself so a re-init with new
# devices (AOT tests) can never serve a stale topology.
_discover_memo: dict[tuple, Topology] = {}


def discover(group: "_state.Group") -> Topology:
    """Topology of ``group`` from JAX device metadata (docstring above).

    ``HOROVOD_TOPOLOGY_SLICES=N`` overrides with N equal contiguous
    slices; a group size not divisible by N raises (an override that
    silently produced ragged slices would feed the hierarchical
    decomposition a partition XLA rejects much later, far from the
    typo)."""
    devices = group.devices
    memo_key = (devices, _env.topology_slices())
    hit = _discover_memo.get(memo_key)
    if hit is not None:
        return hit
    n = len(devices)
    override = _env.topology_slices()
    if override:
        if n % override != 0:
            raise HorovodError(
                f"HOROVOD_TOPOLOGY_SLICES={override} does not divide the "
                f"group size {n}; the override must cut equal slices.")
        local = n // override
        slice_of = tuple(i // local for i in range(n))
    else:
        raw = [getattr(d, "slice_index", None) for d in devices]
        if any(s is None for s in raw):
            slice_of = tuple(0 for _ in range(n))
        else:
            # Renumber to contiguous ids in first-appearance order so a
            # group spanning slices {2, 5} becomes {0, 1}.
            ids: dict[int, int] = {}
            slice_of = tuple(ids.setdefault(s, len(ids)) for s in raw)
    num_slices = max(slice_of) + 1 if slice_of else 1
    counts = [0] * num_slices
    for s in slice_of:
        counts[s] += 1
    local_size = counts[0] if len(set(counts)) == 1 else None
    d0 = devices[0] if devices else jax.devices()[0]
    topo = Topology(
        group_size=n,
        slice_of=slice_of,
        num_slices=num_slices,
        local_size=local_size,
        device_kind=getattr(d0, "device_kind", "cpu"),
        ici=_ici_link(getattr(d0, "device_kind", "cpu"),
                      getattr(d0, "platform", "cpu")),
        dcn=_DCN_SEED,
    )
    _discover_memo[memo_key] = topo
    return topo
