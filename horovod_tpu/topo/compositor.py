"""Collective compositor: hierarchical lowering plans for every collective.

Where ``ops/collectives.py:hierarchical_allreduce`` was a hand-written
special case (local reduce-scatter -> cross allreduce -> local allgather,
the NCCLHierarchicalAllreduce re-expression), this module generalizes the
idea to the whole op set, HiCCL-style (PAPERS.md, arXiv:2408.05962): every
collective is composed from single-hop primitives (reduce-scatter /
allreduce / all-gather / tree-broadcast / all-to-all / local permute)
mapped onto the explicit interconnect hierarchy of ``topo/model.py``, and
an analytic alpha-beta cost model selects the algorithm per (topology,
payload bytes, op).

Two layers, deliberately separable:

- **Planning** (:func:`select_plan`, :class:`Plan`) is pure Python — no
  jax, deterministic, stable JSON. ``tools/topo_plan.py`` and the CI
  smoke consume only this layer.
- **Lowering** (:func:`lower_allreduce` & friends) executes a selected
  algorithm inside a ``shard_map`` trace over the model's mesh axes.
  Every hierarchical lowering is numerically equal to the flat one:
  bitwise for regroupings that commute (MIN/MAX, int sums, gather/
  scatter/permute compositions), tolerance-level for float SUM (the
  association changes) — asserted at 2/4/8 simulated ranks by
  ``tests/test_topo.py``.

Algorithms:

- ``flat`` — one XLA collective over the whole axis tuple (today's
  default path; XLA routes mixed ICI/DCN itself).
- ``ring`` / ``recursive-halving`` — explicit single-hop schedules over
  ``ppermute`` (bandwidth-optimal ring reduce-scatter+allgather; MPICH
  recursive halving-doubling for latency-bound payloads, power-of-two
  ranks only). Cross-rank bitwise-identical by construction: every
  element's reduction is computed once and copied.
- ``two-level`` — the hierarchical composition, generalized to any hop
  depth: allreduce = RS(inner) -> allreduce(outer...) -> AG(inner);
  reduce-scatter pre-permutes blocks locally so the big payload stays on
  ICI; allgather/broadcast/alltoall chain per-hop stages inner->outer.
- ``split`` — FlexLink-style (PAPERS.md) concurrent-link mode for
  multi-slice allreduce: the payload is split into two buckets
  proportional to per-hop bandwidth; the ICI-share bucket lowers
  hierarchically (DCN carries only its 1/L shards) while the DCN-share
  bucket lowers flat — two independent collectives XLA schedules
  concurrently, so the slow hop is driven instead of idled.
- ``two-level-sa`` — scatter-allgather broadcast for large payloads:
  ICI multicast inside the root slice, 1/L shards over DCN, ICI
  allgather to reassemble.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..common.quant import (
    WIRE_BF16,
    WIRE_DTYPES,
    WIRE_F32,
    WIRE_INT8,
    bf16_wire_bytes,
    int8_wire_bytes,
)
from ..common.types import ReduceOp
from .model import Hop, InterconnectModel

COLLECTIVES = (
    "allreduce", "allgather", "reducescatter", "broadcast", "alltoall",
)

# Reduce ops the hierarchical compositions support. PRODUCT stays
# flat-only (the butterfly in ops/collectives.py); ADASUM has its own
# hierarchical schedule in ops/adasum.py.
_HIER_REDUCE_OPS = (
    ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.MIN, ReduceOp.MAX,
)

# Stable stage metadata (consumed by analysis/plan_verify.py): the base
# primitive kind behind each stage label. Suffixes encode the schedule
# variant (``-ring`` / ``-halving`` / ``-doubling`` / ``-tree``), for
# split mode the bucket (``-b0`` / ``-b1``), and for the chunked
# collective-matmul direction stages the round count (``-r<N>`` — the
# rounds depend on the chunk count, not just the hop size). ``local``
# stages move no bytes over any hop.
STAGE_KINDS = {
    "all_reduce": "allreduce",
    "reduce_scatter": "reducescatter",
    "all_gather": "allgather",
    "broadcast": "broadcast",
    "all_to_all": "alltoall",
    "block_permute": "local",
    "collective_matmul_fwd": "collmm",
    "collective_matmul_bwd": "collmm",
}


def _rounds_tag(name: str) -> Tuple[str, Optional[int]]:
    """Strip a trailing ``-r<N>`` round-count tag: ``"x-r6"`` ->
    ``("x", 6)``."""
    head, sep, tail = name.rpartition("-r")
    if sep and tail.isdigit():
        return head, int(tail)
    return name, None


def stage_kind(primitive: str) -> Tuple[str, str, Optional[int]]:
    """Decompose a stage label into ``(kind, variant, bucket)``:
    ``"reduce_scatter-ring-b1"`` -> ``("reducescatter", "ring", 1)``.
    Unknown labels return kind ``"?"`` (the verifier rejects them)."""
    name = primitive
    bucket: Optional[int] = None
    for b in (0, 1):
        if name.endswith(f"-b{b}"):
            name, bucket = name[: -3], b
            break
    variant = ""
    for suffix in ("ring", "halving", "doubling", "tree"):
        if name.endswith("-" + suffix):
            name, variant = name[: -(len(suffix) + 1)], suffix
            break
    name, _ = _rounds_tag(name)
    return STAGE_KINDS.get(name, "?"), variant, bucket


def perm_rounds(primitive: str, size: int) -> Optional[List[List[Tuple[int, int]]]]:
    """The explicit per-round ``ppermute`` schedule a ring/halving stage
    stands for, as ``[[(src, dst), ...], ...]`` over ``range(size)`` —
    the metadata the symbolic plan verifier checks for bijectivity and
    round counts. Non-permute stages (XLA-native collectives, trees,
    local relayouts) return None."""
    kind, variant, _ = stage_kind(primitive)
    n = int(size)
    if kind == "collmm":
        # Chunked collective-matmul direction stage: the round count
        # rides the ``-r<N>`` tag (hops x chunks — not derivable from
        # the hop size alone); every round is the same +1 (fwd) or -1
        # (bwd) ring shift.
        base = primitive
        for suffix in ("-ring",):
            if base.endswith(suffix):
                base = base[: -len(suffix)]
        _, r = _rounds_tag(base)
        if r is None or n <= 1:
            return []
        step = 1 if "_fwd" in primitive else -1
        perm = [(i, (i + step) % n) for i in range(n)]
        return [list(perm) for _ in range(r)]
    if variant == "ring":
        if n <= 1:
            return []
        fwd = [(i, (i + 1) % n) for i in range(n)]
        return [list(fwd) for _ in range(n - 1)]
    if variant in ("halving", "doubling"):
        if n <= 1:
            return []
        if n & (n - 1):
            return [[(i, i) for i in range(n)]]  # caught as a bad round
        k = n.bit_length() - 1
        dists = [n >> (t + 1) for t in range(k)]
        if variant == "doubling":
            dists = list(reversed(dists))
        return [[(i, i ^ d) for i in range(n)] for d in dists]
    return None


@dataclass(frozen=True)
class Stage:
    """One primitive of a lowering schedule: ``bytes_on_wire`` is the
    per-rank traffic this stage puts on its hop, ``rounds`` its latency
    cost in units of the hop's per-round latency. ``wire_dtype`` is the
    stage's wire format: ``"f32"`` (full precision — the payload's own
    width) or ``"int8"`` (blockwise int8+scales, ``common/quant.py``),
    in which case ``bytes_on_wire`` is the COMPRESSED traffic."""

    primitive: str
    hop: str
    axis: str
    bytes_on_wire: int
    rounds: int
    wire_dtype: str = WIRE_F32

    def to_dict(self) -> dict:
        return {
            "primitive": self.primitive,
            "hop": self.hop,
            "axis": self.axis,
            "bytes_on_wire": int(self.bytes_on_wire),
            "rounds": int(self.rounds),
            "wire_dtype": self.wire_dtype,
        }


@dataclass(frozen=True)
class Plan:
    """A selected lowering: the compositor's machine-readable verdict,
    exposed via ``hvd.collective_plan()`` / ``tools/topo_plan.py`` and
    recorded as ``hvd_topo_plan_info`` / ``hvd_topo_bytes_per_hop``."""

    collective: str
    op: str
    algorithm: str
    nbytes: int
    hop_sizes: Tuple[int, ...]
    stages: Tuple[Stage, ...]
    cost_us: float
    # FlexLink split mode only: (flat-bucket bytes, hierarchical-bucket
    # bytes), proportional to per-hop bandwidth.
    split_bytes: Tuple[int, ...] = ()
    # Requested wire format ("f32" or "int8"). An int8 plan must carry
    # at least one int8 stage — a plan claiming compression without a
    # quantize stage fails the symbolic verifier
    # (analysis/plan_verify.py).
    wire_dtype: str = WIRE_F32

    @property
    def bytes_per_hop(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for s in self.stages:
            if s.hop == "-":  # wireless local relayout stages
                continue
            out[s.hop] = out.get(s.hop, 0) + int(s.bytes_on_wire)
        return out

    def to_dict(self) -> dict:
        return {
            "collective": self.collective,
            "op": self.op,
            "algorithm": self.algorithm,
            "nbytes": int(self.nbytes),
            "hop_sizes": list(self.hop_sizes),
            "cost_us": round(float(self.cost_us), 4),
            "bytes_per_hop": {
                k: int(v) for k, v in sorted(self.bytes_per_hop.items())
            },
            "split_bytes": list(self.split_bytes),
            "wire_dtype": self.wire_dtype,
            "stages": [s.to_dict() for s in self.stages],
        }


def _op_name(op: Any) -> str:
    if isinstance(op, ReduceOp):
        return op.name
    return str(op or "-")


def _stage_cost_us(stage: Stage, hop: Hop) -> float:
    # GB/s == 1e3 bytes/us.
    return (
        hop.latency_us * stage.rounds
        + stage.bytes_on_wire / (hop.bandwidth_gbps * 1e3)
    )


def _plan_cost_us(stages: Sequence[Stage],
                  model: InterconnectModel) -> float:
    by_name = {h.name: h for h in model.hops}
    return sum(_stage_cost_us(s, by_name[s.hop]) for s in stages)


def _bottleneck(model: InterconnectModel) -> Hop:
    """The hop a flat (whole-tuple) collective is bound by: the slowest
    one — on a multi-slice model XLA's global collective cannot move
    cross-slice traffic faster than DCN."""
    return min(model.hops, key=lambda h: h.bandwidth_gbps)


def split_fractions(model: InterconnectModel) -> Tuple[float, float]:
    """FlexLink split for 2-level allreduce: payload fractions of the
    two pipelined hierarchical buckets, proportional to per-hop
    bandwidth (inner/ICI share first). Balanced this way, bucket 0's
    DCN stage runs while bucket 1's ICI stages do — both links stay
    driven instead of the fast one idling through the slow hop."""
    inner_bw = model.inner.bandwidth_gbps
    outer_bw = model.hops[0].bandwidth_gbps
    total = inner_bw + outer_bw
    return inner_bw / total, outer_bw / total


# --- candidate schedules (planning layer, pure python) -----------------------


def _flat_stages(model: InterconnectModel, primitive: str, nbytes: int,
                 bytes_factor: float, rounds: int) -> List[Stage]:
    b = _bottleneck(model)
    return [Stage(
        primitive=primitive, hop=b.name, axis="+".join(model.axes),
        bytes_on_wire=int(nbytes * bytes_factor), rounds=rounds,
    )]


def _compress_stage(s: Stage) -> Stage:
    """Re-declare a stage with the int8+scales wire format: same
    schedule, compressed bytes."""
    return Stage(
        primitive=s.primitive, hop=s.hop, axis=s.axis,
        bytes_on_wire=int8_wire_bytes(s.bytes_on_wire), rounds=s.rounds,
        wire_dtype=WIRE_INT8,
    )


def _cast_stage(s: Stage) -> Stage:
    """Re-declare a stage with the bf16 cast wire format: same schedule,
    half the bytes, no scales. A cast commutes with any data movement
    and any SUM/AVERAGE, so unlike int8 this applies to EVERY stage of
    every candidate."""
    return Stage(
        primitive=s.primitive, hop=s.hop, axis=s.axis,
        bytes_on_wire=bf16_wire_bytes(s.bytes_on_wire), rounds=s.rounds,
        wire_dtype=WIRE_BF16,
    )


def _candidates_allreduce(model: InterconnectModel, nbytes: int,
                          op: ReduceOp,
                          wire_dtype: str = WIRE_F32
                          ) -> Dict[str, List[Stage]]:
    n = model.size
    int8 = wire_dtype == WIRE_INT8
    cands: Dict[str, List[Stage]] = {}
    if op not in _HIER_REDUCE_OPS:
        # PRODUCT/ADASUM have no compositor regrouping: one flat plan.
        if model.levels == 1:
            h = model.hops[0]
            return {"flat": [Stage(
                "all_reduce", h.name, h.axis,
                int(nbytes * 2 * (n - 1) / max(n, 1)), max(2 * (n - 1), 0),
            )]} if n > 1 else {"flat": []}
        return {"flat": _flat_stages(
            model, "all_reduce", nbytes, 2 * (n - 1) / n, 2 * (n - 1)
        )}
    if model.levels == 1:
        h = model.hops[0]
        if n <= 1:
            return {"flat": []}
        cands["ring"] = [
            Stage("reduce_scatter-ring", h.name, h.axis,
                  int(nbytes * (n - 1) / n), n - 1),
            Stage("all_gather-ring", h.name, h.axis,
                  int(nbytes * (n - 1) / n), n - 1),
        ]
        if int8:
            # The EQuARX ring: both phases move int8+scales (the only
            # single-level quantized lowering shipped; halving-doubling
            # has no quantized schedule).
            return {"ring": [_compress_stage(s) for s in cands["ring"]]}
        if n & (n - 1) == 0 and op in _HIER_REDUCE_OPS:
            k = int(math.log2(n))
            cands["recursive-halving"] = [
                Stage("reduce_scatter-halving", h.name, h.axis,
                      int(nbytes * (n - 1) / n), k),
                Stage("all_gather-doubling", h.name, h.axis,
                      int(nbytes * (n - 1) / n), k),
            ]
        return cands
    # Multi-level: flat rides the bottleneck hop as a ring.
    cands["flat"] = _flat_stages(
        model, "all_reduce", nbytes, 2 * (n - 1) / n, 2 * (n - 1)
    )
    if int8:
        # Flat quantized = chained int8 rings, every hop compressed;
        # two-level quantized = compressed-on-DCN-only (the outermost
        # all_reduce stage moves int8+scales, the inner reduce-scatter/
        # all-gather stay full precision over ICI). Split has no
        # quantized lowering and is not offered.
        cands["flat"] = [_compress_stage(s) for s in cands["flat"]]
        two = _two_level_allreduce_stages(model, nbytes, op)
        outer = model.hops[0].name
        cands["two-level"] = [
            _compress_stage(s)
            if s.primitive == "all_reduce" and s.hop == outer else s
            for s in two
        ]
        return cands
    if op in _HIER_REDUCE_OPS:
        cands["two-level"] = _two_level_allreduce_stages(model, nbytes, op)
        if (
            model.levels == 2
            and op in (ReduceOp.SUM, ReduceOp.AVERAGE)
            and nbytes >= 2 * model.size
        ):
            cands["split"] = _split_allreduce_stages(model, nbytes)
    return cands


def _two_level_allreduce_stages(model: InterconnectModel, nbytes: int,
                                op: ReduceOp) -> List[Stage]:
    if op in (ReduceOp.MIN, ReduceOp.MAX):
        # Per-hop reduction chain: full payload on every hop, log-depth
        # rounds each (XLA's single-axis all-reduce).
        return [
            Stage("all_reduce", h.name, h.axis, int(nbytes),
                  max(1, math.ceil(math.log2(max(h.size, 2)))))
            for h in reversed(model.hops)
        ]
    # SUM/AVERAGE: RS(inner) -> allreduce(outer...) -> AG(inner),
    # recursively — the shard shrinks by each inner size.
    stages: List[Stage] = []
    remaining = nbytes
    inner_path: List[Tuple[Hop, int]] = []
    for h in reversed(model.hops[1:]):  # inner hops, innermost first
        s = h.size
        stages.append(Stage(
            "reduce_scatter", h.name, h.axis,
            int(remaining * (s - 1) / s), s - 1,
        ))
        inner_path.append((h, remaining))
        remaining = math.ceil(remaining / s)
    top = model.hops[0]
    n0 = top.size
    stages.append(Stage(
        "all_reduce", top.name, top.axis,
        int(remaining * 2 * (n0 - 1) / n0), 2 * (n0 - 1),
    ))
    for h, nb in reversed(inner_path):
        s = h.size
        stages.append(Stage(
            "all_gather", h.name, h.axis, int(nb * (s - 1) / s), s - 1,
        ))
    return stages


def _split_allreduce_stages(model: InterconnectModel,
                            nbytes: int) -> List[Stage]:
    f0, _ = split_fractions(model)
    nb0 = int(nbytes * f0)
    stages = [Stage(
        s.primitive + "-b0", s.hop, s.axis, s.bytes_on_wire, s.rounds,
    ) for s in _two_level_allreduce_stages(model, nb0, ReduceOp.SUM)]
    stages += [Stage(
        s.primitive + "-b1", s.hop, s.axis, s.bytes_on_wire, s.rounds,
    ) for s in _two_level_allreduce_stages(
        model, nbytes - nb0, ReduceOp.SUM
    )]
    return stages


def _candidates_allgather(model: InterconnectModel,
                          nbytes: int) -> Dict[str, List[Stage]]:
    n = model.size
    if model.levels == 1:
        h = model.hops[0]
        return {"ring": [Stage(
            "all_gather-ring", h.name, h.axis, int(nbytes * (n - 1)),
            max(n - 1, 0),
        )]}
    cands = {"flat": _flat_stages(
        model, "all_gather", nbytes, n - 1, n - 1
    )}
    stages: List[Stage] = []
    gathered = nbytes
    for h in reversed(model.hops):  # innermost first
        s = h.size
        stages.append(Stage(
            "all_gather", h.name, h.axis, int(gathered * (s - 1)), s - 1,
        ))
        gathered *= s
    cands["two-level"] = stages
    return cands


def _candidates_reducescatter(model: InterconnectModel, nbytes: int,
                              wire_dtype: str = WIRE_F32
                              ) -> Dict[str, List[Stage]]:
    n = model.size
    int8 = wire_dtype == WIRE_INT8
    if model.levels == 1:
        h = model.hops[0]
        ring = [Stage(
            "reduce_scatter-ring", h.name, h.axis,
            int(nbytes * (n - 1) / max(n, 1)), max(n - 1, 0),
        )]
        if int8:
            # The int8 ring RS (ops/quantized.py, ZeRO-1's gradient
            # hop): the single reduce-scatter phase of the EQuARX ring,
            # every hop int8+scales.
            return {"ring": [_compress_stage(s) for s in ring]}
        return {"ring": ring}
    cands = {"flat": _flat_stages(
        model, "reduce_scatter", nbytes, (n - 1) / n, n - 1
    )}
    stages: List[Stage] = [Stage(
        "block_permute", "-", "-", 0, 0,  # local relayout, no wire
    )]
    remaining = nbytes
    for h in reversed(model.hops):  # innermost first
        s = h.size
        stages.append(Stage(
            "reduce_scatter", h.name, h.axis,
            int(remaining * (s - 1) / s), s - 1,
        ))
        remaining = math.ceil(remaining / s)
    cands["two-level"] = stages
    if int8:
        # Planning-level quantized RS on a hierarchy: flat rides the
        # bottleneck as the int8 ring; two-level compresses only the
        # outermost (DCN) stage — the 1/L shard that actually crosses
        # the slow hop — like the allreduce DCN-only construction.
        outer = model.hops[0].name
        cands["flat"] = [_compress_stage(s) for s in cands["flat"]]
        cands["two-level"] = [
            _compress_stage(s) if s.hop == outer else s
            for s in cands["two-level"]
        ]
    return cands


def _candidates_broadcast(model: InterconnectModel,
                          nbytes: int) -> Dict[str, List[Stage]]:
    if model.levels == 1:
        h = model.hops[0]
        k = max(1, math.ceil(math.log2(max(h.size, 2))))
        if h.size <= 1:
            return {"tree": []}
        return {"tree": [Stage(
            "broadcast-tree", h.name, h.axis, int(nbytes) * k, k,
        )]}
    b = _bottleneck(model)
    n = model.size
    k_all = max(1, math.ceil(math.log2(max(n, 2))))
    cands = {"flat": [Stage(
        "broadcast-tree", b.name, "+".join(model.axes),
        int(nbytes) * k_all, k_all,
    )]}
    # Per-hop trees, inner -> outer (full payload each hop).
    tree: List[Stage] = []
    for h in reversed(model.hops):
        k = max(1, math.ceil(math.log2(max(h.size, 2))))
        tree.append(Stage(
            "broadcast-tree", h.name, h.axis, int(nbytes) * k, k,
        ))
    cands["two-level"] = tree
    # Scatter-allgather: tree inside the root slice, 1/L shards over the
    # outer hops, inner allgather to reassemble.
    inner = model.inner
    L = inner.size
    k_in = max(1, math.ceil(math.log2(max(L, 2))))
    sa: List[Stage] = [Stage(
        "broadcast-tree", inner.name, inner.axis, int(nbytes) * k_in, k_in,
    )]
    shard = math.ceil(nbytes / L)
    for h in reversed(model.hops[:-1]):
        k = max(1, math.ceil(math.log2(max(h.size, 2))))
        sa.append(Stage(
            "broadcast-tree", h.name, h.axis, int(shard) * k, k,
        ))
    sa.append(Stage(
        "all_gather", inner.name, inner.axis,
        int(nbytes * (L - 1) / L), L - 1,
    ))
    cands["two-level-sa"] = sa
    return cands


def _candidates_alltoall(model: InterconnectModel,
                         nbytes: int) -> Dict[str, List[Stage]]:
    n = model.size
    if model.levels == 1:
        h = model.hops[0]
        return {"flat": [Stage(
            "all_to_all", h.name, h.axis,
            int(nbytes * (n - 1) / max(n, 1)), max(n - 1, 0),
        )]}
    cands = {"flat": _flat_stages(
        model, "all_to_all", nbytes, (n - 1) / n, n - 1
    )}
    stages: List[Stage] = []
    for h in model.hops:  # outermost first (the lowering's phase order)
        s = h.size
        stages.append(Stage(
            "all_to_all", h.name, h.axis,
            int(nbytes * (s - 1) / s), s - 1,
        ))
    cands["two-level"] = stages
    return cands


def _effective_model(model: InterconnectModel) -> InterconnectModel:
    if model.eligible or model.levels <= 1:
        return model
    # Collapse to the flat view: hierarchy exists but is unsafe.
    return InterconnectModel(
        hops=(Hop(
            name=_bottleneck(model).name,
            axis="+".join(model.axes),
            size=model.size,
            bandwidth_gbps=_bottleneck(model).bandwidth_gbps,
            latency_us=_bottleneck(model).latency_us,
        ),),
        generation=model.generation, eligible=False,
        source=model.source,
    )


def candidate_plans(
    model: InterconnectModel,
    collective: str,
    nbytes: int,
    op: Any = ReduceOp.SUM,
    wire_dtype: str = WIRE_F32,
) -> Dict[str, Plan]:
    """Every candidate lowering the compositor can emit for
    ``collective`` at this payload on this model, as fully-formed costed
    :class:`Plan` objects keyed by algorithm name. :func:`select_plan`
    picks the cheapest of these; the symbolic plan verifier
    (``analysis/plan_verify.py``) checks every one of them.
    ``wire_dtype="int8"`` (allreduce and reduce-scatter, SUM/AVERAGE
    only — reduce-scatter is ZeRO-1's gradient hop) prices the
    quantized wire: every hop compressed for flat/ring, only the
    outermost (DCN) hop for two-level. ``wire_dtype="bf16"`` is the
    pure-cast rung (docs/topology.md): half the bytes on EVERY stage of
    EVERY candidate of EVERY collective — a cast commutes with any data
    movement and any additive reduction, needs no scales and no error
    feedback."""
    if collective not in COLLECTIVES:
        raise ValueError(
            f"unknown collective {collective!r}; one of {COLLECTIVES}"
        )
    if wire_dtype not in WIRE_DTYPES:
        raise ValueError(
            f"unknown wire_dtype {wire_dtype!r}; one of {WIRE_DTYPES}"
        )
    nbytes = max(int(nbytes), 0)
    op_enum = op if isinstance(op, ReduceOp) else None
    if isinstance(op, str) and op not in ("-", ""):
        op_enum = ReduceOp[op.upper()]
    if op_enum is None:
        op_enum = ReduceOp.SUM
    if wire_dtype == WIRE_INT8 and (
        collective not in ("allreduce", "reducescatter")
        or op_enum not in (ReduceOp.SUM, ReduceOp.AVERAGE)
    ):
        raise ValueError(
            "wire_dtype='int8' is an allreduce/reduce-scatter "
            f"SUM/AVERAGE construction (got {collective}/"
            f"{_op_name(op_enum)}): per-hop int8 requantization "
            "accumulates in f32, which is only sound for additive "
            "reductions"
        )
    eff = _effective_model(model)
    if collective == "allreduce":
        cands = _candidates_allreduce(eff, nbytes, op_enum, wire_dtype)
    elif collective == "allgather":
        cands = _candidates_allgather(eff, nbytes)
    elif collective == "reducescatter":
        cands = _candidates_reducescatter(eff, nbytes, wire_dtype)
    elif collective == "broadcast":
        cands = _candidates_broadcast(eff, nbytes)
    else:
        cands = _candidates_alltoall(eff, nbytes)
    if not cands:
        cands = {"flat": []}
    if wire_dtype == WIRE_BF16:
        # The cast applies uniformly after the fact: same schedules,
        # every wire stage at half the bytes (local relayouts move no
        # wire bytes and stay as-is).
        cands = {
            name: [_cast_stage(s) if s.hop != "-" else s for s in stages]
            for name, stages in cands.items()
        }
    op_label = _op_name(
        op_enum if collective in ("allreduce", "reducescatter") else None
    )
    plans: Dict[str, Plan] = {}
    for name in sorted(cands):
        stages = cands[name]
        if name == "split":
            cost = _split_cost_us(
                eff,
                bf16_wire_bytes(nbytes) if wire_dtype == WIRE_BF16
                else nbytes,
            )
            f0, _ = split_fractions(eff)
            nb0 = int(nbytes * f0)
            split_bytes: Tuple[int, ...] = (nb0, nbytes - nb0)
        else:
            cost = _plan_cost_us(
                [s for s in stages if s.hop != "-"], eff
            )
            split_bytes = ()
        plans[name] = Plan(
            collective=collective,
            op=op_label,
            algorithm=name,
            nbytes=nbytes,
            hop_sizes=tuple(h.size for h in eff.hops),
            stages=tuple(stages),
            cost_us=float(cost),
            split_bytes=split_bytes,
            wire_dtype=wire_dtype,
        )
    return plans


def select_plan(
    model: InterconnectModel,
    collective: str,
    nbytes: int,
    op: Any = ReduceOp.SUM,
    wire_dtype: str = WIRE_F32,
) -> Plan:
    """Cost every candidate algorithm for ``collective`` at this payload
    on this model and return the cheapest as a :class:`Plan`. An
    ineligible model (ragged/interleaved layout, or a single hop) only
    considers single-level algorithms — the "safe to go hierarchical"
    gate from ``Topology.is_homogeneous``."""
    plans = candidate_plans(model, collective, nbytes, op, wire_dtype)
    best: Optional[Plan] = None
    for name in sorted(plans):  # deterministic tie-break
        plan = plans[name]
        if best is None or plan.cost_us < best.cost_us:
            best = plan
    return best


def _split_cost_us(model: InterconnectModel, nbytes: int) -> float:
    """Pipelined estimate for the split mode: across the two buckets,
    each hop's bandwidth terms sum to the same totals as one two-level
    pass (splitting is size-linear), but the hops run CONCURRENTLY —
    take the max of the per-hop busy times — while the latency terms pay
    twice (two dispatched schedules). That is what makes split lose to
    plain two-level for small payloads (latency-bound) and win for large
    ones (the faster hop's busy time hides inside the slower's)."""
    one = _two_level_allreduce_stages(model, nbytes, ReduceOp.SUM)
    by_name = {h.name: h for h in model.hops}
    busy: Dict[str, float] = {}
    alpha = 0.0
    for s in one:
        hop = by_name[s.hop]
        busy[s.hop] = busy.get(s.hop, 0.0) + (
            s.bytes_on_wire / (hop.bandwidth_gbps * 1e3)
        )
        alpha += hop.latency_us * s.rounds
    return max(busy.values()) + 2 * alpha


# --- collective-matmul plan kind (fused TP overlap) --------------------------
#
# docs/parallelism.md "Fused TP overlap": ops/collective_matmul.py's
# all_gather_matmul / matmul_reduce_scatter dissolve the Megatron TP
# psum into bidirectional chunked ppermute chains that ride the wire
# WHILE the MXU multiplies. These plans price one such primitive:
# cost = max(compute, wire) + ramp, where ramp is the pipeline fill (the
# first sub-chunk's hop, which nothing can hide) — more chunks shrink
# the ramp but pay more per-round latency, the trade the tuner searches.

COLLECTIVE_MATMUL_FLAVORS = ("all_gather_matmul", "matmul_reduce_scatter")


def ring_hops(n: int) -> Tuple[int, int]:
    """Hops each ring direction carries for a bidirectional pass over
    ``n`` ranks: ``(ceil((n-1)/2), floor((n-1)/2))`` — together exactly
    the ``n-1`` deliveries, split so both link directions work."""
    n = int(n)
    if n <= 1:
        return (0, 0)
    return (-(-(n - 1) // 2), (n - 1) // 2)


def collective_matmul_cost_us(
    model: InterconnectModel,
    nbytes: int,
    *,
    chunks: int = 1,
    compute_us: float = 0.0,
    wire_dtype: str = WIRE_F32,
) -> Dict[str, float]:
    """Price ONE chunked collective-matmul primitive on the innermost
    hop (the TP axis rides ICI): ``wire`` is the busier ring direction's
    time (the directions run concurrently), ``ramp`` the first
    sub-chunk's un-hideable delivery, ``cost = max(compute, wire) +
    ramp`` and ``exposed = cost - compute`` — what the step pays beyond
    the matmul it had to run anyway. Compare against the classic
    exposed-psum constant (``sim.tp_fixed_comm_us``)."""
    hop = model.hops[-1]
    n = hop.size
    compute_us = float(compute_us)
    if n <= 1:
        return {
            "cost_us": round(compute_us, 4), "exposed_us": 0.0,
            "wire_us": 0.0, "ramp_us": 0.0,
        }
    h_fwd, h_bwd = ring_hops(n)
    c = max(int(chunks), 1)
    wire_bytes = (
        bf16_wire_bytes(nbytes) if wire_dtype == WIRE_BF16
        else int8_wire_bytes(nbytes) if wire_dtype == WIRE_INT8
        else int(nbytes)
    )
    bw = hop.bandwidth_gbps * 1e3  # bytes/us
    wire_fwd = hop.latency_us * h_fwd * c + wire_bytes * h_fwd / n / bw
    wire_bwd = hop.latency_us * h_bwd * c + wire_bytes * h_bwd / n / bw
    wire_us = max(wire_fwd, wire_bwd)
    ramp_us = hop.latency_us + wire_bytes / (n * c) / bw
    cost = max(compute_us, wire_us) + ramp_us
    return {
        "cost_us": round(cost, 4),
        "exposed_us": round(cost - compute_us, 4),
        "wire_us": round(wire_us, 4),
        "ramp_us": round(ramp_us, 4),
    }


def collective_matmul_plan(
    model: InterconnectModel,
    flavor: str,
    nbytes: int,
    *,
    chunks: int = 1,
    compute_us: float = 0.0,
    wire_dtype: str = WIRE_F32,
) -> Plan:
    """The machine-checkable schedule behind one fused primitive: one
    direction stage per ring (the bwd stage vanishes at n=2 where the
    backward ring carries nothing), each ``hops x chunks`` rounds of the
    same +-1 shift with EXACT symbolic bytes ``nbytes*hops/n`` — what
    ``analysis/plan_verify`` Pass 3 executes for per-round bijectivity
    and byte accounting. ``cost_us`` embeds the overlap model of
    :func:`collective_matmul_cost_us`."""
    if flavor not in COLLECTIVE_MATMUL_FLAVORS:
        raise ValueError(
            f"unknown collective_matmul flavor {flavor!r}; one of "
            f"{COLLECTIVE_MATMUL_FLAVORS}"
        )
    if wire_dtype not in WIRE_DTYPES:
        raise ValueError(
            f"unknown wire_dtype {wire_dtype!r}; one of {WIRE_DTYPES}"
        )
    if wire_dtype == WIRE_INT8:
        raise ValueError(
            "wire_dtype='int8' is an allreduce/reduce-scatter "
            "SUM/AVERAGE construction — the collective-matmul chunks "
            "are consumed by a matmul per hop, which has no blockwise "
            "requantization schedule; use 'bf16' for the cast rung"
        )
    nbytes = max(int(nbytes), 0)
    hop = model.hops[-1]
    n = hop.size
    h_fwd, h_bwd = ring_hops(n)
    c = max(int(chunks), 1)
    stages: List[Stage] = []
    for direction, hops in (("fwd", h_fwd), ("bwd", h_bwd)):
        if hops <= 0:
            continue
        s = Stage(
            primitive=(
                f"collective_matmul_{direction}-r{hops * c}-ring"
            ),
            hop=hop.name, axis=hop.axis,
            bytes_on_wire=int(nbytes * hops / n), rounds=hops * c,
        )
        stages.append(_cast_stage(s) if wire_dtype == WIRE_BF16 else s)
    priced = collective_matmul_cost_us(
        model, nbytes, chunks=c, compute_us=compute_us,
        wire_dtype=wire_dtype,
    )
    return Plan(
        collective="collective_matmul",
        op="SUM" if flavor == "matmul_reduce_scatter" else "-",
        algorithm=f"{flavor}-c{c}",
        nbytes=nbytes,
        hop_sizes=tuple(h.size for h in model.hops),
        stages=tuple(stages),
        cost_us=float(priced["cost_us"]),
        wire_dtype=wire_dtype,
    )


# --- lowering layer (inside shard_map traces) --------------------------------
#
# jax imports stay inside the functions so the planning layer (and
# tools/topo_plan.py) never pulls a backend in.


def _axes_tuple(axes) -> Tuple[str, ...]:
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


def _sizes(axes: Tuple[str, ...]) -> List[int]:
    from ..common.compat import axis_size

    return [axis_size(a) for a in axes]


def _check_reduce_op(op: ReduceOp, collective: str) -> None:
    if op not in _HIER_REDUCE_OPS:
        raise ValueError(
            f"hierarchical {collective} supports "
            f"{[o.name for o in _HIER_REDUCE_OPS]}; got {op!r} "
            f"(PRODUCT/ADASUM have no hierarchical regrouping here — "
            f"use the flat lowering or ops/adasum.py)"
        )


def _allreduce_sum_axes(flat, axes: Tuple[str, ...]):
    """k-level SUM allreduce on a flat vector: RS(inner) -> recurse on
    the shard over the outer axes -> AG(inner). The k=2 case is exactly
    the old ``hierarchical_allreduce`` body."""
    import jax.numpy as jnp
    from jax import lax

    from ..common.compat import axis_size

    if len(axes) == 1:
        return lax.psum(flat, axes[0])
    inner = axes[-1]
    L = axis_size(inner)
    n = flat.shape[0]
    pad = (-n) % L
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = lax.psum_scatter(flat, inner, scatter_dimension=0, tiled=True)
    shard = _allreduce_sum_axes(shard, axes[:-1])
    full = lax.all_gather(shard, inner, tiled=True)
    if pad:
        full = full[:n]
    return full


def _ring_allreduce(x, axis: str, combine=None):
    """Explicit ring allreduce over one hop: reduce-scatter ring then
    allgather ring via ``ppermute``, n-1 rounds each, bandwidth-optimal.
    Each chunk's reduction is a single accumulation chain along the ring
    and then copied, so every rank's result is bitwise identical.
    ``combine`` is the elementwise reduction (default add)."""
    import jax.numpy as jnp
    from jax import lax

    from ..common.compat import axis_size

    if combine is None:
        combine = jnp.add
    axes = _axes_tuple(axis)
    assert len(axes) == 1, "ring schedule is a single-hop primitive"
    axis = axes[0]
    n = axis_size(axis)
    if n == 1:
        return x
    shape = x.shape
    flat = x.reshape(-1)
    N = flat.shape[0]
    pad = (-N) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    m = flat.shape[0] // n
    r = lax.axis_index(axis)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    buf = flat
    for t in range(n - 1):
        send_idx = (r - t) % n
        send = lax.dynamic_slice(buf, (send_idx * m,), (m,))
        recv = lax.ppermute(send, axis, fwd)
        recv_idx = (r - t - 1) % n
        acc = combine(lax.dynamic_slice(buf, (recv_idx * m,), (m,)), recv)
        buf = lax.dynamic_update_slice(buf, acc, (recv_idx * m,))
    # Rank r now owns the fully-reduced chunk (r + 1) % n; forward it
    # around the ring.
    for t in range(n - 1):
        send_idx = (r + 1 - t) % n
        send = lax.dynamic_slice(buf, (send_idx * m,), (m,))
        recv = lax.ppermute(send, axis, fwd)
        recv_idx = (r - t) % n
        buf = lax.dynamic_update_slice(buf, recv, (recv_idx * m,))
    if pad:
        buf = buf[:N]
    return buf.reshape(shape)


def _rhd_allreduce(x, axis: str, combine):
    """MPICH recursive halving-doubling over one hop (power-of-two ranks):
    log2(n) halving exchanges reduce-scatter the vector, log2(n) doubling
    exchanges gather it back. ``combine`` is the elementwise reduction
    (add / minimum / maximum). Bitwise identical across ranks — every
    element's reduction tree is computed once by its segment owner."""
    import jax.numpy as jnp
    from jax import lax

    from ..common.compat import axis_size

    axes = _axes_tuple(axis)
    assert len(axes) == 1, "halving-doubling is a single-hop primitive"
    axis = axes[0]
    n = axis_size(axis)
    if n == 1:
        return x
    if n & (n - 1):
        raise ValueError(
            f"recursive-halving needs a power-of-two hop size, got {n}"
        )
    k = n.bit_length() - 1
    shape = x.shape
    flat = x.reshape(-1)
    N = flat.shape[0]
    pad = (-N) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    r = lax.axis_index(axis)
    buf = flat
    bits = []
    # Halving phase: decide the high bit first (partner at distance n/2).
    for t in range(k):
        d = n >> (t + 1)
        half = buf.shape[0] // 2
        bit = (r >> (k - 1 - t)) & 1  # 0 -> keep low half, 1 -> keep high
        bits.append(bit)
        keep = lax.dynamic_slice(buf, (bit * half,), (half,))
        send = lax.dynamic_slice(buf, ((1 - bit) * half,), (half,))
        perm = [(i, i ^ d) for i in range(n)]
        recv = lax.ppermute(send, axis, perm)
        buf = combine(keep, recv)
    # Doubling phase: reverse the exchanges, rebuilding the vector.
    for t in reversed(range(k)):
        d = n >> (t + 1)
        bit = bits[t]
        perm = [(i, i ^ d) for i in range(n)]
        recv = lax.ppermute(buf, axis, perm)
        low_first = jnp.concatenate([buf, recv])
        high_first = jnp.concatenate([recv, buf])
        buf = jnp.where(bit == 0, low_first, high_first)
    if pad:
        buf = buf[:N]
    return buf.reshape(shape)


def lower_allreduce(
    x,
    axes,
    *,
    op: ReduceOp = ReduceOp.SUM,
    algorithm: str = "two-level",
    split_fraction: Optional[float] = None,
    wire_dtype: str = WIRE_F32,
):
    """Allreduce ``x`` over the hierarchy ``axes`` (outermost first) with
    the given algorithm. Numerically equal to
    ``lax.psum/pmin/pmax(x, tuple(axes))`` — exactly for f32 wire, to
    int8 quantization tolerance for ``wire_dtype="int8"`` (SUM/AVERAGE
    only): flat/ring lower through the int8 ring on every hop,
    two-level compresses only the outermost hop
    (``ops/quantized.quantized_hierarchical_allreduce``), to bf16
    rounding for ``wire_dtype="bf16"`` (any op, any algorithm: the
    payload casts down once on entry and back up on exit — the
    pure-cast rung, no scales, no error feedback)."""
    import jax.numpy as jnp
    from jax import lax

    from ..common.compat import axis_size

    axes = _axes_tuple(axes)
    total = axis_size(axes)
    if wire_dtype == WIRE_BF16:
        orig = x.dtype
        out = lower_allreduce(
            x.astype(jnp.bfloat16), axes, op=op, algorithm=algorithm,
            split_fraction=split_fraction, wire_dtype=WIRE_F32,
        )
        return out.astype(orig)
    if wire_dtype == WIRE_INT8:
        from ..ops.quantized import (
            quantized_hierarchical_allreduce,
            quantized_ring_allreduce,
        )

        if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
            raise ValueError(
                f"wire_dtype='int8' supports SUM/AVERAGE; got {op}"
            )
        average = op == ReduceOp.AVERAGE
        if algorithm in ("flat", "ring", "recursive-halving"):
            return quantized_ring_allreduce(
                x, axis_name=axes if len(axes) > 1 else axes[0],
                average=average,
            )
        if algorithm == "two-level":
            return quantized_hierarchical_allreduce(
                x, axes, average=average
            )
        raise ValueError(
            f"allreduce algorithm {algorithm!r} has no int8 lowering"
        )
    if algorithm == "flat":
        from ..ops import collectives as _c

        return _c.allreduce(x, op=op, axis_name=axes)
    if algorithm == "ring":
        _check_reduce_op(op, "ring allreduce")
        combine = {
            ReduceOp.SUM: jnp.add,
            ReduceOp.AVERAGE: jnp.add,
            ReduceOp.MIN: jnp.minimum,
            ReduceOp.MAX: jnp.maximum,
        }[op]
        out = _ring_allreduce(x, axes[0], combine)
        if op == ReduceOp.AVERAGE:
            out = out / total
        return out
    if algorithm == "recursive-halving":
        _check_reduce_op(op, "allreduce")
        combine = {
            ReduceOp.SUM: jnp.add,
            ReduceOp.AVERAGE: jnp.add,
            ReduceOp.MIN: jnp.minimum,
            ReduceOp.MAX: jnp.maximum,
        }[op]
        out = _rhd_allreduce(x, axes[0], combine)
        if op == ReduceOp.AVERAGE:
            out = out / total
        return out
    _check_reduce_op(op, "allreduce")
    if op in (ReduceOp.MIN, ReduceOp.MAX):
        # Per-hop reduction chain, inner -> outer: each stage stays on
        # one hop; regrouping MIN/MAX commutes exactly (bitwise).
        red = lax.pmin if op == ReduceOp.MIN else lax.pmax
        out = x
        for a in reversed(axes):
            out = red(out, a)
        return out
    if algorithm == "two-level":
        flat = x.reshape(-1)
        out = _allreduce_sum_axes(flat, axes).reshape(x.shape)
        if op == ReduceOp.AVERAGE:
            out = out / total
        return out
    if algorithm == "split":
        if len(axes) != 2:
            raise ValueError("split mode composes exactly two hops")
        if split_fraction is None:
            split_fraction = 0.5
        flat = x.reshape(-1)
        N = flat.shape[0]
        n0 = max(min(int(N * split_fraction), N - 1), 1) if N > 1 else 0
        if n0 == 0:
            out = _allreduce_sum_axes(flat, axes)
        else:
            # Two independent hierarchical reductions XLA schedules
            # concurrently: bucket 0's DCN shard-allreduce overlaps
            # bucket 1's ICI reduce-scatter/allgather (FlexLink:
            # aggregate the links, don't idle one). Elementwise SUM
            # splits cleanly, so the concatenation equals the unsplit
            # reduction.
            part0 = _allreduce_sum_axes(flat[:n0], axes)
            part1 = _allreduce_sum_axes(flat[n0:], axes)
            out = jnp.concatenate([part0, part1])
        out = out.reshape(x.shape)
        if op == ReduceOp.AVERAGE:
            out = out / total
        return out
    raise ValueError(f"unknown allreduce algorithm {algorithm!r}")


def lower_allgather(x, axes, *, algorithm: str = "two-level"):
    """Allgather along dim 0 over the hierarchy: per-hop gathers chained
    inner -> outer reproduce the flat rank order exactly (the block
    layout rank = outer*inner_size + inner makes the concatenations
    nest)."""
    from jax import lax

    axes = _axes_tuple(axes)
    if algorithm == "flat" or len(axes) == 1:
        return lax.all_gather(x, axes if len(axes) > 1 else axes[0],
                              tiled=True)
    out = x
    for a in reversed(axes):
        out = lax.all_gather(out, a, tiled=True)
    return out


def lower_reducescatter(
    x, axes, *, op: ReduceOp = ReduceOp.SUM, algorithm: str = "two-level",
    scatter_axis: int = 0,
):
    """Reduce-scatter dim0 over the hierarchy. The two-level schedule
    pre-permutes dim0 blocks locally (free relayout, no wire) so the
    inner reduce-scatter runs FIRST — the big payload stays on ICI and
    only the 1/L shard crosses DCN — while the emitted shard still
    matches the flat op's outer-major rank order."""
    import jax.numpy as jnp
    from jax import lax

    from ..common.compat import axis_size

    axes = _axes_tuple(axes)
    if scatter_axis != 0:
        raise ValueError("compositor reduce-scatter scatters dim0")
    if op == ReduceOp.AVERAGE:
        x = x / axis_size(axes)
    elif op not in (ReduceOp.SUM, ReduceOp.ADASUM):
        raise ValueError(f"reducescatter supports SUM/AVERAGE, got {op}")
    if algorithm == "flat" or len(axes) == 1:
        return lax.psum_scatter(
            x, axes if len(axes) > 1 else axes[0],
            scatter_dimension=0, tiled=True,
        )
    sizes = _sizes(axes)
    n = 1
    for s in sizes:
        n *= s
    if x.shape[0] % n:
        raise ValueError(
            f"reduce-scatter dim0 ({x.shape[0]}) must be divisible by the "
            f"grid size ({n})"
        )

    def rs(v, axs, szs):
        if len(axs) == 1:
            return lax.psum_scatter(v, axs[0], scatter_dimension=0,
                                    tiled=True)
        L = szs[-1]
        M = 1
        for s in szs[:-1]:
            M *= s
        m = v.shape[0] // (M * L)
        # Block transpose: destination blocks are outer-major (o*L + l);
        # putting l outermost lets the inner hop scatter first.
        v = v.reshape((M, L, m) + v.shape[1:])
        v = jnp.swapaxes(v, 0, 1)
        v = v.reshape((M * L * m,) + v.shape[3:])
        shard = lax.psum_scatter(v, axs[-1], scatter_dimension=0,
                                 tiled=True)
        return rs(shard, axs[:-1], szs[:-1])

    return rs(x, axes, sizes)


def _axis_roots(root_rank: int, sizes: Sequence[int]) -> List[int]:
    """Decompose a global root rank (outer-major mixed radix) into
    per-axis root coordinates."""
    roots: List[int] = []
    rem = root_rank
    for s in reversed(sizes):  # innermost first
        roots.append(rem % s)
        rem //= s
    return list(reversed(roots))  # outer-major, matching axes order


def lower_broadcast(
    x, axes, *, root_rank: int = 0, algorithm: str = "two-level",
):
    """Broadcast the global ``root_rank``'s value over the hierarchy.
    ``two-level`` chains per-hop binomial trees inner -> outer;
    ``two-level-sa`` (large payloads) multicasts inside the root slice,
    moves only 1/L shards over the outer hops, and reassembles with an
    inner allgather. Exact: broadcast moves bits, no arithmetic."""
    import jax.numpy as jnp
    from jax import lax

    from ..common.compat import axis_size
    from ..ops.collectives import broadcast as _tree_bcast

    axes = _axes_tuple(axes)
    sizes = _sizes(axes)
    n = 1
    for s in sizes:
        n *= s
    if not 0 <= int(root_rank) < n:
        raise ValueError(
            f"root_rank {root_rank} out of range for grid of size {n}"
        )
    roots = _axis_roots(int(root_rank), sizes)
    if algorithm == "flat" or len(axes) == 1:
        if len(axes) == 1:
            return _tree_bcast(x, root_rank=int(root_rank),
                               axis_name=axes[0])
        # Flat over the tuple: chain is the canonical lowering anyway
        # (XLA has no native multi-axis tree broadcast primitive).
        algorithm = "two-level"
    if algorithm == "two-level":
        out = x
        for a, r in zip(reversed(axes), reversed(roots)):
            out = _tree_bcast(out, root_rank=r, axis_name=a)
        return out
    if algorithm == "two-level-sa":
        inner = axes[-1]
        L = sizes[-1]
        shape = x.shape
        # Stage 1: the root's slice gets the value over ICI.
        out = _tree_bcast(x, root_rank=roots[-1], axis_name=inner)
        flat = out.reshape(-1)
        N = flat.shape[0]
        pad = (-N) % L
        if pad:
            flat = jnp.pad(flat, (0, pad))
        m = flat.shape[0] // L
        li = lax.axis_index(inner)
        shard = lax.dynamic_slice(flat, (li * m,), (m,))
        # Stage 2: only the 1/L shard crosses the outer (DCN) hops.
        for a, r in zip(reversed(axes[:-1]), reversed(roots[:-1])):
            shard = _tree_bcast(shard, root_rank=r, axis_name=a)
        # Stage 3: reassemble over ICI.
        full = lax.all_gather(shard, inner, tiled=True)
        if pad:
            full = full[:N]
        return full.reshape(shape)
    raise ValueError(f"unknown broadcast algorithm {algorithm!r}")


def lower_alltoall(x, axes, *, algorithm: str = "two-level"):
    """All-to-all dim0 over the hierarchy: recursive two-phase exchange —
    outer-hop all-to-all grouping by destination slice, block transpose
    (local relayout), then the inner hops, another transpose restoring
    source-rank order. Exact: pure data movement."""
    import jax.numpy as jnp
    from jax import lax

    axes = _axes_tuple(axes)
    if algorithm == "flat" or len(axes) == 1:
        return lax.all_to_all(
            x, axes if len(axes) > 1 else axes[0],
            split_axis=0, concat_axis=0, tiled=True,
        )
    sizes = _sizes(axes)
    n = 1
    for s in sizes:
        n *= s
    if x.shape[0] % n:
        raise ValueError(
            f"alltoall dim0 ({x.shape[0]}) must be divisible by the grid "
            f"size ({n})"
        )

    def a2a(v, axs, szs):
        if len(axs) == 1:
            return lax.all_to_all(v, axs[0], split_axis=0, concat_axis=0,
                                  tiled=True)
        A = szs[0]
        R = 1
        for s in szs[1:]:
            R *= s
        m = v.shape[0] // (A * R)
        # Phase 1: exchange over the outer hop by destination-outer
        # index (blocks are destination-rank order, outer-major, so the
        # leading dim already groups by it).
        y = lax.all_to_all(v, axs[0], split_axis=0, concat_axis=0,
                           tiled=True)
        # y dim0 = [source-outer][dest-rest]; bring dest-rest leading so
        # the inner hops exchange per-destination.
        y = y.reshape((A, R, m) + y.shape[1:])
        y = jnp.swapaxes(y, 0, 1)
        y = y.reshape((R * A * m,) + y.shape[3:])
        z = a2a(y, axs[1:], szs[1:])
        # z dim0 = [source-rest][source-outer]; restore source-rank
        # (outer-major) order.
        z = z.reshape((R, A, m) + z.shape[1:])
        z = jnp.swapaxes(z, 0, 1)
        return z.reshape((A * R * m,) + z.shape[3:])

    return a2a(x, axes, sizes)


# --- metrics / introspection -------------------------------------------------


def record_plan(plan: Plan, where: str = "compositor") -> Plan:
    """Stamp a selected plan into the metrics registry (gated on the
    metrics tap, so production default cost is one boolean)."""
    from .. import metrics as _metrics
    from .. import trace as _trace

    # Correlation ids for the fleet-trace step spans: the selected
    # lowering algorithm + wire dtype ride every later step span so
    # one trace links step → bucket → collective → hop.
    _trace.note_plan(
        topo_algorithm=plan.algorithm,
        topo_collective=plan.collective,
        wire_dtype=getattr(plan, "wire_dtype", "f32"),
    )
    if _metrics.ACTIVE:
        _metrics.TAP.set(
            "hvd_topo_plan_info", 1.0,
            collective=plan.collective, algorithm=plan.algorithm,
            op=plan.op, where=where,
        )
        for hop, nb in plan.bytes_per_hop.items():
            _metrics.TAP.set(
                "hvd_topo_bytes_per_hop", float(nb),
                collective=plan.collective, hop=hop, where=where,
            )
    return plan


def model_for_axes(axes, generation: Optional[str] = None):
    """Interconnect model for a bound axis tuple, built INSIDE a trace
    (axis sizes come from the live axis bindings): innermost axis maps to
    the ICI hop, the next to DCN, a third to inter-pod DCN — with the
    ``HOROVOD_TOPOLOGY_MODEL`` override applied. This is how the streamed
    (overlap) path prices buckets against the mesh it is actually traced
    over rather than a detected process topology."""
    from .model import (
        DCN, ICI, POD_DCN, InterconnectModel, _mk_hop, apply_override,
        detect_generation,
    )

    axes = _axes_tuple(axes)
    sizes = _sizes(axes)
    generation = generation or detect_generation()
    names = (ICI, DCN, POD_DCN)
    hops = []
    for i, (a, s) in enumerate(zip(reversed(axes), reversed(sizes))):
        hops.append(_mk_hop(names[min(i, 2)], s, generation, axis=a))
    model = InterconnectModel(
        hops=tuple(reversed(hops)), generation=generation,
        eligible=len(axes) > 1 and sizes[-1] > 1, source="axes",
    )
    return apply_override(model)


def auto_reduce_fn(quantized: bool = False,
                   algorithm: Optional[str] = None):
    """A ``reduce_fn`` that builds the model from the bound axes at trace
    time and then defers to :func:`planned_reduce_fn` — the form the
    compiled-mode binding uses for ``hierarchical="auto"``.
    ``algorithm`` pins one allreduce lowering (the offline tuner's
    verdict, docs/autotune.md) instead of per-bucket cost selection."""

    def fn(x, *, op, axis_name, prescale_factor=1.0, postscale_factor=1.0):
        axes = _axes_tuple(axis_name)
        return planned_reduce_fn(
            model_for_axes(axes), axes, quantized=quantized,
            algorithm=algorithm,
        )(
            x, op=op, axis_name=axes,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
        )

    return fn


def planned_reduce_fn(model: InterconnectModel, axes=None,
                      quantized: bool = False,
                      algorithm: Optional[str] = None):
    """A ``reduce_fn`` for ``ops/fusion.py``: per bucket, select the
    allreduce plan for the bucket's payload on this model and lower it
    accordingly — this is what makes ``make_train_step(overlap=True)``
    go hierarchical automatically on multi-slice topologies, per bucket.
    ``axes`` defaults to the model's own axis tuple.

    ``quantized=True`` selects among the wire_dtype=int8 candidates
    (float SUM/AVERAGE buckets only — integer buckets and other ops fall
    back to full precision): the chosen plan lowers with int8 on every
    hop (flat/ring) or on the outermost hop only (two-level).

    Single-hop plan labels (``ring`` / ``recursive-halving``) lower via
    the native XLA collective: on one hop XLA already schedules its own
    ring/halving and the label is the cost model's estimate of that, not
    an instruction to hand-roll ``ppermute`` schedules inside a training
    step. The explicit schedules stay reachable through
    :func:`lower_allreduce` for tests and offline measurement. The int8
    ring is the exception — there IS no native quantized collective, so
    its explicit schedule is the lowering.

    ``algorithm`` (the offline tuner's pinned topo choice) bypasses cost
    selection: when the compositor offers that candidate at the bucket's
    payload it is used; a payload where the pin is unrealizable (e.g.
    split below its minimum size) falls back to cost selection — the
    same fallback the planner's own selection would make."""
    from ..common.types import dtype_from_array, dtype_size

    axes = _axes_tuple(axes if axes is not None else model.axes)

    def fn(x, *, op, axis_name=None, prescale_factor=1.0,
           postscale_factor=1.0):
        import jax.numpy as jnp

        use_axes = _axes_tuple(axis_name) if axis_name is not None else axes
        if prescale_factor != 1.0:
            x = x * prescale_factor
        nbytes = x.size * dtype_size(dtype_from_array(x))
        int8 = (
            quantized
            and op in (ReduceOp.SUM, ReduceOp.AVERAGE)
            and jnp.issubdtype(x.dtype, jnp.floating)
        )
        wire = WIRE_INT8 if int8 else WIRE_F32
        plan = None
        if algorithm:
            plan = candidate_plans(
                model, "allreduce", nbytes, op=op, wire_dtype=wire
            ).get(algorithm)
        if plan is None:
            plan = select_plan(
                model, "allreduce", nbytes, op=op, wire_dtype=wire
            )
        plan = record_plan(plan, where="stream")
        if int8:
            from ..ops.quantized import record_wire_bytes

            record_wire_bytes(nbytes, "stream")
        lower_algo = plan.algorithm
        frac = None
        if lower_algo == "split" and plan.nbytes:
            frac = plan.split_bytes[0] / plan.nbytes
        elif lower_algo in ("ring", "recursive-halving") or len(use_axes) == 1:
            # f32 single-hop labels lower natively; the int8 ring label
            # is handled by lower_allreduce's quantized branch.
            if not int8:
                lower_algo = "flat"
        out = lower_allreduce(
            x, use_axes, op=op, algorithm=lower_algo,
            split_fraction=frac, wire_dtype=wire,
        )
        if postscale_factor != 1.0:
            out = out * postscale_factor
        return out

    return fn
