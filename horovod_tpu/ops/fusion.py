"""Tensor fusion as a compile-time bucketing pass.

The reference fuses at runtime: the coordinator packs ready tensors into a
64 MB fusion buffer each 5 ms cycle (``controller.cc:626-750`` FuseResponses,
``fusion_buffer_manager.cc``). Under XLA the equivalent is a *static*
bucketing pass over the gradient pytree: concatenate same-dtype leaves into
buckets up to the fusion threshold and emit ONE ``psum`` per bucket. XLA then
schedules those large collectives back-to-back on ICI, which is exactly the
bandwidth shape the runtime fusion buffer was built to achieve — without any
memcpy: the pack/unpack reshapes fuse into neighbouring ops.

The same pack/unpack is reused by the eager executor when it materializes a
fused Response from the cycle loop.

The streamed (overlap) path lives here too: :func:`reduce_in_backward` is a
``custom_vjp`` identity whose backward rule issues the bucket psums for a
parameter subtree *inside* the backward pass, as soon as that subtree's
cotangents exist. A post-hoc ``fused_allreduce`` over the whole gradient
pytree data-depends on the complete backward pass, so XLA's latency-hiding
scheduler has nothing to hide the collective behind; per-subtree streamed
psums depend only on their own layer suffix and overlap with the remaining
backward compute (docs/overlap.md).
"""

from __future__ import annotations

import logging
import threading
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import metrics as _metrics
from .. import trace as _trace
from ..common import env as _env
from ..common.types import ReduceOp, dtype_size, dtype_from_array
from ..parallel.mesh import DATA_AXIS
from . import collectives

logger = logging.getLogger("horovod_tpu")


def default_threshold_bytes(threshold_bytes: Optional[int] = None) -> int:
    """Resolve the fusion threshold: explicit value > HOROVOD_FUSION_THRESHOLD
    env knob > the reference's 64 MB default (operations.cc:411-417)."""
    if threshold_bytes is not None:
        return int(threshold_bytes)
    return _env._get_int(
        _env.HOROVOD_FUSION_THRESHOLD, 64 * 1024 * 1024
    )


def default_first_bucket_bytes(first_bucket_bytes: Optional[int] = None) -> int:
    """Resolve the streamed-mode first-bucket size: explicit value >
    HOROVOD_FUSION_FIRST_BUCKET_BYTES > 1 MiB (the DDP idiom: a small first
    bucket puts bytes on the wire as early in the backward as possible)."""
    if first_bucket_bytes is not None:
        return int(first_bucket_bytes)
    return _env._get_int(
        _env.HOROVOD_FUSION_FIRST_BUCKET_BYTES, 1024 * 1024
    )


def plan_buckets(
    leaves: Sequence[Any], threshold_bytes: int
) -> List[List[int]]:
    """Group leaf indices into fusion buckets.

    Same-dtype tensors are packed greedily in submission order up to
    ``threshold_bytes`` per bucket (reference ``FuseResponses`` packs
    same-dtype/device responses up to the fusion threshold with lookahead,
    ``controller.cc:626-750``; order here is deterministic since the pytree
    order is static). An oversized leaf (a bucket of its own) closes its
    dtype's active bucket: later same-dtype leaves keep fusing, but into a
    FRESH bucket, so bucket emission order stays monotone in submission
    order — a leaf never joins a bucket that sits earlier in the stream
    than an already-emitted oversized one.
    """
    buckets: List[List[int]] = []
    # Active bucket per dtype: (bucket_index, bytes_used)
    active: Dict[str, Tuple[int, int]] = {}
    for i, leaf in enumerate(leaves):
        nbytes = leaf.size * dtype_size(dtype_from_array(leaf))
        key = str(leaf.dtype)
        if nbytes >= threshold_bytes:
            buckets.append([i])
            active.pop(key, None)
            continue
        if key in active:
            bidx, used = active[key]
            if used + nbytes <= threshold_bytes:
                buckets[bidx].append(i)
                active[key] = (bidx, used + nbytes)
                continue
        buckets.append([i])
        active[key] = (len(buckets) - 1, nbytes)
    return buckets


def pack_bucket(leaves: Sequence[jax.Array]) -> jax.Array:
    """Flatten+concat a same-dtype bucket into one 1-D buffer."""
    return jnp.concatenate([l.reshape(-1) for l in leaves], axis=0)


def unpack_bucket(
    buf: jax.Array, shapes: Sequence[Tuple[int, ...]]
) -> List[jax.Array]:
    out: List[jax.Array] = []
    offset = 0
    for shape in shapes:
        n = 1
        for d in shape:
            n *= d
        out.append(lax_slice(buf, offset, n).reshape(shape))
        offset += n
    return out


def lax_slice(buf: jax.Array, offset: int, length: int) -> jax.Array:
    return jax.lax.slice_in_dim(buf, offset, offset + length, axis=0)


def axis_label(axis_name) -> str:
    """The stable ``axis`` label of one reduction axis (or axis tuple)
    for per-axis attribution: ``"data"``, ``"model"``, ``"cross+local"``."""
    return "+".join(str(a) for a in _axes_of(axis_name))


def record_axis_wire_bytes(
    payload_bytes: int,
    axis_name,
    collective: str,
    wire_dtype: str = "f32",
) -> None:
    """Trace-time per-axis bytes-on-wire attribution (one emission per
    compile, the ``hvd_quantized_*`` discipline): ring accounting of
    what ONE step moves over the named axis per chip —
    ``hvd_axis_wire_bytes_total{axis,collective}`` (docs/metrics.md) plus
    a trace-tap plan note so step spans carry the split. This is what
    lets a composed DP x TP program report its DP and TP wire bytes
    SEPARATELY (docs/parallelism.md "Per-axis attribution"); the note is
    always recorded, the counter with metrics armed. Must be
    called inside the axis-binding trace (the axis size is read off the
    live binding)."""
    n = _axis_size_of(
        tuple(_axes_of(axis_name)) if isinstance(axis_name, (tuple, list))
        else axis_name
    )
    if n <= 1:
        return
    payload = int(payload_bytes)
    if wire_dtype == "int8":
        from ..common.quant import int8_wire_bytes

        payload = int8_wire_bytes(payload)
    if collective in ("allreduce", "psum"):
        onwire = 2 * (n - 1) * payload // n
    else:  # reduce_scatter / all_gather: one ring pass
        onwire = (n - 1) * payload // n
    label = axis_label(axis_name)
    if _metrics.ACTIVE:
        _metrics.TAP.inc(
            "hvd_axis_wire_bytes_total", float(onwire),
            axis=label, collective=collective,
        )
    _trace.note_plan(
        **{f"axis_wire_bytes:{label}:{collective}": int(onwire)}
    )


def fused_allreduce(
    tree: Any,
    *,
    op: ReduceOp = ReduceOp.AVERAGE,
    axis_name: str = DATA_AXIS,
    threshold_bytes: Optional[int] = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    reduce_fn: Callable[..., jax.Array] | None = None,
    label: str = "posthoc",
    wire_dtype: str = "f32",
) -> Any:
    """Allreduce every leaf of a pytree with bucket fusion.

    Must be called inside an axis-binding context (shard_map / pmap). This is
    the compiled-mode equivalent of wrapping every gradient in
    ``hvd.allreduce`` and letting the background loop fuse them.
    ``threshold_bytes=None`` resolves the HOROVOD_FUSION_THRESHOLD knob.
    """
    threshold_bytes = default_threshold_bytes(threshold_bytes)
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    buckets = plan_buckets(leaves, threshold_bytes)
    record_axis_wire_bytes(
        sum(l.size * dtype_size(dtype_from_array(l)) for l in leaves),
        axis_name, "allreduce", wire_dtype,
    )
    # Correlation ids for the fleet-trace step spans (trace-time,
    # one note per compile): which fusion path reduced how many
    # buckets this step.
    _trace.note_plan(
        fusion_path=label, fusion_buckets=len(buckets)
    )
    if _metrics.ACTIVE:
        # Trace-time plan stats (one emission per compile, not per step).
        _metrics.TAP.set(
            "hvd_fusion_buckets", float(len(buckets)), path=label
        )
        for bucket in buckets:
            _metrics.TAP.observe(
                "hvd_fusion_bucket_bytes",
                float(sum(
                    leaves[i].size * dtype_size(dtype_from_array(leaves[i]))
                    for i in bucket
                )),
                path=label,
            )
    reduce_fn = reduce_fn or collectives.allreduce
    results: List[jax.Array | None] = [None] * len(leaves)
    for bucket in buckets:
        if len(bucket) == 1:
            i = bucket[0]
            with jax.named_scope(_trace.SCOPE_EXCHANGE_REDUCE):
                results[i] = reduce_fn(
                    leaves[i],
                    op=op,
                    axis_name=axis_name,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor,
                )
            continue
        with jax.named_scope(_trace.SCOPE_EXCHANGE_PACK):
            packed = pack_bucket([leaves[i] for i in bucket])
        with jax.named_scope(_trace.SCOPE_EXCHANGE_REDUCE):
            reduced = reduce_fn(
                packed,
                op=op,
                axis_name=axis_name,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
            )
        with jax.named_scope(_trace.SCOPE_EXCHANGE_UNPACK):
            unpacked = unpack_bucket(
                reduced, [leaves[i].shape for i in bucket]
            )
        for i, r in zip(bucket, unpacked):
            results[i] = r
    return jax.tree.unflatten(treedef, results)


# --- streamed (overlap) reduction -------------------------------------------
#
# The post-hoc fused_allreduce above reduces the WHOLE gradient pytree after
# value_and_grad returns, so every psum data-depends on the full backward
# pass and XLA cannot overlap the collective with any compute. The streamed
# path wraps parameter subtrees in a custom_vjp identity whose backward rule
# reduces that subtree's cotangents the moment they exist — the psum's
# operand cone is one layer suffix of the backward, and everything deeper in
# the model is free compute for the latency-hiding scheduler to run behind
# the wire transfer.

# Ops a streamed reduction may use: per-group reduction must equal the
# whole-tree reduction, which holds exactly for elementwise reductions.
# ADASUM normalizes per bucket (bucket plans differ between the paths)
# and stays post-hoc-only. The quantized int8 ring dithers per bucket —
# streamed-quantized equals post-hoc-quantized exactly when the bucket
# plans coincide (per-leaf buckets make it bitwise; docs/overlap.md
# "Quantized wire compression"), and its elementwise SUM/AVERAGE still
# commutes with the group split, so it streams too.
_STREAMABLE_OPS = (
    ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.MIN, ReduceOp.MAX,
)

# Ops the int8 wire supports: per-hop requantization accumulates in f32,
# which is only sound for additive reductions.
_QUANTIZABLE_OPS = (ReduceOp.SUM, ReduceOp.AVERAGE)


@dataclass(frozen=True)
class StreamConfig:
    """Hashable reduction spec closed over by the custom_vjp backward rule
    (custom_vjp nondiff args must hash/compare for trace caching)."""

    op: ReduceOp = ReduceOp.AVERAGE
    axis_name: Any = DATA_AXIS  # str, or a (cross, local) tuple
    threshold_bytes: int = 64 * 1024 * 1024
    hierarchical: bool = False
    # Per-bucket plan selection via the topology compositor
    # (docs/topology.md): each streamed bucket's payload is priced on the
    # interconnect model of the bound axes and lowered with the selected
    # algorithm (flat / two-level / split). Set by hierarchical="auto" /
    # "planned" in the public entry points.
    planned: bool = False
    # Pinned topo algorithm for planned mode (the offline tuner's
    # verdict, docs/autotune.md); None = per-bucket cost selection.
    algorithm: Optional[str] = None
    compression: Any = None  # a common.compression.Compressor class or None
    # Int8 wire (ops/quantized.py): each bucket runs quantize -> ring
    # reduce -> dequantize inside the backward trace. Flat mode moves
    # every hop int8; hierarchical/planned modes compress ONLY the
    # outermost (DCN) hop, full precision over ICI (docs/overlap.md
    # "Quantized wire compression").
    quantized: bool = False
    label: str = "stream"
    # Non-finite guard policy applied to this group's cotangents BEFORE
    # the psum (docs/fault_tolerance.md "Data-plane integrity"): "zero"
    # sanitizes locally so one rank's NaN never reaches the wire. Other
    # policies act at the step level (jax/__init__.py) — the streamed
    # group only sanitizes.
    nonfinite: str = "off"
    # Streamed ZeRO-1 (docs/overlap.md "Streamed ZeRO-1"): each bucket
    # runs reduce-scatter instead of allreduce inside the backward
    # trace — the rule returns a SHARD IMAGE (this rank's reduced shard
    # scattered into a zero bucket buffer), so only 1/N of each bucket's
    # cotangents carry data and only (n-1)/n of the payload rides the
    # wire. Consumed by ``parallel/zero.zero1_stream_update``, which
    # round-trips the identical bucket plan.
    zero1: bool = False


def _hier_reduce_fn(x, *, op, axis_name, prescale_factor=1.0,
                    postscale_factor=1.0):
    """Two-level reduce for the streamed path: reduce-scatter on ICI,
    shard psum on DCN, all-gather back (ops/collectives.py)."""
    cross_axis, local_axis = axis_name
    if prescale_factor != 1.0:
        x = x * prescale_factor
    out = collectives.hierarchical_allreduce(
        x, op=op, local_axis=local_axis, cross_axis=cross_axis
    )
    if postscale_factor != 1.0:
        out = out * postscale_factor
    return out


# --- streamed ZeRO-1: per-bucket reduce-scatter ------------------------------
#
# ZeRO-1's gradient exchange is a reduce-scatter, not an allreduce: each
# rank only needs the shard of the summed gradient its optimizer-state
# shard updates. Run per streamed bucket INSIDE the backward trace, the
# RS keeps the overlap property of the streamed path while moving half
# of the ring-allreduce's gradient bytes — and the cotangent that leaves
# the custom_vjp is a SHARD IMAGE (the reduced shard scattered into a
# zero bucket buffer), so only 1/N of each bucket carries live data.
# ``parallel/zero.zero1_stream_update`` recovers the shard bitwise by
# re-packing the same bucket plan and slicing at this rank's offset.


def _axes_of(axis_name) -> Tuple[Any, ...]:
    if isinstance(axis_name, (tuple, list)):
        return tuple(axis_name)
    return (axis_name,)


def zero1_axis_rank(axis_name):
    """This rank's flat index over an axis (or outer-major axis tuple) —
    the shard offset the streamed-zero1 bucket layout is keyed by. The
    outer-major order matches the compositor's flat rank order, so the
    two-level reduce-scatter lowering and this index always agree."""
    from jax import lax

    idx = 0
    for a in _axes_of(axis_name):
        idx = idx * _axis_size_of(a) + lax.axis_index(a)
    return idx


def _axis_size_of(axis_name) -> int:
    from ..common.compat import axis_size

    return axis_size(axis_name)


def zero1_shard_len(total: int, n_shards: int, quantized: bool) -> int:
    """Per-rank shard length of a packed bucket of ``total`` elements:
    ceil-divided over the shards and, on the int8 wire, rounded up to
    the quantizer's BLOCK so every shard keeps whole scale blocks."""
    k = -(-max(int(total), 1) // n_shards)
    if quantized:
        from ..common.quant import BLOCK

        k = -(-k // BLOCK) * BLOCK
    return k


def zero1_group_layout(params: Any, threshold_bytes: Optional[int] = None,
                       first_bucket_bytes: Optional[int] = None):
    """The streamed-zero1 group partition over ``params``: returns
    ``(children, rebuild, groups)`` — or ``(None, None, None)`` when the
    tree has no splittable top level (one implicit group, the whole
    tree). This is the SAME partition ``stream_param_groups`` wraps, and
    the single source both the backward reduce-scatter and the
    shard-local update derive their bucket layout from: a group's
    registered subtree is ``{str(i): children[i] for i in group}`` and
    its bucket plan is ``plan_buckets`` over that subtree's leaves."""
    threshold = default_threshold_bytes(threshold_bytes)
    first = default_first_bucket_bytes(first_bucket_bytes)
    split = _top_level_children(params)
    if split is None:
        return None, None, None
    children, rebuild = split
    groups = plan_layer_groups(
        [_tree_bytes(c) for c in children], threshold, first
    )
    return children, rebuild, groups


def _record_zero1_bucket(n_shards: int, k: int, dsize: int,
                         quantized: bool, label: str) -> None:
    """Trace-time hvd_zero_* gauges (one emission per compile): what one
    bucket's reduce-scatter puts on the wire (ring accounting, n-1 hops
    of one shard — int8+scales per hop on the quantized wire) and the
    per-rank shard bytes each rank keeps."""
    if not _metrics.ACTIVE:
        return
    from ..common.quant import int8_wire_bytes

    shard_bytes = k * dsize
    hop_bytes = (
        int8_wire_bytes(shard_bytes) if quantized else shard_bytes
    )
    _metrics.TAP.inc(
        "hvd_zero_wire_bytes_total",
        float(max(n_shards - 1, 0) * hop_bytes), path=label,
    )
    _metrics.TAP.observe(
        "hvd_zero_shard_bytes", float(shard_bytes), path=label
    )


def fused_reduce_scatter(
    tree: Any,
    *,
    op: ReduceOp = ReduceOp.AVERAGE,
    axis_name: Any = DATA_AXIS,
    threshold_bytes: Optional[int] = None,
    quantized: bool = False,
    ef: Any = None,
    label: str = "zero1",
) -> Tuple[Any, Any]:
    """Per-bucket reduce-scatter of a pytree into shard images.

    Must run inside an axis-binding context. Leaves are bucketed with
    :func:`plan_buckets` (same plan as the allreduce paths), each bucket
    is packed, padded to ``n_shards`` BLOCK-aligned shards, and
    reduce-scattered so rank r keeps the complete reduction of chunk r;
    the shard is scattered back into a zero buffer at this rank's offset
    and unpacked, so the returned tree has ``tree``'s exact structure
    with only this rank's shard elements live — the layout
    ``parallel/zero.zero1_stream_update`` round-trips bitwise.

    Lowerings: a single bound axis runs ``lax.psum_scatter`` (or the
    int8 ring RS with ``quantized=True``, ``ops/quantized.py``); an axis
    tuple runs the compositor's hierarchical reduce-scatter (inner hop
    first — the big payload stays on ICI, only the 1/L shard crosses
    DCN). MIN/MAX have no native reduce-scatter and lower exactly as
    reduce+slice (bitwise, no wire saving); int buckets reduce exactly.

    ``ef`` (quantized only) is the SHARDED error-feedback residual: a
    ``{"b<i>": f32[k_i]}`` dict over the float buckets. Each rank adds
    its residual to its own chunk of the local payload before the ring
    and carries ``corrected - roundtrip(corrected)`` forward — the
    sharded EF-SGD construction (1/N coverage: a rank compensates its
    own contribution to its own shard; docs/overlap.md). Returns
    ``(shard_images, new_ef)`` (``new_ef`` mirrors ``ef``; None when
    ``ef`` is None)."""
    import jax.numpy as jnp
    from jax import lax

    if op not in _STREAMABLE_OPS:
        raise ValueError(
            f"fused_reduce_scatter supports elementwise ops "
            f"{_STREAMABLE_OPS}; got {op}"
        )
    axes = _axes_of(axis_name)
    if quantized:
        if op not in _QUANTIZABLE_OPS:
            raise ValueError(
                f"quantized reduce-scatter supports {_QUANTIZABLE_OPS}; "
                f"got {op}"
            )
        if len(axes) > 1:
            raise ValueError(
                "quantized zero1 runs the flat int8 ring reduce-scatter; "
                "hierarchical (DCN-only) compression is not defined for "
                "the RS+AG decomposition — drop hierarchical or "
                "quantized"
            )
    if ef is not None and not quantized:
        raise ValueError(
            "sharded error feedback (ef=...) only applies to the "
            "quantized zero1 wire"
        )
    threshold_bytes = default_threshold_bytes(threshold_bytes)
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree, ef
    n = _axis_size_of(axes if len(axes) > 1 else axes[0])
    buckets = plan_buckets(leaves, threshold_bytes)
    record_axis_wire_bytes(
        sum(l.size * dtype_size(dtype_from_array(l)) for l in leaves),
        axis_name, "reduce_scatter",
        "int8" if quantized else "f32",
    )
    _trace.note_plan(
        fusion_path=label, fusion_buckets=len(buckets),
        zero1_reduction="reduce-scatter",
    )
    if _metrics.ACTIVE:
        _metrics.TAP.set(
            "hvd_fusion_buckets", float(len(buckets)), path=label
        )
    idx = zero1_axis_rank(axes if len(axes) > 1 else axes[0])
    results: List[jax.Array | None] = [None] * len(leaves)
    new_ef: Dict[str, Any] = {}
    average = op == ReduceOp.AVERAGE
    for bi, bucket in enumerate(buckets):
        bleaves = [leaves[i] for i in bucket]
        with jax.named_scope(_trace.SCOPE_EXCHANGE_PACK):
            packed = pack_bucket(bleaves)
        total = packed.shape[0]
        if total == 0:
            # Zero-length leaves are identities — no ring, no state.
            for i in bucket:
                results[i] = leaves[i]
            continue
        dtype = packed.dtype
        is_float = jnp.issubdtype(dtype, jnp.floating)
        k = zero1_shard_len(total, n, quantized and is_float)
        padded = n * k
        with jax.named_scope(_trace.SCOPE_EXCHANGE_PACK):
            buf = jnp.pad(packed, (0, padded - total))
        with jax.named_scope(_trace.SCOPE_EXCHANGE_REDUCE):
            if quantized and is_float:
                from .quantized import (
                    quantize_roundtrip,
                    quantized_ring_reduce_scatter,
                )

                work = buf.astype(jnp.float32)
                ef_key = f"b{bi}"
                if ef is not None:
                    if ef_key not in ef:
                        raise ValueError(
                            f"sharded EF residual is missing bucket "
                            f"{ef_key!r} — build it with "
                            f"parallel/zero.init_zero1_stream_state"
                        )
                    chunk = lax.dynamic_slice(work, (idx * k,), (k,))
                    corrected = chunk + ef[ef_key]
                    work = lax.dynamic_update_slice(
                        work, corrected, (idx * k,)
                    )
                    new_ef[ef_key] = corrected - quantize_roundtrip(corrected)
                shard = quantized_ring_reduce_scatter(
                    work, axis_name=axes[0], average=average
                ).astype(dtype)
            elif op in (ReduceOp.SUM, ReduceOp.AVERAGE):
                if len(axes) > 1:
                    from ..topo import compositor as _compositor

                    shard = _compositor.lower_reducescatter(
                        buf, axes, op=ReduceOp.SUM, algorithm="two-level"
                    )
                else:
                    shard = lax.psum_scatter(buf, axes[0], tiled=True)
                if average:
                    shard = shard / n if is_float else shard // n
            else:
                # MIN/MAX: no native reduce-scatter — reduce then slice
                # (exact, bitwise with the flat reduction; no wire saving).
                red = lax.pmin if op == ReduceOp.MIN else lax.pmax
                full = red(buf, axes if len(axes) > 1 else axes[0])
                shard = lax.dynamic_slice(full, (idx * k,), (k,))
        _record_zero1_bucket(
            n, k, dtype_size(dtype_from_array(packed)),
            quantized and is_float, label,
        )
        with jax.named_scope(_trace.SCOPE_EXCHANGE_UNPACK):
            image = lax.dynamic_update_slice(
                jnp.zeros((padded,), dtype), shard.astype(dtype), (idx * k,)
            )
            unpacked = unpack_bucket(
                image[:total], [leaves[i].shape for i in bucket]
            )
        for i, r in zip(bucket, unpacked):
            results[i] = r
    out = jax.tree.unflatten(treedef, results)
    if ef is None:
        return out, None
    missing = set(ef) - set(new_ef)
    if missing:
        raise ValueError(
            f"sharded EF residual carries buckets {sorted(missing)} the "
            f"bucket plan does not — the residual layout is stale for "
            f"this partition (rebuild with init_zero1_stream_state)"
        )
    return out, new_ef


def _reduce_stream_group(cfg: StreamConfig, ct: Any) -> Any:
    """Reduce one registered subtree's cotangents (runs inside the backward
    trace, under the same axis binding as the forward)."""
    if cfg.nonfinite == "zero":
        # Pre-wire sanitization: the healthy ranks' contributions to this
        # group survive a poisoned peer (guard/nonfinite.py).
        from ..guard import nonfinite as _nf

        ct = _nf.sanitize(ct)
    if cfg.zero1:
        # Streamed ZeRO-1: reduce-scatter the bucket (shard images out),
        # no compression layer (the int8 wire is cfg.quantized).
        images, _ = fused_reduce_scatter(
            ct,
            op=cfg.op,
            axis_name=cfg.axis_name,
            threshold_bytes=cfg.threshold_bytes,
            quantized=cfg.quantized,
            label=cfg.label,
        )
        return images
    compression = cfg.compression
    ctxs = None
    if compression is not None:
        leaves, treedef = jax.tree.flatten(ct)
        compressed = [compression.compress(l) for l in leaves]
        ct = jax.tree.unflatten(treedef, [c for c, _ in compressed])
        ctxs = [c for _, c in compressed]
    if cfg.planned:
        from ..topo import compositor as _compositor

        # Built inside the backward trace: axis sizes come from the live
        # bindings, so each bucket is priced on the mesh it runs over.
        # quantized=True prices buckets with wire_dtype=int8 and lowers
        # the selected plan with int8 on the slow hop(s) only.
        reduce_fn = _compositor.planned_reduce_fn(
            _compositor.model_for_axes(cfg.axis_name), cfg.axis_name,
            quantized=cfg.quantized, algorithm=cfg.algorithm,
        )
    elif cfg.quantized:
        from .quantized import quantized_reduce_fn

        reduce_fn = quantized_reduce_fn(
            "two-level" if cfg.hierarchical else "flat", label=cfg.label
        )
    elif cfg.hierarchical:
        reduce_fn = _hier_reduce_fn
    else:
        reduce_fn = None
    reduced = fused_allreduce(
        ct,
        op=cfg.op,
        axis_name=cfg.axis_name,
        threshold_bytes=cfg.threshold_bytes,
        reduce_fn=reduce_fn,
        label=cfg.label,
        # Attribution only: hierarchical/planned wires compress at most
        # the DCN hop, so the flat-int8 accounting would overstate.
        wire_dtype=(
            "int8" if cfg.quantized and not (cfg.planned or cfg.hierarchical)
            else "f32"
        ),
    )
    if compression is not None:
        leaves, treedef = jax.tree.flatten(reduced)
        leaves = [
            compression.decompress(l, c) for l, c in zip(leaves, ctxs)
        ]
        reduced = jax.tree.unflatten(treedef, leaves)
    return reduced


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _stream_identity(cfg: StreamConfig, tree: Any) -> Any:
    return tree


def _stream_fwd(cfg, tree):
    return tree, None


def _stream_bwd(cfg, _res, ct):
    return (_reduce_stream_group(cfg, ct),)


_stream_identity.defvjp(_stream_fwd, _stream_bwd)


# --- quantized reduction with error feedback ---------------------------------
#
# EF-SGD construction (the standard fix that preserves convergence under
# biased compressors): each rank keeps a rank-local residual e, sends
# Q(g + e) instead of Q(g), and carries e' = (g + e) - Q(g + e) into the
# next step — the quantization error is re-injected instead of lost.
# The residual compensates THIS rank's first quantization (the dominant
# local error; later ring hops re-quantize shared partials, which no
# per-rank state can attribute). Residuals legitimately differ across
# ranks: the guard's digest agreement excludes them
# (guard/digest.strip_rank_local).


def quantized_ef_allreduce(
    tree: Any,
    ef: Any,
    *,
    op: ReduceOp = ReduceOp.AVERAGE,
    axis_name: Any = DATA_AXIS,
    threshold_bytes: Optional[int] = None,
    label: str = "quantized-ef",
) -> Tuple[Any, Any]:
    """Bucket-fused int8-wire allreduce with error feedback: returns
    ``(reduced, new_residual)``. ``ef`` must mirror ``tree``'s structure
    with float32 leaves (``ops/quantized.ef_like``). Float buckets move
    ``corrected = g.astype(f32) + e`` through the int8 ring and emit
    ``corrected - dequant(quant(corrected))`` as the next residual;
    integer buckets reduce exactly and pass their residual through
    unchanged (always zero). The SAME function serves the post-hoc and
    the streamed (per-group) paths, so identical bucket plans give
    bitwise-identical steps."""
    from . import collectives as _c
    from .quantized import (
        quantize_roundtrip,
        quantized_ring_allreduce,
        record_wire_bytes,
    )

    if op not in _QUANTIZABLE_OPS:
        raise ValueError(
            f"quantized reduction supports {_QUANTIZABLE_OPS}; got {op}"
        )
    threshold_bytes = default_threshold_bytes(threshold_bytes)
    leaves, treedef = jax.tree.flatten(tree)
    ef_leaves, ef_treedef = jax.tree.flatten(ef)
    if len(ef_leaves) != len(leaves):
        raise ValueError(
            f"error-feedback residual has {len(ef_leaves)} leaves but the "
            f"gradient tree has {len(leaves)} — build it with ef_like(params)"
        )
    if not leaves:
        return tree, ef
    buckets = plan_buckets(leaves, threshold_bytes)
    record_axis_wire_bytes(
        sum(l.size * dtype_size(dtype_from_array(l)) for l in leaves),
        axis_name, "allreduce", "int8",
    )
    # Correlation ids for the fleet-trace step spans (trace-time):
    # the EF int8 wire reduced this many buckets under this label.
    _trace.note_plan(
        fusion_path=label, fusion_buckets=len(buckets)
    )
    results: List[jax.Array | None] = [None] * len(leaves)
    residuals: List[jax.Array | None] = [None] * len(leaves)
    average = op == ReduceOp.AVERAGE
    for bucket in buckets:
        first = leaves[bucket[0]]
        if not jnp.issubdtype(first.dtype, jnp.floating):
            # Exact sums stay exact: no int8 round trip, residual
            # untouched (zero).
            for i in bucket:
                with jax.named_scope(_trace.SCOPE_EXCHANGE_REDUCE):
                    out = _c.allreduce(
                        leaves[i], op=op, axis_name=axis_name
                    )
                results[i] = out.astype(leaves[i].dtype)
                residuals[i] = ef_leaves[i]
            continue
        with jax.named_scope(_trace.SCOPE_EXCHANGE_PACK):
            corrected = [
                leaves[i].astype(jnp.float32) + ef_leaves[i] for i in bucket
            ]
            packed = pack_bucket(corrected)
        if packed.size == 0:
            for i in bucket:
                results[i] = leaves[i]
                residuals[i] = ef_leaves[i]
            continue
        record_wire_bytes(packed.size * 4, label)
        with jax.named_scope(_trace.SCOPE_EXCHANGE_REDUCE):
            new_res = packed - quantize_roundtrip(packed)
            reduced = quantized_ring_allreduce(
                packed, axis_name=axis_name, average=average
            )
        shapes = [leaves[i].shape for i in bucket]
        with jax.named_scope(_trace.SCOPE_EXCHANGE_UNPACK):
            for i, r, e in zip(
                bucket, unpack_bucket(reduced, shapes),
                unpack_bucket(new_res, shapes),
            ):
                results[i] = r.astype(leaves[i].dtype)
                residuals[i] = e
    return (
        jax.tree.unflatten(treedef, results),
        jax.tree.unflatten(ef_treedef, residuals),
    )


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _stream_identity_ef(cfg: StreamConfig, tree: Any, ef: Any) -> Any:
    return tree


def _stream_ef_fwd(cfg, tree, ef):
    # The residual values ride the forward residuals into the backward
    # rule; the "gradient" the rule returns for ``ef`` IS the next
    # step's residual — that is how per-bucket state computed inside the
    # backward trace escapes the custom_vjp (value_and_grad over
    # (params, ef) hands it back to the step).
    return tree, ef


def _stream_ef_bwd(cfg, ef, ct):
    if cfg.nonfinite == "zero":
        # Sentinel BEFORE the quantizer: a NaN reaching the blockwise
        # amax would poison the whole block's scale, so sanitization
        # must run pre-quantize (docs/fault_tolerance.md).
        from ..guard import nonfinite as _nf

        ct = _nf.sanitize(ct)
        ef = _nf.sanitize(ef)
    if cfg.zero1:
        # Streamed ZeRO-1 with the sharded EF residual: the per-bucket
        # int8 ring RS corrects this rank's own chunk and the fresh
        # shard residual comes back as ef's "gradient".
        return fused_reduce_scatter(
            ct,
            op=cfg.op,
            axis_name=cfg.axis_name,
            threshold_bytes=cfg.threshold_bytes,
            quantized=True,
            ef=ef,
            label=cfg.label,
        )
    reduced, new_ef = quantized_ef_allreduce(
        ct, ef,
        op=cfg.op,
        axis_name=cfg.axis_name,
        threshold_bytes=cfg.threshold_bytes,
        label=cfg.label,
    )
    return reduced, new_ef


_stream_identity_ef.defvjp(_stream_ef_fwd, _stream_ef_bwd)


# Per-thread trace ledger: DistributedOptimizer(overlap=True) consumes it to
# detect a model whose layers were never registered for streaming (the
# silent-fallback hazard the analysis lint warns about).
_stream_trace = threading.local()


def _note_stream_registration(n_leaves: int) -> None:
    d = getattr(_stream_trace, "d", None)
    if d is None:
        d = {"calls": 0, "leaves": 0}
        _stream_trace.d = d
    d["calls"] += 1
    d["leaves"] += int(n_leaves)


def take_stream_registrations() -> Dict[str, int]:
    """Return and reset this thread's (calls, leaves) streamed-registration
    counts since the last take — consumed once per optimizer trace."""
    d = getattr(_stream_trace, "d", None) or {"calls": 0, "leaves": 0}
    _stream_trace.d = {"calls": 0, "leaves": 0}
    return dict(d)


def reduce_in_backward(
    tree: Any,
    *,
    op: ReduceOp = ReduceOp.AVERAGE,
    axis_name: Any = DATA_AXIS,
    threshold_bytes: Optional[int] = None,
    hierarchical: Any = False,
    compression: Any = None,
    quantized: bool = False,
    ef: Any = None,
    label: str = "stream",
    nonfinite: str = "off",
    algorithm: Optional[str] = None,
    zero1: bool = False,
) -> Any:
    """Register a parameter subtree for streamed gradient reduction.

    Identity on the forward pass; the backward rule bucket-allreduces the
    subtree's cotangents as soon as they exist, giving XLA a collective
    whose operand cone is only this subtree's layer suffix — overlappable
    with the rest of the backward. Apply it to each layer (or layer group)
    of the params BEFORE the layer's forward computation consumes them;
    ``make_train_step(overlap=True)`` does this automatically via
    :func:`stream_param_groups`.

    ``quantized=True`` moves each bucket through the int8 wire
    (``ops/quantized.py``) inside the same backward trace — the overlap
    property is unchanged, only the bytes shrink. With ``ef`` (a float32
    residual subtree mirroring ``tree``, see ``ops/quantized.ef_like``)
    the backward applies error feedback: it reduces ``ct + ef`` and the
    next residual comes back as the GRADIENT of ``ef`` — differentiate
    with ``jax.value_and_grad(..., argnums=(0, 1))`` over (params, ef)
    and thread the residual into the next step (``make_train_step`` does
    this automatically).

    ``zero1=True`` switches the bucket reduction from allreduce to
    reduce-scatter (docs/overlap.md "Streamed ZeRO-1"): the backward
    returns SHARD IMAGES — only this rank's shard of each bucket is
    live — consumed by ``parallel/zero.zero1_stream_update``; ``ef``
    then takes the SHARDED residual dict (``{"b<i>": f32[k_i]}``), not a
    params-shaped tree.
    """
    if op not in _STREAMABLE_OPS:
        raise ValueError(
            f"reduce_in_backward supports elementwise ops {_STREAMABLE_OPS};"
            f" got {op} (ADASUM normalizes per bucket and must stay post-hoc)"
        )
    if compression is not None:
        from ..common.compression import Compression

        if compression is Compression.none:
            compression = None
    if quantized:
        if op not in _QUANTIZABLE_OPS:
            raise ValueError(
                f"quantized streaming supports {_QUANTIZABLE_OPS}; got {op}"
            )
        if compression is not None:
            raise ValueError(
                "quantized=True already compresses the wire to int8; "
                "stacking cast compression would add loss for no "
                "bandwidth win"
            )
    if ef is not None and not quantized:
        raise ValueError(
            "error feedback (ef=...) only applies to quantized streaming"
        )
    if zero1:
        if compression is not None:
            raise ValueError(
                "zero1 streaming reduce-scatters raw buckets; cast "
                "compression has no shard-image form — use "
                "quantized=True for the int8 wire instead"
            )
        if algorithm is not None:
            raise ValueError(
                "zero1 streaming lowers reduce-scatter directly (flat "
                "ring or the compositor two-level); a pinned allreduce "
                "algorithm does not apply — drop algorithm="
            )
        if quantized and bool(hierarchical):
            raise ValueError(
                "quantized zero1 runs the flat int8 ring "
                "reduce-scatter; hierarchical (DCN-only) compression is "
                "not defined for the RS+AG decomposition"
            )
    # "planned" = per-bucket compositor plan selection over the axis
    # tuple (hierarchical="auto" at the make_train_step level resolves
    # to this when the mesh carries a (pod, cross, local) hierarchy).
    planned = hierarchical == "planned"
    if algorithm is not None and not planned:
        raise ValueError(
            "algorithm= pins a compositor plan and needs "
            "hierarchical='planned' (or 'auto' resolving to it); with "
            f"hierarchical={hierarchical!r} the pin would be silently "
            "ignored"
        )
    if ef is not None and (planned or bool(hierarchical)):
        raise ValueError(
            "error feedback compensates the flat int8 ring; the "
            "hierarchical DCN-only wire quantizes post-local-reduction "
            "state no per-rank residual can attribute — use ef=None"
        )
    cfg = StreamConfig(
        op=op,
        axis_name=tuple(axis_name) if isinstance(axis_name, list)
        else axis_name,
        threshold_bytes=default_threshold_bytes(threshold_bytes),
        hierarchical=bool(hierarchical) and not planned,
        planned=planned,
        algorithm=algorithm,
        compression=compression,
        quantized=bool(quantized),
        label=label,
        nonfinite=str(nonfinite),
        zero1=bool(zero1),
    )
    _note_stream_registration(len(jax.tree.leaves(tree)))
    if ef is not None:
        return _stream_identity_ef(cfg, tree, ef)
    return _stream_identity(cfg, tree)


def stream_scan_body(
    body_fn: Callable[[Any, Any], Any], **reduce_kw
) -> Callable[[Any, Any], Any]:
    """Scan-body variant for scanned layer stacks: wrap a ``lax.scan`` body
    so the per-layer params slice it consumes is registered for streamed
    backward reduction. The scan's backward then issues one bucket psum per
    layer iteration — the reduction streams across the stack instead of
    waiting for the accumulated stacked gradient. Valid because the
    streamed ops are elementwise: psum of the per-iteration cotangent
    slices equals psum of the stacked gradient."""
    reduce_kw.setdefault("label", "stream-scan")

    def wrapped(carry, xs):
        return body_fn(carry, reduce_in_backward(xs, **reduce_kw))

    return wrapped


def _top_level_children(tree: Any):
    """Split a pytree into its top-level children (the layer granularity
    streamed grouping works at). Returns (children, rebuild) or None when
    the tree has no splittable top level.

    Dict children are walked in SORTED key order — jax's canonical
    flatten order, which is what a dict looks like after any
    jit/shard_map boundary reconstructs it. Host-side consumers (the
    zero1 state init, the tuner's program spec) must see the same
    partition the in-trace registration sees, and insertion order does
    not survive the trace boundary."""
    if isinstance(tree, dict) and tree:
        keys = list(tree.keys())
        try:
            keys = sorted(keys)
        except TypeError:  # unsortable mixed-type keys: keep list order
            pass

        def rebuild(vals, keys=keys, cls=type(tree)):
            out = dict(zip(keys, vals))
            try:
                return cls(out)
            except Exception:  # noqa: BLE001 - exotic Mapping subclass
                return out

        return [tree[k] for k in keys], rebuild
    if isinstance(tree, (list, tuple)) and tree:
        def rebuild(vals, cls=type(tree)):
            return cls(vals)

        return list(tree), rebuild
    return None


def _tree_bytes(tree: Any) -> int:
    return sum(
        l.size * dtype_size(dtype_from_array(l))
        for l in jax.tree.leaves(tree)
    )


def plan_layer_groups(
    layer_bytes: Sequence[int],
    threshold_bytes: int,
    first_bucket_bytes: int,
) -> List[List[int]]:
    """Pack layer indices into streamed-reduction groups, walking in
    REVERSE forward order (the order their gradients materialize in the
    backward pass, torch DDP's bucket assignment). The first group to
    reduce is capped at ``first_bucket_bytes`` so the first collective
    launches as early as possible; later groups fill to the fusion
    threshold. Groups are returned in reduction order; each group's member
    list is sorted in forward order."""
    groups: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    cap = max(int(first_bucket_bytes), 1)
    for i in reversed(range(len(layer_bytes))):
        cur.append(i)
        cur_bytes += int(layer_bytes[i])
        if cur_bytes >= cap:
            groups.append(sorted(cur))
            cur, cur_bytes = [], 0
            cap = max(int(threshold_bytes), 1)
    if cur:
        groups.append(sorted(cur))
    return groups


def layer_group_bytes(
    layer_bytes: Sequence[int],
    threshold_bytes: int,
    first_bucket_bytes: int,
) -> List[int]:
    """Per-group payload bytes of the :func:`plan_layer_groups`
    partition, in reduction order — the pure accounting the offline
    tuner (``horovod_tpu/tune``) prices with the compositor cost model.
    One source of truth: a tuned partition and the traced partition can
    never disagree because both come from ``plan_layer_groups``."""
    return [
        sum(int(layer_bytes[i]) for i in group)
        for group in plan_layer_groups(
            layer_bytes, threshold_bytes, first_bucket_bytes
        )
    ]


def stream_param_groups(
    params: Any,
    *,
    op: ReduceOp = ReduceOp.AVERAGE,
    axis_name: Any = DATA_AXIS,
    threshold_bytes: Optional[int] = None,
    first_bucket_bytes: Optional[int] = None,
    hierarchical: Any = False,
    compression: Any = None,
    quantized: bool = False,
    ef: Any = None,
    nonfinite: str = "off",
    algorithm: Optional[str] = None,
    zero1: bool = False,
) -> Any:
    """Partition ``params`` by top-level child (for a flax params dict: one
    child per module, in construction ≈ forward order), pack the children
    into DDP-style reverse-order groups with a smaller first bucket, and
    register every group for streamed backward reduction. A tree with no
    splittable top level degrades to one group (still overlappable with the
    optimizer/loss tail, but not intra-backward).

    ``quantized``/``ef`` follow :func:`reduce_in_backward`: with ``ef``
    (same top-level structure as ``params``) each group carries its own
    error-feedback residual slice and the updated residuals come back as
    the gradient of the ``ef`` argument.

    ``zero1=True`` registers each group for streamed reduce-scatter
    (shard images out; docs/overlap.md "Streamed ZeRO-1"); ``ef`` is
    then the SHARDED residual keyed by group (``{"g<gi>": {"b<bi>":
    f32[k]}}``, rows of ``parallel/zero.Zero1State.ef``)."""
    threshold = default_threshold_bytes(threshold_bytes)
    first = default_first_bucket_bytes(first_bucket_bytes)
    split = _top_level_children(params)
    if split is None:
        return reduce_in_backward(
            params, op=op, axis_name=axis_name, threshold_bytes=threshold,
            hierarchical=hierarchical, compression=compression,
            quantized=quantized,
            ef=(ef["g0"] if zero1 and ef is not None else ef),
            label="stream:g0", nonfinite=nonfinite, algorithm=algorithm,
            zero1=zero1,
        )
    children, rebuild = split
    ef_children = None
    if ef is not None and not zero1:
        ef_split = _top_level_children(ef)
        if ef_split is None or len(ef_split[0]) != len(children):
            raise ValueError(
                "ef must mirror params' top-level structure "
                "(build it with ops.quantized.ef_like(params))"
            )
        ef_children = ef_split[0]
    groups = plan_layer_groups(
        [_tree_bytes(c) for c in children], threshold, first
    )
    if _metrics.ACTIVE:
        _metrics.TAP.set("hvd_overlap_groups", float(len(groups)))
    wrapped = list(children)
    for gi, group in enumerate(groups):
        sub = {str(i): children[i] for i in group}
        if zero1 and ef is not None:
            gkey = f"g{gi}"
            if gkey not in ef:
                raise ValueError(
                    f"sharded EF residual is missing group {gkey!r} — "
                    f"build it with parallel/zero.init_zero1_stream_state"
                )
            sub_ef: Any = ef[gkey]
        elif ef_children is not None:
            sub_ef = {str(i): ef_children[i] for i in group}
        else:
            sub_ef = None
        sub = reduce_in_backward(
            sub, op=op, axis_name=axis_name, threshold_bytes=threshold,
            hierarchical=hierarchical, compression=compression,
            quantized=quantized, ef=sub_ef,
            label=f"stream:g{gi}", nonfinite=nonfinite,
            algorithm=algorithm, zero1=zero1,
        )
        for i in group:
            wrapped[i] = sub[str(i)]
    return rebuild(wrapped)
