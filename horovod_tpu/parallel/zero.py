"""ZeRO-1 optimizer-state sharding over the data axis.

TPU-native extension beyond the reference (whose optimizer state is fully
replicated, ``horovod/torch/__init__.py:381-435`` role): the optimizer
state lives sharded 1/N per device, the gradient allreduce becomes a
reduce-scatter, each rank updates only its parameter shard, and the
updated shards are all-gathered back — the ZeRO stage-1 schedule (Rajbhandari
et al., 2019) expressed as three XLA collectives inside one jitted step:

    flat(grads) --psum_scatter--> g_shard          (ICI ring, 1/N bytes out)
    tx.update(g_shard, state_shard, p_shard)       (compute on 1/N params)
    flat(params') <--all_gather-- p_shard'         (ICI ring)

Memory per device: optimizer state + one params copy of updates shrink by
the data-axis size (Adam: 8 bytes/param -> 8/N). Wire bytes match plain
DP's reduce-scatter + all-gather decomposition of the ring allreduce, so
there is no communication penalty.

The parameter pytree is flattened to one vector (padded to a multiple of
the axis size), so element-wise optax transforms (sgd, momentum, adam,
adamw with scalar weight decay, ...) track plain DP to numerical
tolerance (tested; psum_scatter vs psum reduction order leaves no
bitwise guarantee). Transforms that need per-parameter tree structure
(per-layer masking, lars/lamb trust ratios) need the replicated path
instead.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from .. import trace as _trace
from .mesh import DATA_AXIS

__all__ = [
    "Zero1State",
    "init_zero1_state",
    "init_zero1_stream_state",
    "make_zero1_train_step",
    "zero1_posthoc_reduce",
    "zero1_stream_update",
    "zero1_update",
]


def _flat_meta(params, n_shards: int, block: int = 1):
    flat, unravel = ravel_pytree(params)
    total = flat.shape[0]
    per = -(-total // n_shards)
    per = -(-per // block) * block  # quantized wire: BLOCK-aligned shards
    return flat, unravel, total, per * n_shards, per


def _block(quantized: bool) -> int:
    if not quantized:
        return 1
    from ..ops.quantized import BLOCK

    return BLOCK


def init_zero1_state(optimizer, params, n_shards: int,
                     quantized: bool = False):
    """Per-shard optimizer states, stacked on a leading [n_shards] axis
    (the axis ``make_zero1_train_step`` shards over the mesh). Each
    shard's state is ``optimizer.init`` of that rank's flat parameter
    slice, so stateful transforms (momentum, Adam moments) start exactly
    as they would on the full vector."""
    flat, _, total, padded, k = _flat_meta(
        params, n_shards, _block(quantized)
    )
    flat = jnp.pad(flat, (0, padded - total))
    states = [
        optimizer.init(lax.dynamic_slice(flat, (r * k,), (k,)))
        for r in range(n_shards)
    ]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def _check_axis_shards(axis_name, n_shards: int, where: str) -> None:
    """A silent mismatch between the bound axis size and the shard count
    the state was built for misaligns every shard offset; fail loudly."""
    from ..common.compat import axis_size

    live = axis_size(axis_name)
    if live != n_shards:
        raise ValueError(
            f"{where}: optimizer state is sharded {n_shards} ways but "
            f"the bound axis {axis_name!r} has size {live} — the shard "
            f"offsets would silently misalign; rebuild the state for "
            f"this mesh"
        )


def zero1_update(optimizer, params, state, grads, *,
                 axis_name: str = DATA_AXIS, n_shards: int,
                 quantized: bool = False):
    """The ZeRO-1 update inside an existing shard_map/pmap context:
    reduce-scatter ``grads`` (averaged over the axis), optax-update this
    rank's flat parameter shard against its 1/N ``state`` (un-stacked, as
    produced by ``init_zero1_state`` rows), all-gather the new params.
    Returns ``(new_params, new_state)``. Use ``make_zero1_train_step`` for
    the packaged whole-step version."""
    import optax

    _check_axis_shards(axis_name, n_shards, "zero1_update")
    flat_p, unravel, total, padded, k = _flat_meta(
        params, n_shards, _block(quantized)
    )
    flat_g, _ = ravel_pytree(grads)
    flat_g = jnp.pad(flat_g, (0, padded - total))
    flat_p = jnp.pad(flat_p, (0, padded - total))

    if quantized:
        # int8-wire ring reduce-scatter (ops/quantized.py): the shard
        # length is BLOCK-aligned by _flat_meta, and rank r receives
        # exactly its chunk r, so the composition with the sharded
        # update/all-gather below is layout-free.
        from ..ops.quantized import quantized_ring_reduce_scatter

        g_shard = quantized_ring_reduce_scatter(
            flat_g, axis_name=axis_name, average=True
        )
    else:
        g_shard = lax.psum_scatter(flat_g, axis_name, tiled=True) / n_shards
    idx = lax.axis_index(axis_name)
    p_shard = lax.dynamic_slice(flat_p, (idx * k,), (k,))

    updates, new_state = optimizer.update(g_shard, state, p_shard)
    new_p_shard = optax.apply_updates(p_shard, updates)

    new_flat = lax.all_gather(new_p_shard, axis_name, tiled=True)
    return unravel(new_flat[:total]), new_state


def make_zero1_train_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    optimizer,
    mesh: Mesh,
    *,
    axis_name: str = DATA_AXIS,
    donate: bool = True,
    quantized: bool = False,
):
    """Build the jitted ZeRO-1 step: ``step(params, state, batch) ->
    (params, state, loss)``. ``params`` replicated, ``state`` from
    ``init_zero1_state`` (sharded over ``axis_name``), ``batch`` sharded
    on dim0, gradient averaging over the axis."""
    from ..jax import _shard_map

    n = int(mesh.shape[axis_name])

    def body(params, state_stacked, batch):
        state = jax.tree.map(lambda s: s[0], state_stacked)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, new_state = zero1_update(
            optimizer, params, state, grads,
            axis_name=axis_name, n_shards=n, quantized=quantized,
        )
        loss = lax.pmean(loss, axis_name)
        return (
            new_params,
            jax.tree.map(lambda s: s[None], new_state),
            loss,
        )

    fn = jax.jit(
        _shard_map(
            body, mesh,
            in_specs=(P(), P(axis_name), P(axis_name)),
            out_specs=(P(), P(axis_name), P()),
        ),
        donate_argnums=(0, 1) if donate else (),
    )
    return fn


# --- streamed ZeRO-1: per-bucket shard layout --------------------------------
#
# The whole-flat-vector schedule above reduce-scatters AFTER the backward
# completes, so it can never overlap with compute. The streamed variant
# (docs/overlap.md "Streamed ZeRO-1") re-expresses ZeRO-1 over the SAME
# bucket partition the overlap fast path streams: each
# ``stream_param_groups`` bucket runs reduce-scatter inside the
# custom_vjp backward (``ops/fusion.fused_reduce_scatter``), each rank
# keeps only its shard's cotangents per bucket, the optimizer state is
# sharded per bucket, and the updated shards all-gather back. The bucket
# layout round-trips exactly through ``ops/fusion.plan_buckets`` — the
# backward and the update derive it from the same planners, so the shard
# a rank updates is bitwise the shard its backward reduced.


class Zero1State(NamedTuple):
    """Streamed-ZeRO-1 optimizer state: per-group, per-bucket optax
    states stacked on a leading ``[n_shards]`` axis (``opt["g<gi>"]
    ["b<bi>"]``), plus the optional SHARDED error-feedback residuals for
    the quantized wire (``ef`` mirrors ``opt``'s keys with f32
    ``[n_shards, k]`` leaves; None without EF). Shard rows are RANK-LOCAL
    by construction — each rank holds and updates only its row — so the
    guard's cross-rank digest agreement hashes only the structure, never
    the bytes (``guard/digest.strip_rank_local``)."""

    opt: Any
    ef: Any


def _zero1_groups(params, threshold_bytes, first_bucket_bytes):
    """Resolve the streamed group partition: returns ``(items, finish)``
    where ``items`` is ``[(label, sub_params)]`` in group order and
    ``finish(new_subs)`` rebuilds the full tree from the per-group
    results (``new_subs`` keyed by label)."""
    from ..ops import fusion as F

    children, rebuild, groups = F.zero1_group_layout(
        params, threshold_bytes, first_bucket_bytes
    )
    if children is None:
        def finish_single(new_subs):
            return new_subs["g0"]

        return [("g0", params)], finish_single

    items = []
    membership = []
    for gi, group in enumerate(groups):
        items.append((f"g{gi}", {str(i): children[i] for i in group}))
        membership.append(group)

    def finish(new_subs):
        out = list(children)
        for gi, group in enumerate(membership):
            sub = new_subs[f"g{gi}"]
            for i in group:
                out[i] = sub[str(i)]
        return rebuild(out)

    return items, finish


def init_zero1_stream_state(
    optimizer,
    params,
    n_shards: int,
    *,
    threshold_bytes: Optional[int] = None,
    first_bucket_bytes: Optional[int] = None,
    quantized: bool = False,
    error_feedback: Optional[bool] = None,
) -> Zero1State:
    """Build the :class:`Zero1State` for ``make_train_step(zero1=True)``:
    for every streamed group and fusion bucket, ``optimizer.init`` of
    each rank's packed parameter shard, stacked on a leading
    ``[n_shards]`` axis (shard the leading axis over the data axis /
    hierarchy tuple). Non-float and zero-length buckets carry no state
    (the update passes them through). ``error_feedback`` (default: on
    for the quantized wire) adds the zero sharded residuals."""
    from ..ops import fusion as F

    use_ef = bool(quantized) if error_feedback is None else bool(error_feedback)
    if use_ef and not quantized:
        raise ValueError("error_feedback=True requires quantized=True")
    items, _ = _zero1_groups(params, threshold_bytes, first_bucket_bytes)
    threshold = F.default_threshold_bytes(threshold_bytes)
    opt: Dict[str, Dict[str, Any]] = {}
    ef: Dict[str, Dict[str, Any]] = {}
    for label, sub in items:
        leaves = jax.tree.leaves(sub)
        g_opt: Dict[str, Any] = {}
        g_ef: Dict[str, Any] = {}
        for bi, bucket in enumerate(F.plan_buckets(leaves, threshold)):
            packed = F.pack_bucket([leaves[i] for i in bucket])
            total = packed.shape[0]
            if total == 0 or not jnp.issubdtype(packed.dtype, jnp.floating):
                continue
            k = F.zero1_shard_len(total, n_shards, quantized)
            buf = jnp.pad(packed, (0, n_shards * k - total))
            states = [
                optimizer.init(lax.dynamic_slice(buf, (r * k,), (k,)))
                for r in range(n_shards)
            ]
            g_opt[f"b{bi}"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *states
            )
            if use_ef:
                g_ef[f"b{bi}"] = jnp.zeros((n_shards, k), jnp.float32)
        opt[label] = g_opt
        if use_ef:
            ef[label] = g_ef
    return Zero1State(opt=opt, ef=ef if use_ef else None)


def zero1_posthoc_reduce(
    grads,
    *,
    op=None,
    axis_name: Any = DATA_AXIS,
    threshold_bytes: Optional[int] = None,
    first_bucket_bytes: Optional[int] = None,
    quantized: bool = False,
    ef: Any = None,
    label: str = "zero1-posthoc",
):
    """Post-hoc form of the streamed-zero1 reduction: the SAME group
    partition and per-bucket reduce-scatter the backward rule runs,
    applied to an already-computed gradient tree. Returns
    ``(shard_images, new_ef)`` — bitwise identical to the streamed
    path's output (one reduction, two call sites)."""
    from ..common.types import ReduceOp
    from ..ops import fusion as F

    op = ReduceOp.AVERAGE if op is None else op
    items, finish = _zero1_groups(
        grads, threshold_bytes, first_bucket_bytes
    )
    threshold = F.default_threshold_bytes(threshold_bytes)
    new_subs: Dict[str, Any] = {}
    new_ef: Dict[str, Any] = {}
    for gi, (glabel, sub) in enumerate(items):
        sub_ef = None
        if ef is not None:
            if glabel not in ef:
                raise ValueError(
                    f"sharded EF residual is missing group {glabel!r} — "
                    f"build it with init_zero1_stream_state"
                )
            sub_ef = ef[glabel]
        images, sub_new_ef = F.fused_reduce_scatter(
            sub,
            op=op,
            axis_name=axis_name,
            threshold_bytes=threshold,
            quantized=quantized,
            ef=sub_ef,
            label=f"{label}:{glabel}",
        )
        new_subs[glabel] = images
        if sub_new_ef is not None:
            new_ef[glabel] = sub_new_ef
    return finish(new_subs), (new_ef if ef is not None else None)


def zero1_stream_update(
    optimizer,
    params,
    opt_buckets,
    grads,
    *,
    axis_name: Any = DATA_AXIS,
    n_shards: int,
    threshold_bytes: Optional[int] = None,
    first_bucket_bytes: Optional[int] = None,
    quantized: bool = False,
):
    """The shard-local update against the bucketized shard layout:
    ``grads`` are SHARD IMAGES (from the streamed backward or
    :func:`zero1_posthoc_reduce`), ``opt_buckets`` is this rank's row of
    ``Zero1State.opt``. Per bucket: re-pack the image (recovering the
    reduce-scattered shard bitwise), slice this rank's parameter shard,
    optax-update it against the bucket's 1/N state, and all-gather the
    updated shards back into the full parameter layout (hierarchical
    all-gather on an axis tuple — only the 1/L shard crosses DCN).
    Returns ``(new_params, new_opt_buckets)``. Padding is proven
    zero-contribution: padded tails never leave the gather (the image is
    truncated to the bucket's true length before unpacking)."""
    import optax

    from ..ops import fusion as F

    axes = F._axes_of(axis_name)
    _check_axis_shards(
        axes if len(axes) > 1 else axes[0], n_shards, "zero1_stream_update"
    )
    items, finish = _zero1_groups(params, threshold_bytes, first_bucket_bytes)
    g_items, _ = _zero1_groups(grads, threshold_bytes, first_bucket_bytes)
    threshold = F.default_threshold_bytes(threshold_bytes)
    idx = F.zero1_axis_rank(axes if len(axes) > 1 else axes[0])
    ag_payload = 0
    new_subs: Dict[str, Any] = {}
    new_opt: Dict[str, Dict[str, Any]] = {}
    for (glabel, sub_p), (_, sub_g) in zip(items, g_items):
        p_leaves, treedef = jax.tree.flatten(sub_p)
        g_leaves = jax.tree.leaves(sub_g)
        states = opt_buckets.get(glabel, {})
        results = list(p_leaves)
        g_opt: Dict[str, Any] = {}
        for bi, bucket in enumerate(F.plan_buckets(p_leaves, threshold)):
            bkey = f"b{bi}"
            with jax.named_scope(_trace.SCOPE_EXCHANGE_PACK):
                packed_p = F.pack_bucket([p_leaves[i] for i in bucket])
            total = packed_p.shape[0]
            if (
                total == 0
                or not jnp.issubdtype(packed_p.dtype, jnp.floating)
            ):
                continue  # no shard state: parameters pass through
            if bkey not in states:
                raise ValueError(
                    f"zero1 optimizer state is missing bucket "
                    f"{glabel}/{bkey} — the state was built for a "
                    f"different partition (threshold/first-bucket/"
                    f"quantized knobs must match init_zero1_stream_state)"
                )
            k = F.zero1_shard_len(total, n_shards, quantized)
            pad = n_shards * k - total
            with jax.named_scope(_trace.SCOPE_EXCHANGE_PACK):
                packed_g = F.pack_bucket([g_leaves[i] for i in bucket])
                buf_p = jnp.pad(packed_p, (0, pad))
                buf_g = jnp.pad(packed_g, (0, pad))
                g_shard = lax.dynamic_slice(buf_g, (idx * k,), (k,))
                p_shard = lax.dynamic_slice(buf_p, (idx * k,), (k,))
            updates, new_state = optimizer.update(
                g_shard, states[bkey], p_shard
            )
            new_p_shard = optax.apply_updates(p_shard, updates)
            with jax.named_scope(_trace.SCOPE_EXCHANGE_REDUCE):
                if len(axes) > 1:
                    from ..topo import compositor as _compositor

                    full = _compositor.lower_allgather(
                        new_p_shard, axes, algorithm="two-level"
                    )
                else:
                    full = lax.all_gather(new_p_shard, axes[0], tiled=True)
            ag_payload += n_shards * k * np.dtype(packed_p.dtype).itemsize
            with jax.named_scope(_trace.SCOPE_EXCHANGE_UNPACK):
                unpacked = F.unpack_bucket(
                    full[:total], [p_leaves[i].shape for i in bucket]
                )
            for i, r in zip(bucket, unpacked):
                results[i] = r
            g_opt[bkey] = new_state
        stale = set(states) - set(g_opt)
        if stale:
            raise ValueError(
                f"zero1 optimizer state carries buckets {sorted(stale)} "
                f"the live partition of group {glabel!r} does not — "
                f"stale shard layout"
            )
        new_subs[glabel] = jax.tree.unflatten(treedef, results)
        new_opt[glabel] = g_opt
    if ag_payload:
        # Per-axis attribution (trace-time): the parameter all-gather is
        # always full precision — replicas must stay exact.
        F.record_axis_wire_bytes(ag_payload, axis_name, "all_gather")
    return finish(new_subs), new_opt
