"""Tensor (model) parallelism: Megatron-style column/row-parallel layers.

TPU-native extension beyond the reference framework (which is
model-agnostic DP only, SURVEY.md §2.3): weight matrices shard over a
``model`` mesh axis and activations stay sharded between the column- and
row-parallel halves of each block, so the only collective per MLP/attention
block is ONE psum on the row-parallel output — the classic Megatron
schedule, expressed with ``shard_map`` + ``lax.psum`` so XLA lays the
reduction onto ICI.

Layout (per device, axis size n):
  - column-parallel: W1 [D, F/n]; y = x @ W1 — output feature-sharded,
    no communication (the gelu runs sharded too);
  - row-parallel: W2 [F/n, D]; z = psum(y @ W2) — one allreduce brings the
    block output back replicated.

The same pair implements attention head sharding (QKV projection is
column-parallel over heads, the output projection row-parallel).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..common.compat import axis_size as _axis_size
from .mesh import DATA_AXIS

MODEL_AXIS = "model"

# Who writes the Megatron f/g conjugates.
#
# Inside a vma-CHECKED shard_map (make_tp_train_step, parallel/pp.py, the
# decode step) the varying-axes transpose inserts them itself: the psum of
# a row-parallel output transposes to the identity, and a replicated value
# entering sharded compute gets a cotangent psum. Spelling them out there
# would double-count.
#
# The composed DP x TP step (jax/__init__.py) runs UNCHECKED: its bucket-
# fused data-axis reduction packs model-replicated and model-sharded
# leaves into one buffer and its int8 ring returns ppermute results, and
# the checker can type neither as replicated. There every psum transposes
# to a psum, so the conjugates are custom VJPs.
#
# Each layer reads which of the two it is being traced in off the trace
# itself (:func:`_vma_checked`), so no caller has to say, and a layer
# reused under the other kind of shard_map is retraced for it (the
# setting is part of jit's cache key).


def _vma_checked(axis_name) -> bool:
    """Whether the enclosing shard_map types values by their varying axes
    (``check_vma=True``): only there is the axis index typed as varying."""
    return axis_name in jax.typeof(lax.axis_index(axis_name)).vma


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _psum_identity_bwd(x, axis_name):
    return lax.psum(x, axis_name)


_psum_identity_bwd.defvjp(
    lambda x, axis_name: (lax.psum(x, axis_name), None),
    lambda axis_name, _res, ct: (ct,),
)


def _psum_replicated_grad(x, axis_name):
    """``lax.psum`` whose cotangent is replicated (the consumer is an
    SPMD-identical loss), so its transpose is the identity."""
    if _vma_checked(axis_name):
        return lax.psum(x, axis_name)
    return _psum_identity_bwd(x, axis_name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _block_input_psum_bwd(x, axis_name):
    return x


def _block_input_bwd(axis_name, _res, ct):
    from ..ops import fusion as _fusion

    # The conjugate psum moves the same activation bytes the forward
    # g-psum moves — charge the model axis (trace-time).
    _fusion.record_axis_wire_bytes(
        ct.size * ct.dtype.itemsize, axis_name, "psum"
    )
    return (lax.psum(ct, axis_name),)


_block_input_psum_bwd.defvjp(
    lambda x, axis_name: (x, None), _block_input_bwd
)


def tp_block_input(x: jax.Array, *, axis_name: str = MODEL_AXIS) -> jax.Array:
    """Megatron's ``f`` operator — identity forward, cotangent psum over
    the model axis in the backward: the conjugate of the row-parallel
    ``g`` psum. Apply to a REPLICATED block input right before it feeds
    column-parallel shards; without it, each rank's cotangent for the
    block input carries only its OWN shard's partial, so everything
    upstream (earlier blocks' sharded weights, embeddings) differentiates
    wrong in multi-block stacks.

    In a checked shard_map the vma transpose inserts exactly this psum
    and the function is the identity (see the note at the top)."""
    if _vma_checked(axis_name):
        return x
    return _block_input_psum_bwd(x, axis_name)


def column_parallel(x: jax.Array, w_shard: jax.Array,
                    b_shard=None) -> jax.Array:
    """y = x @ W[:, shard] (+ b[shard]): output is feature-sharded; no
    communication. Call inside shard_map."""
    y = x @ w_shard
    if b_shard is not None:
        y = y + b_shard
    return y


def row_parallel(x_shard: jax.Array, w_shard: jax.Array, b_shard=None, *,
                 axis_name: str = MODEL_AXIS) -> jax.Array:
    """z = psum_i(x_i @ W[shard_i, :] + scatter_i(b_i)): the one collective
    of the Megatron block.

    The bias is genuinely SHARDED ([D/n] per rank, scattered to its offset
    inside the reduction) rather than replicated: a replicated-but-stacked
    bias would be typed device-varying by shard_map's replication checker,
    which flips the psum transpose from pbroadcast back to a sum and
    scales every upstream gradient by the axis size."""
    y = x_shard @ w_shard
    if b_shard is not None:
        n = _axis_size(axis_name)
        f = b_shard.shape[-1]
        if f * n != w_shard.shape[-1]:
            # A full-size bias would silently be added n times (the
            # scatter offset clamps); fail at trace time instead.
            raise ValueError(
                f"row_parallel bias must be the [D/n] shard: got {f} "
                f"features for D={w_shard.shape[-1]} over n={n} shards"
            )
        i = lax.axis_index(axis_name)
        full = jnp.zeros((w_shard.shape[-1],), b_shard.dtype)
        full = lax.dynamic_update_slice(full, b_shard, (i * f,))
        y = y + full
    # Per-axis attribution (trace-time, docs/parallelism.md): the one
    # Megatron psum of this half-block, charged to the MODEL axis so a
    # composed DP x TP program's wire split stays honest. Never
    # bucketized/quantized/re-planned — a plain psum XLA lays onto ICI.
    from ..ops import fusion as _fusion

    _fusion.record_axis_wire_bytes(
        y.size * y.dtype.itemsize, axis_name, "psum"
    )
    return _psum_replicated_grad(y, axis_name)


# ------------------------------------------------- fused TP overlap
#
# The collective-matmul path (docs/parallelism.md "Fused TP overlap"):
# the residual stream rides token-SHARDED between blocks, the column
# consume is an all-gather-matmul and the row produce a
# matmul-reduce-scatter (ops/collective_matmul.py), so the classic
# exposed psum disappears from the forward — ppermute chains carry the
# chunks while the MXU multiplies. ``psum(y@W) ==
# all_gather(reduce_scatter(y@W))`` over tokens keeps the fused block
# numerically equivalent to the classic one.

_OVERLAP_SCOPE: list = []


def overlap_scope(enabled):
    """Context manager pinning the fused-path selection during a trace
    (composed ``make_train_step`` traces the user loss in one, so
    ``make_train_step(rules=..., tp_overlap=...)`` reaches every
    ``tp_apply`` call without threading a flag through user code).
    ``enabled=None`` defers to the environment knob."""
    import contextlib

    @contextlib.contextmanager
    def scope():
        _OVERLAP_SCOPE.append(None if enabled is None else bool(enabled))
        try:
            yield
        finally:
            _OVERLAP_SCOPE.pop()

    return scope()


def tp_overlap_enabled(explicit=None) -> bool:
    """Resolve the fused-path switch: an explicit argument wins, then
    the innermost :func:`overlap_scope`, then ``HOROVOD_TP_OVERLAP``."""
    if explicit is not None:
        return bool(explicit)
    for v in reversed(_OVERLAP_SCOPE):
        if v is not None:
            return v
    from ..common import env as _env

    return _env._get_bool(_env.HOROVOD_TP_OVERLAP, False)


def tp_overlap_chunks() -> int:
    """The configured sub-chunk count (0 = auto: one chunk per rank)."""
    from ..common import env as _env

    return _env._get_int(_env.HOROVOD_TP_OVERLAP_CHUNKS, 0)


def tp_scatter_tokens(x: jax.Array, *,
                      axis_name: str = MODEL_AXIS) -> jax.Array:
    """Enter the fused path: slice this rank's token chunk (dim −2) off
    a REPLICATED activation — free of communication forward; the
    backward reassembles and psums the cotangent over the model axis
    (the embedding-boundary conjugate, like :func:`tp_block_input`)."""
    n = _axis_size(axis_name)
    if x.shape[-2] % n:
        raise ValueError(
            f"tp_scatter_tokens needs tokens ({x.shape[-2]}) divisible "
            f"by the model-axis size ({n})"
        )
    if _vma_checked(axis_name):
        return _scatter_tokens(x, axis_name)
    return _scatter_tokens_psum_bwd(x, axis_name)


def _scatter_tokens(x, axis_name):
    tc = x.shape[-2] // _axis_size(axis_name)
    i = lax.axis_index(axis_name)
    return lax.dynamic_slice_in_dim(x, i * tc, tc, axis=-2)


_scatter_tokens_psum_bwd = jax.custom_vjp(
    _scatter_tokens, nondiff_argnums=(1,)
)


def _scatter_tokens_bwd(axis_name, _res, ct):
    from ..ops import fusion as _fusion

    n = _axis_size(axis_name)
    shape = list(ct.shape)
    shape[-2] = shape[-2] * n
    i = lax.axis_index(axis_name)
    full = jnp.zeros(tuple(shape), ct.dtype)
    idx = [0] * len(shape)
    idx[-2] = i * ct.shape[-2]
    full = lax.dynamic_update_slice(full, ct, tuple(idx))
    _fusion.record_axis_wire_bytes(
        full.size * full.dtype.itemsize, axis_name, "psum"
    )
    return (lax.psum(full, axis_name),)


_scatter_tokens_psum_bwd.defvjp(
    lambda x, axis_name: (_scatter_tokens(x, axis_name), None),
    _scatter_tokens_bwd,
)


def tp_gather_tokens(x_shard: jax.Array, *,
                     axis_name: str = MODEL_AXIS) -> jax.Array:
    """Leave the fused path: all-gather the token chunks (dim −2) back
    to a replicated activation. The backward takes this rank's LOCAL
    cotangent slice — downstream cotangents are replicated-identical
    (the loss is pmean'd over the model axis), so the all_gather's
    psum-scatter transpose would n-fold count."""
    if _vma_checked(axis_name):
        return lax.all_gather(
            x_shard, axis_name, axis=x_shard.ndim - 2, tiled=True
        )
    return _gather_tokens_slice_bwd(x_shard, axis_name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _gather_tokens_slice_bwd(x_shard, axis_name):
    from ..ops import fusion as _fusion

    n = _axis_size(axis_name)
    _fusion.record_axis_wire_bytes(
        x_shard.size * x_shard.dtype.itemsize * n, axis_name,
        "allgather",
    )
    return lax.all_gather(
        x_shard, axis_name, axis=x_shard.ndim - 2, tiled=True
    )


def _gather_tokens_bwd(axis_name, _res, ct):
    tc = ct.shape[-2] // _axis_size(axis_name)
    i = lax.axis_index(axis_name)
    return (lax.dynamic_slice_in_dim(ct, i * tc, tc, axis=-2),)


_gather_tokens_slice_bwd.defvjp(
    lambda x_shard, axis_name: (
        _gather_tokens_slice_bwd(x_shard, axis_name), None
    ),
    _gather_tokens_bwd,
)


def tp_replicated_params(tree: Any, *,
                         axis_name: str = MODEL_AXIS) -> Any:
    """Mark a REPLICATED param subtree consumed by token-sharded compute
    on the fused path (block layernorms): each rank's grad covers only
    its token chunk, so the cotangents psum over the model axis — the
    same conjugate :func:`tp_block_input` provides, applied per leaf."""
    return jax.tree.map(
        lambda leaf: tp_block_input(leaf, axis_name=axis_name), tree
    )


def column_parallel_fused(x_shard: jax.Array, w_shard: jax.Array,
                          b_shard=None, *,
                          axis_name: str = MODEL_AXIS,
                          chunks: int = 0) -> jax.Array:
    """Fused column consume: ``y = all_gather(x_shard over tokens) @
    W[:, shard]`` with the gather chunks riding the bidirectional ring
    while the MXU multiplies — input is the token-sharded residual
    stream, output full-token and feature-sharded (what attention and
    the gelu need)."""
    from ..ops.collective_matmul import all_gather_matmul

    y = all_gather_matmul(
        x_shard, w_shard, axis_name=axis_name, chunks=chunks
    )
    if b_shard is not None:
        y = y + b_shard
    return y


def row_parallel_fused(x_shard: jax.Array, w_shard: jax.Array,
                       b_shard=None, *,
                       axis_name: str = MODEL_AXIS,
                       chunks: int = 0) -> jax.Array:
    """Fused row produce: ``z = reduce_scatter(x @ W[shard, :] over
    tokens)`` — partial products per destination chunk reduced along
    the ring; the classic psum never materializes. Output is the
    token-sharded residual stream
    (``all_gather(row_parallel_fused(...)) == row_parallel(...)``)."""
    from ..ops.collective_matmul import matmul_reduce_scatter

    z = matmul_reduce_scatter(
        x_shard, w_shard, axis_name=axis_name, chunks=chunks
    )
    if b_shard is not None:
        n = _axis_size(axis_name)
        f = b_shard.shape[-1]
        if f * n != w_shard.shape[-1]:
            raise ValueError(
                f"row_parallel_fused bias must be the [D/n] shard: got "
                f"{f} features for D={w_shard.shape[-1]} over n={n} "
                f"shards"
            )
        b_full = lax.all_gather(b_shard, axis_name, axis=0, tiled=True)
        z = z + b_full
    return z


def tp_mlp(params: dict, x: jax.Array, *,
           axis_name: str = MODEL_AXIS,
           activation: Callable = jax.nn.gelu) -> jax.Array:
    """One Megatron MLP block on sharded weights:
    ``params = {"w1": [D, F/n], "b1": [F/n], "w2": [F/n, D], "b2": [D/n]}``
    (every parameter is a true shard — see :func:`row_parallel` on why the
    output bias shards too).
    """
    h = activation(column_parallel(x, params["w1"], params.get("b1")))
    return row_parallel(h, params["w2"], params.get("b2"),
                        axis_name=axis_name)


def tp_attention(params: dict, x: jax.Array, *, head_dim: int,
                 axis_name: str = MODEL_AXIS,
                 causal: bool = True) -> jax.Array:
    """Megatron head-sharded self-attention: the QKV projection is
    column-parallel over heads (each rank holds H/n heads), attention runs
    on the local heads through the Pallas flash kernel, and the output
    projection is row-parallel — again exactly ONE psum per block.

    ``params = {"wqkv": [D, 3*(H/n)*Dh], "wo": [(H/n)*Dh, D],
    "bo": [D/n]}``; ``head_dim`` is static (shapes derive from it).
    """
    from ..ops.pallas_attention import flash_attention_bthd

    B, T, D = x.shape
    qkv = column_parallel(x, params["wqkv"])          # [B, T, 3*Hl*Dh]
    if qkv.shape[-1] % (3 * head_dim):
        raise ValueError(
            f"qkv width {qkv.shape[-1]} is not divisible by 3*head_dim "
            f"({3 * head_dim}); head_dim does not match the sharded weights"
        )
    hl = qkv.shape[-1] // (3 * head_dim)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, hl, head_dim)
    k = k.reshape(B, T, hl, head_dim)
    v = v.reshape(B, T, hl, head_dim)
    a = flash_attention_bthd(q, k, v, causal=causal)
    a = a.reshape(B, T, hl * head_dim)
    return row_parallel(a, params["wo"], params.get("bo"),
                        axis_name=axis_name)


def shard_attention_params(rng, d_model: int, n_heads: int, n_shards: int,
                           dtype=jnp.float32) -> dict:
    """Initialize full attention weights and return head-sharded stacks
    [n, ...] for placement via P(model)."""
    if n_heads % n_shards or d_model % n_heads or d_model % n_shards:
        raise ValueError(
            f"n_heads ({n_heads}) and d_model ({d_model}) must divide by "
            f"n_shards ({n_shards}); d_model by n_heads"
        )
    head_dim = d_model // n_heads
    hl = n_heads // n_shards
    k1, k2 = jax.random.split(rng)
    wqkv = jax.random.normal(k1, (d_model, 3 * d_model), dtype) * (
        d_model ** -0.5
    )
    wo = jax.random.normal(k2, (d_model, d_model), dtype) * (
        d_model ** -0.5
    )
    # Per-shard QKV columns: for each of q/k/v, take that shard's heads.
    wq, wk, wv = jnp.split(wqkv, 3, axis=1)
    f = hl * head_dim

    def col(w, i):
        return w[:, i * f:(i + 1) * f]

    return {
        "wqkv": jnp.stack([
            jnp.concatenate([col(wq, i), col(wk, i), col(wv, i)], axis=1)
            for i in range(n_shards)
        ]),
        "wo": jnp.stack([
            wo[i * f:(i + 1) * f, :] for i in range(n_shards)
        ]),
        "bo": jnp.zeros((n_shards, d_model // n_shards), dtype),
    }


def shard_mlp_params(rng, d_model: int, d_hidden: int, n_shards: int,
                     dtype=jnp.float32) -> dict:
    """Initialize full MLP weights and return them with a leading shard
    dim [n, ...] for placement via P(model) — rank i trains shard i."""
    k1, k2 = jax.random.split(rng)
    w1 = jax.random.normal(k1, (d_model, d_hidden), dtype) * (
        d_model ** -0.5
    )
    w2 = jax.random.normal(k2, (d_hidden, d_model), dtype) * (
        d_hidden ** -0.5
    )
    if d_hidden % n_shards or d_model % n_shards:
        raise ValueError(
            f"d_hidden ({d_hidden}) and d_model ({d_model}) must divide "
            f"by n_shards ({n_shards})"
        )
    f = d_hidden // n_shards
    return {
        "w1": jnp.stack([w1[:, i * f:(i + 1) * f] for i in range(n_shards)]),
        "b1": jnp.zeros((n_shards, f), dtype),
        "w2": jnp.stack([w2[i * f:(i + 1) * f, :] for i in range(n_shards)]),
        "b2": jnp.zeros((n_shards, d_model // n_shards), dtype),
    }


from ._stacked import init_stacked_state as init_tp_state  # noqa: E402


def make_tp_train_step(
    loss_fn: Callable,
    optimizer,
    mesh: Mesh,
    *,
    data_axis: str = DATA_AXIS,
    model_axis: str = MODEL_AXIS,
    donate: bool = True,
):
    """Build a jitted DP×TP train step.

    ``loss_fn(params_shard, batch_shard) -> scalar`` runs on the local
    (batch/nd, weight-shard) pair, calling :func:`tp_mlp`-style layers
    bound to ``model_axis``. Params enter with a leading shard dim
    [n_model, ...] placed P(model); batches [B, ...] placed P(data).

    Gradient reduction: sharded weights reduce over ``data`` only (each
    model rank owns its shard); the loss/replicated stats reduce over both
    axes.
    """
    from ..jax import _shard_map
    from ._stacked import stacked_train_update

    def step(params, opt_state, batch):
        params, opt_state, loss = stacked_train_update(
            optimizer, params, opt_state,
            jax.value_and_grad(lambda p: loss_fn(p, batch)), data_axis,
        )
        loss = lax.pmean(lax.pmean(loss, data_axis), model_axis)
        return params, opt_state, loss

    fn = _shard_map(
        step, mesh, check=True,
        in_specs=(P(model_axis), P(model_axis), P(data_axis)),
        out_specs=(P(model_axis), P(model_axis), P()),
    )
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())
