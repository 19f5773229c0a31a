"""Decoder-only Transformer LM (flax), TPU-first, with pluggable attention.

The long-context flagship: ``attn_fn`` can be the dense reference, ring
attention, or Ulysses (``horovod_tpu.parallel.ring_attention``), letting the
same module run single-chip or sequence-parallel inside a shard_map without
code changes. bfloat16 compute with fp32 logits; positions are passed in so
sequence-sharded shards can feed their global offsets.

Every submodule is EXPLICITLY named (``block_0/attention/query/kernel``,
``mlp/up/bias``, ``ln_f/scale``, ...) so the param tree is a stable,
meaningful namespace the sharding-rules engine can place by regex
(``parallel/rules.py``; the shipped DP x TP table is
``analysis.sharding_rules.EXAMPLE_GPT_RULES``). :func:`tp_apply` is the
tensor-parallel functional forward of the SAME tree: it consumes the
leaves as (possibly TP-local) shards through ``parallel/tp.py``'s
column-/row-parallel layers — one psum per Megatron half-block — with
attention on the local heads through the Pallas flash kernel.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.pallas_attention import flash_attention_bthd
from .recompute import remat_layer


class Attention(nn.Module):
    """Multi-head self-attention with separate q/k/v projections — the
    layout the TP rules shard: a contiguous feature slice of one
    projection is whole heads, so ``P(None, "model")`` on each kernel is
    exactly Megatron head sharding."""

    n_heads: int
    dtype: Any = jnp.bfloat16
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        H = self.n_heads
        D = C // H
        # Default attention is the fused Pallas flash kernel (interpret
        # mode off-TPU); callers plug ring/Ulysses attention in via
        # attn_fn for sequence parallelism.
        attn = self.attn_fn or partial(flash_attention_bthd, causal=True)
        q = nn.Dense(C, use_bias=False, dtype=self.dtype, name="query")(x)
        k = nn.Dense(C, use_bias=False, dtype=self.dtype, name="key")(x)
        v = nn.Dense(C, use_bias=False, dtype=self.dtype, name="value")(x)
        shape = (B, T, H, D)
        a = attn(q.reshape(shape), k.reshape(shape), v.reshape(shape))
        a = a.reshape(B, T, C)
        return nn.Dense(C, use_bias=False, dtype=self.dtype, name="out")(a)


class Mlp(nn.Module):
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        C = x.shape[-1]
        h = nn.Dense(self.mlp_ratio * C, dtype=self.dtype, name="up")(x)
        h = nn.gelu(h)
        return nn.Dense(C, dtype=self.dtype, name="down")(h)


class Block(nn.Module):
    d_model: int
    n_heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        h = nn.LayerNorm(dtype=self.dtype, name="ln_1")(x)
        x = x + Attention(
            n_heads=self.n_heads, dtype=self.dtype, attn_fn=self.attn_fn,
            name="attention",
        )(h)
        h = nn.LayerNorm(dtype=self.dtype, name="ln_2")(x)
        x = x + Mlp(
            mlp_ratio=self.mlp_ratio, dtype=self.dtype, name="mlp"
        )(h)
        return x


class TransformerLM(nn.Module):
    vocab_size: int
    d_model: int = 256
    n_heads: int = 8
    n_layers: int = 4
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    attn_fn: Optional[Callable] = None
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, positions=None):
        """tokens: [B, T_local]; positions: [B, T_local] global positions
        (defaults to arange — only valid unsharded)."""
        B, T = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        tok_emb = nn.Embed(self.vocab_size, self.d_model,
                           dtype=self.dtype, name="embeddings")(tokens)
        pos_emb = nn.Embed(self.max_len, self.d_model,
                           dtype=self.dtype, name="pos_embeddings")(positions)
        x = tok_emb + pos_emb
        block = remat_layer(Block) if self.remat else Block
        for i in range(self.n_layers):
            x = block(
                d_model=self.d_model, n_heads=self.n_heads,
                dtype=self.dtype, attn_fn=self.attn_fn,
                name=f"block_{i}",
            )(x)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        logits = nn.Dense(self.vocab_size, use_bias=False,
                          dtype=jnp.float32, name="lm_head")(x)
        return logits


# --- tensor-parallel functional forward --------------------------------------
#
# The composed DP x TP fast path (docs/parallelism.md) cannot run the
# flax module on TP-local shards — flax shape-checks every param against
# the module's declared (full) feature sizes. tp_apply is the functional
# twin: same param NAMES, same math, but each leaf is consumed at
# whatever (local) shape the sharding rules left it, and the two
# row-parallel projections reduce with ONE psum each over the model
# axis (parallel/tp.py). With model_axis=None it is the dense reference
# the composed parity tests compare against.


def _layer_norm(x, p, dtype):
    """nn.LayerNorm parity (eps 1e-6, f32 statistics) on a raw
    {"scale","bias"} param dict."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(dtype)


def transformer_n_layers(params) -> int:
    return sum(1 for k in params if str(k).startswith("block_"))


def tp_apply(
    params,
    tokens,
    *,
    n_heads: int,
    model_axis: Optional[str] = None,
    positions=None,
    dtype: Any = jnp.bfloat16,
    causal: bool = True,
    tp_overlap: Optional[bool] = None,
):
    """Functional forward of the :class:`TransformerLM` param tree on
    (possibly TP-local) shards.

    ``n_heads`` is the GLOBAL head count (the head dim derives from the
    replicated ``d_model``); with ``model_axis`` bound each rank runs
    its local ``H/n`` heads and ``F/n`` MLP columns through
    ``parallel/tp.py`` — q/k/v and the MLP up-projection are
    column-parallel (no communication), attention-out and MLP-down are
    row-parallel (ONE psum each, biases scattered inside the reduction).
    Embeddings, norms, and the lm head consume replicated leaves. With
    ``model_axis=None`` every shard is full-size and the function is the
    dense single-chip reference (bitwise the same interpretation of the
    same tree).

    ``tp_overlap`` selects the FUSED collective-matmul path
    (docs/parallelism.md "Fused TP overlap"): the residual stream rides
    token-sharded between blocks, q/k/v ride ONE all-gather-matmul,
    attention-out and MLP-down become matmul-reduce-scatters — zero
    model-axis all-reduces inside the blocks. ``None`` defers to
    ``parallel.tp.tp_overlap_enabled()`` (the composed builder's
    ``overlap_scope`` / ``HOROVOD_TP_OVERLAP``)."""
    from ..parallel.tp import column_parallel, row_parallel, tp_block_input
    from ..parallel.tp import tp_overlap_enabled

    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    emb = params["embeddings"]["embedding"]
    pos = params["pos_embeddings"]["embedding"]
    x = (emb[tokens] + pos[positions]).astype(dtype)
    C = emb.shape[-1]
    if C % n_heads:
        raise ValueError(f"d_model {C} not divisible by n_heads {n_heads}")
    head_dim = C // n_heads

    if model_axis is not None and tp_overlap_enabled(tp_overlap):
        from ..common.compat import axis_size as _axis_size

        n = _axis_size(model_axis)
        if n > 1:
            if T % n:
                raise ValueError(
                    f"tp_overlap needs the sequence length ({T}) "
                    f"divisible by the model-axis size ({n}) — the "
                    f"fused path token-shards the residual stream"
                )
            return _tp_apply_fused(
                params, x, model_axis=model_axis, head_dim=head_dim,
                dtype=dtype, causal=causal,
            )

    def f(y):
        # Megatron's `f`: marks the replicated block input feeding
        # column-parallel shards (identity fwd, cotangent psum bwd).
        return y if model_axis is None else tp_block_input(
            y, axis_name=model_axis
        )

    def row(y, w, b=None):
        if model_axis is None:
            out = y @ w
            return out + b if b is not None else out
        return row_parallel(y, w, b, axis_name=model_axis)

    for i in range(transformer_n_layers(params)):
        bp = params[f"block_{i}"]
        h = f(_layer_norm(x, bp["ln_1"], dtype))
        att = bp["attention"]
        q = column_parallel(h, att["query"]["kernel"].astype(dtype))
        k = column_parallel(h, att["key"]["kernel"].astype(dtype))
        v = column_parallel(h, att["value"]["kernel"].astype(dtype))
        if q.shape[-1] % head_dim:
            raise ValueError(
                f"local q/k/v width {q.shape[-1]} is not whole heads of "
                f"dim {head_dim} — n_heads must divide by the model-axis "
                f"size"
            )
        hl = q.shape[-1] // head_dim
        shape = (B, T, hl, head_dim)
        a = flash_attention_bthd(
            q.reshape(shape), k.reshape(shape), v.reshape(shape),
            causal=causal,
        )
        a = a.reshape(B, T, hl * head_dim)
        x = x + row(a, att["out"]["kernel"].astype(dtype))
        h = f(_layer_norm(x, bp["ln_2"], dtype))
        mlp = bp["mlp"]
        u = jax.nn.gelu(column_parallel(
            h, mlp["up"]["kernel"].astype(dtype),
            mlp["up"]["bias"].astype(dtype),
        ))
        x = x + row(
            u, mlp["down"]["kernel"].astype(dtype),
            mlp["down"]["bias"].astype(dtype),
        )
    x = _layer_norm(x, params["ln_f"], dtype)
    w = params["lm_head"]["kernel"].astype(jnp.float32)
    return x.astype(jnp.float32) @ w


def _tp_apply_fused(params, x, *, model_axis, head_dim, dtype, causal):
    """Collective-matmul forward: token-sharded residual stream.

    Per block: LN on the token shard → q/k/v via ONE all-gather-matmul
    over the concatenated kernels (the gather chunks ride the ring while
    the MXU multiplies) → flash attention on full tokens / local heads →
    attention-out via matmul-reduce-scatter → LN → MLP up (all-gather-
    matmul, gelu) → MLP down (matmul-reduce-scatter). Tokens scatter
    once at entry (free slice) and gather once at exit before ln_f, so
    the lm head sees exactly the classic replicated activation —
    ``psum(y@W) == all_gather(reduce_scatter(y@W))`` over tokens makes
    the whole thing block-for-block equivalent to :func:`tp_apply`'s
    classic path with zero model-axis all-reduces in between. Block
    layernorm params route through ``tp_replicated_params`` (their grads
    are per-token-chunk partial on the sharded stream)."""
    from ..parallel.tp import (
        column_parallel_fused,
        row_parallel_fused,
        tp_gather_tokens,
        tp_replicated_params,
        tp_scatter_tokens,
    )

    B, T, C = x.shape
    x = tp_scatter_tokens(x, axis_name=model_axis)  # [B, T/n, C]
    for i in range(transformer_n_layers(params)):
        bp = params[f"block_{i}"]
        ln1 = tp_replicated_params(bp["ln_1"], axis_name=model_axis)
        h = _layer_norm(x, ln1, dtype)
        att = bp["attention"]
        wqkv = jnp.concatenate(
            [
                att["query"]["kernel"].astype(dtype),
                att["key"]["kernel"].astype(dtype),
                att["value"]["kernel"].astype(dtype),
            ],
            axis=-1,
        )
        qkv = column_parallel_fused(h, wqkv, axis_name=model_axis)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        if q.shape[-1] % head_dim:
            raise ValueError(
                f"local q/k/v width {q.shape[-1]} is not whole heads of "
                f"dim {head_dim} — n_heads must divide by the model-axis "
                f"size"
            )
        hl = q.shape[-1] // head_dim
        shape = (B, T, hl, head_dim)
        a = flash_attention_bthd(
            q.reshape(shape), k.reshape(shape), v.reshape(shape),
            causal=causal,
        )
        a = a.reshape(B, T, hl * head_dim)
        x = x + row_parallel_fused(
            a, att["out"]["kernel"].astype(dtype), axis_name=model_axis
        )
        ln2 = tp_replicated_params(bp["ln_2"], axis_name=model_axis)
        h = _layer_norm(x, ln2, dtype)
        mlp = bp["mlp"]
        u = jax.nn.gelu(column_parallel_fused(
            h, mlp["up"]["kernel"].astype(dtype),
            mlp["up"]["bias"].astype(dtype), axis_name=model_axis,
        ))
        x = x + row_parallel_fused(
            u, mlp["down"]["kernel"].astype(dtype),
            mlp["down"]["bias"].astype(dtype), axis_name=model_axis,
        )
    x = tp_gather_tokens(x, axis_name=model_axis)  # [B, T, C] replicated
    x = _layer_norm(x, params["ln_f"], dtype)
    w = params["lm_head"]["kernel"].astype(jnp.float32)
    return x.astype(jnp.float32) @ w


def tp_decode_apply(
    params,
    tokens,
    positions,
    cache,
    page_table,
    *,
    n_heads: int,
    model_axis: Optional[str] = None,
    dtype: Any = jnp.bfloat16,
):
    """One incremental decode step of the SAME param tree over a paged
    KV cache (``serve/kvcache.py``; docs/serving.md).

    ``tokens``/``positions``: [B] current token ids and their global
    positions. ``cache``: the decode-state pytree — per layer,
    ``block_i/attention/cache_k``/``cache_v`` of shape [num_pages,
    page_size, H(local), head_dim]. ``page_table``: [B, max_pages] int32
    page ids mapping each slot's logical positions onto physical pages
    (slot position p lives in page ``page_table[b, p // page_size]`` at
    offset ``p % page_size``); padded slots point every entry at the
    reserved scratch page 0, which the attention mask keeps them from
    ever reading meaningfully.

    Tensor parallelism mirrors :func:`tp_apply` exactly: q/k/v and the
    MLP up-projection are column-parallel (whole local heads — the k/v
    written to the cache are the LOCAL heads, which is why the cache
    rule shards the head dim over "model"), attention-out and MLP-down
    are row-parallel with ONE psum each. With ``model_axis=None`` it is
    the dense single-chip decode the parity tests compare against the
    full-recompute :func:`tp_apply` reference.

    Returns ``(logits [B, vocab] f32, new_cache)``. The new token's k/v
    are written BEFORE attention reads, so position p attends over
    [0..p] inclusive — identical coverage to the causal full recompute.
    """
    from ..parallel.tp import column_parallel, row_parallel, tp_block_input

    B = tokens.shape[0]
    page_size = None
    emb = params["embeddings"]["embedding"]
    pos = params["pos_embeddings"]["embedding"]
    x = (emb[tokens] + pos[positions]).astype(dtype)  # [B, C]
    C = emb.shape[-1]
    if C % n_heads:
        raise ValueError(f"d_model {C} not divisible by n_heads {n_heads}")
    head_dim = C // n_heads

    def f(y):
        return y if model_axis is None else tp_block_input(
            y, axis_name=model_axis
        )

    def row(y, w, b=None):
        if model_axis is None:
            out = y @ w
            return out + b if b is not None else out
        return row_parallel(y, w, b, axis_name=model_axis)

    new_cache = {k: dict(v) for k, v in cache.items()}
    batch_ix = jnp.arange(B)
    for i in range(transformer_n_layers(params)):
        bp = params[f"block_{i}"]
        ck = cache[f"block_{i}"]["attention"]["cache_k"]
        cv = cache[f"block_{i}"]["attention"]["cache_v"]
        page_size = ck.shape[1]
        h = f(_layer_norm(x, bp["ln_1"], dtype))
        att = bp["attention"]
        q = column_parallel(h, att["query"]["kernel"].astype(dtype))
        k = column_parallel(h, att["key"]["kernel"].astype(dtype))
        v = column_parallel(h, att["value"]["kernel"].astype(dtype))
        if q.shape[-1] % head_dim:
            raise ValueError(
                f"local q/k/v width {q.shape[-1]} is not whole heads of "
                f"dim {head_dim} — n_heads must divide by the model-axis "
                f"size"
            )
        hl = q.shape[-1] // head_dim
        q = q.reshape(B, hl, head_dim)
        k = k.reshape(B, hl, head_dim).astype(ck.dtype)
        v = v.reshape(B, hl, head_dim).astype(cv.dtype)
        # Write this position's k/v into its page BEFORE reading.
        page = page_table[batch_ix, positions // page_size]
        off = positions % page_size
        ck = ck.at[page, off].set(k)
        cv = cv.at[page, off].set(v)
        new_cache[f"block_{i}"] = {
            "attention": {"cache_k": ck, "cache_v": cv}
        }
        # Gather each slot's logical cache view through its page table
        # and attend over [0..position].
        keys = ck[page_table]    # [B, MP, page_size, hl, D]
        vals = cv[page_table]
        T = keys.shape[1] * keys.shape[2]
        keys = keys.reshape(B, T, hl, head_dim)
        vals = vals.reshape(B, T, hl, head_dim)
        valid = jnp.arange(T)[None, :] <= positions[:, None]  # [B, T]
        scores = jnp.einsum(
            "bhd,bthd->bth", q.astype(jnp.float32),
            keys.astype(jnp.float32),
        ) / jnp.sqrt(jnp.float32(head_dim))
        scores = jnp.where(valid[:, :, None], scores, jnp.float32(-1e30))
        p = jax.nn.softmax(scores, axis=1)
        a = jnp.einsum(
            "bth,bthd->bhd", p, vals.astype(jnp.float32)
        ).astype(dtype).reshape(B, hl * head_dim)
        x = x + row(a, att["out"]["kernel"].astype(dtype))
        h = f(_layer_norm(x, bp["ln_2"], dtype))
        mlp = bp["mlp"]
        u = jax.nn.gelu(column_parallel(
            h, mlp["up"]["kernel"].astype(dtype),
            mlp["up"]["bias"].astype(dtype),
        ))
        x = x + row(
            u, mlp["down"]["kernel"].astype(dtype),
            mlp["down"]["bias"].astype(dtype),
        )
    x = _layer_norm(x, params["ln_f"], dtype)
    w = params["lm_head"]["kernel"].astype(jnp.float32)
    return x.astype(jnp.float32) @ w, new_cache


def lm_loss(logits, labels):
    """Mean next-token cross entropy (no optax dependency)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(ll)


def make_gpt_loss_fn(
    n_heads: int,
    *,
    model_axis: Optional[str] = None,
    dtype: Any = jnp.bfloat16,
    tp_overlap: Optional[bool] = None,
):
    """``loss_fn(params, (tokens, labels))`` over :func:`tp_apply` — the
    loss the composed ``make_train_step(rules=...)`` trains and the
    dense reference (``model_axis=None``) the parity tests compare
    against. ``tp_overlap`` pins the fused collective-matmul path
    (``None`` defers to the builder's ``overlap_scope`` / the
    ``HOROVOD_TP_OVERLAP`` knob)."""

    def loss_fn(params, batch):
        tokens, labels = batch
        logits = tp_apply(
            params, tokens, n_heads=n_heads, model_axis=model_axis,
            dtype=dtype, tp_overlap=tp_overlap,
        )
        return lm_loss(logits, labels)

    return loss_fn
