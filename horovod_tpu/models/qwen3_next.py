"""Qwen3-Next-style hybrid decoder (flax): Gated DeltaNet layers, a gated
softmax-attention layer every ``full_attention_interval``-th, and a dropless
top-k sparse feed-forward with a shared expert in every layer.

The second language model beside ``models/transformer.py``, and the first
with more than one kind of layer. It is trained like the first: a
``loss_fn`` over its parameter tree through ``hvd.make_train_step`` (see
``docs/models.md``, which also writes the layers' equations out). float32
parameters; bfloat16 products with float32 accumulation; float32 logits,
router, norms, decay and recurrent state.

Every submodule is explicitly named (``layer_0/linear_attn/in_proj_qkvz/
kernel``, ``layer_3/self_attn/q_proj/kernel``, ``layer_1/mlp/experts/gate``,
``norm/scale``, ...) so that ``parallel/rules.py`` can place leaves by regex.
``mlp/experts/*`` hold only the experts that live on this device
(``experts_held`` of ``n_experts``, from ``first_expert`` on): the layer
routes over all of them and computes its own experts' part of the result
(``parallel/ep.dropless_moe``).

Weight layout (seeded weights make any fixed layout the same function; the
published checkpoint interleaves per key head): ``in_proj_qkvz`` columns are
``[q | k | v | z]``, heads contiguous inside each; ``in_proj_ba`` is
``[b | a]``; the convolution's kernel is ``[taps, channels]`` over the
channels ``[q | k | v]``; ``q_proj`` columns are per head ``[query | gate]``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from .. import trace as _trace
from ..ops.gated_delta import DEFAULT_CHUNK, gated_delta_chunked
from ..ops.pallas_attention import flash_attention_bthd
from ..parallel.ep import _load_and_tiles, dropless_moe, route_top_k
from .recompute import remat_layer

_normal = nn.initializers.normal


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * (1 + scale)`` with ``scale`` from zeros
    (zero-centred), or ``* scale`` with ``scale`` from ones; float32 inside."""

    eps: float = 1e-6
    zero_centered: bool = True
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.zero_centered else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],), jnp.float32)
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + self.eps)
        y = y * (1.0 + scale if self.zero_centered else scale)
        return y.astype(self.dtype)


def _dense(features, name, dtype, std):
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name,
                    kernel_init=_normal(std))


def rotary(x, positions, *, rotary_dim: int, theta: float, inv_freq=None):
    """Half-rotation rotary positions on the first ``rotary_dim`` of the last
    axis; ``x``: ``[B, T, H, D]``, ``positions``: ``[B, T]``. float32.
    ``inv_freq`` (``[rotary_dim / 2]``) replaces ``theta``'s frequencies
    where a model rescales them."""
    half = rotary_dim // 2
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                             / rotary_dim)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq  # [B, T, half]
    cos = jnp.cos(angle)[:, :, None, :]
    sin = jnp.sin(angle)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :half], xf[..., half:rotary_dim], xf[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


class GatedAttention(nn.Module):
    """Grouped-query causal softmax attention with per-head q/k norms,
    partial rotary positions and a sigmoid output gate."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, positions):
        B, T, C = x.shape
        H, KV, D = self.n_heads, self.n_kv_heads, self.head_dim
        dense = lambda n, name: _dense(n, name, self.dtype, self.init_std)
        with jax.named_scope(_trace.SCOPE_GATED_ATTN):
            qg = dense(2 * H * D, "q_proj")(x).reshape(B, T, H, 2 * D)
            q, gate = qg[..., :D], qg[..., D:].reshape(B, T, H * D)
            k = dense(KV * D, "k_proj")(x).reshape(B, T, KV, D)
            v = dense(KV * D, "v_proj")(x).reshape(B, T, KV, D)
            q = RMSNorm(self.eps, dtype=jnp.float32, name="q_norm")(q)
            k = RMSNorm(self.eps, dtype=jnp.float32, name="k_norm")(k)
            rot = dict(rotary_dim=int(D * self.partial_rotary_factor),
                       theta=self.rope_theta)
            q = rotary(q, positions, **rot).astype(self.dtype)
            k = rotary(k, positions, **rot).astype(self.dtype)
            # each key/value head serves H / KV query heads
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
            # the kernel's event in a device trace is named by the innermost
            # scope: `attention.<n>`, as in models/transformer.py
            with jax.named_scope("attention"):
                a = flash_attention_bthd(q, k, v, causal=True,
                                         sm_scale=D ** -0.5)
            a = a.reshape(B, T, H * D) * jax.nn.sigmoid(
                gate.astype(jnp.float32)).astype(self.dtype)
            return dense(C, "o_proj")(a)


def causal_depthwise_conv(x, kernel):
    """``y_t = sum_j kernel[j] * x_{t - (taps - 1) + j}`` per channel, zeros
    before the sequence; ``x``: ``[B, T, C]``, ``kernel``: ``[taps, C]``."""
    taps = kernel.shape[0]
    T = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + T] * kernel[j] for j in range(taps))


class GatedDeltaNet(nn.Module):
    """Linear attention by the gated delta rule (``ops/gated_delta.py``):
    projections, a short causal convolution, the chunked rule, a gated
    per-head norm and the output projection."""

    n_k_heads: int
    n_v_heads: int
    head_k_dim: int
    head_v_dim: int
    conv_kernel: int = 4
    eps: float = 1e-6
    init_std: float = 0.02
    chunk: int = DEFAULT_CHUNK
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        Hk, Hv, dk, dv = (self.n_k_heads, self.n_v_heads, self.head_k_dim,
                          self.head_v_dim)
        f32 = jnp.float32
        dense = lambda n, name: _dense(n, name, self.dtype, self.init_std)
        qkvz = dense(2 * Hk * dk + 2 * Hv * dv, "in_proj_qkvz")(x)
        ba = _dense(2 * Hv, "in_proj_ba", f32, self.init_std)(x)
        qkv, z = qkvz[..., :2 * Hk * dk + Hv * dv], qkvz[..., -Hv * dv:]
        conv = self.param("conv", lambda k, s: {"kernel": _normal(
            self.init_std)(k, s, f32)}, (self.conv_kernel, qkv.shape[-1]))
        a_log = self.param("A_log", nn.initializers.zeros, (Hv,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (Hv,), f32)
        with jax.named_scope(_trace.SCOPE_GDN_CONV):
            qkv = jax.nn.silu(causal_depthwise_conv(
                qkv, conv["kernel"].astype(self.dtype)))
        q = qkv[..., :Hk * dk].reshape(B, T, Hk, dk)
        k = qkv[..., Hk * dk:2 * Hk * dk].reshape(B, T, Hk, dk)
        v = qkv[..., 2 * Hk * dk:].reshape(B, T, Hv, dv)
        beta = jax.nn.sigmoid(ba[..., :Hv])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., Hv:] + dt_bias)

        def l2(y):
            yf = y.astype(f32)
            return yf * jax.lax.rsqrt(
                jnp.sum(yf * yf, axis=-1, keepdims=True) + self.eps)

        # a key head serves the Hv // Hk value heads of its group: the rule
        # shares it, nothing is repeated here
        q = (l2(q) * dk ** -0.5).astype(self.dtype)
        k = l2(k).astype(self.dtype)
        with jax.named_scope(_trace.SCOPE_GDN_SCAN):
            o, _ = gated_delta_chunked(q, k, v, g, beta, chunk=self.chunk,
                                       dtype=self.dtype)
        o = RMSNorm(self.eps, zero_centered=False, dtype=f32, name="norm")(o)
        o = o * jax.nn.silu(z.reshape(B, T, Hv, dv).astype(f32))
        return dense(C, "out_proj")(o.reshape(B, T, Hv * dv).astype(self.dtype))


class SparseMoe(nn.Module):
    """Softmax top-k routing over ``n_experts``, this device's
    ``experts_held`` of them computed without dropping a token, plus the
    shared expert behind its sigmoid gate (none where ``shared_dim`` is 0:
    ``models/keye_vl.py``)."""

    n_experts: int
    experts_held: int
    top_k: int
    expert_dim: int
    shared_dim: int
    first_expert: int = 0
    norm_topk: bool = True
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        f32 = jnp.float32
        init = _normal(self.init_std)
        E, F = self.experts_held, self.expert_dim
        router = self.param("router", lambda k, s: {"kernel": init(k, s, f32)},
                            (C, self.n_experts))["kernel"]
        experts = self.param("experts", lambda k: {
            "gate": init(jax.random.fold_in(k, 0), (E, C, F), f32),
            "up": init(jax.random.fold_in(k, 1), (E, C, F), f32),
            "down": init(jax.random.fold_in(k, 2), (E, F, C), f32),
        })
        flat = x.reshape(B * T, C)
        if self.is_mutable_collection("intermediates"):
            _, ids = route_top_k(flat, router, top_k=self.top_k)
            self.sow("intermediates", "held_load", _load_and_tiles(
                ids, self.first_expert, E, self.n_experts))
        y = dropless_moe(
            flat, router, experts["gate"], experts["up"], experts["down"],
            top_k=self.top_k, first_expert=self.first_expert,
            norm_topk=self.norm_topk, dtype=self.dtype,
        ).reshape(B, T, C)
        if not self.shared_dim:   # a model without a shared expert
            return y.astype(self.dtype)
        with jax.named_scope(_trace.SCOPE_MOE_SHARED):
            dense = lambda n, name: _dense(n, name, self.dtype, self.init_std)
            h = (jax.nn.silu(dense(self.shared_dim, "shared_gate_proj")(x))
                 * dense(self.shared_dim, "shared_up_proj")(x))
            shared = dense(C, "shared_down_proj")(h)
            gate = jax.nn.sigmoid(_dense(1, "shared_gate", f32,
                                         self.init_std)(x))
            return (y + gate * shared.astype(f32)).astype(self.dtype)


class DecoderLayer(nn.Module):
    cfg: Any  # Qwen3NextConfig
    attention: bool

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        h = RMSNorm(c.eps, dtype=c.dtype, name="input_norm")(x)
        if self.attention:
            mixed = GatedAttention(
                n_heads=c.n_heads, n_kv_heads=c.n_kv_heads,
                head_dim=c.head_dim,
                partial_rotary_factor=c.partial_rotary_factor,
                rope_theta=c.rope_theta, eps=c.eps, init_std=c.init_std,
                dtype=c.dtype, name="self_attn",
            )(h, positions)
        else:
            mixed = GatedDeltaNet(
                n_k_heads=c.linear_k_heads, n_v_heads=c.linear_v_heads,
                head_k_dim=c.linear_k_dim, head_v_dim=c.linear_v_dim,
                conv_kernel=c.conv_kernel, eps=c.eps, init_std=c.init_std,
                chunk=c.chunk, dtype=c.dtype, name="linear_attn",
            )(h)
        x = x + mixed
        h = RMSNorm(c.eps, dtype=c.dtype, name="post_norm")(x)
        return x + SparseMoe(
            n_experts=c.n_experts, experts_held=c.experts_held,
            top_k=c.top_k, expert_dim=c.expert_dim, shared_dim=c.shared_dim,
            first_expert=c.first_expert, norm_topk=c.norm_topk,
            init_std=c.init_std, dtype=c.dtype, name="mlp",
        )(h)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The published ``config.json``'s sizes under this repo's names, plus the
    share of the experts that lives here (``experts_held`` from
    ``first_expert`` on; all of them by default)."""

    vocab_size: int
    d_model: int = 2048
    n_layers: int = 4
    full_attention_interval: int = 4
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_k_heads: int = 16
    linear_v_heads: int = 32
    linear_k_dim: int = 128
    linear_v_dim: int = 128
    conv_kernel: int = 4
    n_experts: int = 512
    experts_held: int = 512
    first_expert: int = 0
    top_k: int = 10
    expert_dim: int = 512
    shared_dim: int = 512
    norm_topk: bool = True
    eps: float = 1e-6
    init_std: float = 0.02
    chunk: int = DEFAULT_CHUNK
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def is_attention(self, i: int) -> bool:
        """Layer ``i`` is the softmax-attention layer of its period."""
        return (i + 1) % self.full_attention_interval == 0


class Qwen3NextLM(nn.Module):
    """``tokens [B, T] -> logits [B, T, vocab_size]`` float32."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, tokens, positions=None):
        c = self.cfg
        B, T = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        x = nn.Embed(c.vocab_size, c.d_model, dtype=c.dtype,
                     embedding_init=_normal(c.init_std),
                     name="embed_tokens")(tokens)
        layer = remat_layer(DecoderLayer) if c.remat else DecoderLayer
        for i in range(c.n_layers):
            x = layer(cfg=c, attention=c.is_attention(i),
                      name=f"layer_{i}")(x, positions)
        x = RMSNorm(c.eps, dtype=c.dtype, name="norm")(x)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                        kernel_init=_normal(c.init_std), name="lm_head")(x)


def expert_load(model, params, tokens):
    """``[expert layers, 3]`` int32 for one batch of a model whose sparse
    layers sow ``held_load`` (this one and ``models/lfm2_moe.py``): per
    expert layer, in layer order, the (token, expert) pairs that fall on the
    experts held here (what the layer's grouped products compute), the
    busiest held expert's load, and the tiles of rows the layer computes for
    that load (1 where the first tile holds it; more says the batch reached
    the overflow tiles). The numbers also go onto the plan notes."""
    _, state = model.apply({"params": params}, tokens,
                           mutable=["intermediates"])
    inter = state["intermediates"]
    load = jnp.stack([
        ffn["held_load"][0]
        for i in range(model.cfg.n_layers)
        for ffn in inter.get(f"layer_{i}", {}).values() if "held_load" in ffn
    ])
    _trace.note_plan(
        moe_pairs_held=[int(v) for v in load[:, 0]],
        moe_largest_load=[int(v) for v in load[:, 1]],
        moe_tiles_computed=[int(v) for v in load[:, 2]],
    )
    return load
