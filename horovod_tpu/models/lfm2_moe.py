"""LFM2-MoE-style hybrid decoder (flax): gated short-convolution layers and
grouped-query attention layers in an order given as a LIST of kinds, a dense
SwiGLU feed-forward in the first ``n_dense_layers`` layers and a dropless
top-k sparse feed-forward, routed by sigmoid scores with a selection bias and
no shared expert, in the others; the head is the embedding's transpose.

The third language model beside ``models/transformer.py`` and
``models/qwen3_next.py``, trained like them: a ``loss_fn`` over its parameter
tree through ``hvd.make_train_step`` (``docs/models.md`` writes the layers'
equations out). float32 parameters; bfloat16 products with float32
accumulation; float32 logits, router, norms and tap sum.

Every submodule is explicitly named (``layer_0/conv/in_proj/kernel``,
``layer_1/self_attn/q_proj/kernel``, ``layer_0/feed_forward/w1/kernel``,
``layer_2/feed_forward/experts/gate``, ``layer_2/feed_forward/expert_bias``,
``norm/scale``, ...) so that ``parallel/rules.py`` can place leaves by regex.
``feed_forward/experts/*`` hold only the experts that live on this device
(``experts_held`` of ``n_experts``, from ``first_expert`` on): the layer
routes over all of them and computes its own experts' part of the result
(``parallel/ep.dropless_moe``).

Weight layout: ``in_proj`` columns are ``[B | C | u]``, three equal parts;
the convolution's kernel is ``[taps, channels]``, tap ``j`` multiplying the
token ``taps - 1 - j`` back.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from .. import trace as _trace
from ..ops.pallas_attention import flash_attention_bthd
from ..parallel.ep import _load_and_tiles, dropless_moe, route_top_k
from .qwen3_next import (RMSNorm, _dense, _normal, causal_depthwise_conv,
                         expert_load, rotary)
from .recompute import remat_layer

__all__ = ["Lfm2MoeConfig", "Lfm2MoeLM", "expert_load"]

CONV, ATTENTION = "conv", "full_attention"
NORM_EPS = 1e-6  # the published epsilon under the chosen weights' sum


def _norm(eps, dtype, name):
    """Plain RMSNorm: ``w * x * rsqrt(mean(x^2) + eps)``, ``w`` from ones."""
    return RMSNorm(eps, zero_centered=False, dtype=dtype, name=name)


class ShortConv(nn.Module):
    """The double-gated short convolution: ``[B | C | u] = in_proj(x)``, a
    causal depthwise convolution of ``B * u`` with no bias and no activation,
    ``out_proj(C * conv)``."""

    conv_kernel: int = 3
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        C = x.shape[-1]
        f32 = jnp.float32
        bcu = _dense(3 * C, "in_proj", self.dtype, self.init_std)(x)
        kernel = self.param("conv", lambda k, s: {"kernel": _normal(
            self.init_std)(k, s, f32)}, (self.conv_kernel, C))["kernel"]
        _trace.note_plan(short_conv_taps=self.conv_kernel)
        with jax.named_scope(_trace.SCOPE_SHORT_CONV):
            gate_in, gate_out, u = jnp.split(bcu, 3, axis=-1)
            # the first gate is a product of two bfloat16 projections and is
            # rounded as one; taps, their sum and the second gate are float32
            s = causal_depthwise_conv((gate_in * u).astype(f32), kernel)
            y = (gate_out.astype(f32) * s).astype(self.dtype)
        return _dense(C, "out_proj", self.dtype, self.init_std)(y)


class GroupedQueryAttention(nn.Module):
    """Grouped-query causal softmax attention with per-head q/k norms and
    rotary positions over the whole head; without the norms where
    ``qk_norm`` is off and without positions where ``positions`` is None
    (``models/nemotron_h.py``: its state-space layers carry position)."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 1e6
    eps: float = 1e-5
    qk_norm: bool = True
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, positions):
        B, T, C = x.shape
        H, KV, D = self.n_heads, self.n_kv_heads, self.head_dim
        dense = lambda n, name: _dense(n, name, self.dtype, self.init_std)
        with jax.named_scope(_trace.SCOPE_GQA_ATTN):
            q = dense(H * D, "q_proj")(x).reshape(B, T, H, D)
            k = dense(KV * D, "k_proj")(x).reshape(B, T, KV, D)
            v = dense(KV * D, "v_proj")(x).reshape(B, T, KV, D)
            if self.qk_norm:
                q = _norm(self.eps, jnp.float32, "q_layernorm")(q)
                k = _norm(self.eps, jnp.float32, "k_layernorm")(k)
            if positions is not None:
                rot = dict(rotary_dim=D, theta=self.rope_theta)
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
            return dense(C, "out_proj")(a.reshape(B, T, H * D))


class DenseMlp(nn.Module):
    """``w2(silu(w1 x) * w3 x)``."""

    hidden_dim: int
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: _dense(n, name, self.dtype, self.init_std)
        h = jax.nn.silu(dense(self.hidden_dim, "w1")(x)) * dense(
            self.hidden_dim, "w3")(x)
        return dense(x.shape[-1], "w2")(h)


class SparseMoe(nn.Module):
    """Sigmoid top-k routing over ``n_experts``: the choice by score plus
    ``expert_bias``, the weight by the score without it; this device's
    ``experts_held`` of them computed without dropping a token. No shared
    expert. ``expert_bias`` is a float32 leaf whose gradient is exactly zero
    (the published balancing update of it is not part of the model).
    ``norm_eps`` stands under the chosen weights' sum (LFM2's by default).
    ``models/xing4.py`` routes through this layer too, with its shared
    expert beside it, and ``models/nemotron_h.py`` with ``gated`` off: an
    expert is then ``down(activation(up x))`` and holds no ``gate`` leaf, and
    ``shared_dim`` gives the layer a shared expert of that form and that
    width (``shared_up_proj``, ``shared_down_proj``), added unweighted.
    ``models/ling.py`` limits the choice to the best ``topk_group`` of
    ``n_group`` groups of experts (``parallel/ep.route_top_k``)."""

    n_experts: int
    experts_held: int
    top_k: int
    expert_dim: int
    first_expert: int = 0
    norm_topk: bool = True
    routed_scale: float = 1.0
    use_expert_bias: bool = True
    norm_eps: float = NORM_EPS
    gated: bool = True
    activation: Callable = jax.nn.silu
    shared_dim: int = 0
    n_group: int = 1
    topk_group: int = 1
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        f32 = jnp.float32
        init = _normal(self.init_std)
        E, F = self.experts_held, self.expert_dim
        if self.shared_dim and self.gated:
            raise ValueError("the layer's own shared expert has no gate; a "
                             "gated one stands beside the layer "
                             "(models/xing4.py)")
        router = self.param("router", lambda k, s: {"kernel": init(k, s, f32)},
                            (C, self.n_experts))["kernel"]
        bias = (self.param("expert_bias", nn.initializers.zeros,
                           (self.n_experts,), f32)
                if self.use_expert_bias else None)
        experts = self.param("experts", lambda k: {
            **({"gate": init(jax.random.fold_in(k, 0), (E, C, F), f32)}
               if self.gated else {}),
            "up": init(jax.random.fold_in(k, 1), (E, C, F), f32),
            "down": init(jax.random.fold_in(k, 2), (E, F, C), f32),
        })
        routing = dict(top_k=self.top_k, norm_topk=self.norm_topk,
                       score="sigmoid", select_bias=bias,
                       norm_eps=self.norm_eps, scale=self.routed_scale,
                       n_group=self.n_group, topk_group=self.topk_group)
        flat = x.reshape(B * T, C)
        if self.is_mutable_collection("intermediates"):
            _, ids = route_top_k(flat, router, **routing)
            self.sow("intermediates", "held_load", _load_and_tiles(
                ids, self.first_expert, E, self.n_experts))
        y = dropless_moe(
            flat, router, experts.get("gate"), experts["up"], experts["down"],
            first_expert=self.first_expert, activation=self.activation,
            dtype=self.dtype, **routing).reshape(B, T, C)
        if self.shared_dim:
            with jax.named_scope(_trace.SCOPE_MOE_SHARED):
                # every chip of the group computes the shared expert alike
                dense = lambda n, name: _dense(
                    n, name, self.dtype, self.init_std)
                h = self.activation(dense(self.shared_dim, "shared_up_proj")(x))
                y = y + dense(C, "shared_down_proj")(h)
        return y.astype(self.dtype)


class DecoderLayer(nn.Module):
    cfg: Any  # Lfm2MoeConfig
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        h = _norm(c.eps, c.dtype, "operator_norm")(x)
        if self.kind == ATTENTION:
            mixed = GroupedQueryAttention(
                n_heads=c.n_heads, n_kv_heads=c.n_kv_heads,
                head_dim=c.head_dim, rope_theta=c.rope_theta, eps=c.eps,
                init_std=c.init_std, dtype=c.dtype, name="self_attn",
            )(h, positions)
        else:
            mixed = ShortConv(conv_kernel=c.conv_kernel, init_std=c.init_std,
                              dtype=c.dtype, name="conv")(h)
        x = x + mixed
        h = _norm(c.eps, c.dtype, "ffn_norm")(x)
        if self.dense:
            ffn = DenseMlp(hidden_dim=c.dense_dim, init_std=c.init_std,
                           dtype=c.dtype, name="feed_forward")
        else:
            ffn = SparseMoe(
                n_experts=c.n_experts, experts_held=c.experts_held,
                top_k=c.top_k, expert_dim=c.expert_dim,
                first_expert=c.first_expert, norm_topk=c.norm_topk,
                routed_scale=c.routed_scale,
                use_expert_bias=c.use_expert_bias, init_std=c.init_std,
                dtype=c.dtype, name="feed_forward")
        return x + ffn(h)


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published ``config.json``'s sizes under this repo's names, plus the
    share of the experts that lives here (``experts_held`` from
    ``first_expert`` on; all of them by default). ``layer_types`` lists each
    layer's mixer, ``"conv"`` or ``"full_attention"``; the first
    ``n_dense_layers`` layers have the dense feed-forward."""

    vocab_size: int
    layer_types: Tuple[str, ...]
    n_dense_layers: int = 2
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    conv_kernel: int = 3
    dense_dim: int = 7168
    n_experts: int = 32
    experts_held: int = 32
    first_expert: int = 0
    top_k: int = 4
    expert_dim: int = 1792
    norm_topk: bool = True
    routed_scale: float = 1.0
    use_expert_bias: bool = True
    eps: float = 1e-5
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        unknown = set(self.layer_types) - {CONV, ATTENTION}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}; a layer "
                             f"is {CONV!r} or {ATTENTION!r}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)


def _head_product(x, embedding):
    return jax.lax.dot_general(
        x, embedding.astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


@jax.custom_vjp
def tied_head(x, embedding):
    """Logits by the embedding's transpose (what ``Embed.attend`` computes),
    with float32 accumulation and float32 logits.

    The gradient is JAX's own; the backward only ties the table's gradient
    to the activations' with an optimization barrier. Without it XLA folds
    the table's product (``dlogits^T x``) into the optimizer's update of the
    embedding, which waits for the lookup's scatter-add at the very end of
    the backward, and the logits' gradient (2.1 GB at four 8192-token
    sequences over 16384 rows) stays alive under every layer's backward."""
    return _head_product(x, embedding)


def _tied_head_fwd(x, embedding):
    return _head_product(x, embedding), (x, embedding)


def _tied_head_bwd(res, g):
    return jax.lax.optimization_barrier(jax.vjp(_head_product, *res)[1](g))


tied_head.defvjp(_tied_head_fwd, _tied_head_bwd)


class Lfm2MoeLM(nn.Module):
    """``tokens [B, T] -> logits [B, T, vocab_size]`` float32."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, tokens, positions=None):
        c = self.cfg
        B, T = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        embed = nn.Embed(c.vocab_size, c.d_model, dtype=c.dtype,
                         embedding_init=_normal(c.init_std),
                         name="embed_tokens")
        x = embed(tokens)
        layer = remat_layer(DecoderLayer) if c.remat else DecoderLayer
        for i, kind in enumerate(c.layer_types):
            x = layer(cfg=c, kind=kind, dense=i < c.n_dense_layers,
                      name=f"layer_{i}")(x, positions)
        x = _norm(c.eps, c.dtype, "norm")(x)
        # the scope names the head's device time, which would otherwise
        # read as the token table's
        with jax.named_scope("lm_head"):
            return tied_head(x, embed.embedding)
