"""Ling-3.0-style hybrid decoder (flax): Kimi-delta-attention layers (a
delta rule whose state decays PER KEY CHANNEL, under a gate bounded below)
with a latent-attention layer every ``layer_group_size``-th, a dense SwiGLU
feed-forward in the first ``n_dense_layers`` layers and, in the others, a
dropless top-k sparse feed-forward routed by sigmoid scores with a selection
bias INSIDE the best groups of experts, beside a shared expert; untied head.

The seventh language model, trained like the others: ``lm_loss`` over its
parameter tree through ``hvd.make_train_step`` (``docs/models.md`` writes the
layers' equations out). float32 parameters; bfloat16 products with float32
accumulation; float32 logits, router, norms, gates, decays and recurrent
state.

Every submodule is explicitly named (``layer_0/linear_attn/q_proj/kernel``,
``layer_0/linear_attn/q_conv/kernel``, ``layer_0/linear_attn/A_log``,
``layer_5/self_attn/kv_a_proj/kernel``, ``layer_0/mlp/w1/kernel``,
``layer_1/mlp/experts/gate``, ``layer_1/mlp/expert_bias``,
``layer_1/shared_expert/w1/kernel``, ``norm/scale``, ``lm_head/kernel``) so
that ``parallel/rules.py`` can place leaves by regex. ``mlp/experts/*`` hold
only the experts that live on this device (``experts_held`` of
``n_experts``, from ``first_expert`` on): the layer routes over all of them
and computes its own experts' part of the result (``models/lfm2_moe
.SparseMoe`` over ``parallel/ep.dropless_moe``). The latent-attention layer
is ``models/xing4.LatentAttention`` without its query bottleneck, with
rotary pairs of neighbours and one output gate a head.

Weight layout: the projections' columns are heads contiguous (head ``h``
owns columns ``128 h .. 128 h + 127``); a convolution's kernel is ``[taps,
channels]``, tap ``j`` multiplying the token ``taps - 1 - j`` back;
``dt_bias`` is ``[H * d_k]`` in the columns' order, ``A_log`` ``[H]``, the
output norm's weight ``[d_v]``, one for all heads.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from .. import trace as _trace
from ..ops.kda import kda_chunked
from .lfm2_moe import DenseMlp, SparseMoe, _norm
from .qwen3_next import _dense, _normal, causal_depthwise_conv, expert_load
from .recompute import remat_layer
from .xing4 import LatentAttention

__all__ = ["LingConfig", "LingLM", "KdaMixer", "lm_loss", "expert_load"]

KDA, MLA = "kda", "mla"
ROUTE_NORM_EPS = 1e-20  # under the chosen weights' sum


class KdaMixer(nn.Module):
    """Kimi delta attention: ``q``, ``k``, ``v`` each through a product, a
    short causal convolution and SiLU; ``q`` and ``k`` L2-normalised a head;
    ``beta = sigmoid(b_proj x)`` a head; the gate ``g = lower_bound *
    sigmoid(exp(A_log) * (f_proj x + dt_bias))`` a key channel; the rule of
    ``ops/kda.py``; a per-head RMS norm under an element-wise sigmoid gate
    (``g_proj``); ``o_proj``."""

    n_heads: int
    head_k_dim: int
    head_v_dim: int
    conv_kernel: int = 4
    lower_bound: float = -5.0
    eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        H, dk, dv = self.n_heads, self.head_k_dim, self.head_v_dim
        f32 = jnp.float32
        init = _normal(self.init_std)
        dense = lambda n, name: _dense(n, name, self.dtype, self.init_std)
        kernel = lambda name, shape: self.param(
            name, lambda k, s: {"kernel": init(k, s, f32)}, shape)["kernel"]
        a_log = self.param("A_log", nn.initializers.zeros, (H,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H * dk,), f32)
        scale = self.param("o_norm", lambda k, s: {"scale": jnp.ones(s, f32)},
                           (dv,))["scale"]
        # bfloat16 operands, float32 out: a gate's error is summed over
        # the tokens its state lives through
        wide = lambda w: jax.lax.dot_general(
            x.astype(self.dtype), w.astype(self.dtype),
            (((2,), (0,)), ((), ())), preferred_element_type=f32)
        with jax.named_scope(_trace.SCOPE_KDA_MIXER):
            def conv_silu(name, width):
                y = dense(width, name + "_proj")(x)
                taps = kernel(name + "_conv", (self.conv_kernel, width))
                with jax.named_scope(_trace.SCOPE_KDA_CONV):
                    return jax.nn.silu(
                        causal_depthwise_conv(y.astype(f32), taps))

            q = conv_silu("q", H * dk).reshape(B, T, H, dk)
            k = conv_silu("k", H * dk).reshape(B, T, H, dk)
            v = conv_silu("v", H * dv).reshape(B, T, H, dv)
            gate_in = wide(kernel("f_proj", (C, H * dk)))
            beta_in = wide(kernel("b_proj", (C, H)))
            z = dense(H * dv, "g_proj")(x)
            with jax.named_scope(_trace.SCOPE_KDA_SCAN):
                l2 = lambda y: y * jax.lax.rsqrt(
                    jnp.sum(y * y, axis=-1, keepdims=True) + self.eps)
                g = self.lower_bound * jax.nn.sigmoid(
                    jnp.exp(a_log)[:, None]
                    * (gate_in + dt_bias).reshape(B, T, H, dk))
                o, _ = kda_chunked(
                    (l2(q) * dk ** -0.5).astype(self.dtype),
                    l2(k).astype(self.dtype), v.astype(self.dtype), g,
                    jax.nn.sigmoid(beta_in), dtype=self.dtype)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + self.eps) * scale
            o = o * jax.nn.sigmoid(z.reshape(B, T, H, dv).astype(f32))
            return dense(C, "o_proj")(
                o.reshape(B, T, H * dv).astype(self.dtype))


class DecoderLayer(nn.Module):
    cfg: Any  # LingConfig
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        h = _norm(c.eps, c.dtype, "input_layernorm")(x)
        if self.kind == MLA:
            x = x + LatentAttention(c, name="self_attn")(h, positions)
        else:
            x = x + KdaMixer(
                n_heads=c.n_heads, head_k_dim=c.head_dim,
                head_v_dim=c.head_dim, conv_kernel=c.conv_kernel,
                lower_bound=c.kda_lower_bound, eps=c.eps,
                init_std=c.init_std, dtype=c.dtype, name="linear_attn")(h)
        h = _norm(c.eps, c.dtype, "post_attention_layernorm")(x)
        mlp = lambda width, name: DenseMlp(
            hidden_dim=width, init_std=c.init_std, dtype=c.dtype, name=name)
        if self.dense:
            return x + mlp(c.dense_dim, "mlp")(h)
        y = SparseMoe(
            n_experts=c.n_experts, experts_held=c.experts_held,
            top_k=c.top_k, expert_dim=c.expert_dim,
            first_expert=c.first_expert, norm_topk=c.norm_topk,
            routed_scale=c.routed_scale, use_expert_bias=True,
            norm_eps=ROUTE_NORM_EPS, n_group=c.n_group,
            topk_group=c.topk_group, init_std=c.init_std, dtype=c.dtype,
            name="mlp")(h)
        with jax.named_scope(_trace.SCOPE_MOE_SHARED):
            # every chip of the group computes the shared expert alike
            y = y + mlp(c.shared_dim, "shared_expert")(h)
        return x + y


@dataclasses.dataclass(frozen=True)
class LingConfig:
    """The published ``config.json``'s sizes under this repo's names, plus the
    share of the experts that lives here (``experts_held`` from
    ``first_expert`` on; all of them by default). Layer ``l`` is latent
    attention where ``(l + 1) % layer_group_size == 0`` and Kimi delta
    attention elsewhere, unless ``layer_kinds`` lists the mixers (a cut in
    depth that does not start at the published layer 0); the first
    ``n_dense_layers`` layers have the dense feed-forward."""

    vocab_size: int
    n_layers: int = 42
    n_dense_layers: int = 2
    layer_group_size: int = 6
    layer_kinds: Optional[Tuple[str, ...]] = None
    d_model: int = 2560
    n_heads: int = 32
    head_dim: int = 128
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    dense_dim: int = 6144
    n_experts: int = 512
    experts_held: int = 512
    first_expert: int = 0
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    expert_dim: int = 768
    shared_dim: int = 768
    norm_topk: bool = True
    routed_scale: float = 2.5
    eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # what models/xing4.LatentAttention reads beside the sizes above
    q_lora_rank: Optional[int] = None
    rope_interleave: bool = True
    head_gate: bool = True

    def __post_init__(self):
        kinds = self.layer_kinds
        if kinds is not None and (len(kinds) != self.n_layers
                                  or set(kinds) - {KDA, MLA}):
            raise ValueError(f"layer_kinds lists {self.n_layers} mixers, "
                             f"each {KDA!r} or {MLA!r}; got {kinds}")

    def kind(self, i: int) -> str:
        if self.layer_kinds is not None:
            return self.layer_kinds[i]
        return MLA if (i + 1) % self.layer_group_size == 0 else KDA

    def inv_freq(self) -> np.ndarray:
        index = np.arange(self.qk_rope_dim // 2, dtype=np.float32)
        return (self.rope_theta ** (-2.0 * index / self.qk_rope_dim)).astype(
            np.float32)

    def rope_mscale(self) -> float:
        return 1.0

    def softmax_scale(self) -> float:
        return (self.qk_nope_dim + self.qk_rope_dim) ** -0.5


class LingLM(nn.Module):
    """``tokens [B, T] -> logits [B, T, vocab_size]`` float32."""

    cfg: LingConfig

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
            x = layer(cfg=c, kind=c.kind(i), dense=i < c.n_dense_layers,
                      name=f"layer_{i}")(x, positions)
        x = _norm(c.eps, c.dtype, "norm")(x)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                        kernel_init=_normal(c.init_std), name="lm_head")(x)


def lm_loss(model: LingLM, params, batch):
    """Mean next-token cross entropy of ``batch = (tokens, labels)`` over the
    vocabulary the model holds, from float32 logits."""
    tokens, labels = batch
    logits = model.apply({"params": params}, tokens)
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()
