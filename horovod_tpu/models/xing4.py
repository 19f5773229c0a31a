"""Xing4.0-style decoder (flax): latent attention with keys of one width and
values of another, FOUR residual streams mixed round every sublayer by
learned per-token matrices (one of them made doubly stochastic by Sinkhorn
rounds), a dense SwiGLU feed-forward in the first ``n_dense_layers`` layers
and, in the others, a dropless top-k sparse feed-forward routed by sigmoid
scores with a selection bias, beside a shared expert; untied head.

The fourth language model beside ``models/transformer.py``,
``models/qwen3_next.py`` and ``models/lfm2_moe.py``, trained like them: a
``loss_fn`` over its parameter tree through ``hvd.make_train_step``
(``docs/models.md`` writes the layers' equations out). float32 parameters;
bfloat16 products with float32 accumulation; float32 logits, router, norms
and mixing matrices.

Every submodule is explicitly named (``layer_0/attn_hc/phi``,
``layer_0/self_attn/q_a_proj/kernel``, ``layer_0/mlp/w1/kernel``,
``layer_1/mlp/experts/gate``, ``layer_1/mlp/expert_bias`` (the published
``e_score_correction_bias``), ``layer_1/shared_expert/w1/kernel``,
``norm/scale``, ``lm_head/kernel``) so that ``parallel/rules.py`` can place
leaves by regex. ``mlp/experts/*`` hold only the experts that live on this
device (``experts_held`` of ``n_experts``, from ``first_expert`` on): the
layer routes over all of them and computes its own experts' part of the
result (``models/lfm2_moe.SparseMoe`` over ``parallel/ep.dropless_moe``).

Layout: the residual is stream-major, ``[n, B, T, C]``, so that a tile is
``(T, C)``; ``phi`` is ``[n * C, n + n + n * n]``, rows stream-major, columns
``[pre | post | res (row-major)]``; ``q_b_proj`` columns are per head
``[nope | rope]``, ``kv_a_proj`` columns ``[latent | rope]``, ``kv_b_proj``
columns per head ``[k nope | v]``; the rotary pairs are HALVES of the rope
part (element ``i`` with ``i + 32``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from .. import trace as _trace
from ..ops import stream_mix
from ..ops.pallas_attention import flash_attention_bthd
from ..ops.stream_mix import sinkhorn
from .lfm2_moe import DenseMlp, SparseMoe, _norm
from .qwen3_next import _dense, _normal, expert_load, rotary
from .recompute import remat_layer

__all__ = ["Xing4Config", "Xing4LM", "expert_load", "sinkhorn",
           "softmax_scale", "yarn_inv_freq"]


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's rotary frequencies (``[dim / 2]`` float32): a dimension that
    makes more than ``beta_fast`` rotations in ``original_max`` positions
    keeps its frequency, one that makes fewer than ``beta_slow`` has it
    divided by ``factor``, and those between are blended linearly in the
    dimension's index."""
    def correction(rotations):   # the index that makes that many rotations
        return dim * math.log(original_max / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    index = np.arange(dim // 2, dtype=np.float32)
    keep = 1.0 - np.clip((index - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = theta ** (-2.0 * index / dim)
    return (inv_freq / factor * (1.0 - keep) + inv_freq * keep).astype(
        np.float32)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(qk_dim: int, factor: float, mscale_all_dim: float) -> float:
    """``qk_dim ** -0.5`` times the square of YaRN's ``mscale_all_dim``
    correction (0.14468 at 192, factor 64, 1)."""
    return qk_dim ** -0.5 * _yarn_mscale(factor, mscale_all_dim) ** 2


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2's): queries through a
    low-rank bottleneck with a norm, keys and values from ONE normed latent a
    token, a rotary part of the query heads and one rotary key head shared
    by them all. The flash kernels take keys ``qk_nope_dim + qk_rope_dim``
    wide and values ``v_head_dim`` wide, neither padded to the other.

    ``models/ling.py`` runs this module too, by three fields of its
    configuration: ``q_lora_rank`` None (queries by one product,
    ``q_proj``, no bottleneck and no norm), ``rope_interleave`` (the rotary
    pairs are NEIGHBOURS, element ``2 i`` with ``2 i + 1``: the rope part is
    sorted into its even and its odd elements before the half rotation, in
    the queries and the key alike, which leaves every score what the
    rotation in place gives) and ``head_gate`` (``g_proj``: one sigmoid gate
    a head on the attention's output, in front of ``o_proj``)."""

    cfg: Any  # Xing4Config or models/ling.py's LingConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        B, T, C = x.shape
        H, dn, dr, dv = c.n_heads, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim
        dense = lambda n, name: _dense(n, name, c.dtype, c.init_std)
        _trace.note_plan(attn_qk_width=dn + dr, attn_v_width=dv)
        with jax.named_scope(_trace.SCOPE_LATENT_ATTN):
            if c.q_lora_rank is None:
                q = dense(H * (dn + dr), "q_proj")(x)
            else:
                c_q = _norm(c.eps, c.dtype, "q_a_layernorm")(
                    dense(c.q_lora_rank, "q_a_proj")(x))
                q = dense(H * (dn + dr), "q_b_proj")(c_q)
            q = q.reshape(B, T, H, dn + dr)
            kv_a = dense(c.kv_lora_rank + dr, "kv_a_proj")(x)
            c_kv = _norm(c.eps, c.dtype, "kv_a_layernorm")(
                kv_a[..., :c.kv_lora_rank])
            kv = dense(H * (dn + dv), "kv_b_proj")(c_kv).reshape(
                B, T, H, dn + dv)
            rot = dict(rotary_dim=dr, theta=c.rope_theta,
                       inv_freq=jnp.asarray(c.inv_freq()))
            # neighbours sorted into halves, or the halves as they are
            pairs = (lambda r: jnp.concatenate(
                [r[..., 0::2], r[..., 1::2]], -1)) if c.rope_interleave else (
                    lambda r: r)
            # cos and sin carry mscale / mscale_all_dim
            q_rope = rotary(pairs(q[..., dn:]), positions,
                            **rot) * c.rope_mscale()
            k_rope = rotary(pairs(kv_a[:, :, None, c.kv_lora_rank:]),
                            positions, **rot) * c.rope_mscale()
            q = jnp.concatenate([q[..., :dn], q_rope.astype(c.dtype)], -1)
            # the one rotary key head serves every query head
            k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
                k_rope.astype(c.dtype), (B, T, H, dr))], -1)
            # the kernel's event in a device trace is named by the innermost
            # scope: `attention.<n>`, as in models/transformer.py
            with jax.named_scope("attention"):
                a = flash_attention_bthd(q, k, kv[..., dn:], causal=True,
                                         sm_scale=c.softmax_scale())
            if c.head_gate:
                gate = jax.nn.sigmoid(dense(H, "g_proj")(x).astype(
                    jnp.float32))
                a = (a * gate[..., None]).astype(c.dtype)
            return dense(C, "o_proj")(a.reshape(B, T, H * dv))


class StreamMix(nn.Module):
    """One sublayer's read and write of the ``n`` residual streams
    (manifold-constrained hyper-connections): from the token's whole
    residual, RMS-normalised without gain, three small float32 maps: ``pre``
    (``[n]``, sigmoid) weighs the streams into the sublayer's input,
    ``post`` (``[n]``, twice a sigmoid) spreads its output over them, and
    ``res`` (``[n, n]``, the exponential of a clipped map, Sinkhorn-
    normalised) mixes the streams among themselves. :meth:`pre` before the
    sublayer, :meth:`post` after it, both through ``ops/stream_mix.py``: one
    pass over the streams a call and direction. The maps are stream-major
    (``[n, B, T]``, ``[n, n, B, T]``): a token's 4 x 4 matrix is sixteen
    planes, not a tile of four sublanes."""

    cfg: Any  # Xing4Config

    def setup(self):
        c = self.cfg
        n, f32 = c.hc_mult, jnp.float32
        self.phi = self.param("phi", _normal(c.init_std),
                              (n * c.d_model, 2 * n + n * n), f32)
        self.alpha = self.param("alpha", nn.initializers.ones, (3,), f32)
        self.b = self.param("b", nn.initializers.zeros, (2 * n + n * n,), f32)

    def pre(self, streams):
        """``streams [n, B, T, C] -> (h [B, T, C], maps)``; ``maps`` is what
        :meth:`post` takes: ``post``, ``res`` and the streams as the op
        carries them to its second half."""
        c = self.cfg
        h, *maps = stream_mix.pre(
            streams, self.phi, self.alpha, self.b, stream_mix.Spec(
                c.eps, c.hc_eps, tuple(c.hc_clamp), c.hc_sinkhorn_iters))
        return h, tuple(maps)

    def post(self, streams, y, maps):
        """``X'[i] = sum_j res[i, j] X[j] + post[i] y``. The streams are read
        through ``maps``, where :meth:`pre` left them: their cotangent then
        reaches its backward as an argument and is added to in place."""
        post, res, streams = maps
        return stream_mix.post(streams, y, post, res)


class DecoderLayer(nn.Module):
    cfg: Any  # Xing4Config
    dense: bool

    @nn.compact
    def __call__(self, streams, positions):
        c = self.cfg
        mix = StreamMix(c, name="attn_hc")
        h, maps = mix.pre(streams)
        y = LatentAttention(c, name="self_attn")(
            _norm(c.eps, c.dtype, "input_layernorm")(h), positions)
        streams = mix.post(streams, y, maps)
        mix = StreamMix(c, name="ffn_hc")
        h, maps = mix.pre(streams)
        h = _norm(c.eps, c.dtype, "post_attention_layernorm")(h)
        mlp = lambda width, name: DenseMlp(
            hidden_dim=width, init_std=c.init_std, dtype=c.dtype, name=name)
        if self.dense:
            y = mlp(c.dense_dim, "mlp")(h)
        else:
            y = SparseMoe(
                n_experts=c.n_experts, experts_held=c.experts_held,
                top_k=c.top_k, expert_dim=c.expert_dim,
                first_expert=c.first_expert, norm_topk=c.norm_topk,
                routed_scale=c.routed_scale, use_expert_bias=True,
                norm_eps=c.route_norm_eps, init_std=c.init_std,
                dtype=c.dtype, name="mlp")(h)
            with jax.named_scope(_trace.SCOPE_MOE_SHARED):
                # every chip of the group computes the shared expert alike
                y = y + mlp(c.shared_dim, "shared_expert")(h)
        return mix.post(streams, y, maps)


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    """The published ``config.json``'s sizes under this repo's names, plus the
    share of the experts that lives here (``experts_held`` from
    ``first_expert`` on; all of them by default). The first
    ``n_dense_layers`` layers have the dense feed-forward."""

    vocab_size: int
    n_layers: int = 40
    n_dense_layers: int = 2
    d_model: int = 3584
    n_heads: int = 32
    q_lora_rank: Optional[int] = 768
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_value: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rope_interleave: bool = False
    head_gate: bool = False
    dense_dim: int = 9216
    n_experts: int = 64
    experts_held: int = 64
    first_expert: int = 0
    top_k: int = 4
    expert_dim: int = 1024
    shared_dim: int = 1024
    norm_topk: bool = True
    routed_scale: float = 2.0
    route_norm_eps: float = 1e-20
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def inv_freq(self) -> np.ndarray:
        return yarn_inv_freq(self.qk_rope_dim, self.rope_theta,
                             self.rope_factor, self.rope_original_max,
                             self.rope_beta_fast, self.rope_beta_slow)

    def rope_mscale(self) -> float:
        return (_yarn_mscale(self.rope_factor, self.rope_mscale_value)
                / _yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))

    def softmax_scale(self) -> float:
        return softmax_scale(self.qk_nope_dim + self.qk_rope_dim,
                             self.rope_factor, self.rope_mscale_all_dim)


class Xing4LM(nn.Module):
    """``tokens [B, T] -> logits [B, T, vocab_size]`` float32."""

    cfg: Xing4Config

    @nn.compact
    def __call__(self, tokens, positions=None):
        c = self.cfg
        B, T = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        _trace.note_plan(hc_streams=c.hc_mult,
                         hc_sinkhorn_iters=c.hc_sinkhorn_iters)
        x = nn.Embed(c.vocab_size, c.d_model, dtype=c.dtype,
                     embedding_init=_normal(c.init_std),
                     name="embed_tokens")(tokens)
        # the embedding is copied into the streams; they are summed in
        # front of the final norm
        streams = jnp.broadcast_to(x, (c.hc_mult,) + x.shape)
        layer = remat_layer(DecoderLayer) if c.remat else DecoderLayer
        for i in range(c.n_layers):
            streams = layer(cfg=c, dense=i < c.n_dense_layers,
                            name=f"layer_{i}")(streams, positions)
        x = jnp.sum(streams.astype(jnp.float32), axis=0).astype(c.dtype)
        x = _norm(c.eps, c.dtype, "norm")(x)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                        kernel_init=_normal(c.init_std), name="lm_head")(x)
