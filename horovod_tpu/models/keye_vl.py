"""Keye-VL-2.0-style language model (flax): every layer grouped-query
attention behind a learned top-k choice of keys (a lightning indexer, as
DeepSeek-V3.2-Exp's sparse attention) and a dropless softmax top-k expert
layer with no shared expert; multimodal rotary positions; untied head.

The fifth language model beside ``models/transformer.py``,
``models/qwen3_next.py``, ``models/lfm2_moe.py`` and ``models/xing4.py``,
trained like them: a ``loss_fn`` over its parameter tree through
``hvd.make_train_step`` (``docs/models.md`` writes the layers' equations
out). Its loss has TWO terms that feed disjoint leaves (:func:`lm_loss`): the
language-model loss reaches everything but the indexer, through the selected
(query, key) pairs alone; the indexer's objective, ``KL(mean over the heads
of their attention probabilities || softmax of the indexer's scores)`` on
each query's selected keys, reaches the indexer's leaves and nothing else
(its input and its target are detached, and the choice passes no gradient).

float32 parameters; bfloat16 products with float32 accumulation; float32
logits, router, norms, the indexer's weighted sum of rectified scores, the
k-th value and everything after it.

Every submodule is explicitly named (``layer_0/self_attn/q_proj/kernel``,
``layer_0/self_attn/indexer/wq/kernel``, ``layer_0/mlp/experts/gate``,
``norm/scale``, ...) so that ``parallel/rules.py`` can place leaves by regex.
``mlp/experts/*`` hold only the experts that live on this device
(``experts_held`` of ``n_experts``, from ``first_expert`` on).

The vision tower of the published model is not here: the language model
takes ``positions [3, B, T]`` (a temporal, a height and a width id a token),
and text gives all three rows ``arange(T)``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn

from .. import trace as _trace
from ..ops.pallas_attention import flash_attention, flashable
from ..ops.sparse_index import index_kl, select_top_k
from .qwen3_next import RMSNorm, SparseMoe, _dense, _normal, expert_load, rotary
from .recompute import remat_layer

__all__ = ["KeyeVLConfig", "KeyeVLLM", "lm_loss", "expert_load"]


def _norm(eps, dtype, name):
    """Plain RMSNorm: ``w * x * rsqrt(mean(x^2) + eps)``, ``w`` from ones."""
    return RMSNorm(eps, zero_centered=False, dtype=dtype, name=name)


def mrope_angle(positions, sections: Tuple[int, ...], theta: float):
    """``[B, T, sum(sections)]`` float32: the rotary angle of each frequency,
    frequency ``i`` turning with the position id its section names
    (``positions``: ``[3, B, T]``, temporal, height, width)."""
    half = sum(sections)
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    row = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                     total_repeat_length=half)
    pos = jnp.moveaxis(positions.astype(jnp.float32)[row], 0, -1)
    return pos * inv_freq


def rotate(x, angle):
    """Half-rotation rotary positions over the whole last axis (pairs ``(i,
    i + D / 2)``); ``x``: ``[B, T, H, D]``, ``angle``: ``[B, T, D / 2]``."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(angle)[:, :, None, :], jnp.sin(angle)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


class Indexer(nn.Module):
    """The lightning indexer's three products from the layer's (detached)
    input: ``(qI [B, T, J, D], kI [B, T, D], w [B, T, J] float32)``."""

    n_heads: int
    head_dim: int
    rope_theta: float
    eps: float
    init_std: float
    dtype: Any

    @nn.compact
    def __call__(self, g, positions):
        B, T, _ = g.shape
        J, D = self.n_heads, self.head_dim
        q = _dense(J * D, "wq", self.dtype, self.init_std)(g)
        k = _dense(D, "wk", self.dtype, self.init_std)(g)
        k = nn.LayerNorm(epsilon=self.eps, dtype=jnp.float32,
                         use_fast_variance=False,
                         name="k_norm")(k.astype(jnp.float32))
        rot = dict(rotary_dim=D, theta=self.rope_theta)
        q = rotary(q.reshape(B, T, J, D), positions[0], **rot)
        k = rotary(k[:, :, None, :], positions[0], **rot)[:, :, 0]
        w = _dense(J, "weights_proj", jnp.float32, self.init_std)(g)
        return (q.astype(self.dtype), k.astype(self.dtype),
                w * (J ** -0.5 * D ** -0.5))


class SparseIndexAttention(nn.Module):
    """Grouped-query attention over each query's ``top_k`` keys of the
    largest indexer score; returns ``(result, sum over the queries of their
    KL term)``."""

    cfg: Any  # KeyeVLConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        B, T, C = x.shape
        H, KV, D = c.n_heads, c.n_kv_heads, c.head_dim
        dense = lambda n, name: _dense(n, name, c.dtype, c.init_std)
        with jax.named_scope(_trace.SCOPE_SPARSE_INDEX):
            q_i, k_i, w = Indexer(
                n_heads=c.index_heads, head_dim=c.index_head_dim,
                rope_theta=c.rope_theta, eps=c.eps, init_std=c.init_std,
                dtype=c.dtype, name="indexer",
            )(jax.lax.stop_gradient(x), positions)
            selection, lse_i = select_top_k(q_i, k_i, w, top_k=c.index_top_k)
        with jax.named_scope(_trace.SCOPE_GQA_ATTN):
            q = dense(H * D, "q_proj")(x).reshape(B, T, H, D)
            k = dense(KV * D, "k_proj")(x).reshape(B, T, KV, D)
            v = dense(KV * D, "v_proj")(x).reshape(B, T, KV, D)
            angle = mrope_angle(positions, c.mrope_section, c.rope_theta)
            q = rotate(_norm(c.eps, jnp.float32, "q_norm")(q), angle)
            k = rotate(_norm(c.eps, jnp.float32, "k_norm")(k), angle)
            q, k = q.astype(c.dtype), k.astype(c.dtype)
            heads_first = lambda a: a.transpose(0, 2, 1, 3)
            # each key/value head serves H / KV query heads
            kr, vr = (heads_first(jnp.repeat(a, H // KV, axis=2))
                      for a in (k, v))
            if not flashable(T, T):
                raise ValueError(
                    f"a sequence of {T} tokens has no block the attention "
                    "kernel takes; pad it")
            # the kernel's event in a device trace is named by the innermost
            # scope: `attention.<n>`, as in models/transformer.py
            with jax.named_scope("attention"):
                a, lse = flash_attention(heads_first(q), kr, vr,
                                         sm_scale=D ** -0.5,
                                         selection=selection)
            out = dense(C, "o_proj")(heads_first(a).reshape(B, T, H * D))
        with jax.named_scope(_trace.SCOPE_SPARSE_INDEX_LOSS):
            kl = index_kl(jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
                          lse, selection, q_i, k_i, w, lse_i,
                          sm_scale=D ** -0.5)
        return out, kl


class DecoderLayer(nn.Module):
    cfg: Any  # KeyeVLConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        h = _norm(c.eps, c.dtype, "input_layernorm")(x)
        mixed, kl = SparseIndexAttention(c, name="self_attn")(h, positions)
        x = x + mixed
        h = _norm(c.eps, c.dtype, "post_attention_layernorm")(x)
        return x + SparseMoe(
            n_experts=c.n_experts, experts_held=c.experts_held,
            top_k=c.top_k, expert_dim=c.expert_dim, shared_dim=0,
            first_expert=c.first_expert, norm_topk=c.norm_topk,
            init_std=c.init_std, dtype=c.dtype, name="mlp",
        )(h), kl


@dataclasses.dataclass(frozen=True)
class KeyeVLConfig:
    """The published ``config.json``'s sizes under this repo's names (the
    indexer's from its ``sa_config``), plus the share of the experts that
    lives here (``experts_held`` from ``first_expert`` on; all of them by
    default)."""

    vocab_size: int
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    index_heads: int = 16
    index_head_dim: int = 64
    index_top_k: int = 2048
    n_experts: int = 128
    experts_held: int = 128
    first_expert: int = 0
    top_k: int = 8
    expert_dim: int = 768
    norm_topk: bool = True
    eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        if 2 * sum(self.mrope_section) != self.head_dim:
            raise ValueError(
                f"mrope_section {self.mrope_section} divides "
                f"{sum(self.mrope_section)} frequencies; a head of "
                f"{self.head_dim} has {self.head_dim // 2}")


class KeyeVLLM(nn.Module):
    """``tokens [B, T] -> (logits [B, T, vocab_size] float32, L_I)``: the
    indexer's objective is the mean over the queries of their KL term,
    summed over the layers. ``positions``: ``[3, B, T]`` or None (text)."""

    cfg: KeyeVLConfig

    @nn.compact
    def __call__(self, tokens, positions=None):
        c = self.cfg
        B, T = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T), (3, B, T))
        x = nn.Embed(c.vocab_size, c.d_model, dtype=c.dtype,
                     embedding_init=_normal(c.init_std),
                     name="embed_tokens")(tokens)
        # a layer is recomputed in its backward, all but what its kernels
        # named: the objective's gradient, which its one walk leaves beside
        # the value, and the attention's result and logsumexp
        layer = remat_layer(DecoderLayer) if c.remat else DecoderLayer
        index_loss = jnp.zeros((), jnp.float32)
        for i in range(c.n_layers):
            x, kl = layer(cfg=c, name=f"layer_{i}")(x, positions)
            index_loss = index_loss + kl / (B * T)
        x = _norm(c.eps, c.dtype, "norm")(x)
        logits = nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                          kernel_init=_normal(c.init_std), name="lm_head")(x)
        return logits, index_loss


def lm_loss(model: KeyeVLLM, params, batch, *, terms: bool = False):
    """``L_LM + L_I`` of ``batch = (tokens, labels)`` or ``(tokens, labels,
    positions)``: the mean next-token cross entropy and the indexer's
    objective at coefficient 1 (the two feed disjoint leaves, so the
    coefficient is the indexer's learning rate and nothing more).
    ``terms=True`` returns the pair instead of the sum."""
    tokens, labels, *positions = batch
    logits, index_loss = model.apply({"params": params}, tokens, *positions)
    lm = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
    return (lm, index_loss) if terms else lm + index_loss
