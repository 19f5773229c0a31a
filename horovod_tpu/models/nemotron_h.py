"""Nemotron-H-style hybrid decoder (flax): a stack whose every layer is ONE
sublayer behind one norm and one residual add, ``x + f(norm(x))``, in the
order a pattern string gives: ``M`` a Mamba-2 state-space mixer, ``*``
grouped-query attention without positions (``models/lfm2_moe
.GroupedQueryAttention`` with its norms and rotary off), ``E`` a dropless
top-k expert layer of ungated ``relu(.)^2`` experts, routed by sigmoid scores
with a selection bias, beside a shared expert; untied head.

The sixth language model, trained like the others: ``lm_loss`` over its
parameter tree through ``hvd.make_train_step`` (``docs/models.md`` writes the
layers' equations out). float32 parameters; bfloat16 products with float32
accumulation; float32 logits, router, norms, time steps, decays and
recurrent state.

Every submodule is explicitly named (``layer_0/norm/scale``,
``layer_0/mixer/in_proj/kernel``, ``layer_0/mixer/A_log``,
``layer_5/mixer/q_proj/kernel``, ``layer_1/mixer/experts/up``,
``layer_1/mixer/expert_bias``, ``norm_f/scale``, ``lm_head/kernel``) so that
``parallel/rules.py`` can place leaves by regex. ``mixer/experts/*`` hold
only the experts that live on this device (``experts_held`` of
``n_experts``, from ``first_expert`` on): the layer routes over all of them
and computes its own experts' part of the result (``models/lfm2_moe
.SparseMoe`` over ``parallel/ep.dropless_moe``).

Weight layout: ``in_proj`` columns are ``[z | x | B | C | dt]`` (``H P``,
``H P``, ``G N``, ``G N`` and ``H`` wide), heads and groups contiguous
inside each; the convolution's kernel is ``[taps, channels]`` over the
channels ``[x | B | C]``, tap ``j`` multiplying the token ``taps - 1 - j``
back; head ``h`` reads the ``B`` and ``C`` of group ``h // (H / G)``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn

from .. import trace as _trace
from ..ops.ssd import DEFAULT_CHUNK, ssd_chunked_packed
from ..parallel.ep import relu_squared
from .lfm2_moe import GroupedQueryAttention, SparseMoe, _norm
from .qwen3_next import _dense, _normal, causal_depthwise_conv, expert_load
from .recompute import remat_layer

__all__ = ["NemotronHConfig", "NemotronHLM", "lm_loss", "expert_load"]

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
ROUTE_NORM_EPS = 1e-20  # under the chosen weights' sum


class Mamba2Mixer(nn.Module):
    """``[z | xBC | dt] = in_proj(u)``; ``xBC = silu(conv(xBC) + bias)``;
    the selective scan of ``ops/ssd.py`` over ``x`` with ``dt = softplus(dt +
    dt_bias)`` and ``A = -exp(A_log)``; ``y * silu(z)`` RMS-normalised over
    each group's channels; ``out_proj``."""

    n_heads: int
    head_dim: int
    state_dim: int
    n_groups: int
    conv_kernel: int = 4
    chunk: int = DEFAULT_CHUNK
    eps: float = 1e-5
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        b, T, C = u.shape
        H, P, G, N = self.n_heads, self.head_dim, self.n_groups, self.state_dim
        inner, bc = H * P, G * N
        f32 = jnp.float32
        init = _normal(self.init_std)
        w_in = self.param("in_proj", lambda k, s: {"kernel": init(k, s, f32)},
                          (C, 2 * inner + 2 * bc + H))["kernel"]
        conv = self.param("conv", lambda k, s: {
            "kernel": init(k, s, f32),
            "bias": jnp.zeros(s[1:], f32)}, (self.conv_kernel, inner + 2 * bc))
        a_log = self.param("A_log", nn.initializers.zeros, (H,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H,), f32)
        skip = self.param("D", nn.initializers.ones, (H,), f32)
        scale = self.param("norm", lambda k, s: {"scale": jnp.ones(s, f32)},
                           (inner,))["scale"]
        with jax.named_scope(_trace.SCOPE_SSM_MIXER):
            # one matrix, two products: the time steps leave theirs in
            # float32 (64 columns), the rest in the compute dtype
            product = lambda w, out: jax.lax.dot_general(
                u.astype(self.dtype), w.astype(self.dtype),
                (((2,), (0,)), ((), ())), preferred_element_type=out)
            zxbc = product(w_in[:, :-H], self.dtype)
            dt = product(w_in[:, -H:], f32)
            z, xbc = zxbc[..., :inner], zxbc[..., inner:]
            with jax.named_scope(_trace.SCOPE_SSM_CONV):
                xbc = jax.nn.silu(
                    causal_depthwise_conv(xbc.astype(f32), conv["kernel"])
                    + conv["bias"]).astype(self.dtype)
            with jax.named_scope(_trace.SCOPE_SSM_SCAN):
                # x, B and C where the convolution left them, side by side
                y, _ = ssd_chunked_packed(
                    xbc, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log), skip,
                    groups=G, state=N, chunk=self.chunk, dtype=self.dtype)
            # the gate first, then the norm over each group's channels
            y = (y.reshape(b, T, inner) * jax.nn.silu(z.astype(f32))
                 ).reshape(b, T, G, inner // G)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                                  + self.eps)
            y = (y.reshape(b, T, inner) * scale).astype(self.dtype)
            return _dense(C, "out_proj", self.dtype, self.init_std)(y)


class DecoderLayer(nn.Module):
    cfg: Any  # NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        h = _norm(c.eps, c.dtype, "norm")(x)
        common = dict(init_std=c.init_std, dtype=c.dtype, name="mixer")
        if self.kind == MAMBA:
            mixer = Mamba2Mixer(
                n_heads=c.mamba_heads, head_dim=c.mamba_head_dim,
                state_dim=c.ssm_state, n_groups=c.ssm_groups,
                conv_kernel=c.conv_kernel, chunk=c.chunk, eps=c.eps, **common)
        elif self.kind == ATTENTION:
            # no q/k norm and, called without positions, no rotary
            mixer = functools.partial(GroupedQueryAttention(
                n_heads=c.n_heads, n_kv_heads=c.n_kv_heads,
                head_dim=c.head_dim, qk_norm=False, **common), positions=None)
        else:
            mixer = SparseMoe(
                n_experts=c.n_experts, experts_held=c.experts_held,
                top_k=c.top_k, expert_dim=c.expert_dim,
                first_expert=c.first_expert, norm_topk=c.norm_topk,
                routed_scale=c.routed_scale, norm_eps=ROUTE_NORM_EPS,
                gated=False, activation=relu_squared,
                shared_dim=c.shared_dim, **common)
        return x + mixer(h)


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The published ``config.json``'s sizes under this repo's names, plus the
    share of the experts that lives here (``experts_held`` from
    ``first_expert`` on; all of them by default). ``pattern`` gives each
    layer's one sublayer: ``M``, ``*`` or ``E``."""

    vocab_size: int
    pattern: str
    d_model: int = 2688
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    conv_kernel: int = 4
    chunk: int = DEFAULT_CHUNK
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    n_experts: int = 128
    experts_held: int = 128
    first_expert: int = 0
    top_k: int = 6
    expert_dim: int = 1856
    shared_dim: int = 3712
    norm_topk: bool = True
    routed_scale: float = 2.5
    eps: float = 1e-5
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        unknown = set(self.pattern) - {MAMBA, ATTENTION, EXPERTS}
        if unknown:
            raise ValueError(f"pattern holds {sorted(unknown)}; a layer is "
                             f"{MAMBA!r}, {ATTENTION!r} or {EXPERTS!r}")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)


class NemotronHLM(nn.Module):
    """``tokens [B, T] -> logits [B, T, vocab_size]`` float32."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, tokens):
        c = self.cfg
        x = nn.Embed(c.vocab_size, c.d_model, dtype=c.dtype,
                     embedding_init=_normal(c.init_std),
                     name="embed_tokens")(tokens)
        layer = remat_layer(DecoderLayer) if c.remat else DecoderLayer
        for i, kind in enumerate(c.pattern):
            x = layer(cfg=c, kind=kind, name=f"layer_{i}")(x)
        x = _norm(c.eps, c.dtype, "norm_f")(x)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                        kernel_init=_normal(c.init_std), name="lm_head")(x)


def lm_loss(model: NemotronHLM, params, batch):
    """Mean next-token cross entropy of ``batch = (tokens, labels)`` over the
    vocabulary the model holds, from float32 logits."""
    tokens, labels = batch
    logits = model.apply({"params": params}, tokens)
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()
