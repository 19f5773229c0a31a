"""The gated delta rule (Gated DeltaNet's recurrence) in its chunked form.

Per head, with a state ``S`` of ``[d_k, d_v]`` that starts at zero::

    S   = exp(g_t) * S
    d_t = beta_t * (v_t - S^T k_t)
    S   = S + k_t d_t^T
    o_t = S^T q_t

:func:`gated_delta_recurrent` is that, one token a step under ``lax.scan``
(the definition, what the tests hold the chunked form against, and what a
decode step will use). :func:`gated_delta_chunked` is what a model trains
with: inside a chunk of ``C`` tokens the rule is a unit lower-triangular
system, solved once for all chunks at a time by batched products, and only
the state crosses chunks, so a sequence of ``T`` tokens is ``T / C``
sequential steps of MXU-sized products and not ``T`` rank-one updates.

With ``G`` the running sum of ``g`` inside a chunk and ``K_beta = beta * K``::

    A  = -strict_lower((K_beta K^T) * exp(G_i - G_j))
    T  = (I - A)^-1                       (block forward substitution)
    U  = T V_beta          W = T (K_beta * exp(G))
    per chunk:  V' = U - W S
                O  = (Q * exp(G)) S + lower((Q K^T) * exp(G_i - G_j)) V'
                S  = exp(G_last) S + (K * exp(G_last - G))^T V'

The MXU's operands are cast to ``dtype`` (bfloat16 from the model); ``g``,
its sums and exponentials, the triangular inverse and the state are float32.
The backward is JAX's transpose of exactly these products: the scan keeps one
state per chunk, never one per token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import trace as _trace

DEFAULT_CHUNK = 64
_HIGHEST = lax.Precision.HIGHEST


def gated_delta_recurrent(q, k, v, g, beta, initial_state=None):
    """The rule token by token, float32. ``q``, ``k``: ``[B, T, H, d_k]``;
    ``v``: ``[B, T, H, d_v]``; ``g`` (log decay, <= 0) and ``beta``:
    ``[B, T, H]``. Returns ``(o [B, T, H, d_v], final state [B, H, d_k, d_v])``.
    """
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    S0 = (jnp.zeros((B, H, dk, dv), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None, None]
        d_t = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t, precision=_HIGHEST))
        S = S + k_t[..., :, None] * d_t[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta))
    S, o = lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1), S


def _mm(x, y):
    return jnp.matmul(x, y, precision=_HIGHEST)


@jax.custom_vjp
def unit_lower_inverse(m):
    """``m^-1`` of unit lower-triangular ``[..., C, C]`` matrices (``C`` a
    power of two) by block forward substitution: the inverse of
    ``[[P, 0], [R, Q]]`` is ``[[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``, from
    blocks of one (whose inverse is 1) up. Written on whole matrices so that
    every product is a dense ``C x C`` one: with ``X`` the inverse of the
    diagonal blocks of size ``b`` and ``R`` the blocks of ``m`` below them,
    ``X - X R X`` is the inverse of the diagonal blocks of size ``2b``
    (``X`` is block diagonal, so ``X R X`` lands where ``R`` is):
    ``log2(C)`` rounds of two float32 products. The backward is the
    inverse's own, ``-X^T g X^T``: two products, not the rounds' transposes."""
    C = m.shape[-1]
    if C & (C - 1):
        raise ValueError(f"chunk size {C} is not a power of two")
    row = jnp.arange(C)
    inv = jnp.eye(C, dtype=m.dtype)
    b = 1
    while b < C:
        i, j = row[:, None], row[None, :]
        # the lower-left b x b block of every diagonal block of 2b
        below = (i // (2 * b) == j // (2 * b)) & (i // b % 2 == 1) & (
            j // b % 2 == 0)
        r = jnp.where(below, m, 0.0)
        inv = inv - (r if b == 1 else _mm(_mm(inv, r), inv))
        b *= 2
    return jnp.broadcast_to(inv, m.shape)


def _inverse_fwd(m):
    inv = unit_lower_inverse(m)
    return inv, inv


def _inverse_bwd(inv, g):
    t = jnp.swapaxes(inv, -1, -2)
    return (-_mm(_mm(t, g), t),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _dot(a, b, spec, dtype):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def gated_delta_chunked(q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK,
                        dtype=jnp.bfloat16, initial_state=None):
    """The rule in chunks of ``chunk`` tokens; shapes as
    :func:`gated_delta_recurrent`, ``T`` a multiple of ``chunk``. Returns
    ``(o [B, T, H, d_v] float32, final state [B, H, d_k, d_v] float32)``."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    if T % C:
        raise ValueError(f"sequence length {T} is not a multiple of the "
                         f"chunk {C}; pad the sequence")
    N = T // C
    if _trace.ACTIVE:
        _trace.TAP.note_plan(gdn_chunk=C, gdn_heads=H, gdn_chunks=N)

    # [B, T, H, d] -> [B, H, N, C, d]
    chunks = lambda x: jnp.moveaxis(
        x.reshape((B, N, C) + x.shape[2:]), 3, 1)
    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gc = chunks(g.astype(f32))                       # [B, H, N, C]
    bc = chunks(beta.astype(f32))
    G = jnp.cumsum(gc, axis=-1)
    row = jnp.arange(C)
    lower = row[:, None] >= row[None, :]
    # exp(G_i - G_j) where i >= j (masked before the exponential: above the
    # diagonal the difference is positive and may overflow)
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                              -jnp.inf))
    k_beta = kc.astype(f32) * bc[..., None]
    v_beta = vc.astype(f32) * bc[..., None]
    strict = row[:, None] > row[None, :]
    kk = _dot(k_beta, kc, "...id,...jd->...ij", dtype)
    m = jnp.where(strict, kk * decay, 0.0) + jnp.eye(C, dtype=f32)  # I - A
    t_inv = unit_lower_inverse(m)
    g_last = G[..., -1]                               # [B, H, N]
    # what the scan multiplies, already in the MXU's dtype: a chunk's step
    # reads (and the backward keeps) half the bytes of float32
    u = _dot(t_inv, v_beta, "...ij,...jd->...id", dtype)
    w = _dot(t_inv, k_beta * jnp.exp(G)[..., None], "...ij,...jd->...id",
             dtype).astype(dtype)
    qk = (_dot(qc, kc, "...id,...jd->...ij", dtype) * decay).astype(dtype)
    q_g = (qc.astype(f32) * jnp.exp(G)[..., None]).astype(dtype)
    k_g = (kc.astype(f32)
           * jnp.exp(g_last[..., None] - G)[..., None]).astype(dtype)

    S0 = (jnp.zeros((B, H, dk, dv), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(S, x):
        u_c, w_c, qk_c, qg_c, kg_c, last = x
        v_new = u_c - _dot(w_c, S, "bhck,bhkv->bhcv", dtype)
        o_c = (_dot(qg_c, S, "bhck,bhkv->bhcv", dtype)
               + _dot(qk_c, v_new, "bhij,bhjv->bhiv", dtype))
        S = (S * jnp.exp(last)[..., None, None]
             + _dot(kg_c, v_new, "bhck,bhcv->bhkv", dtype))
        return S, o_c

    per_chunk = lambda x: jnp.moveaxis(x, 2, 0)       # N leads
    S, o = lax.scan(step, S0, tuple(
        per_chunk(x) for x in (u, w, qk, q_g, k_g, g_last)))
    # [N, B, H, C, d_v] -> [B, T, H, d_v]
    o = jnp.moveaxis(o, 0, 2)                         # [B, H, N, C, d_v]
    o = jnp.moveaxis(o, 1, 3).reshape(B, T, H, dv)
    return o, S
