"""The selective state-space scan (Mamba-2's recurrence) in its chunked form.

Per head ``h`` of group ``g`` (``H / G`` consecutive heads share a group's
``B`` and ``C``), with a state ``S`` of ``[P, N]`` that starts at zero::

    a_t = exp(dt_t * A_h)                 (A_h < 0: a scalar decay a head)
    S_t = a_t * S_{t-1} + dt_t * x_t B_t^T
    y_t = S_t C_t + D_h * x_t

:func:`ssd_recurrent` is that, one token a step under ``lax.scan`` (the
definition, what the tests hold the chunked form against, and what a decode
step will use). :func:`ssd_chunked` is what a model trains with: the state
takes no part in its own update, so inside a chunk of ``Q`` tokens the rule is
a masked product, every chunk's at once, and only the state crosses chunks.
With ``G_i`` the running sum of ``dt * A`` inside a chunk::

    L_ij = exp(G_i - G_j)  for i >= j, else 0
    y    = ((C B^T) * L) (dt * x)                         inside the chunk
         + exp(G_i) * (C_i S_in)                          from the chunks before
    S_out = exp(G_last) * S_in + sum_j exp(G_last - G_j) (dt_j x_j) B_j^T

``L`` is formed from DIFFERENCES of the running sum, masked before the
exponential: no decay is divided by another, so a head that forgets fast
(``exp(G)`` underflows inside a chunk) is exact. ``C B^T`` is one product a
GROUP; a group's ``B`` and ``C`` are read by its heads through the products'
batch dimensions and never repeated in memory. The chunks' own states
(``sum_j ...``) are one batched product, the pass over the chunks a
``lax.scan`` whose step is a multiply and an add of the ``[B, H, P, N]``
state, and what the chunks before add to ``y`` one batched product with the
states that scan leaves.

The MXU's operands are cast to ``dtype`` (bfloat16 from the model) and
accumulate in float32; ``dt``, the running sums, every exponential and the
state are float32. All of it is XLA: the gradients of ``x, dt, A, B, C, D``
are JAX's transpose of the chunked form, under a checkpoint of its own so
that a layer's backward holds the mask ``L`` (``[B, H, T / Q, Q, Q]``) once
and not in every factor of its product (``PERF.md`` section 6, PR 47).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import trace as _trace

DEFAULT_CHUNK = 128
_HIGHEST = lax.Precision.HIGHEST


def _check(x, B, C):
    H, G = x.shape[2], B.shape[2]
    if B.shape != C.shape:
        raise ValueError(f"B {B.shape} and C {C.shape} differ")
    if H % G:
        raise ValueError(f"{H} heads do not share {G} groups")
    return H // G


def ssd_recurrent(x, dt, A, B, C, D):
    """The rule token by token, float32. ``x``: ``[b, T, H, P]``; ``dt``
    (positive): ``[b, T, H]``; ``A`` (negative) and ``D``: ``[H]``; ``B`` and
    ``C``: ``[b, T, G, N]``. Returns ``(y [b, T, H, P], final state
    [b, H, P, N])``."""
    f32 = jnp.float32
    b, T, H, P = x.shape
    N = B.shape[-1]
    per = _check(x, B, C)
    A, D = A.astype(f32), D.astype(f32)

    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        B_t, C_t = (jnp.repeat(m, per, axis=1) for m in (B_t, C_t))
        S = (S * jnp.exp(dt_t * A)[..., None, None]
             + (dt_t[..., None] * x_t)[..., :, None] * B_t[..., None, :])
        y = jnp.einsum("bhpn,bhn->bhp", S, C_t, precision=_HIGHEST)
        return S, y + D[:, None] * x_t

    xs = tuple(jnp.moveaxis(m.astype(f32), 1, 0) for m in (x, dt, B, C))
    S, y = lax.scan(step, jnp.zeros((b, H, P, N), f32), xs)
    return jnp.moveaxis(y, 0, 1), S


def _dot(spec, a, b, dtype):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


@functools.partial(jax.checkpoint, static_argnums=(5, 6))
def _chunked(x, dt, A, B, C, Q, dtype):
    """``(y, final state)`` without ``D x``; ``T`` a multiple of ``Q``.
    Inside: ``c`` chunks, ``g`` groups, ``h`` heads of a group, ``i``/``j``
    tokens of a chunk, ``p`` and ``n`` the state's two widths."""
    f32 = jnp.float32
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    c, per = T // Q, H // G
    dt = dt.astype(f32).reshape(b, c, Q, G, per)
    xdt = (x.reshape(b, c, Q, G, per, P).astype(f32) * dt[..., None])
    Bc, Cc = (m.reshape(b, c, Q, G, N) for m in (B, C))
    # the running sum of dt * A inside a chunk: [b, c, Q, G, per], <= 0
    run = jnp.cumsum(dt * A.astype(f32).reshape(G, per), axis=2)
    last = run[:, :, -1]                                    # [b, c, G, per]
    row = jnp.arange(Q)
    lower = (row[:, None] >= row[None, :])[:, :, None, None]
    # exp(G_i - G_j) where i >= j, masked before the exponential (above the
    # diagonal the difference is positive and may overflow): [b, c, i, j, g, h]
    decay = jnp.exp(jnp.where(lower, run[:, :, :, None] - run[:, :, None],
                              -jnp.inf))
    cb = _dot("bcign,bcjgn->bcijg", Cc, Bc, dtype)          # one a group
    y = _dot("bcijgh,bcjghp->bcighp", cb[..., None] * decay, xdt, dtype)
    # every chunk's own state, as if it started from zero: [b, c, g, h, p, n]
    own = _dot("bcjghp,bcjgn->bcghpn",
               xdt * jnp.exp(last[:, :, None] - run)[..., None], Bc, dtype)

    def step(S, chunk):
        own_c, last_c = chunk
        return S * jnp.exp(last_c)[..., None, None] + own_c, S

    S, before = lax.scan(step, jnp.zeros((b, G, per, P, N), f32),
                         (jnp.moveaxis(own, 1, 0), jnp.moveaxis(last, 1, 0)))
    # what the chunks before add: exp(G_i) * (C_i S_in)
    y = y + jnp.exp(run)[..., None] * _dot(
        "bcign,cbghpn->bcighp", Cc, before, dtype)
    return y.reshape(b, T, H, P), S.reshape(b, H, P, N)


def ssd_chunked(x, dt, A, B, C, D, *, chunk: int = DEFAULT_CHUNK,
                dtype=jnp.bfloat16):
    """The rule in chunks of ``chunk`` tokens; shapes as
    :func:`ssd_recurrent`. A sequence that is no whole number of chunks is
    padded with tokens of ``dt = 0``, which leave the state as it is and are
    cut from ``y``. Returns ``(y [b, T, H, P] float32, final state
    [b, H, P, N] float32)``."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    _check(x, B, C)
    Q = min(chunk, T)
    pad = -T % Q
    _trace.note_plan(
        ssm_heads=H, ssm_head_dim=P, ssm_state=N, ssm_groups=G, ssm_chunk=Q,
        ssm_chunks=(T + pad) // Q, ssm_kernel=False,
    )
    inputs = (x, dt, B, C)
    if pad:
        inputs = tuple(jnp.pad(m, ((0, 0), (0, pad)) + ((0, 0),) * (m.ndim - 2))
                       for m in inputs)
    x_p, dt_p, B_p, C_p = inputs
    y, S = _chunked(x_p, dt_p, A, B_p, C_p, Q, jnp.dtype(dtype))
    return y[:, :T] + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32), S
