"""The delta rule with a decay PER KEY CHANNEL (Kimi delta attention's
recurrence) in its chunked form.

Per head, with a state ``S`` of ``[d_k, d_v]`` that starts at zero and a log
decay ``g_t`` of ``d_k`` numbers a token (``<= 0``)::

    S   = diag(exp(g_t)) S
    d_t = beta_t * (v_t - S^T k_t)
    S   = S + k_t d_t^T
    o_t = S^T q_t

:func:`kda_recurrent` is that, one token a step under ``lax.scan`` (the
definition, what the tests hold the chunked form against, and a decode
step's rule). :func:`kda_chunked` is what a model trains with. ``ops/
gated_delta.py`` decays a head's whole state by ONE number, so its decay
factors out of ``K K^T`` as a ``[C, C]`` mask ``exp(G_i - G_j)``; a decay
per channel does not, and has to be carried into the operands. With ``G``
the running sum of ``g`` inside a chunk of ``C`` tokens (``[C, d_k]``),
``K+ = K * exp(G)``, ``K- = K * exp(-G)`` and ``Q+ = Q * exp(G)``::

    A  = -strict_lower((beta K+) K-^T)
    T  = (I - A)^-1                       (block forward substitution)
    U  = T (beta V)          W = T (beta K+)
    per chunk:  V' = U - W S
                O  = Q+ S + lower(Q+ K-^T) V'
                S  = diag(exp(G_last)) S + (K * exp(G_last - G))^T V'

``exp(-G)`` grows with the chunk: a gate bounded below by ``-5`` reaches
``exp(5 C)``, past float32 from 18 tokens on. The two triangular products
are therefore taken in SUB-BLOCKS of ``SUB_BLOCK`` rows, with ``G`` referred
to the sub-block's MIDDLE row ``r``: the rows carry ``exp(G_i - G_r)``, the
columns ``exp(G_r - G_j)``, which is at most 1 under the sub-block (``j <
r``) and inside it, either way, within ``exp(+-bound * SUB_BLOCK / 2)``
(``exp(+-40)`` at a bound of -5 and 16 rows). Referred to the first row the
exponents reach ``-75``: float32 holds ``exp(-75)``, but the backward
multiplies a cotangent by it before the matching ``exp(75)`` comes, and that
product leaves float32's range (measured: the gate's gradient 7% off and
the keys' 2% at a model's sizes, in float32 as in bfloat16). Every exponent
is a direct sum of gates and never a difference of two running sums. A
gate is bounded by its caller (``models/ling.py``: ``g = -5 * sigmoid(.)``);
nothing here clips one. The MXU's operands are cast to
``dtype`` (bfloat16 from the model) AFTER the scaling; ``g``, its sums and
exponentials, ``T`` and the state are float32.

Both parts are XLA: the chunk-local part batched products over the chunks of
``LOCAL_TOKENS`` tokens at a time under a checkpoint (one block's scaled
operands and exponentials alive at a time), the pass over chunks a
``lax.scan`` that keeps one state a chunk. The backward is JAX's transpose
of them; of the ``[C, C]`` float32 matrices of a head and chunk it holds
``T`` alone (``unit_lower_inverse``'s own VJP takes the inverse, not its
argument; the two masked products reach the scan in ``dtype``). No Pallas
kernel yet (``PERF.md`` section 7 has the sizes one would start from).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import trace as _trace
from .gated_delta import _dot, gated_delta_recurrent, unit_lower_inverse

DEFAULT_CHUNK = 64
SUB_BLOCK = 16   # rows of a triangular product that share one reference row
LOCAL_TOKENS = 1024   # tokens whose chunk-local part is computed at a time


def kda_recurrent(q, k, v, g, beta, initial_state=None):
    """The rule token by token, float32. ``q``, ``k``, ``g`` (log decay a
    key channel, <= 0): ``[B, T, H, d_k]``; ``v``: ``[B, T, H, d_v]``;
    ``beta``: ``[B, T, H]``. Returns ``(o [B, T, H, d_v], final state
    [B, H, d_k, d_v])``. It is ``ops/gated_delta.py``'s rule with a decay
    of ``d_k`` numbers where that one has one."""
    if g.shape != k.shape:
        raise ValueError(f"a gate a key channel: g {g.shape}, k {k.shape}")
    return gated_delta_recurrent(q, k, v, g, beta, initial_state)


def _local(q, k, v, g, beta, C, sub, dtype):
    """What the pass over chunks multiplies, as batched XLA products over
    all chunks at once: ``(u, w, qk, q_g, k_g, last)``, the chunks leading
    (``[N, B, H, C, ...]``; ``last``, the chunk's whole decay ``exp(G_last)``,
    ``[N, B, H, d_k]``). ``q``, ``k``, ``v``, ``g``: ``[B, T, H, d]``;
    ``beta``: ``[B, T, H]``."""
    f32 = jnp.float32
    B, T = q.shape[:2]
    N = T // C
    # [B, T, H, d] -> [B, H, N, C, d]
    chunks = lambda x: jnp.moveaxis(
        x.reshape((B, N, C) + x.shape[2:]), 3, 1).astype(f32)
    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    bc = chunks(beta[..., None])
    gc = chunks(g)
    k_beta = kc * bc
    # Every exponent is a DIRECT sum of gates, never a difference of two
    # running sums: gates are <= 0, so a direct sum is exact to 1e-7 of its
    # own size, where a difference of two sums of 300 is off by 3e-5
    # whatever its size, and the decays that matter are the small ones.
    n = C // sub
    by_sub = lambda x: x.reshape(x.shape[:-2] + (n, sub, x.shape[-1]))
    after = lambda x: jnp.flip(jnp.cumsum(jnp.flip(jnp.concatenate(
        [x[..., 1:, :], jnp.zeros_like(x[..., :1, :])], -2), -2), -2), -2)
    gs = by_sub(gc)
    # inside a sub-block, from its MIDDLE row r: the sum over (r, i] behind
    # it, minus the sum over (i, r] in front of it: at most sub / 2 gates
    # either way. (From the first row the exponents would reach twice as
    # far, exp(-75) at a bound of -5: float32 holds that, but a cotangent
    # times it does not, and the backward would lose the pairs deep in a
    # fast sub-block.)
    r = sub // 2
    since = jnp.concatenate([-after(gs[..., :r + 1, :]),
                             jnp.cumsum(gs[..., r + 1:, :], axis=-2)], -2)
    up = jnp.exp(since)                                      # [.., n, s, dk]
    # the columns of sub-block J as row block I sees them: under it the sum
    # over (j, r_I] (what is left of J, the sub-blocks between, I's rows up
    # to r_I), inside it minus the sum from r_I, nothing beyond it, where
    # the mask takes all
    I, J, K = (jnp.arange(n).reshape(shape) for shape in (
        (n, 1, 1), (1, n, 1), (1, 1, n)))
    whole = jnp.sum(gs, axis=-2)                             # [.., n, dk]
    between = jnp.sum(jnp.where(
        ((J < K) & (K < I))[..., None], whole[..., None, None, :, :], 0.0),
        axis=-2)                                             # [.., n, n, dk]
    head = jnp.sum(gs[..., :r + 1, :], axis=-2)              # [.., n, dk]
    under = (after(gs)[..., None, :, :, :]
             + (between + head[..., :, None, :])[..., None, :])
    reach = jnp.where((J < I)[..., None], under, jnp.where(
        (J == I)[..., None], -since[..., None, :, :, :], 0.0))
    down = kc[..., None, :, :] * jnp.exp(reach).reshape(
        reach.shape[:-3] + (C, reach.shape[-1]))             # [.., n, C, dk]
    product = lambda rows: _dot(
        by_sub(rows) * up, down, "...nid,...njd->...nij", dtype).reshape(
            rows.shape[:-2] + (C, C))
    kk, qk = product(k_beta), product(qc)
    row = jnp.arange(C)
    strict = row[:, None] > row[None, :]
    # above the diagonal a sub-block's own product is exp(G_i - G_j) > 1 a
    # channel: finite, and masked here
    m = jnp.where(strict, kk, 0.0) + jnp.eye(C, dtype=f32)       # I - A
    t_inv = unit_lower_inverse(m)
    total = jnp.cumsum(gc, axis=-2)        # from the chunk's first token
    decay = jnp.exp(total)
    u = _dot(t_inv, vc * bc, "...ij,...jd->...id", dtype)
    w = _dot(t_inv, k_beta * decay, "...ij,...jd->...id", dtype).astype(dtype)
    qk = jnp.where(row[:, None] >= row[None, :], qk, 0.0).astype(dtype)
    q_g = (qc * decay).astype(dtype)
    k_g = (kc * jnp.exp(after(gc))).astype(dtype)   # to the chunk's last
    return tuple(jnp.moveaxis(x, 2, 0) for x in (
        u, w, qk, q_g, k_g, decay[..., -1, :]))


def kda_chunked(q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK,
                dtype=jnp.bfloat16, initial_state=None):
    """The rule in chunks of ``chunk`` tokens (a power of two); shapes as
    :func:`kda_recurrent`. A sequence that is no multiple of the chunk, a
    shorter one among them, is padded with tokens that leave the state as
    it is (``g`` 0, ``beta`` 0). Returns ``(o [B, T, H, d_v] float32, final
    state [B, H, d_k, d_v] float32)``."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk
    if C < 1 or C & (C - 1):     # the triangular inverse doubles its blocks
        raise ValueError(f"chunk {C} is no power of two")
    sub = min(SUB_BLOCK, C)
    N = -(-T // C)
    # the chunk-local part crosses no chunk: a block of the sequence after
    # another, each under a checkpoint, so that one block's scaled operands
    # and exponentials are alive at a time, forward and backward
    blocks = max(1, N * C // LOCAL_TOKENS)
    if N % blocks:
        blocks = 1
    _trace.note_plan(kda_chunk=C, kda_sub_block=sub, kda_heads=H,
                     kda_chunks=N, kda_padded_tokens=N * C - T,
                     kda_local_blocks=blocks)
    if N * C != T:
        pad = lambda x: jnp.pad(
            x, ((0, 0), (0, N * C - T)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, g, beta = (pad(x) for x in (q, k, v, g, beta))
    dtype = jnp.dtype(dtype)
    split = lambda x: jnp.moveaxis(
        x.reshape((B, blocks, N * C // blocks) + x.shape[2:]), 1, 0)
    local = lax.map(
        lambda xs: jax.checkpoint(functools.partial(
            _local, C=C, sub=sub, dtype=dtype))(*xs),
        tuple(split(x) for x in (q, k, v, g, beta)))
    local = tuple(x.reshape((N,) + x.shape[2:]) for x in local)
    S0 = (jnp.zeros((B, H, dk, dv), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(S, x):
        u_c, w_c, qk_c, qg_c, kg_c, last = x
        v_new = u_c - _dot(w_c, S, "bhck,bhkv->bhcv", dtype)
        o_c = (_dot(qg_c, S, "bhck,bhkv->bhcv", dtype)
               + _dot(qk_c, v_new, "bhij,bhjv->bhiv", dtype))
        S = (S * last[..., None]
             + _dot(kg_c, v_new, "bhck,bhcv->bhkv", dtype))
        return S, o_c

    S, o = lax.scan(step, S0, local)
    # [N, B, H, C, d_v] -> [B, T, H, d_v]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3).reshape(B, N * C, H, dv)
    return o[:, :T], S
