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
system, solved once for all chunks at a time, and only the state crosses
chunks, so a sequence of ``T`` tokens is ``T / C`` sequential steps of
MXU-sized products and not ``T`` rank-one updates.

With ``G`` the running sum of ``g`` inside a chunk and ``K_beta = beta * K``::

    A  = -strict_lower((K_beta K^T) * exp(G_i - G_j))
    T  = (I - A)^-1                       (block forward substitution)
    U  = T V_beta          W = T (K_beta * exp(G))
    per chunk:  V' = U - W S
                O  = (Q * exp(G)) S + lower((Q K^T) * exp(G_i - G_j)) V'
                S  = exp(G_last) S + (K * exp(G_last - G))^T V'

The MXU's operands are cast to ``dtype`` (bfloat16 from the model); ``g``,
its sums and exponentials, the triangular inverse and the state are float32.

Two parts, two forms. What a chunk needs BEFORE the scan (``U``, ``W``,
the masked ``Q K^T``, ``Q exp(G)``, ``K exp(G_last - G)``) crosses no chunk,
and where the shapes allow it (:func:`_plan`) one Pallas kernel a direction
computes it with a block of chunks held in VMEM from its inputs to its
outputs: chunks are laid side by side in tiles of 128 tokens whose
``[128, 128]`` matrices are block diagonal, so every product fills the
MXU's width, of the ``[C, C]`` float32 intermediates only ``T`` itself
reaches HBM (once, for the backward), q, k and v are read where the model
left them (a key head shared by its group's value heads through the index
map) and the outputs are written as the scan reads them. The forward kernel
is ``gdn_fwd.<n>`` in a device trace; the backward kernel (``gdn_bwd.<n>``)
reads the ``T`` it left and is the chunk-local part's own VJP: from the cotangents of the five arrays, which the scan's transpose
produces, to those of q, k, v, ``G`` and ``beta``, with
``dm = -T^T dT T^T`` in place of the rounds' transposes. Shapes the plan
refuses (a chunk that is no power of two, head widths that are no multiple
of the lane width, an ``initial_state``) take the same products as batched
XLA operations (:func:`_local_xla`), whose backward is JAX's transpose of
them. The scan over chunks is plain XLA in both, and its backward JAX's
transpose: it keeps one state per chunk, never one per token.
"""

from __future__ import annotations

import functools
import math
import types
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import trace as _trace
from . import pallas_attention as _pa
from .pallas_attention import (_LANES, _NN, _NT, _TN, _VMEM_BUDGET, _lanes,
                               _vma)

DEFAULT_CHUNK = 64
_HIGHEST = lax.Precision.HIGHEST
_PREF_TILES = 8     # tiles of a head one grid step takes, at most
_UNROLL = 4        # tiles the kernels' loop takes an iteration


def gated_delta_recurrent(q, k, v, g, beta, initial_state=None):
    """The rule token by token, float32. ``q``, ``k``: ``[B, T, H, d_k]``;
    ``v``: ``[B, T, H, d_v]``; ``g`` (log decay, <= 0) and ``beta``:
    ``[B, T, H]``; a ``g`` of ``[B, T, H, d_k]`` decays every key channel by
    its own number (``ops/kda.py``'s rule). Returns ``(o [B, T, H, d_v],
    final state [B, H, d_k, d_v])``."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    S0 = (jnp.zeros((B, H, dk, dv), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        decay = jnp.exp(g_t)
        S = S * decay.reshape(decay.shape + (1,) * (S.ndim - decay.ndim))
        d_t = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t, precision=_HIGHEST))
        S = S + k_t[..., :, None] * d_t[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta))
    S, o = lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1), S


def _mm(x, y):
    return jnp.matmul(x, y, precision=_HIGHEST)


def _inverse_rounds(m, i, j, size):
    """``m^-1`` where ``m`` is unit lower triangular in diagonal blocks of
    ``size`` (a power of two) and ``i``, ``j`` are its row and column
    indices: with ``X`` the inverse of the diagonal blocks of size ``b``
    and ``R`` the blocks of ``m`` below them, ``X - X R X`` is the inverse
    of the diagonal blocks of size ``2b`` (``X`` is block diagonal, so
    ``X R X`` lands where ``R`` is), from blocks of one (whose inverse is
    1) up: ``log2(size)`` rounds of two dense float32 products."""
    inv = jnp.where(i == j, 1.0, 0.0).astype(m.dtype)
    for s in range(size.bit_length() - 1):      # b = 2 ** s
        # the lower-left b x b block of every diagonal block of 2b (shifts,
        # not divisions: the kernels run this on the VPU)
        below = ((i >> s + 1) == (j >> s + 1)) & ((i >> s) & 1 == 1) & (
            (j >> s) & 1 == 0)
        r = jnp.where(below, m, 0.0)
        inv = inv - (r if s == 0 else _mm(_mm(inv, r), inv))
    return inv


@jax.custom_vjp
def unit_lower_inverse(m):
    """``m^-1`` of unit lower-triangular ``[..., C, C]`` matrices (``C`` a
    power of two) by block forward substitution (:func:`_inverse_rounds`),
    written on whole matrices so that every product is a dense ``C x C``
    one. The backward is the inverse's own, ``-X^T g X^T``: two products,
    not the rounds' transposes."""
    C = m.shape[-1]
    if C & (C - 1):
        raise ValueError(f"chunk size {C} is not a power of two")
    row = jnp.arange(C)
    inv = _inverse_rounds(m, row[:, None], row[None, :], C)
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


def _local_xla(q, k, v, G, beta, C, dtype):
    """What the scan multiplies, as batched XLA products over all chunks at
    once: ``(u, w, qk, q_g, k_g)``, the chunks leading
    (``[N, B, H, C, ...]``). ``q``, ``k``, ``v``: ``[B, T, H, d]``; ``G``
    and ``beta``: ``[B, H, N, C]`` float32."""
    f32 = jnp.float32
    B, T, H = q.shape[:3]
    N = T // C
    # [B, T, H, d] -> [B, H, N, C, d]
    chunks = lambda x: jnp.moveaxis(
        x.reshape((B, N, C) + x.shape[2:]), 3, 1)
    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    row = jnp.arange(C)
    lower = row[:, None] >= row[None, :]
    # exp(G_i - G_j) where i >= j (masked before the exponential: above the
    # diagonal the difference is positive and may overflow)
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                              -jnp.inf))
    k_beta = kc.astype(f32) * beta[..., None]
    v_beta = vc.astype(f32) * beta[..., None]
    strict = row[:, None] > row[None, :]
    kk = _dot(k_beta, kc, "...id,...jd->...ij", dtype)
    m = jnp.where(strict, kk * decay, 0.0) + jnp.eye(C, dtype=f32)  # I - A
    t_inv = unit_lower_inverse(m)
    # already in the MXU's dtype: a chunk's step reads (and the backward
    # keeps) half the bytes of float32
    u = _dot(t_inv, v_beta, "...ij,...jd->...id", dtype)
    w = _dot(t_inv, k_beta * jnp.exp(G)[..., None], "...ij,...jd->...id",
             dtype).astype(dtype)
    qk = (_dot(qc, kc, "...id,...jd->...ij", dtype) * decay).astype(dtype)
    q_g = (qc.astype(f32) * jnp.exp(G)[..., None]).astype(dtype)
    k_g = (kc.astype(f32)
           * jnp.exp(G[..., -1:] - G)[..., None]).astype(dtype)
    return tuple(jnp.moveaxis(x, 2, 0) for x in (u, w, qk, q_g, k_g))


# --------------------------------------------------------------------------
# The chunk-local part as Pallas kernels.
#
# A TILE is ``max(C, 128)`` consecutive tokens of one head: ``128 / C``
# chunks side by side when a chunk is narrower than the lanes. Its
# ``[tile, tile]`` matrices are block diagonal (``same``: row and column in
# one chunk), so two chunks of 64 cost one full-width MXU pass where apart
# they would cost two half-empty ones, every transpose is a native
# ``[128, 128]`` one, and only ``qk`` (and its cotangent), which the scan
# wants as ``[C, C]`` a chunk, is folded from and to the tile by a product
# with a 0/1 matrix.

def _mxu(a, b, dims, dtype):
    """A product of MXU operands in ``dtype`` with float32 accumulation, as
    :func:`_dot` rounds them (float32 operands multiply at full precision:
    the tests' comparison, not a model's call)."""
    return lax.dot_general(
        a.astype(dtype), b.astype(dtype), dims,
        precision=_HIGHEST if dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)


def _col(row):
    """A ``[1, tile]`` row as ``[tile, 128]``: its values down the sublanes,
    replicated over the lanes (:func:`_lanes` widens it)."""
    return jnp.broadcast_to(row, (_LANES, row.shape[-1])).T


def _to_row(col):
    """A ``[tile, 1]`` column as the ``[1, tile]`` row."""
    return jnp.broadcast_to(col, (col.shape[0], _LANES)).T[:1]


def _tile_terms(q, k, v, rows, C, dtype):
    """What both directions compute of one tile before anything is solved.
    ``rows``: ``[3, tile]`` float32, the tile's ``G``, ``G_last - G`` and
    ``beta``; ``C`` a power of two."""
    f32 = jnp.float32
    R, dk = q.shape
    dv = v.shape[-1]
    i = lax.broadcasted_iota(jnp.int32, (R, R), 0)
    j = lax.broadcasted_iota(jnp.int32, (R, R), 1)
    log_c = C.bit_length() - 1
    same = (i >> log_c) == (j >> log_c)
    G_row = rows[0:1]
    G_col, to_last, b_col = (_col(rows[n:n + 1]) for n in range(3))
    # masked before the exponential, as in _local_xla; zero outside a chunk
    decay = jnp.exp(jnp.where(same & (i >= j), _lanes(G_col, R) - G_row,
                              -jnp.inf))
    e_g, e_last = jnp.exp(G_col), jnp.exp(to_last)
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    k_beta = kf * _lanes(b_col, dk)
    v_beta = vf * _lanes(b_col, dv)
    kk = _mxu(k_beta, k, _NT, dtype)
    qk = _mxu(q, k, _NT, dtype)
    return types.SimpleNamespace(
        i=i, j=j, strict=same & (i > j), decay=decay, e_g=e_g, e_last=e_last,
        b_col=b_col, qf=qf, kf=kf, vf=vf, k_beta=k_beta, v_beta=v_beta,
        k_beta_g=k_beta * _lanes(e_g, dk), kk=kk, qk=qk,
    )


def _fold(R, C):
    """``[tile, C]`` of 0/1: column ``c`` picks the tile columns that are
    a chunk's ``c``-th."""
    j = lax.broadcasted_iota(jnp.int32, (R, C), 0)
    c = lax.broadcasted_iota(jnp.int32, (R, C), 1)
    return jnp.where(j & (C - 1) == c, 1.0, 0.0)


def _over_tiles(tiles, one):
    """``one(n)`` for every tile of the step, ``_UNROLL`` tiles an iteration
    so that the scheduler has independent chains of products to interleave."""
    per = math.gcd(tiles, _UNROLL)

    def some(s, carry):
        for u in range(per):
            one(s * per + u)
        return carry

    lax.fori_loop(0, tiles // per, some, None)


def _fwd_kernel(q_ref, k_ref, v_ref, rows_ref,
                u_ref, w_ref, qk_ref, qg_ref, kg_ref, t_ref, *,
                C, R, tiles, dtype):
    P = R // C
    dk = q_ref.shape[-1]

    def one(n):
        at = pl.ds(pl.multiple_of(n * R, R), R)
        q, k, v = q_ref[at, :], k_ref[at, :], v_ref[at, :]
        x = _tile_terms(q, k, v, rows_ref[n], C, dtype)
        m = jnp.where(x.strict, x.kk * x.decay, 0.0)
        m = m + jnp.where(x.i == x.j, 1.0, 0.0)          # I - A
        t_inv = _inverse_rounds(m, x.i, x.j, C)
        t_ref[n] = t_inv
        u = _mxu(t_inv, x.v_beta, _NN, dtype)
        w = _mxu(t_inv, x.k_beta_g, _NN, dtype)
        qk = (x.qk * x.decay).astype(dtype)
        if P > 1:   # the chunks' own [C, C] blocks, one under the other
            qk = _mxu(qk, _fold(R, C), _NN, dtype)
        q_g = x.qf * _lanes(x.e_g, dk)
        k_g = x.kf * _lanes(x.e_last, dk)
        for p in range(P):
            rows = slice(p * C, (p + 1) * C)
            c = n * P + p
            u_ref[c] = u[rows].astype(u_ref.dtype)
            w_ref[c] = w[rows].astype(w_ref.dtype)
            qk_ref[c] = qk[rows].astype(qk_ref.dtype)
            qg_ref[c] = q_g[rows].astype(qg_ref.dtype)
            kg_ref[c] = k_g[rows].astype(kg_ref.dtype)

    _over_tiles(tiles, one)


def _bwd_kernel(q_ref, k_ref, v_ref, rows_ref, t_ref,
                du_ref, dw_ref, dqk_ref, dqg_ref, dkg_ref,
                dq_ref, dk_ref, dv_ref, drows_ref, *, C, R, tiles, dtype):
    f32 = jnp.float32
    P = R // C
    dk, dv = q_ref.shape[-1], v_ref.shape[-1]

    def one(n):
        at = pl.ds(pl.multiple_of(n * R, R), R)
        q, k, v = q_ref[at, :], k_ref[at, :], v_ref[at, :]
        x = _tile_terms(q, k, v, rows_ref[n], C, dtype)
        tile = lambda ref: jnp.concatenate(
            [ref[n * P + p] for p in range(P)], axis=0)
        du, dw, dqg, dkg = (tile(r) for r in (du_ref, dw_ref, dqg_ref,
                                              dkg_ref))
        dqk = tile(dqk_ref)                                    # [R, C]
        if P > 1:   # every chunk's [C, C] block back on the diagonal
            dqk = _mxu(dqk, _fold(R, C), _NT, dtype)
        decay, e_g, e_last, b_col = x.decay, x.e_g, x.e_last, x.b_col
        t_inv = t_ref[n]
        # u = T V_beta, w = T (K_beta exp(G)): both operands' cotangents
        d_t = (_mxu(du, x.v_beta, _NT, dtype)
               + _mxu(dw, x.k_beta_g, _NT, dtype))
        d_v_beta = _mxu(t_inv, du, _TN, dtype)
        d_k_beta_g = _mxu(t_inv, dw, _TN, dtype)
        # the inverse's own backward, float32: dm = -T^T dT T^T
        d_m = -_mxu(_mxu(t_inv, d_t, _TN, f32), t_inv, _NT, f32)
        d_kk = jnp.where(x.strict, d_m, 0.0) * decay
        d_qk = dqk.astype(f32) * decay     # decay is 0 outside the chunks
        # decay = exp(G_i - G_j): +E to row i's G, -E to column j's
        e = d_kk * x.kk + d_qk * x.qk
        d_k_beta = (_mxu(d_kk, k, _NN, dtype)
                    + d_k_beta_g * _lanes(e_g, dk))
        d_k = (_mxu(d_kk, x.k_beta, _TN, dtype)
               + _mxu(d_qk, q, _TN, dtype)
               + d_k_beta * _lanes(b_col, dk)
               + dkg.astype(f32) * _lanes(e_last, dk))
        d_q = _mxu(d_qk, k, _NN, dtype) + dqg.astype(f32) * _lanes(e_g, dk)
        dq_ref[at, :] = d_q.astype(dq_ref.dtype)
        dk_ref[at, :] = d_k.astype(dk_ref.dtype)
        dv_ref[at, :] = (d_v_beta * _lanes(b_col, dv)).astype(dv_ref.dtype)
        rowsum = lambda y: jnp.sum(y, axis=1, keepdims=True)
        d_last = rowsum(dkg.astype(f32) * x.kf * _lanes(e_last, dk))
        d_g = (rowsum(e) + rowsum(d_k_beta_g * x.k_beta_g)
               + rowsum(dqg.astype(f32) * x.qf * _lanes(e_g, dk)))
        d_beta = (rowsum(d_k_beta * x.kf) + rowsum(d_v_beta * x.vf))
        drows_ref[n, 0:1] = _to_row(d_g) - jnp.sum(e, axis=0, keepdims=True)
        drows_ref[n, 1:2] = _to_row(d_last)
        drows_ref[n, 2:3] = _to_row(d_beta)

    _over_tiles(tiles, one)


def _step_vmem_bytes(tiles, R, C, dk, dv, in_size, out_size):
    """What one grid step of the larger kernel keeps in VMEM: the
    pipeline's two buffers of every block of ``tiles`` tiles (a ``[C, C]``
    block's minor dim is padded to the lanes, a ``[3, tile]`` one's rows to
    8 sublanes) and one tile's float32 temporaries (some thirty
    ``[tile, tile]`` and twenty ``[tile, d]`` in the backward)."""
    d = max(dk, dv)
    qk = R * max(C, _LANES) * out_size
    rows = 8 * R * 4
    qkv = R * (2 * dk + dv) * in_size
    t_inv = R * R * 4
    outs = R * dv * 4 + 3 * R * dk * out_size + qk   # u, w, q_g, k_g, qk
    fwd = qkv + rows + outs + t_inv
    bwd = qkv + rows + t_inv + outs + qkv + rows
    return 2 * tiles * max(fwd, bwd) + (30 * R * R + 20 * R * d) * 4


def _refusal(T, C, dk, dv):
    """Why the shapes are not the kernels' (the word the build ledger's
    fallback record carries), or None where they are."""
    if C & (C - 1) or C < 16:
        return "chunk_not_power_of_two"
    if dk % _LANES or dv % _LANES:
        return "head_width_not_whole_lanes"
    if T % max(C, _LANES):
        return "sequence_not_whole_tiles"
    return None


def _plan(T, C, dk, dv, in_size, out_size):
    """``(tile, tiles a grid step)`` of the chunk-local kernels, or None
    where the shapes are not theirs: a chunk that is no power of two (or
    under the 16 rows a packed bfloat16 register holds), head widths that
    do not fill whole lanes, a sequence that is no whole number of tiles.
    A step takes the largest divisor of the head's tiles, up to
    ``_PREF_TILES``, that fits the VMEM budget."""
    R = max(C, _LANES)
    if _refusal(T, C, dk, dv):
        return None
    for tiles in range(min(_PREF_TILES, T // R), 0, -1):
        if (T // R) % tiles == 0 and _step_vmem_bytes(
                tiles, R, C, dk, dv, in_size, out_size) <= _VMEM_BUDGET:
            return R, tiles
    return None


def _specs(group, dk, dv, C, R, tiles):
    """The block specs both kernels share, by what a block holds, on the
    grid ``(batch, value head, block of tiles)``. q, k, v are
    ``[B, T, heads * d]`` as the model left them: a head is a block of
    lanes, and a key head serves the ``group`` value heads that share it."""
    step, per = tiles * R, tiles * R // C
    chunked = lambda *minor: pl.BlockSpec(
        (per, None, None) + minor, lambda b, h, n: (n, b, h, 0, 0))
    return dict(
        qk_in=pl.BlockSpec((None, step, dk),
                           lambda b, h, n: (b, n, h // group)),
        q_out=pl.BlockSpec((None, step, dk), lambda b, h, n: (b, n, h)),
        v=pl.BlockSpec((None, step, dv), lambda b, h, n: (b, n, h)),
        rows=pl.BlockSpec((None, None, tiles, 3, R),
                          lambda b, h, n: (b, h, n, 0, 0)),
        t_inv=pl.BlockSpec((None, None, tiles, R, R),
                           lambda b, h, n: (b, h, n, 0, 0)),
        by_v=chunked(C, dv), by_k=chunked(C, dk), by_c=chunked(C, C),
    )


class _Call(NamedTuple):
    """One call of the kernels: everything their programs depend on."""
    B: int
    T: int
    Hv: int
    group: int          # value heads that share a key head
    dk: int
    dv: int
    C: int
    R: int              # the tile (:func:`_plan`)
    tiles: int          # tiles a grid step
    dtype: Any          # the MXU's operands
    in_dtypes: tuple    # of q, k and v as passed
    interpret: bool


def _kernels(call, vma):
    """The two ``pallas_call``s of one call: ``forward(q, k, v, rows)``
    giving ``(u, w, qk, q_g, k_g, T)`` and ``backward(q, k, v, rows, T, du,
    dw, dqk, dq_g, dk_g)`` giving ``(dq, dk, dv, drows)``, ``dq`` and
    ``dk`` a value head each."""
    f32 = jnp.float32
    B, T, Hv, group, dk, dv, C, R, tiles, dtype = call[:10]
    s = _specs(group, dk, dv, C, R, tiles)
    static = dict(C=C, R=R, tiles=tiles, dtype=dtype)
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, vma=vma)
    local = lambda d, dt: shape((T // C, B, Hv, C, d), dt)
    five = [local(dv, f32), local(dk, dtype), local(C, dtype),
            local(dk, dtype), local(dk, dtype)]
    five_specs = [s["by_v"], s["by_k"], s["by_c"], s["by_k"], s["by_k"]]
    ins = [s["qk_in"], s["qk_in"], s["v"], s["rows"]]
    common = dict(
        grid=(B, Hv, T // (tiles * R)), interpret=call.interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")))
    q_dtype, k_dtype, v_dtype = call.in_dtypes
    forward = pl.pallas_call(
        functools.partial(_fwd_kernel, **static),
        out_shape=five + [shape((B, Hv, T // R, R, R), f32)],
        in_specs=ins, out_specs=five_specs + [s["t_inv"]],
        name="gdn_fwd", **common,
    )
    backward = pl.pallas_call(
        functools.partial(_bwd_kernel, **static),
        out_shape=[shape((B, T, Hv * dk), q_dtype),
                   shape((B, T, Hv * dk), k_dtype),
                   shape((B, T, Hv * dv), v_dtype),
                   shape((B, Hv, T // R, 3, R), f32)],
        in_specs=ins + [s["t_inv"]] + five_specs,
        out_specs=[s["q_out"], s["q_out"], s["v"], s["rows"]],
        name="gdn_bwd", **common,
    )
    return forward, backward


@functools.lru_cache(maxsize=32)
def _kernel_jaxprs(mesh, call):
    """:func:`_kernels` traced once per distinct call, as
    ``pallas_attention._forward_jaxpr`` keeps the flash kernel: a model
    calls these once a layer at one shape, and Pallas would trace the
    bodies anew each time. ``mesh`` is the abstract mesh of the caller's
    context: avals carry it, so the jaxprs are kept per context."""
    c = call
    aval = jax.ShapeDtypeStruct
    ins = tuple(aval((c.B, c.T, width), dt) for width, dt in zip(
        (c.Hv // c.group * c.dk, c.Hv // c.group * c.dk, c.Hv * c.dv),
        c.in_dtypes)) + (aval((c.B, c.Hv, c.T // c.R, 3, c.R), jnp.float32),)
    forward, backward = _kernels(call, vma=frozenset())
    fwd = jax.make_jaxpr(forward)(*ins)
    *five, t_inv = fwd.out_avals
    return fwd, jax.make_jaxpr(backward)(*ins, t_inv, *five)


def _run_kernel(call, which, *args):
    vma = _vma(*args)
    if vma:   # typed per mesh axis: traced where the axes are bound
        return _kernels(call, vma)[which](*args)
    closed = _kernel_jaxprs(jax.sharding.get_abstract_mesh(), call)[which]
    return jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _local_kernel(call, q, k, v, rows):
    """The chunk-local part by the kernels: ``(u, w, qk, q_g, k_g)``, the
    chunks leading. ``q``, ``k``: ``[B, T, H_k * d_k]``; ``v``:
    ``[B, T, H_v * d_v]``; ``rows``: ``[B, H_v, T / tile, 3, tile]``
    float32 (a tile's ``G``, ``G_last - G`` and ``beta``)."""
    return tuple(_run_kernel(call, 0, q, k, v, rows)[:5])


def _local_kernel_fwd(call, q, k, v, rows):
    *five, t_inv = _run_kernel(call, 0, q, k, v, rows)
    return tuple(five), (q, k, v, rows, t_inv)


def _local_kernel_bwd(call, res, cts):
    q, k, v, rows, t_inv = res
    dq, dk, dv, drows = _run_kernel(call, 1, q, k, v, rows, t_inv, *cts)

    def shared(x):   # a key head's cotangent: its value heads' summed
        if call.group == 1:
            return x
        by_group = x.reshape(call.B, call.T, -1, call.group, call.dk)
        return jnp.sum(by_group.astype(jnp.float32), axis=3).reshape(
            call.B, call.T, -1).astype(x.dtype)

    return shared(dq), shared(dk), dv, drows


_local_kernel.defvjp(_local_kernel_fwd, _local_kernel_bwd)


def gated_delta_chunked(q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK,
                        dtype=jnp.bfloat16, initial_state=None):
    """The rule in chunks of ``chunk`` tokens; shapes as
    :func:`gated_delta_recurrent`, ``T`` a multiple of ``chunk``, and ``q``
    and ``k`` may have fewer heads than ``v``, each shared by a group of
    consecutive value heads. Returns ``(o [B, T, H, d_v] float32, final
    state [B, H, d_k, d_v] float32)``."""
    f32 = jnp.float32
    B, T, Hk, dk = q.shape
    H, dv = v.shape[2:]
    C = min(chunk, T)
    if T % C:
        raise ValueError(f"sequence length {T} is not a multiple of the "
                         f"chunk {C}; pad the sequence")
    if H % Hk:
        raise ValueError(f"{H} value heads do not share {Hk} key heads")
    N = T // C
    dtype = jnp.dtype(dtype)
    plan = None if initial_state is not None else _plan(
        T, C, dk, dv, max(x.dtype.itemsize for x in (q, k, v)),
        dtype.itemsize)
    R, tiles = plan or (0, 0)
    _trace.note_plan(
        gdn_chunk=C, gdn_heads=H, gdn_chunks=N,
        gdn_kernel=plan is not None,
        gdn_block_chunks=tiles * R // C,
        gdn_grid_steps=B * H * T // (tiles * R) if plan else 0,
        gdn_bwd_recomputes_inverse=False,
    )

    # [B, T, H] -> [B, H, N, C]
    by_chunk = lambda x: jnp.moveaxis(x.astype(f32), 1, 2).reshape(
        B, H, N, C)
    G = jnp.cumsum(by_chunk(g), axis=-1)
    g_last = G[..., -1]                               # [B, H, N]
    if plan is None:
        # the XLA form's backward is JAX's transpose of it: the one record
        # stands for gdn_bwd too
        _trace.note_fallback(
            "gdn_fwd", "initial_state" if initial_state is not None
            else _refusal(T, C, dk, dv) or "no_tile_fits_vmem",
            batch=B, seq=T, heads=H, chunk=C, dk=dk, dv=dv)
        shared = lambda x: jnp.repeat(x, H // Hk, axis=2)
        local = _local_xla(shared(q), shared(k), v, G, by_chunk(beta), C,
                           dtype)
    else:
        R, tiles = plan
        rows = jnp.stack([x.reshape(B, H, T // R, R) for x in (
            G, g_last[..., None] - G, by_chunk(beta))], axis=3)
        flat = lambda x: x.reshape(B, T, -1)
        # the interpreter on the CPU backend, the compiler on a TPU: the
        # flash kernels' rule (and what a test steers to compile both)
        call = _Call(B, T, H, H // Hk, dk, dv, C, R, tiles, dtype,
                     (q.dtype, k.dtype, v.dtype), _pa._resolve_interpret(None))
        local = _local_kernel(call, flat(q), flat(k), flat(v), rows)

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

    S, o = lax.scan(step, S0, local + (jnp.moveaxis(g_last, 2, 0),))
    # [N, B, H, C, d_v] -> [B, T, H, d_v]
    o = jnp.moveaxis(o, 0, 2)                         # [B, H, N, C, d_v]
    o = jnp.moveaxis(o, 1, 3).reshape(B, T, H, dv)
    return o, S
