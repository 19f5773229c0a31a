"""Chunked collective-matmul primitives: compute fused into the wire.

The composed DP x TP fast path (docs/parallelism.md) pays the Megatron
row-parallel psum as fully exposed latency — the wire and the MXU
alternate. These two primitives make them share a timeline:

- :func:`all_gather_matmul` — ``y = all_gather(x_shard) @ w``, the
  column-parallel consume of a token-sharded activation: each of the
  n−1 ring hops transfers the next activation chunk while the MXU
  multiplies the one that just arrived, split bidirectionally so both
  ring directions carry half the gathered payload (FlexLink-style).
- :func:`matmul_reduce_scatter` — ``z = reduce_scatter(y @ w)`` over
  the token dim, the row-parallel produce: partial products are
  computed per DESTINATION chunk and reduced along the ring, again
  split over both directions.

``psum(y @ w) == all_gather(matmul_reduce_scatter(y, w))`` over tokens,
which is what makes the fused Megatron block numerically equivalent to
the classic one-psum-per-half-block schedule (tests lock <=5e-7).

Each primitive is ONE lowering on every backend: a chunked
``lax.ppermute`` ring inside the caller's shard_map, which XLA compiles
to collective-permutes interleaved with the per-chunk matmuls (the HLO
assertions count those permutes). There is no hand-written kernel: how
much of the wire the chip's scheduler actually hides behind the MXU is
a measurement, not a property of this file.

Both primitives carry a custom VJP whose backward is built from the
DUAL primitive — d(all_gather_matmul)/dx is a matmul_reduce_scatter
and d(matmul_reduce_scatter)/dy is an all_gather_matmul — so the
backward overlaps exactly like the forward (the "path-aware backward").

Wire attribution: every ring pass charges the model axis through
``fusion.record_axis_wire_bytes`` under its own collective label
(``all_gather_matmul`` / ``matmul_reduce_scatter``), (n−1)/n of the
full payload per pass — exact under any chunk count, since sub-chunking
changes pipelining, never bytes.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..common.compat import axis_size as _axis_size

__all__ = [
    "all_gather_matmul",
    "matmul_reduce_scatter",
    "resolve_chunks",
    "ring_hops",
    "fusable",
    "expected_ppermutes",
]


# --------------------------------------------------------- ring shape


def ring_hops(n: int):
    """(forward, backward) hop counts of the bidirectional ring: the
    n−1 transfers split so both directions carry half the payload."""
    n = int(n)
    if n <= 1:
        return 0, 0
    return (n - 1 + 1) // 2, (n - 1) // 2


def resolve_chunks(tokens_per_rank: int, chunks: int = 0) -> int:
    """The effective sub-chunk count: ``chunks`` (or the
    ``HOROVOD_TP_OVERLAP_CHUNKS`` knob when 0) clamped to the largest
    divisor of the per-rank token chunk — a ragged split would change
    bytes-on-wire accounting, so we never allow one."""
    c = int(chunks)
    if c <= 0:
        try:
            c = int(os.environ.get("HOROVOD_TP_OVERLAP_CHUNKS", "0"))
        except ValueError:
            c = 0
    if c <= 0:
        c = 1
    t = max(int(tokens_per_rank), 1)
    c = min(c, t)
    while t % c:
        c -= 1
    return max(c, 1)


def expected_ppermutes(n: int, chunks: int = 1) -> int:
    """ppermute ops ONE primitive's forward ring lowers to: every
    sub-chunk makes the full bidirectional traversal."""
    return (int(n) - 1) * max(int(chunks), 1) if n > 1 else 0


def fusable(tokens: int, n: int) -> bool:
    """Whether the token dim splits evenly over the axis — the fused
    schedule needs equal chunks (callers fall back to the classic
    psum path otherwise)."""
    n = int(n)
    return n > 1 and int(tokens) % n == 0


def _perms(n: int):
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    return fwd, bwd


def _record(payload_bytes: int, axis_name: str, collective: str) -> None:
    from . import fusion as _fusion

    _fusion.record_axis_wire_bytes(payload_bytes, axis_name, collective)


# ----------------------------------------------------- ring lowerings


def _upd_tokens(out, val, row_start):
    """dynamic_update_slice of ``val`` into ``out`` at token offset
    ``row_start`` (token dim is -2)."""
    idx = [0] * out.ndim
    idx[-2] = row_start
    return lax.dynamic_update_slice(out, val, tuple(idx))


def _seg_tokens(x, start, size):
    return lax.dynamic_slice_in_dim(x, start, size, axis=-2)


def _ag_matmul_ring(x, w, axis_name: str, chunks: int):
    """Reference all_gather_matmul: bidirectional chunked ppermute ring.

    ``x`` [..., Tc, D] (this rank's token chunk), ``w`` [D, F]. Returns
    [..., n*Tc, F] with source rank j's rows at offset j*Tc — the
    ``lax.all_gather(..., tiled=True)`` order.
    """
    n = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    tc = x.shape[-2]
    local = x @ w
    out = jnp.zeros(x.shape[:-2] + (n * tc, w.shape[-1]), local.dtype)
    out = _upd_tokens(out, local, idx * tc)
    if n <= 1:
        return out
    h_fwd, h_bwd = ring_hops(n)
    perm_f, perm_b = _perms(n)
    c = resolve_chunks(tc, chunks)
    sc = tc // c
    for s in range(c):
        sub = _seg_tokens(x, s * sc, sc)
        fwd = sub
        for k in range(1, h_fwd + 1):
            fwd = lax.ppermute(fwd, axis_name, perm_f)
            src = (idx - k) % n
            out = _upd_tokens(out, fwd @ w, src * tc + s * sc)
        bwd = sub
        for k in range(1, h_bwd + 1):
            bwd = lax.ppermute(bwd, axis_name, perm_b)
            src = (idx + k) % n
            out = _upd_tokens(out, bwd @ w, src * tc + s * sc)
    return out


def _mrs_ring(y, w, axis_name: str, chunks: int):
    """Reference matmul_reduce_scatter: partial products per
    DESTINATION token chunk, reduced bidirectionally along the ring.

    ``y`` [..., T, Fl] (full tokens, local features), ``w`` [Fl, D].
    Returns this rank's [..., T/n, D] chunk of
    ``reduce_scatter(y @ w)`` (token-tiled, SUM).
    """
    n = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    t = y.shape[-2]
    if t % n:
        raise ValueError(
            f"matmul_reduce_scatter needs tokens ({t}) divisible by the "
            f"axis size ({n})"
        )
    tc = t // n
    h_fwd, h_bwd = ring_hops(n)
    perm_f, perm_b = _perms(n)
    c = resolve_chunks(tc, chunks)
    sc = tc // c

    def part(dest, s):
        return _seg_tokens(y, dest * tc + s * sc, sc) @ w

    accs = []
    for s in range(c):
        acc = part(idx, s)
        if h_fwd:
            f = part((idx + h_fwd) % n, s)
            for k in range(h_fwd - 1, 0, -1):
                f = lax.ppermute(f, axis_name, perm_f)
                f = f + part((idx + k) % n, s)
            f = lax.ppermute(f, axis_name, perm_f)
            acc = acc + f
        if h_bwd:
            b = part((idx - h_bwd) % n, s)
            for k in range(h_bwd - 1, 0, -1):
                b = lax.ppermute(b, axis_name, perm_b)
                b = b + part((idx - k) % n, s)
            b = lax.ppermute(b, axis_name, perm_b)
            acc = acc + b
        accs.append(acc)
    return accs[0] if c == 1 else jnp.concatenate(accs, axis=-2)


def _ring_grad_w(circ, full, axis_name: str, circ_is_lhs: bool):
    """The weight-gradient ring shared by both backwards:
    ``sum_j A_j^T @ B_j`` over source ranks j, where one operand's
    chunk circulates (``circ``, this rank's [..., Tc, *]) and the other
    is a local token slice of ``full`` [..., n*Tc, *]. ``circ_is_lhs``
    puts the circulating chunk on the transposed side."""
    n = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    tc = circ.shape[-2]

    def contract(a, b):
        # sum over every batch dim AND tokens: flatten to 2-D.
        a2 = a.reshape(-1, a.shape[-1])
        b2 = b.reshape(-1, b.shape[-1])
        return a2.T @ b2

    def one(chunk, src):
        seg = _seg_tokens(full, src * tc, tc)
        return contract(chunk, seg) if circ_is_lhs else contract(seg, chunk)

    dw = one(circ, idx)
    if n <= 1:
        return dw
    h_fwd, h_bwd = ring_hops(n)
    perm_f, perm_b = _perms(n)
    fwd = circ
    for k in range(1, h_fwd + 1):
        fwd = lax.ppermute(fwd, axis_name, perm_f)
        dw = dw + one(fwd, (idx - k) % n)
    bwd = circ
    for k in range(1, h_bwd + 1):
        bwd = lax.ppermute(bwd, axis_name, perm_b)
        dw = dw + one(bwd, (idx + k) % n)
    return dw


# --------------------------------------------------- public primitives


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _agmm(axis_name, chunks, x, w):
    n = _axis_size(axis_name)
    _record(x.size * x.dtype.itemsize * n, axis_name, "all_gather_matmul")
    return _ag_matmul_ring(x, w, axis_name, chunks)


def _agmm_fwd(axis_name, chunks, x, w):
    return _agmm(axis_name, chunks, x, w), (x, w)


def _agmm_bwd(axis_name, chunks, res, ct):
    x, w = res
    n = _axis_size(axis_name)
    # dx = reduce_scatter(ct @ w^T): the DUAL primitive — the backward
    # overlaps its wire exactly like the forward.
    _record(ct.size * ct.dtype.itemsize, axis_name, "matmul_reduce_scatter")
    dx = _mrs_ring(ct, w.T, axis_name, chunks).astype(x.dtype)
    # dw = all_gather(x)^T @ ct, accumulated as the x chunks ride the
    # same bidirectional ring (a second pass of the forward's bytes).
    _record(x.size * x.dtype.itemsize * n, axis_name, "all_gather_matmul")
    dw = _ring_grad_w(x, ct, axis_name, circ_is_lhs=True).astype(w.dtype)
    return dx, dw


_agmm.defvjp(_agmm_fwd, _agmm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _mrs(axis_name, chunks, y, w):
    _record(
        (y.size // max(y.shape[-1], 1)) * w.shape[-1] * y.dtype.itemsize,
        axis_name, "matmul_reduce_scatter",
    )
    return _mrs_ring(y, w, axis_name, chunks)


def _mrs_fwd(axis_name, chunks, y, w):
    return _mrs(axis_name, chunks, y, w), (y, w)


def _mrs_bwd(axis_name, chunks, res, ct):
    y, w = res
    n = _axis_size(axis_name)
    # dy = all_gather(ct) @ w^T: again the dual primitive.
    _record(ct.size * ct.dtype.itemsize * n, axis_name, "all_gather_matmul")
    dy = _ag_matmul_ring(ct, w.T, axis_name, chunks).astype(y.dtype)
    # dw = y^T @ all_gather(ct): the ct chunks ride the ring while each
    # arriving chunk contracts with its local y token slice.
    _record(ct.size * ct.dtype.itemsize * n, axis_name, "all_gather_matmul")
    dw = _ring_grad_w(ct, y, axis_name, circ_is_lhs=False).astype(w.dtype)
    return dy, dw


_mrs.defvjp(_mrs_fwd, _mrs_bwd)


def all_gather_matmul(
    x_shard: jax.Array,
    w: jax.Array,
    *,
    axis_name: str,
    chunks: int = 0,
) -> jax.Array:
    """``all_gather(x_shard, tiled over tokens) @ w`` with the gather
    fused into the matmul: chunk k+1 rides the ring while chunk k is on
    the MXU. ``x_shard`` [..., T/n, D] (token dim −2), ``w`` [D, F].
    Returns [..., T, F]. ``chunks`` sub-splits each rank chunk for a
    finer pipeline (0 = ``HOROVOD_TP_OVERLAP_CHUNKS``/auto); bytes on
    wire are chunk-count-invariant. Call inside shard_map."""
    return _agmm(axis_name, int(chunks), x_shard, w)


def matmul_reduce_scatter(
    y: jax.Array,
    w: jax.Array,
    *,
    axis_name: str,
    chunks: int = 0,
) -> jax.Array:
    """``reduce_scatter(y @ w, tiled over tokens)`` with the reduction
    fused into the matmul: each destination chunk's partial product is
    computed as the accumulator for it arrives on the ring. ``y``
    [..., T, Fl], ``w`` [Fl, D]. Returns this rank's [..., T/n, D]
    chunk. ``psum(y @ w) == all_gather(matmul_reduce_scatter(y, w))``.
    Call inside shard_map."""
    return _mrs(axis_name, int(chunks), y, w)
