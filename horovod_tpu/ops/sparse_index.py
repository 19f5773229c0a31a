"""A learned top-k choice of keys in front of attention (the lightning
indexer of DeepSeek-V3.2-Exp, as ``models/keye_vl.py`` runs it).

Per query ``t`` and key ``s <= t`` the indexer scores ``I[t, s] = sum_j w[t,
j] * relu(qI[t, j] . kI[s])`` over its ``J`` small heads and ONE key head;
query ``t`` attends to the ``min(t + 1, top_k)`` keys of the largest score,
ties toward the lower position (what ``jax.lax.top_k`` gives on a row with
``-inf`` beyond ``t``), and the indexer is trained to put its softmax over
that set where the attention heads' probabilities, summed over the heads,
are (:func:`index_kl`).

Nothing here holds a float32 ``[T, T]`` array: both functions walk the query
rows in blocks under a ``lax.map`` / ``lax.scan``, and what leaves
:func:`select_top_k` is the selection as an int8 mask (``[B, T, T]``, a
quarter of the float32 scores) and one logsumexp a row.

- :func:`select_top_k`: a block's scores (bfloat16 products, float32
  accumulation, float32 weighted sum), the EXACT k-th largest of each row
  without a sort, by a radix search on the scores' bit patterns (a float32
  maps to a uint32 of the same order; ``_RADIX_BITS`` bits a pass, each pass
  one compare-and-count over the block: 8 passes for 32 bits, where a sort of
  16384 scores a row is some hundred compare-exchange rounds), then of the
  positions that EQUAL the k-th value the lowest that are still missing, by
  the same search over positions.
- :func:`index_kl`: the objective and its gradient in one walk (the gradient
  of ``KL(p || softmax(I))`` by ``I`` is ``softmax(I) - p``, known as soon as
  the value is): per block the heads' scores from the attention's own q, k
  and logsumexp, their mean on the selected keys, the indexer's scores once
  more, and the products that carry ``softmax(I) - p`` into ``qI``, ``kI`` and
  ``w``. A custom VJP: the backward scales what the forward left.

Both are plain XLA in row blocks (no kernel of this module yet: the step's
time under the scopes ``sparse_index`` and ``sparse_index_loss`` is theirs).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import trace as _trace
from . import pallas_attention as _pa

_RADIX_BITS = 4     # bits of a key one pass of the search settles
_BLOCK_ROWS = 256   # query rows scored at a time: J x rows x T float32


def _block_rows(t: int, rows=None) -> int:
    rows = min(rows or _BLOCK_ROWS, t)
    while t % rows:
        rows -= 1
    return rows


def _sortable(x):
    """float32 -> uint32 of the same order (``-0.0`` as ``+0.0``, which
    compare equal as floats and must tie here too)."""
    b = lax.bitcast_convert_type(x, jnp.uint32)
    b = jnp.where(b == jnp.uint32(0x80000000), jnp.uint32(0), b)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def _radix_search(holds, total_bits: int, shape):
    """The largest ``a`` in ``[0, 2 ** total_bits)`` for which ``holds(a)``
    is true, given that it is true at 0 and monotone (true up to some value,
    false above), for every element of ``shape`` at once. ``holds`` takes
    candidates ``[*shape, C]`` uint32 and returns booleans of that shape;
    ``_RADIX_BITS`` bits are settled a pass."""
    passes = -(-total_bits // _RADIX_BITS)
    digits = jnp.arange(1, 2 ** _RADIX_BITS, dtype=jnp.uint32)

    def one(i, found):
        shift = (_RADIX_BITS * (passes - 1 - i)).astype(jnp.uint32)
        cands = found[..., None] | (digits << shift)
        digit = jnp.sum(holds(cands), axis=-1).astype(jnp.uint32)
        return found | (digit << shift)

    return lax.fori_loop(0, passes, one, jnp.zeros(shape, jnp.uint32))


def _count(cond):
    return jnp.sum(cond, axis=-1, dtype=jnp.int32)


def top_k_mask(scores, causal, top_k: int):
    """The selection of each row as booleans: of the positions ``causal``
    allows, the ``top_k`` of the largest ``scores`` (all of them where fewer
    are allowed), ties toward the lower position. ``scores``: float32 ``[...,
    R, T]``; ``causal``: booleans that broadcast to it."""
    T = scores.shape[-1]
    # 0 lies under every score's key, so a position not allowed never counts
    keys = jnp.where(causal, _sortable(scores), jnp.uint32(0))
    allowed = _count(jnp.broadcast_to(causal, keys.shape))
    k = jnp.minimum(allowed, top_k)[..., None]               # [..., R, 1]
    kth = _radix_search(
        lambda c: _count(keys[..., None, :] >= c[..., :, None]) >= k, 32,
        keys.shape[:-1])[..., None]
    above = keys > kth
    equal = (keys == kth) & causal
    missing = k - _count(above)[..., None]                   # at least 1
    pos = jnp.arange(T, dtype=jnp.uint32)
    # the position of the last of the lowest `missing` equal ones
    end = _radix_search(
        lambda a: _count(equal[..., None, :]
                         & (pos < a[..., :, None])) < missing,
        max(T.bit_length(), 1), keys.shape[:-1])[..., None]
    return above | (equal & (pos <= end))


def _scores(q_i, k_i, w):
    """``(x, I)``: the heads' products ``[B, R, J, T]`` float32 and their
    rectified weighted sum ``[B, R, T]``; ``q_i``: ``[B, R, J, D]``, ``k_i``:
    ``[B, T, D]`` (both as passed: bfloat16 from the model), ``w``: ``[B, R,
    J]`` float32."""
    x = jnp.einsum("brjd,bsd->brjs", q_i, k_i,
                   preferred_element_type=jnp.float32)
    return x, jnp.sum(w[..., None] * jax.nn.relu(x), axis=2)


def _split_rows(a, rows):
    """``[B, T, ...] -> [T / rows, B, rows, ...]`` for a walk over blocks."""
    B, T = a.shape[:2]
    return jnp.moveaxis(a.reshape(B, T // rows, rows, *a.shape[2:]), 1, 0)


def _join_rows(a):
    """The inverse of :func:`_split_rows`."""
    a = jnp.moveaxis(a, 0, 1)
    return a.reshape(a.shape[0], a.shape[1] * a.shape[2], *a.shape[3:])


def _select_xla(q_i, k_i, w, top_k, rows):
    """:func:`select_top_k` as plain XLA: a ``lax.map`` over blocks of
    ``rows`` query rows, each against every key."""
    T = k_i.shape[1]

    def block(xs):
        start, q_rows, w_rows = xs
        causal = (start + jnp.arange(rows))[:, None] >= jnp.arange(T)[None, :]
        _, scores = _scores(q_rows, k_i, w_rows.astype(jnp.float32))
        chosen = top_k_mask(scores, causal, top_k)
        lse = jax.nn.logsumexp(jnp.where(chosen, scores, -jnp.inf), axis=-1)
        return chosen.astype(jnp.int8), lse

    mask, lse = lax.map(block, (jnp.arange(0, T, rows),
                                _split_rows(q_i, rows), _split_rows(w, rows)))
    return _join_rows(mask), _join_rows(lse)


# --------------------------------------------------------------------------
# The selection as a kernel: a tile of query rows against its causal keys.
# --------------------------------------------------------------------------

_INT_MIN = -2 ** 31
_SEL_ROWS = 128      # query rows a grid step scores and selects
_SEL_COLS = 2048     # keys a pass of a step's inner loops takes
_SEL_VMEM = 56 * 2 ** 20


def _sel_plan(T: int, J: int, Di: int, dtype) -> Optional[tuple]:
    """``(rows, cols)`` of the selection kernel's grid step, or None where it
    does not run: its tiles are whole int8 tiles of the mask (32 x 128) and
    whole lanes of the keys."""
    rows, cols = min(_SEL_ROWS, T), min(_SEL_COLS, T)
    if (T % rows or T % cols or rows % 32 or cols % 128
            or jnp.dtype(dtype).itemsize > 4):
        return None
    return rows, cols


def _ordered(scores):
    """float32 -> int32 of the same order (``-0.0`` as ``+0.0``)."""
    b = lax.bitcast_convert_type(scores, jnp.int32)
    b = jnp.where(b == _INT_MIN, 0, b)
    return jnp.where(b < 0, b ^ 0x7FFFFFFF, b)


def _select_kernel(q_ref, kt_ref, w_ref, mask_ref, lse_ref, keys_ref, *,
                   top_k: int, rows: int, cols: int, heads: int, bits: int):
    """One tile of ``rows`` queries: their scores against the keys up to the
    tile's last row, ``cols`` at a time, kept as ordered int32 keys in VMEM;
    each row's k-th largest key bit by bit (a compare-and-count over the
    causal chunks a bit), of the positions that equal it the lowest still
    missing by the same search over positions; the mask and the logsumexp of
    the selected scores."""
    i = pl.program_id(1)
    first = i * rows
    chunks = (first + rows + cols - 1) // cols       # those with a causal key
    row = first + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    col0 = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    at = lambda c: pl.ds(pl.multiple_of(c * cols, cols), cols)
    w = w_ref[0]                                     # [rows, heads] f32

    def score(c, carry):
        kt = kt_ref[0, :, at(c)]                     # [Di, cols]
        acc = jnp.zeros((rows, cols), jnp.float32)
        for j in range(heads):
            x = jnp.dot(q_ref[0, j], kt, preferred_element_type=jnp.float32)
            acc = acc + w[:, j:j + 1] * jnp.maximum(x, 0.0)
        causal = row >= c * cols + col0
        keys_ref[:, at(c)] = jnp.where(causal, _ordered(acc), _INT_MIN)
        return carry

    lax.fori_loop(0, chunks, score, None)

    def count(pred):
        """``[rows, 1]``: how many of a row's causal keys meet ``pred(keys,
        positions)``."""
        def one(c, n):
            hit = pred(keys_ref[:, at(c)], c * cols + col0)
            return n + jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)
        return lax.fori_loop(0, chunks, one, jnp.zeros((rows, 1), jnp.int32))

    k = jnp.minimum(row + 1, top_k)
    # the k-th largest key: the largest value that at least k keys reach,
    # built from the sign down (a position not allowed holds INT_MIN, under
    # every candidate)
    kth = jnp.where(count(lambda keys, _: keys >= 0) >= k, 0, _INT_MIN)

    def value_bit(t, kth):
        cand = kth | jnp.left_shift(jnp.int32(1), 30 - t)
        return jnp.where(count(lambda keys, _: keys >= cand) >= k, cand, kth)

    kth = lax.fori_loop(0, 31, value_bit, kth)
    missing = k - count(lambda keys, _: keys > kth)  # at least 1

    # the position of the last of the lowest `missing` keys that equal kth:
    # the largest a with fewer than `missing` of them before it
    def position_bit(t, a):
        cand = a | jnp.left_shift(jnp.int32(1), bits - 1 - t)
        fewer = count(lambda keys, pos: (keys == kth) & (pos < cand)) < missing
        return jnp.where(fewer, cand, a)

    last = lax.fori_loop(0, bits, position_bit, jnp.zeros((rows, 1), jnp.int32))

    mask_ref[...] = jnp.zeros_like(mask_ref)

    def write(c, carry):
        m, l = carry
        keys = keys_ref[:, at(c)]
        chosen = (keys > kth) | ((keys == kth) & (c * cols + col0 <= last))
        mask_ref[0, :, at(c)] = chosen.astype(jnp.int32).astype(jnp.int8)
        scores = lax.bitcast_convert_type(
            jnp.where(keys < 0, keys ^ 0x7FFFFFFF, keys), jnp.float32)
        m_new = jnp.maximum(m, jnp.max(
            jnp.where(chosen, scores, -jnp.inf), axis=1, keepdims=True))
        p = jnp.where(chosen, jnp.exp(scores - m_new), 0.0)
        return m_new, l * jnp.exp(m - m_new) + jnp.sum(p, axis=1,
                                                        keepdims=True)

    m, l = lax.fori_loop(0, chunks, write, (
        jnp.full((rows, 1), -1e30, jnp.float32),
        jnp.zeros((rows, 1), jnp.float32)))
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape[1:])


def _select_pallas(q_i, k_i, w, top_k, rows, cols, interpret):
    """:func:`select_top_k` by :func:`_select_kernel`: the indexer's queries
    head-major, its keys transposed (the keys' positions in lanes)."""
    B, T, J, Di = q_i.shape
    vma = _pa._vma(q_i, k_i, w)
    mask, lse = pl.pallas_call(
        functools.partial(_select_kernel, top_k=top_k, rows=rows, cols=cols,
                          heads=J, bits=max((T - 1).bit_length(), 1)),
        out_shape=[jax.ShapeDtypeStruct((B, T, T), jnp.int8, vma=vma),
                   jax.ShapeDtypeStruct((B, T, 128), jnp.float32, vma=vma)],
        grid=(B, T // rows),
        in_specs=[
            pl.BlockSpec((1, J, rows, Di), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, Di, T), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, rows, J), lambda b, i: (b, i, 0)),
        ],
        out_specs=[pl.BlockSpec((1, rows, T), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, rows, 128), lambda b, i: (b, i, 0))],
        scratch_shapes=[pltpu.VMEM((rows, T), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_SEL_VMEM),
        name="sparse_index_select", interpret=interpret,
    )(q_i.transpose(0, 2, 1, 3), jnp.swapaxes(k_i, 1, 2),
      w.astype(jnp.float32))
    return mask, lse[..., 0]


def select_top_k(q_i, k_i, w, *, top_k: int, block_rows=None,
                 kernel: Optional[bool] = None):
    """``(selection int8 [B, T, T], lse float32 [B, T])``: 1 where query
    ``t`` attends to key ``s``, and the logsumexp of the indexer's scores
    over each row's selection (what :func:`index_kl` normalises by). No
    gradient: the choice is not differentiable and the caller's objective
    has its own rule. ``kernel=False`` asks for the XLA form (``block_rows``
    query rows at a time); left alone, the kernel runs wherever its plan
    takes the shapes, and a call site it refuses is recorded."""
    q_i, k_i, w = map(lax.stop_gradient, (q_i, k_i, w))
    B, T, J, Di = q_i.shape
    plan = None if kernel is False else _sel_plan(T, J, Di, q_i.dtype)
    rows = _block_rows(T, block_rows)
    _trace.note_plan(
        sparse_index_form="int8_mask", sparse_index_top_k=top_k,
        sparse_index_kernel=plan is not None,
        sparse_index_block_rows=plan[0] if plan else rows,
        # sum_t min(t + 1, top_k)
        sparse_index_pairs_selected=B * (
            min(top_k, T) * (min(top_k, T) + 1) // 2
            + max(T - top_k, 0) * top_k),
        sparse_index_pairs_causal=B * T * (T + 1) // 2,
    )
    if plan is None:
        if kernel is not False:
            _trace.note_fallback("sparse_index_select", "tiles_do_not_divide",
                                 t=T, heads=J, head_dim=Di)
        return _select_xla(q_i, k_i, w, top_k, rows)
    return _select_pallas(q_i, k_i, w, top_k, *plan,
                          _pa._resolve_interpret(None))


def _kl_walk(q, k, lse, selection, q_i, k_i, w, lse_i, sm_scale, rows,
             with_grads):
    """The walk of :func:`index_kl` over blocks of ``rows`` query rows:
    the summed KL terms and, ``with_grads``, ``d sum / d (qI, kI, w)``."""
    B, T, H, D = q.shape
    KV = k.shape[2]
    f32 = jnp.float32
    grouped = q.reshape(B, T, KV, H // KV, D)

    def block(carry, xs):
        total, dk_i = carry
        q_rows, lse_rows, sel, qi_rows, w_rows, lsei_rows = xs
        chosen = sel != 0
        s = jnp.einsum("brgjd,bsgd->bgjrs", q_rows, k,
                       preferred_element_type=f32) * sm_scale
        # the heads' probabilities on the selected keys, and their mean
        p = jnp.where(chosen[:, None, None],
                      jnp.exp(s - lse_rows[..., None]), 0.0)
        p = jnp.sum(p, axis=(1, 2)) / H                      # [B, R, T]
        x, scores = _scores(qi_rows, k_i, w_rows)
        logq = scores - lsei_rows[..., None]
        live = chosen & (p > 0)
        kl = jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0)) - logq),
                       0.0)
        total = total + jnp.sum(kl)
        if not with_grads:
            return (total, dk_i), None
        d_scores = jnp.where(chosen, jnp.exp(logq), 0.0) - p  # [B, R, T]
        dw = jnp.einsum("brs,brjs->brj", d_scores, jax.nn.relu(x))
        dx = jnp.where(x > 0, d_scores[:, :, None] * w_rows[..., None],
                       0.0).astype(q_i.dtype)                # [B, R, J, T]
        dq_i = jnp.einsum("brjs,bsd->brjd", dx, k_i,
                          preferred_element_type=f32)
        dk_i = dk_i + jnp.einsum("brjs,brjd->bsd", dx, qi_rows,
                                 preferred_element_type=f32)
        return (total, dk_i), (dq_i, dw)

    # a block's rows of the heads' logsumexp: [T / rows, B, KV, group, rows]
    lse = jnp.moveaxis(lse.reshape(B, KV, H // KV, T // rows, rows), 3, 0)
    xs = (_split_rows(grouped, rows), lse, *(_split_rows(a, rows) for a in (
        selection, q_i, w.astype(f32), lse_i)))
    init = (jnp.zeros((), f32), jnp.zeros(k_i.shape, f32))
    (total, dk_i), ys = lax.scan(block, init, xs)
    if not with_grads:
        return total, None
    dq_i, dw = (_join_rows(y) for y in ys)
    return total, (dq_i, dk_i, dw)


# --------------------------------------------------------------------------
# The objective as a kernel: tiles of (keys, queries), every head in a tile.
# --------------------------------------------------------------------------

KL_RESIDUALS = "sparse_index_kl_grads"   # the objective's saved gradient
_KL_BLOCK = 256
_KL_VMEM = 64 * 2 ** 20
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _kl_plan(T: int, H: int, KV: int) -> Optional[int]:
    """The objective kernel's tile (keys and queries alike), or None where
    it does not run: a tile holds the queries in whole lanes."""
    block = min(_KL_BLOCK, T)
    if T % block or block % 128 or H % KV:
        return None
    return block


def _kl_kernel(table_ref, q_ref, k_ref, lse_ref, selt_ref, qi_ref, ki_ref,
               wt_ref, lsei_ref, *outs, sm_scale: float, block: int,
               heads: int, kv_heads: int, index_heads: int, with_grads: bool):
    """One (q block, k block) pair of the objective, held TRANSPOSED (keys in
    sublanes, queries in lanes) so that every per-query row (a head's
    logsumexp, the indexer's weights and logsumexp) broadcasts along
    sublanes: the heads' probabilities on the selected pairs summed over ALL
    the heads (a loop inside the step: the flash kernels fold heads into
    their grid and never see two of a query), the indexer's scores, the KL
    terms, and, ``with_grads``, the products that carry ``softmax(I) - p``
    into ``qI`` and ``w`` (summed in VMEM across the K axis, the grid's
    innermost) and ``kI`` (its whole float32 gradient stays in VMEM across
    the batch row's grid). A pair without a selected pair is neither fetched
    nor computed (the flash kernels' table)."""
    if with_grads:
        (loss_ref, dqi_ref, dki_ref, dwt_ref,
         loss_acc, dqi_acc, dwt_acc) = outs
    else:
        loss_ref, loss_acc = outs
    b, i, j = (pl.program_id(a) for a in range(3))
    n_q, n_k = pl.num_programs(1), pl.num_programs(2)
    group = heads // kv_heads
    f32 = jnp.float32

    @pl.when(j == 0)
    def _init():
        loss_acc[...] = jnp.zeros_like(loss_acc)
        if with_grads:
            dqi_acc[...] = jnp.zeros_like(dqi_acc)
            dwt_acc[...] = jnp.zeros_like(dwt_acc)

            @pl.when(i == 0)
            def _init_dk():
                dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when(table_ref[(b * n_q + i) * n_k + j] == j)
    def _pair():
        chosen = selt_ref[0].astype(f32) > 0.0                # [Bk, Bq]

        def head(h, total):
            st = lax.dot_general(k_ref[0, lax.div(h, group)], q_ref[0, h],
                                 _NT, preferred_element_type=f32) * sm_scale
            return total + jnp.exp(st - lse_ref[0, h])        # lse: [1, Bq]

        p = lax.fori_loop(0, heads, head, jnp.zeros((block, block), f32))
        p = jnp.where(chosen, p * (1.0 / heads), 0.0)
        ki = ki_ref[0]                                        # [Bk, Di]
        x_of = lambda jj: lax.dot_general(
            ki, qi_ref[0, jj], _NT, preferred_element_type=f32)
        scores = jnp.zeros((block, block), f32)
        for jj in range(index_heads):
            scores = scores + wt_ref[0, jj] * jnp.maximum(x_of(jj), 0.0)
        logq = scores - lsei_ref[0]
        live = p > 0.0
        kl = jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0)) - logq),
                       0.0)
        loss_acc[...] += jnp.sum(kl, axis=0, keepdims=True)
        if not with_grads:
            return
        d_scores = jnp.where(chosen, jnp.exp(logq), 0.0) - p
        dk = jnp.zeros(ki.shape, f32)
        for jj in range(index_heads):
            x = x_of(jj)
            dwt_acc[jj] += jnp.sum(d_scores * jnp.maximum(x, 0.0), axis=0,
                                   keepdims=True)
            dx = jnp.where(x > 0.0, d_scores * wt_ref[0, jj], 0.0).astype(
                ki.dtype)
            dqi_acc[jj] += lax.dot_general(dx, ki, _TN,
                                           preferred_element_type=f32)
            dk = dk + jnp.dot(dx, qi_ref[0, jj], preferred_element_type=f32)
        at = pl.ds(pl.multiple_of(j * block, block), block)
        dki_ref[0, at] += dk

    @pl.when(j == n_k - 1)
    def _finalize():
        loss_ref[0, 0] = loss_acc[...]
        if with_grads:
            dqi_ref[0] = dqi_acc[...].astype(dqi_ref.dtype)
            dwt_ref[0] = dwt_acc[...]


def _kl_pallas(q, k, lse, selection, q_i, k_i, w, lse_i, sm_scale, block,
               with_grads, interpret):
    """:func:`_kl_walk`'s results by :func:`_kl_kernel`."""
    B, T, H, D = q.shape
    KV, (J, Di) = k.shape[2], q_i.shape[2:]
    n = T // block
    f32 = jnp.float32
    vma = _pa._vma(q, k, lse, selection, q_i, k_i, w, lse_i)
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, vma=vma)
    fetched = lambda b, i, j, t: t[(b * n + i) * n + j]
    in_specs = [
        pl.BlockSpec((1, H, block, D), lambda b, i, j, t: (b, 0, i, 0)),
        pl.BlockSpec((1, KV, block, D),
                     lambda b, i, j, t: (b, 0, fetched(b, i, j, t), 0)),
        pl.BlockSpec((1, H, 1, block), lambda b, i, j, t: (b, 0, 0, i)),
        pl.BlockSpec((1, block, block),
                     lambda b, i, j, t: (b, fetched(b, i, j, t), i)),
        pl.BlockSpec((1, J, block, Di), lambda b, i, j, t: (b, 0, i, 0)),
        pl.BlockSpec((1, block, Di),
                     lambda b, i, j, t: (b, fetched(b, i, j, t), 0)),
        pl.BlockSpec((1, J, 1, block), lambda b, i, j, t: (b, 0, 0, i)),
        pl.BlockSpec((1, 1, block), lambda b, i, j, t: (b, 0, i)),
    ]
    out_shape = [shape((B, n, 1, block), f32)]
    out_specs = [pl.BlockSpec((1, 1, 1, block),
                              lambda b, i, j, t: (b, i, 0, 0))]
    scratch = [pltpu.VMEM((1, block), f32)]
    if with_grads:
        out_shape += [shape((B, J, T, Di), q_i.dtype), shape((B, T, Di), f32),
                      shape((B, J, 1, T), f32)]
        out_specs += [
            pl.BlockSpec((1, J, block, Di), lambda b, i, j, t: (b, 0, i, 0)),
            pl.BlockSpec((1, T, Di), lambda b, i, j, t: (b, 0, 0)),
            pl.BlockSpec((1, J, 1, block), lambda b, i, j, t: (b, 0, 0, i)),
        ]
        scratch += [pltpu.VMEM((J, block, Di), f32),
                    pltpu.VMEM((J, 1, block), f32)]
    outs = pl.pallas_call(
        functools.partial(_kl_kernel, sm_scale=sm_scale, block=block,
                          heads=H, kv_heads=KV, index_heads=J,
                          with_grads=with_grads),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, n, n), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            # kI's gradient lies in VMEM across a batch row's whole grid
            dimension_semantics=("parallel",) + (
                ("arbitrary",) if with_grads else ("parallel",))
            + ("arbitrary",),
            vmem_limit_bytes=_KL_VMEM),
        name="sparse_index_kl", interpret=interpret,
    )(_pa._fetch_table(selection, block, block),
      q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), lse[:, :, None, :],
      jnp.swapaxes(selection, 1, 2), q_i.transpose(0, 2, 1, 3), k_i,
      jnp.swapaxes(w.astype(f32), 1, 2)[:, :, None, :], lse_i[:, None, :])
    total = jnp.sum(outs[0])
    if not with_grads:
        return total, None
    dq_i, dk_i, dwt = outs[1:]
    return total, (dq_i.transpose(0, 2, 1, 3), dk_i,
                   jnp.swapaxes(dwt[:, :, 0, :], 1, 2))


def _kl(q, k, lse, selection, q_i, k_i, w, lse_i, sm_scale, rows, kernel,
        with_grads):
    """The summed KL terms and (``with_grads``) their gradient by ``(qI, kI,
    w)``, by the kernel where it runs."""
    if kernel:
        return _kl_pallas(q, k, lse, selection, q_i, k_i, w, lse_i, sm_scale,
                          kernel, with_grads, _pa._resolve_interpret(None))
    return _kl_walk(q, k, lse, selection, q_i, k_i, w, lse_i, sm_scale, rows,
                    with_grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _index_kl(q, k, lse, selection, q_i, k_i, w, lse_i, sm_scale, rows,
              kernel):
    return _kl(q, k, lse, selection, q_i, k_i, w, lse_i, sm_scale, rows,
               kernel, with_grads=False)[0]


def _index_kl_fwd(q, k, lse, selection, q_i, k_i, w, lse_i, sm_scale, rows,
                  kernel):
    total, grads = _kl(q, k, lse, selection, q_i, k_i, w, lse_i, sm_scale,
                       rows, kernel, with_grads=True)
    # as a kernel's backward leaves them: in the dtypes of what they are the
    # cotangents of; NAMED, so that a caller's recomputation can keep them
    # (``models/recompute.remat_layer`` keeps every such name: some
    # 36 MB a layer at 16384 positions) and the walk runs once a step, where
    # an unnamed residual is recomputed and the first pass walks for the
    # value alone
    return total, tuple(checkpoint_name(g.astype(a.dtype), KL_RESIDUALS)
                        for g, a in zip(grads, (q_i, k_i, w)))


def _index_kl_bwd(sm_scale, rows, kernel, grads, g):
    # None: the attention's q, k and logsumexp are the target's side, which
    # the objective holds fixed; the selection is no number; lse_i's part is
    # in the rule already
    scaled = tuple((g * d.astype(jnp.float32)).astype(d.dtype) for d in grads)
    return (None, None, None, None, *scaled, None)


_index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)


def index_kl(q, k, lse, selection, q_i, k_i, w, lse_i, *, sm_scale: float,
             block_rows=None, kernel: Optional[bool] = None):
    """``sum_t KL(p[t, :] || softmax over S_t of I[t, :])`` over all rows of
    the batch (the caller divides by their number).

    ``q``: ``[B, T, H, D]`` and ``k``: ``[B, T, KV, D]`` as the attention
    kernel was fed them, ``lse``: ``[B, H, T]`` its logsumexp over each
    query's SELECTED keys, ``selection``: int8 ``[B, T, T]``; ``p`` is the
    mean over the heads of ``exp(q . k * sm_scale - lse)`` on the selection,
    and is held fixed: no gradient reaches ``q``, ``k`` or ``lse``. ``q_i``
    (``[B, T, J, Di]``), ``k_i`` (``[B, T, Di]``) and ``w`` (``[B, T, J]``)
    are the indexer's, ``lse_i`` (``[B, T]``) :func:`select_top_k`'s second
    result; the gradient reaches those three alone (``lse_i`` is a function
    of them that the rule ``softmax(I) - p`` has already taken in).
    ``kernel=False`` asks for the XLA form (``block_rows`` query rows at a
    time); left alone, the kernel runs wherever its plan takes the shapes,
    and a call site it refuses is recorded."""
    B, T, H, _ = q.shape
    rows = _block_rows(T, block_rows)
    block = None if kernel is False else _kl_plan(T, H, k.shape[2])
    _trace.note_plan(sparse_index_loss_kernel=block is not None,
                     sparse_index_loss_block=block or rows)
    if block is None and kernel is not False:
        _trace.note_fallback("sparse_index_kl", "tiles_do_not_divide", t=T,
                             heads=H, kv_heads=k.shape[2])
    return _index_kl(q, k, lse, selection, q_i, k_i, w, lse_i,
                     float(sm_scale), rows, block)
