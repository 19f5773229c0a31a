"""The stream mix of ``models/xing4.py`` (manifold-constrained
hyper-connections) as an op of its own: the read of the ``n`` residual
streams before a sublayer (:func:`pre`) and their mixed write after it
(:func:`post`), each with a custom VJP, each direction ONE pass over a
``(T, C)`` tile of the streams.

Per token, with ``X [n, C]`` the streams (``docs/models.md`` has the
equations)::

    inv    = rsqrt(mean(X^2) + eps)
    maps   = alpha * ((vec(X) @ phi) * inv) + b           # [2n + n n] float32
    H_pre  = sigmoid(maps[:n]);  H_post = 2 sigmoid(maps[n:2n])
    H_res  = sinkhorn(exp(clip(maps[2n:])))               # [n, n]
    h      = sum_i H_pre[i] X[i]                          # pre
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y         # post

**The passes.** Four Pallas kernels, a grid step a tile of ``tile`` tokens of
all ``n`` streams (:func:`plan`):

* ``hc_mix_pre`` reads the streams once: the sum of squares, the product
  with ``phi``, the maps and the Sinkhorn rounds on the tile's ``[n n,
  tile]`` planes (tokens in lanes), then ``h`` from the tile still in VMEM.
* ``hc_mix_post`` reads the streams and ``y`` and writes ``X'``.
* ``hc_mix_post_bwd`` reads ``dX'``, the streams and ``y`` and writes the
  streams' cotangent, ``dy`` and the per-token row-dots that are the maps'
  cotangents.
* ``hc_mix_pre_bwd`` reads ``dh`` and the streams and ADDS its part of the
  streams' cotangent to the one ``post``'s backward left, in place: ``pre``
  returns the streams again (``carried``) for ``post`` to read, so that
  cotangent arrives as an argument and no XLA add passes over the streams.

**What is saved for the backward:** the streams, ``y``, ``H_post``,
``H_res`` and, a mix, ``2n + n n + 1`` float32 planes: the raw product and
``inv``. The Sinkhorn rounds are recomputed from them inside
``hc_mix_pre_bwd`` and transposed there; none of their planes is kept.

**The split product.** ``phi`` is float32 and the streams are bfloat16, so a
float32 product at ``HIGHEST`` splits both operands into three bfloat16 terms
and multiplies six pairs of them, four of which hold the streams' zero middle
and low terms. Here ``phi = hi + mid + lo`` (:func:`split3`: ``hi =
bf16(phi)``, ``mid = bf16(phi - hi)``, ``lo = bf16(phi - hi - mid)``) is
written side by side and multiplied by the bfloat16 streams in ONE pass with
float32 accumulation: the same products of bfloat16 terms (each exact in
float32) and the same float32 sums as the ``HIGHEST`` product forms, in
another order. Its transposes likewise: ``d_phi = X^T G`` with ``G`` in three
terms (one pass, the parts summed in float32), and ``dX = G phi^T`` with both
operands float32 as one product whose contraction holds the six pairs
``HIGHEST`` forms (``[hi hi mid hi lo mid]`` of ``G`` against ``[hi mid hi lo
hi mid]`` of ``phi``). No term and no round is dropped.

Shapes :func:`plan` refuses (streams that are not bfloat16, a width that is
not whole lanes, a length no tile divides, a tile that does not fit) take the
same equations as plain XLA (:func:`_xla_pre`, :func:`_xla_post`: the form
the model had before the kernels, the tests' oracle), whose backward is
JAX's transpose of them; the build ledger records the call site.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import trace as _trace
from . import pallas_attention as _pa
from .pallas_attention import _LANES, _VMEM_BUDGET, _vma

_HIGHEST = lax.Precision.HIGHEST
_PREF_TILE = 256             # tokens a grid step, at most
_ROWS = 16                   # tokens an inner step: a packed bfloat16 register
_VMEM_CEILING = 48 * 2 ** 20  # the most a kernel asks of the scoped limit
_NN = (((1,), (0,)), ((), ()))


class Spec(NamedTuple):
    """What the equations take besides arrays (``Xing4Config``'s fields)."""
    eps: float
    hc_eps: float
    clamp: Tuple[float, float]
    iters: int


def sinkhorn(m, iters: int, eps: float):
    """``iters`` rounds of rows then columns on ``m`` (``[n, n, ...]``, row
    index first): each divides by the sum plus ``eps``."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def split3(x):
    """``x`` (float32) as three bfloat16 terms, ``x = hi + mid + lo`` to
    float32's last bit: the terms a ``HIGHEST`` product multiplies."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = x.astype(bf16)
    rest = x - hi.astype(f32)
    mid = rest.astype(bf16)
    return hi, mid, (rest - mid.astype(f32)).astype(bf16)


# --------------------------------------------------------------------------
# The XLA form: the fallback and the tests' oracle.

def _xla_pre(streams, phi, alpha, b, spec):
    """``streams [n, B, T, C] -> (h [B, T, C], post [n, B, T], res [n, n, B,
    T])``: float32 maps, the product at full precision a stream's rows of
    ``phi`` at a time, the token's scalar taken out of it."""
    f32 = jnp.float32
    n, B, T, C = streams.shape
    xf = streams.astype(f32)
    inv_rms = lax.rsqrt(jnp.mean(xf * xf, axis=(0, 3)) + spec.eps)
    by_stream = phi.reshape(n, C, -1)
    maps = sum(jnp.einsum("btc,cm->mbt", xf[i], by_stream[i],
                          precision=_HIGHEST) for i in range(n)) * inv_rms
    maps = _wide(alpha, n)[:, None, None] * maps + b[:, None, None]
    pre = jax.nn.sigmoid(maps[:n])
    post = 2.0 * jax.nn.sigmoid(maps[n:2 * n])
    res = sinkhorn(jnp.exp(jnp.clip(maps[2 * n:], *spec.clamp)).reshape(
        n, n, B, T), spec.iters, spec.hc_eps)
    h = sum(pre[i][..., None] * xf[i] for i in range(n)).astype(streams.dtype)
    return h, post, res


def _xla_post(streams, y, post, res):
    """``X'[i] = sum_j res[i, j] X[j] + post[i] y``, float32 sums rounded
    once."""
    xf, yf = streams.astype(jnp.float32), y.astype(jnp.float32)
    n = streams.shape[0]
    return jnp.stack([
        (sum(res[i, j][..., None] * xf[j] for j in range(n))
         + post[i][..., None] * yf).astype(streams.dtype)
        for i in range(n)])


# --------------------------------------------------------------------------
# The plan.

def _tile_bytes(n: int, tile: int, C: int) -> int:
    """What the largest of the four kernels (``hc_mix_pre_bwd``) keeps in
    VMEM at ``tile`` tokens a grid step: the pipeline's two buffers of the
    streams, the cotangent in and the cotangent out (bfloat16), of ``dh``,
    the split ``phi`` transposed (160 bfloat16 rows) and ``d_phi``'s
    accumulator (80 float32 rows), a stream's float32 product and the
    lane-replicated coefficients."""
    stream_blocks = 2 * (3 * n + 1) * tile * C * 2
    phi_blocks = 2 * n * C * (160 * 2 + 80 * 4)
    temporaries = tile * C * 4 + 3 * n * tile * _LANES * 4
    return stream_blocks + phi_blocks + temporaries


def _refusal(n: int, T: int, C: int, dtype) -> Optional[str]:
    """Why the shapes are not the kernels' (the word the build ledger's
    fallback record carries), or None where they are."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "streams_not_bfloat16"
    if C % _LANES:
        return "width_not_whole_lanes"
    if T % _LANES:
        return "tokens_not_whole_tiles"
    if (2 * n + n * n) % 8 or 5 * (2 * n + n * n) > _LANES:
        return "maps_not_whole_sublanes"
    if _tile_bytes(n, _LANES, C) > _VMEM_CEILING:
        return "no_tile_fits_vmem"
    return None


def plan(n: int, T: int, C: int, dtype) -> Optional[int]:
    """Tokens a grid step, or ``None`` where the kernels do not run: streams
    that are not bfloat16 (the split product is the float32 product because
    the streams have no middle and low term), a width that is not whole
    lanes, a length that no tile of whole lanes divides (the maps' planes
    have the tokens in lanes), a tile that does not fit VMEM. The largest
    tile up to ``_PREF_TILE`` that divides ``T`` and fits."""
    if _refusal(n, T, C, dtype):
        return None
    tile = _PREF_TILE
    while tile > _LANES and (T % tile
                             or _tile_bytes(n, tile, C) > _VMEM_CEILING):
        tile //= 2
    return tile


def _vmem_limit(need: int) -> Optional[int]:
    """The scoped limit a kernel asks for: none where Mosaic's default
    holds it."""
    return None if need <= _VMEM_BUDGET else need + 8 * 2 ** 20


# --------------------------------------------------------------------------
# Inside the kernels. PLANES are ``[p, tile]`` float32 with the tokens in
# lanes (the maps, their cotangents); TILES are ``[tile, C]`` with the tokens
# in sublanes (the streams). A plane crosses to the tiles' side as a
# lane-replicated ``[tile, 128]`` COLUMN, and a tile's per-token sums cross
# back as lanes of a ``[tile, 128]`` array that is transposed once.
#
# The kernels' own order of the maps is ``[pre | post | res column-major]``
# (``_reorder``): a column of ``res`` is then four consecutive planes, and the
# Sinkhorn rounds are sums of four ``[n, tile]`` arrays (over a row) and of
# four sublanes (over a column).

def _reorder(x, n: int):
    """The maps (``x``'s last axis) from the model's order to the kernels'
    or back: the ``res`` part transposed, which is its own inverse."""
    res = x[..., 2 * n:].reshape(x.shape[:-1] + (n, n))
    return jnp.concatenate([x[..., :2 * n], jnp.swapaxes(res, -1, -2).reshape(
        x.shape[:-1] + (n * n,))], axis=-1)


def _wide(alpha, n: int):
    """``alpha [3]`` a map: ``[pre | post | res]`` in either order."""
    return jnp.concatenate([jnp.broadcast_to(a, (size,)) for a, size in zip(
        alpha, (n, n, n * n))])


def _column(row, tile):
    """A ``[1, tile]`` plane as ``[tile, 128]``: its values down the
    sublanes, replicated over the lanes."""
    return jnp.broadcast_to(row, (_LANES, tile)).T


def _fill_columns(col_ref, planes, tile):
    for p in range(planes.shape[0]):
        col_ref[p] = _column(planes[p:p + 1], tile)


def _sweep(tile, body):
    """``body(rows)`` for every ``_ROWS`` tokens of the tile."""
    def step(r, carry):
        body(pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS))
        return carry
    lax.fori_loop(0, tile // _ROWS, step, None)


def _chunks(C):
    return [slice(k * _LANES, (k + 1) * _LANES) for k in range(C // _LANES)]


def _maps(raw, inv, ab_ref, n, spec):
    """From the raw product and ``inv`` (planes, the kernels' order) to
    ``(sigmoid of the first 2n maps, the clipped maps' exponential as n
    columns of [n, tile], the clip's mask)``."""
    maps = ab_ref[:, 0:1] * (raw * inv) + ab_ref[:, 1:2]
    sig = jax.nn.sigmoid(maps[:2 * n])
    lo, hi = spec.clamp
    wide = maps[2 * n:]
    m0 = jnp.exp(jnp.clip(wide, lo, hi))
    inside = (wide >= lo) & (wide <= hi)
    cols = [m0[n * j:n * (j + 1)] for j in range(n)]
    return sig, cols, inside


def _sinkhorn_cols(cols, spec, keep=None):
    """The rounds on ``n`` columns of ``[n, tile]`` (``cols[j][i]`` is
    ``M[i, j]``); ``keep`` collects what a round's transpose reads."""
    n = len(cols)
    for _ in range(spec.iters):
        rows = functools.reduce(jnp.add, cols) + spec.hc_eps
        cols = [c / rows for c in cols]
        if keep is not None:
            keep.append((cols, rows))
        sums = [jnp.sum(c, axis=0, keepdims=True) + spec.hc_eps for c in cols]
        cols = [c / s for c, s in zip(cols, sums)]
        if keep is not None:
            keep.append((cols, sums))
    return cols


def _sinkhorn_cols_bwd(d_cols, keep):
    """The rounds transposed: ``y = c / (S(c) + eps)`` gives ``dc = (dy -
    S(dy y)) / (S(c) + eps)``, with ``S`` the sum over a row or a column."""
    for half in range(len(keep) - 1, -1, -1):
        cols, sums = keep[half]
        if half % 2:      # columns: a sum over the sublanes of each
            d_cols = [(d - jnp.sum(d * c, axis=0, keepdims=True)) / s
                      for d, c, s in zip(d_cols, cols, sums)]
        else:             # rows: a sum over the n columns
            dot = functools.reduce(
                jnp.add, [d * c for d, c in zip(d_cols, cols)])
            d_cols = [(d - dot) / sums for d in d_cols]
    return d_cols


def _f32(ref, *index):
    return ref[index].astype(jnp.float32)


def _pre_kernel(x_ref, phi_ref, ab_ref, h_ref, post_ref, res_ref, raw_ref,
                col_ref, *, n, tile, C, spec):
    f32 = jnp.float32
    maps = 2 * n + n * n
    # pass 1 over the tile: the product and the sum of squares
    acc = jnp.zeros((tile, _LANES), f32)
    for i in range(n):
        acc += lax.dot_general(x_ref[i], phi_ref[i * C:(i + 1) * C, :], _NN,
                               preferred_element_type=f32)
    col_ref[0] = jnp.zeros((tile, _LANES), f32)

    def squares(rows):
        part = col_ref[0, rows, :]
        for i in range(n):
            for lanes in _chunks(C):
                x = _f32(x_ref, i, rows, lanes)
                part += x * x
        col_ref[0, rows, :] = part

    _sweep(tile, squares)
    lane = lax.broadcasted_iota(jnp.int32, (tile, _LANES), 1)
    ss = jnp.sum(col_ref[0], axis=1, keepdims=True)
    planes = jnp.where(lane == 3 * maps, ss, acc).T       # [128, tile]
    # the three terms' parts, low to high
    raw = (planes[2 * maps:3 * maps] + planes[maps:2 * maps]) + planes[:maps]
    inv = lax.rsqrt(planes[3 * maps:3 * maps + 1] / (n * C) + spec.eps)
    sig, cols, _ = _maps(raw, inv, ab_ref, n, spec)
    cols = _sinkhorn_cols(cols, spec)
    raw_ref[0:maps, :] = raw
    raw_ref[maps:maps + 1, :] = inv
    post_ref[...] = 2.0 * sig[n:]
    for i in range(n):
        for j in range(n):
            res_ref[n * i + j:n * i + j + 1, :] = cols[j][i:i + 1]
    # pass 2: h from the tile still in VMEM
    _fill_columns(col_ref, sig[:n], tile)

    def read(rows):
        w = [col_ref[i, rows, :] for i in range(n)]
        for lanes in _chunks(C):
            h = w[0] * _f32(x_ref, 0, rows, lanes)
            for i in range(1, n):
                h += w[i] * _f32(x_ref, i, rows, lanes)
            h_ref[rows, lanes] = h.astype(h_ref.dtype)

    _sweep(tile, read)


def _post_kernel(x_ref, y_ref, post_ref, res_ref, out_ref, col_ref, *,
                 n, tile, C):
    _fill_columns(col_ref, jnp.concatenate(
        [res_ref[...], post_ref[...]], axis=0), tile)

    def write(rows):
        w = [col_ref[p, rows, :] for p in range(n * n + n)]
        for lanes in _chunks(C):
            x = [_f32(x_ref, j, rows, lanes) for j in range(n)]
            y = _f32(y_ref, rows, lanes)
            for i in range(n):
                out = w[n * i] * x[0]
                for j in range(1, n):
                    out += w[n * i + j] * x[j]
                out += w[n * n + i] * y
                out_ref[i, rows, lanes] = out.astype(out_ref.dtype)

    _sweep(tile, write)


def _post_bwd_kernel(g_ref, x_ref, y_ref, post_ref, res_ref,
                     dx_ref, dy_ref, dpost_ref, dres_ref, col_ref, dot_ref,
                     *, n, tile, C):
    f32 = jnp.float32
    pairs = n * n + n
    _fill_columns(col_ref, jnp.concatenate(
        [res_ref[...], post_ref[...]], axis=0), tile)
    lane = lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)

    def transposed(rows):
        w = [col_ref[p, rows, :] for p in range(pairs)]
        for lanes in _chunks(C):
            g = [_f32(g_ref, i, rows, lanes) for i in range(n)]
            for j in range(n):
                dx = w[j] * g[0]
                for i in range(1, n):
                    dx += w[n * i + j] * g[i]
                dx_ref[j, rows, lanes] = dx.astype(dx_ref.dtype)
            dy = w[n * n] * g[0]
            for i in range(1, n):
                dy += w[n * n + i] * g[i]
            dy_ref[rows, lanes] = dy.astype(dy_ref.dtype)

    def row_dots(rows):
        # d_res[i, j] = sum_c g[i] x[j], d_post[i] = sum_c g[i] y: a lane of
        # dot_ref a pair, a stream of g at a time
        out = jnp.zeros((_ROWS, _LANES), f32)
        for i in range(n):
            part = [jnp.zeros((_ROWS, _LANES), f32) for _ in range(n + 1)]
            for lanes in _chunks(C):
                g = _f32(g_ref, i, rows, lanes)
                for j in range(n):
                    part[j] += g * _f32(x_ref, j, rows, lanes)
                part[n] += g * _f32(y_ref, rows, lanes)
            for j in range(n + 1):
                p = n * i + j if j < n else n * n + i
                out = jnp.where(lane == p, jnp.sum(
                    part[j], axis=1, keepdims=True), out)
        dot_ref[rows, :] = out

    _sweep(tile, transposed)
    _sweep(tile, row_dots)
    dots = dot_ref[...].T                                  # [128, tile]
    dres_ref[...] = dots[:n * n]
    dpost_ref[...] = dots[n * n:pairs]


def _pre_bwd_kernel(dh_ref, x_ref, carried_ref, raw_ref, dpost_ref, dres_ref,
                    phi_ref, ab_ref, dx_ref, dmaps_ref, dphi_ref,
                    col_ref, dot_ref, prod_ref, *, n, tile, C, spec):
    f32, bf16 = jnp.float32, jnp.bfloat16
    maps = 2 * n + n * n
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(first)
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)

    lane = lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)

    def row_dots(rows):        # d_pre[i] = sum_c dh x[i]
        part = [jnp.zeros((_ROWS, _LANES), f32) for _ in range(n)]
        for lanes in _chunks(C):
            dh = _f32(dh_ref, rows, lanes)
            for i in range(n):
                part[i] += dh * _f32(x_ref, i, rows, lanes)
        out = jnp.zeros((_ROWS, _LANES), f32)
        for i in range(n):
            out = jnp.where(lane == i, jnp.sum(
                part[i], axis=1, keepdims=True), out)
        dot_ref[rows, :] = out

    _sweep(tile, row_dots)
    d_pre = dot_ref[...].T[:n]                             # [n, tile]
    # the maps again from the saved product, and their transposes
    raw, inv = raw_ref[0:maps, :], raw_ref[maps:maps + 1, :]
    sig, cols, inside = _maps(raw, inv, ab_ref, n, spec)
    keep = []
    _sinkhorn_cols(cols, spec, keep)
    dres = dres_ref[...]
    d_cols = _sinkhorn_cols_bwd(
        [jnp.concatenate([dres[n * i + j:n * i + j + 1] for i in range(n)],
                         axis=0) for j in range(n)], keep)
    d_sig = jnp.concatenate([d_pre, 2.0 * dpost_ref[...]], axis=0)
    d_maps = jnp.concatenate(
        [d_sig * sig * (1.0 - sig),
         jnp.where(inside, jnp.concatenate(d_cols, axis=0)
                   * jnp.concatenate(cols, axis=0), 0.0)], axis=0)
    dmaps_ref[...] = d_maps
    scaled = d_maps * ab_ref[:, 0:1]
    g = scaled * inv                                       # d raw
    d_inv = jnp.sum(scaled * raw, axis=0, keepdims=True)
    # inv = (ss / (n C) + eps)^-1/2: the mean square's own term, 2 x d_ss
    square = -d_inv * inv * inv * inv / (n * C)
    hi, mid, lo = (t.astype(f32) for t in split3(g))
    zeros = jnp.zeros((_LANES - 5 * maps, tile), f32)
    # the six pairs of a HIGHEST product, five in one contraction of 128
    # and the sixth in one of 32
    left = jnp.concatenate([hi, hi, mid, hi, lo, zeros], axis=0).T.astype(bf16)
    last = jnp.concatenate(
        [mid, jnp.zeros((_LANES - maps, tile), f32)], axis=0).T[:, :32].astype(
            bf16)
    terms = jnp.concatenate(
        [hi, mid, lo, jnp.zeros((80 - 3 * maps, tile), f32)], axis=0).astype(
            bf16)                                          # [80, tile]
    _fill_columns(col_ref, jnp.concatenate([sig[:n], square], axis=0), tile)
    for i in range(n):
        at = slice(i * C, (i + 1) * C)
        dphi_ref[:, at] += lax.dot_general(
            terms, x_ref[i], _NN, preferred_element_type=f32)
        prod_ref[...] = (
            lax.dot_general(left, phi_ref[0:_LANES, at], _NN,
                            preferred_element_type=f32)
            + lax.dot_general(last, phi_ref[_LANES:_LANES + 32, at], _NN,
                              preferred_element_type=f32))

        def add(rows, i=i):
            w, sq = col_ref[i, rows, :], col_ref[n, rows, :]
            for lanes in _chunks(C):
                dx = (prod_ref[rows, lanes]
                      + w * _f32(dh_ref, rows, lanes)
                      + sq * _f32(x_ref, i, rows, lanes)
                      + _f32(carried_ref, i, rows, lanes))
                dx_ref[i, rows, lanes] = dx.astype(dx_ref.dtype)

        _sweep(tile, add)


# --------------------------------------------------------------------------
# The calls.

class _Call(NamedTuple):
    """One mix's kernels: everything their programs depend on."""
    n: int
    B: int
    T: int
    C: int
    tile: int
    spec: Spec
    interpret: bool


def _specs(call):
    n, tile, C = call.n, call.tile, call.C
    return dict(
        streams=pl.BlockSpec((n, None, tile, C), lambda b, t: (0, b, t, 0)),
        one=pl.BlockSpec((None, tile, C), lambda b, t: (b, t, 0)),
        planes=lambda p: pl.BlockSpec((None, p, tile), lambda b, t: (b, 0, t)),
        whole=lambda rows, cols: pl.BlockSpec((rows, cols),
                                              lambda b, t: (0, 0)),
    )


def _pallas(call, name, kernel, vma, *, in_shape, out_shape, in_specs,
            out_specs, scratch, need, aliases=None, parallel=True):
    """``(the pallas_call, its arguments' (dims, dtype))``."""
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, vma=vma)
    kind = "parallel" if parallel else "arbitrary"
    return pl.pallas_call(
        kernel,
        out_shape=[shape(*s) for s in out_shape],
        grid=(call.B, call.T // call.tile),
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM(dims, jnp.float32) for dims in scratch],
        input_output_aliases=aliases or {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(kind, kind),
            vmem_limit_bytes=_vmem_limit(need)),
        name=name, interpret=call.interpret), in_shape


def _kernels(call, vma):
    """The four ``pallas_call``s of one mix, each with its arguments'
    ``(dims, dtype)``."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    n, B, T, C, tile, spec = call[:6]
    maps, pairs = 2 * n + n * n, n * n + n
    s = _specs(call)
    static = dict(n=n, tile=tile, C=C)
    streams = ((n, B, T, C), bf16)
    one = ((B, T, C), bf16)
    plane = lambda p: ((B, p, T), f32)
    ab = ((maps, 2), f32)
    block = tile * C * 2                    # a stream's block, bytes
    cols = lambda p: (p, tile, _LANES)
    col_bytes = lambda p: p * tile * _LANES * 4
    pre = _pallas(
        call, "hc_mix_pre", functools.partial(_pre_kernel, spec=spec,
                                              **static), vma,
        in_shape=[streams, ((n * C, _LANES), bf16), ab],
        out_shape=[one, plane(n), plane(n * n), plane(maps + 1)],
        in_specs=[s["streams"], s["whole"](n * C, _LANES),
                  s["whole"](maps, 2)],
        out_specs=[s["one"], s["planes"](n), s["planes"](n * n),
                   s["planes"](maps + 1)],
        scratch=[cols(n)],
        need=2 * (n + 1) * block + 2 * n * C * _LANES * 2 + col_bytes(n + 2))
    post = _pallas(
        call, "hc_mix_post", functools.partial(_post_kernel, **static), vma,
        in_shape=[streams, one, plane(n), plane(n * n)],
        out_shape=[streams],
        in_specs=[s["streams"], s["one"], s["planes"](n),
                  s["planes"](n * n)],
        out_specs=[s["streams"]],
        scratch=[cols(pairs)],
        need=2 * (2 * n + 1) * block + col_bytes(pairs))
    post_bwd = _pallas(
        call, "hc_mix_post_bwd",
        functools.partial(_post_bwd_kernel, **static), vma,
        in_shape=[streams, streams, one, plane(n), plane(n * n)],
        out_shape=[streams, one, plane(n), plane(n * n)],
        in_specs=[s["streams"], s["streams"], s["one"], s["planes"](n),
                  s["planes"](n * n)],
        out_specs=[s["streams"], s["one"], s["planes"](n),
                   s["planes"](n * n)],
        scratch=[cols(pairs), (tile, _LANES)],
        need=2 * (3 * n + 2) * block + col_bytes(pairs + 2))
    pre_bwd = _pallas(
        call, "hc_mix_pre_bwd",
        functools.partial(_pre_bwd_kernel, spec=spec, **static), vma,
        in_shape=[one, streams, streams, plane(maps + 1), plane(n),
                  plane(n * n), ((160, n * C), bf16), ab],
        out_shape=[streams, plane(maps), ((80, n * C), f32)],
        in_specs=[s["one"], s["streams"], s["streams"], s["planes"](maps + 1),
                  s["planes"](n), s["planes"](n * n),
                  s["whole"](160, n * C), s["whole"](maps, 2)],
        out_specs=[s["streams"], s["planes"](maps), s["whole"](80, n * C)],
        scratch=[cols(n + 1), (tile, _LANES), (tile, C)],
        need=_tile_bytes(n, tile, C), aliases={2: 0}, parallel=False)
    return pre, post, post_bwd, pre_bwd


@functools.lru_cache(maxsize=32)
def _kernel_jaxpr(mesh, call, which):
    """One of :func:`_kernels` traced once per distinct call, as
    ``gated_delta._kernel_jaxprs`` keeps the delta rule's: a model calls
    these at one shape in every mix, and Pallas would trace the bodies anew
    each time."""
    kernel, in_shape = _kernels(call, vma=frozenset())[which]
    return jax.make_jaxpr(kernel)(
        *(jax.ShapeDtypeStruct(*shape) for shape in in_shape))


def _run(call, which, *args):
    vma = _vma(*args)
    if vma:   # typed per mesh axis: traced where the axes are bound
        return _kernels(call, vma)[which][0](*args)
    closed = _kernel_jaxpr(jax.sharding.get_abstract_mesh(), call, which)
    return jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *args)


def _kernel_order(call, phi, alpha, b):
    """``phi``'s columns and ``[alpha | b]`` in the kernels' order."""
    n = call.n
    return _reorder(phi, n), jnp.stack(
        [_wide(alpha, n), _reorder(b, n)], axis=1)


def _planes_out(x, n):
    """``[B, p, T]`` planes as the model's ``[n, B, T]`` or ``[n, n, B,
    T]``."""
    x = jnp.moveaxis(x, 1, 0)
    return x if x.shape[0] == n else x.reshape((n, n) + x.shape[1:])


def _planes_in(x):
    """The model's ``[n, B, T]`` or ``[n, n, B, T]`` as ``[B, p, T]``."""
    return jnp.moveaxis(x.reshape((-1,) + x.shape[-2:]), 0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pre(call, streams, phi, alpha, b):
    return _pre_fwd(call, streams, phi, alpha, b)[0]


def _pre_fwd(call, streams, phi, alpha, b):
    with jax.named_scope(_trace.SCOPE_HC_MIX):
        n = call.n
        ordered, ab = _kernel_order(call, phi, alpha, b)
        terms = jnp.concatenate(split3(ordered), axis=1)
        terms = jnp.pad(terms, ((0, 0), (0, _LANES - terms.shape[1])))
        h, post, res, raw = _run(call, 0, streams, terms, ab)
        out = (h, _planes_out(post, n), _planes_out(res, n), streams)
    return out, (streams, phi, alpha, b, raw)


def _pre_bwd(call, saved, cts):
    streams, phi, alpha, b, raw = saved
    dh, dpost, dres, dcarried = cts
    n, maps = call.n, 2 * call.n + call.n * call.n
    with jax.named_scope(_trace.SCOPE_HC_MIX):
        ordered, ab = _kernel_order(call, phi, alpha, b)
        hi, mid, lo = (t.T for t in split3(ordered))        # [maps, n C]
        pad = lambda rows: jnp.zeros((rows, phi.shape[0]), jnp.bfloat16)
        right = jnp.concatenate(
            [hi, mid, hi, lo, hi, pad(_LANES - 5 * maps), mid,
             pad(32 - maps)], axis=0)                       # [160, n C]
        dx, dmaps, dphi = _run(
            call, 3, dh, streams, dcarried, raw, _planes_in(dpost),
            _planes_in(dres), right, ab)
        # the three terms' parts, low to high; back to the model's order
        dphi = _reorder(((dphi[2 * maps:3 * maps] + dphi[maps:2 * maps])
                         + dphi[:maps]).T, n)
        by_map = jnp.sum(dmaps, axis=(0, 2))
        scaled = jnp.sum(dmaps * raw[:, :maps] * raw[:, maps:], axis=(0, 2))
        dalpha = jnp.stack([jnp.sum(scaled[:n]), jnp.sum(scaled[n:2 * n]),
                            jnp.sum(scaled[2 * n:])])
    return dx, dphi, dalpha, _reorder(by_map, n)


_pre.defvjp(_pre_fwd, _pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _post(call, streams, y, post, res):
    return _post_fwd(call, streams, y, post, res)[0]


def _post_fwd(call, streams, y, post, res):
    with jax.named_scope(_trace.SCOPE_HC_MIX):
        post, res = _planes_in(post), _planes_in(res)
        (out,) = _run(call, 1, streams, y, post, res)
    return out, (streams, y, post, res)


def _post_bwd(call, saved, g):
    streams, y, post, res = saved
    with jax.named_scope(_trace.SCOPE_HC_MIX):
        dx, dy, dpost, dres = _run(call, 2, g, streams, y, post, res)
        return (dx, dy, _planes_out(dpost, call.n),
                _planes_out(dres, call.n))


_post.defvjp(_post_fwd, _post_bwd)


def _call(streams, spec) -> Optional[_Call]:
    """The kernels' call for these streams, or None (recorded) where the
    XLA form runs."""
    n, B, T, C = streams.shape
    tile = plan(n, T, C, streams.dtype)
    if tile is None:
        return None
    return _Call(n, B, T, C, tile, spec, _pa._resolve_interpret(None))


def _note_fallback(op, streams):
    n, B, T, C = streams.shape
    _trace.note_fallback(op, _refusal(n, T, C, streams.dtype),
                         streams=n, batch=B, seq=T, width=C,
                         dtype=str(streams.dtype))


def pre(streams, phi, alpha, b, spec: Spec):
    """``streams [n, B, T, C] -> (h [B, T, C], post [n, B, T], res [n, n, B,
    T], carried)``: the sublayer's input, the two maps :func:`post` takes,
    and the streams again for :func:`post` to read (so that the cotangent of
    its read reaches this function's backward, which adds to it in place).
    ``phi [n C, 2n + n n]``, ``alpha [3]`` and ``b [2n + n n]`` float32."""
    call = _call(streams, spec)
    if call is None:
        _note_fallback("hc_mix_pre", streams)
        with jax.named_scope(_trace.SCOPE_HC_MIX):
            return _xla_pre(streams, phi, alpha, b, spec) + (streams,)
    _trace.note_plan(hc_mix_tile=call.tile)
    return _pre(call, streams, phi, alpha, b)


def post(streams, y, post, res):
    """``X'[i] = sum_j res[i, j] X[j] + post[i] y`` (``[n, B, T, C]``)."""
    call = _call(streams, None)
    if call is None:
        _note_fallback("hc_mix_post", streams)
        with jax.named_scope(_trace.SCOPE_HC_MIX):
            return _xla_post(streams, y, post, res)
    return _post(call, streams, y.astype(streams.dtype), post, res)
