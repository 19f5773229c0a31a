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
GROUP. The MXU's operands are cast to ``dtype`` (bfloat16 from the model) and
accumulate in float32; ``dt``, the running sums, every exponential and the
state are float32.

Two forms of the same products, chosen by the shapes alone (:func:`_plan`).

**The kernels** (``ssd_fwd.<n>`` and ``ssd_bwd.<n>`` in a device trace),
where heads of 32 to 128 sit side by side in whole blocks of 128 lanes, the
state is whole lanes and the chunk a power of two from 16 up (the published
sizes: 64 heads of 64 in 8 groups, a state of 128, chunks of 128). One grid
step is one chunk of one group, the chunks of a sequence in turn. In VMEM
and nowhere else: the group's state, ``[N, per * P]`` float32 (256 KB) in
scratch across the chunks; ``C B^T``; every head's mask, masked scores and
``dt * x``. From HBM a step reads the chunk's ``x`` (a group's lanes of the
``[b, T, H * P]`` array as the mixer holds it: no transpose in front; through
:func:`ssd_chunked_packed` the lanes of the mixer's one ``[x | B | C]``
array, so that no slice of it is copied in front either), ``B`` and ``C``
once a group, and the running sum with ``dt`` in two small float32 layouts
that XLA builds in front of the call (:func:`_layouts`); it writes ``y`` in
float32, ``D x`` in it. Under differentiation the forward also writes every
chunk's INCOMING state (float32, ``[b, G, T / Q, N, per * P]``: 268 MB a
layer at the cell's shapes, alive inside one layer's backward), and the
backward kernel walks the chunks from the last with the state's cotangent in
scratch as the state was: it rebuilds the masks, reads the kept states, and
writes the cotangents of ``x`` (``D``'s part in it), of ``B`` and ``C`` (a
group's heads summed in float32 before one rounding), of ``dt``, of the
running sum and of ``D`` (a chunk's part), which XLA behind the call turns
into those of ``dt``, ``A`` and ``D``: nothing as wide as ``x`` is read or
written by XLA on either side of the kernels. What a token's ``G`` gives
through the mask is the SAME ``[Q, Q]`` matrix summed by rows and by
columns, and what it gives through the state goes to the chunk's last ``G``
from the same numbers, so that the running sum's transpose cancels them as
the XLA form's does. A step is bound by its traffic and the grid's own
overhead, not by its arithmetic (``PERF.md`` section 6, PR 48): with every
product and exponential taken out the forward runs at 0.9 of its time.

**The XLA form** (:func:`_chunked`), the fallback for every other shape (a
``fallbacks`` record says why) and the kernels' oracle beside
:func:`ssd_recurrent`: the groups' ``B`` and ``C`` are read by their heads
through the products' batch dimensions and never repeated in memory; the
chunks' own states are one batched product, the pass over the chunks a
``lax.scan`` whose step is a multiply and an add of the ``[B, H, P, N]``
state, and what the chunks before add to ``y`` one batched product with the
states that scan leaves. Its gradients are JAX's transpose of it, under a
checkpoint of its own so that a layer's backward holds the mask ``L``
(``[B, H, T / Q, Q, Q]``) once and not in every factor of its product.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import trace as _trace
from . import pallas_attention as _pa
from .gated_delta import _mxu
from .pallas_attention import _LANES, _NN, _NT, _TN, _VMEM_BUDGET, _vma

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


# --------------------------------------------------------------------------
# The chunked form as Pallas kernels.
#
# One grid step is one chunk of one group: ``per`` heads, whose ``x`` is
# ``W = per * P`` lanes of the ``[b, T, H * P]`` array as the mixer holds it.
# The group's state is ``[N, W]`` float32 in VMEM scratch (``n`` down the
# sublanes, head and ``p`` along the lanes), so what the chunks before add to
# ``y`` and what the chunk adds to the state are full-width products over a
# BLOCK of 128 lanes (``128 / P`` heads side by side), and only the masked
# product is a head's own: a head narrower than the lanes multiplies its
# block's 128 lanes by its mask and keeps its own (:func:`_by_head`), never
# a misaligned half.
#
# The running sum ``G`` and ``dt`` come twice, laid out by XLA in front of
# the call (4 MB each at the cell's shapes): ``cols`` ``[Q, 2 * per]`` (a
# head's ``G`` and ``dt`` down the sublanes, broadcast over the lanes where
# a row of the chunk is scaled: a permute a register, 256 a step, which the
# step's traffic hides; the same broadcast as three exact bfloat16 products
# on the MXU ran 0.3 us a step slower) and ``rows`` ``[per, Q]`` (``G``
# along the lanes: the mask's column index). ``last`` and ``skip`` hold a
# chunk's last ``G`` and ``D``, a head's value over its ``P`` lanes.

def _col(cols, n, width=_LANES):
    """Column ``n`` of a ``[Q, k]`` block, replicated over ``width`` lanes."""
    return jnp.broadcast_to(cols[:, n:n + 1], (cols.shape[0], width))


def _by_head(vals, P):
    """``[rows, 128]`` whose lanes ``u * P`` to ``(u + 1) * P`` are
    ``vals[u]``'s: the block's heads side by side."""
    out = vals[0]
    if len(vals) > 1:
        lane = lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for u in range(1, len(vals)):
            out = jnp.where(lane >= u * P, vals[u], out)
    return out


def _own_lanes(x, u, P):
    """``x`` with the lanes of the block's other heads zeroed."""
    if P == _LANES:
        return x
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= u * P) & (lane < (u + 1) * P), x, 0.0)


def _mask(cols, rows, h, lower):
    """``exp(G_i - G_j)`` of head ``h`` where ``i >= j``, else 0: masked
    before the exponential."""
    Q = rows.shape[-1]
    return jnp.exp(jnp.where(
        lower, _col(cols, h, Q) - rows[h:h + 1, :], -jnp.inf))


def _block_terms(cols, last_ref, k, per, P):
    """The lane-replicated scalings of block ``k``: ``dt``, ``exp(G)``,
    ``exp(G_last - G)`` (``[Q, 128]`` each) and ``exp(G_last)``
    (``[1, 128]``)."""
    heads = _LANES // P
    block = lambda first: _by_head(
        [_col(cols, first + k * heads + u) for u in range(heads)], P)
    g, last = block(0), last_ref[:, k * _LANES:(k + 1) * _LANES]
    return block(per), jnp.exp(g), jnp.exp(last - g), jnp.exp(last)


def _lower(Q):
    i = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    j = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return i >= j


def _fwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, last_ref, skip_ref,
                y_ref, final_ref, *rest, per, P, dtype):
    """``rest``: the chunk's incoming state as an output, where a backward
    is to follow, and the running state (scratch)."""
    *states_ref, s_ref = rest
    Q = x_ref.shape[0]
    heads = _LANES // P

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    if states_ref:
        states_ref[0][...] = s_ref[...]
    B, C, cols, rows = b_ref[...], c_ref[...], cols_ref[...], rows_ref[...]
    cb = _mxu(C, B, _NT, dtype)                       # one a group
    lower = _lower(Q)
    for k in range(per // heads):
        at = slice(k * _LANES, (k + 1) * _LANES)
        dt, e_g, to_last, e_last = _block_terms(cols, last_ref, k, per, P)
        x = x_ref[:, at].astype(jnp.float32)
        xdt = x * dt
        own = _by_head([
            _mxu(cb * _mask(cols, rows, k * heads + u, lower), xdt, _NN,
                 dtype) for u in range(heads)], P)
        S = s_ref[:, at]
        y_ref[:, at] = (own + _mxu(C, S, _NN, dtype) * e_g
                        + skip_ref[:, at] * x)
        s_ref[:, at] = S * e_last + _mxu(B, xdt * to_last, _TN, dtype)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        final_ref[...] = s_ref[...]


def _head_sums(x, seg, dtype):
    """``x @ seg`` for a 0/1 ``seg``, float32 to 2^-17: the MXU sums a
    head's lanes in two bfloat16 passes (the value, and what its rounding
    left) where the VPU would rotate and add six times a register."""
    if dtype == jnp.float32:
        return _mxu(x, seg, _NN, dtype)
    hi = x.astype(jnp.bfloat16)
    lo = x - hi.astype(jnp.float32)
    return _mxu(hi, seg, _NN, jnp.bfloat16) + _mxu(lo, seg, _NN, jnp.bfloat16)


def _bwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, last_ref, skip_ref,
                states_ref, dy_ref, dfinal_ref, dx_ref, db_ref, dc_ref,
                dcols_ref, drows_ref, z_ref, dskip_ref, ds_ref, *, per, P,
                dtype):
    """One chunk of one group, the chunks walked from the last: the state's
    cotangent (``ds_ref``, scratch) goes back as the state came forward.
    ``dcols``: a head's ``dG`` by rows and ``d dt``; ``drows``: what the
    mask's columns take from ``dG``; ``z``: what the chunk's last ``G``
    takes through the state, and ``dskip``: ``dy * x`` summed over the
    chunk's tokens, a head's ``P`` lanes still apart in both."""
    f32 = jnp.float32
    Q, N = b_ref.shape
    heads = _LANES // P

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = dfinal_ref[...]

    B, C, cols, rows = b_ref[...], c_ref[...], cols_ref[...], rows_ref[...]
    cb = _mxu(C, B, _NT, dtype)
    lower = _lower(Q)
    lane = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    col = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    col_q = lax.broadcasted_iota(jnp.int32, (Q, _LANES), 1)
    d_cb = jnp.zeros((Q, Q), f32)
    d_b = jnp.zeros((Q, N), f32)
    d_c = jnp.zeros((Q, N), f32)
    d_cols = jnp.zeros((Q, _LANES), f32)
    for k in range(per // heads):
        at = slice(k * _LANES, (k + 1) * _LANES)
        dt, e_g, to_last, e_last = _block_terms(cols, last_ref, k, per, P)
        x = x_ref[:, at].astype(f32)
        xdt = x * dt
        dy = dy_ref[:, at]
        S, dS = states_ref[:, at], ds_ref[:, at]
        d_own = []
        for u in range(heads):
            h = k * heads + u
            decay = _mask(cols, rows, h, lower)
            m = cb * decay
            d_m = _mxu(_own_lanes(dy, u, P), xdt, _NT, dtype)
            d_cb = d_cb + d_m * decay
            # decay = exp(G_i - G_j): +E to row i's G, -E to column j's
            e = d_m * m
            d_cols = d_cols + jnp.where(
                col_q == h, jnp.sum(e, axis=1, keepdims=True), 0.0)
            drows_ref[h:h + 1, :] = -jnp.sum(e, axis=0, keepdims=True)
            d_own.append(_mxu(m, dy, _TN, dtype))
        through_state = _mxu(B, dS, _NN, dtype) * to_last
        d_xdt = _by_head(d_own, P) + through_state
        dx_ref[:, at] = (d_xdt * dt + skip_ref[:, at] * dy).astype(
            dx_ref.dtype)
        dskip_ref[:, at] = jnp.sum(dy * x, axis=0, keepdims=True)
        # exp(G) scales what the chunks before add; exp(G_last - G) what a
        # token adds to the state and exp(G_last) the state itself: what
        # these take from a token's G goes to the chunk's last (``z``), from
        # the same numbers, so that the two cancel in the running sums
        to_state = xdt * through_state
        to_g = dy * (_mxu(C, S, _NN, dtype) * e_g) - to_state
        z_ref[:, at] = (jnp.sum(to_state, axis=0, keepdims=True)
                        + e_last * jnp.sum(dS * S, axis=0, keepdims=True))
        head_of = k * heads + (lane >> P.bit_length() - 1)
        d_cols = (d_cols
                  + _head_sums(to_g, jnp.where(col == head_of, 1.0, 0.0),
                               dtype)
                  + _head_sums(x * d_xdt,
                               jnp.where(col == per + head_of, 1.0, 0.0),
                               dtype))
        e_dy = dy * e_g
        d_c = d_c + _mxu(e_dy, S, _NT, dtype)
        d_b = d_b + _mxu(xdt * to_last, dS, _NT, dtype)
        dS = dS * e_last + _mxu(C, e_dy, _TN, dtype)
        ds_ref[:, at] = dS
    db_ref[...] = (d_b + _mxu(d_cb, C, _TN, dtype)).astype(db_ref.dtype)
    dc_ref[...] = (d_c + _mxu(d_cb, B, _NN, dtype)).astype(dc_ref.dtype)
    dcols_ref[...] = d_cols[:, :2 * per]


def _step_vmem_bytes(Q, P, N, per, in_size):
    """What one grid step of the backward (the larger kernel) keeps in VMEM:
    the pipeline's two buffers of every block (``cols``' minor dim padded to
    the lanes, ``rows``' heads and the one-row blocks to 8 sublanes), the
    state's cotangent, and its float32 temporaries (some ten ``[Q, Q]`` a
    head in flight and twenty ``[Q, 128]`` a block)."""
    W = per * P
    rows = -(-per // 8) * 8 * max(Q, _LANES) * 4
    cols, row = Q * _LANES * 4, 8 * W * 4
    ins = (Q * W * in_size + 2 * Q * N * in_size + cols + rows + 2 * row
           + 2 * N * W * 4 + Q * W * 4)
    outs = Q * W * in_size + 2 * Q * N * in_size + cols + rows + 2 * row
    return (2 * (ins + outs) + N * W * 4
            + (10 * Q * max(Q, _LANES) + 20 * Q * _LANES + 3 * Q * N) * 4)


def _refusal(Q, P, N, per):
    """Why the shapes are not the kernels' (the word the build ledger's
    fallback record carries), or None where they are."""
    if Q & (Q - 1) or Q < 16:
        return "chunk_not_power_of_two"
    if _LANES % P or (per * P) % _LANES or 2 * per > _LANES:
        return "heads_not_whole_lanes"
    if N % _LANES:
        return "state_not_whole_lanes"
    return None


def _plan(Q, P, N, per, in_size):
    """The bytes a grid step keeps in VMEM where the kernels take the
    shapes, or None: a chunk that is no power of two (or under the 16 rows a
    packed bfloat16 register holds), heads that do not sit side by side in
    whole blocks of 128 lanes (a width that does not divide 128, a group
    whose heads fill no whole block), a state that is not whole lanes, a
    step over the VMEM budget."""
    if _refusal(Q, P, N, per):
        return None
    held = _step_vmem_bytes(Q, P, N, per, in_size)
    return held if held <= _VMEM_BUDGET else None


class _Call(NamedTuple):
    """One call of the kernels: everything their programs depend on."""
    b: int
    T: int              # padded: whole chunks
    G: int
    per: int            # heads that share a group
    P: int
    N: int
    Q: int
    dtype: Any          # the MXU's operands
    in_dtypes: tuple    # of x, B and C as passed
    packed: bool        # x, B and C are one array's lanes, side by side
    interpret: bool


def _kernels(call, vma):
    """The three ``pallas_call``s of one call, on the grid ``(sequence,
    group, chunk)`` with the chunks in turn: ``forward(x, B, C, cols,
    rows, last, skip)`` giving ``(y, last state)``, the same keeping every
    chunk's incoming state for the backward, and ``backward(x, B, C, cols,
    rows, last, skip, states, dy, dlast)`` giving ``(dx, dB, dC, dcols,
    drows, z, dskip)``, which walks the chunks from the last."""
    f32 = jnp.float32
    b, T, G, per, P, N, Q, dtype = call[:8]
    W, c = per * P, T // Q
    x_dtype, b_dtype, c_dtype = call.in_dtypes
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, vma=vma)

    def specs(chunk):
        """Block specs by what a block holds; ``chunk`` maps the grid's
        third index to the chunk it works on."""
        tokens = lambda width, first=0: pl.BlockSpec(
            (None, Q, width), lambda i, g, n: (i, chunk(n), first + g))
        # where the three are one array ``[x | B | C]``, B's and C's blocks
        # of N lanes start behind x's and B's
        b_at, c_at = ((G * W // N, G * (W + N) // N) if call.packed
                      else (0, 0))
        return dict(
            x=tokens(W), bc=tokens(N), b_in=tokens(N, b_at),
            c_in=tokens(N, c_at),
            cols=pl.BlockSpec((None, None, Q, 2 * per),
                              lambda i, g, n: (i, g, chunk(n), 0)),
            rows=pl.BlockSpec((None, None, None, per, Q),
                              lambda i, g, n: (i, g, chunk(n), 0, 0)),
            state=pl.BlockSpec((None, None, N, W),
                               lambda i, g, n: (i, g, 0, 0)),
            states=pl.BlockSpec((None, None, None, N, W),
                                lambda i, g, n: (i, g, chunk(n), 0, 0)),
            row=pl.BlockSpec((None, None, None, 1, W),
                             lambda i, g, n: (i, g, chunk(n), 0, 0)),
        )

    common = dict(
        grid=(b, G, c), interpret=call.interpret,
        scratch_shapes=[pltpu.VMEM((N, W), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))
    static = dict(per=per, P=P, dtype=dtype)
    s = specs(lambda n: n)
    skip = pl.BlockSpec((None, 1, W), lambda i, g, n: (g, 0, 0))
    ins = [s["x"], s["b_in"], s["c_in"], s["cols"], s["rows"], s["row"],
           skip]
    y, last = shape((b, T, G * W), f32), shape((b, G, N, W), f32)
    states = shape((b, G, c, N, W), f32)
    forward, forward_keeping = (pl.pallas_call(
        functools.partial(_fwd_kernel, **static),
        out_shape=[y, last] + kept, in_specs=ins,
        out_specs=[s["x"], s["state"]] + [s["states"]] * len(kept),
        name="ssd_fwd", **common) for kept in ([], [states]))
    s = specs(lambda n: c - 1 - n)
    backward = pl.pallas_call(
        functools.partial(_bwd_kernel, **static),
        out_shape=[shape((b, T, G * W), x_dtype),
                   shape((b, T, G * N), b_dtype),
                   shape((b, T, G * N), c_dtype),
                   shape((b, G, T, 2 * per), f32),
                   shape((b, G, c, per, Q), f32),
                   shape((b, G, c, 1, W), f32), shape((b, G, c, 1, W), f32)],
        in_specs=[s["x"], s["b_in"], s["c_in"], s["cols"], s["rows"],
                  s["row"], skip, s["states"], s["x"], s["state"]],
        out_specs=[s["x"], s["bc"], s["bc"], s["cols"], s["rows"], s["row"],
                   s["row"]],
        name="ssd_bwd", **common)
    return forward, forward_keeping, backward


@functools.lru_cache(maxsize=32)
def _kernel_jaxprs(mesh, call):
    """:func:`_kernels` traced once per distinct call, as
    ``gated_delta._kernel_jaxprs`` keeps its own: a model calls these once a
    layer at one shape. ``mesh`` is the abstract mesh of the caller's
    context: avals carry it, so the jaxprs are kept per context."""
    f32 = jnp.float32
    b, T, G, per, P, N, Q = call[:7]
    W = per * P
    aval = jax.ShapeDtypeStruct
    widths = (G * (W + 2 * N),) * 3 if call.packed else (G * W, G * N, G * N)
    ins = tuple(aval((b, T, width), dt) for width, dt in zip(
        widths, call.in_dtypes)) + (
        aval((b, G, T, 2 * per), f32), aval((b, G, T // Q, per, Q), f32),
        aval((b, G, T // Q, 1, W), f32), aval((G, 1, W), f32))
    forward, keeping, backward = _kernels(call, vma=frozenset())
    kept = jax.make_jaxpr(keeping)(*ins)
    y, last, states = kept.out_avals
    return (jax.make_jaxpr(forward)(*ins), kept,
            jax.make_jaxpr(backward)(*ins, states, y, last))


def _run_kernel(call, which, *args):
    vma = _vma(*args)
    if vma:   # typed per mesh axis: traced where the axes are bound
        return _kernels(call, vma)[which](*args)
    closed = _kernel_jaxprs(jax.sharding.get_abstract_mesh(), call)[which]
    return jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *args)


def _layouts(call, run, dt, D):
    """``(cols, rows, last, skip)`` as the kernels read them, from the
    running sum and ``dt`` as ``[b, c, Q, G, per]`` and ``D`` as ``[H]``:
    ``cols`` ``[b, G, T, 2 * per]``, ``rows`` ``[b, G, c, per, Q]``; ``last``
    (a chunk's last ``G``) ``[b, G, c, 1, per * P]`` and ``skip`` (``D``)
    ``[G, 1, per * P]``, a head's value over its ``P`` lanes."""
    b, T, G, per, P = call[:5]
    cols = jnp.concatenate([run, dt], axis=-1).transpose(0, 3, 1, 2, 4)
    last = jnp.repeat(run[:, :, -1].transpose(0, 2, 1, 3), P, axis=-1)
    skip = jnp.repeat(D.astype(jnp.float32).reshape(G, 1, per), P, axis=-1)
    return (cols.reshape(b, G, T, 2 * per), run.transpose(0, 3, 1, 4, 2),
            last[:, :, :, None], skip)


def _operands(call, xs):
    """``(x, B, C)`` as the kernels are handed them: the three arrays, or
    the one that holds all three three times over."""
    return xs * 3 if call.packed else xs


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan_kernel(call, xs, run, dt, D):
    """The chunked form by the kernels: ``(y [b, T, H * P] float32, last
    state [b, G, N, per * P] float32)``. ``xs``: ``(x [b, T, H * P], B, C
    [b, T, G * N])``, or ``([x | B | C],)`` where ``call.packed``; ``run``
    (the running sum of ``dt * A`` inside each chunk) and ``dt``: ``[b, c,
    Q, G, per]`` float32; ``D``: ``[H]``."""
    return tuple(_run_kernel(call, 0, *_operands(call, xs),
                             *_layouts(call, run, dt, D)))


def _scan_kernel_fwd(call, xs, run, dt, D):
    laid = _layouts(call, run, dt, D)
    y, last, states = _run_kernel(call, 1, *_operands(call, xs), *laid)
    return (y, last), (xs, laid, states)


def _scan_kernel_bwd(call, res, cts):
    xs, laid, states = res
    b, T, G, per, P, N, Q = call[:7]
    *d_xs, dcols, drows, z, dskip = _run_kernel(
        call, 2, *_operands(call, xs), *laid, states, *cts)
    if call.packed:
        d_xs = [jnp.concatenate(d_xs, axis=-1)]
    # [b, G, T, 2 per] -> [b, c, Q, G, per], twice
    dcols = dcols.reshape(b, G, T // Q, Q, 2, per).transpose(4, 0, 2, 3, 1, 5)
    by_head = lambda m: jnp.sum(m.reshape(m.shape[:-1] + (per, P)), axis=-1)
    d_run = (dcols[0] + drows.transpose(0, 2, 4, 1, 3)).at[:, :, Q - 1].add(
        by_head(z[:, :, :, 0]).transpose(0, 2, 1, 3))
    d_skip = by_head(jnp.sum(dskip, axis=(0, 2, 3))).reshape(-1)
    return tuple(d_xs), d_run, dcols[1], d_skip


_scan_kernel.defvjp(_scan_kernel_fwd, _scan_kernel_bwd)


def _by_kernels(xs, dt, A, D, dims, Q, dtype, packed):
    """``(y [b, T, H, P], last state [b, H, P, N])`` by the kernels;
    ``xs`` as :func:`_scan_kernel` takes them, whole chunks long."""
    f32 = jnp.float32
    b, T, H, P, G, N = dims
    per = H // G
    # the interpreter on the CPU backend, the compiler on a TPU: the flash
    # kernels' rule (and what a test steers to compile both)
    call = _Call(b, T, G, per, P, N, Q, dtype,
                 tuple(m.dtype for m in xs * (3 if packed else 1)), packed,
                 _pa._resolve_interpret(None))
    dt_c = dt.astype(f32).reshape(b, T // Q, Q, G, per)
    run = jnp.cumsum(dt_c * A.astype(f32).reshape(G, per), axis=2)
    y, S = _scan_kernel(call, xs, run, dt_c, D)
    # [b, G, N, per * P] -> [b, H, P, N]
    S = S.reshape(b, G, N, per, P).transpose(0, 1, 3, 4, 2).reshape(b, H, P, N)
    return y.reshape(b, T, H, P), S


def _note(dims, T, Q, held):
    """The plan notes of one call, and the fallback's record where the
    kernels do not take it; ``T`` is the length before padding."""
    b, Tp, H, P, G, N = dims
    _trace.note_plan(
        ssm_heads=H, ssm_head_dim=P, ssm_state=N, ssm_groups=G, ssm_chunk=Q,
        ssm_chunks=Tp // Q, ssm_kernel=held is not None,
        ssm_grid_steps=b * G * Tp // Q if held else 0,
        ssm_vmem_mb=round(held / 2 ** 20, 1) if held else 0.0,
    )
    if held is None:
        # the XLA form's backward is JAX's transpose of it: the one record
        # stands for ssd_bwd too
        _trace.note_fallback(
            "ssd_fwd", _refusal(Q, P, N, H // G) or "no_chunk_fits_vmem",
            batch=b, seq=T, heads=H, head_dim=P, state=N, groups=G, chunk=Q)


def _padded(arrays, pad):
    if not pad:
        return arrays
    return tuple(jnp.pad(m, ((0, 0), (0, pad)) + ((0, 0),) * (m.ndim - 2))
                 for m in arrays)


def ssd_chunked(x, dt, A, B, C, D, *, chunk: int = DEFAULT_CHUNK,
                dtype=jnp.bfloat16):
    """The rule in chunks of ``chunk`` tokens; shapes as
    :func:`ssd_recurrent`. A sequence that is no whole number of chunks is
    padded with tokens of ``dt = 0``, which leave the state as it is and are
    cut from ``y``. Returns ``(y [b, T, H, P] float32, final state
    [b, H, P, N] float32)``."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    per = _check(x, B, C)
    Q = min(chunk, T)
    pad = -T % Q
    dims, dtype = (b, T + pad, H, P, G, N), jnp.dtype(dtype)
    held = _plan(Q, P, N, per, max(m.dtype.itemsize for m in (x, B, C)))
    _note(dims, T, Q, held)
    x_p, dt_p, B_p, C_p = _padded((x, dt, B, C), pad)
    if held is None:
        f32 = jnp.float32
        y, S = _chunked(x_p, dt_p, A, B_p, C_p, Q, dtype)
        return y[:, :T] + D.astype(f32)[:, None] * x.astype(f32), S
    flat = lambda m: m.reshape(b, T + pad, -1)
    y, S = _by_kernels((flat(x_p), flat(B_p), flat(C_p)), dt_p, A, D, dims, Q,
                       dtype, packed=False)
    return y[:, :T], S


def ssd_chunked_packed(xbc, dt, A, D, *, groups: int, state: int,
                       chunk: int = DEFAULT_CHUNK, dtype=jnp.bfloat16):
    """:func:`ssd_chunked` of ``x``, ``B`` and ``C`` that lie side by side
    in ONE array, ``xbc`` ``[b, T, H * P + 2 * G * N]`` as ``[x | B | C]``
    (what a mixer's convolution leaves): the kernels read the three where
    they lie, and no slice of the array is copied in front of them (0.7 ms
    a call at two 8192-token sequences); their cotangents come back as one
    array. ``dt``: ``[b, T, H]``. Where the kernels do not take the shapes
    (or ``B`` does not start on a block of ``N`` lanes) the slices go
    through :func:`ssd_chunked`. Same returns."""
    b, T, H = dt.shape
    G, N = groups, state
    inner = xbc.shape[-1] - 2 * G * N
    if H % G or inner % H:
        raise ValueError(f"{H} heads in {G} groups over a state of {N} do "
                         f"not lie in {xbc.shape[-1]} lanes")
    P, per = inner // H, H // G
    Q = min(chunk, T)
    pad = -T % Q
    held = _plan(Q, P, N, per, xbc.dtype.itemsize)
    if held is None or inner % N:
        x, B, C = jnp.split(xbc, [inner, inner + G * N], axis=-1)
        return ssd_chunked(x.reshape(b, T, H, P), dt, A,
                           B.reshape(b, T, G, N), C.reshape(b, T, G, N), D,
                           chunk=chunk, dtype=dtype)
    dims = (b, T + pad, H, P, G, N)
    _note(dims, T, Q, held)
    xbc_p, dt_p = _padded((xbc, dt), pad)
    y, S = _by_kernels((xbc_p,), dt_p, A, D, dims, Q, jnp.dtype(dtype),
                       packed=True)
    return y[:, :T], S
