"""Flash attention as a Pallas TPU kernel.

The hot op of the transformer/long-context path, written for the TPU
memory hierarchy: Q/K/V blocks stream HBM -> VMEM, scores and the online-
softmax state live in VMEM scratch, and the [block_q, block_k] score
matmul + [block_k, d] value matmul hit the MXU. O(T) memory instead of
materializing the [T, T] probability matrix.

What one grid step of the forward does follows from what the kernel sees
in its inputs (:func:`_plan`), not from a knob:

- the MXU's operands are q, k and v in the dtype the caller passed (bf16
  from the model, f32 from a caller that wants f32 products); scores,
  mask, running max and sum, ``exp`` and the accumulator are f32, and
  only the PV product's left operand is cast to ``v.dtype``;
- ``block_q`` and ``block_k`` default to 512 (halved while a step would
  overrun the VMEM budget) and several rows of the folded batch x heads
  axis share a step, so a call at ``[128, 1024, 64]`` is 128 grid steps
  of 4 rows, not 8192 of one;
- with ``causal`` a (q block, k block) pair wholly above the diagonal
  (for the traced ``delta``) is neither computed nor fetched, a pair
  wholly below it builds no mask, and only a pair that straddles it
  does;
- the running max and sum leave the kernel as lane-dense ``[1, block_q]``
  rows of a ``[bh, t_q / block_q, 1, block_q]`` array.

The reference framework has no kernels at all (it is gradient plumbing;
SURVEY.md §2.3) — this powers the model-side extensions: it is the default
``attn_fn`` of ``models/transformer.py`` (via :func:`flash_attention_bthd`)
and the per-block compute of ``parallel/ring_attention.py`` (via
:func:`flash_attention_block`, which returns the unnormalized numerator and
the online-softmax statistics so ring steps merge outside the kernel).

Backward: :func:`flash_attention`'s custom VJP is Pallas kernels under the
forward's rules (operands as passed, f32 scores, ``exp`` and accumulators,
masked pairs neither fetched nor computed), fed the saved logsumexp and
``D = rowsum(do * o)`` as lane-dense rows. The dK/dV kernel walks the Q
blocks of one K/V block with ``dk`` and ``dv`` in VMEM and works on
transposed ``[block_k, block_q]`` scores, so the statistics broadcast
along sublanes. Where some rows' WHOLE ``dq`` fits in VMEM beside its
tiles (T 1024 at d 64: 0.5 MB a row in float32; T 8192 at d 256 and T 16384
at d 128: 8 MB, under a scoped limit the call raises) that kernel
accumulates ``dq`` in the same walk, 5 products a pair; where it does not
(T 32768 at d 256: 32 MB) a dQ kernel walks the K/V blocks of one Q block
with ``dq`` in VMEM, and the two recompute the scores and ``dp`` (7
products). Either way ``dq`` is written once. :func:`_plan_bwd` picks
tiles, rows and form from the shapes, under a selection too.
The ring block's VJP recomputes its single [T, T/n] block densely (the
same memory class as the forward block it differentiates).

Queries and keys share one width and the values (with the output and its
cotangent) may have another: a latent-attention head has keys of 192 (128
from the low-rank part, 64 rotary) and values of 128. Plans and VMEM counts
take both widths (``d_v``, the keys' width where it is not given), and no
operand is padded in HBM to make them equal; at equal widths plans, programs
and results are what one width gave. The ring block stays at one width.

Interpret mode runs the same kernels on the CPU backend (the tests'
virtual mesh): it is chosen when the caller asks for it or when the
default backend is ``cpu``, and refused on a TPU backend — there the
kernel is compiled or the call fails (:func:`_resolve_interpret`).
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
from ..trace import SCOPE_FLASH_BWD

# What the forward kernel leaves for its backward, by name: a caller's
# recomputation that keeps these names (``models/recompute.remat_layer``)
# runs the forward kernel once a step, where an unnamed residual is computed
# again. Outside a ``jax.checkpoint`` a name is the identity.
FLASH_RESIDUALS = ("flash_o", "flash_lse")

_NEG_INF = -1e30
_LANES = 128  # TPU lane width: the running max and sum are kept lane-
              # replicated in [block_q, 128] scratch, as the upstream jax
              # flash kernel keeps them.
_PREF_BLOCK = 512           # block_q / block_k where the caller names none
_PREF_ROWS = 8              # rows of bh one grid step takes, at most
# What a grid step may count where its call asks the compiler for nothing:
# under Mosaic's 16 MiB DEFAULT scoped limit, which is the compiler's and not
# the chip's (a v5e core has 128 MiB of VMEM).
_VMEM_BUDGET = 12 * 2 ** 20
# What the one-pass backward's step may count with some rows' whole ``dq``
# beside its tiles, and the scoped limit its call then asks for: a quarter
# over the count for the compiler's own temporaries, half the core's VMEM
# (``ops/sparse_index.py`` asks for as much, and for 56 MiB).
_WHOLE_DQ_BUDGET = 48 * 2 ** 20
_WHOLE_DQ_VMEM = 64 * 2 ** 20


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` means "interpret exactly when the program runs on the CPU
    backend". On a TPU backend the kernel is never interpreted."""
    backend = jax.default_backend()
    if interpret is None:
        return backend == "cpu"
    if interpret and backend == "tpu":
        raise ValueError(
            "interpret=True on a TPU backend would replace the flash "
            "kernel with the Pallas interpreter; compile it instead"
        )
    return bool(interpret)


def _vma(*arrays) -> frozenset:
    """The mesh axes any of ``arrays`` varies over inside a checked
    shard_map (empty outside one, or in an unchecked one)."""
    return frozenset().union(*(jax.typeof(x).vma for x in arrays))


def _pick_block(t: int, pref: int) -> int:
    """Largest block <= pref that divides t (XLA/Mosaic needs an exact
    grid). Degrading a little below ``pref`` is fine; degrading to a tiny
    block (prime/odd T) would silently explode the grid into T*T scalar
    steps, so that case stays a hard error like the original kernel."""
    cap = min(pref, t)
    b = cap
    while t % b:
        b -= 1
    if b < 8 and b < cap:
        raise ValueError(
            f"sequence length {t} has no block divisor >= 8 under "
            f"{pref}; pad the sequence or pass explicit block sizes"
        )
    return b


def flashable(t_q: int, t_k: int, block_q: Optional[int] = None,
              block_k: Optional[int] = None) -> bool:
    """Whether the kernel accepts these sequence lengths (callers with
    arbitrary shapes use this to fall back to dense attention instead of
    crashing on prime/odd lengths)."""
    try:
        _pick_block(t_q, block_q or _PREF_BLOCK)
        _pick_block(t_k, block_k or _PREF_BLOCK)
        return True
    except ValueError:
        return False


def _dense_full(q, k, v, causal, sm_scale):
    """Dense [BH, T, D] attention — the graceful fallback for shapes the
    kernel's block constraint rejects."""
    s = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * sm_scale
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        s = jnp.where(mask[None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bqk,bkd->bqd", p, v.astype(jnp.float32)
    ).astype(q.dtype)


def _lanes(x, n: int):
    """A lane-replicated ``[rows, 128]`` statistic widened (or cut) to
    ``n`` lanes without a lane broadcast where the shape allows it."""
    if n == _LANES:
        return x
    if n < _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _fwd_kernel(delta_ref, q_ref, k_ref, v_ref,
                o_ref, m_out_ref, l_out_ref,
                acc_ref, m_ref, l_ref, *,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                rows: int, normalize: bool, sel=None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    d = v_ref.shape[-1]     # the accumulator's and the output's width

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def update(bias):
        """One online-softmax step of every row of ``bh`` in this grid
        step against the resident K/V block; ``bias`` is the additive
        causal mask of a pair that straddles the diagonal, or None."""
        def one(g, carry):
            q, k, v = q_ref[g], k_ref[g], v_ref[g]     # operands as passed
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale                                # [Bq, Bk] f32
            if bias is not None:
                s = s + bias
            m_prev, l_prev = m_ref[g], l_ref[g]         # [Bq, 128]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - _lanes(m_next, block_k))
            l_ref[g] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            m_ref[g] = m_next
            acc_ref[g] = acc_ref[g] * _lanes(alpha, d) + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return carry

        lax.fori_loop(0, rows, one, None)

    if sel is not None:
        # A selection names its pairs one by one (the causal rule is part of
        # it): a block pair with none is neither fetched nor computed.
        _on_selected_pairs(update, sel, qi, ki, q_axis=0)
    elif causal:
        # Global positions: q at q_pos, k at k_pos + delta, where delta is
        # the (dynamic) offset of the K block's sequence origin relative to
        # Q's — 0 for self-attention, src*T - rank*T inside ring attention.
        # ``first_k`` is the global position of this K block's first key
        # relative to this Q block's first query.
        first_k = ki * block_k + delta_ref[0] - qi * block_q
        visible = first_k <= block_q - 1          # some (q, k) pair is kept
        whole = first_k + block_k - 1 <= 0        # every pair is kept

        @pl.when(whole)
        def _below_diagonal():
            update(None)

        @pl.when(jnp.logical_and(visible, jnp.logical_not(whole)))
        def _on_diagonal():
            rel = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            ) - jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            # Masked scores sit BELOW the running max's floor, so a row
            # with nothing visible yet keeps m = -1e30 and gets p =
            # exp(-1e30) = 0 without a second select.
            update(jnp.where(rel >= first_k, 0.0, 2 * _NEG_INF))
    else:
        update(None)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        def one(g, carry):
            l = l_ref[g]
            if normalize:
                inv = 1.0 / jnp.where(l == 0.0, 1.0, l)  # masked rows -> 0
                o_ref[g] = (acc_ref[g] * _lanes(inv, d)).astype(o_ref.dtype)
            else:
                o_ref[g] = acc_ref[g].astype(o_ref.dtype)
            # [Bq, 128] lane-replicated -> one lane-dense [1, Bq] row.
            m_out_ref[g, 0] = m_ref[g].T[:1]
            l_out_ref[g, 0] = l.T[:1]
            return carry

        lax.fori_loop(0, rows, one, None)


def _fwd_kernel_sel(fetch_ref, q_ref, k_ref, v_ref, sel_ref, *refs, heads,
                    **static):
    """The forward kernel under a selection: the table of block pairs comes
    first (scalar prefetch), the selection's tile after v."""
    _fwd_kernel(None, q_ref, k_ref, v_ref, *refs, causal=False,
                sel=(fetch_ref, sel_ref, heads, static["rows"]), **static)


def _lane_padded(d):
    """A minor dim as VMEM holds it: whole 128-lane tiles."""
    return -(-d // _LANES) * _LANES


def _step_vmem_bytes(rows, block_q, block_k, d, in_size, out_size, d_v=None):
    """What one grid step keeps in VMEM: the pipeline's two buffers of
    every q/k/v/o block (a minor dim is padded to whole 128-lane tiles; q
    and k are ``d`` wide, v and o ``d_v``), the f32 accumulator and
    statistics, and the [Bq, Bk] f32 temporaries of one row of ``bh``
    (scores, probabilities, the mask's bias, the PV operand)."""
    dl = _lane_padded(d)
    vl = dl if d_v is None else _lane_padded(d_v)
    blocks = 2 * rows * (
        (block_q + block_k) * dl * in_size + block_k * vl * in_size
        + block_q * vl * out_size
    )
    scratch = rows * block_q * (vl + 2 * _LANES) * 4
    return blocks + scratch + 4 * block_q * block_k * 4


def _fit_plan(bh, t_q, t_k, block_q, block_k, step_bytes):
    """``(block_q, block_k, rows)`` of one call whose grid step keeps
    ``step_bytes(rows, block_q, block_k)`` in VMEM. A block size the
    caller passed is a preference as before; one left to the kernel
    starts from ``_PREF_BLOCK`` and is halved while a grid step of one row
    overruns ``_VMEM_BUDGET``. ``rows`` is how many rows of the folded
    batch x heads axis one grid step takes: the largest divisor of ``bh``
    up to ``_PREF_ROWS`` that still fits."""
    def blocks(pref):
        return (_pick_block(t_q, block_q or pref),
                _pick_block(t_k, block_k or pref))

    def fits(rows):
        return step_bytes(rows, bq, bk) <= _VMEM_BUDGET

    pref = _PREF_BLOCK
    bq, bk = blocks(pref)
    while not fits(1) and pref > _LANES:
        pref //= 2
        try:
            bq, bk = blocks(pref)
        except ValueError:  # no divisor that small: keep what divides
            break
    rows = min(_PREF_ROWS, bh)
    while bh % rows or (rows > 1 and not fits(rows)):
        rows -= 1
    return bq, bk, rows


def _plan(bh, t_q, t_k, d, in_size, out_size, block_q, block_k, d_v=None):
    """``(block_q, block_k, rows)`` of one forward call
    (:func:`_fit_plan` with the forward's VMEM count)."""
    return _fit_plan(
        bh, t_q, t_k, block_q, block_k,
        lambda rows, bq, bk: _step_vmem_bytes(
            rows, bq, bk, d, in_size, out_size, d_v),
    )


def _pairs_visited(t_q, t_k, block_q, block_k, causal):
    """Share of the (q block, k block) pairs a call computes, at
    ``delta`` 0 (ring steps shift the diagonal at run time)."""
    n_q, n_k = t_q // block_q, t_k // block_k
    if not causal:
        return 1.0
    seen = sum(
        1 for i in range(n_q) for j in range(n_k)
        if j * block_k <= i * block_q + block_q - 1
    )
    return seen / (n_q * n_k)


def _forward(bh, t_q, t_k, d, d_v, out_dtype, sm_scale, causal, block_q,
             block_k, rows, normalize, interpret, vma):
    """The forward ``pallas_call`` at one plan, as a function of
    ``(delta[1] int32, q, k, v)`` returning ``(o, m, l)`` with the
    statistics as ``[bh, t_q / block_q, 1, block_q]``; q and k are ``d``
    wide, v and o ``d_v``."""
    n_q, n_k = t_q // block_q, t_k // block_k
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, rows=rows, normalize=normalize,
    )

    def kv_index(b, i, j, delta_ref):
        if not causal:
            return (b, j, 0)
        # A pair wholly above the diagonal is not computed; point it at
        # the last K/V block this q block needs, so the pipeline sees an
        # unchanged index and fetches nothing.
        last_q = i * block_q + (block_q - 1) - delta_ref[0]
        last = lax.min(lax.div(lax.max(last_q, 0), block_k), n_k - 1)
        return (b, lax.min(j, last), 0)

    q_index = lambda b, i, j, delta_ref: (b, i, 0)
    stat_index = lambda b, i, j, delta_ref: (b, i, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh // rows, n_q, n_k),
        in_specs=[
            pl.BlockSpec((rows, block_q, d), q_index),
            pl.BlockSpec((rows, block_k, d), kv_index),
            pl.BlockSpec((rows, block_k, d_v), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((rows, block_q, d_v), q_index),
            pl.BlockSpec((rows, 1, 1, block_q), stat_index),
            pl.BlockSpec((rows, 1, 1, block_q), stat_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, block_q, d_v), jnp.float32),
            pltpu.VMEM((rows, block_q, _LANES), jnp.float32),
            pltpu.VMEM((rows, block_q, _LANES), jnp.float32),
        ],
    )
    # Inside a checked shard_map the outputs vary over every mesh axis any
    # input varies over; pallas_call wants that stated on out_shape.
    stat = jax.ShapeDtypeStruct((bh, n_q, 1, block_q), jnp.float32, vma=vma)
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q, d_v), out_dtype, vma=vma),
            stat, stat,
        ],
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=64)
def _forward_jaxpr(mesh, in_dtypes, *plan):
    """:func:`_forward` traced once per distinct call. Pallas traces a
    kernel body anew on every ``pallas_call``, and a model calls the
    kernel once a layer at one shape: 24 layers of tracing are seconds of
    a program's set-up on a chip's host. Evaluating the kept jaxpr binds
    the same ``pallas_call`` under the caller's own name stack. ``mesh``
    is the abstract mesh of the caller's context: avals carry it, so a
    jaxpr is kept per context."""
    bh, t_q, t_k, d, d_v = plan[:5]
    shapes = ((1,), (bh, t_q, d), (bh, t_k, d), (bh, t_k, d_v))
    return jax.make_jaxpr(_forward(*plan, vma=frozenset()))(*(
        jax.ShapeDtypeStruct(shape, dtype)
        for shape, dtype in zip(shapes, (jnp.int32,) + in_dtypes)
    ))


def _flash_call(q, k, v, delta, *, sm_scale, causal, block_q, block_k,
                normalize, interpret, out_dtype):
    """Run the forward kernel; returns (o, m, l) with m/l of shape
    [bh, t_q] (row max / softmax denominator in the online recurrence)."""
    bh, t_q, d = q.shape
    t_k, d_v = v.shape[1:]
    out_dtype = jnp.dtype(out_dtype)
    block_q, block_k, rows = _plan(
        bh, t_q, t_k, d, q.dtype.itemsize, out_dtype.itemsize,
        block_q, block_k, d_v,
    )
    # Trace-time, one note per compile (the fusion plan's discipline):
    # what one grid step of this program's forward kernel is.
    _trace.note_plan(
        flash_block_q=block_q, flash_block_k=block_k,
        flash_rows_per_step=rows,
        flash_grid_steps=(bh // rows) * (t_q // block_q) * (t_k // block_k),
        flash_pairs_visited=round(
            _pairs_visited(t_q, t_k, block_q, block_k, causal), 4),
    )
    plan = (bh, t_q, t_k, d, d_v, out_dtype, sm_scale, causal, block_q,
            block_k, rows, normalize, interpret)
    args = (jnp.asarray(delta, jnp.int32).reshape(1), q, k, v)
    vma = _vma(q, k, v)
    if vma:   # typed per mesh axis: traced where the axes are bound
        o, m, l = _forward(*plan, vma=vma)(*args)
    else:
        closed = _forward_jaxpr(
            jax.sharding.get_abstract_mesh(), (q.dtype, k.dtype, v.dtype),
            *plan,
        )
        o, m, l = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *args)
    return o, m.reshape(bh, t_q), l.reshape(bh, t_q)


# --------------------------------------------------------------------------
# Full (self-)attention with blockwise-recompute backward.
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    o, _, _ = _flash_call(
        q, k, v, 0, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, normalize=True, interpret=interpret,
        out_dtype=q.dtype,
    )
    return o


def _named_residuals(o, lse):
    return tuple(map(checkpoint_name, (o, lse), FLASH_RESIDUALS))


def _flash_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    o, m, l = _flash_call(
        q, k, v, 0, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, normalize=True, interpret=interpret,
        out_dtype=q.dtype,
    )
    lse = m + jnp.log(jnp.where(l == 0.0, 1.0, l))   # [bh, tq]
    o, lse = _named_residuals(o, lse)
    return o, (q, k, v, o, lse)


_NT = (((1,), (1,)), ((), ()))   # a @ b.T: both contract their minor dim
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _on_visible_pairs(update, causal, qi, ki, block_q, block_k, q_axis):
    """Run ``update(bias)`` for this (q block, k block) pair as the forward
    does at ``delta`` 0: not at all wholly above the diagonal, without a
    mask wholly below it, with an additive one where the pair straddles
    it. ``q_axis`` is the axis of the score tile that runs over the
    queries."""
    if not causal:
        update(None)
        return
    first_k = ki * block_k - qi * block_q
    visible = first_k <= block_q - 1
    whole = first_k + block_k - 1 <= 0

    @pl.when(whole)
    def _below_diagonal():
        update(None)

    @pl.when(jnp.logical_and(visible, jnp.logical_not(whole)))
    def _on_diagonal():
        # 0 where the key is visible, far under any logsumexp where not:
        # exp(s - lse) is 0 there without a select.
        shape = (block_q, block_k) if q_axis == 0 else (block_k, block_q)
        rel = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis) \
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
        update(jnp.where(rel >= first_k, 0.0, 2 * _NEG_INF))


def _on_selected_pairs(update, sel, qi, ki, q_axis):
    """Run ``update(bias)`` for this (q block, k block) pair if the selection
    holds a pair of it. ``sel`` is ``(table, tile, heads, rows)``: the table
    names, for every step of the grid's inner axis, the block that axis
    fetches (:func:`_fetch_table`: the step's own where the pair holds a
    selected pair, the last such before it otherwise, so that nothing is
    fetched for a pair that is not computed); the int8 tile is 1 where the
    query attends to the key, laid out as the score tile is (``q_axis`` the
    axis that runs over the queries)."""
    table, tile, heads, rows = sel
    batch = lax.div(pl.program_id(0) * rows, heads)
    outer, inner = (qi, ki) if q_axis == 0 else (ki, qi)
    at = (batch * pl.num_programs(1) + outer) * pl.num_programs(2) + inner

    @pl.when(table[at] == inner)
    def _selected():
        # 0 where selected, far under any running max or logsumexp where not
        update((tile[0].astype(jnp.float32) - 1.0) * (-2 * _NEG_INF))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, *outs,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                rows: int, one_pass: bool, sel=None):
    """dK and dV of one K/V block: the Q axis is the innermost grid axis
    and ``dk``/``dv`` stay in f32 scratch across it. Scores are held
    transposed, ``[block_k, block_q]``: the lane-dense ``lse`` and ``D``
    rows broadcast along sublanes, and the four products are plain
    ``a @ b`` or ``a @ b.T``.

    With ``one_pass`` the same walk gives dQ too: the rows' WHOLE ``dq``
    lies in f32 scratch across both inner axes and takes ``ds.T @ k`` at
    each pair, so scores and ``dp`` are computed once, 5 products a pair;
    it is written after the last pair."""
    if one_pass:
        dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc = outs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = outs
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    last_q = qi == pl.num_programs(2) - 1

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if one_pass:
            @pl.when(ki == 0)
            def _init_dq():
                dq_acc[...] = jnp.zeros_like(dq_acc)

    def update(bias):
        def one(g, carry):
            q, k, v, do = q_ref[g], k_ref[g], v_ref[g], do_ref[g]
            st = jax.lax.dot_general(
                k, q, _NT, preferred_element_type=jnp.float32,
            ) * sm_scale                                # [Bk, Bq] f32
            if bias is not None:
                st = st + bias
            pt = jnp.exp(st - lse_ref[g, 0])            # lse: [1, Bq]
            dv_acc[g] += jax.lax.dot_general(
                pt.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32,
            )
            dpt = jax.lax.dot_general(
                v, do, _NT, preferred_element_type=jnp.float32,
            )
            # ds without its factor sm_scale: dk and dq take that once,
            # as they leave.
            ds = (pt * (dpt - dd_ref[g, 0])).astype(q.dtype)
            dk_acc[g] += jax.lax.dot_general(
                ds, q, _NN, preferred_element_type=jnp.float32,
            )
            if one_pass:
                at = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
                dq_acc[g, at] += jax.lax.dot_general(
                    ds, k, _TN, preferred_element_type=jnp.float32,
                )
            return carry

        lax.fori_loop(0, rows, one, None)

    if sel is not None:
        _on_selected_pairs(update, sel, qi, ki, q_axis=1)
    else:
        _on_visible_pairs(update, causal, qi, ki, block_q, block_k, q_axis=1)

    @pl.when(last_q)
    def _finalize():
        dk_ref[...] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        if one_pass:
            @pl.when(ki == pl.num_programs(1) - 1)
            def _finalize_dq():
                dq_ref[...] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
               dq_ref, dq_acc, lse_col, dd_col, *,
               sm_scale: float, causal: bool, block_q: int, block_k: int,
               rows: int, sel=None):
    """dQ of one Q block: the K/V axis is the innermost grid axis, ``dq``
    stays in f32 scratch across it and is written once. The lane-dense
    ``lse`` and ``D`` rows are turned into lane-replicated columns once a
    Q block (the forward's last-step transpose, the other way)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

        def one(g, carry):
            lse_col[g] = jnp.broadcast_to(
                lse_ref[g, 0], (_LANES, block_q)).T     # [Bq, 128]
            dd_col[g] = jnp.broadcast_to(
                dd_ref[g, 0], (_LANES, block_q)).T
            return carry

        lax.fori_loop(0, rows, one, None)

    def update(bias):
        def one(g, carry):
            q, k, v, do = q_ref[g], k_ref[g], v_ref[g], do_ref[g]
            s = jax.lax.dot_general(
                q, k, _NT, preferred_element_type=jnp.float32,
            ) * sm_scale                                # [Bq, Bk] f32
            if bias is not None:
                s = s + bias
            p = jnp.exp(s - _lanes(lse_col[g], block_k))
            dp = jax.lax.dot_general(
                do, v, _NT, preferred_element_type=jnp.float32,
            )
            ds = p * (dp - _lanes(dd_col[g], block_k))  # sm_scale: below
            dq_acc[g] += jax.lax.dot_general(
                ds.astype(k.dtype), k, _NN,
                preferred_element_type=jnp.float32,
            )
            return carry

        lax.fori_loop(0, rows, one, None)

    if sel is not None:
        _on_selected_pairs(update, sel, qi, ki, q_axis=0)
    else:
        _on_visible_pairs(update, causal, qi, ki, block_q, block_k, q_axis=0)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[...] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel_sel(fetch_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                    sel_ref, *outs, heads, **static):
    """The dK/dV kernel under a selection (its tile transposed, as the scores
    are held), in either form."""
    _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, *outs,
                causal=False,
                sel=(fetch_ref, sel_ref, heads, static["rows"]), **static)


def _dq_kernel_sel(fetch_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                   sel_ref, *outs, heads, **static):
    """The dQ kernel under a selection."""
    _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, *outs,
               causal=False, sel=(fetch_ref, sel_ref, heads, static["rows"]),
               **static)


def _bwd_step_vmem_bytes(rows, block_q, block_k, d, in_size, whole_t_q=0,
                         d_v=None):
    """What one grid step of the backward keeps in VMEM: the pipeline's two
    buffers of the q, do, k, v blocks, of the ``lse`` and ``D`` rows (a
    ``[1, Bq]`` f32 row fills 8 sublanes) and of the outputs, the f32
    accumulators, and the [Bq, Bk] temporaries of one row of ``bh``
    (scores, probabilities, ``dp``, ``ds``, the mask's bias in f32 and the
    MXU operands cast from them). With ``whole_t_q`` the one-pass kernel,
    which holds dK/dV's buffers, a ``dq`` of that many queries (its f32 sum
    and the two buffers of the output block: 8 MB a row at T 8192 x 128
    lanes in bf16) and ``ds`` transposed; without, the larger of the dK/dV
    and the dQ kernel (the latter with its two statistic columns). q, k,
    ``dq`` and ``dk`` are ``d`` wide; v, ``do`` and ``dv`` are ``d_v``. A
    selection's tile is counted by the plan (:func:`_sel_tile_bytes`)."""
    dl = _lane_padded(d)
    vl = dl if d_v is None else _lane_padded(d_v)
    # an output's two buffers and its f32 sum, a row of it
    held, held_v = (w * (2 * in_size + 4) for w in (dl, vl))
    inputs = 2 * rows * (
        (block_q + block_k) * (dl + vl) * in_size + 2 * 8 * block_q * 4
    )
    dkv = rows * block_k * (held + held_v)
    if whole_t_q:
        return (inputs + dkv + rows * whole_t_q * held
                + 7 * block_q * block_k * 4)
    dq = rows * block_q * (held + 2 * _LANES * 4)
    return inputs + max(dkv, dq) + 6 * block_q * block_k * 4


def _sel_tile_bytes(block_q, block_k):
    """What a selection's tile adds to a grid step: its int8 buffers, two,
    and the float32 bias made of it."""
    return (2 + 4) * block_q * block_k


def _plan_bwd(bh, t_q, t_k, d, in_size, block_q, block_k, d_v=None,
              sel_heads=None):
    """``(block_q, block_k, rows, one_pass, step_bytes)`` of one backward
    call: :func:`_plan`'s rule with the backward's own VMEM count gives the
    two kernels' tiles and rows under ``_VMEM_BUDGET``; where some of those
    rows' whole ``dq`` fits beside the tiles, the one-pass kernel runs
    instead (5 products a pair for 7): under ``_VMEM_BUDGET`` as far as that
    goes (short sequences: the call asks for no limit), else under
    ``_WHOLE_DQ_BUDGET`` (T 8192 and 16384: :func:`_vmem_limit` raises the
    call's scoped limit). ``step_bytes`` is the count of the step that runs.
    Under a selection (``sel_heads``) the rows of a step are heads of ONE
    batch row, as in :func:`_plan_sel`, and the selection's tile is counted."""
    over = sel_heads or bh

    def count(rows, bq, bk, whole_t_q=0):
        tile = _sel_tile_bytes(bq, bk) if sel_heads else 0
        return tile + _bwd_step_vmem_bytes(
            rows, bq, bk, d, in_size, whole_t_q, d_v)

    bq, bk, rows = _fit_plan(over, t_q, t_k, block_q, block_k, count)
    for budget in (_VMEM_BUDGET, _WHOLE_DQ_BUDGET):
        for r in range(rows, 0, -1):
            held = count(r, bq, bk, t_q)
            if over % r == 0 and held <= budget:
                return bq, bk, r, True, held
    return bq, bk, rows, False, count(rows, bq, bk)


def _vmem_limit(step_bytes):
    """The scoped VMEM limit a backward call asks for: none where its step
    counts no more than ``_VMEM_BUDGET`` (the compiler's default holds it,
    and the call lowers as it did before there was a second budget)."""
    return _WHOLE_DQ_VMEM if step_bytes > _VMEM_BUDGET else None


def _backward(bh, t_q, t_k, d, d_v, dtypes, sm_scale, causal, block_q,
              block_k, rows, one_pass, step_bytes, interpret, vma):
    """The backward ``pallas_call``s at one plan (the one-pass kernel, or
    the dK/dV and the dQ kernel), as a function of ``(q, k, v, do, lse,
    D)`` returning ``(dq, dk, dv)``; ``lse`` and ``D`` are
    ``[bh, t_q / block_q, 1, block_q]`` f32; q and k are ``d`` wide, v and
    ``do`` ``d_v``. ``step_bytes`` is the plan's count of a grid step: what
    the first call's scoped VMEM limit follows."""
    n_q, n_k = t_q // block_q, t_k // block_k
    static = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, rows=rows)

    def first_q(j):
        # dK/dV grid (b, j, i): a Q block wholly above K block j's
        # diagonal is not computed; point it at the first one that is
        # needed, so the pipeline fetches nothing for it.
        if not causal:
            return 0
        return lax.min(lax.div(j * block_k, block_q), n_q - 1)

    def last_k(i):
        # dQ grid (b, i, j): the forward's clamp of the K/V side.
        if not causal:
            return n_k - 1
        return lax.min(lax.div(i * block_q + block_q - 1, block_k), n_k - 1)

    def specs(q_of, k_of):
        """The six inputs' specs from where a grid step's Q and K/V blocks
        lie."""
        q_spec, do_spec = (pl.BlockSpec(
            (rows, block_q, w), lambda b, x, y: (b, q_of(x, y), 0))
            for w in (d, d_v))
        k_spec, v_spec = (pl.BlockSpec(
            (rows, block_k, w), lambda b, x, y: (b, k_of(x, y), 0))
            for w in (d, d_v))
        stat = pl.BlockSpec(
            (rows, 1, 1, block_q), lambda b, x, y: (b, q_of(x, y), 0, 0))
        return [q_spec, k_spec, v_spec, do_spec, stat, stat]

    dq_dtype, dk_dtype, dv_dtype = dtypes
    dq_shape = jax.ShapeDtypeStruct((bh, t_q, d), dq_dtype, vma=vma)
    dk_spec, dv_spec = (pl.BlockSpec(
        (rows, block_k, w), lambda b, j, i: (b, j, 0)) for w in (d, d_v))
    dk_acc, dv_acc = (pltpu.VMEM((rows, block_k, w), jnp.float32)
                      for w in (d, d_v))
    # With one_pass a third output and accumulator: the rows' whole dq,
    # resident across both inner axes (so neither is parallel).
    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, one_pass=one_pass, **static),
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_k, d), dk_dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, t_k, d_v), dv_dtype, vma=vma),
        ] + [dq_shape] * one_pass,
        grid=(bh // rows, n_k, n_q),
        in_specs=specs(lambda j, i: lax.max(i, first_q(j)),
                       lambda j, i: j),
        out_specs=[dk_spec, dv_spec] + [
            pl.BlockSpec((rows, t_q, d), lambda b, j, i: (b, 0, 0))
        ] * one_pass,
        scratch_shapes=[dk_acc, dv_acc] + [
            pltpu.VMEM((rows, t_q, d), jnp.float32)] * one_pass,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary" if one_pass else "parallel",
                "arbitrary"),
            vmem_limit_bytes=_vmem_limit(step_bytes),
        ),
        interpret=interpret,
    )
    dq = None if one_pass else pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        out_shape=dq_shape,
        grid=(bh // rows, n_q, n_k),
        in_specs=specs(lambda i, j: i,
                       lambda i, j: lax.min(j, last_k(i))),
        out_specs=pl.BlockSpec((rows, block_q, d),
                               lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, block_q, d), jnp.float32),
            pltpu.VMEM((rows, block_q, _LANES), jnp.float32),
            pltpu.VMEM((rows, block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )

    def run(*args):
        dk, dv, *whole_dq = dkv(*args)
        return (whole_dq[0] if one_pass else dq(*args)), dk, dv

    return run


@functools.lru_cache(maxsize=64)
def _backward_jaxpr(mesh, *plan):
    """:func:`_backward` traced once per distinct call, as
    :func:`_forward_jaxpr` keeps the forward: the kernel bodies of a layer
    would otherwise be traced 24 times a program. ``do`` has the dtype of
    the output it is the cotangent of, which is q's."""
    bh, t_q, t_k, d, d_v, (q_dtype, k_dtype, v_dtype), _, _, block_q = plan[:9]
    stat = ((bh, t_q // block_q, 1, block_q), jnp.float32)
    return jax.make_jaxpr(_backward(*plan, vma=frozenset()))(*(
        jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
            ((bh, t_q, d), q_dtype), ((bh, t_k, d), k_dtype),
            ((bh, t_k, d_v), v_dtype), ((bh, t_q, d_v), q_dtype), stat, stat,
        )
    ))


def _note_bwd_plan(bh, t_q, t_k, block_q, block_k, rows, one_pass,
                   step_bytes, **more):
    """The backward's plan notes: tiles, rows, which form runs, the grid
    steps of all its kernels and the VMEM its step counts."""
    _trace.note_plan(
        flash_bwd_block_q=block_q, flash_bwd_block_k=block_k,
        flash_bwd_rows_per_step=rows,
        flash_bwd_one_pass=one_pass,
        flash_bwd_grid_steps=(1 if one_pass else 2) * (bh // rows)
        * (t_q // block_q) * (t_k // block_k),
        flash_bwd_vmem_mb=round(step_bytes / 2 ** 20, 1),
        **more,
    )


@jax.named_scope(SCOPE_FLASH_BWD)
def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, interpret, res, do):
    """Flash backward: the kernels of :func:`_backward` on the residuals
    the forward left. Probabilities are recomputed per block pair from the
    saved logsumexp; no [T, T] tensor and no f32 ``dq`` carry ever reach
    HBM."""
    q, k, v, o, lse = res
    bh, t_q, d = q.shape
    t_k, d_v = v.shape[1:]
    block_q, block_k, rows, one_pass, step_bytes = _plan_bwd(
        bh, t_q, t_k, d, q.dtype.itemsize, block_q, block_k, d_v)
    # Beside the forward's note: what one grid step of this program's
    # backward is, which form runs, and the steps of all its kernels.
    _note_bwd_plan(
        bh, t_q, t_k, block_q, block_k, rows, one_pass, step_bytes,
        flash_bwd_pairs_visited=round(
            _pairs_visited(t_q, t_k, block_q, block_k, causal), 4),
    )
    # D_i = sum_j dO_ij O_ij (the softmax-jacobian row term), and the
    # statistics as the lane-dense rows the forward kernel writes.
    dd = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    stat_shape = (bh, t_q // block_q, 1, block_q)
    args = (q, k, v, do, lse.reshape(stat_shape), dd.reshape(stat_shape))
    plan = (bh, t_q, t_k, d, d_v, (q.dtype, k.dtype, v.dtype), sm_scale,
            causal, block_q, block_k, rows, one_pass, step_bytes, interpret)
    vma = _vma(*args)
    if vma:   # typed per mesh axis: traced where the axes are bound
        return _backward(*plan, vma=vma)(*args)
    closed = _backward_jaxpr(jax.sharding.get_abstract_mesh(), *plan)
    return tuple(jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *args))


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# --------------------------------------------------------------------------
# Attention under a selection: each query names its keys.
# --------------------------------------------------------------------------

def _fetch_table(selection, block_r, block_c):
    """int32 ``[B * n_r * n_c]``: for every block pair of ``selection``
    (int8 ``[B, R, C]``, tiles of ``block_r x block_c``) the column block a
    kernel's inner axis fetches at that step: the step's own where the tile
    holds a selected pair (the pair is computed exactly then), else the last
    such block before it, and before the row's first the first. A step that
    computes nothing so names the block the pipeline already holds."""
    B, R, C = selection.shape
    n_r, n_c = R // block_r, C // block_c
    held = selection.reshape(B, n_r, block_r, n_c, block_c).max(
        axis=(2, 4)) != 0
    own = jnp.where(held, jnp.arange(n_c, dtype=jnp.int32), -1)
    last = lax.cummax(own, axis=2)
    first = jnp.argmax(held, axis=2).astype(jnp.int32)[..., None]
    return jnp.where(last >= 0, last, first).reshape(-1)


def _call_sel(kernel, out_shape, grid, in_specs, out_specs, scratch,
              interpret, semantics=("parallel", "parallel", "arbitrary"),
              vmem_limit=None):
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )


def _sel_specs(heads, rows, n_outer, n_inner, q_inner, block_q, block_k, d,
               d_v, stats):
    """The specs of q, k, v (and ``do``, ``lse``, ``D`` with ``stats``) and
    of the selection's tile for a grid ``(bh / rows, outer, inner)`` whose
    inner axis walks the Q blocks (``q_inner``: the dK/dV kernel, the tile
    transposed) or the K/V blocks, fetching what the table names."""
    batch = lambda g: lax.div(g * rows, heads)

    def fetched(g, x, y, table):
        return table[(batch(g) * n_outer + x) * n_inner + y]

    if q_inner:
        q_of, k_of = fetched, lambda g, x, y, table: x
        tile = (1, block_k, block_q)
    else:
        q_of, k_of = lambda g, x, y, table: x, fetched
        tile = (1, block_q, block_k)
    spec = lambda rows_, w, of: pl.BlockSpec(
        (rows, rows_, w), lambda g, x, y, t: (g, of(g, x, y, t), 0))
    stat = pl.BlockSpec((rows, 1, 1, block_q),
                        lambda g, x, y, t: (g, q_of(g, x, y, t), 0, 0))
    sel = pl.BlockSpec(tile, lambda g, x, y, t: (
        batch(g), x, fetched(g, x, y, t)))
    qkv = [spec(block_q, d, q_of), spec(block_k, d, k_of),
           spec(block_k, d_v, k_of)]
    if not stats:
        return qkv + [sel]
    return qkv + [spec(block_q, d_v, q_of), stat, stat, sel]


def _plan_sel(heads, t_q, t_k, block_q, block_k, step_bytes):
    """:func:`_fit_plan` for a call under a selection: the rows of a grid
    step are heads of ONE batch row (they share the selection's tile), and
    the tile is counted."""
    return _fit_plan(
        heads, t_q, t_k, block_q, block_k,
        lambda rows, bq, bk: step_bytes(rows, bq, bk)
        + _sel_tile_bytes(bq, bk))


def _flash_sel_call(q, k, v, selection, sm_scale, heads, block_q, block_k,
                    interpret):
    """The forward kernel under ``selection``; ``(o, lse)``."""
    bh, t_q, d = q.shape
    t_k, d_v = v.shape[1:]
    size = q.dtype.itemsize
    block_q, block_k, rows = _plan_sel(
        heads, t_q, t_k, block_q, block_k,
        lambda r, bq, bk: _step_vmem_bytes(r, bq, bk, d, size, size, d_v))
    n_q, n_k = t_q // block_q, t_k // block_k
    _trace.note_plan(
        flash_block_q=block_q, flash_block_k=block_k,
        flash_rows_per_step=rows, flash_selection=True,
        flash_grid_steps=(bh // rows) * n_q * n_k,
    )
    vma = _vma(q, k, v, selection)
    static = dict(sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                  rows=rows, normalize=True)
    stat = jax.ShapeDtypeStruct((bh, n_q, 1, block_q), jnp.float32, vma=vma)
    o, m, l = _call_sel(
        functools.partial(_fwd_kernel_sel, heads=heads, **static),
        [jax.ShapeDtypeStruct((bh, t_q, d_v), q.dtype, vma=vma), stat, stat],
        (bh // rows, n_q, n_k),
        _sel_specs(heads, rows, n_q, n_k, False, block_q, block_k, d, d_v,
                   stats=False),
        [pl.BlockSpec((rows, block_q, d_v), lambda g, i, j, t: (g, i, 0)),
         pl.BlockSpec((rows, 1, 1, block_q), lambda g, i, j, t: (g, i, 0, 0)),
         pl.BlockSpec((rows, 1, 1, block_q), lambda g, i, j, t: (g, i, 0, 0))],
        [pltpu.VMEM((rows, block_q, d_v), jnp.float32),
         pltpu.VMEM((rows, block_q, _LANES), jnp.float32),
         pltpu.VMEM((rows, block_q, _LANES), jnp.float32)],
        interpret,
    )(_fetch_table(selection, block_q, block_k), q, k, v, selection)
    m, l = m.reshape(bh, t_q), l.reshape(bh, t_q)
    return o, m + jnp.log(jnp.where(l == 0.0, 1.0, l))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_sel(q, k, v, selection, sm_scale, heads, block_q, block_k,
               interpret):
    return _flash_sel_call(q, k, v, selection, sm_scale, heads, block_q,
                           block_k, interpret)


def _flash_sel_vjp_fwd(q, k, v, selection, sm_scale, heads, block_q, block_k,
                       interpret):
    o, lse = _named_residuals(*_flash_sel_call(
        q, k, v, selection, sm_scale, heads, block_q, block_k, interpret))
    return (o, lse), (q, k, v, o, lse, selection)


@jax.named_scope(SCOPE_FLASH_BWD)
def _flash_sel_vjp_bwd(sm_scale, heads, block_q, block_k, interpret, res,
                       cts):
    """The backward under a selection, in the form :func:`_plan_bwd` picks
    with the selection's tile counted: where some heads' whole ``dq`` fits
    beside the tiles, the one-pass kernel alone, on the selection transposed
    once in HBM, as it holds its scores; where not, that kernel for dK/dV and
    the dQ kernel on the selection as it came. The logsumexp is a statistic:
    its cotangent is not used."""
    q, k, v, o, lse, selection = res
    do, _ = cts
    bh, t_q, d = q.shape
    t_k, d_v = v.shape[1:]
    block_q, block_k, rows, one_pass, step_bytes = _plan_bwd(
        bh, t_q, t_k, d, q.dtype.itemsize, block_q, block_k, d_v,
        sel_heads=heads)
    n_q, n_k = t_q // block_q, t_k // block_k
    _note_bwd_plan(bh, t_q, t_k, block_q, block_k, rows, one_pass,
                   step_bytes)
    dd = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    stat_shape = (bh, n_q, 1, block_q)
    args = (q, k, v, do, lse.reshape(stat_shape), dd.reshape(stat_shape))
    vma = _vma(*args, selection)
    static = dict(sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                  rows=rows)
    dq_shape = jax.ShapeDtypeStruct((bh, t_q, d), q.dtype, vma=vma)
    transposed = jnp.swapaxes(selection, 1, 2)
    # With one_pass a third output and accumulator, as in :func:`_backward`.
    dk, dv, *whole_dq = _call_sel(
        functools.partial(_dkv_kernel_sel, heads=heads, one_pass=one_pass,
                          **static),
        [jax.ShapeDtypeStruct((bh, t_k, d), k.dtype, vma=vma),
         jax.ShapeDtypeStruct((bh, t_k, d_v), v.dtype, vma=vma)]
        + [dq_shape] * one_pass,
        (bh // rows, n_k, n_q),
        _sel_specs(heads, rows, n_k, n_q, True, block_q, block_k, d, d_v,
                   stats=True),
        [pl.BlockSpec((rows, block_k, w), lambda g, j, i, t: (g, j, 0))
         for w in (d, d_v)] + [
            pl.BlockSpec((rows, t_q, d), lambda g, j, i, t: (g, 0, 0))
        ] * one_pass,
        [pltpu.VMEM((rows, block_k, w), jnp.float32) for w in (d, d_v)] + [
            pltpu.VMEM((rows, t_q, d), jnp.float32)] * one_pass,
        interpret,
        # the whole dq is resident across both inner axes: neither is parallel
        semantics=("parallel", "arbitrary" if one_pass else "parallel",
                   "arbitrary"),
        vmem_limit=_vmem_limit(step_bytes),
    )(_fetch_table(transposed, block_k, block_q), *args, transposed)
    if one_pass:
        return whole_dq[0], dk, dv, None
    dq = _call_sel(
        functools.partial(_dq_kernel_sel, heads=heads, **static),
        dq_shape,
        (bh // rows, n_q, n_k),
        _sel_specs(heads, rows, n_q, n_k, False, block_q, block_k, d, d_v,
                   stats=True),
        pl.BlockSpec((rows, block_q, d), lambda g, i, j, t: (g, i, 0)),
        [pltpu.VMEM((rows, block_q, d), jnp.float32),
         pltpu.VMEM((rows, block_q, _LANES), jnp.float32),
         pltpu.VMEM((rows, block_q, _LANES), jnp.float32)],
        interpret,
    )(_fetch_table(selection, block_q, block_k), *args, selection)
    return dq, dk, dv, None


_flash_sel.defvjp(_flash_sel_vjp_fwd, _flash_sel_vjp_bwd)


def _check_widths(q, k):
    if q.shape[-1] != k.shape[-1]:
        raise ValueError(
            f"queries are {q.shape[-1]} wide and keys {k.shape[-1]}: they "
            "share one width (only the values may have another)")


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    selection: Optional[jax.Array] = None,
):
    """Fused attention over ``[..., T, D]`` (leading dims fold into one
    batch x heads grid axis). Differentiable; the backward is kernels too,
    which recompute the probabilities per block pair. ``v`` may have
    another width than ``q`` and ``k`` (which share one); the output has
    ``v``'s.

    ``selection`` (int8 ``[B, T_q, T_k]``, for q, k, v of ``[B, H, T, D]``)
    is 1 where a query attends to a key and holds the causal rule itself
    (``causal`` is not read): each head's softmax runs over its query's
    selected keys alone, forward and backward, and a block pair with no
    selected pair is neither fetched nor computed. The result is then ``(out,
    lse)``, ``lse`` (float32 ``[B, H, T_q]``) the logsumexp of each query's
    selected scores, a statistic without a gradient. No gradient reaches the
    selection. Without one the call is what it was: the same plans and
    programs.

    ``interpret=None`` interprets on the CPU backend only, so the same
    code runs in tests on the virtual CPU mesh.
    """
    if q.ndim < 3:
        raise ValueError("expected [..., T, D] with at least one batch dim")
    interpret = _resolve_interpret(interpret)
    _check_widths(q, k)
    lead = q.shape[:-2]
    t_q, d = q.shape[-2:]
    t_k, d_v = v.shape[-2:]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    qf = q.reshape((-1, t_q, d))
    kf = k.reshape((-1, t_k, d))
    vf = v.reshape((-1, t_k, d_v))
    if selection is not None:
        if q.ndim != 4 or selection.shape != (lead[0], t_q, t_k):
            raise ValueError(
                f"a selection is [B, T_q, T_k] for q of [B, H, T_q, D]; got "
                f"{selection.shape} for q of {q.shape}")
        out, lse = _flash_sel(qf, kf, vf, selection.astype(jnp.int8), scale,
                              lead[1], block_q, block_k, interpret)
        return (out.reshape(*lead, t_q, d_v),
                lax.stop_gradient(lse).reshape(*lead, t_q))
    out = _flash(qf, kf, vf, scale, causal, block_q, block_k, interpret)
    return out.reshape(*lead, t_q, d_v)


def flash_attention_bthd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Layout adapter for the transformer's ``[B, T, H, D]`` attention
    signature (``models/transformer.py``): fold heads into the kernel's
    batch axis, run the fused kernel, unfold. Sequence lengths the kernel's
    block constraint rejects (prime/odd T) take a dense fallback instead of
    raising, so the default attention accepts any shape. ``v`` may have
    another head width than ``q`` and ``k``."""
    _check_widths(q, k)
    B, T, H, D = q.shape
    Dv = v.shape[-1]
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, *x.shape[1::2])
    qf, kf, vf = fold(q), fold(k), fold(v)
    scale = sm_scale if sm_scale is not None else D ** -0.5
    if flashable(T, k.shape[1]):
        out = flash_attention(
            qf, kf, vf, causal=causal, sm_scale=scale, interpret=interpret,
        )
    else:
        # the dense form's backward is JAX's transpose of it: the one record
        # stands for both directions
        _trace.note_fallback("attention", "no_block_divisor", t_q=T,
                             t_k=k.shape[1], heads=H, head_dim=D,
                             **({"v_head_dim": Dv} if Dv != D else {}))
        out = _dense_full(qf, kf, vf, causal, scale)
    return out.reshape(B, H, T, Dv).transpose(0, 2, 1, 3)


# --------------------------------------------------------------------------
# Ring-attention block: unnormalized numerator + online-softmax stats.
# --------------------------------------------------------------------------

def _dense_block(q, k, v, delta, sm_scale, causal):
    """Dense computation of exactly the kernel's (o_unnorm, m, l) triple —
    the recompute target for the block VJP."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqd,bkd->bqk", qf, kf) * sm_scale
    t_q, t_k = q.shape[1], k.shape[1]
    if causal:
        mask = (
            jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :] + delta
        )
        s = jnp.where(mask[None], s, _NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=-1), _NEG_INF)
    p = jnp.exp(s - m[..., None])
    if causal:
        p = jnp.where(mask[None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bqk,bkd->bqd", p, vf)
    return o, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_block(q, k, v, delta, sm_scale, causal, block_q, block_k,
                 interpret):
    return _flash_call(
        q, k, v, delta, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, normalize=False, interpret=interpret,
        out_dtype=jnp.float32,
    )


def _flash_block_vjp_fwd(q, k, v, delta, sm_scale, causal, block_q, block_k,
                         interpret):
    out = _flash_block(q, k, v, delta, sm_scale, causal, block_q, block_k,
                       interpret)
    return out, (q, k, v, delta)


@jax.named_scope(SCOPE_FLASH_BWD)
def _flash_block_vjp_bwd(sm_scale, causal, block_q, block_k, interpret, res,
                         cts):
    q, k, v, delta = res
    _trace.note_fallback("flash_bwd", "ring_block_recomputes_densely",
                         bh=q.shape[0], t_q=q.shape[1], t_k=k.shape[1],
                         head_dim=q.shape[2])

    def f(q, k, v):
        return _dense_block(q, k, v, delta, sm_scale, causal)

    _, vjp = jax.vjp(f, q, k, v)
    dq, dk, dv = vjp(cts)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            jnp.zeros_like(delta))


_flash_block.defvjp(_flash_block_vjp_fwd, _flash_block_vjp_bwd)


def flash_attention_block(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    delta,
    *,
    sm_scale: float,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> tuple:
    """One ring-attention block: q/k/v are ``[BH, T, D]``; ``delta`` is a
    float scalar giving the K block's global sequence offset minus Q's
    (traced — ring steps compute it from ``lax.axis_index``). Returns
    ``(o_unnormalized_f32, m, l)`` for the caller's online-softmax merge
    (``parallel/ring_attention.py``). One width: no caller of the ring has
    values of another width than its keys."""
    if not q.shape[-1] == k.shape[-1] == v.shape[-1]:
        raise ValueError(
            "the ring block takes q, k and v of one width; got "
            f"{q.shape[-1]}, {k.shape[-1]}, {v.shape[-1]} (flash_attention "
            "takes values of another width)")
    interpret = _resolve_interpret(interpret)
    delta = jnp.asarray(delta, jnp.float32)
    return _flash_block(q, k, v, delta, sm_scale, causal, block_q, block_k,
                        interpret)
