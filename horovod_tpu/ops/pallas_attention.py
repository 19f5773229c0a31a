"""Flash attention as a Pallas TPU kernel.

The hot op of the transformer/long-context path, written for the TPU
memory hierarchy: Q/K/V blocks stream HBM -> VMEM, scores and the online-
softmax state live in VMEM scratch, and the [block_q, block_k] score
matmul + [block_k, d] value matmul hit the MXU. O(T) memory instead of
materializing the [T, T] probability matrix.

The reference framework has no kernels at all (it is gradient plumbing;
SURVEY.md §2.3) — this powers the model-side extensions: it is the default
``attn_fn`` of ``models/transformer.py`` (via :func:`flash_attention_bthd`)
and the per-block compute of ``parallel/ring_attention.py`` (via
:func:`flash_attention_block`, which returns the unnormalized numerator and
the online-softmax statistics so ring steps merge outside the kernel).

Backward: :func:`flash_attention` uses a custom VJP that recomputes
probabilities from the saved logsumexp blockwise under a ``lax.scan`` —
O(T * block_k) live memory, never a [T, T] residual. The ring block's VJP
recomputes its single [T, T/n] block densely (the same memory class as the
forward block it differentiates).

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..trace import SCOPE_FLASH_BWD

_NEG_INF = -1e30
_LANES = 128  # TPU lane width; m/l carriers keep a lane dim like the
              # upstream jax flash kernel's lse outputs.


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


def flashable(t_q: int, t_k: int, block_q: int = 128,
              block_k: int = 128) -> bool:
    """Whether the kernel accepts these sequence lengths (callers with
    arbitrary shapes use this to fall back to dense attention instead of
    crashing on prime/odd lengths)."""
    try:
        _pick_block(t_q, block_q)
        _pick_block(t_k, block_k)
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


def _fwd_kernel(delta_ref, q_ref, k_ref, v_ref,
                o_ref, m_out_ref, l_out_ref,
                acc_ref, m_ref, l_ref, *,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                normalize: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)   # [Bq, D]
    k = k_ref[0].astype(jnp.float32)   # [Bk, D]
    v = v_ref[0].astype(jnp.float32)   # [Bk, D]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale                        # [Bq, Bk]

    if causal:
        # Global positions: q at q_pos, k at k_pos + delta, where delta is
        # the (dynamic) offset of the K block's sequence origin relative to
        # Q's — 0 for self-attention, src*T - rank*T inside ring attention.
        delta = delta_ref[0]
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        ) + delta
        mask = q_pos >= k_pos
        s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[:, :1]                       # [Bq, 1]
    l_prev = l_ref[:, :1]
    m_curr = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_curr)
    p = jnp.exp(s - m_curr)                     # [Bq, Bk]
    if causal:
        # A fully-masked row has m_curr == _NEG_INF and would turn the
        # masked entries into exp(0) = 1; re-apply the mask to p.
        p = jnp.where(mask, p, 0.0)
    l_curr = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[:] = jnp.broadcast_to(m_curr, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_curr, l_ref.shape)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        if normalize:
            l = l_ref[:, :1]
            l = jnp.where(l == 0.0, 1.0, l)     # fully-masked rows -> 0 out
            o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        else:
            o_ref[0] = acc_ref[:].astype(o_ref.dtype)
        m_out_ref[0] = m_ref[:]
        l_out_ref[0] = l_ref[:]


def _flash_call(q, k, v, delta, *, sm_scale, causal, block_q, block_k,
                normalize, interpret, out_dtype):
    """Run the forward kernel; returns (o, m, l) with m/l of shape
    [bh, t_q] (row max / softmax denominator in the online recurrence)."""
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    block_q = _pick_block(t_q, block_q)
    block_k = _pick_block(t_k, block_k)
    grid = (bh, t_q // block_q, t_k // block_k)

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, normalize=normalize,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, ref: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, ref: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, ref: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, ref: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda b, i, j, ref: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda b, i, j, ref: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
    )
    # Inside a checked shard_map the outputs vary over every mesh axis any
    # input varies over; pallas_call wants that stated on out_shape.
    vma = _vma(q, k, v)
    o, m, l = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q, d), out_dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, t_q, _LANES), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, t_q, _LANES), jnp.float32, vma=vma),
        ],
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(delta, jnp.int32).reshape(1), q, k, v)
    return o, m[:, :, 0], l[:, :, 0]


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


def _flash_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    o, m, l = _flash_call(
        q, k, v, 0, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, normalize=True, interpret=interpret,
        out_dtype=q.dtype,
    )
    lse = m + jnp.log(jnp.where(l == 0.0, 1.0, l))   # [bh, tq]
    return o, (q, k, v, o, lse)


@jax.named_scope(SCOPE_FLASH_BWD)
def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, interpret, res, do):
    """Flash backward: probabilities are recomputed per K/V block from the
    saved logsumexp inside a ``lax.scan`` — live memory is O(T * block_k),
    no [T, T] tensor is ever materialized."""
    q, k, v, o, lse = res
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    bk = _pick_block(t_k, block_k)
    n_blocks = t_k // bk

    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    # D_i = sum_j dO_ij O_ij (the softmax-jacobian row term).
    D = jnp.sum(dof * o.astype(jnp.float32), axis=-1)   # [bh, tq]
    q_pos = jnp.arange(t_q)

    def body(dq_acc, idx):
        kb = lax.dynamic_slice_in_dim(k, idx * bk, bk, axis=1)
        vb = lax.dynamic_slice_in_dim(v, idx * bk, bk, axis=1)
        kbf = kb.astype(jnp.float32)
        vbf = vb.astype(jnp.float32)
        s = jnp.einsum("bqd,bkd->bqk", qf, kbf) * sm_scale
        if causal:
            k_pos = idx * bk + jnp.arange(bk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None], s, _NEG_INF)
        p = jnp.exp(s - lse[:, :, None])                # [bh, tq, bk]
        if causal:
            p = jnp.where(mask[None], p, 0.0)
        dp = jnp.einsum("bqd,bkd->bqk", dof, vbf)
        ds = p * (dp - D[:, :, None]) * sm_scale
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds, kbf)
        dk_b = jnp.einsum("bqk,bqd->bkd", ds, qf)
        dv_b = jnp.einsum("bqk,bqd->bkd", p, dof)
        return dq_acc, (dk_b, dv_b)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    # The scan carry must enter with the type it leaves with: inside a
    # checked shard_map dq varies over every axis the operands vary over.
    vma = _vma(q, k, v, do)
    if vma:
        dq0 = lax.pcast(dq0, tuple(sorted(vma)), to="varying")
    dq, (dks, dvs) = lax.scan(body, dq0, jnp.arange(n_blocks))
    dk = jnp.moveaxis(dks, 0, 1).reshape(k.shape)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(v.shape)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused attention over ``[..., T, D]`` (leading dims fold into one
    batch x heads grid axis). Differentiable; backward recomputes blockwise.

    ``interpret=None`` interprets on the CPU backend only, so the same
    code runs in tests on the virtual CPU mesh.
    """
    if q.ndim < 3:
        raise ValueError("expected [..., T, D] with at least one batch dim")
    interpret = _resolve_interpret(interpret)
    lead = q.shape[:-2]
    t_q, d = q.shape[-2:]
    t_k = k.shape[-2]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    qf = q.reshape((-1, t_q, d))
    kf = k.reshape((-1, t_k, d))
    vf = v.reshape((-1, t_k, d))
    out = _flash(qf, kf, vf, scale, causal, block_q, block_k, interpret)
    return out.reshape(*lead, t_q, d)


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
    raising, so the default attention accepts any shape."""
    B, T, H, D = q.shape
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)
    qf, kf, vf = fold(q), fold(k), fold(v)
    scale = sm_scale if sm_scale is not None else D ** -0.5
    if flashable(T, k.shape[1]):
        out = flash_attention(
            qf, kf, vf, causal=causal, sm_scale=scale, interpret=interpret,
        )
    else:
        out = _dense_full(qf, kf, vf, causal, scale)
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)


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
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> tuple:
    """One ring-attention block: q/k/v are ``[BH, T, D]``; ``delta`` is a
    float scalar giving the K block's global sequence offset minus Q's
    (traced — ring steps compute it from ``lax.axis_index``). Returns
    ``(o_unnormalized_f32, m, l)`` for the caller's online-softmax merge
    (``parallel/ring_attention.py``)."""
    interpret = _resolve_interpret(interpret)
    delta = jnp.asarray(delta, jnp.float32)
    return _flash_block(q, k, v, delta, sm_scale, causal, block_q, block_k,
                        interpret)
