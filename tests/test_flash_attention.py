"""Pallas flash-attention kernel: interpret-mode numerics vs the dense
reference, forward and backward, plus the ring-block merge identity.

(The kernel is also exercised end-to-end as the transformer default
``attn_fn`` in test_models.py and as the ring-attention block compute in
test_ring_attention.py.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops.pallas_attention import (
    flash_attention,
    flash_attention_block,
    flash_attention_bthd,
)
from horovod_tpu.parallel.ring_attention import reference_attention


def _qkv_bhtd(bh=4, t=32, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(bh, t, d).astype(np.float32) * 0.5)
    return mk(), mk(), mk()


def _dense(q, k, v, causal):
    # [BH, T, D] dense reference via the tested reference_attention
    # ([B, T, H, D] layout with H folded out).
    out = reference_attention(
        q[:, :, None, :], k[:, :, None, :], v[:, :, None, :], causal=causal
    )
    return out[:, :, 0, :]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [(128, 128), (8, 16), (None, None)])
def test_forward_matches_dense(causal, blocks):
    q, k, v = _qkv_bhtd()
    bq, bk = blocks
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    expected = _dense(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-5
    )


def _force_form(monkeypatch, one_pass):
    """Make ``_plan_bwd`` give this form at the tiles, rows and count it
    plans (interpreted, a count limits nothing)."""
    plan_bwd = pa._plan_bwd

    def forced(*a, **kw):
        plan = plan_bwd(*a, **kw)
        return plan[:3] + (one_pass,) + plan[4:]

    monkeypatch.setattr(pa, "_plan_bwd", forced)


# (bh, t_q, t_k, d, block_q, block_k, _PREF_BLOCK): what the two backward
# kernels must get right beside the plain case.
_GRAD_SHAPES = {
    "small": (2, 16, 16, 8, 8, 8, None),
    # the cells' head widths, tiles of 16 standing for 512: several rows
    # of bh a grid step, 4 x 4 block pairs
    "d64-rows": (8, 64, 64, 64, None, None, 16),
    "d256": (2, 32, 32, 256, None, None, 16),
    "bq<bk": (4, 64, 64, 16, 16, 32, None),
    "bq>bk": (4, 64, 64, 16, 32, 16, None),
    "blocks-8-16": (4, 32, 32, 16, 8, 16, None),
    # causal: the later Q blocks see every key unmasked
    "tq>tk": (4, 64, 32, 16, 16, 16, None),
    # causal: the second K/V block is seen by the last Q block only, the
    # later ones by none (their dk and dv are zeros the kernel must write)
    "tq<tk": (4, 32, 64, 16, 16, 16, None),
}


@pytest.mark.parametrize("shape", list(_GRAD_SHAPES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("one_pass", [True, False],
                         ids=["one-pass", "two-kernels"])
def test_grad_matches_dense(monkeypatch, one_pass, causal, dtype, shape):
    """dq, dk and dv of the backward kernels against JAX's own gradient of
    the dense reference, under a cotangent that is not uniform. Shapes
    this small always plan the one-pass kernel (a whole dq fits VMEM), so
    the form is forced either way."""
    bh, t_q, t_k, d, bq, bk, pref = _GRAD_SHAPES[shape]
    if pref is not None:
        monkeypatch.setattr(pa, "_PREF_BLOCK", pref)
    assert pa._plan_bwd(bh, t_q, t_k, d, 4, bq, bk)[3]
    _force_form(monkeypatch, one_pass)
    rng = np.random.RandomState(11)
    mk = lambda t: jnp.asarray(
        rng.randn(bh, t, d).astype(np.float32) * 0.5).astype(dtype)
    q, k, v = mk(t_q), mk(t_k), mk(t_k)
    w = mk(t_q).astype(jnp.float32)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
        return jnp.sum(out.astype(jnp.float32) * w)

    def loss_dense(q, k, v):
        out = pa._dense_full(q, k, v, causal, d ** -0.5)
        return jnp.sum(out.astype(jnp.float32) * w)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    tol = dict(rtol=1e-4, atol=1e-5) if dtype == jnp.float32 else dict(
        rtol=2e-2, atol=2e-2)
    for a, b in zip(gf, gd):
        assert a.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **tol)


# (bh, t, d_qk, d_v, block, _PREF_BLOCK): values of another width than the
# keys, as a latent-attention head has them, and equal widths beside them.
_TWO_WIDTHS = {
    # the Xing4.0 head itself, tiles of 64 standing for 512
    "192/128": (2, 256, 192, 128, None, 64),
    "24/16": (4, 64, 24, 16, 16, None),
    "16/24": (4, 64, 16, 24, 16, None),     # values wider than the keys
    "128/128": (2, 128, 128, 128, None, 64),
}


@pytest.mark.parametrize("shape", list(_TWO_WIDTHS))
@pytest.mark.parametrize("one_pass", [True, False],
                         ids=["one-pass", "two-kernels"])
def test_two_widths_match_dense(monkeypatch, one_pass, shape):
    """Forward, dq, dk and dv at keys of one width and values of another,
    in both backward forms, against the dense form's own gradient, causal,
    at the softmax scale a caller passes."""
    bh, t, d, d_v, block, pref = _TWO_WIDTHS[shape]
    if pref is not None:
        monkeypatch.setattr(pa, "_PREF_BLOCK", pref)
    _force_form(monkeypatch, one_pass)
    rng = np.random.RandomState(5)
    mk = lambda w: jnp.asarray(rng.randn(bh, t, w).astype(np.float32) * 0.5)
    q, k, v, w = mk(d), mk(d), mk(d_v), mk(d_v)
    scale = 0.14468

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) * w)

    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, sm_scale=scale, block_q=block, block_k=block)
    dense = lambda q, k, v: pa._dense_full(q, k, v, True, scale)
    out = flash(q, k, v)
    assert out.shape == (bh, t, d_v)
    np.testing.assert_allclose(out, dense(q, k, v), rtol=2e-4, atol=2e-5)
    gf = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b, width in zip(gf, gd, (d, d, d_v)):
        assert a.shape == (bh, t, width)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_two_widths_through_the_bthd_adapter_and_the_checks():
    rng = np.random.RandomState(2)
    mk = lambda w: jnp.asarray(rng.randn(2, 32, 3, w).astype(np.float32))
    q, k, v = mk(24), mk(24), mk(16)
    out = flash_attention_bthd(q, k, v, causal=True, sm_scale=0.2)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(6, 32, -1)
    want = pa._dense_full(fold(q), fold(k), fold(v), True, 0.2)
    np.testing.assert_allclose(
        out, want.reshape(2, 3, 32, 16).transpose(0, 2, 1, 3),
        rtol=2e-4, atol=2e-5)
    # queries and keys share one width; the ring block takes one for all
    with pytest.raises(ValueError, match="share one width"):
        flash_attention(fold(q), fold(v), fold(v))
    with pytest.raises(ValueError, match="ring block"):
        flash_attention_block(fold(q), fold(k), fold(v), 0.0, sm_scale=0.2)


def test_grad_under_checked_shard_map():
    """Inside a vma-checked shard_map the kernels' outputs are typed
    varying over the axes their inputs vary over (traced in place, not
    from the kept jaxpr): the sharded gradient is the unsharded one."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.jax import _shard_map
    from horovod_tpu.parallel.mesh import build_mesh

    n = len(jax.devices())
    mesh = build_mesh({"data": n})
    q, k, v = _qkv_bhtd(bh=2 * n, t=32, d=16, seed=4)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=8)
        return jnp.sum(out ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))
    sharded = jax.jit(_shard_map(
        grads, mesh, in_specs=(P("data"),) * 3, out_specs=(P("data"),) * 3,
        check=True,
    ))(q, k, v)
    for a, b in zip(sharded, grads(q, k, v)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_bf16_dtype_preserved():
    q, k, v = _qkv_bhtd()
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    expected = _dense(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        rtol=5e-2, atol=5e-2,
    )


@pytest.mark.parametrize("bh,t,d,pref,plan", [
    # The cells' shape class at its real T: 512 x 512 tiles, 4 rows of bh
    # a grid step, one pair of four above the diagonal.
    (8, 1024, 64, None, (512, 512, 4)),
    # Scaled down (tiles of 64 stand for 512): bh a multiple of the
    # preferred rows, not divisible by them (12 -> 6, 11 -> 1), d 128.
    (16, 256, 64, 64, (64, 64, 8)),
    (12, 256, 64, 64, (64, 64, 6)),
    (11, 256, 64, 64, (64, 64, 1)),
    (4, 256, 128, 64, (64, 64, 4)),
])
def test_bf16_rows_per_step(monkeypatch, bh, t, d, pref, plan):
    """bf16 operands go to the MXU as passed, several rows of ``bh`` share
    a grid step, and the pairs the causal mask empties are skipped."""
    if pref is not None:
        monkeypatch.setattr(pa, "_PREF_BLOCK", pref)
    assert pa._plan(bh, t, t, d, 2, 2, None, None) == plan
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv_bhtd(bh, t, d, seed=1))
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    expected = pa._dense_full(q, k, v, True, d ** -0.5)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_explicit_blocks_are_honoured():
    assert pa._plan(4, 32, 32, 16, 4, 4, 8, 16)[:2] == (8, 16)
    assert pa._plan(4, 32, 64, 16, 4, 4, None, 16)[:2] == (32, 16)
    # One block may be the whole sequence, whatever its length; a prime
    # length over the preferred block has no divisor to tile by.
    assert pa.flashable(262, 262) and pa.flashable(131, 131)
    assert not pa.flashable(521, 521) and not pa.flashable(1024, 521)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("blocks", [(None, None), (8, 16)])
@pytest.mark.parametrize("delta", [0, -64, -20, 8, 24, 32, 200])
def test_block_matches_dense_block(monkeypatch, delta, blocks, dtype):
    """The ring block's ``(o, m, l)`` against its dense twin for a TRACED
    offset, ``t_q != t_k``: every key visible (-64), the diagonal shifted
    either way, whole q rows masked (8, 24: ``m = -1e30``, ``l = 0``,
    ``o = 0`` there), and every pair masked (32, 200)."""
    monkeypatch.setattr(pa, "_PREF_BLOCK", 16)   # 2 x 4 block pairs
    bh, t_q, t_k, d = 6, 32, 64, 16
    rng = np.random.RandomState(7)
    mk = lambda t: jnp.asarray(
        rng.randn(bh, t, d).astype(np.float32) * 0.5).astype(dtype)
    q, k, v = mk(t_q), mk(t_k), mk(t_k)
    scale = d ** -0.5
    bq, bk = blocks

    @jax.jit
    def run(q, k, v, delta):
        return flash_attention_block(
            q, k, v, delta, sm_scale=scale, block_q=bq, block_k=bk)

    o, m, l = run(q, k, v, jnp.float32(delta))
    eo, em, el = pa._dense_block(q, k, v, delta, scale, True)
    assert o.dtype == jnp.float32 and m.shape == l.shape == (bh, t_q)
    tol = dict(rtol=2e-4, atol=2e-5) if dtype == jnp.float32 else dict(
        rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(m), np.asarray(em), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(l), np.asarray(el), **tol)
    np.testing.assert_allclose(np.asarray(o), np.asarray(eo), **tol)
    masked = np.arange(t_q) < delta          # rows that see no key at all
    assert (np.asarray(m)[:, masked] == pa._NEG_INF).all()
    assert (np.asarray(l)[:, masked] == 0.0).all()
    assert (np.asarray(o)[:, masked] == 0.0).all()


def test_bthd_adapter_matches_reference():
    rng = np.random.RandomState(3)
    B, T, H, D = 2, 16, 4, 8
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5)
    q, k, v = mk(), mk(), mk()
    out = flash_attention_bthd(q, k, v, causal=True)
    expected = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-5
    )


def test_block_merge_equals_full():
    """Splitting K/V in two and merging the block triples with the online
    softmax combination must reproduce full attention — the identity the
    ring relies on (each ring step merges one block)."""
    q, k, v = _qkv_bhtd(bh=2, t=16, d=8)
    scale = 8 ** -0.5
    t_half = 8
    k1, k2 = k[:, :t_half], k[:, t_half:]
    v1, v2 = v[:, :t_half], v[:, t_half:]

    # Causal over the concatenated sequence: block 2's keys sit at global
    # offset +t_half relative to q's origin.
    o1, m1, l1 = flash_attention_block(q, k1, v1, 0.0, sm_scale=scale)
    o2, m2, l2 = flash_attention_block(q, k2, v2, float(t_half),
                                       sm_scale=scale)
    m = jnp.maximum(m1, m2)
    c1, c2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
    o = o1 * c1[..., None] + o2 * c2[..., None]
    l = l1 * c1 + l2 * c2
    l = jnp.where(l == 0.0, 1.0, l)
    merged = (o / l[..., None]).astype(q.dtype)

    expected = _dense(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(merged), np.asarray(expected), rtol=2e-4, atol=2e-5
    )


def test_block_grad_flows():
    q, k, v = _qkv_bhtd(bh=2, t=8, d=8)
    scale = 8 ** -0.5

    def loss(q, k, v):
        o, m, l = flash_attention_block(q, k, v, 0.0, sm_scale=scale)
        l = jnp.where(l == 0.0, 1.0, l)
        return jnp.sum((o / l[..., None]) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(_dense(q, k, v, causal=True) ** 2)

    gf = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4
        )


@pytest.mark.parametrize("T", [131, 521])
def test_odd_length_falls_back_to_dense(T):
    """Prime sequence lengths over the preferred block can't satisfy the
    kernel's block constraint (521); the [B,T,H,D] adapter (transformer
    default / Ulysses local attention) must fall back to dense instead of
    raising. A prime length under it (131) is one block of the kernel."""
    rng = np.random.RandomState(5)
    B, H, D = 1, 2, 8
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5)
    q, k, v = mk(), mk(), mk()
    out = flash_attention_bthd(q, k, v, causal=True)
    expected = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-5
    )


def test_kernel_lowers_for_tpu_target():
    """Cross-lower the real (non-interpret) kernel for the TPU platform:
    exercises the Pallas->Mosaic serialization (grid spec, scalar
    prefetch, the lane-dim m/l output blocks) without needing a chip —
    layout/blockspec mistakes fail here at trace time."""
    from functools import partial

    q = jnp.asarray(
        np.random.RandomState(0).randn(2, 256, 64).astype(np.float32)
    )
    f = jax.jit(partial(flash_attention, causal=True, interpret=False))
    lowered = f.trace(q, q, q).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,kernels", [
    ((128, 1024, 64), 2),      # forward + the one-pass backward
    ((16, 8192, 256), 2),      # the same under a raised scoped limit
    ((16, 32768, 256), 3),     # forward + dK/dV + dQ: a row's dq is 32 MB
])
def test_backward_kernels_lower_for_tpu_target(shape, kernels):
    """The backward at the benchmark cells' own shapes, bf16: the forward
    and the backward calls serialize for Mosaic (lane-dense statistic
    blocks, the clamped index maps, the whole-dq block), and no loop of
    XLA's is left in the gradient."""
    from functools import partial

    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    attn = partial(flash_attention, causal=True, interpret=False)
    grad = jax.jit(jax.grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    text = grad.trace(q, q, q).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == kernels
    assert "stablehlo.while" not in text
    # a limit is asked for exactly where the step counts over the default's
    # budget: the one-pass kernel at T 8192
    assert text.count("scoped_memory_configs") == (shape[1] == 8192)


def test_ring_attention_lowers_for_tpu_target():
    """Cross-lower the flash-block ring (scalar-prefetch delta + per-step
    Mosaic kernel + ppermute rotation) for the TPU platform."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.jax import _shard_map
    from horovod_tpu.parallel.mesh import build_mesh
    from horovod_tpu.parallel.ring_attention import ring_attention

    n = len(jax.devices())
    mesh = build_mesh({"seq": n})
    q = jnp.asarray(
        np.random.RandomState(0)
        .randn(1, 128 * n, 4, 64).astype(np.float32)
    )
    fn = jax.jit(_shard_map(
        lambda a, b, c: ring_attention(
            a, b, c, axis_name="seq", causal=True, interpret=False
        ),
        mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
    ))
    lowered = fn.trace(q, q, q).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "tpu_custom_call" in text          # the Mosaic flash block
    assert "collective_permute" in text        # the K/V rotation


# --------------------------------------------------------------------------
# Attention under a selection: each query names its keys.
# --------------------------------------------------------------------------

def _selection(B, T, seed=0, share=0.3):
    """A random selection under the causal rule, every query keeping itself,
    with whole block pairs empty (the table of block pairs has to skip them)
    and one batch row different from the other."""
    rng = np.random.RandomState(seed)
    causal = np.tril(np.ones((T, T), bool))
    sel = (rng.rand(B, T, T) < share) & causal | np.eye(T, dtype=bool)
    sel[0, T // 2:, T // 4:T // 2] = False     # an empty band below the diagonal
    sel[-1, 3 * T // 4:, :T // 4] = False
    return jnp.asarray(sel)


def _dense_selected(q, k, v, sel, scale):
    """``(out, lse, head-summed probabilities)`` of q, k, v ``[B, H, T, D]``
    under ``sel`` ``[B, T, T]``, densely."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = jnp.where(sel[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return (jnp.einsum("bhqk,bhkd->bhqd", p, v),
            jax.nn.logsumexp(s, axis=-1), jnp.sum(p, axis=1))


@pytest.mark.parametrize("dtype,blocks,tol", [
    (jnp.float32, (32, 32), 2e-5), (jnp.float32, (None, None), 2e-5),
    (jnp.float32, (16, 64), 2e-5), (jnp.bfloat16, (32, 32), 3e-2),
])
def test_selection_matches_dense_masked(dtype, blocks, tol):
    """Forward, logsumexp, dq, dk, dv and the head-summed probabilities (what
    the indexer's objective is trained toward: from the returned logsumexp,
    ``sum_h exp(q_h . k_h * scale - lse_h)`` on the selected keys) against
    the dense masked form; float32 gaps are rounding (2e-5 of unit-size
    values), bfloat16 gaps the operands' 2^-9."""
    B, H, T, D = 2, 4, 128, 16
    rng = np.random.RandomState(1)
    q, k, v, ct = (jnp.asarray(rng.randn(B, H, T, D) * 0.5, dtype)
                   for _ in range(4))
    sel = _selection(B, T)
    bq, bk = blocks
    flash = lambda q, k, v: flash_attention(
        q, k, v, sm_scale=0.25, selection=sel.astype(jnp.int8), block_q=bq,
        block_k=bk)
    f32 = lambda a: a.astype(jnp.float32)
    want, want_lse, want_p = _dense_selected(f32(q), f32(k), f32(v), sel, 0.25)
    out, lse = flash(q, k, v)
    assert out.dtype == dtype and lse.dtype == jnp.float32
    assert lse.shape == (B, H, T)
    np.testing.assert_allclose(f32(out), want, atol=tol)
    np.testing.assert_allclose(lse, want_lse, atol=tol)
    s = jnp.einsum("bhqd,bhkd->bhqk", f32(q), f32(k)) * 0.25
    summed = jnp.sum(jnp.where(sel[:, None], jnp.exp(s - lse[..., None]), 0.0),
                     axis=1)
    np.testing.assert_allclose(summed, want_p, atol=H * tol)
    np.testing.assert_allclose(summed.sum(-1), H, rtol=10 * tol)

    got = jax.grad(lambda *a: jnp.sum(f32(flash(*a)[0]) * f32(ct)),
                   argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(
        _dense_selected(*a, sel, 0.25)[0] * f32(ct)), argnums=(0, 1, 2))(
        f32(q), f32(k), f32(v))
    for a, b in zip(got, ref):
        assert a.dtype == dtype
        np.testing.assert_allclose(f32(a), b, atol=4 * tol)


# (B, heads, key/value heads, T, D, block_q, block_k, _PREF_BLOCK): what the
# backward under a selection must get right in both forms.
_SEL_GRAD_SHAPES = {
    # 4 x 4 block pairs; the four heads of a batch row share a grid step
    # and the selection's tile, two of them a key/value head
    "gqa-rows": (2, 4, 2, 64, 16, 16, 16, None),
    "bq<bk": (2, 4, 2, 64, 16, 16, 32, None),
    "bq>bk": (2, 2, 1, 64, 16, 32, 16, None),
    # the cell's head width, the kernel's own tiles (16 standing for 512)
    "d128": (1, 2, 2, 64, 128, None, None, 16),
}


def _sparse_selection(B, T, block):
    """:func:`_selection` with, in the last batch row, a query block whose
    only pairs lie in ONE K block (its own: every table entry of that row
    names the same block) beside the empty bands (pairs the table skips)."""
    sel = np.array(_selection(B, T, seed=3))
    sel[-1, block:2 * block, :block] = False
    assert not sel[0, T // 2:, T // 4:T // 2].any()
    return jnp.asarray(sel)


@pytest.mark.parametrize(
    "shape,dtype",
    [(shape, jnp.float32) for shape in _SEL_GRAD_SHAPES]
    + [("gqa-rows", jnp.bfloat16)],
    ids=lambda v: v if isinstance(v, str) else jnp.dtype(v).name)
@pytest.mark.parametrize("one_pass", [True, False],
                         ids=["one-pass", "two-kernels"])
def test_selection_grad_matches_dense(monkeypatch, one_pass, shape, dtype):
    """dq, dk and dv of ``flash_attention(..., selection=)`` in both backward
    forms against JAX's own gradient of the dense masked reference, under a
    cotangent that is not uniform, the keys and values repeated over their
    query heads inside the loss (so a key/value head's gradient is its
    heads' sum). Shapes this small always plan the one-pass kernel, so the
    form is forced either way."""
    B, H, KV, T, D, bq, bk, pref = _SEL_GRAD_SHAPES[shape]
    if pref is not None:
        monkeypatch.setattr(pa, "_PREF_BLOCK", pref)
    assert pa._plan_bwd(B * H, T, T, D, 4, bq, bk, sel_heads=H)[3]
    _force_form(monkeypatch, one_pass)
    rng = np.random.RandomState(13)
    mk = lambda heads: jnp.asarray(
        rng.randn(B, heads, T, D).astype(np.float32) * 0.5).astype(dtype)
    q, k, v = mk(H), mk(KV), mk(KV)
    w = mk(H).astype(jnp.float32)
    sel = _sparse_selection(B, T, bq or pref)
    scale = D ** -0.5
    spread = lambda a: jnp.repeat(a, H // KV, axis=1)

    def loss_flash(q, k, v):
        out, _ = flash_attention(
            q, spread(k), spread(v), selection=sel.astype(jnp.int8),
            block_q=bq, block_k=bk)
        return jnp.sum(out.astype(jnp.float32) * w)

    def loss_dense(q, k, v):
        f32 = lambda a: a.astype(jnp.float32)
        out = _dense_selected(f32(q), f32(spread(k)), f32(spread(v)), sel,
                              scale)[0]
        return jnp.sum(out.astype(dtype).astype(jnp.float32) * w)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    tol = dict(rtol=1e-4, atol=1e-5) if dtype == jnp.float32 else dict(
        rtol=2e-2, atol=2e-2)
    for a, b in zip(gf, gd):
        assert a.dtype == dtype and float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **tol)


def test_selection_of_every_causal_key_is_causal_attention():
    """A selection that keeps every causal key is the causal kernel's
    function (another program: the mask is read, not made)."""
    q, k, v = (x.reshape(1, 4, 32, 16) for x in _qkv_bhtd())
    sel = jnp.tril(jnp.ones((1, 32, 32), jnp.int8))
    out, _ = flash_attention(q, k, v, selection=sel)
    np.testing.assert_allclose(out, flash_attention(q, k, v, causal=True),
                               atol=2e-6)
    with pytest.raises(ValueError, match="selection is"):
        flash_attention(q[0], k[0], v[0], selection=sel)


def test_tied_scores_choose_the_lower_position():
    """The selection the kernels are fed: of equal scores the lower position
    is kept, as ``jax.lax.top_k`` keeps it, and attention then runs over
    exactly those keys. All the indexer's scores equal (its queries zero):
    every query keeps its first ``top_k`` keys."""
    from horovod_tpu.ops.sparse_index import select_top_k

    B, T, J, D, K = 1, 64, 2, 8, 4
    rng = np.random.RandomState(2)
    k_i = jnp.asarray(rng.randn(B, T, D), jnp.float32)
    w = jnp.ones((B, T, J), jnp.float32)
    sel, lse_i = select_top_k(jnp.zeros((B, T, J, D)), k_i, w, top_k=K,
                              kernel=False)
    first = np.tril(np.ones((T, T), bool)) & (np.arange(T)[None, :] < K)
    np.testing.assert_array_equal(np.asarray(sel[0]) != 0, first)
    np.testing.assert_allclose(lse_i[0], np.log(np.minimum(np.arange(T) + 1,
                                                           K)), atol=1e-6)
    q, k, v = (jnp.asarray(rng.randn(B, 2, T, 16), jnp.float32)
               for _ in range(3))
    out, _ = flash_attention(q, k, v, selection=sel)
    want, _, _ = _dense_selected(q, k, v, jnp.asarray(first)[None], 0.25)
    np.testing.assert_allclose(out, want, atol=2e-6)


# What ``jax.grad(flash_attention(q, k, v, causal=True))`` in bfloat16 planned
# and which kernels it called at the commit before the selection came
# (8e5d92d), at three cells' shapes ``(bh, t, d, d_v)``: the plan notes are
# this file's arithmetic and the kernels' names this file's functions, so
# neither moves with the toolchain. A PR that changes the causal kernels on
# purpose records its own: PR 45 gave the backward at T 8192 the one-pass
# form (half the grid steps, no dQ kernel), every plan the bytes its step
# counts, and left T 1024 and every forward as they were; T 32768 at width
# 256 stands for the two kernels, planned as before.
def _parent_plan(grid, bwd_grid, visited, rows, one_pass, vmem_mb):
    return {"flash_block_q": 512, "flash_block_k": 512,
            "flash_rows_per_step": rows, "flash_grid_steps": grid,
            "flash_pairs_visited": visited, "flash_bwd_block_q": 512,
            "flash_bwd_block_k": 512, "flash_bwd_rows_per_step": 1,
            "flash_bwd_one_pass": one_pass, "flash_bwd_grid_steps": bwd_grid,
            "flash_bwd_pairs_visited": visited,
            "flash_bwd_vmem_mb": vmem_mb}


_ONE_PASS = {"_fwd_kernel": 1, "_dkv_kernel": 1}
_TWO_KERNELS = {"_fwd_kernel": 1, "_dkv_kernel": 1, "_dq_kernel": 1}
_PARENT_PROGRAMS = {
    (8, 1024, 64, 64): (_parent_plan(8, 32, 0.75, 4, True, 10.1), _ONE_PASS),
    (16, 8192, 256, 256): (_parent_plan(2048, 4096, 0.5312, 2, True, 27.1),
                           _ONE_PASS),
    (32, 8192, 192, 128): (_parent_plan(4096, 8192, 0.5312, 2, True, 26.1),
                           _ONE_PASS),
    (16, 32768, 256, 256): (
        _parent_plan(32768, 131072, 0.5078, 2, False, 10.1), _TWO_KERNELS),
}


@pytest.mark.parametrize("shape", sorted(_PARENT_PROGRAMS))
def test_selection_none_keeps_the_parents_programs(shape):
    """Without a selection the call is what it was before the selection came:
    the parent's plan, note for note, the parent's kernels and no other, no
    int8 operand anywhere, and the same program, kernels included, whether
    the keyword is left out or given as None (one process, one toolchain)."""
    import re
    from collections import Counter
    from functools import partial

    from horovod_tpu import trace

    bh, t, d, d_v = shape
    q = jax.ShapeDtypeStruct((bh, t, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((bh, t, d_v), jnp.bfloat16)

    def lowered(**keyword):
        attn = partial(flash_attention, causal=True, interpret=False,
                       **keyword)
        grad = jax.jit(jax.grad(
            lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        trace.reset_build_ledger()
        text = grad.trace(q, q, v).lower(
            lowering_platforms=("tpu",)).as_text()
        notes = {k: v for k, v in trace.plan_args().items()
                 if k.startswith("flash")}
        return text, notes

    text, notes = lowered()
    plan, kernels = _PARENT_PROGRAMS[shape]
    assert notes == plan
    assert Counter(re.findall(r'kernel_name = "(\w+)"', text)) == kernels
    assert "i8" not in text.split("backend_config")[0]
    assert lowered(selection=None) == (text, notes)


def test_selection_kernels_lower_for_tpu_target():
    """The kernels under a selection at the Keye-VL cell's shape (32 heads
    of 128 over 16384 positions, an int8 selection): they serialize for
    Mosaic with the table of block pairs as scalar prefetch, two heads a grid
    step (the selection's tile is counted), the backward ONE kernel with two
    heads' whole dq (2 x 16 MB) under a raised scoped limit, and the plan
    says so; at four times the length a head's dq does not fit and the dQ
    kernel is built."""
    from functools import partial

    from horovod_tpu import trace

    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16)
    sel = jax.ShapeDtypeStruct((1, 16384, 16384), jnp.int8)
    attn = partial(flash_attention, interpret=False)
    trace.reset_build_ledger()
    grad = jax.jit(jax.grad(
        lambda q, k, v, sel: attn(q, k, v, selection=sel)[0].astype(
            jnp.float32).sum(), argnums=(0, 1, 2)))
    text = grad.trace(q, q, q, sel).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert text.count("scoped_memory_configs") == 1
    notes = trace.plan_args()
    assert notes["flash_selection"] is True
    assert (notes["flash_block_q"], notes["flash_block_k"],
            notes["flash_rows_per_step"]) == (512, 512, 2)
    assert (notes["flash_bwd_block_q"], notes["flash_bwd_block_k"],
            notes["flash_bwd_rows_per_step"], notes["flash_bwd_one_pass"],
            notes["flash_bwd_grid_steps"], notes["flash_bwd_vmem_mb"]) == (
        512, 512, 2, True, 16 * 32 * 32, 44.6)
    long_q = jax.ShapeDtypeStruct((1, 32, 65536, 128), jnp.bfloat16)
    long_sel = jax.ShapeDtypeStruct((1, 65536, 65536), jnp.int8)
    trace.reset_build_ledger()
    text = grad.trace(long_q, long_q, long_q, long_sel).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 3
    assert "scoped_memory_configs" not in text
    notes = trace.plan_args()
    assert (notes["flash_bwd_one_pass"], notes["flash_bwd_grid_steps"]) == (
        False, 2 * 16 * 128 * 128)


def test_fetch_table_names_the_block_the_pipeline_holds():
    """A step of the inner axis names its own block where the pair holds a
    selected pair, the last such before it where not, and before the row's
    first the first: nothing is fetched for a pair that is not computed."""
    held = np.array([[0, 1, 0, 0, 1, 0],
                     [1, 0, 0, 0, 0, 0],
                     [0, 0, 0, 0, 0, 1]], bool)
    sel = jnp.asarray(np.kron(held, np.eye(4, dtype=np.int8)))[None]
    np.testing.assert_array_equal(
        pa._fetch_table(sel, 4, 4).reshape(3, 6),
        [[1, 1, 1, 1, 4, 4], [0, 0, 0, 0, 0, 0], [5, 5, 5, 5, 5, 5]])


# --------------------------------------------------------------------------
# The forward kernel's result and logsumexp are named residuals.
# --------------------------------------------------------------------------

_STACK_LAYERS = 3

# Compile options under which two programs of the same arithmetic give the
# same bits: each operation alone and in the dtype the program states. Left to
# itself XLA keeps a bfloat16 intermediate wider where a fusion allows,
# contracts float32 products and sums, and draws its fusions anew round a kept
# array.
AS_STATED = {"xla_allow_excess_precision": False,
             "xla_disable_hlo_passes": "fusion"}


def _stack(selection=None, wrap=lambda layer: layer):
    """``loss(x, w)`` over a stack of attention layers on ``x [B, H, T, D]``,
    each layer handed to ``wrap`` (a ``jax.checkpoint``, or nothing)."""
    @wrap
    def layer(x, w):
        q, k, v = (x * w[i] for i in range(3))
        if selection is None:
            return x + flash_attention(q, k, v, causal=True)
        out, lse = flash_attention(q, k, v, selection=selection)
        return x + out + lse[..., None].astype(x.dtype)

    def loss(x, w):
        for i in range(_STACK_LAYERS):
            x = layer(x, w[i])
        return jnp.sum(x.astype(jnp.float32) ** 2)
    return loss


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("selected", [False, True],
                         ids=["causal", "selection"])
def test_a_recomputation_that_keeps_the_named_residuals(monkeypatch, selected,
                                                        dtype):
    """Under ``jax.checkpoint`` with the models' policy (``models/recompute
    .KEEPS``) a stack of layers lowered for the chip holds ONE forward kernel
    a layer, under plain ``jax.checkpoint`` two (first pass and
    recomputation), and the backward kernels once either way; the two
    programs' gradients are the same bits (interpreted, ``AS_STATED``)."""
    import re
    from collections import Counter
    from functools import partial

    from horovod_tpu.models.recompute import KEEPS

    B, H, T, D = 1, 2, 32, 16
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(B, H, T, D), dtype)
    w = jnp.asarray(1 + 0.1 * rng.randn(_STACK_LAYERS, 3, D), dtype)
    selection = _selection(B, T) if selected else None
    keeps = jax.checkpoint_policies.save_only_these_names(*KEEPS)
    # (made anew a use: a checkpoint holds the kernels as it first traced them)
    stack = lambda policy: _stack(
        selection, partial(jax.checkpoint, policy=policy))
    # (sequences this short plan the one-pass backward, selection or none)
    fwd, bwd = (("_fwd_kernel_sel", ("_dkv_kernel_sel",))
                if selected else ("_fwd_kernel", ("_dkv_kernel",)))

    def forward_calls(policy):
        with monkeypatch.context() as m:
            m.setattr(pa, "_resolve_interpret", lambda interpret: False)
            text = jax.jit(jax.grad(stack(policy))).trace(x, w).lower(
                lowering_platforms=("tpu",)).as_text()
        calls = Counter(re.findall(r'kernel_name = "(\w+)"', text))
        assert {k: n for k, n in calls.items() if k != fwd} == dict.fromkeys(
            bwd, _STACK_LAYERS)
        return calls[fwd]

    assert forward_calls(keeps) == _STACK_LAYERS
    assert forward_calls(None) == 2 * _STACK_LAYERS
    # one program holds both gradients (what the two share is computed once)
    kept, plain = jax.jit(lambda x, w: tuple(
        jax.grad(stack(policy), argnums=(0, 1))(x, w)
        for policy in (keeps, None))
    ).lower(x, w).compile(compiler_options=AS_STATED)(x, w)
    for a, b in zip(kept, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(jnp.abs(a.astype(jnp.float32)).max()) > 0


@pytest.mark.parametrize("selected", [False, True],
                         ids=["causal", "selection"])
def test_outside_a_checkpoint_a_name_lowers_to_nothing(monkeypatch, selected):
    """A name is the identity where nothing recomputes: the gradient of the
    same stack with no ``jax.checkpoint`` round its layers lowers for the chip
    to the text it lowers to with the names taken out of the module."""
    import re

    args = (jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.bfloat16),
            jax.ShapeDtypeStruct((_STACK_LAYERS, 3, 64), jnp.bfloat16))
    if selected:
        args += (jax.ShapeDtypeStruct((1, 256, 256), jnp.int8),)
    monkeypatch.setattr(pa, "_resolve_interpret", lambda interpret: False)
    grad = jax.jit(jax.grad(lambda x, w, *selection: _stack(*selection)(x, w),
                            argnums=(0, 1)))
    texts = []
    for name in (pa.checkpoint_name, lambda x, name: x):
        monkeypatch.setattr(pa, "checkpoint_name", name)
        # one call site, since a kernel's module holds where it was called
        # from; a helper function's running number follows what the process
        # lowered before
        texts.append(re.sub(r"(@[A-Za-z_]+)_\d+", r"\1", grad.trace(
            *args).lower(lowering_platforms=("tpu",)).as_text()))
    # a short backward is one kernel, under a selection too
    assert texts[0].count("tpu_custom_call") == 2 * _STACK_LAYERS
    assert texts[0] == texts[1]
