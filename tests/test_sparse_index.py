"""``ops/sparse_index.py``: the exact top-k selection against
``jax.lax.top_k`` (ties, signed zeros, rows shorter than k, several row
blocks), and the indexer's objective with its analytic gradient against
autodiff of the dense form; both as plain XLA in row blocks and as the Pallas
kernels (interpreted here), at tiles small enough that a call walks several
tiles, chunks and block pairs, some of them without a selected pair."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import sparse_index as si


def _top_k_indices_mask(scores, causal, k):
    """The set of ``jax.lax.top_k``'s indices on rows with ``-inf`` where not
    allowed, scattered into a mask (the definition the program must meet).
    Zeros of both signs are one value, as they compare (``top_k`` orders
    ``-0.0`` under ``0.0``; a rectified score is either, by its weights'
    signs, and the two tie)."""
    masked = np.where(causal, scores + np.float32(0.0), -np.inf)
    _, ids = jax.lax.top_k(jnp.asarray(masked), min(k, scores.shape[-1]))
    mask = np.zeros(scores.shape, bool)
    np.put_along_axis(mask, np.asarray(ids), True, axis=-1)
    return mask & causal


@pytest.mark.parametrize("case", ["random", "ties", "zeros", "negative"])
def test_top_k_mask_is_top_ks_index_set(case):
    R, T, K = 48, 96, 7
    rng = np.random.default_rng(3)
    scores = rng.normal(size=(2, R, T)).astype(np.float32)
    if case == "ties":          # few distinct values: many ties at the k-th
        scores = np.round(scores * 2) / 2
    elif case == "zeros":       # rectified scores: exact zeros of both signs
        scores = np.maximum(scores, 0) * rng.choice([-0.0, 0.0, 1.0], (2, R, T))
        scores = scores.astype(np.float32)
    elif case == "negative":
        scores = -np.abs(np.round(scores * 3) / 3)
    causal = (np.arange(R)[:, None] + (T - R)) >= np.arange(T)[None, :]
    got = np.asarray(si.top_k_mask(jnp.asarray(scores), jnp.asarray(causal), K))
    want = _top_k_indices_mask(scores, causal, K)
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == np.minimum(causal.sum(-1), K)).all()


def test_the_reference_builds_the_same_set_from_top_ks_kth_value():
    """``benchmark/reference/keye_vl.select`` (the k-th value, what lies above
    it and the lowest that equal it) is ``top_k``'s index set too."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.reference import keye_vl as reference

    rng = np.random.default_rng(4)
    scores = (np.round(rng.normal(size=(2, 32, 32)) * 2) / 2).astype(np.float32)
    causal = np.tril(np.ones((32, 32), bool))
    got = np.asarray(reference.select(jnp.asarray(scores), jnp.asarray(causal),
                                      5))
    np.testing.assert_array_equal(got, _top_k_indices_mask(scores, causal, 5))


def _inputs(B=2, T=64, H=4, KV=2, D=16, J=3, Di=8, seed=5):
    rng = np.random.default_rng(seed)
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return dict(q=arr(B, T, H, D), k=arr(B, T, KV, D), q_i=arr(B, T, J, Di),
                k_i=arr(B, T, Di), w=arr(B, T, J) * 0.3)


def _dense_scores(q_i, k_i, w):
    x = jnp.einsum("btjd,bsd->btjs", q_i, k_i)
    return jnp.sum(w[..., None] * jax.nn.relu(x), axis=2)


@pytest.fixture()
def small_tiles(monkeypatch):
    """Tiles of 64 rows x 128 keys (selection) and 128 x 128 (objective): a
    sequence of 256 walks four row tiles, two chunks and three block pairs."""
    monkeypatch.setattr(si, "_SEL_ROWS", 64)
    monkeypatch.setattr(si, "_SEL_COLS", 128)
    monkeypatch.setattr(si, "_KL_BLOCK", 128)


@pytest.mark.parametrize("T,form", [
    (64, dict(kernel=False)), (64, dict(kernel=False, block_rows=16)),
    (256, dict()), (256, dict(kernel=False, block_rows=32)),
])
def test_select_top_k_in_row_blocks_and_by_the_kernel(small_tiles, T, form):
    from horovod_tpu import trace

    a = _inputs(T=T)
    K = 9 if T == 64 else 70
    # ties at the k-th value in some rows: rounded keys, one head's weight 0
    a["k_i"] = jnp.round(a["k_i"] * 2) / 2
    a["q_i"] = jnp.round(a["q_i"])
    trace.reset_build_ledger()
    sel, lse = si.select_top_k(a["q_i"], a["k_i"], a["w"], top_k=K, **form)
    assert trace.plan_args()["sparse_index_kernel"] is (form == {})
    assert trace.build_ledger()["fallbacks"] == []
    assert sel.dtype == jnp.int8 and sel.shape == (2, T, T)
    scores = np.asarray(_dense_scores(a["q_i"], a["k_i"], a["w"]))
    causal = np.tril(np.ones((T, T), bool))
    want = _top_k_indices_mask(scores, causal, K)
    np.testing.assert_array_equal(np.asarray(sel) != 0, want)
    np.testing.assert_allclose(
        lse, jax.nn.logsumexp(jnp.where(want, scores, -jnp.inf), axis=-1),
        rtol=1e-5, atol=1e-5)
    # no gradient passes through the choice
    g = jax.grad(lambda w: si.select_top_k(
        a["q_i"], a["k_i"], w, top_k=K, **form)[1].sum())(a["w"])
    assert float(jnp.max(jnp.abs(g))) == 0.0


def test_a_length_the_kernels_refuse_takes_the_xla_form_and_says_so():
    from horovod_tpu import trace

    a = _inputs(T=96)
    trace.reset_build_ledger()
    sel, lse_i = si.select_top_k(a["q_i"], a["k_i"], a["w"], top_k=9)
    lse = jnp.zeros((2, 4, 96))
    si.index_kl(a["q"], a["k"], lse, sel, a["q_i"], a["k_i"], a["w"], lse_i,
                sm_scale=0.25)
    assert [(f["op"], f["reason"])
            for f in trace.build_ledger()["fallbacks"]] == [
        ("sparse_index_select", "tiles_do_not_divide"),
        ("sparse_index_kl", "tiles_do_not_divide")]
    notes = trace.plan_args()
    assert notes["sparse_index_kernel"] is False
    assert notes["sparse_index_loss_kernel"] is False


@pytest.mark.parametrize("T,form", [
    (64, dict(kernel=False)), (64, dict(kernel=False, block_rows=16)),
    (256, dict()),
])
def test_index_kl_and_its_gradient_against_autodiff(small_tiles, T, form):
    a = _inputs(T=T)
    B, T, H, D = a["q"].shape
    KV, K, scale = a["k"].shape[2], 9 if T == 64 else 40, D ** -0.5
    sel, _ = si.select_top_k(a["q_i"], a["k_i"], a["w"], top_k=K,
                             kernel=False)
    if T == 256:   # a block pair under the diagonal without a selected pair
        sel = sel.at[0, 128:, :128].set(0)
    chosen = sel != 0
    lse_i = jax.nn.logsumexp(jnp.where(chosen, _dense_scores(
        a["q_i"], a["k_i"], a["w"]), -jnp.inf), axis=-1)
    k_rep = jnp.repeat(a["k"], H // KV, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", a["q"], k_rep) * scale
    s = jnp.where(chosen[:, None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)                       # [B, H, T]
    target = jnp.mean(jax.nn.softmax(s, axis=-1), axis=1)    # [B, T, T]

    def dense(q_i, k_i, w):
        logq = jax.nn.log_softmax(jnp.where(
            chosen, _dense_scores(q_i, k_i, w), -jnp.inf), axis=-1)
        return jnp.sum(jnp.where(chosen & (target > 0), target * (
            jnp.log(jnp.where(target > 0, target, 1.0))
            - jnp.where(chosen, logq, 0.0)), 0.0))

    def program(q_i, k_i, w):
        return si.index_kl(a["q"], a["k"], lse, sel, q_i, k_i, w, lse_i,
                           sm_scale=scale, **form)

    args = (a["q_i"], a["k_i"], a["w"])
    want, g_want = jax.value_and_grad(dense, argnums=(0, 1, 2))(*args)
    got, g_got = jax.value_and_grad(program, argnums=(0, 1, 2))(*args)
    assert float(want) > 1.0
    np.testing.assert_allclose(got, want, rtol=2e-6)
    for x, y in zip(g_got, g_want):
        np.testing.assert_allclose(x, y, atol=2e-5 * float(jnp.max(jnp.abs(y))))
    # a cotangent scales it, and nothing reaches the target's side
    g3 = jax.grad(lambda *p: 3.0 * program(*p), argnums=(0, 1, 2))(*args)
    np.testing.assert_allclose(g3[2], 3.0 * g_got[2], rtol=1e-6)
    side = jax.grad(lambda q, k, lse: si.index_kl(
        q, k, lse, sel, *args, lse_i, sm_scale=scale, **form),
        argnums=(0, 1, 2))(
        a["q"], a["k"], lse)
    assert all(float(jnp.max(jnp.abs(x))) == 0.0 for x in side)


def test_plan_notes_say_form_and_pairs():
    from horovod_tpu import trace

    a = _inputs()
    trace.reset_build_ledger()
    si.select_top_k(a["q_i"], a["k_i"], a["w"], top_k=9, kernel=False)
    notes = trace.plan_args()
    assert notes["sparse_index_form"] == "int8_mask"
    assert notes["sparse_index_top_k"] == 9
    assert notes["sparse_index_pairs_selected"] == 2 * (45 + 55 * 9)
    assert notes["sparse_index_pairs_causal"] == 2 * 64 * 65 // 2
