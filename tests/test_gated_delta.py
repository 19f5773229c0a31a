"""The chunked gated delta rule (``ops/gated_delta.py``) against the rule
itself, token by token: values, final state and gradients, over several
chunk counts and with strong and weak decay. float32 operands here, so the
tolerance is float32's: the two forms are the same mathematics in another
order of summation (measured gaps: 1e-7 to 7e-6 of values of order 0.3).

The chunk-local part has two forms of its own: batched XLA products at the
shapes above (head widths of 16 and 32 fill no lane), and the Pallas kernels
where ``_plan`` takes the shapes (widths of 128 and 256), interpreted on the
CPU. The second half holds the kernels against the rule and against the XLA
form, values and all five gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import gated_delta as gd
from horovod_tpu.ops.gated_delta import (gated_delta_chunked,
                                         gated_delta_recurrent,
                                         unit_lower_inverse)

B, T, H, DK, DV = 2, 128, 3, 16, 32
# mean log-decay a token: 1e-3 keeps the state for the whole sequence (what a
# trained model's slow heads do), 5.0 forgets it within a token or two
DECAYS = {"weak": 1e-3, "middling": 0.7, "strong": 5.0}


def _wide(B, T, Hk, H, dk, dv, decay=0.7, seed=5, dtype=jnp.float32):
    """q, k (unit vectors, ``Hk`` key heads), v (``H`` value heads), the log
    decay and beta from the seed."""
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(B, T, Hk, dk))) / np.sqrt(dk)
    k = unit(rng.normal(size=(B, T, Hk, dk)))
    v = rng.normal(size=(B, T, H, dv))
    g = -decay * np.abs(rng.normal(size=(B, T, H)))
    beta = 1.0 / (1.0 + np.exp(-rng.normal(size=(B, T, H))))
    return (tuple(jnp.asarray(x, dtype) for x in (q, k, v))
            + tuple(jnp.asarray(x, jnp.float32) for x in (g, beta)))


def _inputs(decay, seed=0):
    return _wide(B, T, H, H, DK, DV, decay, seed)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("chunk", [8, 32, 64, 128])
def test_chunked_equals_recurrent(decay, chunk):
    args = _inputs(DECAYS[decay])
    o_ref, s_ref = gated_delta_recurrent(*args)
    o, s = gated_delta_chunked(*args, chunk=chunk, dtype=jnp.float32)
    np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=0)
    np.testing.assert_allclose(s, s_ref, atol=2e-5, rtol=0)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_gradients_equal_recurrent(decay, chunk):
    args = _inputs(DECAYS[decay], seed=1)
    weight = jnp.asarray(np.random.default_rng(2).normal(
        size=(B, T, H, DV)), jnp.float32)
    loss = lambda f: lambda *a: jnp.sum(f(*a)[0] * weight)
    want = jax.grad(loss(gated_delta_recurrent), argnums=range(5))(*args)
    got = jax.grad(loss(lambda *a: gated_delta_chunked(
        *a, chunk=chunk, dtype=jnp.float32)), argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=5e-5 * scale, rtol=0,
                                   err_msg=name)


def test_state_carries_across_calls():
    """Two halves, the second started from the first's state, are the whole."""
    args = _inputs(DECAYS["weak"], seed=3)
    o_ref, s_ref = gated_delta_chunked(*args, chunk=16, dtype=jnp.float32)
    half = lambda x, i: x[:, i * T // 2:(i + 1) * T // 2]
    o1, s1 = gated_delta_chunked(*(half(x, 0) for x in args), chunk=16,
                                 dtype=jnp.float32)
    o2, s2 = gated_delta_chunked(*(half(x, 1) for x in args), chunk=16,
                                 dtype=jnp.float32, initial_state=s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o_ref, atol=2e-5)
    np.testing.assert_allclose(s2, s_ref, atol=2e-5)


def test_bfloat16_operands_stay_near():
    """The model's call: bf16 MXU operands, f32 state. The gap is bf16's
    rounding of the operands (2^-9 relative), not a different rule."""
    args = _inputs(DECAYS["middling"], seed=4)
    o_ref, _ = gated_delta_recurrent(*args)
    o, _ = gated_delta_chunked(*args, chunk=64)
    assert o.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(o - o_ref))) < 0.02 * float(
        jnp.max(jnp.abs(o_ref)))


@pytest.mark.parametrize("size", [1, 2, 16, 64])
def test_unit_lower_inverse(size):
    rng = np.random.default_rng(size)
    m = np.tril(rng.normal(size=(3, 5, size, size)) * 0.3, -1) + np.eye(size)
    inv = unit_lower_inverse(jnp.asarray(m, jnp.float32))
    np.testing.assert_allclose(inv, np.linalg.inv(m), atol=1e-4)


def test_refuses_a_ragged_last_chunk():
    args = _inputs(DECAYS["weak"])
    with pytest.raises(ValueError, match="multiple of the chunk"):
        gated_delta_chunked(*args, chunk=48)


# --------------------------------------------------------------------------
# The chunk-local part as Pallas kernels (interpreted here).

def _xla_form(monkeypatch, *args, **kw):
    """``gated_delta_chunked`` with the plan refusing every shape."""
    with monkeypatch.context() as m:
        m.setattr(gd, "_plan", lambda *a: None)
        return gated_delta_chunked(*args, **kw)


def _uses_kernel(*args, **kw):
    return "pallas_call" in str(jax.make_jaxpr(
        lambda *a: gated_delta_chunked(*a, **kw))(*args))


# (B, T, key heads, value heads, d_k, d_v, chunk, tiles a step at most)
KERNEL_CASES = {
    "one_head": (1, 256, 1, 1, 128, 128, 64, 8),
    "three_heads_two_rows": (2, 128, 3, 3, 128, 128, 64, 8),
    "shared_key_head": (1, 256, 1, 2, 128, 128, 64, 8),
    "dk_not_dv": (1, 256, 2, 2, 128, 256, 32, 8),
    "chunks_a_multiple_of_the_block": (1, 512, 1, 1, 128, 128, 64, 2),
    "chunks_no_multiple_of_the_block": (1, 384, 1, 1, 128, 128, 64, 2),
    "chunk_of_a_whole_tile": (1, 256, 1, 1, 128, 128, 128, 8),
    "chunk_of_16": (1, 256, 1, 2, 128, 128, 16, 8),
}


def _rule(q, k, v, g, beta):
    """The rule itself, a shared key head repeated for it."""
    rep = lambda x: jnp.repeat(x, v.shape[2] // x.shape[2], axis=2)
    return gated_delta_recurrent(rep(q), rep(k), v, g, beta)


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_equals_recurrent_and_xla_form(case, monkeypatch):
    B, T, Hk, H, dk, dv, chunk, tiles = KERNEL_CASES[case]
    monkeypatch.setattr(gd, "_PREF_TILES", tiles)
    args = _wide(B, T, Hk, H, dk, dv)
    kw = dict(chunk=chunk, dtype=jnp.float32)
    assert _uses_kernel(*args, **kw)
    o, s = gated_delta_chunked(*args, **kw)
    o_rule, s_rule = _rule(*args)
    o_xla, s_xla = _xla_form(monkeypatch, *args, **kw)
    for got, rule, xla in ((o, o_rule, o_xla), (s, s_rule, s_xla)):
        np.testing.assert_allclose(got, rule, atol=2e-5, rtol=0)
        np.testing.assert_allclose(got, xla, atol=2e-6, rtol=0)


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_gradients_equal_recurrent_and_xla_form(case, monkeypatch):
    B, T, Hk, H, dk, dv, chunk, tiles = KERNEL_CASES[case]
    monkeypatch.setattr(gd, "_PREF_TILES", tiles)
    args = _wide(B, T, Hk, H, dk, dv, seed=6)
    weight = jnp.asarray(np.random.default_rng(7).normal(
        size=(B, T, H, dv)), jnp.float32)
    kw = dict(chunk=chunk, dtype=jnp.float32)
    grad = lambda f: jax.grad(
        lambda *a: jnp.sum(f(*a)[0] * weight), argnums=range(5))(*args)
    got = grad(lambda *a: gated_delta_chunked(*a, **kw))
    rule = grad(_rule)
    xla = grad(lambda *a: _xla_form(monkeypatch, *a, **kw))
    for name, a, b, c in zip("q k v g beta".split(), got, rule, xla):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=5e-5 * scale, rtol=0,
                                   err_msg=name + " against the rule")
        np.testing.assert_allclose(a, c, atol=5e-6 * scale, rtol=0,
                                   err_msg=name + " against the XLA form")


@pytest.mark.parametrize("case", ["shared_key_head", "dk_not_dv",
                                  "chunks_no_multiple_of_the_block"])
def test_kernel_in_bfloat16_rounds_where_the_xla_form_does(case, monkeypatch):
    """The model's call: bf16 q, k, v and bf16 MXU operands. The five arrays
    the scan reads are rounded at the same points in both forms, so the
    outputs agree far inside bf16's own step; the gradients agree to a few of
    its steps (the kernel sums a head's contributions to dq and dk in float32
    before one rounding, XLA rounds each), and both stay near the rule."""
    B, T, Hk, H, dk, dv, chunk, tiles = KERNEL_CASES[case]
    monkeypatch.setattr(gd, "_PREF_TILES", tiles)
    args = _wide(B, T, Hk, H, dk, dv, seed=8, dtype=jnp.bfloat16)
    weight = jnp.asarray(np.random.default_rng(9).normal(
        size=(B, T, H, dv)), jnp.float32)
    kw = dict(chunk=chunk)
    both = lambda f: (f(*args)[0], jax.grad(
        lambda *a: jnp.sum(f(*a)[0] * weight), argnums=range(5))(*args))
    o, got = both(lambda *a: gated_delta_chunked(*a, **kw))
    o_xla, xla = both(lambda *a: _xla_form(monkeypatch, *a, **kw))
    o_rule, rule = both(_rule)
    top = lambda x: float(jnp.max(jnp.abs(x.astype(jnp.float32))))
    gap = lambda a, b: top(a.astype(jnp.float32) - b.astype(jnp.float32))
    assert gap(o, o_xla) < 1e-4 * top(o_rule)
    assert gap(o, o_rule) < 0.02 * top(o_rule)
    for name, a, b, c in zip("q k v g beta".split(), got, xla, rule):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert gap(a, b) < 0.02 * top(c), name + " against the XLA form"
        assert gap(a, c) < 0.04 * top(c), name + " against the rule"


def test_plan_at_the_cell_s_shapes():
    """Qwen3-Next's DeltaNet layer at one 8192-token sequence: bf16 in and
    out, 32 value heads of 128, chunks of 64."""
    tile, tiles = gd._plan(8192, 64, 128, 128, 2, 2)
    assert (tile, tiles) == (128, 8)        # 16 chunks a grid step
    assert 32 * (8192 // (tile * tiles)) == 256   # grid steps a layer
    held = gd._step_vmem_bytes(tiles, tile, 64, 128, 128, 2, 2)
    assert 6 * 2 ** 20 < held <= gd._VMEM_BUDGET
    # float32 in and out, wider values: fewer tiles a step, still under
    tile, tiles = gd._plan(8192, 64, 128, 256, 4, 4)
    assert tile == 128 and tiles < 8 and (8192 // tile) % tiles == 0
    assert gd._step_vmem_bytes(tiles, tile, 64, 128, 256, 4, 4) \
        <= gd._VMEM_BUDGET
    # a chunk as wide as the lanes is its own tile
    assert gd._plan(1024, 128, 128, 128, 2, 2)[0] == 128


@pytest.mark.parametrize("why,shape", [
    ("chunk_no_power_of_two", (384, 48, 128, 128)),
    ("chunk_under_a_packed_register", (256, 8, 128, 128)),
    ("key_width_fills_no_lane", (256, 64, 64, 128)),
    ("value_width_fills_no_lane", (256, 64, 128, 192)),
    ("sequence_no_whole_tiles", (192, 64, 128, 128)),
    ("a_tile_s_matrices_overrun_vmem", (1024, 256, 128, 128)),
])
def test_plan_refuses(why, shape):
    assert gd._plan(*shape, 2, 2) is None


@pytest.mark.parametrize("why", ["initial_state", "narrow_key_heads",
                                 "chunk_of_8"])
def test_fallback_to_the_xla_form(why):
    dk = 64 if why == "narrow_key_heads" else 128
    args = _wide(1, 384, 1, 1, dk, 128)
    kw = dict(chunk=8 if why == "chunk_of_8" else 64, dtype=jnp.float32)
    if why == "initial_state":
        assert _uses_kernel(*args, **kw)
        kw["initial_state"] = jnp.zeros((1, 1, dk, 128))
    assert not _uses_kernel(*args, **kw)
    o, s = gated_delta_chunked(*args, **kw)
    o_rule, s_rule = gated_delta_recurrent(*args)
    np.testing.assert_allclose(o, o_rule, atol=2e-5, rtol=0)
    np.testing.assert_allclose(s, s_rule, atol=2e-5, rtol=0)


def test_a_chunk_that_is_no_power_of_two_is_the_xla_form_s_to_refuse():
    """The plan hands it on, and the XLA form's inverse refuses it as it
    always has."""
    args = _wide(1, 384, 1, 1, 128, 128)
    with pytest.raises(ValueError, match="not a power of two"):
        gated_delta_chunked(*args, chunk=48)


@pytest.mark.parametrize("kernel", [True, False])
def test_plan_notes_are_always_recorded(kernel):
    from horovod_tpu import trace

    assert not trace.ACTIVE
    trace.reset_build_ledger()
    args = _wide(1, 512, 1, 2, 128 if kernel else 16, 128)
    assert _uses_kernel(*args, chunk=64) == kernel
    notes = trace.plan_args()
    assert notes["gdn_chunk"] == 64 and notes["gdn_chunks"] == 8
    assert notes["gdn_heads"] == 2
    assert notes["gdn_kernel"] is kernel
    assert notes["gdn_bwd_recomputes_inverse"] is False
    assert notes["gdn_block_chunks"] == (8 if kernel else 0)
    assert notes["gdn_grid_steps"] == (2 if kernel else 0)


def test_refuses_key_heads_that_do_not_divide():
    args = _wide(1, 256, 2, 3, 128, 128)
    with pytest.raises(ValueError, match="do not share"):
        gated_delta_chunked(*args)
