"""The chunked gated delta rule (``ops/gated_delta.py``) against the rule
itself, token by token: values, final state and gradients, over several
chunk counts and with strong and weak decay. float32 operands here, so the
tolerance is float32's: the two forms are the same mathematics in another
order of summation (measured gaps: 1e-7 to 7e-6 of values of order 0.3)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.gated_delta import (gated_delta_chunked,
                                         gated_delta_recurrent,
                                         unit_lower_inverse)

B, T, H, DK, DV = 2, 128, 3, 16, 32
# mean log-decay a token: 1e-3 keeps the state for the whole sequence (what a
# trained model's slow heads do), 5.0 forgets it within a token or two
DECAYS = {"weak": 1e-3, "middling": 0.7, "strong": 5.0}


def _inputs(decay, seed=0):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(B, T, H, DK))) / np.sqrt(DK)
    k = unit(rng.normal(size=(B, T, H, DK)))
    v = rng.normal(size=(B, T, H, DV))
    g = -decay * np.abs(rng.normal(size=(B, T, H)))
    beta = 1.0 / (1.0 + np.exp(-rng.normal(size=(B, T, H))))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


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
