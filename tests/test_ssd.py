"""The selective state-space scan (``ops/ssd.py``): the chunked form against
the rule token by token, values and all six gradients.

Tolerances. With float32 products the two are the same mathematics in another
order (a masked product and a pass over the chunks' states against one
rank-one update a token): gaps are float32 rounding, growing with the chunk
(a chunk of 128 sums 128 terms where the recurrence adds one): measured at
most 4e-6 of the largest value and 1.5e-4 of a gradient's largest element
(``dt``'s, which passes through two running sums), limits 2e-5 and 1e-3. With
bfloat16 products every operand of the four products is rounded to 2^-9
relative while decays and state stay float32: measured 0.5% of the largest
value and 1.2% of a gradient's norm, limits 2% and 5%, which a wrong mask, a
wrong group or a dropped chunk state exceeds by far."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import trace
from horovod_tpu.ops.ssd import ssd_chunked, ssd_recurrent

b, T, H, P, G, N = 2, 80, 4, 8, 2, 16


def _inputs(seed=0, heads=H, groups=G, length=T):
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (arr(b, length, heads, P), jax.nn.softplus(arr(b, length, heads)),
            -jnp.exp(arr(heads)), arr(b, length, groups, N),
            arr(b, length, groups, N), arr(heads))


def _grads(fn, args, **kw):
    # a nonlinear readout of y and of the final state, so that every
    # cotangent differs; one compiled program (operation by operation the
    # scans' transposes take seconds)
    def readout(*a):
        y, S = fn(*a, **kw)
        return jnp.sum(jnp.sin(y)) + jnp.sum(S * S)
    # the program runs once: LLVM's expensive passes cost more than they save
    return jax.jit(jax.grad(readout, argnums=tuple(range(6)))).lower(
        *args).compile(compiler_options={
            "xla_llvm_disable_expensive_passes": True})(*args)


@functools.lru_cache(maxsize=None)
def _recurrent(seed, heads=H, groups=G):
    """``(args, (y, S), gradients)`` of the rule token by token, once a
    process."""
    args = _inputs(seed, heads, groups)
    return args, jax.jit(ssd_recurrent)(*args), _grads(ssd_recurrent, args)


@pytest.mark.parametrize("chunk,heads,groups", [
    (16, 4, 2), (32, 4, 2), (128, 4, 2), (16, 4, 4), (32, 6, 1)])
def test_chunked_equals_recurrent_in_float32(chunk, heads, groups):
    """Chunks that divide the 80 tokens (16), that do not (32: the sequence
    is padded with tokens of dt = 0) and that hold all of it (128); groups
    shared by 2, 1 and 6 heads."""
    args, (y0, S0), grads0 = _recurrent(0, heads, groups)
    y1, S1 = ssd_chunked(*args, chunk=chunk, dtype=jnp.float32)
    assert y1.shape == (b, T, heads, P) and y1.dtype == jnp.float32
    assert S1.shape == (b, heads, P, N)
    top = float(jnp.max(jnp.abs(y0)))
    assert float(jnp.max(jnp.abs(y1 - y0))) <= 2e-5 * top
    assert float(jnp.max(jnp.abs(S1 - S0))) <= 2e-5 * float(
        jnp.max(jnp.abs(S0)))
    names = ("x", "dt", "A", "B", "C", "D")
    for name, g0, g1 in zip(names, grads0,
                            _grads(ssd_chunked, args, chunk=chunk,
                                   dtype=jnp.float32)):
        assert g1.shape == g0.shape
        assert float(jnp.max(jnp.abs(g1 - g0))) <= 1e-3 * float(
            jnp.max(jnp.abs(g0))), name


def test_bfloat16_products_stay_inside_their_band():
    args, (y0, _), grads0 = _recurrent(3)
    y1, _ = ssd_chunked(*args, chunk=16)
    assert float(jnp.max(jnp.abs(y1 - y0))) <= 0.02 * float(
        jnp.max(jnp.abs(y0)))
    for g0, g1 in zip(grads0, _grads(ssd_chunked, args, chunk=16)):
        assert float(jnp.linalg.norm(g1 - g0)) <= 0.05 * float(
            jnp.linalg.norm(g0))


def test_a_head_that_forgets_within_a_chunk_is_exact():
    """``dt * A`` of -40 a token: ``exp`` of a chunk's running sum underflows
    float32 after three tokens. The mask is formed from differences, so
    nothing is divided by it and neighbours still see each other."""
    x, dt, A, B, C, D = _inputs(seed=5)
    A = jnp.full_like(A, -40.0)
    dt = jnp.ones_like(dt)
    y0, _ = jax.jit(ssd_recurrent)(x, dt, A, B, C, D)
    y1, _ = ssd_chunked(x, dt, A, B, C, D, chunk=16, dtype=jnp.float32)
    assert bool(jnp.all(jnp.isfinite(y1)))
    np.testing.assert_allclose(y1, y0, atol=2e-5 * float(jnp.max(jnp.abs(y0))))
    g = _grads(ssd_chunked, (x, dt, A, B, C, D), chunk=16, dtype=jnp.float32)
    assert all(bool(jnp.all(jnp.isfinite(leaf))) for leaf in g)


def test_groups_are_read_and_not_repeated():
    """Head ``h`` reads group ``h // (H / G)``: with the groups' B and C
    repeated to the heads by hand the result is the same to the bit, and the
    lowered chunked form holds no ``[.., H, N]`` copy of B or C."""
    x, dt, A, B, C, D = _inputs(seed=7)
    shared = ssd_chunked(x, dt, A, B, C, D, chunk=16, dtype=jnp.float32)[0]
    rep = lambda m: jnp.repeat(m, H // G, axis=2)
    own = ssd_chunked(x, dt, A, rep(B), rep(C), D, chunk=16,
                      dtype=jnp.float32)[0]
    np.testing.assert_allclose(shared, own, atol=1e-5)
    text = jax.jit(lambda *a: ssd_chunked(*a, chunk=16)[0]).lower(
        x, dt, A, B, C, D).as_text()
    assert f"tensor<{b}x5x16x{H}x{N}x" not in text
    with pytest.raises(ValueError, match="share"):
        ssd_chunked(x, dt, A, B[:, :, :1].repeat(3, axis=2),
                    C[:, :, :1].repeat(3, axis=2), D)


def test_plan_notes():
    trace.reset_build_ledger()
    args = _inputs()
    jax.eval_shape(lambda *a: ssd_chunked(*a, chunk=32), *args)
    notes = trace.plan_args()
    assert {k: notes[k] for k in notes if k.startswith("ssm_")} == {
        "ssm_heads": H, "ssm_head_dim": P, "ssm_state": N, "ssm_groups": G,
        "ssm_chunk": 32, "ssm_chunks": 3, "ssm_kernel": False}
    assert trace.build_ledger()["fallbacks"] == []
