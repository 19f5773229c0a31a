"""The per-channel delta rule (``ops/kda.py``): the chunked form against the
rule token by token, values, final state and all five gradients.

Tolerances. With float32 products the two are the same mathematics in
another order (a triangular system a chunk and a pass over the chunks' states
against one rank-one update a token): gaps are float32 rounding. A value is
measured at most 3e-6 of the largest (limit 2e-5). A gradient is held by its
largest element: measured at most 8e-6 for q, k, v and beta and 4e-5 for the
gate (limit 3e-4 for all five). The gate's is the widest because the chunked
form reaches it through the running sum ``G``: a pair ``(i, j)`` of a chunk
gives ``+x`` to ``G_i`` and ``-x`` to ``G_j``, and the sum's transpose adds
both to every token before ``j``, where they cancel, in exact arithmetic to
nothing and in float32 to a residue of ``1e-7 |x|``; the rule token by token
never forms the pair. With bfloat16 products every operand is rounded to 2^-9
AFTER its scaling by ``exp(G - G_r)`` while gates, sums and state stay
float32: measured 0.6% of the largest value and 2% of a gradient's norm,
limits 2% and 6%, which a wrong mask, a wrong reference row or a dropped chunk
state exceeds by far (the last reads a fifth of the largest VALUE, ten times
the limit: see the hand-over test).

Gates are drawn over the whole of (-5, 0) (``spread``: ``-5 sigmoid(6 z)``,
a third of the channels within 0.05 of either end) and with a whole chunk AT
the bound (``bound``: every exponent of a sub-block's columns at its largest,
``exp(75)``): finite in every form, and inside the same limits.
Every form's values and gradients are computed as one compiled program each,
which is how a model calls them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import trace
from horovod_tpu.ops import kda
from horovod_tpu.ops.kda import kda_chunked, kda_recurrent

B, T, H, DK, DV = 2, 128, 2, 32, 16
NAMES = ("q", "k", "v", "g", "beta")


def _inputs(gates, seed=0, length=T, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    g = -5.0 * jax.nn.sigmoid(6.0 * arr(B, length, H, DK))
    if gates == "bound":
        g = g.at[:, 64:128].set(-5.0)
    return ((unit(arr(B, length, H, DK)) * DK ** -0.5).astype(dtype),
            unit(arr(B, length, H, DK)).astype(dtype),
            arr(B, length, H, DV).astype(dtype), g,
            jax.nn.sigmoid(arr(B, length, H)))


def _values(fn, args, **kw):
    return jax.jit(lambda *a: fn(*a, **kw))(*args)


def _grads(fn, args, **kw):
    # a readout that weighs every output differently
    loss = lambda *a: jnp.sum(jnp.sin(3.0 * fn(*a, **kw)[0]))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args)


def _close(got, want, tol):
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("gates", ["spread", "bound"])
@pytest.mark.parametrize("chunk", [32, 64])
def test_chunked_equals_recurrent(gates, chunk):
    args = _inputs(gates)
    o, S = _values(kda_recurrent, args)
    got, S_got = _values(kda_chunked, args, chunk=chunk, dtype=jnp.float32)
    assert got.dtype == jnp.float32 and got.shape == (B, T, H, DV)
    _close(got, o, 2e-5)
    _close(S_got, S, 2e-5)


@pytest.mark.parametrize("gates", ["spread", "bound"])
@pytest.mark.parametrize("chunk", [32, 64])
def test_chunked_gradients_equal_recurrent(gates, chunk):
    args = _inputs(gates)
    want = _grads(kda_recurrent, args)
    got = _grads(kda_chunked, args, chunk=chunk, dtype=jnp.float32)
    for name, a, b in zip(NAMES, got, want):
        assert float(jnp.max(jnp.abs(b))) > 0, name
        _close(a, b, 3e-4)


def test_small_cotangents_keep_the_pairs_deep_in_a_fast_sub_block():
    """Values of a hundredth and a readout of a thousandth, as a model's
    are, with a whole chunk at the bound: the gate's and the keys' gradients
    come from pairs deep in a sub-block, whose cotangents are multiplied by
    the rows' ``exp(G_i - G_r)`` before the columns' ``exp(G_r - G_j)``
    comes back. Referred to the sub-block's first row that product left
    float32's range (``exp(-75)`` times 1e-7) and the two gradients read 2
    and 7% off, in float32 as in bfloat16; referred to its middle row they
    are rounding: measured 3e-6 of a gradient's norm, limit 1e-4."""
    q, k, v, g, beta = _inputs("bound", seed=3)
    args = (q, k, 0.01 * v, g, beta)
    loss = lambda fn, **kw: jax.jit(jax.grad(
        lambda *a: 1e-3 * jnp.sum(jnp.sin(300.0 * fn(*a, **kw)[0])),
        argnums=(0, 1, 2, 3, 4)))(*args)
    want = loss(kda_recurrent)
    got = loss(kda_chunked, chunk=64, dtype=jnp.float32)
    for name, a, b in zip(NAMES, got, want):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(b)), name


@pytest.mark.parametrize("length", [150, 70])
def test_a_sequence_that_is_no_multiple_of_the_chunk_is_padded(length):
    """Padded tokens leave the state as it is (``g`` 0, ``beta`` 0): the
    final state is the rule's after ``length`` tokens."""
    args = _inputs("spread", seed=1, length=length)
    o, S = _values(kda_recurrent, args)
    got, S_got = _values(kda_chunked, args, chunk=64, dtype=jnp.float32)
    assert got.shape == o.shape
    _close(got, o, 2e-5)
    _close(S_got, S, 2e-5)
    assert trace.plan_args()["kda_padded_tokens"] == -length % 64
    for a, b in zip(_grads(kda_chunked, args, chunk=64, dtype=jnp.float32),
                    _grads(kda_recurrent, args)):
        _close(a, b, 3e-4)


def test_state_is_handed_over_between_chunks_blocks_and_calls(monkeypatch):
    """The second half of a sequence from the first half's final state is
    the whole sequence's second half; the chunk-local part in two blocks of
    the sequence is the part in one; and a rule that dropped the state
    between chunks would miss by a fifth of the largest output: the channels
    near ``g = 0`` carry it across (what the benchmark's seeded gate is
    for)."""
    args = _inputs("spread", seed=2)
    o, S = _values(kda_chunked, args, chunk=32, dtype=jnp.float32)
    half = lambda lo, hi: tuple(x[:, lo:hi] for x in args)
    o1, S1 = _values(kda_chunked, half(0, 64), chunk=32, dtype=jnp.float32)
    o2, S2 = _values(kda_chunked, half(64, 128), chunk=32, dtype=jnp.float32,
                     initial_state=S1)
    _close(jnp.concatenate([o1, o2], axis=1), o, 2e-5)
    _close(S2, S, 2e-5)
    dropped, _ = _values(kda_chunked, half(64, 128), chunk=32,
                         dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(dropped - o[:, 64:]))) > 0.1 * float(
        jnp.max(jnp.abs(o)))
    monkeypatch.setattr(kda, "LOCAL_TOKENS", 64)
    blocked, S_b = _values(kda_chunked, args, chunk=32, dtype=jnp.float32)
    assert trace.plan_args()["kda_local_blocks"] == 2
    np.testing.assert_array_equal(blocked, o)
    np.testing.assert_array_equal(S_b, S)
    for a, b in zip(_grads(kda_chunked, args, chunk=32, dtype=jnp.float32),
                    _grads(kda_recurrent, args)):
        _close(a, b, 3e-4)


@pytest.mark.parametrize("gates", ["spread", "bound"])
def test_bfloat16_operands_stay_near(gates):
    args = _inputs(gates, dtype=jnp.bfloat16)
    o, _ = _values(kda_recurrent, args)
    got, _ = _values(kda_chunked, args, chunk=64, dtype=jnp.bfloat16)
    _close(got, o, 2e-2)
    for a, b in zip(_grads(kda_chunked, args, chunk=64, dtype=jnp.bfloat16),
                    _grads(kda_recurrent, args)):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.linalg.norm(a - b)) <= 0.06 * float(
            jnp.linalg.norm(b))


def test_a_short_sequence_is_padded_and_a_chunk_is_a_power_of_two():
    """A sequence shorter than the chunk is padded to it like any other
    length; the chunk is a static argument of the caller's, and one that is
    no power of two (the triangular inverse doubles its blocks) is refused."""
    args = _inputs("spread", length=48)
    o, S = _values(kda_recurrent, args)
    got, S_got = _values(kda_chunked, args, chunk=64, dtype=jnp.float32)
    assert got.shape == o.shape
    _close(got, o, 2e-5)
    _close(S_got, S, 2e-5)
    assert trace.plan_args()["kda_padded_tokens"] == 16
    with pytest.raises(ValueError, match="power of two"):
        jax.eval_shape(lambda *a: kda_chunked(*a, chunk=48), *args)


def test_plan_notes_are_recorded_and_no_fallback_at_the_cell_s_shapes():
    trace.reset_build_ledger()
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    out = jax.eval_shape(
        kda_chunked, shape(1, 4096, 32, 128), shape(1, 4096, 32, 128),
        shape(1, 4096, 32, 128), f32(1, 4096, 32, 128), f32(1, 4096, 32))
    assert out[0].shape == (1, 4096, 32, 128) and out[0].dtype == jnp.float32
    assert out[1].shape == (1, 32, 128, 128)
    notes = trace.plan_args()
    assert (notes["kda_chunk"], notes["kda_sub_block"], notes["kda_heads"],
            notes["kda_chunks"], notes["kda_padded_tokens"],
            notes["kda_local_blocks"]) == (64, 16, 32, 64, 0, 4)
    assert trace.build_ledger()["fallbacks"] == []
    # the largest exponent a sub-block carries under a bound of -5: half
    # its rows' gates either way from its middle row
    assert 5 * (kda.SUB_BLOCK // 2) == 40
