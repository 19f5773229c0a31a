"""The dropless top-k expert layer (``parallel/ep.dropless_moe``): a device's
share of the experts, routed over all of them. float32 operands, so the
tolerance is float32's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import ep

S, D, E, F, K = 96, 16, 16, 8, 4


def _weights(seed=0):
    rng = np.random.default_rng(seed)
    arr = lambda *shape, scale=1.0: jnp.asarray(
        rng.normal(size=shape) * scale, jnp.float32)
    return (arr(S, D), arr(D, E), arr(E, D, F, scale=0.3),
            arr(E, D, F, scale=0.3), arr(E, F, D, scale=0.3))


def _dense(x, wr, wg, wu, wd, first, held):
    """The layer written out: a loop over the held experts with masks."""
    w, ids = jax.lax.top_k(jax.nn.softmax(x @ wr, -1), K)
    w = w / w.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(first, first + held):
        mine = jnp.sum(jnp.where(ids == e, w, 0.0), -1)
        y = y + mine[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e]))
                                 @ wd[e])
    return y


def _share(x, wr, wg, wu, wd, first, held, **kw):
    sl = slice(first, first + held)
    return ep.dropless_moe(x, wr, wg[sl], wu[sl], wd[sl], top_k=K,
                           first_expert=first, dtype=jnp.float32, **kw)


@pytest.mark.parametrize("shares", [1, 2, 4, 16])
def test_shares_add_up_to_the_uncut_layer(shares):
    """Every chip's part of the result, summed, is the whole layer: 16
    experts in ``shares`` shares (the shared expert is the model's, counted
    once there: tests/test_qwen3_next.py)."""
    args = _weights()
    whole = _dense(*args, 0, E)
    held = E // shares
    parts = sum(_share(*args, i * held, held) for i in range(shares))
    np.testing.assert_allclose(parts, whole, atol=2e-5)


@pytest.mark.parametrize("first", [0, 4, 12])
def test_one_share_equals_the_masked_loop(first):
    args = _weights(1)
    np.testing.assert_allclose(_share(*args, first, 4),
                               _dense(*args, first, 4), atol=2e-5)


def test_gradients_equal_the_masked_loop():
    args = _weights(2)
    loss = lambda f: lambda *a: jnp.sum(f(*a, 4, 4) ** 2)
    want = jax.grad(loss(_dense), argnums=range(5))(*args)
    got = jax.grad(loss(_share), argnums=range(5))(*args)
    for name, a, b in zip("x router gate up down".split(), got, want):
        np.testing.assert_allclose(
            a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7, err_msg=name)


def _all_choose(first_k):
    """A router under which every token chooses experts 0 .. K-1."""
    x, _, wg, wu, wd = _weights(3)
    x = jnp.abs(x)
    bias = jnp.concatenate([jnp.full((first_k,), 40.0), jnp.zeros(E - first_k)])
    return x, jnp.ones((D, 1)) * bias[None] / D, wg, wu, wd


def test_no_token_dropped_when_every_token_chooses_the_same_experts():
    """Full imbalance: all S * K pairs fall on the 4 held experts, 8 times
    what balanced routing gives them, through several tiles of rows."""
    args = _all_choose(K)
    w, ids = ep.route_top_k(args[0], args[1], top_k=K)
    assert set(np.asarray(ids).ravel()) == set(range(K))
    pairs, largest = ep.held_load(ids, first_expert=0, experts_held=4)
    assert int(pairs) == S * K and int(largest) == S
    got = _share(*args, 0, 4)
    np.testing.assert_allclose(got, _dense(*args, 0, 4), atol=2e-5)
    assert float(jnp.min(jnp.max(jnp.abs(got), axis=-1))) > 0  # every token


def test_tokens_with_no_held_expert_get_nothing_from_this_share():
    args = _all_choose(K)
    y = _share(*args, 8, 4)   # experts 8..11: nobody chose them
    assert float(jnp.max(jnp.abs(y))) == 0.0
    pairs, largest = ep.held_load(
        ep.route_top_k(args[0], args[1], top_k=K)[1], first_expert=8,
        experts_held=4)
    assert int(pairs) == 0 and int(largest) == 0


def test_router_is_float32_whatever_the_tokens_are():
    x, wr = _weights(4)[:2]
    w32, ids32 = ep.route_top_k(x, wr, top_k=K)
    w16, ids16 = ep.route_top_k(x.astype(jnp.bfloat16).astype(jnp.float32),
                                wr, top_k=K)
    assert w32.dtype == jnp.float32 and ids32.dtype == jnp.int32
    np.testing.assert_allclose(jnp.sum(w32, -1), 1.0, atol=1e-6)
    # the same tokens give the same choice: the product is not rounded again
    w_again, ids_again = ep.route_top_k(x.astype(jnp.bfloat16), wr, top_k=K)
    np.testing.assert_array_equal(ids_again, ids16)


def test_plan_notes_when_tracing_is_armed(monkeypatch):
    from horovod_tpu import trace

    notes = {}

    class Tap:
        def note_plan(self, **kw):
            notes.update(kw)

    monkeypatch.setattr(trace, "ACTIVE", True)
    monkeypatch.setattr(trace, "TAP", Tap())
    _share(*_weights(5), 0, 4)
    assert notes["moe_experts_total"] == E and notes["moe_experts_held"] == 4
    assert notes["moe_top_k"] == K
    assert notes["moe_tile_rows"] * notes["moe_tiles"] >= S * K
