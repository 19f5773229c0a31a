"""The dropless top-k expert layer (``parallel/ep.dropless_moe``): a device's
share of the experts, routed over all of them, by softmax (the defaults) or
by sigmoid scores with a selection bias, the published epsilon and a scale
(``MODES``). float32 operands, so the tolerance is float32's."""

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


BIAS = jnp.asarray(np.random.default_rng(9).normal(size=E) * 0.2, jnp.float32)
# the routing's keyword arguments: none (softmax, no bias, no epsilon, scale
# 1), and the sigmoid-and-bias mode of models/lfm2_moe.py
MODES = {
    "softmax": {},
    "sigmoid_bias": dict(score="sigmoid", select_bias=BIAS, norm_eps=1e-6,
                         scale=1.5),
}
modes = pytest.mark.parametrize("mode", list(MODES))


def _route(x, wr, mode):
    """The routing written out."""
    logits = jnp.matmul(x, wr, precision=jax.lax.Precision.HIGHEST)
    if mode == "softmax":
        w, ids = jax.lax.top_k(jax.nn.softmax(logits, -1), K)
        return w / w.sum(-1, keepdims=True), ids
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + BIAS, K)
    w = jnp.take_along_axis(scores, ids, -1)
    return 1.5 * (w / (w.sum(-1, keepdims=True) + 1e-6)), ids


def _dense(x, wr, wg, wu, wd, first, held, mode="softmax"):
    """The layer written out: a loop over the held experts with masks."""
    w, ids = _route(x, wr, mode)
    y = jnp.zeros_like(x)
    for e in range(first, first + held):
        mine = jnp.sum(jnp.where(ids == e, w, 0.0), -1)
        y = y + mine[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e]))
                                 @ wd[e])
    return y


def _share(x, wr, wg, wu, wd, first, held, mode="softmax"):
    sl = slice(first, first + held)
    return ep.dropless_moe(x, wr, wg[sl], wu[sl], wd[sl], top_k=K,
                           first_expert=first, dtype=jnp.float32,
                           **MODES[mode])


@modes
@pytest.mark.parametrize("shares", [1, 2, 4, 16])
def test_shares_add_up_to_the_uncut_layer(shares, mode):
    """Every chip's part of the result, summed, is the whole layer: 16
    experts in ``shares`` shares (the shared expert is the model's, counted
    once there: tests/test_qwen3_next.py)."""
    args = _weights()
    whole = _dense(*args, 0, E, mode)
    held = E // shares
    parts = sum(_share(*args, i * held, held, mode) for i in range(shares))
    np.testing.assert_allclose(parts, whole, atol=2e-5)


@modes
@pytest.mark.parametrize("first", [0, 4, 12])
def test_one_share_equals_the_masked_loop(first, mode):
    args = _weights(1)
    np.testing.assert_allclose(_share(*args, first, 4, mode),
                               _dense(*args, first, 4, mode), atol=2e-5)


@modes
def test_gradients_equal_the_masked_loop(mode):
    args = _weights(2)
    loss = lambda f: lambda *a: jnp.sum(f(*a, 4, 4, mode) ** 2)
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


def test_defaults_are_the_softmax_routing_to_the_bit():
    """``route_top_k`` and ``dropless_moe`` without the new keyword arguments
    compute what they computed before those came: the same operations in the
    same order (no epsilon added, no scale multiplied), so the values are
    equal and not merely close; spelling the defaults out changes nothing."""
    x, wr, wg, wu, wd = _weights(6)
    logits = jnp.matmul(x, wr, precision=jax.lax.Precision.HIGHEST)
    w_old, ids_old = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
    w_old = w_old / jnp.sum(w_old, axis=-1, keepdims=True)
    spelled = dict(score="softmax", select_bias=None, norm_eps=0.0, scale=1.0)
    for kw in ({}, spelled):
        w, ids = ep.route_top_k(x, wr, top_k=K, **kw)
        np.testing.assert_array_equal(w, w_old)
        np.testing.assert_array_equal(ids, ids_old)
    raw, _ = ep.route_top_k(x, wr, top_k=K, norm_topk=False)
    np.testing.assert_array_equal(
        raw, jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)[0])
    layer = lambda **kw: ep.dropless_moe(
        x, wr, wg[:4], wu[:4], wd[:4], top_k=K, dtype=jnp.float32, **kw)
    np.testing.assert_array_equal(layer(), layer(**spelled))
    with pytest.raises(ValueError, match="softmax"):
        ep.route_top_k(x, wr, top_k=K, score="tanh")


def test_selection_bias_takes_no_gradient_and_changes_the_choice():
    x, wr, wg, wu, wd = _weights(7)
    kw = {k: v for k, v in MODES["sigmoid_bias"].items() if k != "select_bias"}
    layer = lambda bias: ep.dropless_moe(
        x, wr, wg[:4], wu[:4], wd[:4], top_k=K, dtype=jnp.float32,
        select_bias=bias, **kw)
    g = jax.grad(lambda b: jnp.sum(layer(b) ** 2))(BIAS)
    assert g.shape == BIAS.shape and float(jnp.max(jnp.abs(g))) == 0.0
    _, with_bias = ep.route_top_k(x, wr, top_k=K, select_bias=BIAS, **kw)
    _, without = ep.route_top_k(x, wr, top_k=K, **kw)
    assert (np.sort(with_bias, -1) != np.sort(without, -1)).any(-1).mean() > 0.2
    assert float(jnp.max(jnp.abs(layer(BIAS) - layer(None)))) > 1e-3


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
    assert notes["moe_score"] == "softmax" and notes["moe_select_bias"] is False
    _share(*_weights(5), 0, 4, "sigmoid_bias")
    assert notes["moe_score"] == "sigmoid" and notes["moe_select_bias"] is True
