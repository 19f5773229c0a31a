"""The dropless top-k expert layer (``parallel/ep.dropless_moe``): a device's
share of the experts, routed over all of them, by softmax (the defaults) or
by sigmoid scores with a selection bias, the published epsilon and a scale
(``MODES``). float32 operands, so the tolerance is float32's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import moe_combine
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


def _masked_loop(x, wr, wg, wu, wd, first, held, top_k, routing):
    """The layer written out: the routing (``routing``: ``dropless_moe``'s
    routing keywords, none for the softmax) and a loop over the held experts
    with masks."""
    logits = jnp.matmul(x, wr, precision=jax.lax.Precision.HIGHEST)
    if not routing:
        w, ids = jax.lax.top_k(jax.nn.softmax(logits, -1), top_k)
        w = w / w.sum(-1, keepdims=True)
    else:
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(scores + routing["select_bias"], top_k)
        w = jnp.take_along_axis(scores, ids, -1)
        w = routing["scale"] * w / (w.sum(-1, keepdims=True)
                                    + routing["norm_eps"])
    y = jnp.zeros_like(x)
    for e in range(first, first + held):
        mine = jnp.sum(jnp.where(ids == e, w, 0.0), -1)
        y = y + mine[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e]))
                                 @ wd[e])
    return y


def _held_share(x, wr, wg, wu, wd, first, held, top_k, routing):
    sl = slice(first, first + held)
    return ep.dropless_moe(x, wr, wg[sl], wu[sl], wd[sl], top_k=top_k,
                           first_expert=first, dtype=jnp.float32, **routing)


def _dense(x, wr, wg, wu, wd, first, held, mode="softmax"):
    return _masked_loop(x, wr, wg, wu, wd, first, held, K, MODES[mode])


def _share(x, wr, wg, wu, wd, first, held, mode="softmax"):
    return _held_share(x, wr, wg, wu, wd, first, held, K, MODES[mode])


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


def _plan_notes(traced):
    """The plan notes ``traced()`` leaves in the build ledger, tracing off."""
    from horovod_tpu import trace

    assert not trace.ACTIVE
    trace.reset_build_ledger()
    traced()
    return trace.plan_args()


def test_plan_notes_are_always_recorded():
    notes = _plan_notes(lambda: _share(*_weights(5), 0, 4))
    assert notes["moe_experts_total"] == E and notes["moe_experts_held"] == 4
    assert notes["moe_top_k"] == K
    assert notes["moe_tile_rows"] * notes["moe_tiles"] >= S * K
    # width 16 is no whole float32 tile: the per-token sums are XLA gathers
    assert notes["moe_combine_kernel"] is False
    assert notes["moe_combine_block"] == 0 and notes["moe_combine_slots"] == K
    assert notes["moe_score"] == "softmax" and notes["moe_select_bias"] is False
    notes = _plan_notes(lambda: _share(*_weights(5), 0, 4, "sigmoid_bias"))
    assert notes["moe_score"] == "sigmoid" and notes["moe_select_bias"] is True


# --------------------------------------------------------------------------
# The per-token gather-sum in place of the scatter-add, over a first tile
# sized to the expected load and small overflow tiles behind it.
# --------------------------------------------------------------------------

# The two regimes the layer runs in, at rehearsal sizes: few experts with
# many rows each (models/lfm2_moe.py: 8 of 32 held, top 4) and many experts
# with few rows each (models/qwen3_next.py: 32 of 512 held, top 10).
# (tokens, experts, held, top k, first held expert)
REGIMES = {
    "few_experts_many_rows": (256, 8, 2, 4, 2),
    "many_experts_few_rows": (64, 64, 16, 10, 16),
}
# Loads of a share whose sorted pairs are cut into a first tile and overflow
# tiles, top 4. The first three: 1024 tokens, the router sending every token
# to experts 0..3; a share of 4 of 64 experts (64 rows an expert balanced:
# tiles of twice the balanced load, 1024 rows) that holds all four computes
# 4096 pairs in four tiles; of 16 experts (256 rows an expert: a first tile
# of the balanced load and a quarter more, small overflow tiles), a share of
# experts 2..3 computes 2048 pairs in a first tile of 1024 rows and two of
# 512, and a share that nobody chose computes nothing (its first tile runs
# empty). The
# others: 4096 tokens over 16 experts, experts 0..3 held (balanced 4096
# pairs: a first tile of 5120 rows, 22 overflow tiles of 512 for the worst
# case), the router sending ``full`` tokens to experts 0..3 (four held pairs
# each) and ``single`` tokens to expert 0 and three experts held elsewhere
# (one held pair each), the rest to experts nobody holds here: the load is
# ``4 * full + single`` pairs, to the row.
# (tokens, experts, first held expert, held, rows of the first tile, of an
#  overflow tile, tiles the load computes, pairs, (full, single) or None)
LOADS = {
    "fourth_tile": (1024, 64, 0, 4, 1024, 1024, 4, 4096, None),
    "second_tile": (1024, 16, 2, 2, 1024, 512, 3, 2048, None),
    "empty": (1024, 16, 8, 4, 1536, 512, 1, 0, None),
    "one_row_under_the_first_tile": (4096, 16, 0, 4, 5120, 512, 1, 5119,
                                     (1279, 3)),
    "the_first_tile_exactly": (4096, 16, 0, 4, 5120, 512, 1, 5120, (1280, 0)),
    "one_row_past_the_first_tile": (4096, 16, 0, 4, 5120, 512, 2, 5121,
                                    (1280, 1)),
    "ends_inside_the_fourth_overflow_tile": (4096, 16, 0, 4, 5120, 512, 5,
                                             6858, (1714, 2)),
    "worst_case": (4096, 16, 0, 4, 5120, 512, 23, 16384, (4096, 0)),
}


def _routing(mode, e_total):
    """``dropless_moe``'s routing keywords at ``e_total`` experts: none for
    the softmax, and the sigmoid scores with a selection bias, the epsilon
    and a scale that models/lfm2_moe.py passes."""
    if mode == "softmax":
        return {}
    bias = np.random.default_rng(e_total).normal(size=e_total) * 0.05
    return dict(score="sigmoid", select_bias=jnp.asarray(bias, jnp.float32),
                norm_eps=1e-6, scale=1.5)


def _layer_args(s_tokens, e_total, seed, all_choose=None, logit=40.0,
                width=D):
    rng = np.random.default_rng(seed)
    arr = lambda *shape, scale=1.0: jnp.asarray(
        rng.normal(size=shape) * scale, jnp.float32)
    x, wr = arr(s_tokens, width), arr(width, e_total)
    if all_choose:
        x = jnp.abs(x)
        bias = jnp.concatenate([jnp.full((all_choose,), logit),
                                jnp.zeros(e_total - all_choose)])
        wr = jnp.ones((width, 1)) * bias[None] / width
    return (x, wr, arr(e_total, width, F, scale=0.3),
            arr(e_total, width, F, scale=0.3),
            arr(e_total, F, width, scale=0.3))


def _layer_args_of_load(s_tokens, e_total, seed, full, single, logit=40.0,
                        width=D):
    """:func:`_layer_args` with a router that sends the first ``full`` tokens
    to experts 0..3, the next ``single`` to expert 0 and the last three
    experts, and the rest to the last four: a token's first three features
    name its group and the router reads nothing else."""
    x, _, *experts = _layer_args(s_tokens, e_total, seed, width=width)
    group = np.full(s_tokens, 2)
    group[:full], group[full:full + single] = 0, 1
    x = x.at[:, :3].set(jnp.asarray(np.eye(3)[group], jnp.float32))
    chosen = np.zeros((3, e_total))
    chosen[0, :K] = chosen[1, 0] = chosen[1, -3:] = chosen[2, -K:] = logit
    wr = jnp.zeros((width, e_total)).at[:3].set(
        jnp.asarray(chosen, jnp.float32))
    return (x, wr, *experts)


def _scatter_add_layer(x, wr, wg, wu, wd, first, held, top_k, routing):
    """The layer as the parent commit computed it, all sorted pairs in one
    tile: rows gathered and masked past the load, grouped products, rows
    masked and weighted, a scatter-add by token; gradients by JAX."""
    sl = slice(first, first + held)
    weights, ids = ep.route_top_k(x, wr, top_k=top_k, **routing)
    key, sizes = ep._held_groups(ids, first, held)
    order = jnp.argsort(key, stable=True)
    valid = jnp.arange(order.size) < jnp.sum(sizes)
    xs = jnp.where(valid[:, None], x[order // top_k], 0)
    grouped = lambda a, w: jax.lax.ragged_dot(a, w, sizes)
    ys = grouped(jax.nn.silu(grouped(xs, wg[sl])) * grouped(xs, wu[sl]),
                 wd[sl])
    weight = jnp.where(valid, weights.reshape(-1)[order], 0.0)
    return jnp.zeros_like(x).at[order // top_k].add(
        jnp.where(valid[:, None], ys, 0.0) * weight[:, None])


def _layer_and_gradients(layer, args, first, held, top_k, routing):
    fn = lambda *a: layer(*a, first, held, top_k, routing)
    loss = lambda *a: jnp.sum(fn(*a) ** 2)
    return (jax.jit(fn)(*args),
            *jax.jit(jax.grad(loss, argnums=range(5)))(*args))


NAMES = "y x router gate up down".split()


def _assert_layer_and_gradients(args, first, held, top_k, routing):
    """The layer and the gradients of x, router, weights and (through the
    router, which the scale multiplies and the selection bias does not
    reach) the routing weights equal the masked loop's and the parent's
    scatter-add's."""
    got = _layer_and_gradients(_held_share, args, first, held, top_k, routing)
    for reference in (_masked_loop, _scatter_add_layer):
        want = _layer_and_gradients(reference, args, first, held, top_k,
                                    routing)
        for name, a, b in zip(NAMES, got, want):
            np.testing.assert_allclose(
                a, b, atol=2e-5 * max(float(jnp.max(jnp.abs(b))), 1.0),
                err_msg=f"{name} against {reference.__name__}")


@modes
@pytest.mark.parametrize("regime", list(REGIMES))
def test_both_regimes_equal_the_masked_loop_and_the_scatter_add(regime, mode):
    s_tokens, e_total, held, top_k, first = REGIMES[regime]
    _assert_layer_and_gradients(_layer_args(s_tokens, e_total, 11), first,
                                held, top_k, _routing(mode, e_total))


@modes
@pytest.mark.parametrize("load", list(LOADS))
def test_loads_past_one_tile_and_an_empty_load(load, mode):
    """Loads one row under the first tile, exactly at it, one row past it
    (one overflow tile), several overflow tiles on and ending inside one, and
    every token choosing held experts (the worst case); a share nobody chose
    computes nothing: the loop over overflow tiles runs as far as the load
    reaches, forward and backward."""
    (s_tokens, e_total, first, held, first_rows, over_rows, tiles_computed,
     pairs, chosen) = LOADS[load]
    # a sigmoid saturates at 40 and hands the router no gradient: at 4 the
    # first experts still score over 0.85 against the others' half
    logit = 40.0 if mode == "softmax" else 4.0
    args = (_layer_args(s_tokens, e_total, 12, all_choose=K, logit=logit)
            if chosen is None else
            _layer_args_of_load(s_tokens, e_total, 12, *chosen, logit=logit))
    routing = _routing(mode, e_total)
    _, ids = ep.route_top_k(args[0], args[1], top_k=K, **routing)
    got_pairs, _ = ep.held_load(ids, first_expert=first, experts_held=held)
    assert int(got_pairs) == pairs
    assert ep._tile_plan(s_tokens, K, held, e_total)[:2] == (
        first_rows, over_rows)
    assert int(ep._tiles_needed(got_pairs, first_rows, over_rows)) == (
        tiles_computed)
    assert tiles_computed == 1 + -(-max(pairs - first_rows, 0) // over_rows)
    _assert_layer_and_gradients(args, first, held, K, routing)
    y = _held_share(*args, first, held, K, routing)
    if chosen is None and pairs:
        assert float(jnp.min(jnp.max(jnp.abs(y), axis=-1))) > 0  # every token
    elif chosen:
        has_pair = jnp.arange(s_tokens) < sum(chosen)
        assert bool(((jnp.max(jnp.abs(y), axis=-1) > 0) == has_pair).all())
    else:
        assert float(jnp.max(jnp.abs(y))) == 0.0
    notes = _plan_notes(lambda: _held_share(*args, first, held, K, routing))
    assert notes["moe_tile_rows"] == first_rows
    assert notes["moe_overflow_rows"] == over_rows
    # tiles enough for every token choosing only held experts
    assert (first_rows + (notes["moe_tiles"] - 1) * over_rows
            >= s_tokens * min(K, held))
    assert notes["moe_tiles"] >= tiles_computed


@pytest.mark.parametrize(
    "tokens,top_k,held,total,first_rows,over_rows,tiles,block", [
        # models/qwen3_next.py in qwen3next-train-1chip: balanced 5120 pairs,
        # 160 rows an expert: rows are cheap and tiles are not
        (8192, 10, 32, 512, 10752, 10752, 8, 256),
        # models/lfm2_moe.py in lfm2moe-train-1chip: balanced 32768 pairs,
        # 4096 rows an expert: the rows follow the load
        (32768, 4, 8, 32, 40960, 4096, 23, 256),
    ])
def test_the_cells_tiles_follow_their_load_and_take_the_kernel(
        tokens, top_k, held, total, first_rows, over_rows, tiles, block):
    """At the LFM2 cell's shapes (4096 rows an expert, compute-bound) the
    first tile is what balanced routing gives the held experts and a quarter
    more and an overflow tile an eighth of it; at the hybrid cell's (160
    rows an expert, bound by the weights' read) every tile is what balanced
    routing fills twice over and eight rows an expert, as before PR 39; in
    whole 512s, with overflow tiles enough for every token choosing only
    held experts and no more; the rows of width 2048 are whole float32
    tiles, so the kernel runs at both row counts, 256 tokens a grid step,
    with as many slots as a token can hold pairs."""
    arr = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    notes = _plan_notes(lambda: jax.eval_shape(
        lambda *a: ep.dropless_moe(*a, top_k=top_k),
        jax.ShapeDtypeStruct((tokens, 2048), jnp.bfloat16),
        arr(2048, total), arr(held, 2048, 64), arr(held, 2048, 64),
        arr(held, 64, 2048)))
    assert (notes["moe_tile_rows"], notes["moe_overflow_rows"],
            notes["moe_tiles"]) == (first_rows, over_rows, tiles)
    balanced = tokens * top_k * held // total
    worst = tokens * min(top_k, held)
    whole = lambda n: -(-n // 512) * 512
    assert (first_rows, over_rows) == (
        (whole(balanced * 5 // 4), whole(balanced // 8))
        if balanced // held >= 256 else (whole(2 * balanced + 8 * held),) * 2)
    assert first_rows + (tiles - 1) * over_rows >= worst
    assert first_rows + (tiles - 2) * over_rows < worst
    assert notes["moe_combine_kernel"] is True
    from horovod_tpu import trace
    assert trace.build_ledger()["fallbacks"] == []   # no call site without it
    assert notes["moe_combine_block"] == block
    assert notes["moe_combine_slots"] == min(top_k, held)


def _scatter_add_reference(rows, pos, weight):
    """``out[t] += weight[t, c] * rows[pos[t, c]]`` written as the plain
    scatter-add over the named (token, slot) pairs, in numpy."""
    out = np.zeros((pos.shape[0], rows.shape[1]), np.float64)
    t, c = np.nonzero(pos >= 0)
    np.add.at(out, t, weight[t, c, None].astype(np.float64)
              * rows[pos[t, c]].astype(np.float64))
    return out


@pytest.mark.parametrize("width,form", [(16, "xla"), (64, "xla"),
                                        (1024, "kernel"), (2048, "kernel"),
                                        (384, "kernel"), (3584, "kernel")])
@pytest.mark.parametrize("tokens,slots,fill", [
    (64, 4, 0.3),      # some slots empty, some tokens with no pair at all
    (24, 10, 0.06),    # most tokens hold nothing, as at 32 of 512 experts
    (32, 4, 1.0),      # every pair held
])
def test_gather_sum_equals_a_scatter_add(width, form, tokens, slots, fill):
    """Random ``pos`` with unnamed slots; the rows past the load are NaN, as
    a grouped product may leave them on the chip, and must not reach the
    output. Both forms against numpy and against each other: the XLA gathers
    (widths that are no whole lanes) and the Pallas kernel (interpreted on
    the CPU), at rows of whole float32 tiles and at rows laid out on a pitch
    of whole tiles (384 is 3 sublanes of 8, 3584 is 28 of 32: the fourth
    model's width)."""
    assert (moe_combine.plan(tokens, width, jnp.float32) is None) == (
        form == "xla")
    rng = np.random.default_rng(tokens + width)
    load, n_rows = 40, 56
    rows = rng.normal(size=(n_rows, width)).astype(np.float32)
    rows[load:] = np.nan
    pos = rng.integers(0, load, (tokens, slots)).astype(np.int32)
    pos[rng.random((tokens, slots)) > fill] = -1
    if fill < 1:
        pos[: tokens // 2, slots // 2:] = -1   # slots no token of a block fills
        pos[3] = -1                            # a token with no held pair
        assert (pos < 0).all(-1).any() and (pos >= 0).any()
    else:
        assert (pos >= 0).all()
    weight = rng.normal(size=(tokens, slots)).astype(np.float32)
    got = np.asarray(jax.jit(moe_combine.gather_sum)(rows, pos, weight))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _scatter_add_reference(rows, pos, weight),
                               atol=1e-5)
    assert (got[(pos < 0).all(-1)] == 0).all()
    # the same sum in the same order: the forms differ by a fused
    # multiply-add's rounding at most
    np.testing.assert_allclose(
        got, jax.jit(moe_combine._xla)(rows, pos, weight), rtol=1e-5,
        atol=1e-6)


def test_gather_sum_of_other_rows_takes_the_gathers():
    """bfloat16 rows, or a token count no block of eight divides, are not
    the kernel's: the same sum as XLA gathers, in float32."""
    assert moe_combine.plan(64, 1024, jnp.bfloat16) is None
    assert moe_combine.plan(60, 1024, jnp.float32) is None
    assert moe_combine.plan(64, 1024 + 64, jnp.float32) is None
    assert moe_combine.plan(8192, 2048, jnp.float32) == 256
    # 28 sublanes on a pitch of 32: two buffers of 4096 and two blocks of 3584
    assert moe_combine.plan(8192, 3584, jnp.float32) == 128
    rows = jnp.arange(12 * 1024, dtype=jnp.bfloat16).reshape(12, 1024) / 64
    pos = jnp.asarray([[0, 11], [5, -1], [-1, -1], [2, 2]], jnp.int32)
    weight = jnp.asarray([[1, 2], [3, 4], [5, 6], [0.5, 0.25]], jnp.float32)
    out = moe_combine.gather_sum(rows, pos, weight)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(out, _scatter_add_reference(
        np.asarray(rows, np.float32), np.asarray(pos), np.asarray(weight)))


def _poisoned_products(real):
    """``ep._tile_products`` as the chip runs it: the rows past the last
    group hold garbage (NaN here), forward and transposed."""
    def poison(rows, sizes):
        past = jnp.arange(rows.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, rows)

    def with_activation(xs, w_gate, w_up, w_down, sizes, activation):
        @jax.custom_vjp
        def products(xs, w_gate, w_up, w_down, sizes):
            return poison(real(xs, w_gate, w_up, w_down, sizes, activation),
                          sizes)

        def fwd(xs, w_gate, w_up, w_down, sizes):
            return (products(xs, w_gate, w_up, w_down, sizes),
                    (xs, w_gate, w_up, w_down, sizes))

        def bwd(res, dys):
            *primals, sizes = res
            _, vjp = jax.vjp(lambda *a: real(*a, sizes, activation), *primals)
            dxs, *dw = vjp(dys)
            return (poison(dxs, sizes), *dw, None)

        products.defvjp(fwd, bwd)
        return products(xs, w_gate, w_up, w_down, sizes)

    return with_activation


# 512 tokens over 16 experts, top 4, by _layer_args_of_load.
# (first held expert, held, (full, single), pairs, the tile plan)
GARBAGE = {
    # experts 1..3 held: 900 pairs, inside the first tile of 1024 rows; the
    # overflow tile behind it is not reached
    "inside_the_first_tile": (1, 3, (300, 0), 900, (1024, 1024, 1)),
    # experts 0..3 held: 1603 pairs fill the first tile of 1536 rows and end
    # inside the overflow tile
    "ends_inside_an_overflow_tile": (0, 4, (400, 3), 1603, (1536, 1536, 1)),
}


@modes
@pytest.mark.parametrize("load", list(GARBAGE))
@pytest.mark.parametrize("width", [16, 1024])
def test_garbage_past_the_load_reaches_nothing(monkeypatch, mode, width, load):
    """NaN in every row past the load, in the products' result and in their
    transposed result, changes neither the layer nor any gradient: the sums
    read the rows that held pairs name and the masks that kept the rest
    harmless are gone. Both forms of the sum; a load that ends inside the
    first tile, and one that fills it and ends inside an overflow tile."""
    first, held, chosen, pairs, plan = GARBAGE[load]
    args = _layer_args_of_load(512, E, 13, *chosen, width=width,
                               logit=40.0 if mode == "softmax" else 4.0)
    routing = _routing(mode, E)
    assert ep._tile_plan(512, K, held, E) == plan
    _, ids = ep.route_top_k(args[0], args[1], top_k=K, **routing)
    assert int(ep.held_load(ids, first_expert=first,
                            experts_held=held)[0]) == pairs
    clean = _layer_and_gradients(_held_share, args, first, held, K, routing)
    monkeypatch.setattr(ep, "_tile_products",
                        _poisoned_products(ep._tile_products))
    dirty = _layer_and_gradients(_held_share, args, first, held, K, routing)
    assert float(jnp.max(jnp.abs(clean[0]))) > 0
    for name, a, b in zip(NAMES, dirty, clean):
        assert bool(jnp.isfinite(a).all()), name
        # the poisoned layer is another program to XLA: a fusion's rounding
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6 * float(jnp.max(jnp.abs(b))),
            err_msg=name)


def _row_scatters(hlo: str, width: int):
    """The ``scatter`` instructions of an HLO module's text whose result (the
    operand they add into) is a float array with ``width`` last."""
    found = []
    for line in hlo.splitlines():
        head, call, _ = line.partition(" scatter(")
        if not call:
            continue
        result = head.split("=", 1)[1].strip()     # f32[96,64]{1,0}
        dims = result[result.index("[") + 1:result.index("]")].split(",")
        if result[0] in "fb" and dims[-1] == str(width):
            found.append(line.strip())
    return found


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("width", [64, 1024])
def test_lowered_layer_scatters_no_rows(width, direction):
    """Neither direction of the lowered layer holds a scatter into an array
    of the model's width: ``y`` and ``dx`` are gather-sums (the scores'
    gradient, a scatter into ``[S, experts]``, and the weights' cotangent, a
    scalar scatter, stay). The check finds the parent's scatter-add."""
    e_total = 32
    args = _layer_args(S, e_total, 3, width=width)
    if direction == "forward":
        fn = lambda layer: lambda *a: layer(*a, 4, 4, K, {})
    else:
        fn = lambda layer: jax.grad(
            lambda *a: jnp.sum(layer(*a, 4, 4, K, {}) ** 2),
            argnums=range(5))
    hlo = lambda layer: jax.jit(fn(layer)).lower(*args).as_text(dialect="hlo")
    assert not _row_scatters(hlo(_held_share), width)
    assert _row_scatters(hlo(_scatter_add_layer), width)


# --------------------------------------------------------------------------
# Experts without a gate (``w_gate`` None, models/nemotron_h.py): two grouped
# products a tile and ``relu(.)^2`` between; with a gate, the parent's program.
# --------------------------------------------------------------------------

def _ungated_loop(x, wr, wu, wd, first, held, top_k, routing):
    """The layer written out for experts ``down(relu(up x)^2)``: the
    sigmoid-and-bias routing and a dense loop over the held experts."""
    scores = jax.nn.sigmoid(jnp.matmul(x, wr,
                                       precision=jax.lax.Precision.HIGHEST))
    _, ids = jax.lax.top_k(scores + routing["select_bias"], top_k)
    w = jnp.take_along_axis(scores, ids, -1)
    w = routing["scale"] * w / (w.sum(-1, keepdims=True)
                                + routing["norm_eps"])
    y = jnp.zeros_like(x)
    for e in range(first, first + held):
        mine = jnp.sum(jnp.where(ids == e, w, 0.0), -1)
        y = y + mine[:, None] * (jnp.square(jax.nn.relu(x @ wu[e])) @ wd[e])
    return y


def _ungated_share(x, wr, wu, wd, first, held, top_k, routing):
    sl = slice(first, first + held)
    return ep.dropless_moe(x, wr, None, wu[sl], wd[sl], top_k=top_k,
                           first_expert=first, activation=ep.relu_squared,
                           dtype=jnp.float32, **routing)


@pytest.mark.parametrize("load", ["second_tile", "empty",
                                  "ends_inside_the_fourth_overflow_tile"])
def test_ungated_experts_equal_a_dense_loop(load):
    """Values and the gradients of x, the router and both weights against
    the dense loop, under loads that the router makes uneven: three tiles,
    none, and a load that ends inside the fourth overflow tile. float32
    operands, so the tolerance is float32's, as above."""
    (s_tokens, e_total, first, held, first_rows, over_rows, tiles_computed,
     pairs, chosen) = LOADS[load]
    x, wr, _, wu, wd = (
        _layer_args(s_tokens, e_total, 14, all_choose=K, logit=4.0)
        if chosen is None else
        _layer_args_of_load(s_tokens, e_total, 14, *chosen, logit=4.0))
    routing = _routing("sigmoid_bias", e_total)
    _, ids = ep.route_top_k(x, wr, top_k=K, **routing)
    got_pairs, _ = ep.held_load(ids, first_expert=first, experts_held=held)
    assert int(got_pairs) == pairs
    assert int(ep._tiles_needed(got_pairs, first_rows, over_rows)) == (
        tiles_computed)
    both = []
    for layer in (_ungated_share, _ungated_loop):
        fn = lambda *a: layer(*a, first, held, K, routing)
        loss = lambda *a: jnp.sum(fn(*a) ** 2)
        both.append((jax.jit(fn)(x, wr, wu, wd), *jax.jit(
            jax.grad(loss, argnums=range(4)))(x, wr, wu, wd)))
    for name, a, b in zip("y x router up down".split(), *both):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * max(float(jnp.max(jnp.abs(b))), 1.0),
            err_msg=name)
    assert (float(jnp.max(jnp.abs(both[0][0]))) > 0) == bool(pairs)
    notes = _plan_notes(lambda: _ungated_share(x, wr, wu, wd, first, held, K,
                                               routing))
    assert notes["moe_gated"] is False
    assert _plan_notes(lambda: _share(*_weights(5), 0, 4))["moe_gated"] is True


def _parent_dropless_moe():
    """``dropless_moe`` as the commit before the ungated form wrote it (PR 46,
    31dec6b): three grouped products a tile and three weights through the
    custom VJP, over the helpers that commit left unchanged. The names are
    the program's, so that nothing in a lowered module tells the two apart
    but what they compute."""
    import functools

    from horovod_tpu import trace as _trace
    from horovod_tpu.ops import moe_combine as _combine

    def _tile_products(xs, w_gate, w_up, w_down, sizes):
        with jax.named_scope(_trace.SCOPE_MOE_EXPERTS):
            grouped = lambda a, w: jax.lax.ragged_dot(
                a, w, sizes, preferred_element_type=jnp.float32)
            h = jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up)
            return grouped(h.astype(w_down.dtype), w_down)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
    def _experts(x, scale, w_gate, w_up, w_down, order, pos, starts, ends,
                 plan, slots):
        def tile_sum(lo, rows):
            order_t, sizes_t = ep._tile(order, lo, rows, starts, ends)
            with jax.named_scope(_trace.SCOPE_MOE_ROUTE):
                xs = x[order_t // scale.shape[1]].astype(w_gate.dtype)
            ys = _tile_products(xs, w_gate, w_up, w_down, sizes_t)
            with jax.named_scope(_trace.SCOPE_MOE_ROUTE):
                slot_pos, slot_w = ep._token_slots(
                    pos, lo, rows, ends[-1], slots, scale)
                return _combine.gather_sum(ys, slot_pos, slot_w)

        return ep._walk_tiles(tile_sum, plan, ends[-1])

    def _experts_fwd(x, scale, w_gate, w_up, w_down, order, pos, starts, ends,
                     plan, slots):
        y = _experts(x, scale, w_gate, w_up, w_down, order, pos, starts, ends,
                     plan, slots)
        return y, (x, scale, w_gate, w_up, w_down, order, pos, starts, ends)

    def _experts_bwd(plan, slots, res, dy):
        x, scale, w_gate, w_up, w_down, order, pos, starts, ends = res
        top_k = scale.shape[1]
        f32 = lambda tree: jax.tree.map(lambda g: g.astype(jnp.float32), tree)

        def tile_grads(lo, rows):
            order_t, sizes_t = ep._tile(order, lo, rows, starts, ends)
            with jax.named_scope(_trace.SCOPE_MOE_ROUTE):
                token = order_t // top_k
                xs = x[token].astype(w_gate.dtype)
            ys, vjp = jax.vjp(
                lambda *a: _tile_products(*a, sizes_t),
                xs, w_gate, w_up, w_down)
            with jax.named_scope(_trace.SCOPE_MOE_ROUTE):
                valid = jnp.arange(rows) < jnp.sum(sizes_t)
                weight = jnp.where(valid, scale.reshape(-1)[order_t], 0.0)
                dy_rows = dy[token]
                dweight = jnp.where(valid, jnp.sum(ys * dy_rows, axis=-1), 0.0)
                dys = dy_rows * weight[:, None]
            dxs, *dw = vjp(dys)
            with jax.named_scope(_trace.SCOPE_MOE_ROUTE):
                slot_pos, slot_one = ep._token_slots(pos, lo, rows, ends[-1],
                                                     slots)
                dx = _combine.gather_sum(dxs.astype(jnp.float32), slot_pos,
                                         slot_one)
                dscale = jnp.zeros(scale.size, jnp.float32).at[order_t].add(
                    dweight).reshape(scale.shape)
            return (dx, dscale, *f32(dw))

        grads = ep._walk_tiles(tile_grads, plan, ends[-1])
        primals = (x, scale, w_gate, w_up, w_down)
        return tuple(g.astype(p.dtype) for g, p in zip(grads, primals)) + (
            None, None, None, None)

    _experts.defvjp(_experts_fwd, _experts_bwd)

    def dropless_moe(x, w_router, w_gate, w_up, w_down, *, top_k,
                     first_expert=0, norm_topk=True, score="softmax",
                     select_bias=None, norm_eps=0.0, scale=1.0,
                     dtype=jnp.bfloat16):
        s_tokens, d_model = x.shape
        e_total = w_router.shape[-1]
        e_held = w_gate.shape[0]
        plan = first, over, n_over = ep._tile_plan(s_tokens, top_k, e_held,
                                                   e_total)
        rows_in_all = first + n_over * over
        slots = min(top_k, e_held)
        with jax.named_scope(_trace.SCOPE_MOE_ROUTE):
            weights, ids = ep.route_top_k(
                x, w_router, top_k=top_k, norm_topk=norm_topk, score=score,
                select_bias=select_bias, norm_eps=norm_eps, scale=scale)
            key, sizes = ep._held_groups(ids, first_expert, e_held)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            pos = jnp.argsort(order).astype(jnp.int32)
            order = jnp.pad(order[:rows_in_all],
                            (0, max(0, rows_in_all - order.size)))
            ends = jnp.cumsum(sizes)
        with jax.named_scope(_trace.SCOPE_MOE_EXPERTS):
            w_gate, w_up, w_down = (w.astype(dtype)
                                    for w in (w_gate, w_up, w_down))
        return _experts(x, weights, w_gate, w_up, w_down, order,
                        pos.reshape(ids.shape), ends - sizes, ends, plan,
                        slots)

    return dropless_moe


# The expert layer at the four expert cells' shapes: tokens a step, width,
# experts, held, top k, expert width, and the routing their models pass.
_SIGMOID = dict(score="sigmoid", bias=True, norm_eps=1e-6, scale=1.0)
CELL_SHAPES = {
    "qwen3next-train-1chip": (8192, 2048, 512, 32, 10, 512, {}),
    "lfm2moe-train-1chip": (32768, 2048, 32, 8, 4, 1792, _SIGMOID),
    "xing4-train-1chip": (8192, 3584, 64, 8, 4, 1024,
                          {**_SIGMOID, "norm_eps": 1e-20, "scale": 2.0}),
    "keyevl-train-1chip": (16384, 2048, 128, 16, 8, 768, {}),
}
_KERNEL_BODY = __import__("re").compile(r'backend_config = "[^"]*"')


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_with_a_gate_the_lowered_program_is_the_parents(cell, monkeypatch):
    """At an expert cell's shapes the layer and its gradients lower, for the
    chip, to the module the parent's three-product layer lowers to. The
    gather-sum kernel's serialized body is left out of the comparison: it
    embeds the source lines of its callers, which moved."""
    from horovod_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "_resolve_interpret", lambda interpret: False)
    tokens, d, e_total, held, top_k, f, routing = CELL_SHAPES[cell]
    routing = dict(routing)
    biased = routing.pop("bias", False)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)

    def lowered(layer):
        def loss(x, wr, wg, wu, wd, bias):
            more = {"select_bias": bias} if biased else {}
            return layer(x, wr, wg, wu, wd, top_k=top_k, **routing,
                         **more).sum()
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).trace(
            jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16), f32(d, e_total),
            f32(held, d, f), f32(held, d, f), f32(held, f, d),
            f32(e_total)).lower(lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text and "ragged_dot" in text
        return _KERNEL_BODY.sub("", text)

    assert lowered(ep.dropless_moe) == lowered(_parent_dropless_moe())


@modes
def test_with_a_gate_the_gradients_are_the_parents_to_the_bit(mode):
    args = _weights(21)
    sl = slice(4, 12)
    routing = MODES[mode]

    def grads(layer):
        loss = lambda x, wr, wg, wu, wd: jnp.sum(layer(
            x, wr, wg[sl], wu[sl], wd[sl], top_k=K, first_expert=4,
            **routing) ** 2)
        return jax.jit(jax.value_and_grad(loss, argnums=range(5)))(*args)

    (l, g), (l0, g0) = grads(ep.dropless_moe), grads(_parent_dropless_moe())
    assert float(l) == float(l0) and float(l) > 0
    for name, a, b in zip(NAMES[1:], g, g0):
        np.testing.assert_array_equal(a, b, err_msg=name)
