"""``models/lfm2_moe.py`` against the plain reference
(``benchmark/reference/lfm2_moe.py``, which imports nothing of the program) on
seeded weights at a small size: logits, loss, every leaf's gradient and three
AdamW steps; the share test; the selection bias; the layer list; the tied
head.

Tolerances. With float32 products the program and the reference are the same
mathematics in another order (sorted grouped products against a masked loop,
flash blocks against one softmax): gaps are float32 rounding, measured at most
2.4e-7 of logits spread over 2.0 and 5e-7 of a leaf's gradient norm; the
limits are some ten times that. With the model's bfloat16 products every
operand is rounded to 2^-9 relative, and a top-k choice near a tie flips (the
router's product is float32, but its input is the bfloat16 residual stream):
measured 0.2% of the logits' spread and 1.4% of a leaf's gradient norm; the
limits are 2% and 15%, which a dropped layer, a wrong mask, a wrong head
grouping or a left-out bias exceeds by far."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import lfm2_moe as family  # noqa: E402
from benchmark.reference import lfm2_moe as reference  # noqa: E402
from benchmark.reference import optim  # noqa: E402
from benchmark.weights import make_params  # noqa: E402
from horovod_tpu.models import lfm2_moe as lm  # noqa: E402
from horovod_tpu.parallel import ep  # noqa: E402

CFG = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 5,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
    "num_dense_layers": 1, "num_attention_heads": 4, "num_key_value_heads": 2,
    "rope_theta": 1000000, "conv_L_cache": 3, "norm_eps": 1e-5,
    "num_experts": 4, "num_experts_routed": 8, "first_expert_held": 2,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 251,
    "initializer_range": 0.02, "expert_bias_std": 0.01, "train": {},
}
OPT = {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9, "b2": 0.999,
       "eps": 1e-8, "weight_decay": 1e-4}
B, T = 2, 64


@functools.lru_cache(maxsize=None)
def _inputs(seed, over=()):
    """Weights and a batch of ``CFG`` with ``over`` from ``seed``: made once
    a process (the tests share them; the one test whose step donates its
    input copies)."""
    cfg = {**CFG, **dict(over)}
    params = make_params(family.param_spec(cfg), seed)
    # norm weights start at one: move every vector off its initial value so
    # that a leaf the program ignores shows (the bias keeps its own draw)
    keys = jax.random.split(jax.random.PRNGKey(seed), 400)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    leaves = [x + 0.05 * jax.random.normal(k, x.shape)
              if x.ndim == 1 and "expert_bias" not in jax.tree_util.keystr(p)
              else x for (p, x), k in zip(flat, keys)]
    params = jax.tree.unflatten(tree, leaves)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg["vocab_size"], (B, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg["vocab_size"], (B, T)), jnp.int32)
    return cfg, params, tokens, labels


def _setup(dtype, seed=11, **over):
    cfg, params, tokens, labels = _inputs(seed, tuple(sorted(over.items())))
    model = lm.Lfm2MoeLM(dataclasses.replace(
        family.model_config(cfg), dtype=dtype))
    return cfg, model, params, tokens, labels


def _loss(model):
    def f(p, tokens, labels):
        logits = model.apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
    return f


def _jit(f, *args):
    """``f(*args)`` as ONE compiled program (run operation by operation the
    whole-model tests of this file took twice as long) that rounds where the
    operation-by-operation run does: no wider bfloat16 intermediates inside
    a fusion, so the tolerances measured on that run hold. It runs once, so
    LLVM's expensive passes cost more than they save (same bits without)."""
    return jax.jit(f).lower(*args).compile(compiler_options={
        "xla_allow_excess_precision": False,
        "xla_llvm_disable_expensive_passes": True})(*args)


def test_parameter_tree_is_the_benchmarks_spec():
    cfg, model, params, tokens, _ = _setup(jnp.float32)
    made = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                             tokens)["params"])
    assert (jax.tree.map(lambda x: x.shape, made)
            == jax.tree.map(lambda x: x.shape, params))
    assert "lm_head" not in params            # the head is the embedding


def test_layers_follow_the_list_of_kinds():
    cfg, model, params, *_ = _setup(jnp.float32)
    mixers = ["self_attn" if "self_attn" in params[f"layer_{i}"] else "conv"
              for i in range(5)]
    assert mixers == ["conv", "self_attn", "conv", "conv", "conv"]
    ffn = [sorted(params[f"layer_{i}"]["feed_forward"]) for i in range(5)]
    assert ffn[0] == ["w1", "w2", "w3"]                     # dense first
    assert all(f == ["expert_bias", "experts", "router"] for f in ffn[1:])
    # another list, another stack: nothing assumes an interval
    other = dataclasses.replace(
        model.cfg, layer_types=("full_attention", "full_attention", "conv"),
        n_dense_layers=2)
    made = jax.eval_shape(lambda: lm.Lfm2MoeLM(other).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert ["self_attn" in made[f"layer_{i}"] for i in range(3)] == [
        True, True, False]
    assert ["w1" in made[f"layer_{i}"]["feed_forward"]
            for i in range(3)] == [True, True, False]
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(model.cfg, layer_types=("conv", "mamba"))


@pytest.mark.parametrize("dtype,logit_tol,grad_tol", [
    (jnp.float32, 3e-6, 1e-5), (jnp.bfloat16, 2e-2, 0.15),
])
def test_program_equals_reference(dtype, logit_tol, grad_tol):
    cfg, model, params, tokens, labels = _setup(dtype)
    want = _jit(lambda p: reference.logits(p, tokens, cfg), params)
    got = _jit(lambda p: model.apply({"params": p}, tokens), params)
    assert got.dtype == jnp.float32
    spread = float(jnp.max(want) - jnp.min(want))
    assert float(jnp.max(jnp.abs(got - want))) <= logit_tol * spread

    l_ref, g_ref = _jit(jax.value_and_grad(
        lambda p: reference.loss(p, (tokens, labels), cfg)), params)
    l, g = _jit(jax.value_and_grad(_loss(model)), params, tokens, labels)
    assert abs(float(l) - float(l_ref)) <= logit_tol * abs(float(l_ref))
    flat_ref = jax.tree_util.tree_leaves_with_path(g_ref)
    norms = [float(jnp.linalg.norm(x)) for _, x in flat_ref]
    floor = float(np.median(norms))
    unread = []
    for (path, a), b, n in zip(flat_ref, jax.tree.leaves(g), norms):
        gap = float(jnp.linalg.norm(b - a))
        assert gap <= grad_tol * max(n, floor), (
            jax.tree_util.keystr(path), gap)
        if n == 0:
            unread.append(jax.tree_util.keystr(path))
            assert float(jnp.max(jnp.abs(b))) == 0.0
    # the selection bias enters only the choice: exactly zero, in both
    assert unread == [f"['layer_{i}']['feed_forward']['expert_bias']"
                      for i in range(1, 5)]


def test_three_adamw_steps_equal_the_reference():
    """The program's step (optax's AdamW on the model's gradient) and the
    reference's (``reference/optim.py`` on its own gradient) from the same
    weights, three steps on three batches, float32 products: every leaf's
    change agrees in norm to float32 rounding (Adam's first steps divide a
    gradient by its own size, so an element whose gradient is near zero
    moves by a whole step on rounding alone: measured at most 1.0e-4 of the
    leaf's change, limit ten times that), and the bias moves by the
    decoupled decay alone."""
    cfg, model, params, tokens, labels = _setup(jnp.float32)
    rng = np.random.default_rng(3)
    batches = [(jnp.asarray(rng.integers(0, 251, (B, T)), jnp.int32),
                jnp.asarray(rng.integers(0, 251, (B, T)), jnp.int32))
               for _ in range(3)]
    tx = optax.adamw(OPT["learning_rate"], b1=OPT["b1"], b2=OPT["b2"],
                     eps=OPT["eps"], weight_decay=OPT["weight_decay"])
    p, state = params, tx.init(params)
    q = jax.tree.map(jnp.copy, params)
    ref_state = optim.init(OPT, q)
    grad = jax.jit(jax.grad(_loss(model)))
    grad_ref = jax.jit(jax.grad(lambda w, batch: reference.loss(w, batch, cfg)))
    for batch in batches:
        g = grad(p, *batch)
        updates, state = tx.update(g, state, p)
        p = optax.apply_updates(p, updates)
        g_ref = grad_ref(q, batch)
        q, ref_state = optim.update(OPT, q, g_ref, ref_state)
    flat = jax.tree_util.tree_leaves_with_path(params)
    for (path, w0), a, b in zip(flat, jax.tree.leaves(p), jax.tree.leaves(q)):
        moved = float(jnp.linalg.norm(b - w0))
        gap = float(jnp.linalg.norm(a - b))
        assert gap <= 1e-3 * moved, jax.tree_util.keystr(path)
    bias0 = params["layer_2"]["feed_forward"]["expert_bias"]
    decay = (1 - OPT["learning_rate"] * OPT["weight_decay"]) ** 3
    np.testing.assert_allclose(p["layer_2"]["feed_forward"]["expert_bias"],
                               bias0 * decay, rtol=1e-6)
    assert float(jnp.max(jnp.abs(bias0))) > 0


def _moe(held, first, **kw):
    return lm.SparseMoe(n_experts=8, experts_held=held, top_k=2,
                        expert_dim=32, first_expert=first, dtype=jnp.float32,
                        **kw)


def _moe_params(seed=5):
    cfg = {**CFG, "num_experts": 8, "first_expert_held": 0}
    params = make_params(family.param_spec(cfg), seed)["layer_1"][
        "feed_forward"]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, 48, cfg["hidden_size"])), jnp.float32)
    return cfg, params, x


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """The guide's share test: the routed parts of the four shares of the
    experts (this layer has nothing that every chip computes alike to count
    once) add up to what the uncut reference gives for the whole layer."""
    cfg, params, x = _moe_params()
    whole = reference._moe(x, params, cfg, "highest")
    cut = lambda first: {**params, "experts": jax.tree.map(
        lambda w: w[first:first + 2], params["experts"])}
    parts = [_moe(2, f).apply({"params": cut(f)}, x) for f in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(parts), whole, atol=2e-6)
    np.testing.assert_allclose(_moe(8, 0).apply({"params": params}, x), whole,
                               atol=2e-6)
    # a token none of whose experts is held gets nothing from this share
    _, ids = reference.route(x[0], params, cfg)
    alone = ~np.isin(np.asarray(ids), (0, 1)).any(axis=-1)
    assert alone.any()
    assert float(jnp.max(jnp.abs(parts[0][0][alone]))) == 0.0


def test_the_bias_decides_the_choice_and_not_the_weight():
    """A bias large enough to change choices: the layer with it equals the
    reference with it; leaving it out of the choice, or weighing by score
    plus bias, gives another result by far."""
    cfg, params, x = _moe_params()
    bias = jnp.asarray([0.3, -0.3, 0.2, 0.0, -0.2, 0.1, 0.0, -0.1])
    params = {**params, "expert_bias": bias}
    want = reference._moe(x, params, cfg, "highest")
    got = _moe(8, 0).apply({"params": params}, x)
    np.testing.assert_allclose(got, want, atol=2e-6)
    size = float(jnp.max(jnp.abs(want)))

    _, with_bias = reference.route(x[0], params, cfg)
    _, without = reference.route(x[0], {**params,
                                        "expert_bias": jnp.zeros(8)}, cfg)
    moved = (np.sort(with_bias, -1) != np.sort(without, -1)).any(-1)
    assert moved.mean() > 0.3
    left_out = _moe(8, 0, use_expert_bias=False).apply(
        {"params": {k: v for k, v in params.items() if k != "expert_bias"}}, x)
    assert float(jnp.max(jnp.abs(left_out - want))) > 0.1 * size

    # weighing by the biased score: the same choice, other weights
    flat = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(flat @ params["router"]["kernel"])
    biased, ids = jax.lax.top_k(scores + bias, 2)
    np.testing.assert_array_equal(ids, with_bias)
    w_good, _ = ep.route_top_k(flat, params["router"]["kernel"], top_k=2,
                               score="sigmoid", select_bias=bias,
                               norm_eps=1e-6)
    w_bad = biased / (biased.sum(-1, keepdims=True) + 1e-6)
    assert float(jnp.max(jnp.abs(w_bad - w_good))) > 0.05
    np.testing.assert_allclose(
        w_good, reference.route(flat, params, cfg)[0], atol=1e-6)


def test_weights_are_normalised_with_the_published_epsilon():
    """``g / (sum(g) + 1e-6)``: the weights sum to ``sum / (sum + 1e-6)``,
    which float32 tells from one where the chosen scores are small."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    w_router = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    # all-negative tokens on an all-positive router: logits about -12,
    # scores about 6e-6
    for tokens, w_router in ((x, w_router),
                             (-1.2 * jnp.abs(x), jnp.abs(w_router))):
        scores = jax.nn.sigmoid(jnp.matmul(
            tokens, w_router, precision=jax.lax.Precision.HIGHEST))
        top, ids = jax.lax.top_k(scores, 2)
        total = top.sum(-1, keepdims=True)
        w, got = ep.route_top_k(tokens, w_router, top_k=2, score="sigmoid",
                                norm_eps=1e-6)
        np.testing.assert_array_equal(got, ids)
        np.testing.assert_allclose(w, top / (total + 1e-6), rtol=1e-6)
        plain, _ = ep.route_top_k(tokens, w_router, top_k=2, score="sigmoid")
        np.testing.assert_allclose(plain.sum(-1), 1.0, atol=1e-6)
    assert float(jnp.min(w.sum(-1))) < 0.99    # the epsilon is no rounding
    scaled, _ = ep.route_top_k(tokens, w_router, top_k=2, score="sigmoid",
                               norm_eps=1e-6, scale=2.5)
    base, _ = ep.route_top_k(tokens, w_router, top_k=2, score="sigmoid",
                             norm_eps=1e-6)
    np.testing.assert_allclose(scaled, 2.5 * base, rtol=1e-6)


def test_head_is_the_embeddings_transpose():
    cfg, model, params, tokens, labels = _setup(jnp.float32)
    _, state = model.apply({"params": params}, tokens,
                           capture_intermediates=lambda m, _: m.name == "norm")
    x = state["intermediates"]["norm"]["__call__"][0]
    want = jnp.einsum("btd,vd->btv", x, params["embed_tokens"]["embedding"],
                      precision="highest")
    np.testing.assert_allclose(model.apply({"params": params}, tokens), want,
                               atol=1e-5)
    # the table's gradient holds both uses: the lookup's rows and the head's
    g = _jit(jax.grad(_loss(model)), params, tokens, labels)[
        "embed_tokens"]["embedding"]
    unseen = np.setdiff1d(np.arange(cfg["vocab_size"]), np.asarray(tokens))
    assert unseen.size and float(jnp.min(jnp.linalg.norm(
        g[unseen], axis=-1))) > 0            # rows no token looked up
    # the backward's barrier changes no value
    plain = jax.grad(lambda a, e: jnp.sum(lm._head_product(a, e) ** 2),
                     argnums=(0, 1))(x, params["embed_tokens"]["embedding"])
    tied = jax.grad(lambda a, e: jnp.sum(lm.tied_head(a, e) ** 2),
                    argnums=(0, 1))(x, params["embed_tokens"]["embedding"])
    for a, b in zip(plain, tied):
        np.testing.assert_array_equal(a, b)


def test_short_convolution_against_a_loop():
    """The two gates and the three causal taps, written out per position and
    channel."""
    cfg, model, params, *_ = _setup(jnp.float32)
    p = params["layer_0"]["conv"]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 10, cfg["hidden_size"])), jnp.float32)
    got = lm.ShortConv(dtype=jnp.float32).apply({"params": p}, x)[0]
    xs = np.asarray(x[0], np.float64)
    w_in, w_out, k = (np.asarray(p[n]["kernel"], np.float64)
                      for n in ("in_proj", "out_proj", "conv"))
    d = xs.shape[1]
    bcu = xs @ w_in
    gate_in, gate_out, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    bu = gate_in * u
    out = np.zeros_like(xs)
    for t in range(xs.shape[0]):
        s = sum(k[j] * bu[t - 2 + j] for j in range(3) if t - 2 + j >= 0)
        out[t] = gate_out[t] * s
    np.testing.assert_allclose(got, out @ w_out, atol=1e-6)


def test_trains_through_make_train_step():
    import horovod_tpu.jax as hvd

    cfg, model, params, tokens, labels = _setup(jnp.bfloat16)
    mesh = hvd.build_mesh({"data": 1}, devices=jax.devices()[:1])
    tx = hvd.DistributedOptimizer(optax.adamw(3e-3))
    loss_fn = lambda p, batch: _loss(model)(p, *batch)
    step = hvd.make_train_step(loss_fn, tx, mesh)
    params = jax.tree.map(jnp.copy, params)       # the step donates them
    state = tx.init(params)
    losses = []
    for _ in range(8):
        params, state, loss = step(params, state, (tokens, labels))
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.05


def test_scopes_and_plan_notes():
    from horovod_tpu import trace

    cfg, model, params, tokens, labels = _setup(jnp.bfloat16)
    assert not trace.ACTIVE         # the notes are recorded all the same
    trace.reset_build_ledger()
    text = jax.jit(jax.grad(_loss(model))).lower(
        params, tokens, labels).as_text(debug_info=True)
    notes = trace.plan_args()
    for scope in trace.LFM2_SCOPES + ("lm_head",):
        assert scope in text, scope
    assert set(trace.LFM2_SCOPES) & set(trace.MODEL_SCOPES) == {
        trace.SCOPE_MOE_ROUTE, trace.SCOPE_MOE_EXPERTS}
    assert notes["short_conv_taps"] == 3
    assert notes["moe_score"] == "sigmoid" and notes["moe_select_bias"] is True
    assert notes["moe_experts_total"] == 8 and notes["moe_experts_held"] == 4
    load = np.asarray(lm.expert_load(model, params, tokens))
    notes = trace.plan_args()
    assert load.shape == (4, 3)               # the four sparse layers
    assert notes["moe_pairs_held"] == list(load[:, 0])
    assert notes["moe_largest_load"] == list(load[:, 1])
    # a rehearsal's first tile holds its worst case: one tile, whatever falls
    assert notes["moe_tiles_computed"] == list(load[:, 2]) == [1] * 4
    # uniform routing: about k * held / routed of B * T * k pairs, here half
    assert (load[:, 0] > 0.25 * B * T * 2).all()
    assert (load[:, 0] < 0.75 * B * T * 2).all()


def test_expert_load_counts_the_tiles_a_batch_computes():
    """``expert_load()`` reports, a sparse layer, the tiles of rows that
    batch computes, by the arithmetic ``dropless_moe``'s loop uses: 1 where
    the seeded router's load lies inside the first tile; with the selection
    bias sending every token's first choice to a held expert the load passes
    the first tile by part of an overflow tile, and with both choices held
    it is the worst case."""
    from horovod_tpu import trace

    cfg, model, params, _, _ = _setup(jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg["vocab_size"], (2, 1024)), jnp.int32)
    s_tokens, top_k = tokens.size, cfg["num_experts_per_tok"]
    held, total = cfg["num_experts"], cfg["num_experts_routed"]
    first, over, n_over = ep._tile_plan(s_tokens, top_k, held, total)
    assert (first, over, n_over) == (2560, 512, 3)    # balanced 2048 pairs

    def biased(chosen):
        """The parameters with a selection bias no score outweighs on the
        experts ``chosen``."""
        flat, tree = jax.tree_util.tree_flatten_with_path(params)
        return jax.tree.unflatten(tree, [
            x.at[jnp.asarray(chosen)].set(10.0) if chosen
            and "expert_bias" in jax.tree_util.keystr(p) else x
            for p, x in flat])

    lo = cfg["first_expert_held"]
    for chosen in ([], [lo], [lo, lo + 1]):
        load = np.asarray(lm.expert_load(model, biased(chosen), tokens))
        assert load.shape == (4, 3)
        pairs, tiles = load[:, 0], load[:, 2]
        assert list(tiles) == list(1 + -(-np.maximum(pairs - first, 0) // over))
        assert list(tiles) == [int(ep._tiles_needed(p, first, over))
                               for p in pairs]
        assert trace.plan_args()["moe_tiles_computed"] == list(tiles)
        if not chosen:      # the seeded router alone: within 35% of balanced
            assert (tiles == 1).sum() >= 3 and (tiles <= 2).all(), load
        elif len(chosen) == 1:      # 2048 pairs and about 3 / 7 of 2048 more
            assert (pairs > first).all() and (tiles >= 2).all(), load
            assert (tiles < 1 + n_over).all(), load
        else:                       # every pair held: the worst case
            assert (pairs == s_tokens * top_k).all(), load
            assert (tiles == 1 + n_over).all(), load
    # every note the layer and the counter leave is in the documents' list
    with open(os.path.join(ROOT, "docs", "timeline.md")) as f:
        listed = f.read()
    for note in trace.plan_args():
        if note.startswith("moe_"):
            assert f"`{note}`" in listed, note
    assert {"moe_overflow_rows", "moe_tiles_computed"} <= set(
        trace.plan_args())
