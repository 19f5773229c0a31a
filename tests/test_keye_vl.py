"""``models/keye_vl.py`` against the plain reference
(``benchmark/reference/keye_vl.py``, which imports nothing of the program) on
seeded weights at a small size at which the selection BINDS (top 16 of 128
positions, three different position rows so that ``mrope_section`` is
exercised): logits, both loss terms and every leaf's gradient; the two terms
feed disjoint leaves; a dense-attention model, a model without ``L_I`` and
bfloat16 where float32 is stated each fail; the share test; the multimodal
rotary angles against numbers written out here; the train step; scopes and
plan notes.

Tolerances. With float32 products the program and the reference are the same
mathematics in another order (a radix search against ``top_k``'s k-th value,
flash blocks under a mask against one masked softmax, the objective's analytic
gradient against autodiff, sorted grouped products against a masked loop):
gaps are float32 rounding, measured 2.3e-7 of the logits' norm, 1e-7 of either
loss term and at most 9e-7 of a leaf's gradient norm; the limits are some
twenty times that. With the model's bfloat16 products every operand is
rounded to 2^-9 relative, a routing choice near a tie flips and so does a key
near a query's 16th score, and at 16 keys a query one flipped key moves that
query's logits by a tenth of their spread: the logits are compared by the norm
of the gap over the norm (measured 7.5%; the limit is 15%) and a leaf's
gradient by the same (measured at most 24%, the indexer's ``wk``; the limit is
45%). Attention over every causal key moves the logits by 45% of their norm
in float32, and a model without ``L_I`` leaves the indexer's leaves without
a gradient (a gap of 0.015 to 1 against the float32 limit of 3e-5)."""

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

from benchmark.families import keye_vl as family  # noqa: E402
from benchmark.reference import keye_vl as reference  # noqa: E402
from benchmark.weights import make_params  # noqa: E402
from horovod_tpu.models import keye_vl as km  # noqa: E402

CFG = {
    "hidden_size": 64, "moe_intermediate_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 10000, "rope_scaling": {"mrope_section": [2, 3, 3]},
    "rms_norm_eps": 1e-6, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "topk": 16},
    "num_experts": 4, "num_experts_routed": 8, "first_expert_held": 2,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "vocab_size": 251,
    "initializer_range": 0.02, "train": {},
}
B, T = 2, 128
INDEXER = "['indexer']"


@functools.lru_cache(maxsize=None)
def _inputs(seed):
    """Weights and a batch of ``CFG`` from ``seed``: made once a process (the
    tests share them; the one test whose step donates its input copies)."""
    params = make_params(family.param_spec(CFG), seed)
    # norm weights start at one and the indexer's LayerNorm bias at zero: move
    # every vector off its initial value so that a leaf the program ignores
    # shows
    keys = jax.random.split(jax.random.PRNGKey(seed), 400)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    leaves = [x + 0.05 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
              for (_, x), k in zip(flat, keys)]
    params = jax.tree.unflatten(tree, leaves)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, CFG["vocab_size"], (B, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, CFG["vocab_size"], (B, T)), jnp.int32)
    # an image of 5 x 7 patches in the middle of the text: three DIFFERENT rows
    t = np.arange(T)
    image = (t >= 40) & (t < 75)
    rows = np.stack([np.where(image, 40, np.where(t >= 75, t - 28, t)),
                     np.where(image, 40 + (t - 40) // 7,
                              np.where(t >= 75, t - 28, t)),
                     np.where(image, 40 + (t - 40) % 7,
                              np.where(t >= 75, t - 28, t))])
    positions = jnp.asarray(np.broadcast_to(rows[:, None], (3, B, T)),
                            jnp.int32)
    return params, tokens, labels, positions


def _setup(dtype, seed=11):
    model = km.KeyeVLLM(dataclasses.replace(
        family.model_config(CFG), dtype=dtype))
    return (CFG, model) + _inputs(seed)


def _gaps(got, want):
    """Per leaf ``(path, |got - want| / max(|want|, median |want|), |want|)``."""
    flat = jax.tree_util.tree_leaves_with_path(want)
    norms = [float(jnp.linalg.norm(x)) for _, x in flat]
    floor = float(np.median([n for n in norms if n > 0]))
    return [(jax.tree_util.keystr(p), float(jnp.linalg.norm(b - a))
             / max(n, floor), n)
            for (p, a), b, n in zip(flat, jax.tree.leaves(got), norms)]


def test_parameter_tree_is_the_benchmarks_spec():
    cfg, model, params, tokens, *_ = _setup(jnp.float32)
    made = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                             tokens)["params"])
    assert (jax.tree.map(lambda x: x.shape, made)
            == jax.tree.map(lambda x: x.shape, params))
    attn = params["layer_0"]["self_attn"]
    assert sorted(attn["indexer"]) == ["k_norm", "weights_proj", "wk", "wq"]
    assert sorted(attn["indexer"]["k_norm"]) == ["bias", "scale"]
    assert attn["indexer"]["wq"]["kernel"].shape == (64, 4 * 8)
    assert attn["indexer"]["wk"]["kernel"].shape == (64, 8)     # ONE key head
    assert sorted(params["layer_1"]["mlp"]) == ["experts", "router"]
    assert "lm_head" in params                                  # untied


@pytest.mark.parametrize("dtype,logit_tol,grad_tol", [
    (jnp.float32, 5e-6, 3e-5), (jnp.bfloat16, 0.15, 0.45),
])
def test_program_equals_reference(dtype, logit_tol, grad_tol):
    cfg, model, params, tokens, labels, positions = _setup(dtype)
    # (every whole-model call of this file is one jitted program: run
    # operation by operation the same tests took three times as long)
    want, want_index = jax.jit(lambda p: reference.logits(
        p, tokens, cfg, positions=positions))(params)
    got, got_index = jax.jit(lambda p: model.apply(
        {"params": p}, tokens, positions))(params)
    assert got.dtype == jnp.float32
    gap = lambda a: float(jnp.linalg.norm(a - want) / jnp.linalg.norm(want))
    assert gap(got) <= logit_tol
    # the selection binds: attention over every causal key is another model,
    # and the comparison says so (45% in float32)
    dense, _ = jax.jit(lambda p: reference.logits(
        p, tokens, cfg, positions=positions, selected=False))(params)
    assert gap(dense) > 2 * 0.15

    batch = (tokens, labels, positions)
    (lm_ref, index_ref), g_ref = _value_and_grad(
        lambda p: reference.loss_terms(p, (tokens, labels), cfg,
                                       positions=positions), params)
    (lm, index), g = _value_and_grad(
        lambda p: km.lm_loss(model, p, batch, terms=True), params)
    assert float(index) == pytest.approx(float(got_index), rel=1e-6)
    assert abs(float(lm) - float(lm_ref)) <= logit_tol * abs(float(lm_ref))
    assert abs(float(index) - float(index_ref)) <= logit_tol * abs(
        float(index_ref))
    assert float(index_ref) > 0.05                # the objective is no zero
    for path, leaf_gap, norm in _gaps(g, g_ref):
        assert leaf_gap <= grad_tol, (path, leaf_gap)
        assert norm > 0, path                     # every leaf is read
    if dtype == jnp.bfloat16:
        # bfloat16 where float32 is stated fails the float32 tolerances
        assert max(x for _, x, _ in _gaps(g, g_ref)) > 3e-5
        assert gap(got) > 5e-6


def _value_and_grad(terms, params):
    """``((L_LM, L_I), d (L_LM + L_I) / d params)``."""
    def total(p):
        lm, index = terms(p)
        return lm + index, (lm, index)

    (_, pair), grads = jax.jit(
        jax.value_and_grad(total, has_aux=True))(params)
    return pair, grads


def test_the_two_terms_feed_disjoint_leaves():
    """The indexer's leaves take no gradient from ``L_LM`` and the others
    none from ``L_I``: exactly zero, in the program and in the reference; so a
    model without ``L_I`` leaves the indexer's gradient at zero, which the
    float32 tolerance refuses by four orders."""
    cfg, model, params, tokens, labels, positions = _setup(jnp.float32)
    batch = (tokens, labels, positions)

    def each_terms_gradient(terms):
        """``(d L_LM / d params, d L_I / d params)``, one program."""
        return jax.jit(lambda p: tuple(
            jax.grad(lambda q, i=i: terms(q)[i])(p) for i in (0, 1)))(params)

    program = each_terms_gradient(
        lambda p: km.lm_loss(model, p, batch, terms=True))
    plain = each_terms_gradient(
        lambda p: reference.loss_terms(p, (tokens, labels), cfg,
                                       positions=positions))
    for grads in (program, plain):
        for term, indexer_reads in ((0, False), (1, True)):
            for path, x in jax.tree_util.tree_leaves_with_path(grads[term]):
                reads = float(jnp.max(jnp.abs(x))) > 0
                mine = INDEXER in jax.tree_util.keystr(path)
                assert reads == (mine == indexer_reads), (
                    term, jax.tree_util.keystr(path))
    # (each leaf is fed by one term: the sum's gradient is the two trees' sum)
    g_ref = jax.tree.map(jnp.add, *plain)
    without = program[0]
    gaps = {path: gap for path, gap, _ in _gaps(without, g_ref)}
    # (a leaf's gap is taken against its norm or the median leaf's, whichever
    # is larger: a missing gradient reads its norm over that, 0.015 to 1 here)
    bad = {path: gap for path, gap in gaps.items()
           if not ((gap > 0.01) if INDEXER in path else (gap < 3e-5))}
    assert not bad, bad


def test_the_eight_shares_add_up_to_the_uncut_reference_layer():
    """The guide's share test: the routed parts of the eight shares of the
    experts add up to what the uncut reference gives for the whole expert
    layer (there is no shared expert to count once)."""
    cfg = {**CFG, "num_experts": 16, "num_experts_routed": 16,
           "first_expert_held": 0}
    layer = make_params(family.param_spec(cfg), 5)["layer_1"]["mlp"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 48, 64)),
                    jnp.float32)
    whole = reference.routed(x, layer, cfg, "highest")
    c = family.model_config(cfg)

    def share(held, first):
        moe = km.SparseMoe(
            n_experts=c.n_experts, experts_held=held, top_k=c.top_k,
            expert_dim=c.expert_dim, shared_dim=0, first_expert=first,
            norm_topk=c.norm_topk, dtype=jnp.float32)
        cut = {**layer, "experts": jax.tree.map(
            lambda w: w[first:first + held], layer["experts"])}
        return moe.apply({"params": cut}, x)

    parts = [share(2, f) for f in range(0, 16, 2)]
    np.testing.assert_allclose(sum(parts), whole, atol=3e-6)
    np.testing.assert_allclose(share(16, 0), whole, atol=3e-6)
    # the chosen weights of a token sum to one (norm_topk_prob)
    weights, _ = reference.route(x[0], layer, cfg)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)


def test_multimodal_rotary_angles():
    """Frequency ``i`` of ``head_dim / 2`` turns with the temporal id for ``i
    < 2``, the height id for ``2 <= i < 5``, the width id above (sections 2,
    3, 3), at ``theta ** (-i / 8)``; text (three equal rows) is ordinary
    rotary."""
    pos = jnp.asarray([[[3]], [[5]], [[7]]], jnp.int32)       # [3, 1, 1]
    inv = 10000.0 ** (-np.arange(8) / 8)
    want = np.asarray([3, 3, 5, 5, 5, 7, 7, 7]) * inv
    got = km.mrope_angle(pos, (2, 3, 3), 10000.0)[0, 0]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(reference.mrope_angle(pos, CFG)[0, 0], want,
                               rtol=1e-6)
    text = jnp.broadcast_to(jnp.arange(6), (3, 1, 6))
    np.testing.assert_allclose(km.mrope_angle(text, (2, 3, 3), 10000.0)[0],
                               np.arange(6)[:, None] * inv, rtol=1e-6)
    # the rotation pairs element i with i + D / 2
    x = jnp.zeros((1, 1, 1, 16)).at[..., 0].set(1.0)
    y = km.rotate(x, jnp.full((1, 1, 8), np.pi / 2))
    np.testing.assert_allclose(y[0, 0, 0, 8], 1.0, atol=1e-6)
    with pytest.raises(ValueError, match="mrope_section"):
        km.KeyeVLConfig(vocab_size=8, head_dim=16, mrope_section=(2, 3, 4))


def test_trains_through_make_train_step():
    import horovod_tpu.jax as hvd

    cfg, model, params, tokens, labels, _ = _setup(jnp.bfloat16)
    mesh = hvd.build_mesh({"data": 1}, devices=jax.devices()[:1])
    tx = hvd.DistributedOptimizer(optax.adamw(3e-3))
    step = hvd.make_train_step(
        lambda p, batch: km.lm_loss(model, p, batch), tx, mesh)
    start = jax.tree.map(np.asarray, params)
    params = jax.tree.map(jnp.asarray, start)  # the step donates its input
    state = tx.init(params)
    losses = []
    for _ in range(8):
        params, state, loss = step(params, state, (tokens, labels))
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.05
    # the one step moved both sets of leaves, each by its own term
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))) > 0,
                         start, params)
    assert all(jax.tree.leaves(moved))


def test_scopes_and_plan_notes():
    from horovod_tpu import trace

    cfg, model, params, tokens, labels, _ = _setup(jnp.bfloat16)
    trace.reset_build_ledger()
    text = jax.jit(jax.grad(
        lambda p: km.lm_loss(model, p, (tokens, labels)))).lower(
        params).as_text(debug_info=True)
    notes = trace.plan_args()
    for scope in trace.KEYE_SCOPES + ("lm_head", "attention", "flash_bwd"):
        assert scope in text, scope
    assert trace.KEYE_SCOPES[:3] == ("gqa_attn", "sparse_index",
                                     "sparse_index_loss")
    assert notes["sparse_index_form"] == "int8_mask"
    assert notes["sparse_index_top_k"] == 16
    # 16 * 17 / 2 + (128 - 16) * 16 pairs a sequence, of 128 * 129 / 2
    assert notes["sparse_index_pairs_selected"] == B * 1928
    assert notes["sparse_index_pairs_causal"] == B * 8256
    assert notes["flash_selection"] is True
    assert notes["layer_recompute_keeps"] == (
        "flash_o", "flash_lse", "sparse_index_kl_grads")
    assert notes["moe_experts_total"] == 8 and notes["moe_experts_held"] == 4
    assert notes["sparse_index_kernel"] is True
    assert notes["sparse_index_loss_kernel"] is True
    # (at width 64 the gather-sum takes its XLA form; nothing else does)
    assert {f["op"] for f in trace.build_ledger()["fallbacks"]} <= {
        "moe_combine"}
    load = np.asarray(km.expert_load(model, params, tokens))
    assert load.shape == (2, 3)               # every layer is sparse


def test_the_objective_walks_once_a_layer_and_step(monkeypatch):
    """A layer is recomputed in its backward, all but what its kernels named
    (``models/recompute.KEEPS``): the objective's one walk leaves the value
    and the gradient and the forward flash kernel its result and logsumexp,
    the layer's recomputation keeps them, and a step holds ONE
    ``sparse_index_kl`` and ONE ``_fwd_kernel_sel`` call a layer (two until
    PR 44); the selection runs twice (first pass and recomputation: its mask
    is not kept), the backward flash kernel once, and it is ONE kernel: the
    small model's whole dq fits beside its tiles, as the cell's does under
    the raised limit (the dQ kernel is not built). Counted in the step
    lowered for the chip."""
    import re
    from collections import Counter

    from horovod_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "_resolve_interpret", lambda interpret: False)
    cfg, model, params, tokens, labels, _ = _setup(jnp.bfloat16)
    step = jax.jit(jax.value_and_grad(
        lambda p: km.lm_loss(model, p, (tokens, labels))))
    text = step.trace(params).lower(lowering_platforms=("tpu",)).as_text()
    calls = Counter(re.findall(r'kernel_name = "(\w+)"', text))
    layers = cfg["num_hidden_layers"]
    assert calls == {
        "sparse_index_kl": layers, "sparse_index_select": 2 * layers,
        "_fwd_kernel_sel": layers, "_dkv_kernel_sel": layers}


def test_the_expert_layer_is_the_shared_one_without_a_shared_expert():
    """The layer's experts are ``parallel/ep.dropless_moe`` as every caller
    gets it: the shared tile plan at the cell's load (16384 tokens, top 8 of
    128, 16 held: 1024 rows an expert, so a first tile of 1.25 balanced loads
    and overflow tiles of an eighth), the model's result the function's own,
    and no shared expert's leaves."""
    from horovod_tpu.parallel import ep

    assert ep._tile_plan(16384, 8, 16, 128) == (20480, 2048, 54)
    cfg, model, params, tokens, *_ = _setup(jnp.float32)
    mlp = params["layer_0"]["mlp"]
    assert sorted(mlp) == ["experts", "router"]
    x = jax.random.normal(jax.random.PRNGKey(5), (B, T, cfg["hidden_size"]))
    layer = km.SparseMoe(
        n_experts=cfg["num_experts_routed"], experts_held=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"], shared_dim=0,
        first_expert=cfg["first_expert_held"], dtype=jnp.float32)
    want = ep.dropless_moe(
        x.reshape(B * T, -1), mlp["router"]["kernel"], mlp["experts"]["gate"],
        mlp["experts"]["up"], mlp["experts"]["down"],
        top_k=cfg["num_experts_per_tok"],
        first_expert=cfg["first_expert_held"], dtype=jnp.float32)
    np.testing.assert_array_equal(layer.apply({"params": mlp}, x),
                                  want.reshape(B, T, -1))


def test_the_seeded_table_alone_is_drawn_wider():
    """``seeded_embedding_std`` is the benchmark's draw of the embedding
    table and of nothing else: every matrix keeps ``initializer_range``, and
    left out the table does too. The model has no such option: its own
    ``init`` draws every leaf at ``init_std``."""
    from benchmark.weights import is_leaf

    def stds(cfg):
        flat, _ = jax.tree_util.tree_flatten_with_path(
            family.param_spec(cfg), is_leaf=is_leaf)
        return {jax.tree_util.keystr(p): leaf.std for p, leaf in flat
                if leaf.kind == "normal"}

    table = "['embed_tokens']['embedding']"
    plain, wide = stds(CFG), stds({**CFG, "seeded_embedding_std": 1.0})
    assert set(plain.values()) == {0.02}
    assert wide.pop(table) == 1.0 and plain.pop(table) == 0.02
    assert wide == plain
    assert not hasattr(family.model_config(CFG), "seeded_embedding_std")
