"""``models/ling.py`` against the plain reference
(``benchmark/reference/ling.py``, which imports nothing of the program) on
seeded weights at a small size: logits, loss and every leaf's gradient; the
layer order by ``layer_group_size``; the share test; group-limited routing
against a choice written out here, ties included; the rotary pairs; the train
step; scopes and plan notes; and the lowered steps of the six configurations
that were there, which the new fields leave the parent's at their defaults.

Tolerances. With float32 products the program and the reference are the same
mathematics in another order (a chunked triangular system against one
rank-one update a token, sorted grouped products against a masked loop, flash
blocks against one softmax, the rope part sorted into halves against a
rotation in place): gaps are float32 rounding, measured at most 1.5e-7 of the
logits' spread and 5e-6 of a leaf's gradient norm (or of the median leaf's,
where that is larger); the limits are 1e-5 and 1e-4. (The gradient's limit
is what caught ``ops/kda.py``'s first form: with a sub-block's exponents
referred to its FIRST row the float32 gradients of the gate's and the keys'
leaves read 2 to 7% here, in float32 as in bfloat16, because a cotangent
times ``exp(-75)`` leaves float32's range before the matching ``exp(75)``
comes; ``tests/test_kda.py`` now holds that at the operator.) With the
model's bfloat16 products every operand is rounded to 2^-9 relative and a
top-k choice near a tie flips (128 tokens over 16 experts: one flip moves an
expert's gradient by a twentieth): measured 0.26% of the logits' spread and
5.6% of a leaf's gradient norm (an expert's ``up``; 1.6% without the experts'
leaves, at ``f_proj``); the limits are 1% and 15%. The reference computed
with int8 products reads 0.64% of the spread and 5.9% (``o_proj`` of the
attention layer): the control that a lower precision fails is the benchmark's
own comparison, which ``tests/benchmark/test_benchmark_ling.py`` holds at the
cell's rehearsal size. A dropped chunk state, a wrong group mask or rotary
pairs taken as halves exceed these limits by far (they read 0.5 to 1)."""

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

from benchmark.families import ling as family  # noqa: E402
from benchmark.reference import ling as reference  # noqa: E402
from benchmark.weights import make_params  # noqa: E402
from horovod_tpu.models import ling as lm  # noqa: E402
from horovod_tpu.parallel import ep  # noqa: E402

CFG = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32, "num_shared_experts": 1,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "layer_group_size": 2, "first_layer_published": 0,
    "layer_mixers": ["kda", "mla", "kda"],
    "num_attention_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5, "kda_safe_gate": True, "no_kda_lora": True,
    "q_lora_rank": None, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 6000000,
    "rope_scaling": None, "rope_interleave": True, "use_mla_nope": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "rms_norm_eps": 1e-6, "num_experts": 4, "num_experts_routed": 16,
    "first_expert_held": 4, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "score_function": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "expert_swiglu_limit_list": [0] * 3,
    "share_expert_swiglu_limit_list": [0] * 3, "vocab_size": 251,
    "initializer_range": 0.02, "seeded_embedding_std": 1.0,
    "seeded_dt_bias_std": 2.0, "expert_bias_std": 0.01,
    "train": {},
}
B, T = 2, 128       # two of the rule's chunks: a state is handed over


@functools.lru_cache(maxsize=None)
def _inputs(seed):
    """Weights and a batch of ``CFG`` from ``seed``, made once a process."""
    params = make_params(family.param_spec(CFG), seed)
    # norm weights start at one: move every vector off its initial value so
    # that a leaf the program ignores shows (the drawn ones keep their draw)
    keys = jax.random.split(jax.random.PRNGKey(seed), 400)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    drawn = ("expert_bias", "dt_bias")
    leaves = [x + 0.05 * jax.random.normal(k, x.shape)
              if x.ndim == 1 and not any(
                  d in jax.tree_util.keystr(p) for d in drawn)
              else x for (p, x), k in zip(flat, keys)]
    params = jax.tree.unflatten(tree, leaves)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, CFG["vocab_size"], (B, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, CFG["vocab_size"], (B, T)), jnp.int32)
    return params, tokens, labels


def _setup(dtype, seed=11):
    params, tokens, labels = _inputs(seed)
    model = lm.LingLM(dataclasses.replace(
        family.model_config(CFG), dtype=dtype))
    return model, params, tokens, labels


def _jit(f, *args):
    """``f(*args)`` as ONE compiled program that rounds where the
    operation-by-operation run does (tests/test_lfm2_moe.py says why)."""
    return jax.jit(f).lower(*args).compile(compiler_options={
        "xla_allow_excess_precision": False,
        "xla_llvm_disable_expensive_passes": True})(*args)


def test_parameter_tree_is_the_benchmarks_spec_and_follows_the_list():
    model, params, tokens, _ = _setup(jnp.float32)
    made = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                             tokens)["params"])
    assert (jax.tree.map(lambda x: x.shape, made)
            == jax.tree.map(lambda x: x.shape, params))
    kinds = ["kda" if "linear_attn" in params[f"layer_{i}"] else "mla"
             for i in range(3)]
    assert kinds == CFG["layer_mixers"]
    assert sorted(params["layer_0"]["mlp"]) == ["w1", "w2", "w3"]
    assert sorted(params["layer_1"]["mlp"]) == ["expert_bias", "experts",
                                                "router"]
    assert "shared_expert" in params["layer_1"]
    assert "shared_expert" not in params["layer_0"]
    # no query bottleneck, one gate a head; KDA's gates at full rank
    assert sorted(params["layer_1"]["self_attn"]) == [
        "g_proj", "kv_a_layernorm", "kv_a_proj", "kv_b_proj", "o_proj",
        "q_proj"]
    assert params["layer_1"]["self_attn"]["g_proj"]["kernel"].shape == (64, 4)
    kda = params["layer_0"]["linear_attn"]
    assert (kda["f_proj"]["kernel"].shape, kda["g_proj"]["kernel"].shape,
            kda["dt_bias"].shape, kda["A_log"].shape,
            kda["o_norm"]["scale"].shape) == (
        (64, 64), (64, 64), (64,), (4,), (16,))
    assert "lm_head" in params                     # untied


def test_layer_order_is_layer_group_size_s():
    """Without a list a layer is latent attention where ``(l + 1) %
    layer_group_size == 0``: the published 42 layers are 35 and 7, five to
    one; a list (a cut that does not start at layer 0) replaces the rule,
    and the family holds the list to the rule from the first kept layer."""
    c = lm.LingConfig(vocab_size=8)
    kinds = [c.kind(i) for i in range(c.n_layers)]
    assert kinds.count("mla") == 7 and kinds.count("kda") == 35
    assert [i for i, k in enumerate(kinds) if k == "mla"] == [
        5, 11, 17, 23, 29, 35, 41]
    assert kinds[1:8] == ["kda", "kda", "kda", "kda", "mla", "kda", "kda"]
    listed = dataclasses.replace(c, n_layers=3,
                                 layer_kinds=("mla", "kda", "mla"))
    assert [listed.kind(i) for i in range(3)] == ["mla", "kda", "mla"]
    with pytest.raises(ValueError, match="layer_kinds"):
        dataclasses.replace(c, n_layers=3, layer_kinds=("mla", "kda"))
    with pytest.raises(ValueError, match="layer_kinds"):
        dataclasses.replace(c, n_layers=2, layer_kinds=("mla", "gqa"))
    assert family.model_config(CFG).layer_kinds == ("kda", "mla", "kda")
    with pytest.raises(ValueError, match="layer_group_size"):
        family.dims({**CFG, "layer_mixers": ["kda", "kda", "mla"]})
    with pytest.raises(ValueError, match="num_hidden_layers"):
        family.dims({**CFG, "num_hidden_layers": 4})
    for key, other in (("q_lora_rank", 32), ("rope_interleave", False),
                       ("kda_safe_gate", False), ("topk_method", "greedy"),
                       ("expert_swiglu_limit_list", [0, 0, 4])):
        with pytest.raises(ValueError, match="asks for another"):
            family.model_config({**CFG, key: other})


@functools.lru_cache(maxsize=None)
def _reference(seed=11):
    """The reference's logits, loss and gradients at ``seed``, once a
    process: both precisions of the program are held to the same numbers."""
    params, tokens, labels = _inputs(seed)
    loss, grads = _jit(jax.value_and_grad(
        lambda p: reference.loss(p, (tokens, labels), CFG)), params)
    return (_jit(lambda p: reference.logits(p, tokens, CFG), params), loss,
            grads)


@pytest.mark.parametrize("dtype,logit_tol,grad_tol", [
    (jnp.float32, 1e-5, 1e-4), (jnp.bfloat16, 1e-2, 0.15),
])
def test_program_equals_reference(dtype, logit_tol, grad_tol):
    model, params, tokens, labels = _setup(dtype)
    want, l_ref, g_ref = _reference()
    got = _jit(lambda p: model.apply({"params": p}, tokens), params)
    assert got.dtype == jnp.float32
    spread = float(jnp.max(want) - jnp.min(want))
    assert float(jnp.max(jnp.abs(got - want))) <= logit_tol * spread

    l, g = _jit(jax.value_and_grad(
        lambda p: lm.lm_loss(model, p, (tokens, labels))), params)
    assert abs(float(l) - float(l_ref)) <= logit_tol * abs(float(l_ref))
    flat_ref = jax.tree_util.tree_leaves_with_path(g_ref)
    norms = [float(jnp.linalg.norm(x)) for _, x in flat_ref]
    floor = float(np.median(norms))
    unread = []
    for (path, a), b, n in zip(flat_ref, jax.tree.leaves(g), norms):
        gap = float(jnp.linalg.norm(b - a))
        assert gap <= grad_tol * max(n, floor), (
            jax.tree_util.keystr(path), gap / max(n, floor))
        if n == 0:
            unread.append(jax.tree_util.keystr(path))
            assert float(jnp.max(jnp.abs(b))) == 0.0
    # the selection bias enters only the choice: exactly zero, in both;
    # every other leaf (A_log, dt_bias, the taps, both gates) is read
    assert unread == [f"['layer_{i}']['mlp']['expert_bias']"
                      for i in range(1, 3)]


def _written_out_choice(biased, n_group, kept, k):
    """The group-limited choice for one token, by sorting: a stable sort of
    the negated scores takes the lower id on a tie, as ``lax.top_k``."""
    per = len(biased) // n_group
    best = lambda v, n: list(np.argsort(-np.asarray(v, np.float64),
                                        kind="stable")[:n])
    score = [sum(sorted(biased[g * per:(g + 1) * per], reverse=True)[:2])
             for g in range(n_group)]
    open_groups = best(score, kept)
    masked = [v if i // per in open_groups else -np.inf
              for i, v in enumerate(biased)]
    return best(masked, k)


def test_group_limited_routing_against_a_written_out_choice():
    """16 experts in 4 groups of which 2 are kept, top 4, on scores with
    exact ties (quarters of integers, so that sums tie exactly too): between
    experts inside a group, between groups' scores, and a token whose best
    expert lies in a group that is NOT kept (its two best sum lower)."""
    rng = np.random.default_rng(3)
    logits = rng.integers(-6, 7, size=(64, 16)) / 4.0
    # token 0: the single best expert (id 13) in a group whose second is bad
    logits[0] = [1, 1, 0, 0, 1.25, 1, 0, 0, -2, -2, -2, -2, -9, 3, -9, -9]
    # token 1: every group alike: groups 0 and 1, experts 0, 1, 4, 5
    logits[1] = [2, 2, 0, 0] * 4
    x = jnp.eye(64, dtype=jnp.float32)
    bias = jnp.asarray(rng.integers(-2, 3, size=16) / 8.0, jnp.float32)
    scores = jax.nn.sigmoid(jnp.asarray(logits, jnp.float32))
    routing = dict(top_k=4, score="sigmoid", select_bias=bias,
                   norm_eps=1e-20, scale=2.5, n_group=4, topk_group=2)
    weights, ids = jax.jit(lambda w: ep.route_top_k(x, w, **routing))(
        jnp.asarray(logits, jnp.float32))
    biased = np.asarray(scores + bias)
    for t in range(64):
        assert list(np.asarray(ids[t])) == _written_out_choice(
            biased[t], 4, 2, 4), t
    assert 13 not in np.asarray(ids[0]) and set(np.asarray(ids[1])) == {
        0, 1, 4, 5}
    # at most two groups a token; the weights are the scores WITHOUT the
    # bias, normalised and scaled
    assert all(len({int(i) // 4 for i in row}) <= 2 for row in np.asarray(ids))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(ids), axis=-1)
    np.testing.assert_allclose(
        weights, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # the reference's choice is the same one
    p = {"router": {"kernel": jnp.asarray(logits, jnp.float32)},
         "expert_bias": bias}
    w_ref, ids_ref = reference.route(x, p, CFG)
    np.testing.assert_array_equal(ids_ref, ids)
    np.testing.assert_allclose(w_ref, weights, rtol=1e-6)
    # without groups the choice is the parent's: token 0 takes expert 13
    _, plain = ep.route_top_k(x, jnp.asarray(logits, jnp.float32), **{
        **routing, "n_group": 1, "topk_group": 1})
    assert 13 in np.asarray(plain[0])
    with pytest.raises(ValueError, match="groups"):
        ep.route_top_k(x, jnp.asarray(logits, jnp.float32), **{
            **routing, "n_group": 4, "topk_group": 1, "top_k": 8})


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """The guide's share test at 16 experts in 4 groups: the routed parts of
    the four shares (ids ``4 s .. 4 s + 3``, a group each), with the shared
    expert, which every chip computes alike, counted once, add up to what the
    uncut reference gives for the whole sparse layer."""
    cfg = {**CFG, "num_experts": 16, "first_expert_held": 0}
    layer = make_params(family.param_spec(cfg), 5)["layer_1"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 48, 64)),
                    jnp.float32)
    shared = reference._swiglu(x, layer["shared_expert"], "highest")
    whole = reference.routed(x, layer["mlp"], cfg, "highest") + shared

    def part(held, first):
        c = family.model_config(cfg)
        return lm.SparseMoe(
            n_experts=c.n_experts, experts_held=held, top_k=c.top_k,
            expert_dim=c.expert_dim, first_expert=first,
            routed_scale=c.routed_scale, norm_eps=lm.ROUTE_NORM_EPS,
            n_group=c.n_group, topk_group=c.topk_group, dtype=jnp.float32)

    cut = lambda first: {**layer["mlp"], "experts": jax.tree.map(
        lambda w: w[first:first + 4], layer["mlp"]["experts"])}
    parts = [part(4, f).apply({"params": cut(f)}, x) for f in range(0, 16, 4)]
    top = float(jnp.max(jnp.abs(whole)))
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=3e-6 * top)
    np.testing.assert_allclose(
        part(16, 0).apply({"params": layer["mlp"]}, x) + shared, whole,
        atol=3e-6 * top)
    # the reference's own share is the program's
    np.testing.assert_allclose(
        reference.routed(x, cut(8), {**cfg, "num_experts": 4,
                                     "first_expert_held": 8}, "highest"),
        parts[2], atol=3e-6 * top)
    # a token's four choices lie in two groups: two shares at most see it
    seen = sum((jnp.abs(p).sum(-1) > 0).astype(jnp.int32) for p in parts)
    assert int(seen.max()) <= 2 and int(seen.min()) >= 1
    weights, _ = reference.route(x[0], layer["mlp"], cfg)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)


def test_rotary_pairs_are_neighbours():
    """``LatentAttention`` sorts the rope part into its even and its odd
    elements and rotates halves; the reference rotates neighbours in place.
    The two give every query-key product the same value, and the rotation
    taken over halves of the UNSORTED part (the other models' layout) does
    not."""
    from horovod_tpu.models.qwen3_next import rotary

    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=(1, 12, 2, 8)), jnp.float32)
            for _ in range(2))
    pos = jnp.arange(12)[None]
    inv_freq = jnp.asarray(lm.LingConfig(vocab_size=8, qk_rope_dim=8)
                           .inv_freq())
    np.testing.assert_allclose(inv_freq, 6e6 ** (-np.arange(4) / 4.0),
                               rtol=1e-6)
    sort = lambda r: jnp.concatenate([r[..., 0::2], r[..., 1::2]], -1)
    rot = lambda r: rotary(r, pos, rotary_dim=8, theta=6e6, inv_freq=inv_freq)
    scores = lambda a, b: jnp.einsum("bqhd,bkhd->bhqk", a, b)
    cfg = {"rope_theta": 6000000}
    want = scores(reference._rotary(q, cfg), reference._rotary(k, cfg))
    np.testing.assert_allclose(scores(rot(sort(q)), rot(sort(k))), want,
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(scores(rot(q), rot(k)) - want))) > 0.1


def test_trains_through_make_train_step():
    import horovod_tpu.jax as hvd

    model, params, tokens, labels = _setup(jnp.bfloat16)
    mesh = hvd.build_mesh({"data": 1}, devices=jax.devices()[:1])
    tx = hvd.DistributedOptimizer(optax.adamw(3e-3))
    step = hvd.make_train_step(
        lambda p, batch: lm.lm_loss(model, p, batch), tx, mesh)
    params = jax.tree.map(jnp.copy, params)       # the step donates them
    state = tx.init(params)
    # (compiled without LLVM's expensive passes: the test is of the step's
    # composition, and its time is the compiler's)
    step = step.lower(params, state, (tokens, labels)).compile(
        compiler_options={"xla_llvm_disable_expensive_passes": True})
    losses = []
    for _ in range(6):
        params, state, loss = step(params, state, (tokens, labels))
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.05


def test_scopes_and_plan_notes():
    from horovod_tpu import trace

    model, params, tokens, labels = _setup(jnp.bfloat16)
    trace.reset_build_ledger()
    # (the forward alone enters every scope)
    text = jax.jit(lambda p: model.apply({"params": p}, tokens)).lower(
        params).as_text(debug_info=True)
    notes = trace.plan_args()
    for scope in trace.LING_SCOPES + ("lm_head", "attention"):
        assert scope in text, scope
    assert trace.LING_SCOPES[:3] == ("kda_mixer", "kda_conv", "kda_scan")
    assert "kda_mixer/kda_scan" in text and "kda_mixer/kda_conv" in text
    assert (notes["kda_chunk"], notes["kda_sub_block"], notes["kda_heads"],
            notes["kda_chunks"], notes["kda_local_blocks"]) == (
        64, 16, 4, 2, 1)
    assert notes["attn_qk_width"] == 24 and notes["attn_v_width"] == 16
    assert notes["moe_score"] == "sigmoid" and notes["moe_select_bias"] is True
    assert (notes["moe_groups"], notes["moe_groups_kept"]) == (4, 2)
    assert notes["moe_experts_total"] == 16 and notes["moe_experts_held"] == 4
    # (at width 64 the gather-sum takes its XLA form; the attention does
    # not fall back)
    assert not [f for f in trace.build_ledger()["fallbacks"]
                if f["op"] == "attention"]
    # what ``expert_load`` (the other expert models' own) stacks: the two
    # sparse layers sow their held experts' load
    from horovod_tpu.models import qwen3_next

    assert lm.expert_load is qwen3_next.expert_load
    _, sown = jax.eval_shape(lambda p: model.apply(
        {"params": p}, tokens, mutable=["intermediates"]), params)
    assert {name: ffn["held_load"][0].shape
            for name, layer in sown["intermediates"].items()
            for ffn in layer.values() if "held_load" in ffn} == {
        "layer_1": (3,), "layer_2": (3,)}


# --------------------------------------------------------------------------
# The configurations that were there: with the new fields at their defaults
# their steps lower to the parent's modules.

def _parent_route_top_k(x, w_router, *, top_k, norm_topk=True,
                        score="softmax", select_bias=None, norm_eps=0.0,
                        scale=1.0, n_group=1, topk_group=1):
    """``parallel/ep.route_top_k`` as the parent commit had it (no groups);
    the two new arguments are taken and must be the defaults."""
    from jax import lax

    assert (n_group, topk_group) == (1, 1)
    logits = jnp.matmul(x.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
              else jax.nn.sigmoid(logits))
    if select_bias is None:
        weights, ids = lax.top_k(scores, top_k)
    else:
        _, ids = lax.top_k(scores + lax.stop_gradient(
            select_bias.astype(jnp.float32)), top_k)
        weights = jnp.take_along_axis(scores, ids, axis=-1)
    if norm_topk:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / (total + norm_eps if norm_eps else total)
    if scale != 1.0:
        weights = weights * scale
    return weights, ids.astype(jnp.int32)


def _parent_latent_attention():
    """``models/xing4.LatentAttention`` as the parent commit had it."""
    from horovod_tpu import trace as _trace
    from horovod_tpu.models import xing4 as xm

    class LatentAttention(xm.nn.Module):
        cfg: object

        @xm.nn.compact
        def __call__(self, x, positions):
            c = self.cfg
            B, T, C = x.shape
            H, dn, dr, dv = (c.n_heads, c.qk_nope_dim, c.qk_rope_dim,
                             c.v_head_dim)
            dense = lambda n, name: xm._dense(n, name, c.dtype, c.init_std)
            _trace.note_plan(attn_qk_width=dn + dr, attn_v_width=dv)
            with jax.named_scope(_trace.SCOPE_LATENT_ATTN):
                c_q = xm._norm(c.eps, c.dtype, "q_a_layernorm")(
                    dense(c.q_lora_rank, "q_a_proj")(x))
                q = dense(H * (dn + dr), "q_b_proj")(c_q).reshape(
                    B, T, H, dn + dr)
                kv_a = dense(c.kv_lora_rank + dr, "kv_a_proj")(x)
                c_kv = xm._norm(c.eps, c.dtype, "kv_a_layernorm")(
                    kv_a[..., :c.kv_lora_rank])
                kv = dense(H * (dn + dv), "kv_b_proj")(c_kv).reshape(
                    B, T, H, dn + dv)
                rot = dict(rotary_dim=dr, theta=c.rope_theta,
                           inv_freq=jnp.asarray(c.inv_freq()))
                q_rope = xm.rotary(q[..., dn:], positions,
                                   **rot) * c.rope_mscale()
                k_rope = xm.rotary(kv_a[:, :, None, c.kv_lora_rank:],
                                   positions, **rot) * c.rope_mscale()
                q = jnp.concatenate(
                    [q[..., :dn], q_rope.astype(c.dtype)], -1)
                k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
                    k_rope.astype(c.dtype), (B, T, H, dr))], -1)
                with jax.named_scope("attention"):
                    a = xm.flash_attention_bthd(
                        q, k, kv[..., dn:], causal=True,
                        sm_scale=c.softmax_scale())
                return dense(C, "o_proj")(a.reshape(B, T, H * dv))

    return LatentAttention


OTHER_CELLS = ["gpt2m-train-1chip", "qwen3next-train-1chip",
               "lfm2moe-train-1chip", "xing4-train-1chip",
               "keyevl-train-1chip", "nemotronh-train-1chip"]


@pytest.mark.parametrize("cell", OTHER_CELLS)
def test_the_other_cells_steps_lower_to_the_parents(cell, monkeypatch):
    """A cell's whole step (``hvd.make_train_step`` over the family's model,
    at the cell's rehearsal sizes) lowered with this tree's
    ``route_top_k`` and ``LatentAttention`` and with the parent's put in
    their place: the same module, so the new arguments and fields cost the
    six configurations nothing at their defaults. (At the cells' own sizes
    the two checkouts were lowered for the chip side by side when the
    fields were added: ``PERF.md`` section 6.)"""
    import horovod_tpu.jax as hvd
    from benchmark import manifest, weights
    from horovod_tpu.models import xing4 as xm

    c = manifest.Cell(manifest.load_manifest(), cell, rehearse=True)
    mesh = hvd.build_mesh({"data": 1}, devices=jax.devices()[:1])
    params = jax.eval_shape(
        lambda: weights.make_params(c.family.param_spec(c.config), 3))
    shape = (c.traffic["per_chip_batch"], c.traffic["seq_len"])
    batch = jax.ShapeDtypeStruct(shape, jnp.int32)

    def lowered():
        step, tx = c.family.build_train(c.config, c.traffic, {}, mesh)
        state = jax.eval_shape(tx.init, params)
        return step.lower(params, state, (batch, batch)).as_text()

    mine = lowered()
    monkeypatch.setattr(ep, "route_top_k", _parent_route_top_k)
    from horovod_tpu.models import lfm2_moe, qwen3_next
    for module in (lfm2_moe, qwen3_next):
        monkeypatch.setattr(module, "route_top_k", _parent_route_top_k)
    monkeypatch.setattr(xm, "LatentAttention", _parent_latent_attention())
    assert lowered() == mine
    assert ("top_k" in mine) == (cell != "gpt2m-train-1chip")
