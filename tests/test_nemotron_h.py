"""``models/nemotron_h.py`` against the plain reference
(``benchmark/reference/nemotron_h.py``, which imports nothing of the program)
on seeded weights at a small size: logits, loss and every leaf's gradient; the
pattern string; the Mamba-2 mixer against a loop; the share test.

Tolerances. With float32 products the program and the reference are the same
mathematics in another order (a chunked scan against one update a token,
sorted grouped products against a masked loop, flash blocks against one
softmax): gaps are float32 rounding, measured at most 6e-7 of the logits'
spread and 4e-6 of a leaf's gradient norm; the limits are some five times
that. With the model's bfloat16 products every operand is rounded to 2^-9
relative, and a top-k choice near a tie flips (the router's product is
float32, but its input is the bfloat16 residual stream; the chosen expert's
weight is some 0.4 of 2.5 here, so a flip moves that token's logits by 3.5%
of their spread, two tokens of 128 at this seed): measured 0.4% of the
logits' spread at the nine tenths of the tokens that err least and 3.5% at
the worst, and 2.6% of a leaf's gradient norm; the limits are 2%, 10% and
15%, which a dropped layer, a wrong group, a wrong decay or a left-out bias
exceeds by far."""

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

from benchmark.families import nemotron_h as family  # noqa: E402
from benchmark.reference import nemotron_h as reference  # noqa: E402
from benchmark.weights import make_params  # noqa: E402
from horovod_tpu.models import nemotron_h as nm  # noqa: E402
from horovod_tpu.parallel import ep  # noqa: E402

CFG = {
    "hidden_size": 64, "num_hidden_layers": 5,
    "hybrid_override_pattern": "ME*EM",
    "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 4, "n_routed_experts_routed": 8,
    "first_expert_held": 2, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-5, "vocab_size": 251,
    "initializer_range": 0.02, "expert_bias_std": 0.01, "train": {},
}
B, T = 2, 64


@functools.lru_cache(maxsize=None)
def _inputs(seed):
    """Weights and a batch of ``CFG`` from ``seed``, made once a process."""
    params = make_params(family.param_spec(CFG), seed)
    # norm weights, the decays' leaves and the convolution's bias start at
    # one or zero: move every vector off its initial value so that a leaf
    # the program ignores shows (the selection bias keeps its own draw)
    keys = jax.random.split(jax.random.PRNGKey(seed), 400)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    leaves = [x + 0.05 * jax.random.normal(k, x.shape)
              if x.ndim == 1 and "expert_bias" not in jax.tree_util.keystr(p)
              else x for (p, x), k in zip(flat, keys)]
    params = jax.tree.unflatten(tree, leaves)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, CFG["vocab_size"], (B, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, CFG["vocab_size"], (B, T)), jnp.int32)
    return params, tokens, labels


def _setup(dtype, seed=11):
    params, tokens, labels = _inputs(seed)
    model = nm.NemotronHLM(dataclasses.replace(
        family.model_config(CFG), dtype=dtype))
    return model, params, tokens, labels


def _jit(f, *args):
    """``f(*args)`` as ONE compiled program that rounds where the
    operation-by-operation run does (tests/test_lfm2_moe.py says why)."""
    return jax.jit(f).lower(*args).compile(compiler_options={
        "xla_allow_excess_precision": False,
        "xla_llvm_disable_expensive_passes": True})(*args)


def test_parameter_tree_is_the_benchmarks_spec_and_follows_the_pattern():
    model, params, tokens, _ = _setup(jnp.float32)
    made = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                             tokens)["params"])
    assert (jax.tree.map(lambda x: x.shape, made)
            == jax.tree.map(lambda x: x.shape, params))
    # ONE sublayer a layer, behind one norm: nothing beside `mixer`
    assert all(sorted(params[f"layer_{i}"]) == ["mixer", "norm"]
               for i in range(5))
    kinds = ["M" if "A_log" in params[f"layer_{i}"]["mixer"] else
             "*" if "q_proj" in params[f"layer_{i}"]["mixer"] else "E"
             for i in range(5)]
    assert "".join(kinds) == CFG["hybrid_override_pattern"]
    # an expert without a gate holds two matrices, and the layer its own
    # shared expert
    assert sorted(params["layer_1"]["mixer"]) == [
        "expert_bias", "experts", "router", "shared_down_proj",
        "shared_up_proj"]
    assert sorted(params["layer_1"]["mixer"]["experts"]) == ["down", "up"]
    # another string, another stack: nothing assumes a period
    other = dataclasses.replace(model.cfg, pattern="**EM")
    made = jax.eval_shape(lambda: nm.NemotronHLM(other).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"])
    assert ["q_proj" in made[f"layer_{i}"]["mixer"] for i in range(4)] == [
        True, True, False, False]
    with pytest.raises(ValueError, match="pattern"):
        dataclasses.replace(model.cfg, pattern="ME-")
    with pytest.raises(ValueError, match="num_hidden_layers"):
        family.dims({**CFG, "num_hidden_layers": 6})


@pytest.mark.parametrize("dtype,logit_tol,worst_tol,grad_tol", [
    (jnp.float32, 3e-6, 3e-6, 2e-5), (jnp.bfloat16, 2e-2, 0.1, 0.15),
])
def test_program_equals_reference(dtype, logit_tol, worst_tol, grad_tol):
    model, params, tokens, labels = _setup(dtype)
    want = _jit(lambda p: reference.logits(p, tokens, CFG), params)
    got = _jit(lambda p: model.apply({"params": p}, tokens), params)
    assert got.dtype == jnp.float32
    spread = float(jnp.max(want) - jnp.min(want))
    per_token = jnp.max(jnp.abs(got - want), axis=-1).reshape(-1)
    assert float(jnp.quantile(per_token, 0.9)) <= logit_tol * spread
    assert float(jnp.max(per_token)) <= worst_tol * spread

    l_ref, g_ref = _jit(jax.value_and_grad(
        lambda p: reference.loss(p, (tokens, labels), CFG)), params)
    l, g = _jit(jax.value_and_grad(
        lambda p: nm.lm_loss(model, p, (tokens, labels))), params)
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
    # the selection bias enters only the choice: exactly zero, in both; every
    # other leaf (A_log, dt_bias, D, the convolution's bias) is read
    assert unread == [f"['layer_{i}']['mixer']['expert_bias']"
                      for i in (1, 3)]


def test_mamba2_mixer_against_a_loop():
    """The mixer written out per position in float64 numpy: the split of
    ``in_proj``, four causal taps and a bias under ``silu``, the recurrence
    with head ``h`` reading group ``h // 2``, the gate BEFORE a norm over
    each group's channels."""
    model, params, *_ = _setup(jnp.float32)
    p = jax.tree.map(lambda x: np.asarray(x, np.float64),
                     params["layer_0"]["mixer"])
    H, P, G, N, taps, inner = 4, 8, 2, 16, 4, 32
    rng = np.random.default_rng(0)
    u = rng.normal(size=(10, CFG["hidden_size"]))
    got = nm.Mamba2Mixer(n_heads=H, head_dim=P, state_dim=N, n_groups=G,
                         chunk=4, dtype=jnp.float32).apply(
        {"params": params["layer_0"]["mixer"]},
        jnp.asarray(u[None], jnp.float32))[0]
    silu = lambda a: a / (1 + np.exp(-a))
    zxbcdt = u @ p["in_proj"]["kernel"]
    z, xbc, dt = np.split(zxbcdt, [inner, 2 * inner + 2 * G * N], axis=1)
    conv = np.zeros_like(xbc)
    for t in range(10):
        conv[t] = p["conv"]["bias"] + sum(
            p["conv"]["kernel"][j] * xbc[t - (taps - 1) + j]
            for j in range(taps) if t - (taps - 1) + j >= 0)
    xbc = silu(conv)
    x = xbc[:, :inner].reshape(10, H, P)
    Bm = xbc[:, inner:inner + G * N].reshape(10, G, N)
    Cm = xbc[:, inner + G * N:].reshape(10, G, N)
    dt = np.log1p(np.exp(dt + p["dt_bias"]))
    y = np.zeros((10, H, P))
    S = np.zeros((H, P, N))
    for t in range(10):
        for h in range(H):
            g = h // (H // G)
            S[h] = (np.exp(-dt[t, h] * np.exp(p["A_log"][h])) * S[h]
                    + dt[t, h] * np.outer(x[t, h], Bm[t, g]))
            y[t, h] = S[h] @ Cm[t, g] + p["D"][h] * x[t, h]
    y = (y.reshape(10, inner) * silu(z)).reshape(10, G, inner // G)
    y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5)
    y = y.reshape(10, inner) * p["norm"]["scale"]
    np.testing.assert_allclose(got, y @ p["out_proj"]["kernel"], atol=2e-6)


def _moe(held, first, shared=48):
    return nm.SparseMoe(
        n_experts=128, experts_held=held, top_k=6, expert_dim=32,
        first_expert=first, routed_scale=2.5, norm_eps=nm.ROUTE_NORM_EPS,
        gated=False, activation=ep.relu_squared, shared_dim=shared,
        dtype=jnp.float32)


def test_the_sixteen_shares_add_up_to_the_uncut_reference_layer():
    """The guide's share test at the configuration's counts (128 experts,
    top 6, sixteen shares of ids ``8 s .. 8 s + 7``): the routed parts of the
    shares, with the shared expert, which every chip computes alike, counted
    once, add up to what the uncut reference gives for the whole layer."""
    cfg = {**CFG, "n_routed_experts": 128, "n_routed_experts_routed": 128,
           "first_expert_held": 0, "num_experts_per_tok": 6}
    params = make_params(family.param_spec(cfg), 5)["layer_1"]["mixer"]
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(1, 48, cfg["hidden_size"])), jnp.float32)
    whole = reference._moe(x, params, cfg, "highest")
    shared = reference._expert(
        x[0], params["shared_up_proj"]["kernel"],
        params["shared_down_proj"]["kernel"], "highest")[None]
    routed_only = {k: v for k, v in params.items() if "shared" not in k}

    @jax.jit
    def routed(first):
        # the share from `first` on, without the shared expert: the layer's
        # own grouped products over eight held experts
        held = jax.tree.map(
            lambda w: jax.lax.dynamic_slice_in_dim(w, first, 8),
            params["experts"])
        return ep.dropless_moe(
            x[0], params["router"]["kernel"], None, held["up"], held["down"],
            top_k=6, first_expert=first, score="sigmoid",
            select_bias=params["expert_bias"], norm_eps=nm.ROUTE_NORM_EPS,
            scale=2.5, activation=ep.relu_squared, dtype=jnp.float32)[None]

    parts = [routed(8 * s) for s in range(16)]
    top = float(jnp.max(jnp.abs(whole)))
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=3e-6 * top)
    # the module's share is the same routed part plus the shared expert
    cut = {**params, "experts": jax.tree.map(lambda w: w[16:24],
                                             params["experts"])}
    np.testing.assert_allclose(
        _moe(8, 16).apply({"params": cut}, x), parts[2] + shared,
        atol=3e-6 * top)
    np.testing.assert_allclose(
        _moe(8, 16, shared=0).apply(
            {"params": {**routed_only, "experts": cut["experts"]}}, x),
        parts[2], atol=3e-6 * top)
    # every token's six choices lie in some share, 0.375 of them in one
    _, ids = reference.route(x[0], params, cfg)
    assert np.isin(np.asarray(ids), np.arange(16, 24)).sum() > 0
    with pytest.raises(ValueError, match="shared expert"):
        nm.SparseMoe(n_experts=8, experts_held=8, top_k=2, expert_dim=8,
                     shared_dim=8).init(jax.random.PRNGKey(0), x)


def test_trains_through_make_train_step():
    import horovod_tpu.jax as hvd

    model, params, tokens, labels = _setup(jnp.bfloat16)
    mesh = hvd.build_mesh({"data": 1}, devices=jax.devices()[:1])
    tx = hvd.DistributedOptimizer(optax.adamw(3e-3))
    step = hvd.make_train_step(
        lambda p, batch: nm.lm_loss(model, p, batch), tx, mesh)
    params = jax.tree.map(jnp.copy, params)       # the step donates them
    state = tx.init(params)
    losses = []
    for _ in range(8):
        params, state, loss = step(params, state, (tokens, labels))
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.05


def test_scopes_and_plan_notes():
    from horovod_tpu import trace

    model, params, tokens, labels = _setup(jnp.bfloat16)
    assert not trace.ACTIVE         # the notes are recorded all the same
    trace.reset_build_ledger()
    text = jax.jit(jax.grad(
        lambda p: nm.lm_loss(model, p, (tokens, labels)))).lower(
        params).as_text(debug_info=True)
    notes = trace.plan_args()
    for scope in trace.NEMOTRON_H_SCOPES + ("lm_head", "attention"):
        assert scope in text, scope
    # the convolution and the scan lie inside the mixer's scope
    assert "ssm_mixer/ssm_conv" in text and "ssm_mixer/ssm_scan" in text
    assert {k: v for k, v in notes.items() if k.startswith("ssm_")} == {
        "ssm_heads": 4, "ssm_head_dim": 8, "ssm_state": 16, "ssm_groups": 2,
        "ssm_chunk": 16, "ssm_chunks": 4, "ssm_kernel": False,
        "ssm_grid_steps": 0, "ssm_vmem_mb": 0.0}
    assert notes["moe_gated"] is False and notes["moe_score"] == "sigmoid"
    assert notes["moe_select_bias"] is True
    assert notes["moe_experts_total"] == 8 and notes["moe_experts_held"] == 4
    # heads of 8 over a state of 16 fill no lane: the scan takes its XLA
    # form, one record a Mamba-2 layer; at width 64 the gather-sum takes its
    # own, as in the other models' small tests
    fallbacks = trace.build_ledger()["fallbacks"]
    assert {f["op"] for f in fallbacks} == {"moe_combine", "ssd_fwd"}
    scans = [f for f in fallbacks if f["op"] == "ssd_fwd"]
    assert len(scans) == model.cfg.pattern.count("M")
    assert {f["reason"] for f in scans} == {"heads_not_whole_lanes"}
    load = np.asarray(nm.expert_load(model, params, tokens))
    assert load.shape == (2, 3)               # the two expert layers
    assert trace.plan_args()["moe_pairs_held"] == list(load[:, 0])
