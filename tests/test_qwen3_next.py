"""``models/qwen3_next.py`` against the plain reference
(``benchmark/reference/qwen3_next.py``, which imports nothing of the program)
on seeded weights at a small size: logits, loss and every leaf's gradient.

Tolerances. With float32 products the program and the reference are the same
mathematics in another order (chunked rule against token by token, sorted
grouped products against a masked loop, flash blocks against one softmax):
gaps are float32 rounding, measured at most 3e-6 of logits of order 0.5 and
2e-5 of a leaf's largest gradient; the limits are 10 times that. With the
model's bfloat16 products every operand is rounded to 2^-9 relative, and a
top-k choice near a tie flips (the router's product is float32, but its
input is the bfloat16 residual stream): at this size an expert sees about 16
tokens, so one flipped token moves that expert's gradient by 1/16 (measured:
0.07 of the leaf's norm on one expert leaf, under 0.02 on every other). The
limit is 2% of the logits' range and 15% of a leaf's gradient norm, which a
dropped layer, a wrong mask or a wrong head grouping exceeds by far (they
move logits and gradients by tens of percent)."""

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

from benchmark.families import qwen3_next as family  # noqa: E402
from benchmark.reference import qwen3_next as reference  # noqa: E402
from benchmark.weights import make_params  # noqa: E402
from horovod_tpu.models import qwen3_next as qn  # noqa: E402

CFG = {
    "hidden_size": 64, "num_hidden_layers": 4, "vocab_size": 251,
    "full_attention_interval": 4, "num_attention_heads": 8,
    "num_key_value_heads": 1, "head_dim": 16, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "num_experts": 4, "num_experts_routed": 8, "first_expert_held": 2,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "rms_norm_eps": 1e-6, "initializer_range": 0.02,
    "train": {"gdn_chunk": 16},
}
B, T = 2, 64


@functools.lru_cache(maxsize=None)
def _inputs(seed, over=()):
    """Weights and a batch of ``CFG`` with ``over`` from ``seed``: made once
    a process (the tests share them; the one test whose step donates its
    input copies)."""
    cfg = {**CFG, **dict(over)}
    params = make_params(family.param_spec(cfg), seed)
    # norm weights and biases start at zero or one: move every leaf off its
    # initial value so that a leaf the program ignores shows
    keys = jax.random.split(jax.random.PRNGKey(seed), 400)
    leaves, tree = jax.tree.flatten(params)
    leaves = [x + 0.05 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
              for x, k in zip(leaves, keys)]
    params = jax.tree.unflatten(tree, leaves)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg["vocab_size"], (B, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg["vocab_size"], (B, T)), jnp.int32)
    return cfg, params, tokens, labels


def _setup(dtype, seed=11, **over):
    cfg, params, tokens, labels = _inputs(seed, tuple(sorted(over.items())))
    model = qn.Qwen3NextLM(dataclasses.replace(
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


@pytest.mark.parametrize("dtype,logit_tol,grad_tol", [
    (jnp.float32, 3e-5, 2e-4), (jnp.bfloat16, 2e-2, 0.15),
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
    for (path, a), b in zip(flat_ref, jax.tree.leaves(g)):
        gap = float(jnp.linalg.norm(b - a))
        assert gap <= grad_tol * max(float(jnp.linalg.norm(a)), floor), (
            jax.tree_util.keystr(path), gap)
    assert all(n > 0 for n in norms), "a leaf the reference never reads"


def test_reference_in_head_groups_equals_the_program():
    """At the published head counts the reference computes the DeltaNet mixer
    in groups of heads (and attention in blocks of rows) so that it fits the
    chip beside the harness's state; with 4 key heads here it takes that
    path, and the program, which does not, gives the same logits."""
    cfg, model, params, tokens, _ = _setup(
        jnp.float32, linear_num_key_heads=4, linear_num_value_heads=8)
    assert cfg["linear_num_key_heads"] % reference.GROUPS == 0
    want = _jit(lambda p: reference.logits(p, tokens, cfg), params)
    got = _jit(lambda p: model.apply({"params": p}, tokens), params)
    spread = float(jnp.max(want) - jnp.min(want))
    assert float(jnp.max(jnp.abs(got - want))) <= 3e-5 * spread


def test_attention_grouping_and_partial_rotary_against_a_loop():
    """The 8-to-1 head grouping and the rotary positions on the first quarter
    of each head, written out per head and per position."""
    cfg, model, params, tokens, _ = _setup(jnp.float32)
    H, KV, D = 8, 1, 16
    rot = 4
    p = params["layer_3"]["self_attn"]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 12, cfg["hidden_size"])), jnp.float32)
    layer = qn.GatedAttention(n_heads=H, n_kv_heads=KV, head_dim=D,
                              dtype=jnp.float32)
    got = layer.apply({"params": p}, x,
                      jnp.arange(12)[None])[0]

    def rms(v, w):
        return v / np.sqrt(np.mean(v * v) + 1e-6) * (1.0 + w)

    def rope(v, t):
        out = np.array(v)
        for i in range(rot // 2):
            angle = t * 1e7 ** (-2.0 * i / rot)
            a, b = v[i], v[i + rot // 2]
            out[i] = a * np.cos(angle) - b * np.sin(angle)
            out[i + rot // 2] = b * np.cos(angle) + a * np.sin(angle)
        return out

    xs = np.asarray(x[0], np.float64)
    w = {k: np.asarray(v["kernel"], np.float64) for k, v in p.items()
         if "kernel" in v}
    qn_w, kn_w = (np.asarray(p[k]["scale"], np.float64)
                  for k in ("q_norm", "k_norm"))
    n = xs.shape[0]
    out = np.zeros((n, H * D))
    for t in range(n):
        qg = (xs[t] @ w["q_proj"]).reshape(H, 2 * D)
        for h in range(H):
            q = rope(rms(qg[h, :D], qn_w), t)
            gate = 1.0 / (1.0 + np.exp(-qg[h, D:]))
            scores, values = [], []
            for s in range(t + 1):
                kv = h // (H // KV)          # the key/value head serving h
                k = (xs[s] @ w["k_proj"]).reshape(KV, D)[kv]
                scores.append(q @ rope(rms(k, kn_w), s) / np.sqrt(D))
                values.append((xs[s] @ w["v_proj"]).reshape(KV, D)[kv])
            pr = np.exp(np.array(scores) - max(scores))
            pr /= pr.sum()
            out[t, h * D:(h + 1) * D] = gate * (pr @ np.array(values))
    np.testing.assert_allclose(got, out @ w["o_proj"], atol=2e-5)


def test_shared_expert_is_counted_once_over_all_shares():
    """Summed over the shares of the experts, with what every chip computes
    alike (the shared expert) counted once, the cut layers give the uncut
    layer."""
    cfg = {**CFG, "num_experts": 8, "first_expert_held": 0}
    params = make_params(family.param_spec(cfg), 5)["layer_0"]["mlp"]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, 32, cfg["hidden_size"])), jnp.float32)
    moe = lambda held, first: qn.SparseMoe(
        n_experts=8, experts_held=held, top_k=2, expert_dim=32, shared_dim=32,
        first_expert=first, dtype=jnp.float32)
    whole = moe(8, 0).apply({"params": params}, x)
    cut = lambda first: {**params, "experts": jax.tree.map(
        lambda w: w[first:first + 2], params["experts"])}
    nothing = {**params, "experts": jax.tree.map(
        lambda w: jnp.zeros_like(w[:2]), params["experts"])}
    shared_only = moe(2, 0).apply({"params": nothing}, x)
    parts = [moe(2, f).apply({"params": cut(f)}, x) for f in (0, 2, 4, 6)]
    total = sum(parts) - 3 * shared_only
    np.testing.assert_allclose(total, whole, atol=2e-5)
    # a token none of whose experts is held gets the shared expert only
    ids = np.asarray(qn.route_top_k(x[0], params["router"]["kernel"],
                                    top_k=2)[1])
    alone = ~np.isin(ids, (0, 1)).any(axis=-1)
    assert alone.any()
    np.testing.assert_allclose(parts[0][0][alone], shared_only[0][alone],
                               atol=1e-6)


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
    for scope in trace.MODEL_SCOPES:
        assert scope in text, scope
    assert notes["gdn_chunk"] == 16 and notes["gdn_heads"] == 4
    assert notes["moe_experts_total"] == 8 and notes["moe_experts_held"] == 4
    load = np.asarray(qn.expert_load(model, params, tokens))
    notes = trace.plan_args()
    assert load.shape == (4, 3)
    assert notes["moe_pairs_held"] == list(load[:, 0])
    assert notes["moe_largest_load"] == list(load[:, 1])
    # a rehearsal's first tile holds its worst case: one tile, whatever falls
    assert notes["moe_tiles_computed"] == list(load[:, 2]) == [1] * 4
    # uniform routing: about k * held / routed of B * T * k pairs, here half
    assert (load[:, 0] > 0.25 * B * T * 2).all()
    assert (load[:, 0] < 0.75 * B * T * 2).all()
    assert (load[:, 1] <= B * T).all()
