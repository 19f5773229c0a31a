"""``models/xing4.py`` against the plain reference
(``benchmark/reference/xing4.py``, which imports nothing of the program) on
seeded weights at a small size: logits, loss and every leaf's gradient; the
share test; Sinkhorn's sums; YaRN's frequencies and the softmax scale against
numbers written out here; the train step; scopes and plan notes.

Tolerances. With float32 products the program and the reference are the same
mathematics in another order (stream-major maps against per-token matrices,
sorted grouped products against a masked loop, flash blocks against one
softmax): gaps are float32 rounding, measured at most 1.2e-6 of the logits'
spread and 6e-6 of a leaf's gradient norm; the limits are some ten times
that. With the model's bfloat16 products every operand is rounded to 2^-9
relative and a top-k choice near a tie flips (128 tokens over 8 experts: one
flip moves an expert's gradient by a tenth): measured 2.4% of the logits'
spread and 16% of a leaf's gradient norm (an expert's ``down``; 4% without
the experts' leaves); the limits are 6% and 30%, which a dropped mix, a wrong
mask or left-out rotary keys exceed by far (they read 1)."""

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

from benchmark.families import xing4 as family  # noqa: E402
from benchmark.reference import xing4 as reference  # noqa: E402
from benchmark.weights import make_params  # noqa: E402
from horovod_tpu.models import xing4 as xm  # noqa: E402

ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
CFG = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 10000, "rope_scaling": ROPE, "rms_norm_eps": 1e-6,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "n_routed_experts": 4, "n_routed_experts_routed": 8,
    "first_expert_held": 2, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2, "vocab_size": 251,
    "initializer_range": 0.02, "route_norm_eps": 1e-20,
    "expert_bias_std": 0.01, "hc_phi_std": 0.02, "hc_b_std": 1.0,
    "train": {},
}
B, T = 2, 64


@functools.lru_cache(maxsize=None)
def _inputs(seed, over=()):
    """Weights and a batch of ``CFG`` with ``over`` from ``seed``: made once
    a process (the tests share them; the one test whose step donates its
    input copies)."""
    cfg = {**CFG, **dict(over)}
    params = make_params(family.param_spec(cfg), seed)
    # norm weights and alpha start at one: move every vector off its initial
    # value so that a leaf the program ignores shows (the drawn ones keep
    # their own draw)
    keys = jax.random.split(jax.random.PRNGKey(seed), 400)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    drawn = ("expert_bias", "'b'")
    leaves = [x + 0.05 * jax.random.normal(k, x.shape)
              if x.ndim == 1 and not any(
                  d in jax.tree_util.keystr(p) for d in drawn)
              else x for (p, x), k in zip(flat, keys)]
    params = jax.tree.unflatten(tree, leaves)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg["vocab_size"], (B, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg["vocab_size"], (B, T)), jnp.int32)
    return cfg, params, tokens, labels


def _setup(dtype, seed=11, **over):
    cfg, params, tokens, labels = _inputs(seed, tuple(sorted(over.items())))
    model = xm.Xing4LM(dataclasses.replace(
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
    assert sorted(params["layer_0"]["mlp"]) == ["w1", "w2", "w3"]
    assert sorted(params["layer_1"]["mlp"]) == ["expert_bias", "experts",
                                                "router"]
    assert "shared_expert" in params["layer_1"]
    assert "shared_expert" not in params["layer_0"]
    assert params["layer_0"]["attn_hc"]["phi"].shape == (4 * 64, 24)
    assert "lm_head" in params                     # untied


@pytest.mark.parametrize("dtype,logit_tol,grad_tol", [
    (jnp.float32, 1e-5, 5e-5), (jnp.bfloat16, 6e-2, 0.3),
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
    assert unread == [f"['layer_{i}']['mlp']['expert_bias']"
                      for i in range(1, 3)]


def test_the_eight_shares_add_up_to_the_uncut_reference_layer():
    """The guide's share test: the routed parts of the eight shares of the
    experts, with the shared expert (which every chip computes alike)
    counted once, add up to what the uncut reference gives for the whole
    sparse layer."""
    cfg = {**CFG, "n_routed_experts": 16, "n_routed_experts_routed": 16,
           "first_expert_held": 0}
    layer = make_params(family.param_spec(cfg), 5)["layer_1"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 48, 64)),
                    jnp.float32)
    whole = (reference.routed(x, layer["mlp"], cfg, "highest")
             + reference._swiglu(x, layer["shared_expert"], "highest"))

    class Sparse(xm.nn.Module):   # the sparse sublayer as the layer calls it
        held: int
        first: int
        shared: bool

        @xm.nn.compact
        def __call__(self, h):
            c = dataclasses.replace(
                family.model_config(cfg), dtype=jnp.float32,
                experts_held=self.held, first_expert=self.first)
            y = xm.SparseMoe(
                n_experts=c.n_experts, experts_held=c.experts_held,
                top_k=c.top_k, expert_dim=c.expert_dim,
                first_expert=c.first_expert, routed_scale=c.routed_scale,
                norm_eps=c.route_norm_eps, dtype=c.dtype, name="mlp")(h)
            if self.shared:
                y = y + xm.DenseMlp(hidden_dim=c.shared_dim, dtype=c.dtype,
                                    name="shared_expert")(h)
            return y

    cut = lambda first: {**layer, "mlp": {**layer["mlp"], "experts": (
        jax.tree.map(lambda w: w[first:first + 2],
                     layer["mlp"]["experts"]))}}
    parts = [Sparse(2, f, shared=f == 0).apply({"params": cut(f)}, x)
             for f in range(0, 16, 2)]
    np.testing.assert_allclose(sum(parts), whole, atol=3e-6)
    np.testing.assert_allclose(
        Sparse(16, 0, shared=True).apply({"params": layer}, x), whole,
        atol=3e-6)
    # the routed weights of a token sum to routed_scaling_factor
    weights, _ = reference.route(x[0], layer["mlp"], cfg)
    np.testing.assert_allclose(weights.sum(-1), 2.0, rtol=1e-6)


def test_sinkhorn_gives_unit_column_sums_and_near_unit_row_sums():
    """Twenty rounds, columns last: a column sums to ``s / (s + hc_eps)``,
    one to within hc_eps and float32 rounding (measured 1.3e-6, limit 1e-5)
    whatever the maps; a row sums to one as far as twenty rounds have
    converged: from maps drawn as the configuration draws them (``b``
    normal(0, 1), entries within e^+-3 of each other) the measured worst row
    is 6e-5 off, limit 1e-3 (one round alone leaves 0.6). From maps ten
    times as wide (the clamp allows +-30) twenty rounds leave a row 0.23 off:
    only the columns are held there."""
    rng = np.random.default_rng(0)
    for width, row_tol in ((1.0, 1e-3), (10.0, None)):
        maps = jnp.asarray(rng.normal(size=(4, 4, 2, 512)) * width,
                           jnp.float32)
        m = xm.sinkhorn(jnp.exp(jnp.clip(maps, -30, 30)), 20, 1e-6)
        assert float(jnp.min(m)) >= 0.0
        np.testing.assert_allclose(m.sum(0), 1.0, atol=1e-5)   # columns
        if row_tol:
            np.testing.assert_allclose(m.sum(1), 1.0, atol=row_tol)
            one = xm.sinkhorn(jnp.exp(maps), 1, 1e-6)
            assert float(jnp.max(jnp.abs(one.sum(1) - 1.0))) > 0.3
        # the reference's rounds on per-token matrices are the same rounds
        r = jnp.exp(jnp.clip(jnp.moveaxis(maps, (0, 1), (2, 3)), -30, 30))
        for _ in range(20):
            r = r / (r.sum(-1, keepdims=True) + 1e-6)
            r = r / (r.sum(-2, keepdims=True) + 1e-6)
        np.testing.assert_allclose(jnp.moveaxis(m, (0, 1), (2, 3)), r,
                                   rtol=2e-5, atol=1e-7)


def test_yarn_frequencies_and_softmax_scale():
    """At the published sizes (rope width 64, theta 10000, factor 64,
    original length 4096, beta 32 and 1): dimension ``i`` turns
    ``4096 / (2 pi 10000^(2 i / 64))`` times in 4096 positions, so indices
    0..10 make more than 32 turns and keep ``10000^(-i / 32)``, indices
    23..31 make fewer than one and are divided by 64, and index ``i`` between
    keeps the share ``1 - (i - 10) / 13``."""
    got = xm.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    turns = 4096 * plain / (2 * np.pi)
    assert (turns[:11] > 32).all() and turns[11] < 32
    assert (turns[23:] < 1).all() and turns[22] > 1
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    keep = 1 - (np.arange(11, 23) - 10) / 13.0
    np.testing.assert_allclose(
        got[11:23], plain[11:23] * (keep + (1 - keep) / 64), rtol=1e-6)
    # written out: the first, the last kept, one blended, the first divided
    np.testing.assert_allclose(
        got[[0, 10, 16, 23, 31]],
        [1.0, 5.6234133e-2, 5.4567312e-3, 2.0836273e-5, 2.0836274e-6],
        rtol=1e-6)
    cfg = {**CFG, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64}
    np.testing.assert_allclose(reference.yarn_inv_freq(cfg), got, rtol=1e-6)
    # 192^-0.5 * (0.1 * ln 64 + 1)^2
    assert xm.softmax_scale(192, 64.0, 1.0) == pytest.approx(0.14468, abs=5e-6)
    assert reference.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64.0) + 1) ** 2)
    # mscale / mscale_all_dim is 1: cos and sin are not rescaled
    assert family.model_config({**cfg, "train": {}}).rope_mscale() == 1.0


def test_the_first_mix_of_identical_streams_ignores_its_read_and_res_maps():
    """Layer 0's attention mix sees the embedding copied n times: its read
    is ``(sum_i H_pre[i]) x``, whose scale the sublayer's RMSNorm removes,
    and its ``H_res`` mixes equal streams with unit row sums. The gradient
    of those 20 of ``phi``'s 24 columns is rounding alone there (which is
    why that leaf's Adam update compares badly: benchmark/cells), and real
    from the next mix on."""
    cfg, model, params, tokens, labels = _setup(jnp.float32)
    g = _jit(jax.grad(_loss(model)), params, tokens, labels)
    size = lambda phi, cols: float(jnp.abs(phi[:, cols]).mean())
    first, later = g["layer_0"]["attn_hc"]["phi"], g["layer_0"]["ffn_hc"]["phi"]
    post = slice(4, 8)
    for cols in (slice(0, 4), slice(8, 24)):
        assert size(first, cols) < 1e-3 * size(first, post)
        assert size(later, cols) > 1e-2 * size(later, post)


def test_trains_through_make_train_step():
    import horovod_tpu.jax as hvd

    # (two layers and three Sinkhorn rounds: the step's compile is most of
    # this test's time)
    cfg, model, params, tokens, labels = _setup(
        jnp.bfloat16, num_hidden_layers=2, hc_sinkhorn_iters=3)
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
    trace.reset_build_ledger()
    text = jax.jit(jax.grad(_loss(model))).lower(
        params, tokens, labels).as_text(debug_info=True)
    notes = trace.plan_args()
    for scope in trace.XING4_SCOPES + ("lm_head", "attention"):
        assert scope in text, scope
    assert trace.XING4_SCOPES[:2] == ("latent_attn", "hc_mix")
    assert notes["attn_qk_width"] == 24 and notes["attn_v_width"] == 16
    assert notes["hc_streams"] == 4 and notes["hc_sinkhorn_iters"] == 20
    assert notes["moe_score"] == "sigmoid" and notes["moe_select_bias"] is True
    assert notes["moe_experts_total"] == 8 and notes["moe_experts_held"] == 4
    # (at width 64 the gather-sum takes its XLA form; the attention does not)
    assert not [f for f in trace.build_ledger()["fallbacks"]
                if f["op"] == "attention"]
    load = np.asarray(xm.expert_load(model, params, tokens))
    assert load.shape == (2, 3)               # the two sparse layers


def test_two_width_dense_fallback_is_recorded():
    """A length with no block divisor takes the dense form at two widths
    too, and says so."""
    from horovod_tpu import trace
    from horovod_tpu.ops import pallas_attention as pa

    trace.reset_build_ledger()
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=(1, 1031, 2, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, 1031, 2, 16)), jnp.float32)
    out = pa.flash_attention_bthd(q, k, v, causal=True, sm_scale=0.2)
    assert out.shape == (1, 1031, 2, 16)
    (record,) = trace.build_ledger()["fallbacks"]
    assert (record["op"], record["reason"]) == ("attention",
                                                "no_block_divisor")
    assert record["shape"]["head_dim"] == 24
    assert record["shape"]["v_head_dim"] == 16
