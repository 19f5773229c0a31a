"""Tensor- and pipeline-parallel training: numerics vs single-device
references on the 8-way virtual mesh (TPU-native extensions beyond the
reference's DP-only scope; the graft contract's tp/pp shardings)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu.jax import _shard_map
from horovod_tpu.parallel.mesh import build_mesh
from horovod_tpu.parallel.pp import (
    init_pp_state,
    make_pp_train_step,
    pipeline_apply,
)
from horovod_tpu.parallel.tp import (
    init_tp_state,
    make_tp_train_step,
    shard_mlp_params,
    tp_block_input,
    tp_mlp,
)


def _full_mlp(params_stacked, x):
    """Dense reference: reassemble the full weights from the shards."""
    w1 = jnp.concatenate(list(params_stacked["w1"]), axis=1)
    b1 = jnp.concatenate(list(params_stacked["b1"]), axis=0)
    w2 = jnp.concatenate(list(params_stacked["w2"]), axis=0)
    b2 = jnp.concatenate(list(params_stacked["b2"]), axis=0)
    return jax.nn.gelu(x @ w1 + b1) @ w2 + b2


def test_tp_mlp_forward_matches_dense():
    n = 4
    mesh = build_mesh({"data": 2, "model": n})
    params = shard_mlp_params(jax.random.PRNGKey(0), d_model=8,
                              d_hidden=16, n_shards=n)
    x = jnp.asarray(np.random.RandomState(0).randn(6, 8).astype(np.float32))

    fn = _shard_map(
        lambda p, xb: tp_mlp(jax.tree.map(lambda t: t[0], p), xb,
                             axis_name="model"),
        mesh,
        in_specs=(P("model"), P("data")),
        out_specs=P("data"),
    )
    out = jax.jit(fn)(params, x)
    expected = _full_mlp(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_tp_train_step_matches_dense_reference():
    """One DP x TP SGD step must equal the single-device step on the
    reassembled dense weights (grads of a shard are exactly the dense
    grads' slice; the data axis averages)."""
    n = 4
    mesh = build_mesh({"data": 2, "model": n})
    params = shard_mlp_params(jax.random.PRNGKey(1), d_model=8,
                              d_hidden=16, n_shards=n)
    tx = optax.sgd(0.1)
    opt_state = init_tp_state(tx, params)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 8).astype(np.float32))
    y = jnp.asarray(rng.randn(8, 8).astype(np.float32))

    def loss_fn(p_local, batch):
        xb, yb = batch
        pred = tp_mlp(p_local, xb, axis_name="model")
        return jnp.mean((pred - yb) ** 2)

    step = make_tp_train_step(loss_fn, tx, mesh, donate=False)
    new_params, _, loss = step(params, opt_state, (x, y))

    # Dense reference step.
    def ref_loss(p):
        pred = _full_mlp(p, x)
        return jnp.mean((pred - y) ** 2)

    ref_loss_v, ref_grads = jax.value_and_grad(ref_loss)(params)
    np.testing.assert_allclose(float(loss), float(ref_loss_v), rtol=1e-5)
    # Compare one updated shard against the dense update's slice.
    upd_w1 = np.asarray(new_params["w1"])  # [n, D, F/n]
    ref_w1 = np.asarray(
        jax.tree.map(lambda p, g: p - 0.1 * g, params, ref_grads)["w1"]
    )
    np.testing.assert_allclose(upd_w1, ref_w1, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("check", [True, False])
def test_tp_layer_gradients_match_dense_in_either_shard_map(check):
    """The TP layers read off the trace whether the enclosing shard_map
    types varying axes, and write their f/g conjugates out where it does
    not: two stacked Megatron blocks differentiate to the dense gradients
    (replicated input AND weight shards) checked or unchecked, with
    nothing told to them by the caller."""
    n = 4
    mesh = build_mesh({"model": n}, devices=jax.devices()[:n])
    p1 = shard_mlp_params(jax.random.PRNGKey(2), 8, 16, n)
    p2 = shard_mlp_params(jax.random.PRNGKey(3), 8, 16, n)
    x = jnp.asarray(np.random.RandomState(2).randn(6, 8).astype(np.float32))

    def loss(params, xb, mlp, enter):
        h = xb
        for p in params:
            h = h + mlp(p, enter(h))
        return jnp.mean(h ** 2)

    def local(params, xb):
        params = jax.tree.map(lambda t: t[0], params)
        g_p, g_x = jax.grad(
            lambda p, v: loss(
                p, v, lambda q, h: tp_mlp(q, h, axis_name="model"),
                lambda h: tp_block_input(h, axis_name="model"),
            ),
            argnums=(0, 1),
        )(params, xb)
        return jax.tree.map(lambda t: t[None], g_p), g_x

    g_p, g_x = jax.jit(_shard_map(
        local, mesh, check=check,
        in_specs=(P("model"), P()), out_specs=(P("model"), P()),
    ))((p1, p2), x)

    def dense_loss(params, v):
        return loss(params, v, _full_mlp, lambda h: h)

    d_p, d_x = jax.grad(dense_loss, argnums=(0, 1))((p1, p2), x)
    np.testing.assert_allclose(np.asarray(g_x), np.asarray(d_x),
                               rtol=1e-5, atol=1e-6)
    for got, want in zip(jax.tree.leaves(g_p), jax.tree.leaves(d_p)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def _stage_fn(p, x, s):
    return jax.nn.relu(x @ p["w"] + p["b"])


def _stacked_stage_params(rng, n_stages, d):
    k = jax.random.split(rng, n_stages)
    return {
        "w": jnp.stack([
            jax.random.normal(k[i], (d, d)) * (d ** -0.5)
            for i in range(n_stages)
        ]),
        "b": jnp.zeros((n_stages, d)),
    }


def _ref_pipeline(params_stacked, x_micro):
    y = x_micro
    for i in range(params_stacked["w"].shape[0]):
        p = jax.tree.map(lambda t, i=i: t[i], params_stacked)
        y = jax.vmap(lambda mb: _stage_fn(p, mb, i))(y)
    return y


def test_pipeline_apply_matches_sequential():
    n_stages = 8
    mesh = build_mesh({"stage": n_stages})
    d = 8
    params = _stacked_stage_params(jax.random.PRNGKey(2), n_stages, d)
    x = jnp.asarray(
        np.random.RandomState(2).randn(4, 2, d).astype(np.float32)
    )  # [n_micro, mb, d]

    def run(p, xm):
        outs = pipeline_apply(_stage_fn, jax.tree.map(lambda t: t[0], p),
                              xm, axis_name="stage")
        # Only the last stage holds real outputs; bring them everywhere.
        import jax.numpy as jnp
        from jax import lax

        mask = (lax.axis_index("stage") == n_stages - 1).astype(outs.dtype)
        return lax.psum(outs * mask, "stage")

    fn = _shard_map(run, mesh, in_specs=(P("stage"), P()), out_specs=P())
    out = jax.jit(fn)(params, x)
    expected = _ref_pipeline(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_pp_train_step_matches_sequential_reference():
    n_stages, dp = 4, 2
    mesh = build_mesh({"stage": n_stages, "data": dp})
    d = 8
    params = _stacked_stage_params(jax.random.PRNGKey(3), n_stages, d)
    tx = optax.sgd(0.05)
    opt_state = init_pp_state(tx, params)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(4, 4, d).astype(np.float32))  # [n_micro, B, d]
    y = jnp.asarray(rng.randn(4, 4, d).astype(np.float32))

    def loss_fn(outs, labels):
        return jnp.mean((outs - labels) ** 2)

    step = make_pp_train_step(loss_fn, _stage_fn, tx, mesh, donate=False)
    new_params, _, loss = step(params, opt_state, x, y)

    def ref_loss(p):
        return loss_fn(_ref_pipeline(p, x), y)

    ref_v, ref_g = jax.value_and_grad(ref_loss)(params)
    np.testing.assert_allclose(float(loss), float(ref_v), rtol=1e-5)
    ref_new = jax.tree.map(lambda p, g: p - 0.05 * g, params, ref_g)
    np.testing.assert_allclose(
        np.asarray(new_params["w"]), np.asarray(ref_new["w"]),
        rtol=1e-4, atol=1e-5,
    )


def test_pp_grad_flows_through_all_stages():
    """Every stage's parameters must receive nonzero gradient through the
    backward pipeline (the ppermute transpose chain)."""
    n_stages = 4
    mesh = build_mesh({"stage": n_stages, "data": 2})
    d = 4
    params = _stacked_stage_params(jax.random.PRNGKey(4), n_stages, d)
    tx = optax.sgd(1.0)
    opt_state = init_pp_state(tx, params)
    x = jnp.ones((2, 4, d))
    y = jnp.zeros((2, 4, d))
    step = make_pp_train_step(
        lambda o, l: jnp.mean((o - l) ** 2), _stage_fn, tx, mesh,
        donate=False,
    )
    new_params, _, _ = step(params, opt_state, x, y)
    moved = np.asarray(
        jnp.abs(new_params["w"] - params["w"]).sum(axis=(1, 2))
    )
    assert (moved > 1e-8).all(), f"stages without gradient: {moved}"


def test_tp_attention_matches_dense():
    """Head-sharded attention (QKV column-parallel, flash per local heads,
    output row-parallel) must equal dense multi-head attention on the
    reassembled weights."""
    from horovod_tpu.parallel.ring_attention import reference_attention
    from horovod_tpu.parallel.tp import shard_attention_params, tp_attention

    n = 4
    H, D = 8, 32
    head_dim = D // H
    mesh = build_mesh({"data": 2, "model": n})
    params = shard_attention_params(jax.random.PRNGKey(5), D, H, n)
    x = jnp.asarray(np.random.RandomState(5).randn(4, 8, D)
                    .astype(np.float32) * 0.5)

    fn = _shard_map(
        lambda p, xb: tp_attention(
            jax.tree.map(lambda t: t[0], p), xb, head_dim=head_dim,
            axis_name="model", causal=True,
        ),
        mesh,
        in_specs=(P("model"), P("data")),
        out_specs=P("data"),
    )
    out = jax.jit(fn)(params, x)

    # Dense reference: reassemble wqkv (per-shard q|k|v column groups).
    wq = jnp.concatenate([w[:, : w.shape[1] // 3] for w in params["wqkv"]],
                         axis=1)
    wk = jnp.concatenate(
        [w[:, w.shape[1] // 3: 2 * w.shape[1] // 3] for w in params["wqkv"]],
        axis=1)
    wv = jnp.concatenate([w[:, 2 * w.shape[1] // 3:] for w in params["wqkv"]],
                         axis=1)
    wo = jnp.concatenate(list(params["wo"]), axis=0)
    bo = jnp.concatenate(list(params["bo"]), axis=0)
    B, T, _ = x.shape
    q = (x @ wq).reshape(B, T, H, head_dim)
    k = (x @ wk).reshape(B, T, H, head_dim)
    v = (x @ wv).reshape(B, T, H, head_dim)
    a = reference_attention(q, k, v, causal=True).reshape(B, T, D)
    expected = a @ wo + bo
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def _lm_pp_setup(n_stages=4, dp=2, d=8, vocab=16, mb=2, n_micro=4, seed=5):
    """Toy LM pipeline: embed table -> per-stage MLP -> vocab head + CE."""
    mesh = build_mesh({"stage": n_stages, "data": dp})
    kp = jax.random.split(jax.random.PRNGKey(seed), 4)
    embed_p = {"table": jax.random.normal(kp[0], (vocab, d)) * 0.5}
    stage_p = {"w": jax.random.normal(kp[1], (n_stages, d, d)) * 0.3}
    head_p = {"proj": jax.random.normal(kp[2], (d, vocab)) * 0.5}
    rng = np.random.RandomState(seed)
    tokens = jnp.asarray(
        rng.randint(0, vocab, (n_micro, mb * dp, 6)), jnp.int32
    )
    labels = jnp.asarray(
        rng.randint(0, vocab, (n_micro, mb * dp, 6)), jnp.int32
    )

    def embed_fn(p, tok):
        return p["table"][tok]

    def stage_fn(p, h, s):
        return jnp.tanh(h @ p["w"])

    def head_loss_fn(p, h, lab):
        logits = h @ p["proj"]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, lab
        ).mean()

    params = {"embed": embed_p, "stages": stage_p, "head": head_p}
    return mesh, params, tokens, labels, embed_fn, stage_fn, head_loss_fn


def _lm_ref_loss(params, tokens, labels, n_stages):
    h = params["embed"]["table"][tokens]  # [n_micro, B, T, d]
    for s in range(n_stages):
        h = jnp.tanh(h @ params["stages"]["w"][s])
    logits = h @ params["head"]["proj"]
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels
    ).mean()


@pytest.mark.parametrize("remat", [True, False])
def test_pp_lm_heterogeneous_matches_sequential(remat):
    """Heterogeneous pipeline (embed on stage 0, head+loss on the last
    stage, hidden-only wire) must match the unpipelined model: same loss
    AND the same post-SGD update for embed, every body stage, and head —
    closing the round-3 'homogeneous stages only' limitation."""
    from horovod_tpu.parallel.pp import init_pp_lm_state, make_pp_lm_train_step

    n_stages = 4
    (mesh, params, tokens, labels,
     embed_fn, stage_fn, head_loss_fn) = _lm_pp_setup(n_stages=n_stages)
    tx = optax.sgd(0.1)
    opt_state = init_pp_lm_state(tx, params)
    step = make_pp_lm_train_step(
        embed_fn, stage_fn, head_loss_fn, tx, mesh,
        remat=remat, donate=False,
    )
    new_params, _, loss = step(params, opt_state, tokens, labels)

    ref_v, ref_g = jax.value_and_grad(
        lambda p: _lm_ref_loss(p, tokens, labels, n_stages)
    )(params)
    np.testing.assert_allclose(float(loss), float(ref_v), rtol=1e-5)
    ref_new = jax.tree.map(lambda p, g: p - 0.1 * g, params, ref_g)
    for path, got, want in (
        ("embed", new_params["embed"]["table"], ref_new["embed"]["table"]),
        ("stages", new_params["stages"]["w"], ref_new["stages"]["w"]),
        ("head", new_params["head"]["proj"], ref_new["head"]["proj"]),
    ):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5,
            err_msg=path,
        )


def test_pp_lm_trains_loss_down():
    from horovod_tpu.parallel.pp import init_pp_lm_state, make_pp_lm_train_step

    (mesh, params, tokens, labels,
     embed_fn, stage_fn, head_loss_fn) = _lm_pp_setup()
    tx = optax.adam(3e-2)
    opt_state = init_pp_lm_state(tx, params)
    step = make_pp_lm_train_step(
        embed_fn, stage_fn, head_loss_fn, tx, mesh, donate=False,
    )
    first = None
    for _ in range(12):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        first = first if first is not None else float(loss)
    assert float(loss) < first * 0.9, (first, float(loss))
