"""ZeRO-1 optimizer-state sharding: the sharded schedule (reduce-scatter
grads -> shard-local optax update -> all-gather params) must produce the
SAME training trajectory as the replicated make_train_step, while the
live optimizer state is 1/N per shard."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvdj
from horovod_tpu.parallel.mesh import build_mesh
from horovod_tpu.parallel.zero import init_zero1_state, make_zero1_train_step

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    return build_mesh({"data": N_DEV})


def _problem(seed=0, d=13):  # deliberately not divisible by 8 (padding path)
    rng = np.random.RandomState(seed)
    params = {
        "w": jnp.asarray(rng.randn(d, 3).astype(np.float32)),
        "b": jnp.zeros((3,), jnp.float32),
    }
    X = jnp.asarray(rng.randn(N_DEV * 4, d).astype(np.float32))
    y = jnp.asarray(rng.randn(N_DEV * 4, 3).astype(np.float32))

    def loss_fn(p, batch):
        xb, yb = batch
        return jnp.mean((xb @ p["w"] + p["b"] - yb) ** 2)

    return params, (X, y), loss_fn


@pytest.mark.parametrize("tx_name", ["sgd_momentum", "adamw"])
def test_zero1_matches_replicated_dp(mesh, tx_name):
    tx = (
        optax.sgd(0.1, momentum=0.9)
        if tx_name == "sgd_momentum" else optax.adamw(1e-2)
    )
    params, batch, loss_fn = _problem()

    rep_step = hvdj.make_train_step(loss_fn, tx, mesh, donate=False)
    rep_params = jax.tree.map(jnp.copy, params)
    rep_state = tx.init(rep_params)

    z_step = make_zero1_train_step(loss_fn, tx, mesh, donate=False)
    z_params = jax.tree.map(jnp.copy, params)
    z_state = init_zero1_state(tx, z_params, N_DEV)

    for _ in range(5):
        rep_params, rep_state, rep_loss = rep_step(
            rep_params, rep_state, batch
        )
        z_params, z_state, z_loss = z_step(z_params, z_state, batch)
        np.testing.assert_allclose(
            float(rep_loss), float(z_loss), rtol=1e-6
        )
    for ka in rep_params:
        np.testing.assert_allclose(
            np.asarray(rep_params[ka]), np.asarray(z_params[ka]),
            rtol=1e-5, atol=1e-6,
        )


def test_zero1_state_is_sharded(mesh):
    """The live state leaves carry a leading [n_shards] axis holding 1/N
    of the flat parameter vector each — that is the memory win."""
    params, batch, loss_fn = _problem(d=16)
    tx = optax.adam(1e-3)
    state = init_zero1_state(tx, params, N_DEV)
    total = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    padded = ((total + N_DEV - 1) // N_DEV) * N_DEV
    mus = [
        leaf for leaf in jax.tree.leaves(state)
        if getattr(leaf, "ndim", 0) == 2
    ]
    assert mus, "expected vector state leaves (mu/nu)"
    for leaf in mus:
        assert leaf.shape == (N_DEV, padded // N_DEV), leaf.shape

    step = make_zero1_train_step(loss_fn, tx, mesh, donate=False)
    p2, s2, loss = step(params, state, batch)
    assert np.isfinite(float(loss))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(s2)):
        assert a.shape == b.shape


def test_zero1_quantized_tracks_replicated(mesh):
    """quantized=True ZeRO-1 (int8-wire ring reduce-scatter feeding the
    sharded update) follows the full-precision replicated trajectory
    within quantization noise."""
    tx = optax.sgd(0.05, momentum=0.9)
    params, (X, y), loss_fn = _problem(seed=7, d=29)

    rep_step = hvdj.make_train_step(loss_fn, tx, mesh)
    rep_p, rep_s = jax.tree.map(jnp.copy, params), tx.init(params)

    z_state = init_zero1_state(tx, params, N_DEV, quantized=True)
    z_step = make_zero1_train_step(
        loss_fn, tx, mesh, quantized=True, donate=False
    )
    z_p = jax.tree.map(jnp.copy, params)

    for _ in range(10):
        rep_p, rep_s, _ = rep_step(rep_p, rep_s, (X, y))
        z_p, z_state, _ = z_step(z_p, z_state, (X, y))

    for k in params:
        a, b = np.asarray(rep_p[k]), np.asarray(z_p[k])
        # int8 wire adds noise; the trajectories must stay close.
        assert np.abs(a - b).max() < 5e-3 + 0.02 * np.abs(a).max(), (
            k, np.abs(a - b).max(),
        )


def test_quantized_convergence_tracks_fp32(mesh):
    """End-to-end convergence evidence: the
    int8-wire and int8+ZeRO-1 training curves must track full-precision
    DP — asserted on the final loss after real optimization steps, not a
    per-call error bound. ``utils/convergence.run`` defaults to 300
    steps; this CI version runs fewer."""
    from horovod_tpu.utils import convergence

    result = convergence.run(steps=40, record_every=10)
    final = result["final_loss"]
    # The curves must actually be training...
    assert final["fp32"] < result["curves"]["fp32"][0] * 0.8
    # ...and the lossy paths must land within 5% of fp32.
    assert result["rel_gap_vs_fp32"]["quantized"] < 0.05, final
    assert result["rel_gap_vs_fp32"]["quantized+zero1"] < 0.05, final
