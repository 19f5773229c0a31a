"""Adasum numeric tests against the NumPy reference implementation —
parity with ``test/test_adasum_pytorch.py`` / ``test_adasum_tensorflow.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.jax import _shard_map
from horovod_tpu.ops.adasum import (
    adasum_allreduce,
    adasum_allreduce_reference,
)
from horovod_tpu.parallel.mesh import build_mesh


def _spmd_adasum(x, mesh):
    fn = _shard_map(
        lambda t: adasum_allreduce(t),
        mesh,
        in_specs=(P("data"),),
        out_specs=P("data"),
    )
    return jax.jit(fn)(x)


def test_adasum_matches_numpy_reference():
    n = len(jax.devices())
    mesh = build_mesh()
    rng = np.random.RandomState(42)
    per_rank = rng.randn(n, 33).astype(np.float32)
    out = _spmd_adasum(jnp.asarray(per_rank), mesh)
    expected = adasum_allreduce_reference(list(per_rank))
    for r in range(n):
        np.testing.assert_allclose(
            np.asarray(out)[r], expected, rtol=1e-5, atol=1e-6
        )


def test_adasum_parallel_gradients_average():
    """Identical vectors on all ranks must come out ~unchanged (Adasum of
    parallel vectors is an average)."""
    n = len(jax.devices())
    mesh = build_mesh()
    v = np.linspace(1, 2, 17).astype(np.float32)
    per_rank = np.tile(v, (n, 1))
    out = _spmd_adasum(jnp.asarray(per_rank), mesh)
    for r in range(n):
        np.testing.assert_allclose(np.asarray(out)[r], v, rtol=1e-5)


def test_adasum_orthogonal_gradients_sum():
    """Mutually orthogonal vectors must add exactly."""
    n = len(jax.devices())
    mesh = build_mesh()
    per_rank = np.zeros((n, n), dtype=np.float32)
    for r in range(n):
        per_rank[r, r] = float(r + 1)
    out = _spmd_adasum(jnp.asarray(per_rank), mesh)
    expected = np.arange(1, n + 1, dtype=np.float32)
    for r in range(n):
        np.testing.assert_allclose(np.asarray(out)[r], expected, rtol=1e-5)


def test_adasum_zero_vectors():
    n = len(jax.devices())
    mesh = build_mesh()
    per_rank = np.zeros((n, 5), dtype=np.float32)
    out = _spmd_adasum(jnp.asarray(per_rank), mesh)
    np.testing.assert_array_equal(np.asarray(out), per_rank)


def test_adasum_reference_properties():
    # reference impl itself: parallel → average, orthogonal → sum
    a = np.array([1.0, 0.0])
    b = np.array([0.0, 2.0])
    np.testing.assert_allclose(adasum_allreduce_reference([a, b]), [1.0, 2.0])
    np.testing.assert_allclose(adasum_allreduce_reference([a, a]), a)


def test_hierarchical_adasum_matches_numpy_reference():
    """Compiled-mode hierarchical Adasum on a (cross=2, local=4) mesh vs
    the NumPy reference (local RS -> cross VHDD -> local AG, reference
    adasum_cuda_operations.cc)."""
    from horovod_tpu.ops.adasum import (
        hierarchical_adasum_allreduce,
        hierarchical_adasum_reference,
    )
    from horovod_tpu.parallel.mesh import build_hierarchical_mesh

    mesh = build_hierarchical_mesh(local_size=4)
    n = 8
    rng = np.random.RandomState(5)
    vecs = [rng.randn(12).astype(np.float32) * (i + 1) for i in range(n)]
    x = jnp.asarray(np.stack(vecs))

    fn = _shard_map(
        lambda t: hierarchical_adasum_allreduce(
            t[0], local_axis="local", cross_axis="cross"
        )[None],
        mesh,
        in_specs=(P(("cross", "local")),),
        out_specs=P(("cross", "local")),
    )
    out = jax.jit(fn)(x)
    expected = hierarchical_adasum_reference(vecs, local_size=4)
    for r in range(n):
        np.testing.assert_allclose(
            np.asarray(out)[r], expected, rtol=1e-4, atol=1e-5
        )


def test_adasum_reduce_fn_accepts_axis_tuple():
    """adasum_reduce_fn routes a (cross, local) tuple to the hierarchical
    variant instead of raising."""
    from horovod_tpu.ops.adasum import adasum_reduce_fn
    from horovod_tpu.parallel.mesh import build_hierarchical_mesh

    mesh = build_hierarchical_mesh(local_size=2)
    x = jnp.asarray(
        np.random.RandomState(7).randn(8, 6).astype(np.float32)
    )
    fn = _shard_map(
        lambda t: adasum_reduce_fn(t[0], axis_name=("cross", "local"))[None],
        mesh,
        in_specs=(P(("cross", "local")),),
        out_specs=P(("cross", "local")),
    )
    out = np.asarray(jax.jit(fn)(x))
    # all ranks agree
    for r in range(1, 8):
        np.testing.assert_allclose(out[r], out[0], rtol=1e-5)
