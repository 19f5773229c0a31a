"""Basics-API tests: init/rank/size, eager ops, handles, errors, timeline.

Models the reference's single-process-degenerate tests (SURVEY.md §4:
"tests also pass with size=1").
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu.common.types import StatusType


def test_init_shutdown_cycle():
    hvd.shutdown()
    assert not hvd.is_initialized()
    hvd.init()
    assert hvd.is_initialized()
    assert hvd.size() == 1
    assert hvd.rank() == 0
    assert hvd.local_rank() == 0
    assert hvd.local_size() == 1
    assert hvd.is_homogeneous()
    # double-init is a no-op, like the reference InitializeHorovodOnce
    hvd.init()
    assert hvd.is_initialized()
    hvd.shutdown()
    assert not hvd.is_initialized()


def test_build_probes(hvd_session):
    assert hvd.xla_built() and hvd.xla_enabled()
    assert not hvd.mpi_built() and not hvd.gloo_built() and not hvd.nccl_built()
    assert not hvd.ddl_built() and not hvd.mlsl_built()
    assert not hvd.mpi_threads_supported()


def test_uninitialized_raises():
    hvd.shutdown()
    with pytest.raises(Exception):
        hvd.size()
    with pytest.raises(Exception):
        hvd.allreduce(jnp.ones((2, 2)))


def test_allreduce_average_sum(hvd_session):
    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    # size=1: average == sum == identity
    np.testing.assert_allclose(hvd.allreduce(x), x)
    np.testing.assert_allclose(hvd.allreduce(x, op=hvd.Sum), x)
    np.testing.assert_allclose(hvd.allreduce(x, average=True), x)
    y = hvd.allreduce(x, op=hvd.Sum, prescale_factor=2.0, postscale_factor=0.5)
    np.testing.assert_allclose(y, x, rtol=1e-6)


def test_allreduce_average_and_op_mutually_exclusive(hvd_session):
    x = jnp.ones((2,))
    with pytest.raises(ValueError):
        hvd.allreduce(x, average=True, op=hvd.Sum)


def test_allreduce_async_poll_synchronize(hvd_session):
    x = jnp.ones((4,), dtype=jnp.float32)
    h = hvd.allreduce_async(x, name="t0")
    out = hvd.synchronize(h)
    np.testing.assert_allclose(out, x)
    assert hvd.poll(h)  # completed handles poll True


def test_duplicate_name_rejected(hvd_session):
    """Parity with the reference duplicate-name guard (common.h:160-163):
    two in-flight ops with one name must fail one of them."""
    x = jnp.ones((2,))
    h1 = hvd.allreduce_async(x, name="dup")
    h2 = hvd.allreduce_async(x, name="dup")
    results = []
    for h in (h1, h2):
        try:
            hvd.synchronize(h)
            results.append("ok")
        except RuntimeError:
            results.append("err")
    assert "ok" in results
    # The second may have been enqueued after the first completed (cycle
    # granularity); only assert failure when both were truly concurrent.
    # To force concurrency, enqueue many pairs:
    failures = 0
    for i in range(20):
        ha = hvd.allreduce_async(x, name="dup2")
        hb = hvd.allreduce_async(x, name="dup2")
        for h in (ha, hb):
            try:
                hvd.synchronize(h)
            except RuntimeError:
                failures += 1
    assert failures >= 1


def test_allgather_broadcast_size1(hvd_session):
    x = jnp.arange(6, dtype=jnp.int32).reshape(2, 3)
    np.testing.assert_array_equal(hvd.allgather(x), x)
    np.testing.assert_array_equal(hvd.broadcast(x, root_rank=0), x)


def test_join_size1(hvd_session):
    hvd.join()  # must not deadlock at size=1


def test_fp16_compression(hvd_session):
    x = jnp.arange(8, dtype=jnp.float32) / 7.0
    out = hvd.allreduce(x, compression=hvd.Compression.fp16)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(out, x, rtol=1e-3)


def test_bf16_compression(hvd_session):
    x = jnp.arange(8, dtype=jnp.float32) / 7.0
    out = hvd.allreduce(x, compression=hvd.Compression.bf16)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(out, x, rtol=1e-2)


def test_timeline_written(tmp_path):
    """Parity with test/test_timeline.py: the trace must contain negotiation
    and op events in chrome-tracing format."""
    import json

    hvd.shutdown()
    fname = str(tmp_path / "timeline.json")
    from horovod_tpu.common.env import Config

    cfg = Config.from_env()
    cfg.timeline_filename = fname
    cfg.timeline_mark_cycles = True
    hvd.init(cfg)
    x = jnp.ones((4,))
    hvd.allreduce(x, name="tl_tensor")
    hvd.shutdown()
    with open(fname) as f:
        events = json.load(f)
    names = {e.get("name") for e in events}
    assert "NEGOTIATE_ALLREDUCE" in names
    assert "XLA_ALLREDUCE" in names
    tensor_threads = [
        e for e in events
        if e.get("ph") == "M" and e.get("args", {}).get("name") == "tl_tensor"
    ]
    assert tensor_threads


def test_topology_from_env(monkeypatch):
    hvd.shutdown()
    monkeypatch.setenv("HOROVOD_RANK", "3")
    monkeypatch.setenv("HOROVOD_SIZE", "8")
    monkeypatch.setenv("HOROVOD_LOCAL_RANK", "3")
    monkeypatch.setenv("HOROVOD_LOCAL_SIZE", "4")
    from horovod_tpu.common import topology

    topo = topology.detect()
    assert topo.rank == 3
    assert topo.size == 8
    assert topo.local_size == 4
    assert topo.cross_rank == 0
    assert topo.cross_size == 2
    assert topo.is_homogeneous
    assert topo.source == "env"


def test_reducescatter_single_process(hvd_session):
    # size=1: the sum is the tensor and the single shard is all of it.
    x = jnp.arange(6, dtype=jnp.float32)
    np.testing.assert_allclose(hvd.reducescatter(x), x)
    np.testing.assert_allclose(hvd.reducescatter(x, op=hvd.Average), x)


def test_reducescatter_rejects_bad_args(hvd_session):
    with pytest.raises(ValueError, match="SUM/AVERAGE"):
        hvd.reducescatter(jnp.ones((4,)), op=hvd.Min)
    with pytest.raises(ValueError, match="dim0"):
        hvd.reducescatter(jnp.float32(1.0))


def test_grouped_allreduce(hvd_session):
    xs = [jnp.full((3,), float(i), jnp.float32) for i in range(4)]
    outs = hvd.grouped_allreduce(xs, op=hvd.Sum)
    assert len(outs) == 4
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o, np.full((3,), float(i)))


def test_grouped_allreduce_async_and_average(hvd_session):
    xs = [jnp.ones((2,), jnp.float32) * i for i in range(3)]
    handles = hvd.grouped_allreduce_async(xs, average=True)
    outs = [hvd.synchronize(h) for h in handles]
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o, np.ones((2,)) * i)


def test_profiler_session_env(tmp_path, monkeypatch):
    """HOROVOD_PROFILER_DIR starts a jax.profiler trace session at init
    and stops it at shutdown; plan executions inside carry the
    hvd_plan_<id> annotation matching the timeline's correlation ids."""
    import os

    import numpy as np

    import horovod_tpu as hvd

    hvd.shutdown()
    monkeypatch.setenv("HOROVOD_PROFILER_DIR", str(tmp_path))
    hvd.init()
    hvd.allreduce(np.ones(4, np.float32), name="prof_t")
    hvd.shutdown()
    monkeypatch.delenv("HOROVOD_PROFILER_DIR")
    # A trace session writes under <dir>/plugins/profile/<ts>/.
    written = [
        os.path.join(dp, f)
        for dp, _, fs in os.walk(tmp_path)
        for f in fs
    ]
    assert written, "profiler session produced no trace files"


def test_tensorflow_keras_alias_module():
    """``horovod_tpu.tensorflow.keras`` mirrors the reference's dual
    import path for the Keras binding."""
    pytest.importorskip("tensorflow")
    import horovod_tpu.keras as hk
    import horovod_tpu.tensorflow.keras as htk

    assert htk.DistributedOptimizer is hk.DistributedOptimizer
    assert htk.callbacks is hk.callbacks
    assert htk.load_model is hk.load_model
    assert htk.elastic.KerasState is hk.elastic.KerasState


def test_the_run_keeps_one_compile_cache():
    """``tests/conftest.py``: one compile cache a run, named by the pid of
    the process that started it so that xdist's workers, its children, share
    it, and holding every program whatever it cost to compile; none is made
    where ``JAX_COMPILATION_CACHE_DIR`` names one. A program compiled a
    second time is read back from it."""
    import conftest
    import jax

    from horovod_tpu import trace

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        assert conftest._RUN_CACHE is None
        return
    worker = "PYTEST_XDIST_WORKER" in os.environ
    assert conftest._RUN_CACHE.endswith(
        f"_{os.getppid() if worker else os.getpid()}")
    assert jax.config.jax_compilation_cache_dir == conftest._RUN_CACHE
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1

    def run_cache_probe(x):
        return jnp.cos(x) * 3

    trace.install_build_listeners()       # what importing the program does
    jax.jit(run_cache_probe)(jnp.ones(13))
    assert any("run_cache_probe" in entry
               for entry in os.listdir(conftest._RUN_CACHE))
    jax.clear_caches()                    # what the next worker starts with
    hits = trace.build_ledger()["cache"]["cache_hits"]
    jax.jit(run_cache_probe)(jnp.ones(13))
    assert trace.build_ledger()["cache"]["cache_hits"] > hits
