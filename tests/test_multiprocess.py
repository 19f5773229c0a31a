"""Real multi-process eager collective tests.

The parity analogue of the reference's CI running pytest under
``mpirun -np 2 -H localhost:2`` (SURVEY.md §4): here `hvdrun` spawns the
ranks, the native core's TCP controller negotiates, and the XLA data plane
(gloo-backed CPU collectives under jax.distributed) moves the data. The
same code path drives TPU pods.
"""

import os
import pickle
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_workers(script_body: str, np_: int = 2, timeout: int = 180,
                 extra_env=None, expect_failure: bool = False):
    """Run a worker script under hvdrun on the CPU backend; returns
    per-rank stdout, or (with ``expect_failure``) the completed launcher
    process without asserting rc == 0."""
    script = textwrap.dedent(script_body)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["HOROVOD_CYCLE_TIME"] = "1"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    env.update(extra_env or {})
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        worker = os.path.join(td, "worker.py")
        with open(worker, "w") as f:
            f.write(script)
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.run", "-np", str(np_),
             "--output-dir", td, sys.executable, worker],
            env=env, cwd=REPO, capture_output=True, timeout=timeout,
        )
        outs = []
        for r in range(np_):
            path = os.path.join(td, f"rank.{r}.out")
            outs.append(open(path).read() if os.path.exists(path) else "")
        errs = [
            open(os.path.join(td, f"rank.{r}.err")).read()
            for r in range(np_)
            if os.path.exists(os.path.join(td, f"rank.{r}.err"))
        ]
    if expect_failure:
        return proc
    assert proc.returncode == 0, (
        f"launcher rc={proc.returncode}\nstdout={proc.stdout.decode()}\n"
        f"stderr={proc.stderr.decode()}\nrank outs={outs}\nrank errs={errs}"
    )
    return outs


pytestmark = pytest.mark.multiproc


def test_allreduce_two_ranks():
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        x = jnp.full((4,), float(hvd.rank() + 1), jnp.float32)
        s = hvd.allreduce(x, op=hvd.Sum)
        a = hvd.allreduce(x, op=hvd.Average)
        print("SUM", np.asarray(s).tolist())
        print("AVG", np.asarray(a).tolist())
        hvd.shutdown()
        """
    )
    for out in outs:
        assert "SUM [3.0, 3.0, 3.0, 3.0]" in out, outs
        assert "AVG [1.5, 1.5, 1.5, 1.5]" in out, outs


def test_allgather_broadcast_two_ranks():
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        r = hvd.rank()
        g = hvd.allgather(jnp.full((2, 2), float(r), jnp.float32))
        b = hvd.broadcast(jnp.full((3,), float(r * 10 + 7), jnp.float32),
                          root_rank=1)
        print("GATHER", np.asarray(g).reshape(-1).tolist())
        print("BCAST", np.asarray(b).tolist())
        hvd.shutdown()
        """
    )
    for out in outs:
        assert "GATHER [0.0, 0.0, 0.0, 0.0, 1.0, 1.0, 1.0, 1.0]" in out, outs
        assert "BCAST [17.0, 17.0, 17.0]" in out, outs


def test_fusion_and_many_tensors_two_ranks():
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        r = hvd.rank()
        handles = [hvd.allreduce_async(jnp.full((8,), float(i + r), jnp.float32),
                                       name=f"grad.{i}", op=hvd.Sum)
                   for i in range(16)]
        outs = [hvd.synchronize(h) for h in handles]
        total = sum(float(o[0]) for o in outs)
        # sum over ranks of (i + r) = 2i + 1 -> total = 2*sum(i) + 16 = 256
        print("TOTAL", total)
        hvd.shutdown()
        """
    )
    for out in outs:
        assert "TOTAL 256.0" in out, outs


def test_join_uneven_ranks():
    """Rank 1 runs fewer steps and joins early; rank 0's later tensors
    reduce with zero-substitution and a participant-aware divisor."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        r = hvd.rank()
        steps = 3 if r == 0 else 1
        for i in range(steps):
            out = hvd.allreduce(jnp.full((2,), float(r + 1), jnp.float32),
                                name=f"step{i}", op=hvd.Sum)
            print(f"STEP{i}", np.asarray(out).tolist())
        hvd.join()
        print("JOINED")
        hvd.shutdown()
        """
    )
    # step0: both ranks -> 1+2=3. steps 1,2: only rank 0 (+zeros) -> 1.
    assert "STEP0 [3.0, 3.0]" in outs[0], outs
    assert "STEP1 [1.0, 1.0]" in outs[0], outs
    assert "STEP2 [1.0, 1.0]" in outs[0], outs
    assert "STEP0 [3.0, 3.0]" in outs[1], outs
    for out in outs:
        assert "JOINED" in out, outs


def test_shape_mismatch_error_two_ranks():
    """Coordinator must detect mismatched shapes and fail BOTH ranks with a
    precondition error (reference test_horovod_allreduce_error)."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        shape = (4,) if hvd.rank() == 0 else (5,)
        try:
            hvd.allreduce(jnp.ones(shape, jnp.float32), name="mismatch")
            print("NO_ERROR")
        except RuntimeError as e:
            print("GOT_ERROR", "shapes" in str(e).lower())
        hvd.shutdown()
        """
    )
    for out in outs:
        assert "GOT_ERROR True" in out, outs


def test_run_api_returns_results():
    from horovod_tpu.run import run as hvd_run

    env = {
        "JAX_PLATFORMS": "cpu",
        "HOROVOD_CYCLE_TIME": "1",
        # the pickled fn lives in this test module
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(__file__), REPO,
             os.environ.get("PYTHONPATH", "")]
        ),
    }

    results = hvd_run(_worker_fn, np=2, env=env)
    assert sorted(results) == [
        (0, 2, [3.0, 3.0]),
        (1, 2, [3.0, 3.0]),
    ]


def _worker_fn():
    import numpy as np
    import jax

    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd

    hvd.init()
    import jax.numpy as jnp

    out = hvd.allreduce(
        jnp.full((2,), float(hvd.rank() + 1), jnp.float32), op=hvd.Sum
    )
    result = (hvd.rank(), hvd.size(), np.asarray(out).tolist())
    hvd.shutdown()
    return result


def test_torch_distributed_optimizer_two_ranks():
    """Hook-driven torch DistributedOptimizer across 2 real ranks: both
    ranks must converge to identical weights (grads averaged)."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import torch
        import horovod_tpu.torch as hvd
        hvd.init()
        torch.manual_seed(0)
        model = torch.nn.Linear(4, 1)
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters())
        hvd.broadcast_parameters(model.state_dict(), root_rank=0)
        # different data per rank
        torch.manual_seed(hvd.rank() + 1)
        X = torch.randn(16, 4); y = torch.randn(16, 1)
        for _ in range(5):
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(model(X), y)
            loss.backward()
            opt.step()
        w = model.weight.detach().numpy().round(6).tolist()
        print("W", w)
        hvd.shutdown()
        """
    )
    w0 = [l for l in outs[0].splitlines() if l.startswith("W ")]
    w1 = [l for l in outs[1].splitlines() if l.startswith("W ")]
    assert w0 and w1
    assert w0 == w1, (w0, w1)


def test_adasum_eager_two_ranks():
    """Eager op=Adasum across 2 real ranks vs the NumPy VHDD reference."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        from horovod_tpu.ops.adasum import adasum_allreduce_reference
        hvd.init()
        import jax.numpy as jnp
        vecs = [np.linspace(1, 2, 8).astype(np.float32),
                np.linspace(-1, 1, 8).astype(np.float32)]
        mine = jnp.asarray(vecs[hvd.rank()])
        out = hvd.allreduce(mine, op=hvd.Adasum, name="adasum0")
        expected = adasum_allreduce_reference(vecs)
        ok = np.allclose(np.asarray(out), expected, rtol=1e-5)
        print("ADASUM_OK", bool(ok))
        hvd.shutdown()
        """
    )
    for out in outs:
        assert "ADASUM_OK True" in out, outs


def test_alltoall_two_ranks():
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        r = hvd.rank()
        # rank r holds rows [r*2, r*2+1] -> after alltoall holds row r from
        # each rank
        x = jnp.asarray(np.arange(r * 2, r * 2 + 2, dtype=np.float32))
        out = hvd.alltoall(x.reshape(2, 1))
        print("A2A", np.asarray(out).reshape(-1).tolist())
        # Uneven splits (later-reference alltoallv API): rank 0 sends
        # [10] to itself and [11, 12] to rank 1; rank 1 sends [20, 21, 22]
        # to rank 0 and nothing to itself.
        data = [np.asarray([10.0, 11.0, 12.0], np.float32),
                np.asarray([20.0, 21.0, 22.0], np.float32)][r]
        splits = [[1, 2], [3, 0]][r]
        got, rs = hvd.alltoall(data, splits=splits, name="a2av")
        print("A2AV", np.asarray(got).tolist(), np.asarray(rs).tolist())
        # Zero-row edge: nobody sends anything.
        e, ers = hvd.alltoall(np.zeros((0, 2), np.float32),
                              splits=[0, 0], name="a2av.empty")
        print("A2AVE", tuple(e.shape), np.asarray(ers).tolist())
        hvd.shutdown()
        """
    )
    assert "A2A [0.0, 2.0]" in outs[0], outs
    assert "A2A [1.0, 3.0]" in outs[1], outs
    assert "A2AV [10.0, 20.0, 21.0, 22.0] [1, 3]" in outs[0], outs
    assert "A2AV [11.0, 12.0] [2, 0]" in outs[1], outs
    for out in outs:
        assert "A2AVE (0, 2) [0, 0]" in out, outs


def test_eager_latency_knobs_disabled_path():
    """HOROVOD_INLINE_SYNC=0 / HOROVOD_FLUSH_HINT=0 restore the
    executor-thread-only consumption and the plain fusion grace; the
    kill switches must keep producing correct numerics (they are the
    documented escape hatch if the round-5 fast paths misbehave on
    some backend)."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        for i in range(4):
            r = hvd.allreduce(jnp.full((8,), float(hvd.rank() + 1)),
                              op=hvd.Sum, name=f'k{i}')
        g = hvd.allgather(jnp.full((2,), float(hvd.rank())), name='kg')
        print('KNOBS', float(np.asarray(r)[0]),
              np.asarray(g).reshape(-1).tolist())
        hvd.shutdown()
        """,
        extra_env={"HOROVOD_INLINE_SYNC": "0", "HOROVOD_FLUSH_HINT": "0"},
    )
    for out in outs:
        assert "KNOBS 3.0 [0.0, 0.0, 1.0, 1.0]" in out, outs


def test_alltoallv_skewed_splits_bounded_carrier():
    """A heavily skewed split (one destination 1000x the
    others) must NOT allocate an O(n * max_split) carrier — the chunked
    exchange caps the carrier near k * total/n rows and moves the hot
    block over multiple rounds, with results identical to the naive
    pad-to-max path."""
    outs = _run_workers(
        """
        import os
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        # factor 1 so the cap bites at n=2 (with the default k=4 the cap
        # k*total/n only beats the naive n*max carrier once n > k).
        os.environ['HOROVOD_ALLTOALLV_CARRIER_FACTOR'] = '1'
        import horovod_tpu as hvd
        hvd.init()
        r = hvd.rank()
        # rank 0 sends 1 row to itself and 1000 rows to rank 1;
        # rank 1 sends 1 row each way. max_split=1000, total=1003.
        if r == 0:
            data = np.arange(1001, dtype=np.float32).reshape(1001, 1)
            splits = [1, 1000]
        else:
            data = np.asarray([[5000.0], [6000.0]], np.float32)
            splits = [1, 1]
        got, rs = hvd.alltoall(data, splits=splits, name='a2av.skew')
        carrier = hvd.alltoall._last_carrier_rows
        # Unchunked would be n*max = 2000 carrier rows; the capped
        # carrier is 2*ceil(1003/4) = 502, over 4 rounds.
        print('SKEW', r, np.asarray(rs).tolist(), float(np.asarray(got).sum()),
              tuple(np.asarray(got).shape), carrier)
        assert carrier <= 502, carrier
        hvd.shutdown()
        """
    )
    # rank 0 receives rows [0] (from itself) + [5000] -> sum 5000.0,
    # shape (2, 1); rank 1 receives rows 1..1000 (sum 500500) + [6000].
    assert "SKEW 0 [1, 1] 5000.0 (2, 1)" in outs[0], outs
    assert "SKEW 1 [1000, 1] 506500.0 (1001, 1)" in outs[1], outs


def test_reducescatter_two_ranks():
    """Eager reducescatter (TPU-native extension): sum across ranks,
    rank r keeps dim0 shard r; AVERAGE divides by participant count.
    Uneven dim0 takes Allgatherv-parity split sizes (later-reference
    reducescatter): earlier ranks absorb the remainder rows."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        r = hvd.rank()
        x = jnp.asarray(np.arange(4, dtype=np.float32) + r)  # [r,1+r,2+r,3+r]
        s = hvd.reducescatter(x, op=hvd.Sum)        # sum=[1,3,5,7]; shard 2
        a = hvd.reducescatter(x, op=hvd.Average)
        print("RS", np.asarray(s).tolist())
        print("RSAVG", np.asarray(a).tolist())
        # Uneven: sum=[1,3,5]; rank0 keeps 2 rows, rank1 keeps 1.
        u = hvd.reducescatter(
            jnp.asarray(np.arange(3, dtype=np.float32) + r), name="uneven")
        print("RSU", np.asarray(u).tolist())
        # Uneven 2-D, device-resident input, on-device output shard.
        d = jax.device_put(np.full((5, 2), float(r + 1), np.float32))
        du = hvd.reducescatter(d, name="uneven2d")
        print("RSU2D", np.asarray(du).sum().item(), tuple(du.shape))
        hvd.shutdown()
        """
    )
    assert "RS [1.0, 3.0]" in outs[0], outs
    assert "RS [5.0, 7.0]" in outs[1], outs
    assert "RSAVG [0.5, 1.5]" in outs[0], outs
    assert "RSAVG [2.5, 3.5]" in outs[1], outs
    assert "RSU [1.0, 3.0]" in outs[0], outs
    assert "RSU [5.0]" in outs[1], outs
    # sum over ranks = 3.0 per element; rank0: 3 rows x 2 cols x 3 = 18,
    # rank1: 2 rows x 2 cols x 3 = 12.
    assert "RSU2D 18.0 (3, 2)" in outs[0], outs
    assert "RSU2D 12.0 (2, 2)" in outs[1], outs


_FAKE_GRID_PROLOGUE = """
        import os
        # Fake a 2-host x 2-rank grid on localhost so the (cross, local)
        # mesh exists — the eager analogue of the reference's LOCAL/CROSS
        # communicator pair (mpi_context.cc:149-158).
        _r = int(os.environ['HOROVOD_RANK'])
        os.environ['HOROVOD_LOCAL_SIZE'] = '2'
        os.environ['HOROVOD_LOCAL_RANK'] = str(_r % 2)
        os.environ['HOROVOD_CROSS_SIZE'] = '2'
        os.environ['HOROVOD_CROSS_RANK'] = str(_r // 2)
"""


def test_hierarchical_allreduce_eager_four_ranks():
    """HOROVOD_HIERARCHICAL_ALLREDUCE flips the eager lowering to
    RS->cross-psum->AG on the (cross, local) mesh (reference op selection,
    operations.cc:142-223 / nccl_operations.cc:348-355) with identical
    numerics to the flat op."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        """ + _FAKE_GRID_PROLOGUE + """
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        r = hvd.rank()
        x = jnp.arange(6, dtype=jnp.float32) + r
        s = hvd.allreduce(x, op=hvd.Sum, name="hier_sum")
        a = hvd.allreduce(x, op=hvd.Average, name="hier_avg")
        # hierarchical mesh really exists in the executor
        from horovod_tpu import _runtime
        print("MESH2", _runtime.executor._mesh2 is not None)
        print("SUM", np.asarray(s).tolist())
        print("AVG", np.asarray(a).tolist())
        hvd.shutdown()
        """,
        np_=4,
        extra_env={"HOROVOD_HIERARCHICAL_ALLREDUCE": "1"},
        timeout=240,
    )
    # sum over r in 0..3 of (i + r) = 4i + 6
    expected_sum = [4.0 * i + 6.0 for i in range(6)]
    expected_avg = [i + 1.5 for i in range(6)]
    for out in outs:
        assert "MESH2 True" in out, outs
        assert f"SUM {expected_sum}" in out, outs
        assert f"AVG {expected_avg}" in out, outs


def test_hierarchical_allgather_and_adasum_four_ranks():
    """HOROVOD_HIERARCHICAL_ALLGATHER two-stage gather keeps rank order;
    eager Adasum on the grid runs the hierarchical variant (local RS ->
    cross VHDD -> local AG, reference adasum_cuda_operations.cc) and
    matches the NumPy reference."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        """ + _FAKE_GRID_PROLOGUE + """
        import horovod_tpu as hvd
        from horovod_tpu.ops.adasum import hierarchical_adasum_reference
        hvd.init()
        import jax.numpy as jnp
        r = hvd.rank()
        g = hvd.allgather(jnp.full((2, 2), float(r), jnp.float32))
        print("GATHER", np.asarray(g)[:, 0].tolist())
        vecs = [np.linspace(1, 2, 8).astype(np.float32) * (i + 1)
                for i in range(4)]
        out = hvd.allreduce(jnp.asarray(vecs[r]), op=hvd.Adasum,
                            name="hadasum")
        # Executor prescales by 1/local_size so VHDD runs on node averages
        # (flat-consistent semantics; reference framework-layer divisor).
        expected = hierarchical_adasum_reference(
            [v / 2.0 for v in vecs], local_size=2)
        print("ADASUM_OK", bool(np.allclose(np.asarray(out), expected,
                                            rtol=1e-4)))
        hvd.shutdown()
        """,
        np_=4,
        extra_env={"HOROVOD_HIERARCHICAL_ALLGATHER": "1"},
        timeout=240,
    )
    gather = [0.0, 0.0, 1.0, 1.0, 2.0, 2.0, 3.0, 3.0]
    for out in outs:
        assert f"GATHER {gather}" in out, outs
        assert "ADASUM_OK True" in out, outs


def test_uneven_allgather_two_ranks():
    """Different dim0 per rank: the coordinator's rank_sizes drive the
    pad+compact Allgatherv path (reference mpi_operations.cc:83-162)."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        r = hvd.rank()
        rows = 1 if r == 0 else 3
        x = jnp.full((rows, 2), float(r + 1), jnp.float32)
        g = hvd.allgather(x, name="uneven")
        print("SHAPE", list(np.asarray(g).shape))
        print("COL", np.asarray(g)[:, 0].tolist())
        hvd.shutdown()
        """
    )
    for out in outs:
        assert "SHAPE [4, 2]" in out, outs
        assert "COL [1.0, 2.0, 2.0, 2.0]" in out, outs


def test_timeline_two_ranks(tmp_path):
    """Each rank writes its own chrome-trace via the C++ writer."""
    import json

    td = str(tmp_path)
    outs = _run_workers(
        f"""
        import os, numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        os.environ['HOROVOD_TIMELINE'] = (
            '{td}/tl.' + os.environ['HOROVOD_RANK'] + '.json')
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        hvd.allreduce(jnp.ones((4,), jnp.float32), name='tl_t')
        hvd.shutdown()
        print('TL_DONE')
        """
    )
    for r in range(2):
        with open(f"{td}/tl.{r}.json") as f:
            events = json.load(f)
        names = {e.get("name") for e in events}
        assert "XLA_ALLREDUCE" in names, (r, sorted(names))
        # Plan correlation id (SURVEY §5 timeline<->XLA interop): every
        # executed plan's Begin event carries args.plan = hvd_plan_<id>,
        # the same string the executor annotates into any active
        # jax.profiler trace.
        plan_ids = {
            e["args"]["plan"]
            for e in events
            if e.get("ph") == "B" and "plan" in e.get("args", {})
        }
        assert any(p.startswith("hvd_plan_") for p in plan_ids), (
            r, events[:10],
        )


def test_spark_gated():
    import horovod_tpu.spark as hvds

    if hvds._SPARK_AVAILABLE:
        pytest.skip("pyspark installed; gating path not reachable")
    with pytest.raises(ImportError, match="pyspark"):
        hvds.run(lambda: 0)


def test_spark_run_real_engine():
    """Real local-mode pyspark end-to-end (reference ``test/test_spark.py``
    role, driving ``horovod/spark/__init__.py:36-235``):
    ``horovod_tpu.spark.run`` maps a barrier stage onto the KV-rendezvous
    launcher primitives, every task ``hvd.init()``s and allreduces, and
    per-task results come back in rank order. Skips only when pyspark is
    ABSENT — so installing the engine ADDS coverage (the
    old tests skipped when it was present, inverting coverage)."""
    pyspark = pytest.importorskip("pyspark")

    import horovod_tpu.spark as hvds

    conf = pyspark.SparkConf().setMaster("local[2]").setAppName("hvd-test")
    sc = pyspark.SparkContext.getOrCreate(conf)
    try:
        def fn():
            import os  # noqa: F401

            import jax

            jax.config.update("jax_platforms", "cpu")
            import numpy as _np

            import horovod_tpu as hvd

            hvd.init()
            import jax.numpy as jnp

            s = float(_np.asarray(
                hvd.allreduce(jnp.ones((2,), jnp.float32), op=hvd.Sum,
                              name="spark.s")
            )[0])
            rank, size = hvd.rank(), hvd.size()
            hvd.shutdown()
            return (rank, size, s)

        results = hvds.run(fn, num_proc=2)
    finally:
        sc.stop()
    assert sorted(r[0] for r in results) == [0, 1], results
    assert all(r[1] == 2 and r[2] == 2.0 for r in results), results


def test_autotune_params_propagate_and_stick_two_ranks():
    """Rank 0 tunes; the verdict must carry (cycle, fusion) to rank 1 and,
    after the sample budget, freeze — both ranks end at identical tuned
    values (reference Controller::SynchronizeParameters,
    controller.cc:33-47)."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        for i in range(150):
            hvd.allreduce(np.ones(64, np.float32), name=f"t{i}",
                          op=hvd.Sum)
        from horovod_tpu.common.basics import NativeCore
        lib = NativeCore().lib
        print("TUNED", round(float(lib.hvd_core_cycle_time_ms()), 4),
              int(lib.hvd_core_fusion_threshold()),
              int(lib.hvd_core_tuned_flags()))
        hvd.shutdown()
        """,
        extra_env={
            "HOROVOD_AUTOTUNE": "1",
            "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "0",
            "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "1",
        },
        timeout=300,
    )
    tuned = [l for out in outs for l in out.splitlines()
             if l.startswith("TUNED")]
    assert len(tuned) == 2, outs
    # Identical tuned state on both ranks, and moved off the default
    # (cycle 5.0ms / fusion 64MB would mean the sync never happened; the
    # worker env sets cycle=1 via _run_workers, so any propagation shows).
    assert tuned[0] == tuned[1], tuned
    flags = int(tuned[0].split()[-1])
    assert flags >= 0


def test_autotune_categorical_grid_four_ranks():
    """With a (cross, local) grid the tuner explores the hierarchical dims;
    every plan must carry verdict-consistent tuned_flags so all ranks
    compile the same lowering — numerics stay correct throughout the
    exploration sweep."""
    outs = _run_workers(
        _FAKE_GRID_PROLOGUE + """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        r = hvd.rank()
        # 28 GP samples x 5 scores need 140 plans; 90 iters x 2 ops = 180,
        # so the tuner converges and pins before the final flag read
        # (pre-convergence reads race rank 0's still-moving proposals).
        for i in range(90):
            out = hvd.allreduce(
                np.full((32,), float(r + 1), np.float32),
                name=f"g{i}", op=hvd.Sum)
            assert np.allclose(out, 1.0 + 2.0 + 3.0 + 4.0), (i, out[:4])
            ga = hvd.allgather(
                np.full((2, 2), float(r), np.float32), name=f"ag{i}")
            assert ga.shape == (8, 2) and np.allclose(
                ga[2 * r], float(r)), (i, ga)
        from horovod_tpu.common.basics import NativeCore
        lib = NativeCore().lib
        print("FLAGS", int(lib.hvd_core_tuned_flags()))
        hvd.shutdown()
        """,
        np_=4,
        extra_env={
            "HOROVOD_AUTOTUNE": "1",
            "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "0",
            "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "1",
        },
        timeout=300,
    )
    flags = [l for out in outs for l in out.splitlines()
             if l.startswith("FLAGS")]
    assert len(flags) == 4 and len(set(flags)) == 1, (flags, outs)


def test_tensorflow_gradient_tape_two_ranks():
    """A TF DistributedGradientTape step across 2 real ranks: per-rank
    losses differ, the tape allreduces the gradients (Average), and both
    ranks apply the identical averaged update (the reference runs every
    framework suite under mpirun -np 2, Dockerfile.test.cpu:52)."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import tensorflow as tf
        import horovod_tpu.tensorflow as hvd
        hvd.init()
        r = hvd.rank()
        w = tf.Variable(np.zeros(2, np.float32))
        # loss_r = sum(w * (r+1)) -> dL/dw = r+1; averaged -> 1.5
        with hvd.DistributedGradientTape(tf.GradientTape()) as tape:
            loss = tf.reduce_sum(w * float(r + 1))
        (g,) = tape.gradient(loss, [w])
        print("GRAD", np.asarray(g).tolist())
        # broadcast_variables parity: rank 0's weights win
        w.assign(np.full(2, float(r * 10 + 1), np.float32))
        hvd.broadcast_variables([w], root_rank=0)
        print("BCASTED", w.numpy().tolist())
        hvd.shutdown()
        """,
        timeout=240,
    )
    for out in outs:
        assert "GRAD [1.5, 1.5]" in out, outs
        assert "BCASTED [1.0, 1.0]" in out, outs


def test_keras_fit_two_ranks():
    """Keras fit() across 2 ranks: DistributedOptimizer averages the
    gradients, the broadcast callback syncs rank 0's init, and both ranks
    converge to identical weights on a deterministic least-squares
    problem."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import tensorflow as tf
        import horovod_tpu.keras as hvdk
        import horovod_tpu.tensorflow as hvd
        hvd.init()
        r = hvd.rank()
        tf.keras.utils.set_random_seed(1234 + r)  # deliberately different
        model = tf.keras.Sequential(
            [tf.keras.layers.Dense(1, use_bias=False, input_shape=(4,))]
        )
        opt = hvdk.DistributedOptimizer(
            tf.keras.optimizers.SGD(learning_rate=0.05)
        )
        model.compile(optimizer=opt, loss="mse")
        rng = np.random.RandomState(7)  # same data on both ranks
        X = rng.randn(64, 4).astype(np.float32)
        y = (X @ np.array([[1.0], [-2.0], [0.5], [3.0]],
                          np.float32)).astype(np.float32)
        model.fit(
            X, y, epochs=8, batch_size=16, verbose=0,
            callbacks=[hvdk.callbacks.BroadcastGlobalVariablesCallback(0)],
        )
        wt = model.layers[0].kernel.numpy().reshape(-1)
        print("W", " ".join(f"{v:.4f}" for v in wt))
        hvd.shutdown()
        """,
        timeout=300,
    )
    ws = [l for out in outs for l in out.splitlines() if l.startswith("W ")]
    assert len(ws) == 2, outs
    # Ranks started from different seeds; the broadcast + averaged grads
    # must keep them bit-identical through training.
    assert ws[0] == ws[1], ws
    vals = [float(v) for v in ws[0].split()[1:]]
    expect = [1.0, -2.0, 0.5, 3.0]
    assert all(abs(a - b) < 0.5 for a, b in zip(vals, expect)), vals


def test_topology_metadata_drives_hierarchical_mesh_four_ranks():
    """End-to-end closure of the slice-metadata path: derive the
    (cross, local) grid from simulated 2-slice metadata via
    topology_from_slice_metadata (NOT hand-set HOROVOD_LOCAL_*/CROSS_*
    env), hand it to XlaPlanExecutor, and run a hierarchical allreduce
    plan through the resulting _mesh2."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()  # brings up jax.distributed across the 4 ranks
        r = hvd.rank()
        from horovod_tpu.common.topology import topology_from_slice_metadata
        from horovod_tpu.common.types import TensorTableEntry, ReduceOp
        from horovod_tpu.core.xla_executor import XlaPlanExecutor

        # Simulated multi-slice pod metadata: 2 slices x 2 processes.
        pairs = [(0, 0), (1, 0), (2, 1), (3, 1)]
        topo = topology_from_slice_metadata(r, pairs)
        assert topo.local_size == 2 and topo.cross_size == 2, topo
        ex = XlaPlanExecutor(topo)
        assert ex._mesh2 is not None, "hierarchical mesh not built"

        plan = {"type": 0, "op": int(ReduceOp.SUM), "participants": 4,
                "tuned_flags": 1}  # bit0: hierarchical_allreduce on
        entries = [TensorTableEntry(
            name="h", tensor=np.full((6,), float(r + 1), np.float32))]
        out = ex.execute(plan, entries, topo)["h"]
        print("HIER", np.asarray(out)[:2].tolist())
        hvd.shutdown()
        """,
        np_=4,
    )
    for out in outs:
        assert "HIER [10.0, 10.0]" in out, outs


def test_allreduce_dtype_sweep_two_ranks():
    """Op-correctness across the dtype table (reference test strategy:
    every collective x dtype, test_tensorflow.py:123-380). Exercises the
    XLA executor's pack/collective/unpack for each wire dtype at a real
    communicator size, including the device-resident jax path for bf16."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import jax.numpy as jnp
        import horovod_tpu as hvd
        hvd.init()
        r = hvd.rank()
        checks = []
        for name in ("uint8", "int16", "int32", "int64", "float16",
                     "float32", "float64"):
            x = np.full((5,), r + 1, dtype=name)
            out = np.asarray(hvd.allreduce(x, op=hvd.Sum, name=f"dt.{name}"))
            # dtype must survive the wire (64-bit computes in 32-bit but
            # the executor restores the caller's dtype).
            checks.append((name, bool((out == 3).all())
                           and out.dtype == np.dtype(name)))
        xb = jnp.full((5,), float(r + 1), jnp.bfloat16)
        ob = hvd.allreduce(xb, op=hvd.Sum, name="dt.bf16")
        checks.append(("bfloat16", bool(
            np.allclose(np.asarray(ob, np.float32), 3.0))))
        bad = [n for n, ok in checks if not ok]
        print("DTYPES_OK" if not bad else f"DTYPES_BAD {bad}")
        # MIN/MAX on ints (reference covers non-sum ops too)
        mn = np.asarray(hvd.allreduce(
            np.full((3,), r + 1, np.int32), op=hvd.Min, name="dt.min"))
        mx = np.asarray(hvd.allreduce(
            np.full((3,), r + 1, np.int32), op=hvd.Max, name="dt.max"))
        print("MINMAX", int(mn[0]), int(mx[0]))
        hvd.shutdown()
        """
    )
    for out in outs:
        assert "DTYPES_OK" in out, outs
        assert "MINMAX 1 2" in out, outs


def test_worker_crash_terminates_job_cleanly():
    """Failure detection at the launcher level (the reference horovodrun
    contract): a rank that dies mid-job must bring the whole job down
    promptly with a clear report — the surviving rank is terminated, the
    launcher exits non-zero, and nothing hangs."""
    import time as _time

    script = """
        import os, sys, time
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        r = hvd.rank()
        out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name="ok")
        assert np.allclose(out, 2.0)
        if r == 1:
            print("RANK1 EXITING", flush=True)
            os._exit(7)  # simulate a crash: no shutdown handshake
        # Rank 0 would block here forever without failure propagation.
        for i in range(1000):
            hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                          name=f"after.{i}")
            time.sleep(0.05)
    """
    t0 = _time.monotonic()
    proc = _run_workers(script, timeout=120, expect_failure=True)
    dt = _time.monotonic() - t0
    stderr = proc.stderr.decode()
    assert proc.returncode != 0
    assert "exit code 7" in stderr and "terminating" in stderr, stderr
    assert dt < 90, f"job did not come down promptly: {dt:.0f}s"


def test_torch_adasum_optimizer_two_ranks():
    """Delta-space Adasum optimizer across 2 real ranks (reference
    ``horovod/torch/__init__.py:211-379``): each rank SGD-steps on its own
    gradient, and the applied update must equal the NumPy VHDD reference
    combine of the two local deltas."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import torch
        import horovod_tpu.torch as hvd
        from horovod_tpu.ops.adasum import adasum_allreduce_reference
        hvd.init()
        r = hvd.rank()
        torch.manual_seed(0)
        model = torch.nn.Linear(4, 1, bias=False)
        hvd.broadcast_parameters(model.state_dict(), root_rank=0)
        w0 = model.weight.detach().clone()
        lr = 0.1
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=lr),
            named_parameters=model.named_parameters(), op=hvd.Adasum,
        )
        # Deterministic per-rank batch -> known local gradient/delta.
        X = torch.eye(4)[: 4]
        y = torch.full((4, 1), float(r + 1))
        opt.zero_grad()
        torch.nn.functional.mse_loss(model(X), y).backward()
        grad = model.weight.grad.detach().clone()
        opt.step()
        local_delta = (-lr * grad).numpy().ravel()
        # Reconstruct both ranks' deltas: grad depends on y = r+1.
        deltas = []
        for rr in range(2):
            yy = torch.full((4, 1), float(rr + 1))
            ww = w0.clone().requires_grad_(True)
            loss = torch.nn.functional.mse_loss(X @ ww.t(), yy)
            g, = torch.autograd.grad(loss, ww)
            deltas.append((-lr * g).numpy().ravel())
        assert np.allclose(deltas[r], local_delta, atol=1e-6)
        expected = w0.numpy().ravel() + adasum_allreduce_reference(deltas)
        got = model.weight.detach().numpy().ravel()
        ok = np.allclose(got, expected, rtol=1e-5, atol=1e-6)
        print("TORCH_ADASUM_OK", bool(ok))
        hvd.shutdown()
        """
    )
    for out in outs:
        assert "TORCH_ADASUM_OK True" in out, outs


def test_tf_adasum_optimizer_two_ranks():
    """TF delta-space Adasum across 2 real ranks: the applied update must
    equal the NumPy VHDD reference combine of the two ranks' local SGD
    deltas (reference ``tensorflow/__init__.py:313-407``)."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import tensorflow as tf
        import horovod_tpu.tensorflow as hvd
        from horovod_tpu.ops.adasum import adasum_allreduce_reference
        hvd.init()
        r = hvd.rank()
        w = tf.Variable([[1.0, 2.0], [3.0, 4.0]])
        hvd.broadcast_variables([w], root_rank=0)
        w0 = w.numpy().copy()
        lr = 0.1
        opt = hvd.DistributedOptimizer(
            tf.keras.optimizers.SGD(lr), op=hvd.Adasum
        )
        x = tf.eye(2)
        y = tf.fill((2, 2), float(r + 1))
        with tf.GradientTape() as tape:
            loss = tf.reduce_mean((tf.matmul(x, w) - y) ** 2)
        g = tape.gradient(loss, [w])
        opt.apply_gradients(zip(g, [w]))
        # Reconstruct both ranks' deltas from the shared start point.
        deltas = []
        for rr in range(2):
            yy = np.full((2, 2), float(rr + 1), np.float32)
            grad = (2.0 / 4.0) * (w0 - yy)  # d/dw mean((w-y)^2), eye(2) x
            deltas.append((-lr * grad).ravel())
        expected = w0.ravel() + adasum_allreduce_reference(deltas)
        got = w.numpy().ravel()
        ok = np.allclose(got, expected, rtol=1e-5, atol=1e-6)
        print("TF_ADASUM_OK", bool(ok), got.tolist(), expected.tolist())
        hvd.shutdown()
        """
    )
    for out in outs:
        assert "TF_ADASUM_OK True" in out, outs


def test_allgather_object_two_ranks():
    """Per-rank picklables of DIFFERENT sizes gather into the same
    rank-ordered list everywhere (rides the Allgatherv-parity path)."""
    outs = _run_workers(
        """
        import jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu.torch as hvd
        hvd.init()
        r = hvd.rank()
        objs = hvd.allgather_object({"rank": r, "pad": "z" * (10 + 100 * r)})
        ok = (len(objs) == 2
              and objs[0]["rank"] == 0 and len(objs[0]["pad"]) == 10
              and objs[1]["rank"] == 1 and len(objs[1]["pad"]) == 110)
        print("GATHER_OBJ_OK", bool(ok))
        hvd.shutdown()
        """
    )
    for out in outs:
        assert "GATHER_OBJ_OK True" in out, outs


def test_tf_graph_native_collectives_two_ranks():
    """tf.function collectives across 2 real ranks execute as graph-native
    HorovodTpu* AsyncOpKernel nodes — the concrete graph contains NO
    PyFunc/EagerPyFunc — and match eager numerics (reference parity:
    the compiled custom-op path of tensorflow/mpi_ops.cc:287-339).
    Covers a full DistributedGradientTape step, graph allgather with
    uneven dim0, and graph broadcast."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import tensorflow as tf
        import horovod_tpu.tensorflow as hvd
        from horovod_tpu.tensorflow import graph_ops
        hvd.init()
        assert graph_ops.available(), "graph-native op library must build"
        r = hvd.rank()

        w = tf.Variable(np.zeros(2, np.float32))
        opt = tf.keras.optimizers.SGD(1.0)

        @tf.function
        def train_step():
            with hvd.DistributedGradientTape(tf.GradientTape()) as tape:
                loss = tf.reduce_sum(w * float(r + 1))
            grads = tape.gradient(loss, [w])
            opt.apply_gradients(zip(grads, [w]))
            return loss

        train_step()
        # Concrete graph must be PyFunc-free and contain the native node.
        gdef = train_step.get_concrete_function().graph.as_graph_def()
        types = set()
        def walk(g):
            for n in g.node:
                types.add(n.op)
        walk(gdef)
        for f in gdef.library.function:
            for n in f.node_def:
                types.add(n.op)
        assert not any("PyFunc" in t for t in types), sorted(types)
        assert any(t.startswith("HorovodTpu") for t in types), sorted(types)
        print("STEP_W", w.numpy().tolist())   # -averaged grad = -1.5

        # Graph allreduce matches the eager (DLPack) path bit-for-bit.
        x = tf.constant([1.0, 2.0]) * float(r + 1)
        eager = hvd.allreduce(x, op=hvd.Sum, name="cmp.eager")
        graphed = tf.function(
            lambda t: hvd.allreduce(t, op=hvd.Sum, name="cmp.graph")
        )(x)
        assert np.array_equal(eager.numpy(), graphed.numpy())

        # Dynamic output shape: uneven allgather inside tf.function.
        y = tf.ones([r + 1, 2], tf.float32) * float(r + 1)
        gathered = tf.function(
            lambda t: hvd.allgather(t, name="gath.graph")
        )(y)
        print("GATHER", gathered.numpy().sum(), gathered.shape.as_list())

        # Graph broadcast.
        z = tf.constant([float(r * 7 + 3)])
        bc = tf.function(
            lambda t: hvd.broadcast(t, 0, name="bc.graph")
        )(z)
        print("BCAST", bc.numpy().tolist())
        hvd.shutdown()
        """,
        timeout=300,
    )
    for out in outs:
        assert "STEP_W [-1.5, -1.5]" in out, outs
        # rows: 1 row of 1s*1 (2 cols) + 2 rows of 2s -> sum = 2 + 8 = 10
        assert "GATHER 10.0 [3, 2]" in out, outs
        assert "BCAST [3.0]" in out, outs


def test_grouped_allreduce_one_plan_two_ranks():
    """A 10-member grouped_allreduce under a 1 ms cycle, with enqueues
    deliberately staggered across many cycle boundaries, executes as ONE
    fused plan on every rank (first-class groups: the coordinator holds
    the group until complete — fusion semantics of the later reference's
    grouped API, controller.cc:626-750 lineage)."""
    outs = _run_workers(
        """
        import time
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        from horovod_tpu.core import xla_executor

        plans = []
        orig = xla_executor.XlaPlanExecutor.execute
        def spy(self, plan, entries, topo):
            plans.append(list(plan.get("names", [])))
            return orig(self, plan, entries, topo)
        xla_executor.XlaPlanExecutor.execute = spy

        hvd.init()
        r = hvd.rank()
        tensors = [np.full(8, i + 1, np.float32) for i in range(10)]
        # Stagger the member enqueues well past the 1 ms cycle time so a
        # cycle-boundary-based grouping would provably split them.
        base = "grp"
        handles = []
        import horovod_tpu
        gid_handles = hvd.grouped_allreduce_async(
            tensors, op=hvd.Sum, name=base)
        outs = [hvd.synchronize(h) for h in gid_handles]
        for i, o in enumerate(outs):
            assert np.allclose(np.asarray(o), 2.0 * (i + 1)), (i, o)
        grp_plans = [p for p in plans if any("grp." in n for n in p)]
        assert len(grp_plans) == 1, grp_plans
        assert sorted(grp_plans[0]) == sorted(
            f"grp.{i}" for i in range(10)), grp_plans
        print("ONEPLAN", len(grp_plans[0]))

        # Staggered: re-run with sleeps between member announcements via
        # two explicit enqueue waves — rank skew plus 3 ms gaps spans
        # multiple cycles; still one plan.
        plans.clear()
        import hashlib
        gid = int.from_bytes(hashlib.md5(b"wave").digest()[:8], "little")
        hs = []
        for i in range(10):
            hs.append(hvd.allreduce_async(
                tensors[i], op=hvd.Sum, name=f"wave.{i}",
                _group=(gid, 10)))
            time.sleep(0.003 * (1 + (r == 0)))
        outs = [hvd.synchronize(h) for h in hs]
        wave_plans = [p for p in plans if any("wave." in n for n in p)]
        assert len(wave_plans) == 1, wave_plans
        assert len(wave_plans[0]) == 10, wave_plans
        print("STAGGERED_ONEPLAN", len(wave_plans[0]))
        hvd.shutdown()
        """,
        timeout=300,
    )
    for out in outs:
        assert "ONEPLAN 10" in out, outs
        assert "STAGGERED_ONEPLAN 10" in out, outs


def test_megascale_env_drives_hierarchical_mesh_four_ranks():
    """Multi-slice deployment detection end to end: the megascale env
    (MEGASCALE_SLICE_ID/NUM_SLICES + TPU_WORKER_*) alone — no hand-set
    HOROVOD_* topology vars — yields the (cross, local) grid, and a
    hierarchical allreduce plan executes over the resulting _mesh2
    (ICI-within-slice, DCN-across analogue of nccl_operations.cc:151-346)."""
    outs = _run_workers(
        """
        import os
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()  # launcher env brings up jax.distributed
        r = hvd.rank()
        from horovod_tpu.common import topology
        from horovod_tpu.common.types import TensorTableEntry, ReduceOp
        from horovod_tpu.core.xla_executor import XlaPlanExecutor

        # Simulate what the multislice runtime sets: 2 slices x 2 workers.
        for v in ("HOROVOD_RANK", "HOROVOD_SIZE", "HOROVOD_LOCAL_RANK",
                  "HOROVOD_LOCAL_SIZE", "HOROVOD_CROSS_RANK",
                  "HOROVOD_CROSS_SIZE"):
            os.environ.pop(v, None)
        os.environ["MEGASCALE_NUM_SLICES"] = "2"
        os.environ["MEGASCALE_SLICE_ID"] = str(r // 2)
        os.environ["TPU_WORKER_HOSTNAMES"] = "worker-0,worker-1"
        os.environ["TPU_WORKER_ID"] = str(r % 2)

        # hvd.init() already initialized jax.distributed, which detect()
        # treats as authoritative; production multislice detection runs
        # BEFORE jax init, so exercise that path directly.
        topo = topology._from_megascale_env()
        assert topo is not None and topo.source == "megascale-env", topo
        assert topo.rank == r and topo.size == 4, topo
        assert topo.local_size == 2 and topo.cross_size == 2, topo
        ex = XlaPlanExecutor(topo)
        assert ex._mesh2 is not None, "hierarchical mesh not built"

        plan = {"type": 0, "op": int(ReduceOp.SUM), "participants": 4,
                "tuned_flags": 1}  # bit0: hierarchical_allreduce on
        entries = [TensorTableEntry(
            name="m", tensor=np.full((6,), float(r + 1), np.float32))]
        out = ex.execute(plan, entries, topo)["m"]
        print("MEGA_HIER", np.asarray(out)[:2].tolist())
        hvd.shutdown()
        """,
        np_=4,
    )
    for out in outs:
        assert "MEGA_HIER [10.0, 10.0]" in out, outs


def test_tf_graph_grouped_allreduce_one_plan_two_ranks():
    """tf.function grouped_allreduce: the group id crosses the graph
    boundary via the custom op attrs, so all members fuse into ONE plan
    even though each is its own graph node."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import tensorflow as tf
        import horovod_tpu.tensorflow as hvd
        from horovod_tpu.core import xla_executor
        hvd.init()
        r = hvd.rank()

        plans = []
        orig = xla_executor.XlaPlanExecutor.execute
        def spy(self, plan, entries, topo):
            plans.append(list(plan.get("names", [])))
            return orig(self, plan, entries, topo)
        xla_executor.XlaPlanExecutor.execute = spy

        @tf.function
        def f(a, b, c):
            return hvd.grouped_allreduce(
                [a, b, c], op=hvd.Sum, name="gg")

        outs = f(tf.constant([1.0]) * (r + 1),
                 tf.constant([2.0]) * (r + 1),
                 tf.constant([3.0]) * (r + 1))
        vals = [float(o[0]) for o in outs]
        assert vals == [3.0, 6.0, 9.0], vals
        gg_plans = [p for p in plans if any("gg." in n for n in p)]
        assert len(gg_plans) == 1 and len(gg_plans[0]) == 3, gg_plans

        # Gradient through the graph group (default auto-name exercises
        # the 63-bit group-id mask; the adjoint is a grouped SUM).
        v = tf.Variable([1.0, 2.0])
        @tf.function
        def g():
            with tf.GradientTape() as tape:
                a, b = hvd.grouped_allreduce(
                    [v * 2.0, v * 3.0], op=hvd.Sum)
                loss = tf.reduce_sum(a) + tf.reduce_sum(b)
            return tape.gradient(loss, v)
        gv = g()
        # d/dv sum(psum(2v)) + sum(psum(3v)) = 2*size + 3*size = 10
        assert gv.numpy().tolist() == [10.0, 10.0], gv.numpy()
        gdef = f.get_concrete_function(
            tf.TensorSpec([1]), tf.TensorSpec([1]), tf.TensorSpec([1])
        ).graph.as_graph_def()
        types = {n.op for n in gdef.node}
        for fn in gdef.library.function:
            types |= {n.op for n in fn.node_def}
        assert not any("PyFunc" in t for t in types), sorted(types)
        print("GRAPH_GROUP_ONEPLAN", len(gg_plans[0]))
        hvd.shutdown()
        """,
        timeout=300,
    )
    for out in outs:
        assert "GRAPH_GROUP_ONEPLAN 3" in out, outs


def test_process_sets_two_ranks():
    """Dynamic process sets (later-reference hvd.ProcessSet): singleton
    sets alongside the global set. Each rank's set-allreduce sees only
    its own contribution; global ops keep working around them."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        r = hvd.rank()
        import jax.numpy as jnp

        even = hvd.add_process_set([0])
        odd = hvd.add_process_set([1])
        mine = even if r == 0 else odd
        other = odd if r == 0 else even
        assert mine.included() and not other.included()
        assert mine.rank() == 0 and mine.size() == 1
        assert hvd.global_process_set.included()
        assert hvd.global_process_set.size() == 2

        x = jnp.full((4,), float(r + 1), jnp.float32)
        s_set = hvd.allreduce(x, op=hvd.Sum, process_set=mine, name="ps.ar")
        s_glob = hvd.allreduce(x, op=hvd.Sum, name="glob.ar")
        assert np.allclose(np.asarray(s_set), r + 1), np.asarray(s_set)
        assert np.allclose(np.asarray(s_glob), 3.0), np.asarray(s_glob)

        # Non-member submission fails fast (local validation).
        try:
            hvd.allreduce(x, process_set=other, name="bad")
            raise AssertionError("non-member enqueue should fail")
        except RuntimeError as e:
            assert "not a member" in str(e), e

        # remove_process_set is collective: identical calls on every rank.
        hvd.remove_process_set(even)
        hvd.remove_process_set(odd)
        assert even.process_set_id is None and odd.process_set_id is None
        print("PS2 OK")
        hvd.shutdown()
        """,
    )
    for out in outs:
        assert "PS2 OK" in out, outs


def test_process_sets_disjoint_pairs_four_ranks():
    """4-rank job split into two disjoint 2-rank sets: each pair's
    collectives ride a sub-mesh of its member devices only. Covers
    allreduce (set-local sum), uneven allgather (member-ordered
    displacements), broadcast (GLOBAL root rank mapped to the member
    position), grouped allreduce within a set, and set+global mixing."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        r = hvd.rank()
        import jax.numpy as jnp

        lo = hvd.add_process_set([0, 1])
        hi = hvd.add_process_set([2, 3])
        mine = lo if r < 2 else hi
        assert mine.rank() == r % 2 and mine.size() == 2

        x = jnp.full((3,), float(r + 1), jnp.float32)
        s = hvd.allreduce(x, op=hvd.Sum, process_set=mine, name="pair.ar")
        want = 3.0 if r < 2 else 7.0
        assert np.allclose(np.asarray(s), want), (r, np.asarray(s))

        # Uneven allgather within the set: member m contributes m+1 rows.
        rows = mine.rank() + 1
        g = hvd.allgather(
            np.full((rows, 2), float(r), np.float32), name="pair.ag",
            process_set=mine)
        g = np.asarray(g)
        base = 0 if r < 2 else 2
        want_rows = [float(base)] * 1 + [float(base + 1)] * 2
        assert g.shape == (3, 2) and g[:, 0].tolist() == want_rows, g

        # Broadcast with a GLOBAL root rank (root 2 lives in `hi`).
        root = 0 if r < 2 else 2
        b = hvd.broadcast(
            np.full((2,), float(r), np.float32), root_rank=root,
            name="pair.bc", process_set=mine)
        assert np.asarray(b).tolist() == [float(root)] * 2, np.asarray(b)

        # Grouped allreduce stays one plan inside the set.
        outs2 = hvd.grouped_allreduce(
            [jnp.ones((2,)) * (r + 1), jnp.ones((1,)) * 10 * (r + 1)],
            op=hvd.Sum, name="pair.grp", process_set=mine)
        w0 = 3.0 if r < 2 else 7.0
        assert np.allclose(np.asarray(outs2[0]), w0)
        assert np.allclose(np.asarray(outs2[1]), 10 * w0)

        # Global collective still healthy after set traffic.
        tot = hvd.allreduce(jnp.ones((2,)), op=hvd.Sum, name="glob.ar2")
        assert np.allclose(np.asarray(tot), 4.0)

        # Set-local object gather (member-ordered).
        objs = hvd.allgather_object({"r": r}, name="pair.obj",
                                    process_set=mine)
        assert [o["r"] for o in objs] == ([0, 1] if r < 2 else [2, 3]), objs
        # Fence before shutdown: a pair that is done may not exit while the
        # other pair is still inside its set's collective.
        hvd.barrier()
        print("PS4 OK")
        hvd.shutdown()
        """,
        np_=4,
        timeout=300,
    )
    for out in outs:
        assert "PS4 OK" in out, outs


def test_process_set_divergent_registration_fails_loudly():
    """A divergent add_process_set (different membership per rank) must
    raise ValueError on EVERY rank — including the rank whose local
    validation failed — instead of stranding peers in the barrier."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        r = hvd.rank()
        ranks = [0, 1] if r == 0 else [0]
        try:
            hvd.add_process_set(ranks)
            raise AssertionError("divergent registration should fail")
        except ValueError as e:
            assert "identically" in str(e), e
        # Rank 1's id allocation diverged? No: both allocated id 1 and
        # rolled back; a subsequent identical registration must agree.
        ps = hvd.add_process_set([0, 1])
        s = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                          process_set=ps, name="after.ar")
        assert np.allclose(np.asarray(s), 2.0)
        # Out-of-range ranks on ONE rank only: the failing rank raises
        # its local error, the healthy rank raises the agreement error.
        try:
            hvd.add_process_set([0, 1] if r == 0 else [0, 99])
            raise AssertionError("should fail")
        except ValueError as e:
            assert ("identically" in str(e)) or ("lie in" in str(e)), e
        # Failed calls consume the shared id/barrier sequence on EVERY
        # rank (even the locally-invalid one), so registration recovers.
        ps3 = hvd.add_process_set([1])
        if r == 1:
            s3 = hvd.allreduce(np.ones(1, np.float32), op=hvd.Sum,
                               process_set=ps3, name="solo.ar")
            assert np.allclose(np.asarray(s3), 1.0)
        # Fence before shutdown: the solo set op above needs the global
        # coordinator (rank 0) alive until it completes.
        hvd.allreduce(np.ones(1, np.float32), op=hvd.Sum, name="fence")
        print("PSDIV OK")
        hvd.shutdown()
        """,
    )
    for out in outs:
        assert "PSDIV OK" in out, outs


def test_torch_sync_batch_norm_two_ranks():
    """SyncBatchNorm (later-reference horovod.torch.SyncBatchNorm):
    2-rank forward, input gradients, and running stats must match a
    single-process BatchNorm2d over the CONCATENATED batch (float32
    tolerances: the per-channel stats ride the f32 eager wire)."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import torch
        import horovod_tpu.torch as hvd
        hvd.init()
        r = hvd.rank()
        torch.manual_seed(0)
        xs = [torch.randn(2, 3, 2, 2) for _ in range(2)]
        dys = [torch.randn(2, 3, 2, 2) for _ in range(2)]
        x = xs[r].clone().requires_grad_(True)

        sbn = hvd.SyncBatchNorm(3, eps=1e-5, momentum=0.1)
        with torch.no_grad():
            sbn.weight.mul_(0).add_(torch.tensor([1.5, 0.5, 2.0]))
            sbn.bias.add_(torch.tensor([0.1, -0.2, 0.3]))
        y = sbn(x)
        y.backward(dys[r])

        # single-process reference over the concatenated global batch
        ref = torch.nn.BatchNorm2d(3, eps=1e-5, momentum=0.1)
        with torch.no_grad():
            ref.weight.copy_(sbn.weight.detach())
            ref.bias.copy_(sbn.bias.detach())
        xg = torch.cat(xs).clone().requires_grad_(True)
        yg = ref(xg)
        yg.backward(torch.cat(dys))

        sl = slice(r * 2, r * 2 + 2)
        ok_y = torch.allclose(y, yg[sl], atol=1e-5, rtol=1e-4)
        ok_dx = torch.allclose(x.grad, xg.grad[sl], atol=1e-4, rtol=1e-3)
        ok_rm = torch.allclose(sbn.running_mean, ref.running_mean,
                               atol=1e-5)
        ok_rv = torch.allclose(sbn.running_var, ref.running_var,
                               atol=1e-5)
        # eval mode: no communication, matches reference eval
        sbn.eval(); ref.eval()
        ok_eval = torch.allclose(sbn(xs[0]), ref(xs[0]),
                                 atol=1e-5, rtol=1e-4)
        # bf16 path: stats ride the f32 wire; output/grads stay bf16+finite
        sbn_b = hvd.SyncBatchNorm(3).bfloat16()
        xb = xs[r].bfloat16().clone().requires_grad_(True)
        yb = sbn_b(xb)
        yb.sum().backward()
        ok_bf16 = (yb.dtype == torch.bfloat16
                   and xb.grad.dtype == torch.bfloat16
                   and bool(yb.float().isfinite().all())
                   and bool(xb.grad.float().isfinite().all()))
        # momentum=None + no running stats must not crash (torch parity)
        sbn_n = hvd.SyncBatchNorm(3, momentum=None,
                                  track_running_stats=False)
        ok_none = bool(sbn_n(xs[r]).isfinite().all())
        print("SBN", bool(ok_y), bool(ok_dx), bool(ok_rm), bool(ok_rv),
              bool(ok_eval), bool(ok_bf16), bool(ok_none))
        hvd.shutdown()
        """
    )
    for out in outs:
        assert "SBN True True True True True True True" in out, outs


def test_barrier_two_ranks():
    """hvd.barrier (later-reference API): rank 1 enters late; rank 0's
    barrier return must wait for it."""
    outs = _run_workers(
        """
        import time
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        if hvd.rank() == 1:
            time.sleep(1.0)
        t0 = time.monotonic()
        hvd.barrier()
        waited = time.monotonic() - t0
        print("BARRIER", hvd.rank(), waited > 0.6 if hvd.rank() == 0
              else True)
        hvd.shutdown()
        """
    )
    assert "BARRIER 0 True" in outs[0], outs
    assert "BARRIER 1 True" in outs[1], outs


def test_grouped_allgather_reducescatter_two_ranks():
    """grouped_allgather / grouped_reducescatter (later-reference v0.28):
    heterogeneous members complete atomically as one held group."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import horovod_tpu as hvd
        hvd.init()
        import jax.numpy as jnp
        r = hvd.rank()
        outs = hvd.grouped_allgather([
            jnp.full((1, 2), float(r), jnp.float32),       # -> (2, 2)
            jnp.full((3,), float(10 + r), jnp.float32),    # -> (6,)
        ], name="gag")
        print("GAG", [np.asarray(o).reshape(-1).tolist() for o in outs])
        rs = hvd.grouped_reducescatter([
            jnp.full((2,), float(r + 1), jnp.float32),     # sum=[3,3]
            jnp.asarray(np.arange(4, dtype=np.float32)),   # sum=2*arange
        ], name="grs")
        print("GRS", [np.asarray(o).tolist() for o in rs])
        hvd.shutdown()
        """
    )
    for out in outs:
        assert ("GAG [[0.0, 0.0, 1.0, 1.0], "
                "[10.0, 10.0, 10.0, 11.0, 11.0, 11.0]]") in out, outs
    assert "GRS [[3.0], [0.0, 2.0]]" in outs[0], outs
    assert "GRS [[3.0], [4.0, 6.0]]" in outs[1], outs


def test_torch_sparse_as_dense_two_ranks():
    """sparse_as_dense (reference DistributedOptimizer option): sparse
    embedding gradients densify before the allreduce; without the flag
    the submission fails with actionable guidance."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import torch
        import horovod_tpu.torch as hvd
        hvd.init()
        r = hvd.rank()
        torch.manual_seed(0)
        emb = torch.nn.Embedding(8, 4, sparse=True)
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(emb.parameters(), lr=0.1),
            named_parameters=emb.named_parameters(),
            sparse_as_dense=True)
        # rank r touches rows {r, 4}: row 4 overlaps, rows 0/1 disjoint
        idx = torch.tensor([r, 4])
        emb(idx).sum().backward()
        opt.step()
        w = emb.weight.detach()
        print("SPARSE", [round(float(x), 4) for x in w.sum(1)[:5]])

        emb2 = torch.nn.Embedding(4, 2, sparse=True)
        opt2 = hvd.DistributedOptimizer(
            torch.optim.SGD(emb2.parameters(), lr=0.1),
            named_parameters=emb2.named_parameters())
        try:
            emb2(torch.tensor([0])).sum().backward()
            opt2.step()
            print("NOERR")
        except Exception as e:   # raised from the grad hook in backward
            print("SPARSE_ERR", "sparse_as_dense" in str(e))
        hvd.shutdown()
        """
    )
    vals = None
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("SPARSE ")][0]
        vals = vals or line
        assert line == vals, outs          # identical updates both ranks
        assert "SPARSE_ERR True" in out, outs


def test_torch_grouped_allgather_reducescatter_two_ranks():
    """torch binding surfaces for the grouped allgather/reducescatter
    (later-reference v0.28): conversion, handle wiring, op=Average, and
    atomic completion through the torch wrappers."""
    outs = _run_workers(
        """
        import numpy as np, jax
        jax.config.update('jax_platforms', 'cpu')
        import torch
        import horovod_tpu.torch as hvd
        hvd.init()
        r, n = hvd.rank(), hvd.size()
        outs = hvd.grouped_allgather([
            torch.full((1, 2), float(r)),
            torch.full((3,), float(10 + r)),
        ])
        ok_g = (outs[0].shape == (n, 2) and outs[1].shape == (3 * n,)
                and bool(outs[1][:3].eq(10.0).all())
                and bool(outs[1][3:].eq(11.0).all()))
        rs = hvd.grouped_reducescatter(
            (t for t in [torch.ones(4) * (r + 1),      # generator input
                         torch.arange(4.0)]),
            op=hvd.Average)
        ok_r = (bool(rs[0].eq(1.5).all())               # avg of 1,2
                and rs[0].shape == (2,)
                and bool(torch.allclose(
                    rs[1], torch.arange(4.0)[r * 2:(r + 1) * 2])))
        print("TGROUPED", bool(ok_g), bool(ok_r))
        hvd.shutdown()
        """
    )
    for out in outs:
        assert "TGROUPED True True" in out, outs
