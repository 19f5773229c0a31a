"""Unit tests for the gated mxnet/spark integrations using mocked engines
(the reference tests its launcher with mocks the same way,
``test/test_run.py``). Each test runs in a subprocess so the fake modules
never leak into this interpreter's import caches.
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(body: str, timeout=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True, timeout=timeout, text=True, env=env,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-3000:])
    return proc.stdout


FAKE_MXNET = """
    import sys, types
    import numpy as np

    mx = types.ModuleType("mxnet")

    class FakeND:
        def __init__(self, arr, ctx="cpu(0)", dtype=None):
            self._a = np.asarray(arr, dtype=dtype)
            self.context = ctx
        @property
        def dtype(self):
            return self._a.dtype
        def asnumpy(self):
            return self._a
        def __setitem__(self, k, v):
            self._a[k] = v._a if isinstance(v, FakeND) else np.asarray(v)
        def __getitem__(self, k):
            return self._a[k]

    nd = types.ModuleType("mxnet.nd")
    nd.array = lambda a, ctx=None, dtype=None: FakeND(a, ctx or "cpu(0)", dtype)
    mx.nd = nd

    optimizer = types.ModuleType("mxnet.optimizer")
    class Optimizer:
        pass
    optimizer.Optimizer = Optimizer
    mx.optimizer = optimizer

    gluon = types.ModuleType("mxnet.gluon")
    class Trainer:
        def __init__(self, params, optimizer, optimizer_params=None,
                     kvstore=None):
            self._params = list(params)
            self._optimizer = optimizer
            self._scale = 1.0
    gluon.Trainer = Trainer
    mx.gluon = gluon
    sys.modules["mxnet"] = mx
    sys.modules["mxnet.nd"] = nd
"""


def test_mxnet_binding_with_mock_engine():
    out = _run_sub(FAKE_MXNET + """
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd_core
    import horovod_tpu.mxnet as hvd
    FakeND = sys.modules["mxnet"].nd.array(np.zeros(1)).__class__

    hvd.init()
    assert hvd.size() == 1

    # allreduce: identity at size 1, dtype/ctx preserved through the bridge
    t = sys.modules["mxnet"].nd.array(
        np.arange(4, dtype=np.float32), ctx="gpu(7)")
    out = hvd.allreduce(t, average=True, name="mx.ar")
    assert isinstance(out, FakeND) and out.context == "gpu(7)"
    np.testing.assert_allclose(out.asnumpy(), np.arange(4))

    # broadcast_parameters (dict form) writes in place
    p = {"w": sys.modules["mxnet"].nd.array(np.ones(3, np.float32))}
    hvd.broadcast_parameters(p, root_rank=0)
    np.testing.assert_allclose(p["w"].asnumpy(), np.ones(3))

    # DistributedOptimizer reduces before delegating to the wrapped update
    calls = []
    class Inner(sys.modules["mxnet"].optimizer.Optimizer):
        rescale_grad = 1.0
        def update(self, index, weight, grad, state):
            calls.append((index, grad.asnumpy().copy()))
    opt = hvd.DistributedOptimizer(Inner())
    g = sys.modules["mxnet"].nd.array(np.full(2, 6.0, np.float32))
    opt.update(3, None, g, None)
    assert calls and calls[0][0] == 3
    np.testing.assert_allclose(calls[0][1], np.full(2, 6.0))

    # DistributedTrainer divides the gluon scale by size and allreduces
    class Param:
        grad_req = "write"
        name = "w0"
        def __init__(self):
            self._g = sys.modules["mxnet"].nd.array(
                np.full(2, 4.0, np.float32))
        def list_grad(self):
            return [self._g]
    prm = Param()
    tr = hvd.DistributedTrainer([prm], Inner())
    assert tr._scale == 1.0  # size 1
    tr._allreduce_grads()
    np.testing.assert_allclose(prm._g.asnumpy(), np.full(2, 4.0))

    # broadcast_object pickles through the numpy broadcast path
    obj = hvd.broadcast_object({"lr": 0.1, "step": 7}, root_rank=0)
    assert obj == {"lr": 0.1, "step": 7}
    hvd_core.shutdown()
    print("MXNET-MOCK-OK")
    """)
    assert "MXNET-MOCK-OK" in out


def test_mxnet_gate_message_without_engine():
    out = _run_sub("""
    import horovod_tpu.mxnet as hvd
    try:
        hvd.init()
        raise SystemExit("gate did not fire")
    except ImportError as e:
        assert "MXNet is not installed" in str(e), e
    print("GATE-OK")
    """)
    assert "GATE-OK" in out


def test_spark_run_with_mock_engine():
    """horovod_tpu.spark.run() against a fake pyspark whose barrier stage
    forks one process per task: exercises the driver KV rendezvous, host
    collection, slot allocation, per-rank env plumbing, and result
    collection — everything except Spark itself."""
    out = _run_sub("""
    import sys, types, os
    import multiprocessing as mp

    pyspark = types.ModuleType("pyspark")

    class FakeRDD:
        def __init__(self, data, parts):
            self.data, self.parts = list(data), parts
        def barrier(self):
            return self
        def mapPartitions(self, f):
            self._f = f
            return self
        def collect(self):
            ctx = mp.get_context("fork")
            procs = [ctx.Process(target=lambda i=i: list(self._f(iter([i]))))
                     for i in self.data]
            for p in procs: p.start()
            for p in procs: p.join(90)
            bad = [p.exitcode for p in procs if p.exitcode != 0]
            assert not bad, f"task exit codes: {bad}"
            return self.data

    class SparkContext:
        defaultParallelism = 2
        _active = None
        @classmethod
        def getOrCreate(cls):
            if cls._active is None:
                cls._active = cls()
            return cls._active
        def parallelize(self, rng, n):
            return FakeRDD(rng, n)

    pyspark.SparkContext = SparkContext
    sys.modules["pyspark"] = pyspark

    import horovod_tpu.spark as hvd_spark

    def fn(tag):
        # Runs inside a forked task with its rank env applied.
        return (tag, os.environ["HOROVOD_RANK"], os.environ["HOROVOD_SIZE"])

    results = hvd_spark.run(fn, args=("t",), num_proc=2)
    assert len(results) == 2, results
    ranks = sorted(r[1] for r in results)
    assert ranks == ["0", "1"], results
    assert all(r[2] == "2" for r in results), results
    print("SPARK-MOCK-OK")
    """)
    assert "SPARK-MOCK-OK" in out
