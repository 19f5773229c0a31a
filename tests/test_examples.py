"""BASELINE.json config-parity smoke tests: every example named in the
baseline configs runs end-to-end under the launcher at -np 2 (the
reference CI runs its examples under ``mpirun -np 2``)."""

import os
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = [pytest.mark.multiproc, pytest.mark.slow]


def _run_example(script, args, np_=2, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["HOROVOD_CYCLE_TIME"] = "1"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    with tempfile.TemporaryDirectory() as td:
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.run", "-np", str(np_),
             "--output-dir", td, sys.executable,
             os.path.join(REPO, "examples", script)] + args,
            env=env, cwd=td, capture_output=True, timeout=timeout,
            text=True,
        )
        outs = []
        for r in range(np_):
            p = os.path.join(td, f"rank.{r}.out")
            outs.append(open(p).read() if os.path.exists(p) else "")
        errs = []
        for r in range(np_):
            p = os.path.join(td, f"rank.{r}.err")
            errs.append(open(p).read()[-1500:] if os.path.exists(p) else "")
    return proc, outs, errs


def test_keras_mnist():
    proc, outs, errs = _run_example(
        "keras_mnist.py",
        ["--synthetic", "--epochs", "2", "--batch-size", "64",
         "--steps-per-epoch", "3"],
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr, errs)
    assert any("Test accuracy:" in o for o in outs), (outs, errs)


def test_tensorflow2_synthetic_benchmark():
    proc, outs, errs = _run_example(
        "tensorflow2_synthetic_benchmark.py",
        ["--image-size", "64", "--batch-size", "4",
         "--num-warmup-batches", "1", "--num-batches-per-iter", "2",
         "--num-iters", "2"],
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr, errs)
    joined = "\n".join(outs)
    assert "Img/sec per worker:" in joined, (outs, errs)
    assert "Total img/sec on 2 worker(s):" in joined, (outs, errs)


def test_pytorch_imagenet_resnet50_synthetic():
    proc, outs, errs = _run_example(
        "pytorch_imagenet_resnet50.py",
        ["--epochs", "1", "--synthetic-batches", "2", "--batch-size", "4",
         "--image-size", "64", "--warmup-epochs", "1"],
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr, errs)
    assert any("val_acc" in o for o in outs), (outs, errs)


def _has_module(name):
    import importlib.util
    return importlib.util.find_spec(name) is not None


def test_mxnet_example_gates_cleanly():
    if _has_module("mxnet"):
        pytest.skip("mxnet installed; gate path not reachable")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "mxnet_imagenet_resnet50.py")],
        capture_output=True, timeout=60, text=True,
    )
    assert proc.returncode == 3
    assert "MXNet is not available" in proc.stderr


def test_keras_imagenet_resnet50_synthetic():
    proc, outs, errs = _run_example(
        "keras_imagenet_resnet50.py",
        ["--epochs", "1", "--synthetic-batches", "2", "--batch-size", "4",
         "--image-size", "64", "--warmup-epochs", "1"],
        timeout=540,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr, errs)
    assert any("TRAINING DONE" in o for o in outs), (outs, errs)


def test_tensorflow2_word2vec_sparse_path():
    proc, outs, errs = _run_example("tensorflow2_word2vec.py", [])
    assert proc.returncode == 0, (proc.stdout, proc.stderr, errs)
    joined = "\n".join(outs)
    assert "nce_loss" in joined, (outs, errs)
    assert "done" in joined, (outs, errs)


def test_spark_example_gates_cleanly():
    if _has_module("pyspark"):
        pytest.skip("pyspark installed; gate path not reachable")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "keras_spark_rossmann.py")],
        capture_output=True, timeout=60, text=True,
    )
    assert proc.returncode == 3
    assert "PySpark is not installed" in proc.stderr


def test_jax_tp_pp_demo():
    """The TP/PP demo (incl. the heterogeneous LM pipeline section) runs
    end to end on the 8-device virtual mesh; single-process SPMD, so no
    launcher needed."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "jax_tp_pp_demo.py"),
         "--steps", "4"],
        env=env, capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "DEMO DONE" in proc.stdout
    assert "heterogeneous LM" in proc.stdout


def _run_elastic_example(script, expect, np_=2, extra_env=None):
    """Elastic example smoke run through the shared conftest harness."""
    from conftest import run_elastic_job

    proc, outs = run_elastic_job(
        ["-np", str(np_), "--min-np", str(np_), "--max-np", str(np_)],
        script_path=os.path.join(REPO, "examples", script),
        timeout=420, extra_env=extra_env,
    )
    out = "".join(v for k, v in outs.items() if not k.endswith(".err"))
    assert proc.returncode == 0, (proc.stdout, proc.stderr, out)
    assert expect in out, out
    return out


def test_jax_elastic_train():
    """The jax elastic example completes under the elastic driver at a
    fixed size of 2 and converges (later-reference elastic example
    role)."""
    out = _run_elastic_example("jax_elastic_train.py",
                               "done: 200 steps on 2 ranks")
    err = float(out.split("|w - w*| = ")[1].split()[0])
    assert err < 0.05, out


def test_jax_elastic_train_respawn_mode():
    """The same unmodified elastic example under the respawn fallback
    (HOROVOD_ELASTIC_REJOIN_MODE=respawn): user code needs zero changes
    when the private-API in-process path is unavailable — the mode is a
    launcher/runtime concern."""
    out = _run_elastic_example(
        "jax_elastic_train.py", "done: 200 steps on 2 ranks",
        extra_env={"HOROVOD_ELASTIC_REJOIN_MODE": "respawn"},
    )
    err = float(out.split("|w - w*| = ")[1].split()[0])
    assert err < 0.05, out


def test_pytorch_mnist_elastic():
    """The elastic pytorch example (upstream pytorch_mnist_elastic role)
    completes under the elastic driver."""
    _run_elastic_example("pytorch_mnist_elastic.py",
                         "done: 2 epochs on 2 ranks")


def test_tensorflow2_keras_mnist_elastic():
    """The elastic Keras example (upstream tensorflow2_keras_mnist_elastic
    role) completes under the elastic driver."""
    _run_elastic_example("tensorflow2_keras_mnist_elastic.py",
                         "done: 4 epochs on 2 ranks")
