"""Model-zoo tests: init + forward shapes + dtype policy for the
reference's headline benchmark families (ResNet / VGG-16 / Inception V3,
``docs/benchmarks.rst:13-14`` upstream)."""

import os
import runpy
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import get_model


@pytest.mark.parametrize(
    "name,size",
    [
        ("resnet18", 64),
        ("resnet50", 64),
        ("vgg16", 64),
        ("inception3", 96),
    ],
)
def test_model_forward_shapes(name, size):
    model = get_model(name, num_classes=10)
    rng = jax.random.PRNGKey(0)
    x = jnp.ones((2, size, size, 3), jnp.float32)
    variables = model.init(rng, x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32  # head stays fp32
    assert np.all(np.isfinite(np.asarray(logits)))


def test_model_train_step_mutates_batch_stats():
    model = get_model("resnet18", num_classes=10)
    x = jnp.ones((2, 64, 64, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    _, new_state = model.apply(
        variables, x, train=True, mutable=["batch_stats"]
    )
    assert "batch_stats" in new_state


def test_vgg_has_no_batch_stats_and_uses_dropout_rng():
    model = get_model("vgg16", num_classes=10)
    x = jnp.ones((2, 64, 64, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    assert "batch_stats" not in variables
    logits = model.apply(
        variables, x, train=True, rngs={"dropout": jax.random.PRNGKey(1)}
    )
    assert logits.shape == (2, 10)


def test_bf16_compute_policy():
    """Conv params are stored fp32 (flax default param_dtype) while
    compute runs bfloat16 — the MXU-native mixed-precision policy."""
    model = get_model("resnet18", num_classes=10)
    x = jnp.ones((1, 64, 64, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    kernel = variables["params"]["conv_init"]["kernel"]
    assert kernel.dtype == jnp.float32


def test_get_model_unknown_name():
    with pytest.raises(ValueError):
        get_model("alexnet")


def test_synthetic_benchmark_example_runs(monkeypatch, capsys):
    """``examples/jax_resnet50_synthetic_benchmark.py`` at a tiny size on the
    virtual CPU mesh: warm-up, timed iterations, upstream's last lines."""
    script = os.path.join(os.path.dirname(__file__), os.pardir, "examples",
                          "jax_resnet50_synthetic_benchmark.py")
    monkeypatch.setattr(sys, "argv", [
        script, "--model", "resnet18", "--batch-size", "2", "--image-size",
        "32", "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "2"])
    runpy.run_path(script, run_name="__main__")
    out = capsys.readouterr().out
    n = len(jax.devices())
    assert "Iter #1:" in out and "Img/sec per chip:" in out
    assert f"Total img/sec on {n} chip(s):" in out
