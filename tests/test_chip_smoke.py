"""chip_smoke.py off the chip: the device gate holds, and every phase's
control flow and checks run at tiny sizes on the virtual CPU mesh (the CPU
rehearsal of docs' on-chip run; what it says about speed is nothing)."""

import os
import subprocess
import sys

import pytest

import jax

import chip_smoke
from horovod_tpu.common import env as env_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LM = dict(d_model=64, n_heads=4, n_layers=2, vocab=256, seq=128, batch=2,
          steps=3)


@pytest.fixture()
def off_chip(monkeypatch):
    """Lift what only a chip can satisfy: interpret mode puts no
    ``tpu_custom_call`` in a CPU program. (The device gate itself is in
    ``main``, which these tests do not call.)"""
    monkeypatch.setattr(
        chip_smoke, "check_kernel_in_program", lambda text, what: None
    )


def test_refuses_to_report_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "not a TPU" in proc.stderr


def test_kernel_check_fails_on_an_interpreted_program():
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.check_kernel_in_program("fusion(...)", "step")
    chip_smoke.check_kernel_in_program('custom_call_target="tpu_custom_call"',
                                       "step")


def test_train_lm_then_serve(off_chip, devices, capsys):
    one = chip_smoke.phase_train_lm(LM, seed=0, devices=devices[:1])
    chip_smoke.phase_serve(
        LM, dict(requests=2, prompt_min=3, prompt_max=9, max_tokens=4),
        seed=0, params=one,
    )
    out = capsys.readouterr().out
    assert '"phase": "train_lm"' in out and '"phase": "serve"' in out


def test_train_resnet(devices, capsys):
    chip_smoke.phase_train_resnet(
        dict(model="resnet18", classes=10, image=32, batch=4, steps=3),
        seed=0, devices=devices[:1],
    )
    assert '"phase": "train_resnet"' in capsys.readouterr().out


def test_eager(capsys):
    import horovod_tpu as hvd

    hvd.shutdown()
    chip_smoke.phase_eager(dict(elements=1024), started=0.0)
    assert '"runtime": "NativeRuntime"' in capsys.readouterr().out


def test_four_chips(off_chip, devices, capsys):
    chip_smoke.phase_four_chips(
        LM, dict(steps=3, global_batch=8), seed=0, devices=devices[:4]
    )
    out = capsys.readouterr().out
    for name in ("reference_one_chip", "dp4", "dp2xtp2", "dp2xtp2_fused"):
        assert f'"phase": "four_chips.{name}"' in out


@pytest.fixture()
def cache_config():
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    from jax.experimental.compilation_cache import compilation_cache

    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = env_mod.configure_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    # The same path on every call: it is part of the cache's key.
    assert env_mod.configure_compile_cache() == path


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path,
                                               cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert env_mod.configure_compile_cache() == str(tmp_path)
    # No directory is set in code: JAX reads the variable itself.
    assert jax.config.jax_compilation_cache_dir == before
