"""Test harness: force an 8-device virtual CPU mesh.

Mirrors the reference's test execution model (SURVEY.md §4): the reference
runs pytest under ``mpirun -np 2`` to simulate multi-node on localhost; the
TPU build simulates a multi-chip slice with
``--xla_force_host_platform_device_count=8`` on the CPU backend, which
exercises every collective's numerics over a real 8-way mesh in one process.
"""

import os
import shutil
import tempfile

# Must happen before the first JAX backend initialization.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Tests run on the virtual CPU mesh.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# One compile cache a run, empty at its start and removed at its end, which
# the run's xdist workers share: the same small programs are compiled over
# and over, by one test after another and by six workers side by side (an
# interpreted kernel called eagerly is traced anew and compiled again each
# call), and the suite's time is CPU seconds of compiling (ROADMAP D16).
# The workers are the controller's children, so its pid names the directory.
# Where JAX_COMPILATION_CACHE_DIR is set, that is the cache and stays.
_RUN_CACHE = None
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _worker = "PYTEST_XDIST_WORKER" in os.environ
    _RUN_CACHE = os.path.join(
        tempfile.gettempdir(), "horovod_tpu_tests_jax_cache_"
        f"{os.getppid() if _worker else os.getpid()}")
    if not _worker:
        shutil.rmtree(_RUN_CACHE, ignore_errors=True)
    jax.config.update("jax_compilation_cache_dir", _RUN_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def pytest_unconfigure(config):
    if _RUN_CACHE and not hasattr(config, "workerinput"):
        shutil.rmtree(_RUN_CACHE, ignore_errors=True)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


@pytest.fixture()
def hvd_session():
    """Initialized single-process runtime, shut down after the test."""
    import horovod_tpu as hvd

    hvd.shutdown()
    hvd.init()
    yield hvd
    hvd.shutdown()


def run_elastic_job(hvdrun_args, script_text=None, script_path=None,
                    extra_env=None, timeout=300):
    """Shared harness for elastic-driver jobs (used by test_elastic and
    test_examples): scrubbed CPU env, launch under ``hvdrun`` with the
    given elastic flags, collect per-worker ``worker.<id>.out`` files.
    Returns (completed_process, {worker_id_or_errname: text})."""
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["HOROVOD_CYCLE_TIME"] = "1"
    env["PYTHONPATH"] = os.pathsep.join(
        [repo, env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    env.update(extra_env or {})
    with tempfile.TemporaryDirectory() as td:
        if script_path is None:
            script_path = os.path.join(td, "worker.py")
            with open(script_path, "w") as f:
                f.write(script_text)
        env["ELASTIC_TD"] = td
        # Chaos runs: all injections land in one shared event file (no-op
        # for jobs without a fault plan — the injector only writes when a
        # fault actually fires).
        env.setdefault(
            "HOROVOD_FAULT_EVENT_LOG", os.path.join(td, "fault_events.jsonl")
        )
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.run", *hvdrun_args,
             "--output-dir", td, sys.executable, script_path],
            env=env, cwd=repo, capture_output=True, timeout=timeout,
        )
        outs = {}
        for fn in os.listdir(td):
            if fn.startswith("worker.") and fn.endswith(".out"):
                outs[fn[len("worker."):-len(".out")]] = open(
                    os.path.join(td, fn)
                ).read()
            if fn.startswith("worker.") and fn.endswith(".err"):
                outs[fn[len("worker."):]] = open(
                    os.path.join(td, fn)
                ).read()
            if fn in ("driver.log", "fault_schedule.json",
                      "fault_events.jsonl"):
                outs[fn] = open(os.path.join(td, fn)).read()
    return proc, outs
