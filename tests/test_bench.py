"""bench.py validates its own harness on the CPU when asked to
(``--platform cpu``), and never otherwise: without that flag a host with
no TPU gets a non-zero exit and no metric line, and a CPU run's line is
named as one.
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")


def _run(args, timeout=540, extra_env=None):
    env = dict(os.environ)
    # --platform cpu does its own pinning; don't inherit the test
    # harness's virtual-mesh XLA_FLAGS or JAX_PLATFORMS.
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, BENCH] + args,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        timeout=timeout, text=True, env=env,
    )


def _json_line(stdout: str) -> dict:
    lines = [l for l in stdout.splitlines() if l.strip().startswith("{")]
    assert lines, f"no JSON line in stdout: {stdout!r}"
    return json.loads(lines[-1])


@pytest.mark.slow
def test_smoke_cpu_end_to_end():
    proc = _run([
        "--smoke", "--platform", "cpu", "--cpu-devices", "2",
        "--model", "resnet18", "--num-classes", "10",
    ])
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = _json_line(proc.stdout)
    assert out["metric"] == \
        "cpu_harness_resnet18_synthetic_images_per_sec_per_chip"
    assert out["value"] and out["value"] > 0
    assert out["unit"] == "img/s/chip"
    assert out["detail"]["platform"] == "cpu"
    assert out["detail"]["n_chips"] == 2
    # FLOPs cost analysis populated => MFU is computable on TPU.
    assert out["detail"]["flops_per_step_per_chip"], out["detail"]


@pytest.mark.parametrize("args, env, says", [
    # No --smoke: smoke mode overrides batch-size, and the negative batch
    # must reach the benchmark to crash it (ValueError from randn) before
    # any compile happens.
    (["--platform", "cpu", "--cpu-devices", "1", "--model", "resnet18",
      "--batch-size", "-1", "--image-size", "8"], {}, "ValueError"),
    # A host with no TPU (JAX finds the CPU) and no --platform cpu: the
    # benchmark refuses instead of printing a CPU number.
    (["--smoke", "--model", "resnet18"], {"JAX_PLATFORMS": "cpu"},
     "not a TPU"),
], ids=["benchmark_error", "no_tpu_without_platform_cpu"])
def test_failing_run_exits_nonzero_and_prints_no_metric_line(args, env, says):
    proc = _run(args, timeout=300, extra_env=env)
    assert proc.returncode != 0
    assert says in proc.stderr, proc.stderr[-2000:]
    assert not [l for l in proc.stdout.splitlines()
                if l.strip().startswith("{")], proc.stdout


def test_moe_smoke_cpu_end_to_end():
    """DP x EP MoE benchmark path: switch routing + all_to_all over a
    (data, expert) mesh, tokens/s metric, FLOPs reconciliation wired."""
    proc = _run([
        "--smoke", "--platform", "cpu", "--cpu-devices", "4",
        "--model", "moe",
    ])
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = _json_line(proc.stdout)
    assert out["metric"] == \
        "cpu_harness_moe_synthetic_tokens_per_sec_per_chip"
    assert out["value"] and out["value"] > 0
    assert out["detail"]["mesh"] == {"data": 1, "expert": 4}
    assert out["detail"]["flops_per_step_per_chip"], out["detail"]


def test_overlap_schedule_parser():
    """The HLO-schedule parser behind the committed overlap evidence
    (PROFILE_OVERLAP_PHASEB_*.json): async pairs are matched by operand
    name including TUPLE-typed (variadic) forms — a miss there would
    turn real latency hiding into a false 'no overlap' verdict — and
    compute between start/done is counted across tuple-shaped fusions."""
    import importlib.util
    import os

    repo = os.path.join(os.path.dirname(__file__), os.pardir)
    spec = importlib.util.spec_from_file_location(
        "tpo", os.path.join(repo, "tools", "tpu_profile_overlap.py")
    )
    tpo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tpo)

    hlo = "\n".join([
        "ENTRY %main {",
        "  %p0 = f32[8]{0} parameter(0)",
        "  %ars = (f32[64]{0}, f32[32]{0}) all-reduce-start(%g1, %g2), "
        "replica_groups={{0,1}}",
        "  %f.1 = (f32[64]{0}, f32[8]{0}) fusion(%p0), kind=kLoop",
        "  %conv = f32[1,8,8,64]{3,2,1,0} convolution(%x, %k), window={}",
        "  %ard = (f32[64]{0}, f32[32]{0}) all-reduce-done(%ars)",
        "  %sync = f32[64]{0} all-reduce(%f.1), replica_groups={{0,1}}",
        "  %gte = f32[64]{0} get-tuple-element(%ard), index=0",
        "}",
    ])
    stats = tpo._schedule_overlap_stats(hlo)
    assert stats["async_all_reduce_pairs"] == 1, stats
    assert stats["compute_ops_overlapped_per_pair"] == [2], stats
    assert stats["pairs_with_overlap"] == 1, stats
    assert stats["sync_all_reduce_count"] == 1, stats


def test_tp_flag_validation():
    """--tp / --rules parser contract: transformer-only, degree >= 2,
    --rules needs --tp, and --tp defaults its table to gpt."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    args = bench._parse_args(
        ["--model", "transformer", "--tp", "2"]
    )
    assert args.rules == "gpt"
    for bad in (
        ["--model", "resnet18", "--tp", "2"],
        ["--model", "transformer", "--tp", "1"],
        ["--model", "transformer", "--rules", "gpt"],
    ):
        with pytest.raises(SystemExit):
            bench._parse_args(bad)


def test_tuned_mesh_hash_rejection(tmp_path):
    """--quantized --tuned with a tuning pinned on a DIFFERENT mesh-axes
    hash is a hard error naming BOTH hashes; a params-half mismatch
    alone still falls back with the loud warning."""
    import argparse
    import importlib.util

    import jax.numpy as jnp

    from horovod_tpu import tune as T

    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    params = {"w": jnp.ones((8, 8))}
    pinned_sig = T.step_signature(params, mesh={"data": 8})
    cfg = T.TunedConfig(
        knobs={"fusion_threshold_bytes": 1 << 20,
               "first_bucket_bytes": 1 << 18,
               "wire_dtype": "int8", "topo_algorithm": None},
        signature=pinned_sig, objectives={}, baseline={},
        program="unit",
    )
    path = str(tmp_path / "tuned.json")
    T.save_tuned(cfg, path)

    live_mesh = {"data": 4, "model": 2}
    args = argparse.Namespace(tuned=path, quantized=True)
    with pytest.raises(SystemExit) as e:
        bench._resolve_tuned(args, params, live_mesh)
    msg = str(e.value)
    assert T.mesh_axes_hash(pinned_sig) in msg
    assert T.mesh_axes_hash(T.step_signature(params, mesh=live_mesh)) \
        in msg
    # Without --quantized the same mismatch falls back (no exception),
    # reporting matched=False.
    args = argparse.Namespace(tuned=path, quantized=False)
    kw, detail = bench._resolve_tuned(args, params, live_mesh)
    assert kw is None and detail["matched"] is False
