#!/usr/bin/env python
"""Synthetic benchmark — the TPU-native counterpart of the reference's
``examples/tensorflow2_synthetic_benchmark.py`` (img/sec on synthetic data,
averaged over timed iterations; ``:119-132``). CNN img/s by default;
``--model transformer`` benchmarks the flash-attention LM in tokens/s
(optionally ``--zero1`` for sharded optimizer state).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "img/s/chip", "vs_baseline": N}

The benchmark runs in this process, on the TPU JAX finds. Without
``--platform cpu`` it fails when the default device is not a TPU: a CPU
run is something the caller asks for, and its numbers say how fast XLA's
CPU backend is, nothing about the chip. A failing run exits non-zero and
prints no metric line.

Extra outputs in ``detail``:
  - ``mfu`` / ``mfu_analytic``: model-FLOPs utilization = (FLOPs per
    step) / (step time x per-chip peak bf16 FLOPs), once from XLA's cost
    analysis (``flops_per_step_per_chip``) and once from the analytic
    per-model table (``flops_analytic_per_step_per_chip``), side by side.
    TPU runs only; a ``device_kind`` missing from the peak table below is
    an error, not a null.
  - ``scan``: whether the timed region is a fused on-device ``lax.scan``
    over the batches (self-describing across default changes).

Baseline anchor: the reference's published tf_cnn_benchmarks ResNet number —
1656.82 total img/s on 16 GPUs = 103.55 img/s/GPU (``docs/benchmarks.rst:29-43``).
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

BASELINE_IMG_PER_SEC_PER_CHIP = {
    "resnet18": 1656.82 / 16.0,
    "resnet34": 1656.82 / 16.0,
    "resnet50": 1656.82 / 16.0,
    "resnet101": 1656.82 / 16.0,
    "resnet152": 1656.82 / 16.0,
}

# Peak dense bf16 FLOP/s per chip, by device_kind substring (public specs).
PEAK_BF16_FLOPS = [
    ("v6", 918e12),       # Trillium
    ("v5p", 459e12),
    ("v5 lite", 197e12),  # v5e device_kind is "TPU v5 lite"
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]

# Analytic forward FLOPs per image (public MAC tables x 2 FLOPs/MAC, the
# same multiply+add=2 convention as the peak table above and as XLA's
# HloCostAnalysis — verified: CPU cost analysis of resnet50 @64 b4 train
# reports 7.3 GF vs 8.0 GF from this table). Keyed at the model's native
# input size; conv FLOPs scale ~quadratically with the spatial side.
ANALYTIC_FWD_FLOPS_PER_IMAGE = {
    # model: (flops at native size, native side)
    "resnet18": (3.6e9, 224),
    "resnet34": (7.3e9, 224),
    "resnet50": (8.2e9, 224),
    "resnet101": (15.2e9, 224),
    "resnet152": (22.6e9, 224),
    "vgg16": (31.0e9, 224),
    "inception3": (11.4e9, 299),
}


def _analytic_flops_cnn(model, image_size, batch_per_chip):
    """Per-chip training-step FLOPs from public per-model tables: backward
    ~= 2x forward, so train = 3x fwd (the reference's benchmark convention,
    ``docs/benchmarks.rst:46-83``, counts images/sec; MFU needs FLOPs)."""
    entry = ANALYTIC_FWD_FLOPS_PER_IMAGE.get(model)
    if entry is None:
        return None
    fwd_native, native_side = entry
    fwd = fwd_native * (image_size / native_side) ** 2
    return 3.0 * fwd * batch_per_chip


def _analytic_flops_lm(n_params, n_layers, d_model, batch_per_chip, seq_len):
    """Per-chip training-step FLOPs, standard 6*N*tokens estimate plus the
    quadratic attention term (4*L*T^2*d fwd, x3 for train)."""
    return (6.0 * n_params * batch_per_chip * seq_len
            + 12.0 * n_layers * batch_per_chip * seq_len ** 2 * d_model)


def _flops_side_by_side(measured, analytic, steps_per_iter, best_dt,
                        device):
    """The ``detail`` fields for per-step FLOPs and MFU: XLA's cost
    analysis and the analytic table next to each other, each with the MFU
    it implies. Neither replaces the other; a reader who sees them far
    apart knows one of the two counts is wrong for this program."""
    if measured is not None and analytic is not None \
            and not 0.5 <= measured / analytic <= 2.0:
        print(
            f"[bench] cost-analysis FLOPs ({measured:.3g}) and the analytic "
            f"table ({analytic:.3g}) are {measured / analytic:.2g}x apart",
            file=sys.stderr, flush=True,
        )
    return {
        "flops_per_step_per_chip": round(measured) if measured else None,
        "flops_analytic_per_step_per_chip": (
            round(analytic) if analytic else None
        ),
        "mfu": _mfu(measured, steps_per_iter, best_dt, device),
        "mfu_analytic": _mfu(analytic, steps_per_iter, best_dt, device),
    }


def _parse_args(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--model", default="resnet50",
        choices=["resnet18", "resnet34", "resnet50", "resnet101",
                 "resnet152", "vgg16", "inception3", "transformer", "moe"],
        help="CNN img/sec benchmarks; 'transformer': a GPT-style LM "
             "(Pallas flash attention) in tokens/sec; 'moe': a "
             "Switch-style mixture-of-experts layer stack trained with "
             "expert parallelism (DP x EP alltoall) in tokens/sec",
    )
    parser.add_argument("--batch-size", type=int, default=32, help="per-chip batch")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--seq-len", type=int, default=1024,
                        help="transformer: sequence length")
    parser.add_argument("--devices", type=int, default=0,
                        help="use only the first N devices (0 = all); lets "
                             "a scaling-efficiency sweep compare 1 vs N on "
                             "the same host")
    parser.add_argument("--num-warmup-batches", type=int, default=5)
    parser.add_argument("--num-batches-per-iter", type=int, default=50)
    parser.add_argument("--num-iters", type=int, default=3)
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--smoke", action="store_true",
                        help="tiny shapes for CPU sanity runs")
    parser.add_argument(
        "--platform", default="tpu", choices=["tpu", "cpu"],
        help="'tpu' (default) sets nothing and fails unless JAX's default "
             "device is a TPU; 'cpu' pins the CPU backend with "
             "--cpu-devices virtual devices, for validating the harness",
    )
    parser.add_argument(
        "--cpu-devices", type=int, default=8,
        help="with --platform cpu: virtual host device count "
             "(--xla_force_host_platform_device_count), so collectives run "
             "over a real multi-device mesh",
    )
    parser.add_argument(
        "--scan", action=argparse.BooleanOptionalAction, default=True,
        help="fold each iter's batches into one on-device lax.scan",
    )
    parser.add_argument(
        "--micro", action="store_true",
        help="also run the eager-vs-compiled allreduce micro-benchmark "
             "(results go into the detail block)",
    )
    parser.add_argument(
        "--zero1", action="store_true",
        help="transformer: shard optimizer state over the data axis "
             "(ZeRO-1; parallel/zero.py) instead of replicating it",
    )
    parser.add_argument(
        "--quantized", action="store_true",
        help="transformer: int8 gradient wire (ops/quantized.py; ~1%% "
             "gradient noise at 8 ranks) — ring allreduce on the "
             "replicated path, ring reduce-scatter when composed with "
             "--zero1, per-bucket quantize inside the backward when "
             "composed with --overlap (docs/overlap.md)",
    )
    parser.add_argument(
        "--overlap", action="store_true",
        help="streamed in-backward gradient reduction (docs/overlap.md): "
             "per-layer-group bucket psums issued inside the backward so "
             "XLA can overlap them with remaining backward compute; "
             "composes with --quantized (int8 wire per streamed bucket) "
             "and with --zero1 (per-bucket reduce-scatter inside the "
             "backward, shard-local update, param all-gather — "
             "docs/overlap.md \"Streamed ZeRO-1\")",
    )
    parser.add_argument(
        "--tuned", default="",
        help="apply a pinned compiled-path tuning (tuned.json from "
             "tools/autotune_compiled.py; docs/autotune.md) to the "
             "benchmark step when its signature matches this "
             "program+mesh — the chosen knobs are reported in the JSON "
             "detail so tuner wins are attributable; a mismatch warns "
             "and runs untuned",
    )
    parser.add_argument(
        "--calibration", default="",
        help="transformer: calibration.json (tools/fleet_sim.py "
             "--calibrate; docs/simulation.md) pricing the report's "
             "`sim` block with measured per-hop constants — without "
             "it the block reports the prediction on generation "
             "defaults and an honest zero divergence ratio",
    )
    parser.add_argument(
        "--tp", type=int, default=0,
        help="transformer: composed DP x TP (docs/parallelism.md "
             "'Composed DP x TP fast path') — shard the model N ways "
             "over a 'model' mesh axis via the sharding-rules engine "
             "(make_train_step(rules=...)), one Megatron psum per "
             "half-block, with --overlap/--quantized/--zero1 scoped to "
             "the data axis only; the wire block then splits DP vs TP "
             "bytes",
    )
    parser.add_argument(
        "--tp-overlap", action="store_true",
        help="with --tp N: fuse the TP psums into chunked "
             "collective-matmul rings (docs/parallelism.md 'Fused TP "
             "overlap') — the residual stream token-shards, each "
             "in-block psum becomes all_gather_matmul + "
             "matmul_reduce_scatter, and the sim prices only the "
             "un-hideable remainder (chunk count rides "
             "HOROVOD_TP_OVERLAP_CHUNKS)",
    )
    parser.add_argument(
        "--rules", default="", choices=["", "gpt"],
        help="sharding-rules table for --tp (default: gpt, the shipped "
             "models/transformer.py table)",
    )
    parser.add_argument(
        "--serve", action="store_true",
        help="closed-loop benchmark of the hvd.serve() continuous-"
             "batching engine (docs/serving.md): N clients each keep "
             "one request in flight; p50/p99 request latency, tokens/s "
             "and mean batch occupancy land in the detail block",
    )
    parser.add_argument("--serve-clients", type=int, default=8,
                        help="--serve: concurrent closed-loop clients")
    parser.add_argument("--serve-requests", type=int, default=64,
                        help="--serve: total requests across clients")
    parser.add_argument("--serve-max-batch", type=int, default=8,
                        help="--serve: engine max batch size")
    parser.add_argument("--serve-max-wait-us", type=int, default=2000,
                        help="--serve: batcher head deadline")
    parser.add_argument("--serve-max-tokens", type=int, default=16,
                        help="--serve: tokens generated per request")
    parser.add_argument("--serve-replicas", type=int, default=1,
                        help="--serve: DP serving replicas")
    args = parser.parse_args(argv)
    if args.serve and args.zero1:
        parser.error(
            "--serve benchmarks the inference decode path: --zero1 "
            "shards OPTIMIZER state across data-parallel gradient "
            "updates (parallel/zero.py) and serving has no optimizer "
            "or gradients — drop --zero1"
        )
    if args.serve and args.overlap:
        parser.error(
            "--serve benchmarks the inference decode path: --overlap "
            "streams gradient reduce-scatter behind BACKWARD compute "
            "(docs/overlap.md) and serving runs no backward pass — "
            "drop --overlap"
        )
    if args.serve and args.quantized:
        parser.error(
            "--serve benchmarks the inference decode path: --quantized "
            "compresses the GRADIENT wire (ops/quantized.py) and "
            "serving moves no gradients — drop --quantized"
        )
    if args.serve:
        # Serving decodes the transformer LM; --model selects training
        # benchmark bodies and is ignored here.
        args.model = "transformer"
    if args.zero1 and args.model != "transformer":
        parser.error("--zero1 is implemented for --model transformer only")
    if args.quantized and args.model != "transformer":
        parser.error("--quantized applies to --model transformer only")
    if args.tp and args.model != "transformer":
        parser.error("--tp applies to --model transformer only")
    if args.rules and not args.tp:
        parser.error("--rules needs --tp N (the composed DP x TP mode)")
    if args.tp and args.tp < 2:
        parser.error("--tp needs a model-axis degree >= 2")
    if args.tp_overlap and not args.tp:
        parser.error(
            "--tp-overlap fuses the TENSOR-PARALLEL psums into chunked "
            "collective-matmul rings — without --tp N there is no "
            "model axis and no TP psum to fuse; add --tp N (N >= 2)"
        )
    if args.tp and not args.rules:
        args.rules = "gpt"
    return args


def _devices(platform: str, cpu_devices: int):
    """The devices the benchmark runs on, and the seconds the backend took
    to start. ``tpu`` sets nothing and refuses any other default device;
    ``cpu`` pins the CPU backend with ``cpu_devices`` virtual devices
    (before the backend's first use) so collectives run over a real
    multi-device mesh."""
    import re

    import jax

    if platform != "cpu":
        # A chip call starts on a fresh machine; keep what it compiles.
        from horovod_tpu.common.env import configure_compile_cache

        configure_compile_cache()
    else:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            os.environ.get("XLA_FLAGS", ""),
        )
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={cpu_devices}"
        ).strip()
        jax.config.update("jax_platforms", "cpu")
    t0 = time.time()
    devices = jax.devices()
    if platform != "cpu" and devices[0].platform != "tpu":
        raise SystemExit(
            f"bench: JAX's default device is {devices[0].platform!r}, not a "
            "TPU. A CPU run is asked for with --platform cpu; it is never "
            "a substitute for a device number."
        )
    return devices, time.time() - t0


def _resolve_tuned(args, params, mesh):
    """Resolve --tuned against the live program: returns
    ``(step_kwargs_or_None, detail_block_or_None)``. The detail block
    always lands in the report (matched or not) so a bench capture is
    attributable to the exact knobs that produced it."""
    if not getattr(args, "tuned", ""):
        return None, None
    from horovod_tpu import tune as T

    cfg = T.load_tuned(args.tuned)
    live = T.step_signature(params, mesh=mesh)
    matched = T.signatures_match(cfg.signature, live)
    if not matched:
        # Say WHY: a mismatch is either a different program (params
        # half) or the same program pinned on a DIFFERENT MESH.
        tuned_mesh = T.mesh_axes_hash(cfg.signature)
        live_mesh = T.mesh_axes_hash(live)
        if getattr(args, "quantized", False) and tuned_mesh != live_mesh:
            # The int8-wire verdict is a function of the mesh's hop
            # ladder — a tuning pinned on another mesh cannot vouch for
            # this wire, so --quantized --tuned across meshes is a hard
            # error, not a silent untuned fallback.
            raise SystemExit(
                f"bench: refusing --quantized with --tuned "
                f"{args.tuned}: the tuning was pinned on mesh-axes "
                f"hash {tuned_mesh} but this run's mesh axes hash to "
                f"{live_mesh} — re-run tools/autotune_compiled.py on "
                f"THIS mesh (or drop --quantized/--tuned)"
            )
        why = (
            f"mesh-axes hash {tuned_mesh} (pinned) vs {live_mesh} "
            f"(live)" + ("; params half matches"
                         if T.params_match(cfg.signature, live)
                         else "; params half differs too")
        )
        print(f"[bench] tuned signature mismatch: {why}",
              file=sys.stderr, flush=True)
        T.warn_signature_mismatch(cfg, live.get("hash", "?"), "bench")
    T.note_applied("file", cfg.signature_hash, matched, "bench")
    detail = {
        "path": args.tuned,
        "program": cfg.program,
        "signature": cfg.signature_hash,
        "matched": bool(matched),
        "knobs": dict(cfg.knobs) if matched else None,
    }
    return (T.tuned_step_kwargs(cfg) if matched else None), detail


def _sim_block(args, params, mesh, n_chips, measured_step_s, *,
               quantized_eff=False, tuned_kw=None, tp=0,
               tp_psum_bytes=0, tp_psums=0, tp_overlap=False,
               local_params=None):
    """Fleet-simulator cross-check for the transformer report
    (docs/simulation.md): the digital twin's predicted step time for
    THIS program at THIS chip count next to the measured one, plus the
    divergence ratio. Without a calibration the prediction runs on
    coarse generation defaults, so the ratio is an honest zero with a
    pointer at the calibration workflow rather than a fake
    agreement number. Never raises — a sim failure must not cost a
    bench capture."""
    try:
        from horovod_tpu import sim as hvdsim
        from horovod_tpu import tune as T
        from horovod_tpu.topo.model import detect_generation, synthetic_model

        spec = T.spec_from_params(
            "bench-transformer", local_params or params, mesh=mesh
        )
        config = {}
        if tuned_kw:
            config = {
                "fusion_threshold_bytes": tuned_kw["fusion_threshold_bytes"],
                "first_bucket_bytes": tuned_kw["first_bucket_bytes"],
            }
        calib = hvdsim.resolve_calibration(
            getattr(args, "calibration", "") or None
        )
        model = hvdsim.apply_calibration(
            synthetic_model(n_chips, generation=detect_generation()),
            calib, where="bench",
        )
        fixed_comm_us = 0.0
        tp_overlap_block = None
        if tp and tp > 1:
            # The composed TP psums as a fixed per-step ICI term
            # alongside the DP staircase (docs/parallelism.md).
            fixed_comm_us = hvdsim.tp_fixed_comm_us(
                model, int(tp_psum_bytes), int(tp),
                psums_per_step=int(tp_psums),
            )
            if tp_overlap:
                from horovod_tpu.ops.collective_matmul import (
                    resolve_chunks,
                )

                chunks = resolve_chunks(
                    max(int(args.batch_size) * int(args.seq_len)
                        // int(tp), 1)
                )
                fused_us = hvdsim.tp_fixed_comm_us(
                    model, int(tp_psum_bytes), int(tp),
                    psums_per_step=int(tp_psums),
                    overlap=True, chunks=chunks,
                )
                tp_overlap_block = {
                    "chunks": int(chunks),
                    "fixed_comm_us": round(float(fused_us), 4),
                    "classic_fixed_comm_us": round(
                        float(fixed_comm_us), 4
                    ),
                    # Priced with no adjacent-matmul hiding
                    # (compute_us=0) — an upper bound; the fused rings
                    # only improve as the matmul grows.
                    "compute_hidden_us": 0.0,
                }
                fixed_comm_us = fused_us
        program = hvdsim.program_from_spec(
            spec, config, fixed_comm_us=fixed_comm_us
        )
        res = hvdsim.simulate(
            model, program,
            hvdsim.SimConfig(
                wire_dtype="int8" if quantized_eff else "f32",
                zero1=bool(getattr(args, "zero1", False)),
                overlap=bool(getattr(args, "overlap", False)),
            ),
            steps=2,
        )
        predicted_s = res.mean_step_us / 1e6
        calibrated = calib is not None and model.source.endswith(
            "+calibrated"
        )
        block = {
            "predicted_step_time_s": round(predicted_s, 6),
            "measured_step_time_s": round(float(measured_step_s), 6),
            "scaling_efficiency": round(res.scaling_efficiency, 6),
            "ranks": int(n_chips),
            "calibrated": bool(calibrated),
            **({"tp": {
                "degree": int(tp),
                "fixed_comm_us": round(float(fixed_comm_us), 4),
                **({"overlap": tp_overlap_block}
                   if tp_overlap_block else {}),
            }} if tp and tp > 1 else {}),
        }
        if calibrated and measured_step_s > 0:
            block["divergence_ratio"] = round(
                predicted_s / float(measured_step_s), 6
            )
            from horovod_tpu import metrics as _metrics

            if _metrics.ACTIVE:
                _metrics.TAP.set(
                    "hvd_sim_divergence_ratio",
                    block["divergence_ratio"], hop="step",
                )
        else:
            block["divergence_ratio"] = 0.0
            block["note"] = (
                "no calibration applied — prediction uses coarse "
                "generation defaults; fit real constants with "
                "tools/fleet_sim.py --calibrate (docs/simulation.md "
                "'Calibration workflow') and pass --calibration / "
                "HOROVOD_CALIBRATION_FILE"
            )
        return block
    except Exception as e:  # noqa: BLE001 - advisory block only
        return {"error": repr(e)}


def _metric(name: str, device) -> str:
    """A device metric's name belongs to a run on the device. A
    ``--platform cpu`` run validates the harness; its line says so in the
    name, so it can never be filed as a chip reading."""
    return name if device.platform == "tpu" else f"cpu_harness_{name}"


def _peak_flops(device) -> float:
    kind = device.device_kind.lower()
    for key, peak in PEAK_BF16_FLOPS:
        if key in kind:
            return peak
    raise SystemExit(
        f"bench: device_kind {device.device_kind!r} is not in "
        "PEAK_BF16_FLOPS; add its published peak before reporting MFU"
    )


def _aot_compile(fn, *inputs, with_flops=True):
    """AOT-compile a jitted fn once. ``with_flops=False`` skips the cost
    analysis (scan callers analyze the single step separately — see
    _step_flops)."""
    lowered = fn.lower(*inputs)
    flops = _flops_from_cost_analysis(lowered) if with_flops else None
    return lowered.compile(), flops


def _step_flops(step_fn, *inputs) -> float | None:
    """Model FLOPs of ONE training step, from the step fn's pre-backend
    (lowered HLO) cost analysis. HloCostAnalysis counts a lax.scan body
    ONCE, not times trip count, so the scanned train loop must never be
    the thing analyzed — always analyze the single step and multiply by
    steps elsewhere."""
    try:
        flops = _flops_from_cost_analysis(step_fn.lower(*inputs))
    except Exception as e:
        print(f"[bench] step FLOPs analysis failed: {e!r}", file=sys.stderr)
        return None
    if flops is None:
        print("[bench] step FLOPs analysis returned no flops; "
              "mfu will be null", file=sys.stderr)
    return flops


def _mfu(flops_per_step, steps_per_iter, best_dt, device):
    """Model-FLOPs utilization vs the chip's peak bf16 rate. A device
    metric: None on a CPU run, and None when there is no FLOPs count.
    ``flops_per_step`` is PER DEVICE: the lowered shard_map module is the
    per-device SPMD program, so its cost analysis already excludes other
    chips' shards (verified: equal per-chip batch gives equal flops at 1
    and 8 devices)."""
    if flops_per_step is None or device.platform != "tpu":
        return None
    mfu = flops_per_step * steps_per_iter / best_dt / _peak_flops(device)
    if mfu > 1.0:
        # Physically impossible — the FLOPs count or the timer is wrong.
        # Never publish it as real.
        print(f"[bench] computed mfu {mfu:.3f} > 1.0 — FLOPs accounting "
              "inconsistent with throughput; publishing null",
              file=sys.stderr, flush=True)
        return None
    return round(mfu, 4)


def _flops_from_cost_analysis(obj) -> float | None:
    """Total FLOPs via ``obj.cost_analysis()`` (best-effort: not every
    backend exposes it). ``obj`` is a jax Lowered (pre-backend HLO
    analysis) or Compiled module."""
    try:
        cost = obj.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None


def _micro_benchmark():
    """Eager-vs-compiled allreduce overhead sweep at a REAL communicator
    size: spawns a 2-rank CPU job under the launcher running
    ``horovod_tpu.utils.micro_bench`` (single-process "eager" is a local
    identity, which measures nothing). Returns the worker's rows; see
    micro_bench.py for the columns.
    """
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    # The children are CPU-only on purpose: this process owns the chip, a
    # child that needed it would fail or hang, and what the sweep compares
    # is the eager control plane against a compiled psum on equal ground.
    env["JAX_PLATFORMS"] = "cpu"
    env["HOROVOD_CYCLE_TIME"] = "1"
    env["PYTHONPATH"] = os.pathsep.join(
        [repo, env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    with tempfile.TemporaryDirectory() as td:
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
             "--output-dir", td,
             sys.executable, "-m", "horovod_tpu.utils.micro_bench"],
            env=env, cwd=repo, capture_output=True, timeout=240, text=True,
        )
        out_path = os.path.join(td, "rank.0.out")
        out = open(out_path).read() if os.path.exists(out_path) else ""
    if proc.returncode != 0:
        raise RuntimeError(
            f"micro bench launcher rc={proc.returncode}: "
            f"{proc.stderr[-1000:]}"
        )
    for line in out.splitlines():
        if line.strip().startswith("{"):
            return json.loads(line)["rows"]
    raise RuntimeError(f"micro bench produced no JSON: {out!r}")


def run_lm_benchmark(args) -> int:
    """GPT-style decoder LM benchmark in tokens/sec — the long-context
    flagship path: Pallas flash attention (default attn of
    models/transformer.py), bf16 compute, fusion-bucketed gradient
    allreduce over the data axis, lax.scan over the timed batches."""
    if args.smoke:
        args.batch_size, args.seq_len = 2, 128
        args.num_batches_per_iter, args.num_iters = 2, 2
        dims = dict(d_model=128, n_heads=4, n_layers=2, vocab=512)
    else:
        # GPT-2-small-class: ~124M params at vocab 32k.
        dims = dict(d_model=768, n_heads=12, n_layers=12, vocab=32768)

    devices, init_s = _devices(args.platform, args.cpu_devices)

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hvdj
    from horovod_tpu.jax import _shard_map
    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.parallel.mesh import build_mesh

    if args.devices > 0:
        devices = devices[:args.devices]
    n_chips = len(devices)
    tp = int(args.tp or 0)
    if tp:
        if n_chips % tp:
            raise SystemExit(
                f"bench: --tp {tp} does not divide {n_chips} devices"
            )
        dp = n_chips // tp
        mesh = build_mesh({"data": dp, "model": tp}, devices=devices)
        global_batch = args.batch_size * dp
    else:
        dp = n_chips
        mesh = build_mesh({"data": n_chips}, devices=devices)
        global_batch = args.batch_size * n_chips
    T = args.seq_len

    model = TransformerLM(
        vocab_size=dims["vocab"], d_model=dims["d_model"],
        n_heads=dims["n_heads"], n_layers=dims["n_layers"], max_len=T,
    )
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(
        rng.randint(0, dims["vocab"], (global_batch, T)), jnp.int32
    )
    labels = jnp.asarray(
        rng.randint(0, dims["vocab"], (global_batch, T)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    tx = optax.adamw(3e-4)

    # Pinned offline tuning (--tuned; docs/autotune.md): applies to every
    # reduction mode — including zero1, whose streamed form shares the
    # threshold/first-bucket partition and wire dtype with the overlap
    # fast path (the tuner prices its RS+AG shape, tune/objective.py).
    # Explicit CLI flags win.
    tuned_kw, tuned_detail = _resolve_tuned(args, params, mesh)
    quantized_eff = bool(args.quantized) or bool(
        tuned_kw and tuned_kw["quantized"]
    )
    spg_kw = dict(quantized=quantized_eff)
    ar_kw = dict(quantized=quantized_eff)
    if tuned_kw:
        spg_kw.update(
            threshold_bytes=tuned_kw["fusion_threshold_bytes"],
            first_bucket_bytes=tuned_kw["first_bucket_bytes"],
        )
        ar_kw.update(
            fusion_threshold_bytes=tuned_kw["fusion_threshold_bytes"]
        )

    def loss_fn(p, tok, lab):
        logits = model.apply({"params": p}, tok)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, lab
        ).mean()

    if tp:
        # Composed DP x TP fast path (docs/parallelism.md): the
        # sharding-rules engine places the param tree on the
        # (data, model) mesh, the loss runs tp_apply's Megatron layers
        # (one psum per half-block, Pallas flash attention on the local
        # heads), and --overlap/--quantized/--zero1 apply to the DATA
        # axis only.
        from horovod_tpu.models.transformer import make_gpt_loss_fn

        composed_loss = make_gpt_loss_fn(
            dims["n_heads"], model_axis="model"
        )
        czk = dict(
            threshold_bytes=(
                tuned_kw["fusion_threshold_bytes"] if tuned_kw else None
            ),
            first_bucket_bytes=(
                tuned_kw["first_bucket_bytes"] if tuned_kw else None
            ),
        )
        if args.zero1:
            opt_state = hvdj.init_composed_zero1_state(
                tx, params, args.rules, mesh,
                quantized=quantized_eff, **czk,
            )
        else:
            opt_state = tx.init(params)
        composed_step = hvdj.make_train_step(
            composed_loss, tx, mesh, rules=args.rules,
            overlap=bool(args.overlap), quantized=quantized_eff,
            zero1=bool(args.zero1),
            tp_overlap=(True if args.tp_overlap else None),
            fusion_threshold_bytes=czk["threshold_bytes"],
            first_bucket_bytes=czk["first_bucket_bytes"],
        )

        def step(p, s, tok, lab):
            return composed_step(p, s, (tok, lab))
    elif args.zero1 and args.overlap:
        # Streamed ZeRO-1 (docs/overlap.md "Streamed ZeRO-1"): each
        # stream_param_groups bucket reduce-scatters INSIDE the backward
        # (int8 ring with --quantized), the shard-local update runs
        # against the per-bucket sharded state, and the updated shards
        # all-gather back — the overlap property of the streamed path at
        # half the gradient wire bytes.
        from horovod_tpu.parallel.zero import (
            Zero1State,
            init_zero1_stream_state,
            zero1_stream_update,
        )

        zknobs = dict(
            threshold_bytes=(
                tuned_kw["fusion_threshold_bytes"] if tuned_kw else None
            ),
            first_bucket_bytes=(
                tuned_kw["first_bucket_bytes"] if tuned_kw else None
            ),
        )
        # EF off in the bench — it measures throughput; the residual add
        # is elementwise noise (same policy as the overlap path).
        opt_state = init_zero1_stream_state(
            tx, params, n_chips, quantized=quantized_eff,
            error_feedback=False, **zknobs,
        )

        def step(p, s_stacked, tok, lab):
            s = jax.tree.map(lambda x: x[0], s_stacked)

            def streamed(p_, tok_, lab_):
                return loss_fn(
                    hvdj.stream_param_groups(
                        p_, zero1=True, quantized=quantized_eff, **zknobs
                    ),
                    tok_, lab_,
                )

            loss, grads = jax.value_and_grad(streamed)(p, tok, lab)
            p, new_opt = zero1_stream_update(
                tx, p, s.opt, grads, axis_name="data",
                n_shards=n_chips, quantized=quantized_eff, **zknobs,
            )
            news = Zero1State(opt=new_opt, ef=None)
            return (p, jax.tree.map(lambda x: x[None], news),
                    jax.lax.pmean(loss, "data"))
    elif args.zero1:
        # Optimizer state sharded 1/n_chips over the data axis; the
        # gradient allreduce becomes reduce-scatter + all-gather around
        # the shard-local update (parallel/zero.py). Post-hoc: the RS
        # waits for the whole backward (no overlap).
        from horovod_tpu.parallel.zero import init_zero1_state, zero1_update

        opt_state = init_zero1_state(
            tx, params, n_chips, quantized=quantized_eff
        )

        def step(p, s_stacked, tok, lab):
            s = jax.tree.map(lambda x: x[0], s_stacked)
            loss, grads = jax.value_and_grad(loss_fn)(p, tok, lab)
            p, s = zero1_update(
                tx, p, s, grads, axis_name="data", n_shards=n_chips,
                quantized=quantized_eff,
            )
            return (p, jax.tree.map(lambda x: x[None], s),
                    jax.lax.pmean(loss, "data"))
    else:
        opt_state = tx.init(params)

        def step(p, s, tok, lab):
            if args.overlap:
                def streamed(p_, tok_, lab_):
                    # --quantized composes here: each streamed bucket
                    # runs quantize->int8 ring->dequantize inside the
                    # backward trace (EF off in the bench — it measures
                    # throughput; the residual add is elementwise noise).
                    return loss_fn(
                        hvdj.stream_param_groups(p_, **spg_kw),
                        tok_, lab_
                    )

                loss, grads = jax.value_and_grad(streamed)(p, tok, lab)
            else:
                loss, grads = jax.value_and_grad(loss_fn)(p, tok, lab)
                grads = hvdj.allreduce_gradients(grads, **ar_kw)
            updates, s = tx.update(grads, s, p)
            p = optax.apply_updates(p, updates)
            return p, s, jax.lax.pmean(loss, "data")

    def scan_steps(p, s, tok, lab):
        def body(carry, _):
            p, s = carry
            p, s, loss = step(p, s, tok, lab)
            return (p, s), loss

        (p, s), losses = jax.lax.scan(
            body, (p, s), None, length=args.num_batches_per_iter
        )
        return p, s, losses[-1]

    state_spec = P("data") if args.zero1 else P()

    def _jit(f):
        return jax.jit(
            _shard_map(
                f, mesh,
                in_specs=(P(), state_spec, P("data"), P("data")),
                out_specs=(P(), state_spec, P()),
            ),
            donate_argnums=(0, 1),
        )

    if tp:
        # The composed dispatch builds (preflights the rules, matches
        # placement) on its first call — no AOT lowering to analyze;
        # MFU is reported null rather than guessed (the TP duplicate
        # compute of replicated layers would skew any analytic count).
        if args.scan:
            print("[bench] --tp: on-device scan disabled (the composed "
                  "step builds on first call)", file=sys.stderr)
            args.scan = False
        fn, flops_per_step = step, None
    elif args.scan:
        flops_per_step = _step_flops(
            _jit(step), params, opt_state, tokens, labels
        )
        fn, _ = _aot_compile(
            _jit(scan_steps), params, opt_state, tokens, labels,
            with_flops=False,
        )
    else:
        # One lowering serves both the FLOPs analysis and the compile.
        fn, flops_per_step = _aot_compile(
            _jit(step), params, opt_state, tokens, labels
        )

    # Warmup (same methodology as the CNN path: one scan call, or
    # --num-warmup-batches plain steps).
    for _ in range(1 if args.scan else max(args.num_warmup_batches, 1)):
        params, opt_state, loss = fn(params, opt_state, tokens, labels)
    float(loss)

    calls_per_iter = 1 if args.scan else args.num_batches_per_iter
    steps_per_iter = args.num_batches_per_iter
    # Fleet-tracing step tap (docs/timeline.md "Step spans"): with
    # HOROVOD_TRACE set the timed calls record host-side step-boundary
    # spans (stamped with the wire/overlap correlation ids) feeding the
    # per-step summary below; disabled, wrap_step returns fn UNCHANGED.
    from horovod_tpu import trace as _trace

    fn = _trace.wrap_step(
        fn,
        overlap=bool(args.overlap), quantized=quantized_eff,
        wire_dtype="int8" if quantized_eff else "f32",
    )
    tok_secs, iter_times = [], []
    for _ in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(calls_per_iter):
            params, opt_state, loss = fn(params, opt_state, tokens, labels)
        np.asarray(jax.device_get(jax.tree.leaves(params)[0].ravel()[:1]))
        dt = time.perf_counter() - t0
        iter_times.append(dt)
        tok_secs.append(global_batch * T * steps_per_iter / dt)

    total = float(np.mean(tok_secs))
    per_chip = total / n_chips
    flops_detail = _flops_side_by_side(
        flops_per_step,
        None if tp else _analytic_flops_lm(
            n_params, dims["n_layers"], dims["d_model"],
            args.batch_size, T,
        ),
        steps_per_iter, min(iter_times), devices[0],
    )

    # Wire-bytes attribution (analytic, the honest no-TPU evidence):
    # what one step's gradient exchange puts on the wire per chip — a
    # ring moves 2(n-1)/n of the payload; --quantized shrinks the
    # payload to int8+scales (common/quant.py byte math, the same
    # accounting the topo plans and the structural profiler use).
    # Composed (--tp): the DP ring runs over the data axis on each
    # rank's LOCAL gradient bytes (sharded kernels are 1/tp), and the
    # TP psums are accounted separately under per_axis.
    from horovod_tpu.common.quant import int8_wire_bytes

    grad_bytes = 4 * n_params
    tp_axis_block = None
    if tp:
        from horovod_tpu.parallel import rules as RUL

        specs = RUL.match_partition_rules(args.rules, params)
        local = RUL.local_shard_tree(params, specs, {"model": (0, tp)})
        grad_bytes = 4 * sum(
            int(np.prod(l.shape)) for l in jax.tree.leaves(local)
        )
        psum_payload = args.batch_size * T * dims["d_model"] * 2  # bf16
        tp_psums = 4 * dims["n_layers"]  # fwd psums + bwd conjugates
        tp_axis_block = {
            "psum_payload_bytes": int(psum_payload),
            "psums_per_step": int(tp_psums),
            "bytes_on_wire_per_step_per_chip": int(
                tp_psums * 2 * (tp - 1) / tp * psum_payload
            ),
            "wire_dtype": "bf16 (never quantized, never re-planned)",
            # The fused pair moves the same total: AG (n-1)/n + RS
            # (n-1)/n of the payload — fusion changes WHEN the bytes
            # move (inside the matmul), not how many.
            "path": ("collective_matmul (fused)" if args.tp_overlap
                     else "psum (classic)"),
        }
    ring_factor = 2 * (dp - 1) / max(dp, 1)
    rs_factor = (dp - 1) / max(dp, 1)
    full_wire = int(grad_bytes * ring_factor)
    rs_bytes = ag_bytes = None
    if args.zero1:
        # ZeRO-1 decomposes the exchange: gradient reduce-scatter
        # ((n-1)/n, int8-compressible) + parameter all-gather ((n-1)/n,
        # always full precision — replicas must stay exact). Reported
        # separately so "+overlap+zero1+quantized" savings are honest:
        # only the gradient hop shrinks.
        rs_bytes = int(
            (int8_wire_bytes(grad_bytes) if quantized_eff else grad_bytes)
            * rs_factor
        )
        ag_bytes = int(grad_bytes * rs_factor)
        wire_bytes = rs_bytes + ag_bytes
    else:
        wire_bytes = (
            int(int8_wire_bytes(grad_bytes) * ring_factor)
            if quantized_eff else full_wire
        )
    mode = (
        ("overlap+" if args.overlap else "")
        + ("quantized" if quantized_eff else
           ("streamed" if args.overlap else "posthoc"))
    )
    if args.zero1:
        mode += "+zero1"
    if tp:
        mode += f"+tp{tp}"
    if tuned_kw:
        mode += "+tuned"

    # Per-step skew summary (docs/timeline.md "Step spans & straggler
    # attribution"): a single-controller bench has one host process, so
    # cross-rank HOST skew is structurally zero here; a multi-process
    # `hvdrun` round gets real skew via the driver's
    # hvd_step_skew_seconds / hvd_straggler_total metrics and
    # tools/trace_merge.py.
    step_skew = {
        "p50_skew_s": 0.0,
        "p99_skew_s": 0.0,
        "worst_rank": None,
        "ranks_observed": 1,
        "note": "single-controller run: host-side cross-rank skew needs "
                "the multi-process launcher (hvd_step_skew_seconds / "
                "hvd_straggler_total on the driver's /metrics)",
    }

    measured_step_s = float(np.mean(iter_times)) / steps_per_iter
    sim_block = _sim_block(
        args, params, mesh, dp, measured_step_s,
        quantized_eff=quantized_eff, tuned_kw=tuned_kw,
        tp=tp,
        tp_psum_bytes=(
            tp_axis_block["psum_payload_bytes"] if tp_axis_block else 0
        ),
        tp_psums=(
            tp_axis_block["psums_per_step"] if tp_axis_block else 0
        ),
        tp_overlap=bool(args.tp_overlap),
        local_params=(local if tp else None),
    )

    print(json.dumps({
        "metric": _metric(
            "transformer_synthetic_tokens_per_sec_per_chip", devices[0]
        ),
        "value": round(per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None,
        "detail": {
            "total_tokens_per_sec": round(total, 1),
            "n_chips": n_chips,
            **({"mesh": {"data": dp, "model": tp},
                "rules": args.rules} if tp else {}),
            "batch_per_chip": args.batch_size,
            "seq_len": T,
            "n_params": n_params,
            "loss": float(loss),
            "platform": devices[0].platform,
            "device_kind": getattr(devices[0], "device_kind", "unknown"),
            "attention": "pallas-flash (interpret off-TPU)",
            "optimizer_state": "zero1-sharded" if args.zero1 else "replicated",
            "gradient_wire": (
                "int8-quantized" if quantized_eff else "full-precision"
            ),
            "reduction_mode": mode,
            "tuned": tuned_detail,
            "step_time_s": round(
                float(np.mean(iter_times)) / steps_per_iter, 6
            ),
            "wire": {
                "gradient_bytes": grad_bytes,
                "bytes_on_wire_per_step_per_chip": wire_bytes,
                "full_precision_bytes_on_wire_per_step_per_chip": full_wire,
                "savings_ratio": (
                    round(1.0 - wire_bytes / full_wire, 4)
                    if full_wire else 0.0
                ),
                **({
                    "reduce_scatter_bytes_per_step_per_chip": rs_bytes,
                    "all_gather_bytes_per_step_per_chip": ag_bytes,
                    "gradient_reduction_savings_ratio": (
                        round(1.0 - rs_bytes / (full_wire / 2), 4)
                        if full_wire else 0.0
                    ),
                } if args.zero1 else {}),
                **({
                    # Composed DP x TP: the split the
                    # hvd_axis_wire_bytes_total{axis,collective} metric
                    # reports live (docs/parallelism.md).
                    "per_axis": {
                        "data": {
                            "bytes_on_wire_per_step_per_chip": wire_bytes,
                            "local_gradient_bytes": grad_bytes,
                            "dp_degree": dp,
                        },
                        "model": dict(tp_axis_block, tp_degree=tp),
                    },
                } if tp_axis_block else {}),
            },
            "step_skew": step_skew,
            "sim": sim_block,
            "scan": bool(args.scan),
            **flops_detail,
            "backend_init_s": round(init_s, 1),
        },
    }), flush=True)
    return 0


def _analytic_flops_moe(d_model, d_hidden, vocab, n_layers,
                        tokens_per_chip):
    """Per-chip step FLOPs for the top-1 switch stack: each token runs
    ONE expert's two matmuls per layer plus embed/head projections
    (2 FLOPs/MAC, x3 for train)."""
    per_token_fwd = (
        n_layers * 2 * (2 * d_model * d_hidden)  # expert in+out matmuls
        + 2 * d_model * vocab                    # head projection
    )
    return 3.0 * per_token_fwd * tokens_per_chip


def run_moe_benchmark(args) -> int:
    """DP x EP mixture-of-experts benchmark in tokens/sec: Switch-style
    top-1 routing, experts sharded over the expert axis, token shards
    exchanged with lax.all_to_all over ICI (parallel/ep.py — a TPU-native
    extension; the reference has no alltoall at all, message.h:48-50)."""
    if args.smoke:
        args.batch_size, args.seq_len = 2, 64
        args.num_batches_per_iter, args.num_iters = 2, 2
        dims = dict(d_model=64, d_hidden=128, n_layers=2, experts=8,
                    vocab=512)
    else:
        dims = dict(d_model=512, d_hidden=2048, n_layers=4, experts=16,
                    vocab=32768)

    devices, init_s = _devices(args.platform, args.cpu_devices)

    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.parallel.ep import (
        init_moe_params,
        make_ep_train_step,
        moe_ffn,
    )
    from horovod_tpu.parallel.mesh import build_mesh

    if args.devices > 0:
        devices = devices[:args.devices]
    n_chips = len(devices)
    ep = 4 if n_chips % 4 == 0 else (2 if n_chips % 2 == 0 else 1)
    dp = n_chips // ep
    mesh = build_mesh({"data": dp, "expert": ep}, devices=devices)
    tokens_per_chip = args.batch_size * args.seq_len
    total_tokens = tokens_per_chip * n_chips

    rngs = jax.random.split(jax.random.PRNGKey(0), dims["n_layers"] + 2)
    params = {
        "embed": jax.random.normal(
            rngs[0], (dims["vocab"], dims["d_model"])) * 0.02,
        "layers": [
            init_moe_params(
                rngs[1 + i], d_model=dims["d_model"],
                d_hidden=dims["d_hidden"], num_experts=dims["experts"],
                num_expert_shards=ep,
            )
            for i in range(dims["n_layers"])
        ],
        "head": jax.random.normal(
            rngs[-1], (dims["d_model"], dims["vocab"])) * 0.02,
    }
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(
        rng.randint(0, dims["vocab"], (total_tokens,)), jnp.int32)
    labels = jnp.asarray(
        rng.randint(0, dims["vocab"], (total_tokens,)), jnp.int32)

    def loss_fn(p, batch):
        tok, lab = batch
        h = p["embed"][tok].astype(jnp.bfloat16)
        aux_total = 0.0
        for layer in p["layers"]:
            out, aux = moe_ffn(
                jax.tree.map(lambda x: x.astype(jnp.bfloat16), layer),
                h, expert_axis="expert",
            )
            h = h + out
            aux_total = aux_total + aux
        logits = (h @ p["head"].astype(jnp.bfloat16)).astype(jnp.float32)
        task = optax.softmax_cross_entropy_with_integer_labels(
            logits, lab
        ).mean()
        return task, aux_total

    step = make_ep_train_step(
        loss_fn, tx, mesh, params, opt_state, donate=False,
    )

    flops_per_step = _step_flops(step, params, opt_state, (tokens, labels))
    params, opt_state, loss = step(params, opt_state, (tokens, labels))
    float(loss)  # warmup barrier (includes compile)

    tok_secs, iter_times = [], []
    for _ in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            params, opt_state, loss = step(params, opt_state,
                                           (tokens, labels))
        np.asarray(jax.device_get(
            jax.tree.leaves(params)[0].ravel()[:1]))
        dt = time.perf_counter() - t0
        iter_times.append(dt)
        tok_secs.append(total_tokens * args.num_batches_per_iter / dt)

    total = float(np.mean(tok_secs))
    per_chip = total / n_chips
    flops_detail = _flops_side_by_side(
        flops_per_step,
        _analytic_flops_moe(dims["d_model"], dims["d_hidden"],
                            dims["vocab"], dims["n_layers"],
                            tokens_per_chip),
        args.num_batches_per_iter, min(iter_times), devices[0],
    )

    print(json.dumps({
        "metric": _metric(
            "moe_synthetic_tokens_per_sec_per_chip", devices[0]
        ),
        "value": round(per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None,
        "detail": {
            "total_tokens_per_sec": round(total, 1),
            "n_chips": n_chips,
            "mesh": {"data": dp, "expert": ep},
            "tokens_per_chip_per_step": tokens_per_chip,
            "n_params": n_params,
            "n_experts": dims["experts"],
            "loss": float(loss),
            "platform": devices[0].platform,
            "device_kind": getattr(devices[0], "device_kind", "unknown"),
            "routing": "switch-top1 (static capacity, all_to_all)",
            "scan": False,
            **flops_detail,
            "backend_init_s": round(init_s, 1),
        },
    }), flush=True)
    return 0


def run_serve_benchmark(args) -> int:
    """Closed-loop serving benchmark (docs/serving.md "Capacity
    planning"): ``--serve-clients`` threads each keep exactly one
    request in flight against a live :class:`ServeEngine`, so measured
    latency includes queueing + batching + decode — the lab twin of the
    open-loop ``tools/fleet_sim.py --serve`` sweep."""
    devices, init_s = _devices(args.platform, args.cpu_devices)

    import threading

    import jax
    import jax.numpy as jnp

    from horovod_tpu.jax import make_decode_step
    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.parallel.mesh import build_mesh
    from horovod_tpu.serve import ServeEngine

    if args.devices > 0:
        devices = devices[:args.devices]

    vocab, d_model, n_heads, n_layers, max_len = 256, 128, 4, 2, 128
    if args.smoke:
        vocab, d_model, n_heads, n_layers, max_len = 64, 32, 2, 1, 64
        args.serve_clients = min(args.serve_clients, 4)
        args.serve_requests = min(args.serve_requests, 16)

    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          n_heads=n_heads, n_layers=n_layers,
                          max_len=max_len)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, max_len), jnp.int32)
    )["params"]

    tp = int(args.tp or 0)
    mesh = rules = None
    if tp:
        if len(devices) < tp:
            _fail_json(args, f"--tp {tp} needs {tp} devices, have "
                             f"{len(devices)}")
            return 1
        mesh = build_mesh({"model": tp}, devices=devices[:tp])
        rules = args.rules or "gpt"
    step = make_decode_step(n_heads=n_heads, mesh=mesh, rules=rules,
                            dtype=jnp.float32)

    engine = ServeEngine(
        params, step,
        n_layers=n_layers, n_heads=n_heads, head_dim=d_model // n_heads,
        num_pages=max(64, 8 * args.serve_max_batch), page_size=8,
        max_batch_size=args.serve_max_batch,
        max_wait_us=args.serve_max_wait_us,
        max_context=max_len, replicas=args.serve_replicas,
        cache_dtype=jnp.float32,
    )

    n_clients = max(1, args.serve_clients)
    per_client = max(1, args.serve_requests // n_clients)
    results, res_lock = [], threading.Lock()

    def client(cid):
        rng = np.random.RandomState(1000 + cid)
        for j in range(per_client):
            prompt = [int(t) for t in
                      rng.randint(0, vocab, size=1 + rng.randint(8))]
            rid = engine.submit(prompt, max_tokens=args.serve_max_tokens,
                                request_id=f"c{cid}.{j}")
            comp = engine.result(rid, timeout=300.0)
            with res_lock:
                results.append(comp)

    with engine:
        # Warmup outside the timed window: the decode step compiles
        # once (batch padded to max_batch_size).
        warm = engine.submit([1, 2, 3], max_tokens=2, request_id="warmup")
        engine.result(warm, timeout=300.0)
        warm_batches = engine.batches
        t0 = time.time()
        threads = [threading.Thread(target=client, args=(c,),
                                    name=f"bench-client-{c}")
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.time() - t0
        batches = engine.batches - warm_batches
        occupancy = (
            (engine.batched_requests - 1) / batches if batches else 0.0
        )

    ok = [c for c in results if c is not None and c.outcome == "ok"]
    if not ok:
        _fail_json(args, "serving benchmark completed no requests")
        return 1
    lat_ms = np.sort([c.latency_s * 1e3 for c in ok])
    total_tokens = int(sum(len(c.tokens) for c in ok))
    tokens_per_s = total_tokens / wall if wall > 0 else 0.0

    print(json.dumps({
        "metric": _metric("serve_decode_tokens_per_sec", devices[0]),
        "value": round(tokens_per_s, 1),
        "unit": "tokens/s",
        "vs_baseline": None,
        "detail": {
            "requests": len(ok),
            "clients": n_clients,
            "replicas": args.serve_replicas,
            "latency_ms": {
                "p50": round(float(np.percentile(lat_ms, 50)), 3),
                "p99": round(float(np.percentile(lat_ms, 99)), 3),
                "mean": round(float(np.mean(lat_ms)), 3),
                "max": round(float(lat_ms[-1]), 3),
            },
            "requests_per_sec": round(len(ok) / wall, 2) if wall else 0.0,
            "batch_occupancy_mean": round(float(occupancy), 3),
            "batches": batches,
            "max_batch_size": args.serve_max_batch,
            "max_wait_us": args.serve_max_wait_us,
            "max_tokens": args.serve_max_tokens,
            "model": {"vocab": vocab, "d_model": d_model,
                      "n_heads": n_heads, "n_layers": n_layers,
                      "max_len": max_len},
            **({"mesh": {"model": tp}, "rules": rules} if tp else {}),
            "platform": devices[0].platform,
            "device_kind": getattr(devices[0], "device_kind", "unknown"),
            "init_s": round(init_s, 1),
        },
    }))
    return 0


def run_benchmark(args) -> int:
    if args.serve:
        return run_serve_benchmark(args)
    if args.model == "transformer":
        return run_lm_benchmark(args)
    if args.model == "moe":
        return run_moe_benchmark(args)
    if args.smoke:
        args.batch_size, args.image_size = 4, 64
        if args.model == "inception3":
            args.image_size = 96  # stem's VALID convs need >=75px
        args.num_batches_per_iter, args.num_iters = 2, 2
        args.num_classes = 100

    devices, init_s = _devices(args.platform, args.cpu_devices)

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hvdj
    from horovod_tpu.jax import _shard_map
    from horovod_tpu.models import get_model
    from horovod_tpu.parallel.mesh import build_mesh

    if args.devices > 0:
        devices = devices[:args.devices]
    n_chips = len(devices)
    mesh = build_mesh({"data": n_chips}, devices=devices)
    global_batch = args.batch_size * n_chips

    model = get_model(args.model, num_classes=args.num_classes)
    rng = jax.random.PRNGKey(0)
    dropout_rng = jax.random.PRNGKey(7)
    images = jnp.asarray(
        np.random.RandomState(0)
        .randn(global_batch, args.image_size, args.image_size, 3)
        .astype(np.float32)
    )
    labels = jnp.asarray(
        np.random.RandomState(1).randint(0, args.num_classes, (global_batch,)),
        dtype=jnp.int32,
    )

    variables = model.init(rng, images[:2], train=False)
    params = variables["params"]
    # VGG has no BatchNorm; keep the pipeline uniform with an empty dict.
    batch_stats = variables.get("batch_stats", {})
    has_bn = bool(batch_stats)
    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = tx.init(params)

    # Pinned offline tuning (--tuned; docs/autotune.md).
    tuned_kw, tuned_detail = _resolve_tuned(args, params, mesh)
    spg_kw, ar_kw = {}, {}
    if tuned_kw:
        spg_kw = dict(
            threshold_bytes=tuned_kw["fusion_threshold_bytes"],
            first_bucket_bytes=tuned_kw["first_bucket_bytes"],
            quantized=tuned_kw["quantized"],
        )
        ar_kw = dict(
            fusion_threshold_bytes=tuned_kw["fusion_threshold_bytes"],
            quantized=tuned_kw["quantized"],
        )

    def loss_fn(p, bs, x, y, it):
        var_in = {"params": p, **({"batch_stats": bs} if has_bn else {})}
        out = model.apply(
            var_in, x, train=True,
            mutable=["batch_stats"] if has_bn else False,
            rngs={"dropout": jax.random.fold_in(dropout_rng, it)},
        )
        if has_bn:
            logits, new_state = out
            new_bs = new_state["batch_stats"]
        else:
            logits, new_bs = out, bs
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        return loss, new_bs

    def step(p, bs, s, x, y, it):
        if args.overlap:
            def streamed(p_, bs_, x_, y_, it_):
                return loss_fn(
                    hvdj.stream_param_groups(p_, **spg_kw),
                    bs_, x_, y_, it_
                )

            (loss, new_bs), grads = jax.value_and_grad(
                streamed, has_aux=True
            )(p, bs, x, y, it)
        else:
            (loss, new_bs), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(p, bs, x, y, it)
            # The whole reference DistributedOptimizer pipeline: fusion-
            # bucketed allreduce of gradients over the data axis.
            grads = hvdj.allreduce_gradients(grads, **ar_kw)
        new_bs = jax.tree.map(lambda v: jax.lax.pmean(v, "data"), new_bs)
        updates, s = tx.update(grads, s, p)
        p = optax.apply_updates(p, updates)
        return p, new_bs, s, jax.lax.pmean(loss, "data")

    fn = jax.jit(
        _shard_map(
            step,
            mesh,
            in_specs=(P(), P(), P(), P("data"), P("data"), P()),
            out_specs=P(),
        ),
        donate_argnums=(0, 1, 2),
    )

    if args.scan:
        # Train-loop-on-device: one jit runs num_batches_per_iter steps via
        # lax.scan (zero host round-trips inside the timed region).
        def scan_steps(p, bs, s, x, y, it0):
            def body(carry, i):
                p, bs, s = carry
                p, bs, s, loss = step(p, bs, s, x, y, it0 + i)
                return (p, bs, s), loss

            (p, bs, s), losses = jax.lax.scan(
                body, (p, bs, s),
                jnp.arange(args.num_batches_per_iter),
            )
            return p, bs, s, losses[-1]

        fn_scan = jax.jit(
            _shard_map(
                scan_steps,
                mesh,
                in_specs=(P(), P(), P(), P("data"), P("data"), P()),
                out_specs=P(),
            ),
            donate_argnums=(0, 1, 2),
        )

    ex_args = (params, batch_stats, opt_state, images, labels, jnp.int32(0))
    if args.scan:
        flops_per_step = _step_flops(fn, *ex_args)
        timed_fn, _ = _aot_compile(fn_scan, *ex_args, with_flops=False)
    else:
        # One lowering serves both the FLOPs analysis and the compile.
        timed_fn, flops_per_step = _aot_compile(fn, *ex_args)

    # Warmup (includes compile when the AOT path was unavailable).
    it = 0
    if args.scan:
        params, batch_stats, opt_state, loss = timed_fn(
            params, batch_stats, opt_state, images, labels, jnp.int32(it)
        )
        it += args.num_batches_per_iter
    else:
        for _ in range(args.num_warmup_batches):
            params, batch_stats, opt_state, loss = timed_fn(
                params, batch_stats, opt_state, images, labels, jnp.int32(it)
            )
            it += 1
    float(loss)  # full device->host roundtrip barrier

    img_secs = []
    iter_times = []
    for _ in range(args.num_iters):
        t0 = time.perf_counter()
        if args.scan:
            params, batch_stats, opt_state, loss = timed_fn(
                params, batch_stats, opt_state, images, labels, jnp.int32(it)
            )
            it += args.num_batches_per_iter
        else:
            for _ in range(args.num_batches_per_iter):
                params, batch_stats, opt_state, loss = timed_fn(
                    params, batch_stats, opt_state, images, labels,
                    jnp.int32(it),
                )
                it += 1
        # Fetch a value that depends on the *updated params* of the final
        # step: guarantees every queued step fully executed before the
        # clock stops.
        first_param = jax.tree.leaves(params)[0]
        np.asarray(jax.device_get(first_param[..., :1]))
        dt = time.perf_counter() - t0
        iter_times.append(dt)
        img_secs.append(global_batch * args.num_batches_per_iter / dt)

    total = float(np.mean(img_secs))
    per_chip = total / n_chips

    flops_detail = _flops_side_by_side(
        flops_per_step,
        _analytic_flops_cnn(args.model, args.image_size, args.batch_size),
        args.num_batches_per_iter, min(iter_times), devices[0],
    )

    detail = {
        "total_img_per_sec": round(total, 2),
        "n_chips": n_chips,
        "batch_per_chip": args.batch_size,
        "image_size": args.image_size,
        "loss": float(loss),
        "platform": devices[0].platform,
        "device_kind": getattr(devices[0], "device_kind", "unknown"),
        "scan": bool(args.scan),
        "dtype": "bf16 compute / f32 params",
        "tuned": tuned_detail,
        **flops_detail,
        "backend_init_s": round(init_s, 1),
    }
    if args.micro:
        try:
            detail["micro_allreduce"] = _micro_benchmark()
        except Exception as e:
            print(f"[bench] micro benchmark failed: {e!r}", file=sys.stderr)

    print(
        json.dumps(
            {
                "metric": _metric(
                    f"{args.model}_synthetic_images_per_sec_per_chip",
                    devices[0],
                ),
                "value": round(per_chip, 2),
                "unit": "img/s/chip",
                "vs_baseline": (
                    round(per_chip / BASELINE_IMG_PER_SEC_PER_CHIP[args.model], 3)
                    if args.model in BASELINE_IMG_PER_SEC_PER_CHIP
                    and devices[0].platform == "tpu" else None
                ),
                "detail": detail,
            }
        ),
        flush=True,
    )
    return 0


def main() -> int:
    return run_benchmark(_parse_args())


if __name__ == "__main__":
    sys.exit(main())
