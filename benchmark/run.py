#!/usr/bin/env python3
"""One run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run; it runs on the machine it is started on, fails (non-zero,
no result line) when JAX's default device is not a TPU or there are fewer chips
than the cell asks for, and prints as its last line one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``compared``: each number `correct` compared beside its
limit, which are also the last lines on standard error. With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics. ``--rehearse-cpu`` runs the same files at their ``rehearsal`` sizes on
the CPU; its line says ``"platform": "cpu"`` and is never a chip reading.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse
import json
import math
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Context:
    """What a kind and the per-layer readers see of one run."""

    def __init__(self, cell, args, devices):
        from benchmark.spans import Recorder

        self.cell = cell
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.devices = devices
        self.spans = Recorder()
        self.counters = {}
        self.notes = {}
        self.reference_s = 0.0  # the plain reference's seconds: not set-up
        self.window_start = None
        self.window_end = None
        self.trace_dir = os.path.join(ROOT, ".bench_out", "trace", cell.name)
        self._tracing = False
        self._trace = None

    def note(self, key, value):
        self.notes[key] = value

    def memory_peak(self):
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks)

    def start_window(self):
        if self.trace:
            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
            self._tracing = self.spans.tracing = True
        self.window_start = time.perf_counter()

    def end_trace(self):
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = self.spans.tracing = False

    def end_window(self):
        self.window_end = time.perf_counter()
        self.end_trace()

    @property
    def device_trace(self):
        """The profiler's trace of the window, reduced to plain lists."""
        if self._trace is None:
            from benchmark import trace_reduce

            self._trace = trace_reduce.load_xplane(
                trace_reduce.find_xplane(self.trace_dir)
            )
        return self._trace

    def launch_match(self):
        pattern = re.compile(self.counters["launch_pattern"])
        return lambda name: bool(pattern.search(name))


def _devices(cell, rehearse, chips=None):
    import jax

    chips = cell.chips if chips is None else chips
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        raise SystemExit(
            f"benchmark: JAX's default device is {platform!r}, not a TPU; "
            "nothing to report (a CPU rehearsal needs --rehearse-cpu)"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"benchmark: cell {cell.name} needs {chips} chips, "
            f"found {len(devices)}"
        )
    return devices[:chips]


def _compile_cache():
    import jax

    # Always inside the checkout, at a fixed path (the path is part of the
    # cache's key), whatever JAX_COMPILATION_CACHE_DIR says: the two sides of a
    # comparison then share nothing, and no quota of another directory applies.
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _plain(value):
    """A number as JSON holds it; a non-finite one by its name."""
    return value if math.isfinite(value) else repr(value)


def _layer_metrics(ctx):
    from benchmark import manifest

    metrics = {}
    for m in ctx.cell.per_layer():
        value = manifest.load_reader(m["name"]).compute(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU; never a chip reading")
    args = ap.parse_args(argv)

    from benchmark import check_train, manifest, trace_reduce

    cell = manifest.Cell(manifest.load_manifest(), args.workload,
                         rehearse=args.rehearse_cpu)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}"
        )
    cache = None if args.rehearse_cpu else _compile_cache()
    devices = _devices(cell, args.rehearse_cpu)
    ctx = Context(cell, args, devices)
    print(f"benchmark: cell {cell.name} seed {ctx.seed} on "
          f"{len(devices)} x {devices[0].device_kind}; compile cache {cache}",
          flush=True)

    result = cell.kind.run(ctx)

    limits = cell.options["limits"]
    correct, rows = check_train.verdict(result["numbers"], limits)
    correct = correct and result["failed"] == 0
    for row in rows:
        print("compared: " + json.dumps(row), flush=True)
    setup_s = ctx.window_start - _PROCESS_START
    ctx.note("reference_s", ctx.reference_s)
    ctx.note("memory_peak_bytes", ctx.memory_peak())
    print("notes: " + json.dumps(ctx.notes), flush=True)

    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": ctx.notes.get("program_peak_bytes",
                                           ctx.memory_peak()),
    }
    line = {"correct": bool(correct), "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if ctx.trace:
        line["metrics"] = _layer_metrics(ctx)
        match = ctx.launch_match()
        per_chip = trace_reduce.busy_and_window(ctx.device_trace, match)
        if per_chip:
            device["busy_s"] = sum(b for b, _ in per_chip) / len(per_chip) / 1e9
            device["window_s"] = sum(w for _, w in per_chip) / len(per_chip) / 1e9
        line["breakdown"] = {
            "device_ops": trace_reduce.top_ops(ctx.device_trace, match),
            "idle_gaps": trace_reduce.idle_gaps(ctx.device_trace, match),
        }
    else:
        values = dict(result["values"], setup_s=setup_s)
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end()
        }
        for name, metric in line["metrics"].items():
            print(f"{name}: {metric['value']} {metric['unit']}", flush=True)
    line["device"] = device
    # each number compared beside its limit: last in the result's line, and
    # the last lines on standard error (what a refused run leaves behind)
    line["compared"] = {
        row["number"]: {"value": _plain(row["value"]), "limit": row["limit"]}
        for row in rows
    }
    print(json.dumps(line), flush=True)
    for row in rows:
        print(f"compared {row['number']} {row['value']!r} limit "
              f"{row['limit']!r} {'within' if row['within'] else 'OVER'}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
