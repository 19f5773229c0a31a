"""Shared by the benchmark's tests: run one rehearsal and parse its last line."""

import copy
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# Every test file here imports this module first: the repo root goes first on
# sys.path so that ``benchmark`` is the package at the root. (No conftest.py in
# this directory: other tests import names from ``conftest`` and must find
# tests/conftest.py.)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "compared"}


def rehearse(cell, seed=3, seconds=1.5, trace=0, timeout=300):
    """``run.py --rehearse-cpu`` in a new process; returns (last line, stdout)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [l for l in out.stdout.splitlines() if l.strip()]
    return json.loads(lines[-1]), out.stdout


def run_in_process(capsys, argv):
    """``run.main`` in this process (the chip look is skipped by
    ``--rehearse-cpu``); returns the parsed last line."""
    from benchmark import run

    assert run.main(argv) == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    return json.loads(lines[-1])


# The committed limits are the chip's, at full size; a rehearsal at tiny sizes
# gets limits that its own sound run meets (asserted where they are used).
REHEARSAL_LIMITS = {"loss_rel": 1e-3, "first_grad_norm": 0.05,
                    "update_norm": 0.05, "nonfinite_losses": 0}


def added_benchmark(root):
    """The benchmark's data copied to ``root``, plus one new configuration,
    one new traffic mix and one new cell, none of which edits a file that was
    there. Returns the new manifest."""
    from benchmark import manifest

    committed = manifest.load_manifest()
    data = os.path.join(root, "benchmark")
    for sub in ("configs", "traffic", "cells"):
        shutil.copytree(os.path.join(manifest.HERE, sub),
                        os.path.join(data, sub))
    first = committed["workloads"][0]
    cfg_entry = next(c for c in committed["configs"] if c["name"] == first["config"])
    cfg = manifest.load_json(os.path.join(manifest.ROOT, cfg_entry["file"]))
    cfg.update(cfg["rehearsal"], n_layer=3)
    traffic = manifest.load_json(os.path.join(
        manifest.HERE, "traffic", first["traffic"] + ".json"))
    traffic.update(traffic["rehearsal"], per_chip_batch=3)
    cell = manifest.load_json(os.path.join(
        manifest.HERE, "cells", first["name"] + ".json"))
    cell["limits"] = REHEARSAL_LIMITS  # the committed ones are the chip's
    for path, body in (("configs/added-config.json", cfg),
                       ("traffic/added-mix.json", traffic),
                       ("cells/added-cell.json", cell)):
        with open(os.path.join(data, path), "w") as f:
            json.dump(body, f)
    new = copy.deepcopy(committed)
    new["configs"].append({
        "name": "added-config", "source": cfg_entry["source"],
        "file": "benchmark/configs/added-config.json",
        "reduced": cfg["reduced"], "why": "a later PR's configuration",
    })
    new["workloads"].append({
        "name": "added-cell", "config": "added-config",
        "traffic": "added-mix", "chips": 1, "why": "a later PR's cell",
    })
    for m in new["end_to_end"] + new["per_layer"]:
        if first["name"] in m.get("workloads", []):
            m["workloads"].append("added-cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(new, f)
    return new
