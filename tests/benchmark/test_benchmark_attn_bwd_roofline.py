"""``attn_bwd_roofline`` reads the backward flash kernels by event name and
opcode: on a hand-made trace whose answer can be worked out on paper, and on
the trace recorded on the chip before the backward was a kernel (nothing to
read there: the metric is left out, as on a parent's side of a comparison)."""

import gzip
import json
import os
import types

import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import manifest
from benchmark import trace_reduce as tr

MS = 1_000_000
MATCH = lambda n: n.startswith("jit_step(")  # noqa: E731
reader = manifest.load_reader("attn_bwd_roofline")

OPCODES = {
    "attention.3": "custom-call", "flash_bwd.4": "custom-call",
    "flash_bwd.5": "custom-call", "flash_bwd": "custom-call",
    "flash_bwd.9": "fusion", "while.2": "while", "fusion.1": "fusion",
}


def _trace(names, launches=3):
    """``launches`` launches of 100 ms, 120 ms apart; in each, the named
    ops one after the other, 5 ms each, the i-th of them i ms longer."""
    modules = [["jit_step(1)", i * 120 * MS, 100 * MS]
               for i in range(launches)]
    events = [[n, i * 120 * MS + k * 10 * MS, (5 + k) * MS]
              for i in range(launches) for k, n in enumerate(names)]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": modules},
        {"name": tr.OPS_LINE, "events": events},
    ]}]}


def test_kernel_events_are_found_and_the_rest_ignored():
    # 5 ms forward kernel, 6 ms while, then two backward kernels of 7 and
    # 8 ms, a fusion that XLA happened to name flash_bwd.9 (9 ms), and one
    # more kernel without a number (10 ms)
    names = ["attention.3", "while.2", "flash_bwd.4", "flash_bwd.5",
             "flash_bwd.9", "flash_bwd"]
    ns, calls = reader.kernel_ns(_trace(names), OPCODES, MATCH)
    assert ns == (7 + 8 + 10) * MS
    assert calls == 3


def test_no_kernel_event_gives_nothing_to_read():
    names = ["attention.3", "while.2", "fusion.1", "flash_bwd.9"]
    assert reader.kernel_ns(_trace(names), OPCODES, MATCH) is None
    assert reader.kernel_ns({"planes": []}, OPCODES, MATCH) is None


def test_recorded_parent_trace_reads_none():
    """The four-chip cell's trace kept from before this kernel: forward
    kernels ``attention.<n>``, backward ``while.<n>`` under ``flash_bwd``."""
    path = os.path.join(bench_helpers.ROOT, "benchmark", "testdata",
                        "lm_train_dp4_scoped.json.gz")
    with gzip.open(path, "rt") as f:
        scoped = json.load(f)
    assert any("flash_bwd" in p for p in scoped["scopes"].values())
    assert reader.kernel_ns(scoped, scoped["opcodes"], MATCH) is None


@pytest.mark.parametrize("cell,least_ms", [
    # 2 x (2 L B T^2 d) / 197e12: twice the forward's bound
    ("gpt2m-train-1chip", 2 * 2.0 * 24 * 128 * 1024 * 1024 * 64 / 197e12 * 1e3),
    ("qwen3next-train-1chip", 2 * 2.0 * 16 * 8192 * 8192 * 256 / 197e12 * 1e3),
])
def test_bound_is_twice_the_forwards(cell, least_ms):
    c = manifest.Cell(manifest.load_manifest(), cell)
    batch = c.traffic["per_chip_batch"]
    run = types.SimpleNamespace(
        cell=c, counters={"per_chip_batch": batch},
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
    )
    least, which = reader.bound(run)
    assert which == "compute"
    assert least * 1e3 == pytest.approx(least_ms)
    fwd, _ = manifest.load_reader("attn_fwd_roofline").bound(run)
    assert least == pytest.approx(2 * fwd)


def test_metric_is_declared_for_the_three_train_cells():
    entry = dict(next(m for m in manifest.load_manifest()["per_layer"]
                      if m["name"] == "attn_bwd_roofline"))
    # the list CONTAINS the three cells; later cells with attention join it
    assert {"gpt2m-train-1chip", "gpt2m-train-dp4",
            "qwen3next-train-1chip"} <= set(entry.pop("workloads"))
    assert entry == {
        "name": "attn_bwd_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Attention kernel",
        "moves": "train_samples_per_s_per_chip",
    }
