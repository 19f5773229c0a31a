"""``attn_fwd_roofline`` holds ONE forward pass's bound against ONE pass's
share of the kernel's time (PR 40): on made-up launches of one, two and four
``attention.<n>`` events against families whose pass makes one and two calls.
Until PR 40 the reader held one pass's bound against every event of the step,
so a cell with recomputation on could not pass 50%."""

import json
import types

import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import manifest
from benchmark import trace_reduce as tr

MS = 1_000_000
MATCH = lambda n: n.startswith("jit_step(")  # noqa: E731
PEAK = 197e12   # v5e, bf16
reader = manifest.load_reader("attn_fwd_roofline")


def _trace(events, launches=3):
    """``launches`` launches of 100 ms, 120 ms apart; in each, ``events``
    forward kernel calls of 5 ms one after the other, a backward kernel
    (7 ms) and a fusion XLA happened to call ``attention_weights.3``."""
    names = [f"attention.{k + 2}" for k in range(events)]
    names += ["flash_bwd.9", "attention_weights.3"]
    modules = [["jit_step(1)", i * 120 * MS, 100 * MS]
               for i in range(launches)]
    ops = [[n, i * 120 * MS + k * 8 * MS, (7 if n == "flash_bwd.9" else 5) * MS]
           for i in range(launches) for k, n in enumerate(names)]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": modules},
        {"name": tr.OPS_LINE, "events": ops},
    ]}]}


def _run(events, layers, family=None):
    """A family whose pass makes ``layers`` calls, each 4 ms at the least
    (compute-bound), in a step that holds ``events`` calls of 5 ms."""
    family = family or types.SimpleNamespace(
        attn_fwd_cost=lambda cfg, traffic, batch: (
            layers * 0.004 * PEAK, 1.0),
        attn_fwd_calls=lambda cfg: layers,
    )
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(family=family, config={}, traffic={}),
        counters={"per_chip_batch": 1}, device_trace=_trace(events),
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
        launch_match=lambda: MATCH,
    )


@pytest.mark.parametrize("events,layers,passes", [
    (1, 1, 1),      # one layer, no recomputation (the share a call reaches)
    (2, 1, 2),      # one layer, recomputation on: both hybrid cells
    (4, 1, 4),
    (2, 2, 1),      # two layers, no recomputation: the gpt2-medium cells' kind
    (4, 2, 2),      # two layers, recomputation on
    (1, 2, 0.5),    # half a pass's calls: one call's time still counts twice
])
def test_one_passs_bound_over_one_passs_time(events, layers, passes, capsys):
    value = reader.compute(_run(events, layers))
    # every call is 5 ms against 4 at the least, however many there are
    assert value == pytest.approx(80.0)
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("attn_fwd_kernel: ")]
    assert len(line) == 1
    said = json.loads(line[0].split(": ", 1)[1])
    assert said["kernel_ms"] == 5.0 * events
    assert (said["calls_per_step"], said["calls_per_pass"],
            said["passes"]) == (events, layers, passes)
    assert said["least_ms"] == pytest.approx(4.0 * layers)
    assert said["bound"] == "compute"
    # what the reader held until PR 40: the bound against every event
    assert 100.0 * said["least_ms"] / said["kernel_ms"] == pytest.approx(
        80.0 / passes)


def test_other_events_are_not_the_kernels():
    found = reader.kernel_ns(_trace(2), MATCH)
    assert found == (10 * MS, 2)       # not flash_bwd.9, not attention_weights.3


def test_nothing_to_read_gives_nothing():
    bare = types.SimpleNamespace(
        attn_fwd_cost=lambda cfg, traffic, batch: (1.0, 1.0))
    assert reader.compute(_run(2, 1, family=bare)) is None     # no call count
    assert reader.compute(_run(0, 1)) is None                  # no kernel event
    run = _run(1, 1)
    run.device_trace = {"planes": []}
    assert reader.compute(run) is None                         # a CPU rehearsal


@pytest.mark.parametrize("cell,calls", [
    ("gpt2m-train-1chip", 24), ("gpt2m-train-dp4", 24),
    ("qwen3next-train-1chip", 1), ("lfm2moe-train-1chip", 1),
])
def test_families_say_how_many_calls_a_pass_makes(cell, calls):
    c = manifest.Cell(manifest.load_manifest(), cell)
    assert c.family.attn_fwd_calls(c.config) == calls
