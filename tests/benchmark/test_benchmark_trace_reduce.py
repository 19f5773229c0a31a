"""The reduction from a trace to numbers: on a hand-made trace whose answers
can be worked out on paper, and on the small trace recorded on the chip that
``benchmark/testdata/`` keeps."""

import gzip
import json
import os
import types

import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import manifest
from benchmark import trace_reduce as tr

MS = 1_000_000


def _trace():
    """Two chips, three launches of ``jit_step`` each (100 ms apart, 80 ms
    long). In every launch of chip 0: a fusion 0-30, an all-reduce 30-50 of
    which 40-50 runs beside a fusion 40-60, a custom call 60-70, idle 70-80."""
    def ops(base):
        return [
            ["fusion.1", base, 30 * MS],
            ["all-reduce.7", base + 30 * MS, 20 * MS],
            ["fusion.2", base + 40 * MS, 20 * MS],
            ["custom-call.3:tpu_custom_call", base + 60 * MS, 10 * MS],
        ]
    planes = []
    for chip in (0, 1):
        modules = [["jit_step(1)", i * 100 * MS, 80 * MS] for i in range(3)]
        events = [e for i in range(3) for e in ops(i * 100 * MS)]
        planes.append({"name": f"/device:TPU:{chip}", "lines": [
            {"name": tr.MODULES_LINE, "events": modules},
            {"name": tr.OPS_LINE, "events": events},
        ]})
    planes.append({"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench:dispatch", 95 * MS, 2 * MS],
        ["bench:fetch_loss", 170 * MS, 29 * MS],
        ["not ours", 0, 5 * MS],
    ]}]})
    return {"planes": planes}


def test_interval_arithmetic():
    assert tr.merge([(0, 5), (3, 8), (10, 12)]) == [(0, 8), (10, 12)]
    assert tr.total(tr.merge([(0, 5), (3, 8), (10, 12)])) == 10
    assert tr.subtract([(0, 10)], [(2, 4), (6, 20)]) == [(0, 2), (4, 6)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_busy_idle_and_window_skip_the_first_launch():
    per_chip = tr.busy_and_window(_trace(), lambda n: n.startswith("jit_step"))
    # steady window: start of launch 1 (100 ms) to end of launch 2 (280 ms);
    # busy: 70 ms in each of the two launches
    assert per_chip == [(140 * MS, 180 * MS)] * 2


def test_per_launch_busy_period_and_collectives():
    plane = tr.device_planes(_trace())[0]
    launches = tr.per_launch(plane)
    assert [l["busy"] for l in launches] == [70 * MS, 70 * MS]
    assert [l["period"] for l in launches] == [100 * MS, None]
    summed, exposed = tr.collective_times(launches[0])
    assert summed == 20 * MS      # the all-reduce's duration
    assert exposed == 10 * MS     # 30-40: nothing else ran; 40-50 was hidden


def test_kernel_time_by_target_name():
    plane = tr.device_planes(_trace())[0]
    kernel = [e for e in tr.per_launch(plane)[0]["ops"]
              if e[0].endswith(":tpu_custom_call")]
    assert sum(e[2] for e in kernel) == 10 * MS
    assert tr.op_name(
        '%custom-call.3 = bf16[8]{0} custom-call(%p), '
        'custom_call_target="tpu_custom_call"'
    ) == "custom-call.3:tpu_custom_call"
    assert tr.op_name("%fusion.4 = f32[2]{0} fusion(%x)") == "fusion.4"


def test_breakdown_names_gaps_by_the_span_the_host_was_in():
    t = _trace()
    assert tr.top_ops(t)[0] == ["fusion.1", 0.06]
    gaps = dict(tr.idle_gaps(t))
    # 170-200 ms (end of launch 1's ops to launch 2): its middle lies in
    # fetch_loss; 270-280 lies in no span of ours
    assert gaps["fetch_loss"] == pytest.approx(0.03)
    assert gaps["between"] == pytest.approx(0.01)
    assert tr.host_spans(t) == [("dispatch", 95 * MS, 97 * MS),
                                ("fetch_loss", 170 * MS, 199 * MS)]


RECORDED = os.path.join(manifest.HERE, "testdata", "lm_train_1chip.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace_reduces_to_known_numbers():
    with gzip.open(RECORDED, "rt") as f:
        t = json.load(f)
    with open(RECORDED.replace(".json.gz", ".expected.json")) as f:
        want = json.load(f)
    plane = tr.device_planes(t)[0]
    match = lambda n: n.startswith("jit_step(")
    launches = tr.per_launch(plane, match)
    assert len(launches) == want["launches"]
    assert [l["busy"] for l in launches] == want["busy_ns"]
    assert tr.busy_and_window(t, match) == [tuple(want["busy_and_window"])]
    kernel = sum(e[2] for e in launches[0]["ops"]
                 if e[0].startswith("attention"))
    assert kernel == want["kernel_ns_first_launch"]
    assert 100e6 < kernel < 140e6  # 24 forward kernel calls, ~5 ms each


@pytest.mark.parametrize("name,is_one", [
    # XLA names the instruction after its opcode, or after the JAX primitive
    # where the exchange reduces one array: both are the wire (PR 40; before
    # it the second was read as no collective, a quarter of the wire's time)
    ("all-reduce.12", True), ("all-reduce", True), ("psum.221", True),
    ("psum", True), ("all-reduce-start.3", True), ("reduce-scatter.1", True),
    ("all-gather", True), ("collective-permute.2", True), ("all-to-all", True),
    ("fusion.3", False), ("reduce-window.101", False), ("copy-start.4", False),
    ("custom-call.3:tpu_custom_call", False), ("reduce.7", False),
])
def test_a_collective_is_known_by_either_name(name, is_one):
    assert tr.is_collective(name) is is_one


def test_the_wire_readers_hold_a_one_leaf_buckets_all_reduce():
    """``grad_collective_ms`` and ``grad_collective_exposed_ms`` on the
    hand-made trace with a ``psum.<n>`` of 6 ms in each launch's idle tail
    (70-76): 20 + 6 summed, 10 + 6 exposed."""
    t = _trace()
    for plane in tr.device_planes(t):
        ops = tr.line_events(plane, tr.OPS_LINE)
        ops += [["psum.9", i * 100 * MS + 70 * MS, 6 * MS] for i in range(3)]
        plane["lines"][1]["events"] = ops
    run = types.SimpleNamespace(
        device_trace=t, launch_match=lambda: lambda n: n.startswith("jit_step"))
    assert manifest.load_reader("grad_collective_ms").compute(run) == 26.0
    assert manifest.load_reader(
        "grad_collective_exposed_ms").compute(run) == 16.0


SCOPED = os.path.join(manifest.HERE, "testdata", "lm_train_dp4_scoped.json.gz")


@pytest.mark.skipif(not os.path.exists(SCOPED), reason="no recorded trace")
def test_recorded_four_chip_trace_reads_the_whole_wire():
    """Chip 0 of the four-chip cell as PR 23 recorded it (the gradients were
    reduced twice then: 28 all-reduces a step, four of them ``psum.<n>``). By
    name the reader now finds what the opcodes say is there: 56.45 ms a step,
    where the opcode's own name alone gave 42.01."""
    with gzip.open(SCOPED, "rt") as f:
        scoped = json.load(f)
    opcodes = scoped["opcodes"]
    by_opcode = {n for n, op in opcodes.items() if op.startswith("all-reduce")}
    assert {n for n in opcodes if tr.is_collective(n)} == by_opcode
    assert len(by_opcode) == 28
    assert sum(n.startswith("psum.") for n in by_opcode) == 4
    launches = tr.per_launch(tr.device_planes(scoped)[0],
                             lambda n: n.startswith("jit_step("))
    assert len(launches) == 3
    for launch in launches:
        summed, exposed = tr.collective_times(launch)
        named = sum(e[2] for e in launch["ops"]
                    if e[0].startswith("all-reduce"))
        assert summed == sum(e[2] for e in launch["ops"]
                             if e[0] in by_opcode)
        assert 56.4 * MS < summed < 56.5 * MS
        assert 41.9 * MS < named < 42.1 * MS
        assert exposed == summed   # synchronous, after the backward
