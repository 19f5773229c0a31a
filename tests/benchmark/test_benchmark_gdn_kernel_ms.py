"""``gdn_kernel_ms.train`` reads the delta rule's chunk-local kernels by event
name and opcode: on a hand-made trace whose answer can be worked out on paper,
and on a trace with no such event (the parent's side of a comparison: the
metric is left out). The last tests hold what it is the kernels' own part
of: since PR 40 the groups of ``scope_groups/qwen3_next.json`` leave a kernel
under ``gdn_scan`` to the delta rule's groups (from PR 31 to PR 39 a rule on
every ``pallas_call`` sent it to ``attn_fwd``)."""

import types

import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import manifest
from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr

MS = 1_000_000
MATCH = lambda n: n.startswith("jit_step(")  # noqa: E731
NAME = "gdn_kernel_ms.train"
reader = manifest.load_reader(NAME)

OPCODES = {
    "gdn_fwd.3": "custom-call", "gdn_fwd.7": "custom-call",
    "gdn_bwd.4": "custom-call", "gdn_bwd": "custom-call",
    "gdn_fwd.9": "fusion", "attention.2": "custom-call",
    "flash_bwd.5": "custom-call", "while.2": "while",
}


def _trace(names, launches=3, outside=()):
    """``launches`` launches of 100 ms, 120 ms apart; in each, the named ops
    one after the other, 5 ms each, the i-th of them i ms longer; the ops of
    ``outside`` run in the gap after each launch."""
    modules = [["jit_step(1)", i * 120 * MS, 100 * MS]
               for i in range(launches)]
    events = [[n, i * 120 * MS + k * 10 * MS, (5 + k) * MS]
              for i in range(launches) for k, n in enumerate(names)]
    events += [[n, i * 120 * MS + 105 * MS, 3 * MS]
               for i in range(launches) for n in outside]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": modules},
        {"name": tr.OPS_LINE, "events": sorted(events, key=lambda e: e[1])},
    ]}]}


def test_kernel_events_are_found_by_direction_and_the_rest_ignored():
    # 5 ms flash kernel, 6 ms gdn_fwd.3, 7 ms while, 8 ms gdn_bwd.4, a fusion
    # that XLA happened to name gdn_fwd.9 (9 ms), 10 ms gdn_fwd.7, 11 ms
    # flash_bwd.5 and a backward kernel without a number (12 ms); one more
    # gdn_fwd.3 between the launches, which is no step's
    names = ["attention.2", "gdn_fwd.3", "while.2", "gdn_bwd.4", "gdn_fwd.9",
             "gdn_fwd.7", "flash_bwd.5", "gdn_bwd"]
    found = reader.kernel_ns(_trace(names, outside=["gdn_fwd.3"]), OPCODES,
                             MATCH)
    assert found["fwd"] == ((6 + 10) * MS, 2)
    assert found["bwd"] == ((8 + 12) * MS, 2)
    assert found["all"] == ((6 + 10 + 8 + 12) * MS, 4)


def test_a_forward_alone_is_read():
    found = reader.kernel_ns(_trace(["gdn_fwd.3"]), OPCODES, MATCH)
    assert found["fwd"] == (5 * MS, 1) and found["bwd"] == (0, 0)
    assert found["all"] == (5 * MS, 1)


@pytest.mark.parametrize("names,outside", [
    (["attention.2", "while.2", "flash_bwd.5", "gdn_fwd.9"], []),
    (["attention.2"], ["gdn_fwd.3", "gdn_bwd.4"]),   # in no launch
])
def test_no_kernel_event_gives_nothing_to_read(names, outside):
    assert reader.kernel_ns(_trace(names, outside=outside), OPCODES,
                            MATCH) is None
    assert reader.kernel_ns({"planes": []}, OPCODES, MATCH) is None


def test_compute_returns_none_without_a_trace(tmp_path):
    assert reader.compute(types.SimpleNamespace(trace=False)) is None
    # traced, and no profile was written (a CPU rehearsal's run directory)
    assert reader.compute(types.SimpleNamespace(
        trace=True, trace_dir=str(tmp_path))) is None


def test_metric_is_declared_for_the_qwen3_next_cell_alone():
    # "alone" of the cells this PR knew: a later cell with a delta rule may
    # join the list, and a later metric may stand behind this one
    per_layer = manifest.load_manifest()["per_layer"]
    entry = next(m for m in per_layer if m["name"] == NAME)
    assert "qwen3next-train-1chip" in entry.pop("workloads")
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "Delta rule",
        "moves": "train_samples_per_s_per_chip",
    }
    layers = {m["layer"] for m in per_layer if m["name"].startswith("gdn_")}
    assert layers == {"Delta rule"}


QWEN = sr.Groups("qwen3_next")
FWD = "jit(step)/hvd_loss_grad/jvp(Qwen3NextLM)/layer_0/linear_attn/gdn_scan/"
BWD = ("jit(step)/hvd_loss_grad/transpose(jvp(Qwen3NextLM))/layer_0/"
       "linear_attn/gdn_scan/")


@pytest.mark.parametrize("opcode,path,group", [
    # a kernel is its scope's: the first pass's call is the forward's, the
    # recomputed call and the backward kernel the backward's, so gdn_ms.train
    # holds the kernels' time and gdn_fwd_roofline the first call's
    ("custom-call", FWD + "gdn_fwd/pallas_call", "gdn_scan_fwd"),
    ("custom-call", BWD + "gdn_fwd/pallas_call", "gdn_scan_bwd"),
    ("custom-call", BWD + "gdn_bwd/pallas_call", "gdn_scan_bwd"),
    ("custom-call", FWD + "pallas_call", "gdn_scan_fwd"),
    # and so is what lies round them
    ("while", FWD + "while/body/dot_general", "gdn_scan_fwd"),
    ("fusion", FWD + "cumsum", "gdn_scan_fwd"),
    ("copy", FWD + "gdn_fwd/pallas_call", "gdn_scan_fwd"),
    ("while", BWD + "while/body/dot_general", "gdn_scan_bwd"),
    ("fusion", BWD + "reduce_sum", "gdn_scan_bwd"),
])
def test_a_kernel_under_gdn_scan_falls_to_the_delta_rules_groups(opcode, path,
                                                                 group):
    assert sr.group_of(QWEN.rules, opcode, path) == group
