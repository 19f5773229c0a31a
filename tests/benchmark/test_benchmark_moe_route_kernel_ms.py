"""``moe_route_kernel_ms.train`` reads the expert layer's gather-sum kernel by
event name and opcode: on a hand-made trace whose answer can be worked out on
paper, on a scoped trace recorded on the chip (``benchmark/testdata/``), and
on a trace with no such event (the parent's side of a comparison: the metric
is left out). The last tests hold what it is the kernel's own part of: since
PR 40 the groups of both expert families leave a kernel under ``moe_route`` to
``moe_route`` (from PR 37 to PR 39 a rule on every ``pallas_call`` sent it to
``attn_fwd``)."""

import gzip
import json
import os
import types

import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import manifest
from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr

MS = 1_000_000
MATCH = lambda n: n.startswith("jit_step(")  # noqa: E731
NAME = "moe_route_kernel_ms.train"
CELLS = ("qwen3next-train-1chip", "lfm2moe-train-1chip")
reader = manifest.load_reader(NAME)

OPCODES = {
    "moe_combine.3": "custom-call", "moe_combine.7": "custom-call",
    "moe_combine": "custom-call", "moe_combine.9": "fusion",
    "moe_combine_rows.2": "custom-call", "attention.2": "custom-call",
    "gdn_fwd.4": "custom-call", "ragged-dot-none.5": "custom-call",
    "while.2": "while",
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


def test_kernel_events_are_found_and_the_rest_ignored():
    # 5 ms flash kernel, 6 ms moe_combine.3, 7 ms grouped product, 8 ms
    # moe_combine.7, a fusion that XLA happened to name moe_combine.9 (9 ms),
    # a kernel of another name that begins alike (10 ms), 11 ms delta-rule
    # kernel and a gather-sum without a number (12 ms); one more moe_combine.3
    # between the launches, which is no step's
    names = ["attention.2", "moe_combine.3", "ragged-dot-none.5",
             "moe_combine.7", "moe_combine.9", "moe_combine_rows.2",
             "gdn_fwd.4", "moe_combine"]
    found = reader.kernel_ns(_trace(names, outside=["moe_combine.3"]),
                             OPCODES, MATCH)
    assert found == ((6 + 8 + 12) * MS, 3)


@pytest.mark.parametrize("names,outside", [
    (["attention.2", "while.2", "ragged-dot-none.5", "moe_combine.9"], []),
    (["attention.2"], ["moe_combine.3", "moe_combine.7"]),   # in no launch
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


RECORDED = os.path.join(manifest.HERE, "testdata", "moe_combine_scoped.json.gz")


def test_recorded_chip_trace_reads_as_recorded():
    """Three launches of ``lfm2moe-train-1chip`` on the chip, cut to the
    events round the expert layers (``testdata/moe_combine_scoped.
    expected.json`` says how it was made): eight calls a step, two a sparse
    layer, and the number the reader gave when it was recorded. The same
    trace with the kernels taken out is the parent's: nothing to read."""
    with gzip.open(RECORDED, "rt") as f:
        scoped = json.load(f)
    expected = manifest.load_json(RECORDED.replace(".json.gz",
                                                   ".expected.json"))
    ns, calls = reader.kernel_ns(scoped, scoped["opcodes"], MATCH)
    assert calls == expected["calls_per_step"] == 8
    assert ns / 1e6 == pytest.approx(expected["moe_route_kernel_ms"], rel=1e-9)
    by_hand = [sum(e[2] for e in launch["ops"]
                   if e[0].startswith("moe_combine"))
               for launch in tr.per_launch(scoped["planes"][0], MATCH)]
    assert ns == tr.median(by_hand)
    # and every one of them lies under the scope moe_route
    assert all("moe_route" in scoped["scopes"][n] for n in scoped["scopes"]
               if n.startswith("moe_combine"))
    for line in scoped["planes"][0]["lines"]:
        line["events"] = [e for e in line["events"]
                          if not e[0].startswith("moe_combine")]
    assert reader.kernel_ns(scoped, scoped["opcodes"], MATCH) is None


def test_metric_is_declared_for_the_two_expert_cells():
    """Order-free: the entry exists, and its cells CONTAIN the two that run
    the expert layer (a later cell may join them)."""
    per_layer = manifest.load_manifest()["per_layer"]
    mine = [m for m in per_layer if m["name"] == NAME]
    assert len(mine) == 1
    entry = dict(mine[0])
    assert set(CELLS) <= set(entry.pop("workloads"))
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "Expert layer",
        "moves": "train_samples_per_s_per_chip",
    }
    layers = {m["layer"] for m in per_layer if m["name"].startswith("moe_")}
    assert layers == {"Expert layer"}
    for cell in CELLS:
        names = {m["name"] for m in manifest.Cell(
            manifest.load_manifest(), cell).per_layer()}
        assert {NAME, "moe_route_ms.train"} <= names


FWD = "jit(step)/hvd_loss_grad/jvp({lm})/layer_2/{ffn}/moe_route/"
BWD = ("jit(step)/hvd_loss_grad/transpose(jvp({lm}))/layer_2/{ffn}/"
       "moe_route/")


@pytest.mark.parametrize("family,lm,ffn", [
    ("qwen3_next", "Qwen3NextLM", "mlp"),
    ("lfm2_moe", "Lfm2MoeLM", "feed_forward"),
])
@pytest.mark.parametrize("opcode,path,group", [
    # a kernel is its scope's, so moe_route_ms.train holds the kernel's time
    ("custom-call", FWD + "moe_combine/pallas_call", "moe_route"),
    ("custom-call", FWD + "jit(_pallas)/moe_combine/pallas_call",
     "moe_route"),
    ("custom-call", BWD + "moe_combine/pallas_call", "moe_route"),
    ("custom-call", FWD + "pallas_call", "moe_route"),
    # and so is what lies round it
    ("fusion", FWD + "gather", "moe_route"),
    ("copy", FWD + "moe_combine/pallas_call", "moe_route"),
    ("fusion", FWD + "reshape", "moe_route"),
    ("fusion", BWD + "reduce_sum", "moe_route"),
    ("sort", FWD + "jit(argsort)/sort", "moe_route"),
])
def test_a_kernel_under_moe_route_falls_to_the_group_moe_route(
        family, lm, ffn, opcode, path, group):
    rules = sr.Groups(family).rules
    assert sr.group_of(rules, opcode, path.format(lm=lm, ffn=ffn)) == group
