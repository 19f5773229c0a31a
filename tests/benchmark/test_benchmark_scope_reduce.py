"""The scope-aware reduction: a step's device time by the program's own scopes.
On hand-made traces whose answers can be worked out on paper (a ``while`` that
holds its body's events is not counted twice; the groups sum to the busy
time), on a hand-encoded ``.xplane.pb`` (the scope path lies in the event
metadata's ``tf_op`` stat), and on the small trace with scope paths recorded on
chip 0 of the four-chip cell that ``benchmark/testdata/`` keeps."""

import glob
import gzip
import json
import os
import re
import types

import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import manifest
from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr

MS = 1_000_000
STEP = "jit(step)/"
LOSS = STEP + "hvd_loss_grad/"
FWD = LOSS + "jvp(TransformerLM)/"
BWD = LOSS + "transpose(jvp(TransformerLM))/"
MATCH = lambda n: n.startswith("jit_step(")  # noqa: E731

# name -> scope path, as the chip's trace gives them (PERF.md section 3)
SCOPES = {
    "attention.3": FWD + "block_0/attention/pallas_call",
    "copy.9": FWD + "block_0/attention/pallas_call",
    "fusion.1": FWD + "block_0/mlp/up/dot_general",
    "while.2": BWD + "block_0/attention/flash_bwd/while/body/dot_general",
    "fusion.20": BWD + "block_0/attention/flash_bwd/while/body/dot_general",
    "fusion.21": BWD + "block_0/attention/flash_bwd/while/body/exp",
    "fusion.4": BWD + "lm_head/dot_general",
    "fusion.5": LOSS + "jvp()/reduce_sum",
    "all-reduce.7": STEP + "hvd_exchange/reduce/psum",
    "fusion.8": STEP + "hvd_exchange/pack/concatenate",
    "all-reduce.9": STEP + "hvd_optimizer/hvd_exchange/reduce/psum",
    "fusion.10": STEP + "hvd_optimizer/hvd_exchange/reduce/div",
    "fusion.11": STEP + "hvd_optimizer/add",
    "copy-done.1": "",
    "psum.12": STEP + "hvd_exchange/reduce/psum",
}
# name -> HLO opcode: what the operation is, whatever it is called. JAX names
# the all-reduce of a one-leaf bucket psum.<n>.
OPCODES = {n: re.sub(r"\.\d+$", "", n) for n in SCOPES}
OPCODES.update({"attention.3": "custom-call", "psum.12": "all-reduce"})


def _ops(base):
    """One launch, 0-100 ms busy but for 96-100. The ``while`` (20-50) holds
    two body events (22-30, 30-48); an all-reduce (60-70) runs beside a
    fusion (66-76) that started later."""
    rows = [
        ("attention.3", 0, 10), ("copy.9", 10, 2), ("fusion.1", 12, 8),
        ("while.2", 20, 30), ("fusion.20", 22, 8), ("fusion.21", 30, 18),
        ("fusion.4", 50, 6), ("fusion.5", 56, 2), ("fusion.8", 58, 1),
        ("psum.12", 59, 1),
        ("all-reduce.7", 60, 10), ("fusion.10", 66, 10),
        ("all-reduce.9", 76, 10), ("fusion.11", 86, 8),
        ("copy-done.1", 94, 2),
    ]
    return [[n, base + s * MS, d * MS] for n, s, d in rows]


def _scoped(scopes=SCOPES, launches=3):
    modules = [["jit_step(1)", i * 120 * MS, 100 * MS]
               for i in range(launches)]
    events = [e for i in range(launches) for e in _ops(i * 120 * MS)]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": modules},
        {"name": tr.OPS_LINE, "events": events},
    ]}], "scopes": dict(scopes), "opcodes": dict(OPCODES)}


GROUPS = sr.Groups("gpt_dense")


def test_a_while_and_its_body_are_not_counted_twice():
    ops = _ops(0)
    own = dict(zip([e[0] for e in ops], sr.innermost_ns(ops, 0, 100 * MS)))
    # 20-22 and 48-50 are the while's own; its body's 26 ms are the body's
    assert own["while.2"] == 4 * MS
    assert own["fusion.20"] == 8 * MS and own["fusion.21"] == 18 * MS
    # the one that started last is the innermost: the all-reduce keeps 60-66
    assert own["all-reduce.7"] == 6 * MS and own["fusion.10"] == 10 * MS
    # every instant of the busy time went to exactly one event
    busy = tr.total(tr.merge(tr.as_intervals(ops)))
    assert sum(own.values()) == busy == 96 * MS
    assert sum(e[2] for e in ops) == 126 * MS  # what summing would give
    assert own["psum.12"] == 1 * MS


def test_clipped_to_the_launch_and_empty_is_empty():
    ops = [["a", 0, 10], ["b", 5, 10]]
    assert sr.innermost_ns(ops, 2, 12) == [3, 7]
    assert sr.innermost_ns([], 0, 10) == []


def test_groups_sum_to_the_busy_time_and_read_on_paper():
    got = sr.reduce(_scoped(), GROUPS, MATCH)
    assert got["steps"] == 2  # the first launch is skipped, as everywhere
    assert got["busy_ms"] == 96.0
    assert got["groups_sum_ms"] == pytest.approx(got["busy_ms"])
    assert got["worst_step_sum_gap"] == 0.0
    assert got["groups_ms"] == {
        "attn_fwd": 10.0,        # the kernel, not the copy beside it
        "attn_bwd": 30.0,        # the while's own 4 and its body's 26
        "head_loss": 8.0,        # lm_head's backward and the loss
        "embed": 0.0,
        "blocks_fwd": 10.0,      # the mlp, and the kernel's layout copy
        "blocks_bwd": 0.0,
        "exchange_reduce": 17.0,  # 59-66 of the step's, 76-86 of the wrapper's
        "exchange_copy": 11.0,   # the pack, and the wrapper's division
        "guard": 0.0,
        "optimizer": 8.0,        # outside the exchange inside it
        "unnamed": 2.0,
    }
    assert got["hvd_exchange"] == {
        "hvd_optimizer": {"collective_ms": 10.0, "copy_ms": 10.0},
        "step": {"collective_ms": 7.0, "copy_ms": 1.0},
    }


def test_a_program_without_the_scopes_gives_nothing_to_read():
    """The parent of the PR that brought the scopes: flax's module scopes are
    there, ``hvd_loss_grad`` is not, and the readers leave their metrics out
    instead of calling the loss, the wire and the update ``unnamed``."""
    old = {k: v.replace("hvd_loss_grad/", "").replace("hvd_optimizer/", "")
           .replace("hvd_exchange/reduce/", "").replace("flash_bwd/", "")
           for k, v in SCOPES.items()}
    assert sr.reduce(_scoped(old), GROUPS, MATCH) is None
    assert sr.reduce({"planes": [], "scopes": {}, "opcodes": {}}, GROUPS,
                     MATCH) is None


@pytest.mark.parametrize("opcode,path,group", [
    ("custom-call", FWD + "block_3/attention/pallas_call", "attn_fwd"),
    ("copy", FWD + "block_3/attention/pallas_call", "blocks_fwd"),
    ("while", BWD + "block_3/attention/flash_bwd/while/body/mul",
     "attn_bwd"),
    ("fusion", BWD + "block_3/attention/flash_bwd/reduce_sum", "attn_bwd"),
    ("fusion", BWD + "lm_head/dot_general", "head_loss"),
    ("fusion", FWD + "lm_head/dot_general", "head_loss"),
    ("fusion", LOSS + "jvp()/reduce_sum", "head_loss"),
    ("fusion", LOSS + "transpose(jvp())/div", "head_loss"),
    ("fusion", LOSS + "jvp(jit(take_along_axis))/gather", "head_loss"),
    ("fusion", FWD + "embeddings/jit(_take)/gather", "embed"),
    ("fusion", BWD + "pos_embeddings/jit(_take)/scatter-add", "embed"),
    ("fusion", FWD + "add", "embed"),
    ("fusion", FWD + "block_11/mlp/up/dot_general", "blocks_fwd"),
    ("fusion", FWD + "ln_f/mul", "blocks_fwd"),
    ("fusion", BWD + "block_0/ln_1/reduce_sum", "blocks_bwd"),
    ("fusion", BWD + "block_0/attention/query/dot_general", "blocks_bwd"),
    ("all-reduce", STEP + "hvd_exchange/reduce/psum", "exchange_reduce"),
    ("reduce-scatter", STEP + "hvd_exchange/reduce/reduce_scatter",
     "exchange_reduce"),
    ("all-gather", STEP + "hvd_optimizer/hvd_exchange/reduce/all_gather",
     "exchange_reduce"),
    ("fusion", STEP + "hvd_exchange/reduce/div", "exchange_copy"),
    ("fusion", STEP + "hvd_exchange/pack/concatenate", "exchange_copy"),
    ("fusion", STEP + "hvd_optimizer/hvd_exchange/unpack/slice",
     "exchange_copy"),
    ("fusion", STEP + "hvd_optimizer/hvd_guard/is_finite", "guard"),
    ("fusion", STEP + "hvd_guard/select_n", "guard"),
    ("fusion", STEP + "hvd_optimizer/add", "optimizer"),
    ("fusion", STEP + "hvd_optimizer/mul", "optimizer"),
    ("copy-done", "", "unnamed"),
    ("fusion", STEP + "pmean", "unnamed"),
    ("all-reduce-start", STEP + "hvd_exchange/reduce/psum",
     "exchange_reduce"),
    # the streamed path reduces inside the backward, under hvd_loss_grad
    ("all-reduce", LOSS + "transpose(hvd_loss_grad)/jvp(hvd_exchange/reduce)"
     "/psum", "exchange_reduce"),
    ("fusion", LOSS + "transpose(hvd_loss_grad)/jvp(hvd_exchange/unpack)"
     "/slice", "exchange_copy"),
])
def test_first_rule_that_matches_names_the_group(opcode, path, group):
    assert sr.group_of(GROUPS.rules, opcode, path) == group


def test_an_exchange_is_named_by_the_scope_it_lies_under():
    assert sr.exchange_parent(STEP + "hvd_exchange/pack/concatenate") == "step"
    assert sr.exchange_parent(
        STEP + "hvd_optimizer/hvd_exchange/reduce/psum") == "hvd_optimizer"
    # the streamed path reduces inside the backward, and JAX writes its
    # custom VJP's scopes so (lowered on the CPU, overlap=True)
    assert sr.exchange_parent(
        LOSS + "transpose(hvd_loss_grad)/jvp(hvd_exchange/reduce)/psum"
    ) == "hvd_loss_grad"


# --- the wire format -------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, stat_names, events):
    """An XPlane with the fields the reader reads, and a line (field 3) and a
    fixed-width field it must skip."""
    body = _field(1, 7) + _field(2, name) + _field(3, b"\x0a\x03abc")
    body += _varint(9 << 3 | 1) + b"\0" * 8
    for key, text in stat_names.items():
        body += _field(5, _field(1, key) + _field(2, _field(1, key)
                                                  + _field(2, text)))
    for key, (text, stats) in enumerate(events, 1):
        meta = _field(1, key) + _field(2, text) + _field(4, "shown")
        for sid, value in stats:
            stat = _field(1, sid) + (
                _field(7, value) if isinstance(value, int)
                else _field(5, value))
            meta += _field(5, stat)
        body += _field(4, _field(1, key) + _field(2, meta))
    return _field(1, body)


def test_the_path_is_read_from_the_event_metadatas_tf_op(tmp_path):
    names = {3: "hlo_category", 5: "tf_op", 9: "jit(step)/by/reference"}
    chip1 = _plane("/device:TPU:1", names, [
        ("%fusion.1 = f32[2]{0} fusion(%x)", [(5, "jit(step)/other:")]),
    ])
    chip0 = _plane("/device:TPU:0", names, [
        ("%fusion.1 = f32[2]{0} fusion(%x), kind=kLoop",
         [(3, "fusion"), (5, "jit(step)/hvd_optimizer/add:")]),
        ("%while.2 = (s32[]{:T(128)}, /*index=1*/f32[8]{0:T(8,128)S(1)}) "
         "while((s32[]{:T(128)}, f32[8]{0:T(8,128)S(1)}) %t), body=%b",
         [(3, "while")]),
        ("%psum.3 = f32[2]{0:T(128)} all-reduce(%y), channel_id=1", [(5, 9)]),
    ])
    host = _plane("/host:CPU", {}, [("bench:dispatch", [])])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(host + chip1 + chip0)
    paths, opcodes = sr.op_metadata(str(path))
    assert paths == {
        "fusion.1": "jit(step)/hvd_optimizer/add",
        "while.2": "",
        "psum.3": "jit(step)/by/reference",
    }
    # what it is, not what it is called
    assert opcodes == {"fusion.1": "fusion", "while.2": "while",
                       "psum.3": "all-reduce"}
    path.write_bytes(host)
    assert sr.op_metadata(str(path)) == ({}, {})


# --- files and readers -----------------------------------------------------

def test_every_group_file_parses_and_names_the_programs_scopes():
    from horovod_tpu import trace as hvd_trace

    files = glob.glob(os.path.join(manifest.HERE, "scope_groups", "*.json"))
    assert files
    for path in files:
        doc = manifest.load_json(path)
        groups = sr.Groups(os.path.basename(path)[:-len(".json")])
        assert groups.names[-1] == sr.UNNAMED
        assert tuple(doc["scopes"]) == hvd_trace.STEP_SCOPES
        assert doc["program_scope"] in doc["scopes"]
        for rule in doc["rules"]:
            assert set(rule) <= {"group", "path", "op", "why"}
        # every scope of the vocabulary falls to a group of its own rules
        for scope in doc["scopes"]:
            assert sr.group_of(groups.rules, "fusion",
                               "jit(step)/" + scope + "/x") != sr.UNNAMED


NEW = {"attn_bwd_ms.train": "attn_bwd", "head_loss_ms.train": "head_loss",
       "optimizer_ms.train": "optimizer", "grad_pack_ms": "exchange_copy"}


def test_new_readers_are_declared_and_read_their_group(monkeypatch, capsys):
    declared = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    result = sr.reduce(_scoped(), GROUPS, MATCH)
    run = types.SimpleNamespace(_scope_reduction=result)
    for name, group in NEW.items():
        assert declared[name]["source"] == "device_trace"
        assert declared[name]["unit"] == "ms"
        assert group in GROUPS.names
        assert (manifest.load_reader(name).compute(run)
                == result["groups_ms"][group])
    share = manifest.load_reader("scope_unnamed_share.train").compute(run)
    assert share == pytest.approx(100.0 * 2.0 / 96.0)
    # the scopes: line, one JSON object, printed by that reader
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("scopes: ")]
    assert len(line) == 1
    assert json.loads(line[0][len("scopes: "):])["hvd_exchange"].keys() == {
        "step", "hvd_optimizer"}


def test_readers_return_none_where_there_is_nothing_to_read(tmp_path):
    """No trace taken, no trace file, no device plane (a CPU rehearsal): the
    metric is left out of the line; nothing raises."""
    cell = types.SimpleNamespace(config={"family": "gpt_dense"})
    for trace in (False, True):
        run = types.SimpleNamespace(cell=cell, trace=trace,
                                    trace_dir=str(tmp_path),
                                    launch_match=lambda: MATCH)
        for name in list(NEW) + ["scope_unnamed_share.train"]:
            assert manifest.load_reader(name).compute(run) is None
    other = types.SimpleNamespace(
        cell=types.SimpleNamespace(config={"family": "no_such_family"}),
        trace=True, trace_dir=str(tmp_path), launch_match=lambda: MATCH)
    assert sr.of_run(other) is None


RECORDED = os.path.join(manifest.HERE, "testdata",
                        "lm_train_dp4_scoped.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace_reduces_to_known_groups():
    with gzip.open(RECORDED, "rt") as f:
        scoped = json.load(f)
    with open(RECORDED.replace(".json.gz", ".expected.json")) as f:
        want = json.load(f)
    got = sr.reduce(scoped, GROUPS, MATCH)
    assert got["steps"] == want["steps"] == 3
    assert got["groups_ms"] == pytest.approx(want["groups_ms"])
    assert got["busy_ms"] == pytest.approx(want["busy_ms"])
    assert got["worst_step_sum_gap"] == 0.0
    # the same busy time trace_reduce reads, so the groups sum to
    # step_device_ms.train
    plane = tr.device_planes(scoped)[0]
    assert got["busy_ms"] == tr.median(
        [l["busy"] for l in tr.per_launch(plane, MATCH)]) / 1e6
    assert got["groups_sum_ms"] == pytest.approx(got["busy_ms"], rel=1e-3)
    # the attention backward (an XLA scan then, kernels since PR 26) is
    # found, and only under its scope
    whiles = {n for n in scoped["scopes"] if re.match(r"while(\.\d+)?$", n)}
    assert whiles and all("flash_bwd" in scoped["scopes"][n] for n in whiles)
    # the program PR 23 recorded reduced the gradients twice, under the step
    # and under the optimizer's wrapper (since PR 28 the step opens the
    # wrapper and the cell holds one exchange): the reader tells the two apart
    assert set(got["hvd_exchange"]) == {"step", "hvd_optimizer"}
    for parent in got["hvd_exchange"].values():
        assert parent["collective_ms"] > 10.0
    assert got["groups_ms"]["unnamed"] / got["busy_ms"] < 0.03
    # JAX calls the all-reduce of a one-leaf bucket psum.<n>; it is one
    called_psum = {n for n, op in scoped["opcodes"].items()
                   if n.startswith("psum") and op == "all-reduce"}
    assert len(called_psum) == 4  # embeddings and lm_head, in both exchanges
