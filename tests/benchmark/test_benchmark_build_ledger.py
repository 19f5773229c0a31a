"""``compiles_in_window`` and ``kernel_fallbacks.train`` read the program's
build ledger (``horovod_tpu.trace.build_ledger()``): on a hand-made ledger
whose answers can be worked out on paper, on a program that keeps no ledger
(the parent's side of a comparison: both are left out and nothing is printed),
and in the traced CPU rehearsal of ``gpt2m-train-1chip``, whose ``setup:``
line must hold the step's three phases under the name ``step``."""

import json
import types

import pytest

import bench_helpers
from benchmark import build_ledger, manifest

NAMES = ("compiles_in_window", "kernel_fallbacks.train")
WINDOW = (100.0, 110.0)


def _record(phase, fun, start, end, **cache):
    return {"phase": phase, "fun": fun, "dur_s": float(end - start),
            "end_perf_s": float(end), "end_wall_s": 1.7e9 + end, **cache}


def _ledger():
    """Import 80..82; ``step`` traced 83..87 with ``inner`` (an operation run
    at trace time) traced inside it 84..85, lowered 87..88, compiled 88..95; ``init`` compiled 96..97 (a
    cache hit); then one compile that ends INSIDE the window (``late``,
    104..105), one on its first instant, and one after it (the reference)."""
    return {
        "import_s": 2.0, "first_perf_s": 80.0,
        "compiles": [
            _record("trace", "inner", 84, 85),
            _record("trace", "step", 83, 87),
            _record("lower", "step", 87, 88),
            _record("compile", "step", 88, 95, cache="miss",
                    cache_retrieval_s=0.0),
            _record("compile", "init", 96, 97, cache="hit",
                    cache_retrieval_s=0.5),
            _record("compile", "edge", 99.5, 100.0, cache="miss",
                    cache_retrieval_s=0.0),
            _record("trace", "late", 103, 104),
            _record("compile", "late", 104, 105, cache=None,
                    cache_retrieval_s=0.0),
            _record("compile", "reference", 120, 125, cache="hit",
                    cache_retrieval_s=2.0),
        ],
        # the ledger's own counters run on through the reference
        "cache": {"cache_hits": 2, "cache_misses": 2,
                  "cache_retrieval_s": 2.5},
        "plans": {"flash_block_q": 512, "fusion_path": "posthoc"},
        "fallbacks": [
            {"op": "gdn_fwd", "reason": "head_width_not_whole_lanes",
             "shape": {"dk": 16}},
            {"op": "moe_combine", "reason": "row_not_whole_tiles",
             "shape": {"width": 64}},
        ],
    }


def _run():
    return types.SimpleNamespace(window_start=WINDOW[0], window_end=WINDOW[1])


def test_compiles_are_counted_by_where_they_end():
    inside = build_ledger.compiles_in(_ledger(), *WINDOW)
    # the window's own ends count; a trace in the window is no compile
    assert [r["fun"] for r in inside] == ["edge", "late"]
    assert build_ledger.compiles_in(_ledger(), 100.5, 103.9) == []


def test_setup_is_read_over_what_ended_before_the_window():
    line = build_ledger.setup(_ledger(), WINDOW[0], process_start_s=70.0)
    assert line == {
        "import_s": 2.0,
        "trace_s": 4.0,               # `inner` lies inside `step`: once
        "lower_s": 1.0,
        "compile_or_load_s": 8.5,     # 7 + 1 + 0.5, `edge` ends at the start
        "cache_hits": 1, "cache_misses": 2, "cache_retrieval_s": 0.5,
        "missed": ["step", "edge"],   # the costliest first
        "programs": 3,
        "accounted_s": 15.5,          # 2 + (83..95) + (96..97) + (99.5..100)
        "before_window_s": 20.0,
        "top": [["step", 4.0, 1.0, 7.0], ["inner", 1.0, 0.0, 0.0],
                ["init", 0.0, 0.0, 1.0], ["edge", 0.0, 0.0, 0.5]],
        "before_ledger_s": 10.0,
    }
    assert "before_ledger_s" not in build_ledger.setup(_ledger(), WINDOW[0])
    many = _ledger()
    many["compiles"] = [_record("compile", f"f{i}", 80 + i, 81 + i)
                        for i in range(12)]
    assert len(build_ledger.setup(many, WINDOW[0])["top"]) == 8


def test_readers_on_a_hand_made_ledger(monkeypatch, capsys):
    monkeypatch.setattr(build_ledger, "read", _ledger)
    compiles, fallbacks = (manifest.load_reader(n) for n in NAMES)
    assert compiles.compute(_run()) == 2
    out = capsys.readouterr().out
    assert 'compiled_in_window: [["edge", 0.5], ["late", 1.0]]' in out
    assert out.count("setup: ") == 1
    assert fallbacks.compute(_run()) == 2
    out = capsys.readouterr().out
    assert "plans: " + json.dumps(_ledger()["plans"]) in out
    assert "fallbacks: " + json.dumps(_ledger()["fallbacks"]) in out
    # a sound run: a number, not nothing
    quiet = dict(_ledger(), fallbacks=[], compiles=_ledger()["compiles"][:5])
    monkeypatch.setattr(build_ledger, "read", lambda: quiet)
    assert compiles.compute(_run()) == 0 and fallbacks.compute(_run()) == 0


def test_a_program_without_a_ledger_gives_nothing_to_read(monkeypatch, capsys):
    """The parent's side of this PR's comparison: ``horovod_tpu.trace`` has
    no ``build_ledger``; the readers return None, print nothing, raise
    nothing."""
    from horovod_tpu import trace

    monkeypatch.delattr(trace, "build_ledger")
    assert build_ledger.read() is None
    for name in NAMES:
        assert manifest.load_reader(name).compute(_run()) is None
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name,layer", zip(NAMES, ("Entry points",
                                                   "Step builders")))
def test_metric_is_declared_for_every_train_cell(name, layer):
    """Order-free: the entry exists once, with no ``workloads`` list, so every
    cell that reports the rate reads it, a later one too."""
    data = manifest.load_manifest()
    mine = [m for m in data["per_layer"] if m["name"] == name]
    assert mine == [{
        "name": name, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": layer,
        "moves": "train_samples_per_s_per_chip",
    }]
    for cell in data["workloads"]:
        read = manifest.Cell(data, cell["name"]).per_layer()
        assert name in [m["name"] for m in read]


def test_traced_rehearsal_reports_both_and_says_where_setup_went():
    line, out = bench_helpers.rehearse("gpt2m-train-1chip", seconds=0.5,
                                       trace=1)
    assert line["metrics"]["compiles_in_window"] == {"value": 0,
                                                     "unit": "count"}
    # the flash kernels run (interpreted) at the rehearsal's shapes too
    assert line["metrics"]["kernel_fallbacks.train"]["value"] == 0
    printed = {l.split(": ", 1)[0]: json.loads(l.split(": ", 1)[1])
               for l in out.splitlines()
               if l.startswith(("setup: ", "plans: ", "fallbacks: "))}
    setup = printed["setup"]
    assert setup["programs"] >= 1 and setup["before_ledger_s"] > 0
    assert 0 < setup["accounted_s"] <= setup["before_window_s"]
    # with no trace knob set the ledger holds the step's three phases under
    # the name `step`, and the cell's plan notes
    step = next(row for row in setup["top"] if row[0] == "step")
    assert all(seconds > 0 for seconds in step[1:])
    assert printed["fallbacks"] == []
    assert {"flash_block_q", "flash_bwd_one_pass", "fusion_path",
            "fusion_buckets", "optimizer"} <= set(printed["plans"])
