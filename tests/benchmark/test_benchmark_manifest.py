"""BENCHMARK.json against the contract's rules, and every name it holds
against the files it must lead to. The same rules are then held against a
fixture: a copy of the benchmark in a temporary root to which a cell, a
traffic mix and a configuration are ADDED as new files and new entries only,
as a later PR will add them. Nothing here knows a cell by name."""

import json
import os
import re

import pytest

import bench_helpers  # first: puts the repo root on sys.path
from benchmark import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = manifest.load_manifest()


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("added"))
    return root, bench_helpers.added_benchmark(root)


@pytest.fixture(params=["committed", "added"])
def case(request, added):
    """``(root, manifest)``: the committed benchmark, and the fixture."""
    return (manifest.ROOT, M) if request.param == "committed" else added


def test_top_level_keys_and_limits(case):
    _, m = case
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    assert len(json.dumps(m)) < 64 * 1024
    assert all(not w.startswith("/") and ".." not in w for w in m["command"])
    # at most a quarter of the cells, rounded down, on four chips; one always
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    # 2 + 14 x 24 runs of run_seconds + 60, 180 a cell, 1200 spare
    assert (2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("section,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
])
def test_entries_have_just_the_keys(section, keys, case):
    _, m = case
    names = [e["name"] for e in m[section]]
    assert len(names) == len(set(names))
    for e in m[section]:
        assert set(e) == keys, e["name"]
        assert NAME.match(e["name"])
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}


def test_metrics_follow_the_rules(case):
    _, m = case
    cells = [w["name"] for w in m["workloads"]]
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = list(e2e) + [x["name"] for x in m["per_layer"]]
    assert len(names) == len(set(names))
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert x["moves"] in e2e and x["moves"] != "setup_s"
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        for cell in x.get("workloads", []):
            assert cell in cells


def test_every_per_layer_metric_has_a_reader():
    for m in M["per_layer"]:
        assert callable(manifest.load_reader(m["name"]).compute)


def test_every_cell_resolves_to_files(case):
    root, m = case
    for w in m["workloads"]:
        cell = manifest.Cell(m, w["name"], root=root)
        assert cell.chips in (1, 4)
        assert cell.traffic["kind"] == cell.kind.__name__.rsplit(".", 1)[1]
        assert hasattr(cell.family, "param_spec")
        assert hasattr(cell.reference(), "loss")
        assert "limits" in cell.options
        reported = {x["name"] for x in cell.end_to_end()}
        assert "setup_s" in reported and len(reported) >= 2
        assert set(cell.kind.END_TO_END) <= reported
        assert cell.per_layer(), "a cell reports at least one per-layer metric"
        for x in cell.per_layer():
            assert x["moves"] in reported


def test_added_cell_reports_what_its_metrics_list(added):
    """A metric without a ``workloads`` list is read in every cell that
    reports the end-to-end metric it moves, a later PR's cells too; a metric
    with a list only where the list names the cell."""
    root, m = added
    first = manifest.Cell(m, m["workloads"][0]["name"], root=root)
    new = manifest.Cell(m, "added-cell", root=root)
    assert new.config["n_layer"] == 3 and new.traffic["per_chip_batch"] == 3
    assert ([x["name"] for x in new.per_layer()]
            == [x["name"] for x in first.per_layer()])
    everywhere = [x["name"] for x in m["per_layer"] if "workloads" not in x]
    assert everywhere and set(everywhere) <= {x["name"]
                                              for x in new.per_layer()}


def test_config_files_lie_under_paths_and_state_their_cut(case):
    root, m = case
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))
    for c in m["configs"]:
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        cfg = manifest.load_json(os.path.join(root, c["file"]))
        assert cfg["reduced"] == c["reduced"]
        for key in ("family", "source", "departures", "assumed",
                    "deployment"):
            assert key in cfg


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in M["paths"]:
        for base, _, files in os.walk(os.path.join(manifest.ROOT, p)):
            if "__pycache__" in base:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), manifest.ROOT)
                assert ok.match(rel), rel


def test_unknown_device_is_an_error_not_a_default():
    assert manifest.peak_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit):
        manifest.peak_for("TPU v9000")
