"""BENCHMARK.json and the files its names lead to. A cell is data: its entry
in ``workloads``, its configuration file, its traffic file and (optionally)
``cells/<cell>.json`` with the mesh, the step options and the limits
of ``correct``. Nothing here knows a cell by name."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root=None):
    return load_json(os.path.join(root or ROOT, "BENCHMARK.json"))


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise SystemExit(f"benchmark: no {what} named {name!r} (have: {known})")


def _rehearse(d):
    """A CPU rehearsal runs the same files at the sizes their ``rehearsal``
    object overrides; it is never a chip reading."""
    out = dict(d)
    for key, value in d.get("rehearsal", {}).items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = {**out[key], **value}
        else:
            out[key] = value
    return out


class Cell:
    def __init__(self, manifest, name, rehearse=False, root=None):
        root = root or ROOT
        data = os.path.join(root, "benchmark")
        self.manifest = manifest
        self.entry = _by_name(manifest["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = _by_name(manifest["configs"], self.entry["config"],
                             "configuration")
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            data, "traffic", self.entry["traffic"] + ".json"
        ))
        path = os.path.join(data, "cells", name + ".json")
        self.options = load_json(path) if os.path.exists(path) else {}
        if rehearse:
            self.config = _rehearse(self.config)
            self.traffic = _rehearse(self.traffic)
            self.options = _rehearse(self.options)
        self.family = importlib.import_module(
            f"benchmark.families.{self.config['family']}"
        )
        self.kind = importlib.import_module(
            f"benchmark.kinds.{self.traffic['kind']}"
        )

    def reference(self):
        return importlib.import_module(
            f"benchmark.reference.{self.family.REFERENCE}"
        )

    def _applies(self, metric):
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self):
        return [m for m in self.manifest["end_to_end"] if self._applies(m)]

    def per_layer(self):
        """The per-layer metrics to read in this cell: those that list it,
        and those without a list whose end-to-end metric it reports."""
        mine = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.manifest["per_layer"]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in mine:
                out.append(m)
        return out


def load_reader(name):
    """``layer_metrics/<name>.py``, loaded by path (names hold dots)."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.layer_metrics." + name.replace(".", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def peak_for(device_kind):
    peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in peaks:
        raise SystemExit(
            f"benchmark: device_kind {device_kind!r} is not in peaks.json; "
            "add its published peaks before reporting a share of them"
        )
    return peaks[device_kind]
