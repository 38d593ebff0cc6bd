"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name the manifest
gives it: ``configs/<config>.json``, ``references/<config>.py``,
``traffic/<traffic>.json``, ``metrics/<metric>.py``. A later PR adds a
cell by adding files and manifest entries; nothing here lists names.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_manifest(path: str = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold ``-``)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration, traffic file and
    the metrics it has to report."""

    def __init__(self, manifest: dict, name: str):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; the manifest has "
                             f"{sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        conf = next(c for c in manifest["configs"]
                    if c["name"] == self.entry["config"])
        self.config_name = conf["name"]
        with open(os.path.join(ROOT, conf["file"])) as f:
            self.config = json.load(f)
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json("traffic", self.traffic_name + ".json")
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in manifest["per_layer"]
                          if m["moves"] in e2e
                          and name in m.get("workloads", [name])]

    def rehearsal(self) -> "Cell":
        """The same cell at the tiny sizes its files keep under
        ``rehearsal``: for the CPU rehearsal and the tests only."""
        self.config = {**self.config, **self.config.get("rehearsal", {})}
        self.traffic = {**self.traffic, **self.traffic.get("rehearsal", {})}
        return self
