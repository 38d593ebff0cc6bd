"""BENCHMARK.json against the contract's static rules, and against the
files it names."""
import json
import os
import re

import pytest

from benchmark.lib import manifest

M = manifest.load_manifest()
WITHHELD = os.path.join(os.path.dirname(__file__), "data",
                        "withheld-serving.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj|head_dim|"
                    r"_dim$|_rank$|expansion|per_tok)")


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    n = 24
    assert (2 + 14 * n) * (M["run_seconds"] + 60) + n * 180 + 1200 <= 43200
    assert len(json.dumps(M)) < 64 * 1024
    assert 1 <= len(M["paths"]) <= 16 and len(M["command"]) <= 32
    assert 1 <= len(M["configs"]) <= 24 and 1 <= len(M["workloads"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16
    assert 1 <= len(M["per_layer"]) <= 128


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in M[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            for key in ("why", "layer", "source"):
                if key in e and group != "end_to_end":
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                        and "\t" not in e[key], (e["name"], key)
    assert len(set(names)) == len(names)
    for e in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in M["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for e in M["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
        assert not any(WIDTHS.search(k) for k in c["reduced"])


def test_cells_configs_and_chips():
    cells = M["workloads"]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    used = {w["config"] for w in cells}
    files = [c["file"] for c in M["configs"]]
    assert len(set(files)) == len(files)
    for c in M["configs"]:
        assert c["name"] in used, f"{c['name']} has no cell"
        assert c["file"].startswith(tuple(p + "/" for p in M["paths"]))
        assert os.path.exists(os.path.join(manifest.ROOT, c["file"]))
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "references", c["name"] + ".py")), \
            f"{c['name']} has no plain reference"
        conf = json.load(open(os.path.join(manifest.ROOT, c["file"])))
        assert conf["reduced"] == c["reduced"]
        assert "assumed" in conf and "limits" in conf and "source" in conf
    for w in cells:
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "traffic", w["traffic"] + ".json"))


def test_every_cell_reports_what_the_contract_asks():
    setup = [m for m in M["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    all_cells = {w["name"] for w in M["workloads"]}
    e2e_cells = {m["name"]: set(m.get("workloads", all_cells))
                 for m in M["end_to_end"]}
    for w in M["workloads"]:
        cell = manifest.Cell(M, w["name"])
        assert {m["name"] for m in cell.end_to_end} > {"setup_s"}
        assert cell.per_layer, f"{w['name']} reports no per-layer metric"
    for m in M["per_layer"]:
        assert m["moves"] in e2e_cells
        # every cell that reports the metric reports what it moves
        for cell in m.get("workloads", e2e_cells[m["moves"]]):
            assert cell in e2e_cells[m["moves"]], (m["name"], cell)
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "metrics", m["name"] + ".py")), m["name"]


def test_layers_are_spelled_one_way():
    layers = {m["layer"] for m in M["per_layer"]}
    assert layers <= {"fit loop", "data plane", "compiled step", "kernels",
                      "mesh", "device", "serving host", "decode engine"}


def test_the_withheld_serving_cell_keeps_to_the_same_rules():
    """The serving cell this PR measured and withheld (PERF.md section 7)
    keeps its entries beside the tests, which drive the serving harness
    through them; they name files that exist."""
    w = manifest.load_manifest(WITHHELD)
    for c in w["configs"]:
        assert os.path.exists(os.path.join(manifest.ROOT, c["file"]))
    for m in w["per_layer"]:
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "metrics", m["name"] + ".py")), m["name"]
    cell = manifest.Cell(w, w["workloads"][0]["name"])
    assert {m["name"] for m in cell.end_to_end} > {"setup_s"}


@pytest.mark.parametrize("path", M["paths"])
def test_files_under_paths_are_named_from_name_characters(path):
    for d, _, files in os.walk(os.path.join(manifest.ROOT, path)):
        if "__pycache__" in d:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), manifest.ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
