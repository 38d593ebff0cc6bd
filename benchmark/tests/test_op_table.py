"""The three readers of the step's instruction table
(``step_unscoped_share``, ``xla_matmul_mxu_pct``, ``step_remat_share``) and
the tables they print, on a hand-made trace and a hand-made table: two
whole runs of a scan-of-2 step between a cut run and a run that ends with
the stretch."""
import types

import pytest

from benchmark.lib import device, manifest, op_table, xplane

PEAKS = device.PEAKS["TPU v5 lite"]
PEAK = PEAKS["flops_bf16"]


def _row(name, opcode="fusion", layer="blk0", part=None, recomputed=False,
         dot_flops=0, parent="while.9", bytes_in=0, bytes_out=0):
    return {"name": name, "opcode": opcode, "kind": None,
            "computation": "body", "parent": parent,
            "scope": None if part is None and layer is None else "x",
            "layer": layer, "part": part, "recomputed": recomputed,
            "direction": "forward", "dot_flops": dot_flops,
            "bytes_in": bytes_in, "bytes_out": bytes_out}


#: the matmul fusion runs 0.2 s an event: exactly at the peak
AT_PEAK = int(PEAK * 0.2)
TABLE = [
    _row("while.9", "while", layer=None, parent=None),
    # `lax.cond`'s instruction: a container by OPCODE, whatever its name
    _row("cond.3", "conditional", part="moe/dispatch"),
    _row("fusion.1", part="mlp/gated", dot_flops=AT_PEAK),
    _row("fusion.2", part="mlp/gated", recomputed=True, dot_flops=AT_PEAK),
    _row("copy.4", "copy", part=None),                    # in a layer only
    _row("copy-done.5", "copy-done", layer=None),         # XLA's own
    # XLA keeps the outer end of a grouped product's scope only
    _row("ragged-dot-none.6", "custom-call", part="moe/blocks",
         parent="cond.3"),
    _row("fusion.7", layer=None, part="opt/update", bytes_in=819_000_000),
]


def _events(start):
    """One run of the step program from ``start``, 1 s long: the scan's
    body twice (two optimizer steps), each trip the same instructions."""
    out = [("while.9", start, start + 1.0, "")]
    for trip in (0, 1):
        t = start + 0.5 * trip
        out += [("fusion.1", t, t + 0.2, "kOutput"),
                ("cond.3", t + 0.2, t + 0.3, ""),
                ("ragged-dot-none.6", t + 0.2, t + 0.28, ""),
                ("fusion.2", t + 0.3, t + 0.4, "kOutput"),
                ("copy.4", t + 0.4, t + 0.42, ""),
                ("copy-done.5", t + 0.42, t + 0.43, ""),
                ("fusion.7", t + 0.43, t + 0.48, "kLoop")]
    return out


def _ctx(table=TABLE, monkeypatch=None, cut=None):
    """``cut``: a fifth run that the profiler's end cut at that length
    (its last ops have no event), followed by the run that ends with the
    stretch."""
    tr = xplane.Trace.__new__(xplane.Trace)
    starts = (-0.6, 1.0, 2.0, 3.0)      # cut by the start; two whole; the
    ops = [e for s in starts for e in _events(s)]   # last ends with the
    modules = [("jit_kstep", s, s + 1.0) for s in starts]   # stretch
    window = (0.0, 4.0)
    if cut:
        ops += [(n, s, min(e, 4.0 + cut), k) for n, s, e, k in _events(4.0)
                if s < 4.0 + cut]
        modules += [("jit_kstep", 4.0, 4.0 + cut),
                    ("jit_kstep", 4.0 + cut, 5.5)]
        window = (0.0, 5.5)
    tr.devices = [{"ops": ops, "modules": modules}]
    tr.spans, tr.window = [], window
    records = [] if table is None else [
        types.SimpleNamespace(name="mln/scan_step", ops=table),
        # another program's table: the step's is the one that fits
        types.SimpleNamespace(name="other", ops=[_row("fusion.99")])]
    monkeypatch.setattr(op_table, "_records", lambda: records)
    return {"trace": tr, "steps_per_call": 2, "peaks": PEAKS, "batch": 4,
            "cell": types.SimpleNamespace(chips=1, config={}),
            "system": types.SimpleNamespace(STEP_PROGRAM="jit_kstep"),
            "reference": types.SimpleNamespace(
                train_flops_per_example=lambda cfg: 1e12)}


def _read(name, ctx):
    return manifest.load_module("metrics", name).read(ctx)


def test_only_whole_runs_count_and_every_event_counts_once(monkeypatch):
    rows, steps, run_seconds = op_table.events(_ctx(monkeypatch=monkeypatch))
    # two whole runs of two steps; the cut run and the last one are out
    assert steps == 4 and run_seconds == pytest.approx(2.0)
    # a while body's trips count themselves: four events an instruction
    names = [row["name"] for row, _ in rows]
    assert names.count("fusion.1") == 4 and names.count("copy.4") == 4
    # containers are counted by their children alone, `cond.3` too
    assert "while.9" not in names and "cond.3" not in names
    assert sum(took for _, took in rows) == pytest.approx(
        4 * (0.2 + 0.08 + 0.1 + 0.02 + 0.01 + 0.05))


def test_a_run_cut_by_little_is_not_a_whole_run(monkeypatch):
    """`scopes.step_runs` lets a run through that is cut by less than a
    tenth: its last ops have no event, so the join leaves it out."""
    rows, steps, run_seconds = op_table.events(
        _ctx(monkeypatch=monkeypatch, cut=0.95))
    # three whole runs now (the third no longer ends with the stretch);
    # the run of 0.95 s is out, or the steps would be eight
    assert steps == 6 and run_seconds == pytest.approx(3.0)
    names = [row["name"] for row, _ in rows]
    assert names.count("fusion.1") == names.count("fusion.7") == 6


def test_the_three_readers(monkeypatch, capsys):
    ctx = _ctx(monkeypatch=monkeypatch)
    leaf = 0.2 + 0.08 + 0.1 + 0.02 + 0.01 + 0.05
    # no part: the layer's copy and XLA's own; the grouped product counts
    # as the experts' by its name
    assert _read("step_unscoped_share", ctx) == pytest.approx(
        100 * 0.03 / leaf)
    assert _read("step_remat_share", ctx) == pytest.approx(100 * 0.1 / leaf)
    # fusion.1 runs exactly at the peak, fusion.2 at twice the peak: the
    # reader says so by name and returns what it found
    found = _read("xla_matmul_mxu_pct", ctx)
    assert found == pytest.approx(100 * 2 * AT_PEAK / (PEAK * 0.3))
    out = capsys.readouterr().out
    assert "OVER THE PEAK: fusion.2 reads 200.00 %" in out
    assert "fusion.1 reads" not in out
    assert "no layer" in out and "[ops_by_part]" in out
    assert "[ops_by_layer]" in out and "[ops] " in out


def test_an_instruction_at_the_peak_reads_one_hundred(monkeypatch, capsys):
    table = [r for r in TABLE if r["name"] != "fusion.2"]
    ctx = _ctx(table, monkeypatch)
    assert _read("xla_matmul_mxu_pct", ctx) == pytest.approx(100.0)
    assert "OVER THE PEAK" not in capsys.readouterr().out
    # an event of an instruction the table lacks is a leaf under no scope
    assert _read("step_unscoped_share", ctx) == pytest.approx(
        100 * (0.03 + 0.1) / 0.46)


def test_the_tables_add_up_to_the_runs(monkeypatch, capsys):
    ctx = _ctx(monkeypatch=monkeypatch)
    op_table.print_tables(ctx)
    out = capsys.readouterr().out
    part = next(line for line in out.splitlines()
                if line.startswith("[ops_by_part]"))
    # 460 ms of leaf events a step; the rest of a step's 500 ms lies
    # between ops (the containers' own time)
    assert "sum 460.000 ms + 40.000 between ops = 500.000 ms a step" in part
    for key, ms in (("'mlp/gated'", 300.0), ("'moe/experts'", 80.0),
                    ("'opt/update'", 50.0), ("'-'", 30.0)):
        assert f"{key}: {{'ms': {ms}" in part, part
    assert "'remat_ms': 100.0" in part and "'mxu_pct': 133.33" in part
    layer = next(line for line in out.splitlines()
                 if line.startswith("[ops_by_layer]"))
    assert "'blk0': {'ms': 400.0" in layer and "'-': {'ms': 60.0" in layer
    # the instruction with the most time over its bound comes first (the
    # grouped product has no bound here); bytes are marked as the upper
    # bound they are
    first = next(line for line in out.splitlines()
                 if line.startswith("[ops] "))
    assert first.startswith("[ops] ragged-dot-none.6 custom-call "
                            "layer=blk0 part=moe/experts 80.000 ms a step "
                            "in 1 events")
    assert "fusion.7 fusion layer=None part=opt/update" in out
    assert "bytes<=" in out
    assert f"dot_flops a step as compiled" in out
    assert f"{2 * AT_PEAK:.4e}, of which recomputed {AT_PEAK:.4e}" in out
    assert "4.0000e+12" in out          # the reference's count x batch


def test_a_record_without_the_table_reads_nothing(monkeypatch, capsys):
    ctx = _ctx(None, monkeypatch)
    for name in ("step_unscoped_share", "xla_matmul_mxu_pct",
                 "step_remat_share"):
        assert _read(name, ctx) is None
    assert "[ops" not in capsys.readouterr().out
    # nor a table none of whose instructions ran
    ctx = _ctx([_row("fusion.99")], monkeypatch)
    assert _read("step_unscoped_share", ctx) is None
    # nor a run without a trace
    ctx = _ctx(monkeypatch=monkeypatch)
    ctx["trace"] = None
    assert _read("step_remat_share", ctx) is None


def test_the_manifest_lists_them_for_the_three_lm_cells():
    m = manifest.load_manifest()
    cells = ["kimi-linear-fit-8k-1chip", "glm-4.7-flash-fit-8k-1chip",
             "lfm2-24b-a2b-fit-8k-1chip"]
    for name, better, layer in (
            ("step_unscoped_share", "lower", "compiled step"),
            ("xla_matmul_mxu_pct", "higher", "kernels"),
            ("step_remat_share", "lower", "compiled step")):
        entry = next(e for e in m["per_layer"] if e["name"] == name)
        assert entry == {
            "name": name, "unit": "%", "better": better,
            "source": "device_trace", "layer": layer,
            "moves": "train_examples_per_s",
            "workloads": entry["workloads"]}
        assert entry["workloads"][:3] == cells
        assert "resnet50-fit-1chip" not in entry["workloads"]
