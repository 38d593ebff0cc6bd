"""`nemotron-3-super-120b-a12b` and its cell
`nemotron-3-super-fit-8k-1chip`: the three new readers on a recorded span
table, the reference's FLOP and least-time functions against hand counts,
the configuration file against the catalog row it was cut from, the float8
control and the planted faults failing the configuration's limits at a
small size, and the CPU rehearsal of the cell through ``benchmark/run.py``
from its files' ``rehearsal`` keys."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import checks, manifest, xplane

M = manifest.load_manifest()
CELL = "nemotron-3-super-fit-8k-1chip"
CONFIG = "nemotron-3-super-120b-a12b"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("ssd_scan_roofline", "ssd_share", "moe_latent_share")
APPENDED = (
    "fit_segment_median_rate", "fit_window_rate_ratio", "fit_host_share",
    "fit_data_wait_share", "train_mfu_pct", "fit_device_idle_share",
    "lm_step_device_ms", "optimizer_share", "moe_experts_roofline",
    "moe_dispatch_share", "moe_expert_load_max_over_mean",
    "moe_rows_walked_over_live", "gqa_attn_roofline",
    "attn_fwd_runs_per_bwd", "flash_interior_tile_share",
    "step_unscoped_share", "step_remat_share", "xla_matmul_mxu_pct")
REDUCED = ["num_hidden_layers", "n_routed_experts", "mamba_num_heads",
           "n_groups", "num_attention_heads", "num_key_value_heads",
           "vocab_size", "num_nextn_predict_layers"]


def _trace():
    """Two whole runs of a step program of two steps and the head of a
    third that the profiler's end cut; a step spends 0.04 s in the scan's
    kernels and 0.01 in the ops around them, 0.06 in Mamba-2's projections,
    0.02 in its taps, 0.03 in its gated norm and output projection, 0.04 in
    the latent projections, 0.05 in the optimizer and 0.15 elsewhere."""
    t = xplane.Trace.__new__(xplane.Trace)
    ops, ms = [], 1000       # whole milliseconds: a run's edge is an op's
    per_step = [("ssd_chunk_fwd.3", 15), ("ssd_chunk_bwd.3", 25),
                ("fusion.1", 10), ("convolution.2", 60), ("fusion.3", 20),
                ("fusion.4", 30), ("convolution.5", 40), ("fusion.9", 50),
                ("fusion.10", 150)]
    for _ in range(4):
        for name, d in per_step:
            ops.append((name, ms / 1000, (ms + d) / 1000, ""))
            ms += d
    t.devices = [{"ops": ops + [("while.1", 1.0, ms / 1000, "")],
                  "modules": [("jit_kstep", 1.0, 1.8), ("jit_kstep", 1.8, 2.6),
                              ("jit_kstep", 2.7, 2.71)]}]
    t.spans, t.window = [], (0.9, 2.71)
    return t


SCOPES = {
    "ssd_chunk_fwd.3": "jit(kstep)/while/body/checkpoint/ssd/scan/"
                       "ssd_chunk_fwd",
    "ssd_chunk_bwd.3": "jit(kstep)/while/body/transpose(jvp(ssd/scan))/"
                       "ssd_chunk_bwd",
    "fusion.1": "jit(kstep)/while/body/checkpoint/ssd/scan/cumsum",
    "convolution.2": "jit(kstep)/while/body/jvp(ssd/proj)/dot_general",
    "fusion.3": "jit(kstep)/while/body/checkpoint/rematted_computation/"
                "ssd/conv/mul",
    "fusion.4": "jit(kstep)/while/body/transpose(jvp(ssd/out))/mul",
    "convolution.5": "jit(kstep)/while/body/jvp(moe/latent)/dot_general",
    "fusion.9": "jit(kstep)/while/body/opt/update/add",
}


def _ctx(system=None):
    cell = manifest.Cell(M, CELL)
    system = system or types.SimpleNamespace(
        STEP_PROGRAM="jit_kstep", op_scopes=lambda: SCOPES)
    return {"cell": cell, "trace": _trace(), "system": system,
            "reference": manifest.load_module("references",
                                              cell.config_name),
            "peaks": PEAKS, "batch": 2, "steps_per_call": 2}


def _read(name, ctx):
    return manifest.load_module("metrics", name).read(ctx)


def test_the_new_readers_on_a_recorded_span_table(capsys):
    ctx = _ctx()
    cfg, ref = ctx["cell"].config, ctx["reference"]
    # the kernels and the ops around them under ssd/scan: 0.05 s of a
    # step's 0.40
    least = ref.ssd_scan_min_seconds(cfg, PEAKS, 2)
    assert _read("ssd_scan_roofline", ctx) == pytest.approx(
        100 * least["least_s"] / 0.05)
    # the four scopes of the mixer: 0.16 of 0.40
    assert _read("ssd_share", ctx) == pytest.approx(40.0)
    assert "'ssd/proj': 60.0, 'ssd/conv': 20.0, 'ssd/scan': 50.0, " \
        "'ssd/out': 30.0" in capsys.readouterr().out
    assert _read("moe_latent_share", ctx) == pytest.approx(10.0)
    assert _read("optimizer_share", ctx) == pytest.approx(12.5)
    assert _read("lm_step_device_ms", ctx) == pytest.approx(400.0)


def test_a_program_without_the_scopes_gives_the_readers_nothing():
    """The parent of the PR that added them, or another configuration's
    adapter and reference: no map, no least-time function, and no reader
    raises."""
    bare = types.SimpleNamespace(STEP_PROGRAM="jit_kstep")
    other = types.SimpleNamespace(
        STEP_PROGRAM="jit_kstep",
        op_scopes=lambda: {"fusion.9": "jit(kstep)/opt/update/add"})
    glm = manifest.load_module("references", "glm-4.7-flash")
    for name in NEW:
        assert _read(name, _ctx(bare)) is None
        assert _read(name, _ctx() | {"trace": None}) is None
        assert _read(name, _ctx(other)) is None
    assert _read("ssd_scan_roofline", _ctx() | {"reference": glm}) is None
    assert _read("ssd_scan_roofline", _ctx() | {"peaks": None}) is None


def test_flops_and_least_times_against_hand_counts():
    cell = manifest.Cell(M, CELL)
    ref, cfg = manifest.load_module("references", cell.config_name), \
        cell.config
    t = 8192
    mamba = 4096 * (2 * 1024 + 2 * 128 + 16) + 1024 * 4096
    attn = 4096 * (2 * 4 + 2 * 1) * 128
    experts = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 \
        + 2 * 1024 * 2688 * 22 * 8 / 512
    scan = 2 * 16 * 64 * 128
    per_token = 5 * (mamba + scan) + attn + 5 * experts + 4096 * 16384
    mixing = (t * (t + 1) / 2) * 4 * (128 + 128)
    assert ref.train_flops_per_example(cfg) == pytest.approx(
        6 * (per_token * t + mixing))
    # 860 MFLOP a token forward; 42.5 TFLOP a step of two sequences, 56 with
    # the forward made again
    assert 2 * (per_token + mixing / t) == pytest.approx(860e6, rel=0.01)
    assert 2 * ref.train_flops_per_example(cfg) * 4 / 3 == pytest.approx(
        56.6e12, rel=0.01)
    shares = ref.flops_shares(cfg)
    assert [round(100 * shares[k]) for k in (
        "shared expert", "head", "Mamba-2 projections", "latent projections",
        "router", "attention projections", "held experts")] \
        == [51, 16, 16, 10, 2, 1, 2]
    assert round(1000 * shares["state-space scan"]) == 3
    assert round(1000 * shares["attention scores"]) == 10
    gqa = ref.gqa_attn_min_seconds(cfg, PEAKS, 2)
    assert gqa["flops_s"] * 197e12 == pytest.approx(
        2 * 6 * (t * (t + 1) / 2) * 4 * 256)
    assert gqa["bytes_s"] * 819e9 == pytest.approx(
        2 * 2 * t * (2 * 4 + 2 * 1) * 128 * 2)
    ssd = ref.ssd_scan_min_seconds(cfg, PEAKS, 2)
    # two multiply-adds and the decay an element of 16 states of 64 x 128
    assert ssd["flops_s"] * 197e12 == pytest.approx(
        3 * 5 * 16 * 64 * 128 * t * 2 * 5)
    assert ssd["bytes_s"] * 819e9 == pytest.approx(
        3 * ((1024 + 256) * 2 + 64 + 4096) * t * 2 * 5)
    assert ssd["least_s"] == ssd["bytes_s"] > ssd["flops_s"]
    least = ref.experts_min_seconds(cfg, PEAKS, 704.0)
    assert least["flops_s"] * 197e12 == pytest.approx(
        6 * 2 * 704 * 1024 * 2688)
    import jax
    import numpy as np
    shapes = jax.tree_util.tree_leaves(
        ref.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s in shapes) == cfg["parameters"] \
        == 700862960
    assert [ref.stage_of(cfg, leaf) for leaf in (
        "['embed']['W']", "['layer3']['mixer']['Wr']", "['norm']['gamma']",
        "['head']['W']", "['layer10']['mixer']['A_log']",
        "['layer0']['ln']['gamma']")] == [
            "embed", "layer3", "head", "head", "layer10", "layer0"]
    assert "".join(ref.layer_kinds(cfg)) == "*EMEMEMEMEM"


def test_the_cell_reports_what_its_issue_named():
    cell = manifest.Cell(M, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"train_examples_per_s",
                                                    "setup_s"}
    assert cell.chips == 1 and cell.traffic["plan"] is None
    assert cell.traffic_name == "fit-tokens-8k-b2"
    ref = manifest.load_module("references", cell.config_name)
    t = cell.traffic
    assert (t["batch"], ref.seq_length(cell.config)) == (2, 8192)
    assert (t["scan_steps"], t["check_steps"]) == (2, 2)
    # at least these: a later PR may add a metric to the cell
    assert {m["name"] for m in cell.per_layer} >= set(NEW) | set(APPENDED)
    assert not {m["name"] for m in cell.per_layer} & {
        "train_step_device_ms", "conv_roofline", "kda_share",
        "kda_scan_roofline", "mla_attn_roofline", "mtp_share",
        "mla_proj_share", "shortconv_share", "dsa_share", "bd_attn_share"}
    layers = {"ssd_scan_roofline": ("kernels", "higher"),
              "ssd_share": ("compiled step", "lower"),
              "moe_latent_share": ("compiled step", "lower")}
    for m in M["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] \
                and m["moves"] == "train_examples_per_s" \
                and (m["layer"], m["better"]) == layers[m["name"]] \
                and m["unit"] == "%" and m["source"] == "device_trace"
        if m["name"] in NEW + APPENDED:
            assert CELL in m["workloads"] and os.path.exists(os.path.join(
                manifest.BENCH_DIR, "metrics", m["name"] + ".py"))
    assert len(cell.entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in M["workloads"]) == 0
    assert len(M["workloads"]) <= 24


def test_the_configuration_file_against_the_catalog_row():
    """Every number of the catalog's ``config`` under the same key; what
    differs is in ``reduced`` with the published count beside it; no width
    is reduced; the free choices are ``assumed``."""
    cfg = manifest.Cell(M, CELL).config
    entry = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == entry["reduced"] == REDUCED
    assert not any(k.endswith(("_dim", "_rank")) or "intermediate" in k
                   or "latent" in k or "state" in k for k in REDUCED)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"]
                       == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        for key, value in row["config"].items():
            if key in REDUCED:
                assert cfg["published"][key] == value and cfg[key] < value
            else:
                assert cfg[key] == value and type(cfg[key]) is type(value), \
                    key
        assert entry["source"].startswith(row["source_url"] + " ")
    assert [cfg[k] for k in REDUCED] == [11, 8, 16, 1, 4, 1, 16384, 0]
    assert [cfg["published"][k] for k in REDUCED] \
        == [88, 512, 128, 8, 32, 2, 131072, 1]
    assert len(cfg["hybrid_override_pattern"]) == 88 \
        and cfg["first_layer"] == 25
    assert cfg["experts_held"] == [0, 8] and cfg["router_experts"] == 512
    for said in ("64 chips share each layer", "704 rows a step",
                 "shared expert's part alone", "No code stands in"):
        assert said in cfg["deployment"], said
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    for key in ("position_free", "latent_placement", "mamba_layout",
                "mamba_dtypes", "head_slices", "expert_bias",
                "router_dtype", "optimizer", "init_scales", "weights_seed",
                "learning_rate", "mtp", "host_batch"):
        assert key in cfg["assumed"], key
    assert cfg["learning_rate"] <= 1e-5
    assert set(cfg["limits"]["stage_momentum_gap"]) == {"embed", "head"} | {
        f"layer{i}" for i in range(11)}
    assert set(cfg["limits_reasons"]) >= set(cfg["limits"])
    # the rehearsal changes sizes only, never the mechanisms
    assert not set(cfg["rehearsal"]) & {
        "hybrid_override_pattern", "first_layer", "num_hidden_layers",
        "conv_kernel", "n_groups", "routed_scaling_factor"}


def _followed(ref, cfg, pool, precision="highest", fault=None):
    import jax
    seeded = jax.device_get(ref.make_params(cfg))
    losses, trace, params = ref.train_steps(
        cfg, ref.make_params(cfg), pool, precision=precision, fault=fault)
    return {"losses": losses, "momentum": checks.leaf_norms(trace),
            "update": checks.leaf_norms(jax.tree_util.tree_map(
                lambda a, b: a - b, params, seeded))}


def _judged(cfg, ref, got, sound):
    return {name: (value, limit) for name, value, limit in
            checks.training_rows(got, sound,
                                 lambda leaf: ref.stage_of(cfg, leaf),
                                 cfg["limits"])}


@pytest.fixture(scope="module")
def small():
    """The rehearsal's sizes with TWO key/value heads held, so that a
    query head on the wrong one can show (the cut holds one)."""
    from benchmark.lib import train_cell
    cfg = {**manifest.Cell(M, CELL).rehearsal().config,
           "num_key_value_heads": 2}
    ref = manifest.load_module("references", CONFIG)
    pool = train_cell.make_batches(11, 2, 2, cfg)
    return cfg, ref, pool, _followed(ref, cfg, pool)


def test_fp8_training_fails_the_limits(small):
    cfg, ref, pool, sound = small
    rows = _judged(cfg, ref, _followed(ref, cfg, pool, "fp8"), sound)
    assert any(v > limit for v, limit in rows.values()), rows


@pytest.mark.parametrize("fault", [
    "half_batch", "no_decay", "no_dt_input", "norm_over_head",
    "gate_after_norm", "no_latent_down", "relu_not_squared", "no_scaling",
    "kv_head_mod"])
def test_a_planted_fault_changes_the_result_and_fails_the_limits(small,
                                                                  fault):
    """The reference with a fault put in the program's place, judged as a
    run is, at rehearsal size under the configuration's limits."""
    cfg, ref, pool, sound = small
    assert fault in ref.FAULTS and len(ref.FAULTS) == 9
    bad = _followed(ref, cfg, pool, fault=fault)
    assert bad["momentum"] != sound["momentum"]
    rows = _judged(cfg, ref, bad, sound)
    assert any(v > limit for v, limit in rows.values()), rows


def test_the_cell_rehearses_on_the_cpu_through_run_py(tmp_path):
    """The one command, traced, at the files' ``rehearsal`` sizes: counts
    and the metrics that a CPU run can read (the program's counters and
    spans), no device metric."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "2",
         "--trace", "1", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["attempted"] >= 2 and result["device"]["platform"] == "cpu"
    assert set(result["counts"]["metrics_read"]) >= {
        "moe_rows_walked_over_live", "moe_expert_load_max_over_mean",
        "fit_segment_median_rate", "fit_host_share"}
    out = os.path.join(str(tmp_path), CELL, "seed-2147483999-trace-1")
    with open(os.path.join(out, "check.json")) as f:
        check = json.load(f)
    assert len(check["program"]["losses"]) == 2
    assert set(check["program"]["momentum"]) \
        == set(check["reference"]["momentum"])
    assert "[check] stage_momentum_gap.layer10" in done.stdout
