"""`xing4.0-29b-a4b` and its cell `xing4.0-fit-8k-1chip`: the three new
readers on a recorded span table, the reference's FLOP count and the least
times and bytes against hand counts, the configuration file against the
catalog row it was cut from, the float8 control and the planted faults
failing the configuration's limits at a small size, and the CPU rehearsal
of the cell through ``benchmark/run.py`` from its files' ``rehearsal``
keys."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import checks, manifest, xplane

M = manifest.load_manifest()
CELL = "xing4.0-fit-8k-1chip"
CONFIG = "xing4.0-29b-a4b"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("mhc_share", "mhc_mix_roofline", "mhc_res_gap")
APPENDED = (
    "fit_segment_median_rate", "fit_window_rate_ratio", "fit_host_share",
    "fit_data_wait_share", "train_mfu_pct", "fit_device_idle_share",
    "mla_attn_roofline", "moe_experts_roofline", "moe_dispatch_share",
    "optimizer_share", "moe_expert_load_max_over_mean", "lm_step_device_ms",
    "mla_proj_share", "moe_rows_walked_over_live", "attn_fwd_runs_per_bwd",
    "flash_interior_tile_share", "step_unscoped_share", "xla_matmul_mxu_pct",
    "step_remat_share")
REDUCED = ["num_hidden_layers", "n_routed_experts", "num_attention_heads",
           "num_key_value_heads", "vocab_size", "num_nextn_predict_layers"]


def _trace():
    """Two whole runs of a step program of two steps and the head of a
    third that the profiler's end cut; a step spends 0.03 s on the reading
    side of the mixing, 0.02 in the Sinkhorn steps, 0.04 on the writing
    side, 0.01 at the ends, 0.05 in the optimizer and 0.25 elsewhere."""
    t = xplane.Trace.__new__(xplane.Trace)
    ops, ms = [], 1000       # whole milliseconds: a run's edge is an op's
    per_step = [("fusion.1", 20), ("convolution.2", 10), ("fusion.3", 20),
                ("fusion.4", 40), ("fusion.5", 10), ("fusion.9", 50),
                ("fusion.10", 250)]
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
    "fusion.1": "jit(kstep)/while/body/checkpoint/mhc/pre/mul",
    "convolution.2": "jit(kstep)/while/body/transpose(jvp(mhc/pre))/"
                     "dot_general",
    "fusion.3": "jit(kstep)/while/body/checkpoint/rematted_computation/"
                "mhc/sinkhorn/div",
    "fusion.4": "jit(kstep)/while/body/transpose(jvp(mhc/post))/mul",
    "fusion.5": "jit(kstep)/while/body/layer:streams/mhc/io/tile",
    "fusion.9": "jit(kstep)/while/body/opt/update/add",
}


def _ctx(system=None):
    cell = manifest.Cell(M, CELL)
    system = system or types.SimpleNamespace(
        STEP_PROGRAM="jit_kstep", op_scopes=lambda: SCOPES,
        mhc_res_gap=lambda: 0.0042)
    return {"cell": cell, "trace": _trace(), "system": system,
            "reference": manifest.load_module("references",
                                              cell.config_name),
            "peaks": PEAKS, "batch": 1, "steps_per_call": 2}


def _read(name, ctx):
    return manifest.load_module("metrics", name).read(ctx)


def test_the_new_readers_on_a_recorded_span_table(capsys):
    ctx = _ctx()
    cfg = ctx["cell"].config
    # the four scopes: 0.10 of a step's 0.40
    assert _read("mhc_share", ctx) == pytest.approx(25.0)
    assert "'mhc/pre': 30.0, 'mhc/sinkhorn': 20.0, 'mhc/post': 40.0, " \
        "'mhc/io': 10.0" in capsys.readouterr().out
    least = manifest.load_module("metrics", "mhc_mix_roofline").least_bytes(
        cfg, 1)
    # the two sides' ops, 0.07 s a step; the Sinkhorn steps are not theirs
    assert _read("mhc_mix_roofline", ctx) == pytest.approx(
        100 * least["bytes"] / 819e9 / 0.07)
    assert _read("mhc_res_gap", ctx) == 0.0042
    assert _read("optimizer_share", ctx) == pytest.approx(12.5)
    assert _read("lm_step_device_ms", ctx) == pytest.approx(400.0)


def test_a_program_without_the_scopes_gives_the_readers_nothing():
    """The parent of the PR that added them, or another configuration's
    adapter: no map, no gauge, and no reader raises."""
    bare = types.SimpleNamespace(STEP_PROGRAM="jit_kstep")
    other = types.SimpleNamespace(
        STEP_PROGRAM="jit_kstep",
        op_scopes=lambda: {"fusion.9": "jit(kstep)/opt/update/add"})
    for name in NEW:
        assert _read(name, _ctx(bare)) is None
        assert _read(name, _ctx(other)) is None
    for name in NEW[:2]:
        assert _read(name, _ctx() | {"trace": None}) is None
    assert _read("mhc_mix_roofline", _ctx() | {"peaks": None}) is None


def test_flops_and_least_times_against_hand_counts():
    cell = manifest.Cell(M, CELL)
    ref, cfg = manifest.load_module("references", cell.config_name), \
        cell.config
    t = 8192
    attn = 3584 * 768 + 768 * 4 * 192 + 3584 * 576 + 512 * 4 * 256 \
        + 4 * 128 * 3584
    mappings = 2 * (14336 * 24 + 24 * 3584)
    experts = 3584 * 64 + 3 * 3584 * 1024 + 3 * 3584 * 1024 * 4 * 8 / 64
    per_token = 5 * (attn + mappings) + 3 * 3584 * 9216 + 4 * experts \
        + 3584 * 16384
    mixing = 5 * (t * (t + 1) / 2) * 4 * (192 + 128)
    assert ref.train_flops_per_example(cfg) == pytest.approx(
        6 * (per_token * t + mixing))
    # 14.5 TFLOP a step of one sequence, 19.3 with the forward made again
    assert ref.train_flops_per_example(cfg) == pytest.approx(14.46e12,
                                                             rel=0.01)
    mla = ref.mla_attn_min_seconds(cfg, PEAKS, 1)
    assert mla["flops_s"] * 197e12 == pytest.approx(6 * mixing)
    assert mla["bytes_s"] * 819e9 == pytest.approx(
        5 * 2 * t * 4 * (2 * 192 + 2 * 128) * 2)
    least = ref.experts_min_seconds(cfg, PEAKS, 512.0)
    assert least["flops_s"] * 197e12 == pytest.approx(
        3 * 3 * 2 * 512 * 3584 * 1024)
    reader = manifest.load_module("metrics", "mhc_mix_roofline")
    got = reader.least_bytes(cfg, 1)
    assert got["streams"] == 5 * t * (2 * 14 + 19 + 2 * 27) * 3584 * 2
    assert got["phi"] == 10 * 4 * 14336 * 24 * 2
    assert got["bytes"] / 819e9 == pytest.approx(0.0362, rel=0.01)
    import jax
    import numpy as np
    shapes = jax.tree_util.tree_leaves(
        ref.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s in shapes) == cfg["parameters"] \
        == ref.parameters(cfg) == 656_126_990
    assert [ref.stage_of(cfg, leaf) for leaf in (
        "['embed']['W']", "['layer3']['ffn']['Wr']", "['norm']['gamma']",
        "['head']['W']", "['layer4']['hc_ffn']['phi']",
        "['layer0']['ln1']['gamma']")] == [
            "embed", "layer3", "head", "head", "layer4", "layer0"]
    assert [ref._dense_layer(cfg, i) for i in range(5)] \
        == [True, False, False, False, False]


def test_the_cell_reports_what_its_issue_named():
    cell = manifest.Cell(M, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"train_examples_per_s",
                                                    "setup_s"}
    assert cell.chips == 1 and cell.traffic["plan"] is None
    # b1 or b2 by the compile's rule; whichever the manifest names
    assert cell.traffic_name in ("fit-tokens-8k-b1", "fit-tokens-8k-b2")
    ref = manifest.load_module("references", cell.config_name)
    t = cell.traffic
    assert (t["batch"], ref.seq_length(cell.config)) \
        == (int(cell.traffic_name[-1]), 8192)
    assert (t["scan_steps"], t["check_steps"]) == (2, 2)
    # at least these: a later PR may add a metric to the cell
    assert {m["name"] for m in cell.per_layer} >= set(NEW) | set(APPENDED)
    assert not {m["name"] for m in cell.per_layer} & {
        "train_step_device_ms", "conv_roofline", "kda_share",
        "kda_scan_roofline", "mtp_share", "shortconv_share", "dsa_share",
        "bd_attn_share", "ssd_share", "gqa_attn_roofline"}
    layers = {"mhc_share": ("compiled step", "lower", "%", "device_trace"),
              "mhc_mix_roofline": ("kernels", "higher", "%", "device_trace"),
              "mhc_res_gap": ("compiled step", "lower", "x",
                              "program_counter")}
    for m in M["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] \
                and m["moves"] == "train_examples_per_s" \
                and (m["layer"], m["better"], m["unit"], m["source"]) \
                == layers[m["name"]]
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
                   or "hidden_size" in k or "per_tok" in k for k in REDUCED)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        for key, value in row["config"].items():
            if key in REDUCED:
                assert cfg["published"][key] == value and cfg[key] < value
            else:
                assert cfg[key] == value and type(cfg[key]) is type(value), \
                    key
        assert entry["source"].startswith(row["source_url"] + " ")
    assert [cfg[k] for k in REDUCED] == [5, 8, 4, 4, 16384, 0]
    assert [cfg["published"][k] for k in REDUCED] \
        == [40, 64, 32, 32, 131072, 1]
    assert (cfg["hc_mult"], cfg["hc_sinkhorn_iters"], cfg["hc_eps"],
            cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]) \
        == (4, 20, 1e-6, -30, 30)
    assert cfg["first_layer"] == 1 and cfg["first_k_dense_replace"] == 2
    assert cfg["experts_held"] == [0, 8] and cfg["router_experts"] == 64
    for said in ("8 chips share each layer", "512 rows a step",
                 "No code stands in", "whole on every chip"):
        assert said in cfg["deployment"], said
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    for key in ("streams_in_and_out", "mapping_input", "sinkhorn",
                "mapping_dtypes", "mapping_leaves", "yarn", "head_slices",
                "e_score_correction_bias", "router_dtype", "optimizer",
                "init_scales", "mapping_init", "weights_seed",
                "learning_rate", "mtp", "host_batch", "parameters"):
        assert key in cfg["assumed"], key
    assert cfg["learning_rate"] <= 1e-5
    assert set(cfg["limits"]["stage_momentum_gap"]) == {"embed", "head"} | {
        f"layer{i}" for i in range(5)}
    assert set(cfg["limits_reasons"]) >= set(cfg["limits"])
    # the rehearsal changes sizes only, never the mechanisms
    assert not set(cfg["rehearsal"]) & {
        "hc_mult", "hc_sinkhorn_iters", "hc_eps", "first_layer",
        "num_hidden_layers", "rope_scaling", "routed_scaling_factor",
        "hc_alpha", "hc_res_diagonal"}


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
    """The rehearsal's sizes, a batch of two as the other LM cells' small
    checks take, with matrices ten times as large: at 8 rotated dims and
    the cell's 0.02 the attention's scores are flat (q . k of 0.05), and no
    temperature or frequency shows in flat scores; at the cell's 64 dims
    and widths they do (the chip's readings: `limits_reasons`)."""
    from benchmark.lib import train_cell
    cfg = {**manifest.Cell(M, CELL).rehearsal().config, "matrix_std": 0.2}
    ref = manifest.load_module("references", CONFIG)
    pool = train_cell.make_batches(11, 2, 2, cfg)
    return cfg, ref, pool, _followed(ref, cfg, pool)


def test_fp8_training_fails_the_limits(small):
    cfg, ref, pool, sound = small
    rows = _judged(cfg, ref, _followed(ref, cfg, pool, "fp8"), sound)
    assert any(v > limit for v, limit in rows.values()), rows


@pytest.mark.parametrize("fault", [
    "half_batch", "static_mappings", "no_sinkhorn", "one_iteration",
    "rows_first", "post_without_2", "out_first_stream",
    "no_yarn_temperature", "plain_frequencies"])
def test_a_planted_fault_changes_the_result_and_fails_the_limits(small,
                                                                  fault):
    """The reference with a fault put in the program's place, judged as a
    run is, at rehearsal size under the configuration's limits (a result
    that is not finite fails them too: the exponential without its
    Sinkhorn steps overflows)."""
    cfg, ref, pool, sound = small
    assert fault in ref.FAULTS and len(ref.FAULTS) == 9
    bad = _followed(ref, cfg, pool, fault=fault)
    assert bad["momentum"] != sound["momentum"]
    rows = _judged(cfg, ref, bad, sound)
    assert any(not v <= limit for v, limit in rows.values()), rows


def test_the_cell_rehearses_on_the_cpu_through_run_py(tmp_path):
    """The one command, traced, at the files' ``rehearsal`` sizes: counts
    and the metrics that a CPU run can read (the program's counters and
    spans), no device metric."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "2",
         "--trace", "1", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["attempted"] >= 2 and result["device"]["platform"] == "cpu"
    assert set(result["counts"]["metrics_read"]) >= {
        "moe_rows_walked_over_live", "moe_expert_load_max_over_mean",
        "fit_segment_median_rate", "fit_host_share", "mhc_res_gap"}
    out = os.path.join(str(tmp_path), CELL, "seed-2147483999-trace-1")
    with open(os.path.join(out, "check.json")) as f:
        check = json.load(f)
    assert len(check["program"]["losses"]) == 2
    assert set(check["program"]["momentum"]) \
        == set(check["reference"]["momentum"])
    assert "[check] stage_momentum_gap.layer4" in done.stdout
