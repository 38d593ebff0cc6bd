"""`sdar-30b-a3b-chat` and its cell `sdar-30b-a3b-fit-8k-1chip`: the four
new readers on a recorded span table and made counters, the reference's
FLOP and least-time functions against hand counts, the configuration file
against the catalog row it was cut from, the float8 control and the eight
planted faults failing the configuration's limits at a small size, and the
CPU rehearsal of the cell through ``benchmark/run.py`` from its files'
``rehearsal`` keys."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import checks, manifest, xplane

M = manifest.load_manifest()
CELL = "sdar-30b-a3b-fit-8k-1chip"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("bd_attn_share", "bd_tiles_walked_over_live", "denoise_masked_share",
       "denoise_batch_ms")
SHARED = ("fit_segment_median_rate", "fit_window_rate_ratio",
          "fit_host_share", "fit_data_wait_share", "train_mfu_pct",
          "fit_device_idle_share", "lm_step_device_ms", "gqa_attn_roofline",
          "flash_interior_tile_share", "attn_fwd_runs_per_bwd",
          "moe_experts_roofline", "moe_dispatch_share",
          "moe_expert_load_max_over_mean", "moe_rows_walked_over_live",
          "moe_tokens_held_share", "optimizer_share", "step_unscoped_share",
          "xla_matmul_mxu_pct", "step_remat_share")
FAULTS = ("plain_causal", "sees_own_clean_block", "noisy_sees_noisy_past",
          "positions_run_on", "no_weight", "mean_over_masked",
          "loss_on_clean_half", "kv_head_mod")


def _cell():
    return manifest.Cell(M, CELL).rehearsal()


def _trace():
    """Two whole runs of a step program of two steps and the head of a
    third that the profiler's end cut; a step spends 0.10 s in the forward
    kernel, 0.12 and 0.18 in the two backward kernels, 0.02 on the
    attention's projections, 0.05 in the optimizer and 0.13 elsewhere."""
    t = xplane.Trace.__new__(xplane.Trace)
    ops, at = [], 1.0
    per_step = [("flash_fwd.1", 0.10), ("flash_bwd_dq.1", 0.12),
                ("flash_bwd_dkv.1", 0.18), ("fusion.3", 0.02),
                ("fusion.9", 0.05), ("fusion.10", 0.13)]
    for _ in range(4):
        for name, d in per_step:
            ops.append((name, at, at + d, ""))
            at += d
    t.devices = [{"ops": ops + [("while.1", 1.0, at, "")],
                  "modules": [("jit_kstep", 1.0, 2.2), ("jit_kstep", 2.2, 3.4),
                              ("jit_kstep", 3.5, 3.51)]}]
    t.spans, t.window = [], (0.9, 3.51)
    return t


_BODY = "jit(kstep)/while/body/"
SCOPES = {
    "flash_fwd.1": _BODY + "layer:layer0/checkpoint/mha/attn/flash_fwd",
    "flash_bwd_dq.1": _BODY + "transpose(jvp(layer:layer0))/checkpoint/"
                              "mha/attn/flash_bwd_dq",
    "flash_bwd_dkv.1": _BODY + "transpose(jvp(layer:layer0))/checkpoint/"
                               "mha/attn/flash_bwd_dkv",
    "fusion.3": _BODY + "layer:layer0/checkpoint/mha/proj/dot_general",
    "fusion.9": _BODY + "opt/update/add",
}


def _ctx(system=None):
    cell = manifest.Cell(M, CELL)
    system = system or types.SimpleNamespace(
        STEP_PROGRAM="jit_kstep", op_scopes=lambda: SCOPES,
        expert_rows_per_step=lambda: {
            f"layer{i}": 4096.0 for i in range(5)},
        expert_load_max_over_mean=lambda: 2.5,
        expert_rows_walked_over_live=lambda: 2.0,
        tokens_with_held_pair_share=lambda: 0.667,
        denoise_counts=lambda: (8201.0, 16384.0),
        tiles_walked_over_live=lambda: 1.0)
    return {"cell": cell, "trace": _trace(), "system": system,
            "reference": manifest.load_module("references",
                                              cell.config_name),
            "peaks": PEAKS, "batch": 2, "steps_per_call": 2}


def _read(name, ctx):
    return manifest.load_module("metrics", name).read(ctx)


def test_the_new_readers_on_a_recorded_span_table(capsys):
    ctx = _ctx()
    cfg, ref = ctx["cell"].config, ctx["reference"]
    # the three kernels: 0.40 s of a step's 0.60
    assert _read("bd_attn_share", ctx) == pytest.approx(100 * 0.40 / 0.60)
    assert "'mha/proj': 20.0, 'mha/norm': 0.0, 'mha/rope': 0.0, " \
        "'mha/attn': 400.0" in capsys.readouterr().out
    least = ref.gqa_attn_min_seconds(cfg, PEAKS, 2)
    assert _read("gqa_attn_roofline", ctx) == pytest.approx(
        100 * least["least_s"] / 0.40)
    assert _read("bd_tiles_walked_over_live", ctx) == 1.0
    assert _read("denoise_masked_share", ctx) == pytest.approx(
        100 * 8201 / 16384)
    # the readers the cell shares with the other LM cells
    assert _read("optimizer_share", ctx) == pytest.approx(100 * 0.05 / 0.60)
    assert _read("lm_step_device_ms", ctx) == pytest.approx(600.0)
    assert _read("attn_fwd_runs_per_bwd", ctx) == 1.0
    assert _read("moe_tokens_held_share", ctx) == pytest.approx(66.7)


def test_the_span_reader_takes_the_median_denoise_span(monkeypatch):
    from benchmark.lib import spans
    made = [spans.Span("train/epoch", 0.0, 10.0, 1, {}),
            spans.Span("train/chunk", 0.0, 1.0, 1, {"batches": 2}),
            spans.Span("train/chunk", 1.0, 2.0, 1, {"batches": 2}),
            spans.Span("etl/denoise", 0.5, 0.52, 7, {}),     # the fill
            spans.Span("etl/denoise", 1.2, 1.203, 7, {}),
            spans.Span("etl/denoise", 2.2, 2.205, 7, {}),
            spans.Span("etl/denoise", 3.2, 3.204, 7, {}),
            spans.Span("etl/denoise", 9.0, 9.5, 7, {})]      # the profiler
    monkeypatch.setattr(spans, "program_spans", lambda: made)
    ctx = _ctx() | {"fit_s": {"wall": 8.0}}
    assert _read("denoise_batch_ms", ctx) == pytest.approx(4.0)
    monkeypatch.setattr(spans, "program_spans", lambda: made[:3])
    assert _read("denoise_batch_ms", ctx) is None


def test_a_program_without_the_rule_gives_the_readers_nothing():
    """The parent of the PR that added them, or another configuration's
    adapter: no map, no counter, no gauge, no span, and no reader
    raises."""
    bare = types.SimpleNamespace(STEP_PROGRAM="jit_kstep")
    for name in NEW[:3]:
        assert _read(name, _ctx(bare)) is None
    assert _read("bd_attn_share", _ctx() | {"trace": None}) is None
    other = types.SimpleNamespace(
        STEP_PROGRAM="jit_kstep", denoise_counts=lambda: None,
        tiles_walked_over_live=lambda: None,
        op_scopes=lambda: {"fusion.9": "jit(kstep)/opt/update/add"})
    for name in NEW[:3]:    # the map, but none of these scopes or counters
        assert _read(name, _ctx(other)) is None


def test_the_adapter_reads_the_programs_counters_and_gauge():
    from deeplearning4j_tpu import monitor
    system = manifest.load_module("systems", "dl4j_fit_sdar_moe")
    before = system.denoise_counts() or (0.0, 0.0)
    monitor.counter("denoise_masked_total", "").inc(50)
    monitor.counter("denoise_positions_total", "").inc(100)
    masked, seen = system.denoise_counts()
    assert (masked - before[0], seen - before[1]) == (50, 100)
    monitor.gauge("flash_tiles_walked_over_live", "").set(1.0)
    assert system.tiles_walked_over_live() == 1.0


def test_flops_and_least_times_against_hand_counts():
    cell = manifest.Cell(M, CELL)
    ref, cfg = manifest.load_module("references", cell.config_name), \
        cell.config
    t = 8192
    assert ref.seq_length(cfg) == t
    pairs = t * t + 4 * t
    assert pairs == 67141632 == ref.pairs_visible(t, 4)
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    experts = 2048 * 128 + 3 * 2048 * 768 * 8 * 16 / 128
    per_sequence = 5 * (attn + experts) * 2 * t + 2048 * 18992 * t \
        + 5 * pairs * 32 * 2 * 128
    assert ref.train_flops_per_example(cfg) == pytest.approx(
        6 * per_sequence)
    assert ref.flops is ref.train_flops_per_example
    # 60.3 TFLOP a step of two sequences
    assert 2 * ref.train_flops_per_example(cfg) == pytest.approx(60.3e12,
                                                                 rel=0.01)
    shares = ref.flops_shares(cfg)
    assert [round(100 * shares[k]) for k in (
        "attention projections", "attention inside the rule",
        "held experts", "head")] == [31, 55, 8, 6]
    attn_s = ref.gqa_attn_min_seconds(cfg, PEAKS, 2)
    assert attn_s["flops_s"] * 197e12 == pytest.approx(
        5 * 2 * 6 * pairs * 32 * 256)
    # q and the output at 32 heads, k and v at 4, over the 2L stream rows,
    # read or written once a pass, two passes, bf16
    assert attn_s["bytes_s"] * 819e9 == pytest.approx(
        5 * 2 * 2 * (2 * t) * (2 * 32 + 2 * 4) * 128 * 2)
    assert attn_s["least_s"] == attn_s["flops_s"] > attn_s["bytes_s"]
    least = ref.experts_min_seconds(cfg, PEAKS, 4096.0)
    assert least["flops_s"] * 197e12 == pytest.approx(
        9 * 2 * 4096 * 2048 * 768)
    assert ref.moe_experts_min_seconds is ref.experts_min_seconds
    import jax
    import numpy as np
    shapes = jax.tree_util.tree_leaves(
        ref.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s in shapes) == cfg["parameters"] \
        == 550984960
    assert [ref.stage_of(cfg, leaf) for leaf in (
        "['embed']['W']", "['layer3']['ffn']['Wr']", "['norm']['gamma']",
        "['head']['W']", "['layer1']['attn']['q_norm']")] == [
            "embed", "layer3", "head", "head", "layer1"]


def test_the_cell_reports_what_its_issue_named():
    cell = manifest.Cell(M, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"train_examples_per_s",
                                                    "setup_s"}
    assert cell.chips == 1 and cell.traffic["plan"] is None
    assert cell.traffic_name == "fit-denoise-8k-b2"
    ref = manifest.load_module("references", cell.config_name)
    t = cell.traffic
    assert (t["batch"], ref.seq_length(cell.config)) == (2, 8192)
    assert (t["scan_steps"], t["check_steps"], t["segment_steps"],
            t["pool_batches"]) == (2, 2, 4, 20)
    # at least these: a later PR may add a metric to the cell
    assert {m["name"] for m in cell.per_layer} >= set(NEW) | set(SHARED)
    layers = {"bd_attn_share": "compiled step",
              "bd_tiles_walked_over_live": "kernels",
              "denoise_masked_share": "data plane",
              "denoise_batch_ms": "data plane"}
    for m in M["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] \
                and m["moves"] == "train_examples_per_s" \
                and m["layer"] == layers[m["name"]]
        if m["name"] in NEW + SHARED:
            assert CELL in m["workloads"] and os.path.exists(os.path.join(
                manifest.BENCH_DIR, "metrics", m["name"] + ".py"))
    assert CELL in [w["name"] for w in M["workloads"]]
    assert len(cell.entry["why"]) <= 200
    # the other cells' traffic files are as they were
    assert manifest.load_json("traffic", "fit-tokens-8k-b2.json")["batch"] \
        == 2
    assert manifest.load_json("traffic", "fit-tokens-32k-b1.json")["batch"] \
        == 1


def test_the_configuration_file_against_the_catalog_row():
    """Every number of the catalog's ``config`` under the same key; what
    differs is in ``reduced`` with the published count beside it; no width
    is reduced; the free choices are ``assumed``."""
    cfg = manifest.Cell(M, CELL).config
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    entry = next(c for c in M["configs"] if c["name"] == cfg["name"])
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value and type(cfg[key]) is type(value), key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert row["config"] == published
        assert entry["source"].startswith(row["source_url"] + " ")
        assert set(row["not_given"]) == {"block length", "noise schedule"}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 16, 18992)
    assert cfg["experts_held"] == [0, 16] and cfg["router_experts"] == 128
    assert "8 chips share each layer" in cfg["deployment"] \
        and "2,048 rows a step" in cfg["deployment"] \
        and "33.3 %" in cfg["deployment"] \
        and "exactly zero" in cfg["deployment"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
        "config.json one of 8 chips sharing each layer, layers 0-4")
    assert len(entry["source"]) <= 200
    assert entry["file"] == "benchmark/configs/sdar-30b-a3b-chat.json"
    for key in ("block_length", "noise_schedule", "no_shift",
                "mask_token_id", "stream", "loss", "qk_norm", "rope_layout",
                "router_dtype", "optimizer", "init_scales", "weights_seed",
                "noise_seed", "learning_rate", "host_batch"):
        assert key in cfg["assumed"], key
    assert (cfg["block_length"], cfg["noise_t_min"],
            cfg["mask_token_id"]) == (4, 1e-3, 18991)
    assert cfg["learning_rate"] <= 1e-5 and not cfg["tie_word_embeddings"]
    assert cfg["out_proj_std"] == pytest.approx(0.02 / 96 ** 0.5)
    assert cfg["weights_seed"] == 40
    assert set(cfg["limits"]["stage_momentum_gap"]) == {
        "embed", "layer0", "layer1", "layer2", "layer3", "layer4", "head"}
    assert set(cfg["limits_reasons"]) >= set(cfg["limits"])
    # the rehearsal changes sizes only, never the mechanisms
    assert not set(cfg["rehearsal"]) & {
        "num_experts_per_tok", "rope_theta", "norm_topk_prob",
        "decoder_sparse_step", "mlp_only_layers", "num_hidden_layers",
        "block_length", "noise_t_min"}
    small = {**cfg, **cfg["rehearsal"]}
    ref = manifest.load_module("references", cfg["name"])
    assert ref.seq_length(small) % small["attention_block"] == 0
    assert small["mask_token_id"] == ref.mask_token_id(small)
    assert small["num_attention_heads"] // small["num_key_value_heads"] == 4


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
    from benchmark.lib import train_cell
    cell = _cell()
    cfg = cell.config
    ref = manifest.load_module("references", cell.config_name)
    pool = train_cell.make_batches(11, 2, 2, cfg)
    return cfg, ref, pool, _followed(ref, cfg, pool)


def test_fp8_training_fails_the_limits(small):
    cfg, ref, pool, sound = small
    rows = _judged(cfg, ref, _followed(ref, cfg, pool, "fp8"), sound)
    assert any(v > limit for v, limit in rows.values()), rows


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_changes_the_result_and_fails_the_limits(small,
                                                                  fault):
    """The reference with a fault put in the program's place: causal over
    the 2L stream, a noisy row that sees the clean copy of its own block
    (the answer leaks), noisy rows block-causal among themselves,
    positions that run on through the clean half, the 1 / t dropped, the
    loss normalised by the weights' sum, the head on the clean half, query
    head h on key head h % 2: none is named chip-only, every one fails the
    configuration's limits at the rehearsal's widths."""
    cfg, ref, pool, sound = small
    assert ref.FAULTS == FAULTS
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
        env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["attempted"] >= 2 and result["device"]["platform"] == "cpu"
    assert set(result["counts"]["metrics_read"]) >= {
        "denoise_masked_share", "denoise_batch_ms", "moe_tokens_held_share",
        "moe_rows_walked_over_live", "moe_expert_load_max_over_mean",
        "fit_host_share"}
    # two sequences of 128 tokens a batch, about half of them masked
    masked, seen = (float(n) for n in done.stdout.split(
        "[denoise_masked_share] ")[1].split(" positions")[0].split(" of "))
    assert seen % 256 == 0 and seen >= 6 * 256
    assert 0.4 < masked / seen < 0.6
    out = os.path.join(str(tmp_path), CELL, "seed-2147483999-trace-1")
    with open(os.path.join(out, "check.json")) as f:
        check = json.load(f)
    assert len(check["program"]["losses"]) == 2
    assert set(check["program"]["momentum"]) \
        == set(check["reference"]["momentum"])
    assert "[check] stage_momentum_gap.layer4" in done.stdout
