"""`keye-vl-2.0-30b-a3b` and its cell `keye-vl-2.0-fit-32k-1chip`: the five
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
CELL = "keye-vl-2.0-fit-32k-1chip"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("dsa_index_roofline", "dsa_attn_roofline", "dsa_select_share",
       "dsa_share", "dsa_pairs_selected_share")
SHARED = ("lm_step_device_ms", "moe_experts_roofline", "moe_dispatch_share",
          "optimizer_share", "moe_expert_load_max_over_mean",
          "moe_rows_walked_over_live", "moe_tokens_held_share",
          "step_unscoped_share", "xla_matmul_mxu_pct", "step_remat_share")
FAULTS = ("no_relu", "no_head_weights", "half_topk", "sees_next",
          "no_indexer_loss", "kl_head0", "kv_head_mod", "no_renorm")


def _cell():
    return manifest.Cell(M, CELL).rehearsal()


def _trace():
    """Two whole runs of a step program of two steps and the head of a
    third that the profiler's end cut; a step spends 0.02 s making the
    indexer's scores, 0.03 selecting, 0.20 in the attention kernels, 0.04
    on the indexer's loss, 0.01 in the indexer's projections, 0.05 in the
    optimizer and 0.15 elsewhere."""
    t = xplane.Trace.__new__(xplane.Trace)
    ops, at = [], 1.0
    per_step = [("dsa_index.1", 0.02), ("dsa_select.1", 0.03),
                ("dsa_attn_fwd.1", 0.05), ("dsa_attn_bwd_dq.1", 0.07),
                ("dsa_attn_bwd_dkv.1", 0.08), ("dsa_kl_fwd.1", 0.04),
                ("fusion.3", 0.01), ("fusion.9", 0.05), ("fusion.10", 0.15)]
    for _ in range(4):
        for name, d in per_step:
            ops.append((name, at, at + d, ""))
            at += d
    t.devices = [{"ops": ops + [("while.1", 1.0, at, "")],
                  "modules": [("jit_kstep", 1.0, 2.0), ("jit_kstep", 2.0, 3.0),
                              ("jit_kstep", 3.1, 3.11)]}]
    t.spans, t.window = [], (0.9, 3.11)
    return t


_BODY = "jit(kstep)/while/body/"
SCOPES = {
    "dsa_index.1": _BODY + "layer:layer0/checkpoint/while/body/dsa/index/"
                           "dsa_index",
    "dsa_select.1": _BODY + "layer:layer0/checkpoint/while/body/dsa/select/"
                            "dsa_select",
    "dsa_attn_fwd.1": _BODY + "layer:layer0/checkpoint/dsa/attn/"
                              "dsa_attn_fwd",
    "dsa_attn_bwd_dq.1": _BODY + "transpose(jvp(layer:layer0))/checkpoint/"
                                 "dsa/attn/dsa_attn_bwd_dq",
    "dsa_attn_bwd_dkv.1": _BODY + "transpose(jvp(layer:layer0))/checkpoint/"
                                  "dsa/attn/dsa_attn_bwd_dkv",
    "dsa_kl_fwd.1": _BODY + "layer:layer0/checkpoint/dsa/kl/dsa_kl_fwd",
    "fusion.3": _BODY + "layer:layer0/checkpoint/dsa/index/proj/dot_general",
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
        sparse_pairs=lambda: (5 * 65012736.0, 5 * 536887296.0))
    return {"cell": cell, "trace": _trace(), "system": system,
            "reference": manifest.load_module("references",
                                              cell.config_name),
            "peaks": PEAKS, "batch": 1, "steps_per_call": 2}


def _read(name, ctx):
    return manifest.load_module("metrics", name).read(ctx)


def test_the_new_readers_on_a_recorded_span_table(capsys):
    ctx = _ctx()
    cfg, ref = ctx["cell"].config, ctx["reference"]
    # the scores' kernel alone, not the indexer's projections: 0.02 s
    least = ref.dsa_index_min_seconds(cfg, PEAKS, 1)
    assert _read("dsa_index_roofline", ctx) == pytest.approx(
        100 * least["least_s"] / 0.02)
    # the three attention kernels: 0.20 s of a step's 0.50
    least = ref.dsa_attn_min_seconds(cfg, PEAKS, 1)
    assert _read("dsa_attn_roofline", ctx) == pytest.approx(
        100 * least["least_s"] / 0.20)
    assert _read("dsa_select_share", ctx) == pytest.approx(6.0)
    # every dsa scope: 0.02 + 0.03 + 0.20 + 0.04 + 0.01 of 0.50
    assert _read("dsa_share", ctx) == pytest.approx(60.0)
    out = capsys.readouterr().out
    assert "'dsa/index/proj': 10.0, 'dsa/index': 20.0, 'dsa/select': 30.0, " \
        "'dsa/attn': 200.0, 'dsa/kl': 40.0" in out
    assert _read("dsa_pairs_selected_share", ctx) == pytest.approx(
        12.1092, abs=1e-4)
    # the readers the cell shares with the other LM cells
    assert _read("optimizer_share", ctx) == pytest.approx(10.0)
    assert _read("lm_step_device_ms", ctx) == pytest.approx(500.0)
    assert _read("moe_rows_walked_over_live", ctx) == 2.0
    assert _read("moe_tokens_held_share", ctx) == pytest.approx(66.7)


def test_a_program_without_the_scopes_gives_the_readers_nothing():
    """The parent of the PR that added them, or another configuration's
    adapter and reference: no map, no counter, no least-time function,
    and no reader raises."""
    bare = types.SimpleNamespace(STEP_PROGRAM="jit_kstep")
    for name in NEW:
        assert _read(name, _ctx(bare)) is None
    for name in NEW[:4]:
        assert _read(name, _ctx() | {"trace": None}) is None
    other = types.SimpleNamespace(
        STEP_PROGRAM="jit_kstep",
        op_scopes=lambda: {"fusion.9": "jit(kstep)/opt/update/add"})
    for name in NEW:        # the map, but none of these scopes or counters
        assert _read(name, _ctx(other)) is None
    lfm2 = manifest.load_module("references", "lfm2-24b-a2b")
    for name in NEW[:2]:    # a reference without the least-time function
        assert _read(name, _ctx() | {"reference": lfm2}) is None


def test_the_adapter_reads_the_pairs_from_the_programs_counters():
    from deeplearning4j_tpu import monitor
    system = manifest.load_module("systems", "dl4j_fit_keye_vl2")
    before = system.sparse_pairs() or (0.0, 0.0)
    monitor.counter("dsa_pairs_selected_total", "",
                    labels=("layer",)).inc(65012736, layer="test-a")
    monitor.counter("dsa_pairs_causal_total", "",
                    labels=("layer",)).inc(536887296, layer="test-a")
    kept, causal = system.sparse_pairs()
    assert (kept - before[0], causal - before[1]) == (65012736, 536887296)


def test_flops_and_least_times_against_hand_counts():
    cell = manifest.Cell(M, CELL)
    ref, cfg = manifest.load_module("references", cell.config_name), \
        cell.config
    t = 32768
    assert ref.seq_length(cfg) == t
    causal, kept = t * (t + 1) / 2, 2048 * 2049 / 2 + (t - 2048) * 2048
    assert (causal, kept) == (536887296, 65012736)
    assert (ref.pairs_causal(t), ref.pairs_selected(cfg, t)) == (causal,
                                                                 kept)
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    index = 2048 * (1024 + 64 + 16)
    experts = 2048 * 128 + 3 * 2048 * 768 * 8 * 16 / 128
    per_token = 5 * (attn + index + experts) + 2048 * 18992
    pairs = 5 * (causal * 16 * 64 + kept * 32 * 2 * 128)
    assert ref.train_flops_per_example(cfg) == pytest.approx(
        6 * (per_token * t + pairs))
    # 65.8 TFLOP a step of one sequence
    assert ref.train_flops_per_example(cfg) == pytest.approx(65.8e12,
                                                             rel=0.01)
    shares = ref.flops_shares(cfg)
    assert [round(100 * shares[k]) for k in (
        "attention projections", "indexer projections", "indexer scores",
        "selected attention", "held experts", "head")] == [
            28, 3, 25, 24, 7, 12]
    index_s = ref.dsa_index_min_seconds(cfg, PEAKS, 1)
    assert index_s["flops_s"] * 197e12 == pytest.approx(
        5 * 2 * causal * 16 * 64)
    assert index_s["least_s"] == index_s["flops_s"] > index_s["bytes_s"]
    attn_s = ref.dsa_attn_min_seconds(cfg, PEAKS, 1)
    assert attn_s["flops_s"] * 197e12 == pytest.approx(
        5 * 6 * kept * 32 * 256)
    # q and the output at 32 heads, k and v at 4, read or written once a
    # pass, two passes, bf16
    assert attn_s["bytes_s"] * 819e9 == pytest.approx(
        5 * 2 * t * (2 * 32 + 2 * 4) * 128 * 2)
    assert attn_s["least_s"] == attn_s["flops_s"] > attn_s["bytes_s"]
    least = ref.experts_min_seconds(cfg, PEAKS, 4096.0)
    assert least["flops_s"] * 197e12 == pytest.approx(
        9 * 2 * 4096 * 2048 * 768)
    import jax
    import numpy as np
    shapes = jax.tree_util.tree_leaves(
        ref.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s in shapes) == cfg["parameters"] \
        == 562290560
    assert [ref.stage_of(cfg, leaf) for leaf in (
        "['embed']['W']", "['layer3']['ffn']['Wr']", "['norm']['gamma']",
        "['head']['W']", "['layer1']['attn']['q_norm']",
        "['layer4']['attn']['indexer']['Wq']")] == [
            "embed", "layer3", "head", "head", "layer1", "indexer"]


def test_the_cell_reports_what_its_issue_named():
    cell = manifest.Cell(M, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"train_examples_per_s",
                                                    "setup_s"}
    assert cell.chips == 1 and cell.traffic["plan"] is None
    assert cell.traffic_name == "fit-tokens-32k-b1"
    ref = manifest.load_module("references", cell.config_name)
    t = cell.traffic
    assert (t["batch"], ref.seq_length(cell.config)) == (1, 32768)
    assert (t["scan_steps"], t["check_steps"], t["segment_steps"],
            t["pool_batches"]) == (2, 2, 4, 20)
    # at least these: a later PR may add a metric to the cell
    assert {m["name"] for m in cell.per_layer} >= set(NEW) | set(SHARED) | {
        "fit_segment_median_rate", "fit_window_rate_ratio", "fit_host_share",
        "fit_data_wait_share", "train_mfu_pct", "fit_device_idle_share"}
    assert not {m["name"] for m in cell.per_layer} & {
        "train_step_device_ms", "conv_roofline", "kda_share",
        "kda_scan_roofline", "mla_attn_roofline", "mtp_share",
        "mla_proj_share", "gqa_attn_roofline", "shortconv_share"}
    layers = {"dsa_index_roofline": "kernels", "dsa_attn_roofline": "kernels",
              "dsa_select_share": "compiled step",
              "dsa_share": "compiled step",
              "dsa_pairs_selected_share": "compiled step"}
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
    assert manifest.load_json("traffic", "fit-tokens-8k-b4.json")["batch"] \
        == 4


def test_the_configuration_file_against_the_catalog_row():
    """Every number of the catalog's ``config`` under the same key; what
    differs is in ``reduced`` with the published count beside it; no width
    is reduced; the free choices are ``assumed``."""
    cfg = manifest.Cell(M, CELL).config
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "KeyeVL2", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
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
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert row["config"] == published
        assert entry["source"].startswith(row["source_url"] + " ")
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 16, 18992)
    assert cfg["experts_held"] == [0, 16] and cfg["router_experts"] == 128
    assert "8 chips share each layer" in cfg["deployment"] \
        and "2,048 rows a step" in cfg["deployment"] \
        and "33.3 %" in cfg["deployment"] \
        and "exactly zero" in cfg["deployment"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json language model, one of 8 chips sharing each layer, "
        "layers 0-4")
    assert len(entry["source"]) <= 200
    assert entry["file"] == "benchmark/configs/keye-vl-2.0-30b-a3b.json"
    for key in ("vision_tower", "qk_norm", "rope_layout", "indexer_input",
                "indexer_key_norm", "indexer_rope", "indexer_scale",
                "selection", "chunk_sizes", "indexer_loss", "router_dtype",
                "optimizer", "init_scales", "weights_seed", "learning_rate",
                "host_batch"):
        assert key in cfg["assumed"], key
    assert "LEFT OUT" in cfg["assumed"]["vision_tower"]
    assert cfg["learning_rate"] <= 1e-5 and not cfg["tie_word_embeddings"]
    assert cfg["out_proj_std"] == pytest.approx(0.02 / 96 ** 0.5)
    assert set(cfg["limits"]["stage_momentum_gap"]) == {
        "embed", "layer0", "layer1", "layer2", "layer3", "layer4",
        "indexer", "head"}
    assert set(cfg["limits_reasons"]) >= set(cfg["limits"])
    # the rehearsal changes sizes only, never the mechanisms; its
    # selection selects (fewer keys kept than the sequence is long)
    assert not set(cfg["rehearsal"]) & {
        "num_experts_per_tok", "rope_theta", "norm_topk_prob",
        "decoder_sparse_step", "mlp_only_layers", "num_hidden_layers"}
    small = {**cfg, **cfg["rehearsal"]}
    ref = manifest.load_module("references", cfg["name"])
    assert small["sa_config"]["topk"] < ref.seq_length(small) // 4
    assert sum(small["rope_scaling"]["mrope_section"]) \
        == small["head_dim"] // 2
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
    pool = train_cell.make_batches(11, 2, 1, cfg)
    return cfg, ref, pool, _followed(ref, cfg, pool)


def test_fp8_training_fails_the_limits(small):
    cfg, ref, pool, sound = small
    rows = _judged(cfg, ref, _followed(ref, cfg, pool, "fp8"), sound)
    assert any(v > limit for v, limit in rows.values()), rows


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_changes_the_result_and_fails_the_limits(small,
                                                                  fault):
    """The reference with a fault put in the program's place: the
    indexer's ReLU or its head weights left out, half the keys kept, a
    query that sees the next key, the indexer's loss left out, its target
    taken from head 0, query head h on key head h % 2, the routers without
    the renormalisation of the kept weights."""
    cfg, ref, pool, sound = small
    assert ref.FAULTS == FAULTS
    bad = _followed(ref, cfg, pool, fault=fault)
    assert bad["momentum"] != sound["momentum"]
    rows = _judged(cfg, ref, bad, sound)
    if fault == "no_renorm":
        # at the rehearsal's widths the experts' leaves are a small part of
        # their stages: the norms move (the update's by 0.75 %) and pass;
        # at the cell's sizes on the chip the fault fails nine limits
        # (`limits_reasons`, PERF.md section 2)
        assert rows["update_norm_gap"][0] > 3e-3, rows
        return
    assert any(v > limit for v, limit in rows.values()), rows
    if fault == "no_indexer_loss":
        assert rows["stage_momentum_gap.indexer"][0] == 1.0


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
        "dsa_pairs_selected_share", "moe_tokens_held_share",
        "moe_rows_walked_over_live", "moe_expert_load_max_over_mean",
        "fit_host_share"}
    # 512 positions, 64 keys a query: sum_t min(t + 1, 64) = 30,752 of
    # T (T + 1) / 2 = 131,328 pairs a sequence and layer, whatever ran
    kept, causal = (float(n) for n in done.stdout.split(
        "[dsa_pairs_selected_share] ")[1].split(" pairs")[0].split(" of "))
    assert kept * 131328 == causal * 30752 and kept >= 4 * 5 * 30752
    out = os.path.join(str(tmp_path), CELL, "seed-2147483999-trace-1")
    with open(os.path.join(out, "check.json")) as f:
        check = json.load(f)
    assert len(check["program"]["losses"]) == 2
    assert set(check["program"]["momentum"]) \
        == set(check["reference"]["momentum"])
    assert "[check] stage_momentum_gap.indexer" in done.stdout
