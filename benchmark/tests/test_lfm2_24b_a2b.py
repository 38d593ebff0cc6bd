"""`lfm2-24b-a2b` and its cell `lfm2-24b-a2b-fit-8k-1chip`: the four new
readers on a recorded span table and made counters, the reference's FLOP and
least-time functions against hand counts, the configuration file against
the catalog row it was cut from, the float8 control and the six planted
faults failing the configuration's limits at a small size, and the CPU
rehearsal of the cell through ``benchmark/run.py`` from its files'
``rehearsal`` keys."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import checks, manifest, xplane

M = manifest.load_manifest()
CELL = "lfm2-24b-a2b-fit-8k-1chip"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("gqa_attn_roofline", "shortconv_roofline", "shortconv_share",
       "moe_tokens_held_share")
SHARED = ("lm_step_device_ms", "moe_experts_roofline", "moe_dispatch_share",
          "optimizer_share", "moe_expert_load_max_over_mean",
          "moe_rows_walked_over_live", "attn_fwd_runs_per_bwd")
FAULTS = ("half_batch", "kv_head_mod", "taps_reversed", "no_qk_norm",
          "no_rope", "no_renorm")


def _cell():
    return manifest.Cell(M, CELL).rehearsal()


def _trace():
    """Two whole runs of a step program of two steps and the head of a
    third that the profiler's end cut; a step spends 0.05 s in the flash
    kernels, 0.01 norming and 0.01 rotating q and k, 0.02 in attention's
    projections, 0.06 in the convolutions' projections, 0.04 in their
    gates and taps, 0.05 in the optimizer and 0.16 elsewhere."""
    t = xplane.Trace.__new__(xplane.Trace)
    ops, at = [], 1.0
    per_step = [("flash_fwd.7", 0.03), ("flash_bwd_dq.7", 0.02),
                ("fusion.1", 0.01), ("fusion.2", 0.01), ("fusion.3", 0.02),
                ("convolution.4", 0.06), ("fusion.5", 0.03),
                ("reduce.6", 0.01), ("fusion.9", 0.05), ("fusion.10", 0.16)]
    for _ in range(4):
        for name, d in per_step:
            ops.append((name, at, at + d, ""))
            at += d
    t.devices = [{"ops": ops + [("while.1", 1.0, at, "")],
                  "modules": [("jit_kstep", 1.0, 1.8), ("jit_kstep", 1.8, 2.6),
                              ("jit_kstep", 2.7, 2.71)]}]
    t.spans, t.window = [], (0.9, 2.71)
    return t


SCOPES = {
    "flash_fwd.7": "jit(kstep)/while/body/checkpoint/mha/attn/flash_fwd",
    "flash_bwd_dq.7":
        "jit(kstep)/while/body/transpose(jvp(mha/attn))/flash_bwd_dq",
    "fusion.1": "jit(kstep)/while/body/checkpoint/mha/norm/mul",
    "fusion.2": "jit(kstep)/while/body/checkpoint/mha/rope/mul",
    "fusion.3": "jit(kstep)/while/body/transpose(jvp(mha/proj))/dot",
    "convolution.4": "jit(kstep)/while/body/jvp(sconv/proj)/dot_general",
    "fusion.5": "jit(kstep)/while/body/checkpoint/rematted_computation/"
                "sconv/mix/mul",
    "reduce.6": "jit(kstep)/while/body/transpose(jvp())/checkpoint/"
                "sconv/mix/reduce_sum",
    "fusion.9": "jit(kstep)/while/body/opt/update/add",
}


def _ctx(system=None):
    cell = manifest.Cell(M, CELL)
    system = system or types.SimpleNamespace(
        STEP_PROGRAM="jit_kstep", op_scopes=lambda: SCOPES,
        expert_rows_per_step=lambda: {
            name: 16384.0 for name in ("layer1", "layer2", "layer3",
                                       "layer4")},
        expert_load_max_over_mean=lambda: 2.5,
        expert_rows_walked_over_live=lambda: 2.0,
        tokens_with_held_pair_share=lambda: 0.422)
    return {"cell": cell, "trace": _trace(), "system": system,
            "reference": manifest.load_module("references",
                                              cell.config_name),
            "peaks": PEAKS, "batch": 4, "steps_per_call": 2}


def _read(name, ctx):
    return manifest.load_module("metrics", name).read(ctx)


def test_the_new_readers_on_a_recorded_span_table(capsys):
    ctx = _ctx()
    cfg, ref = ctx["cell"].config, ctx["reference"]
    # the kernels under mha/attn: 0.05 s of a step's 0.40
    least = ref.gqa_attn_min_seconds(cfg, PEAKS, 4)
    assert _read("gqa_attn_roofline", ctx) == pytest.approx(
        100 * least["least_s"] / 0.05)
    # gates and taps, the stand-alone reduction among them: 0.04 s
    least = ref.shortconv_min_seconds(cfg, PEAKS, 4)
    assert _read("shortconv_roofline", ctx) == pytest.approx(
        100 * least["least_s"] / 0.04)
    # both of the convolution's scopes: 0.10 of 0.40
    assert _read("shortconv_share", ctx) == pytest.approx(25.0)
    out = capsys.readouterr().out
    assert "'sconv/proj': 60.0, 'sconv/mix': 40.0" in out
    assert "'mha/norm': 10.0, 'mha/rope': 10.0, 'mha/attn': 50.0" in out
    assert _read("moe_tokens_held_share", ctx) == pytest.approx(42.2)
    # the readers the cell shares with the two other LM cells
    assert _read("optimizer_share", ctx) == pytest.approx(12.5)
    assert _read("lm_step_device_ms", ctx) == pytest.approx(400.0)
    assert _read("moe_rows_walked_over_live", ctx) == 2.0
    assert _read("attn_fwd_runs_per_bwd", ctx) == 1.0


def test_a_program_without_the_scopes_gives_the_readers_nothing():
    """The parent of the PR that added them, or another configuration's
    adapter and reference: no map, no counter, no least-time function,
    and no reader raises."""
    bare = types.SimpleNamespace(STEP_PROGRAM="jit_kstep")
    for name in NEW:
        assert _read(name, _ctx(bare)) is None
    for name in NEW[:3]:
        assert _read(name, _ctx() | {"trace": None}) is None
    other = types.SimpleNamespace(
        STEP_PROGRAM="jit_kstep",
        op_scopes=lambda: {"fusion.9": "jit(kstep)/opt/update/add"})
    for name in NEW:        # the map, but none of these scopes or counters
        assert _read(name, _ctx(other)) is None
    glm = manifest.load_module("references", "glm-4.7-flash")
    for name in NEW[:2]:    # a reference without the least-time function
        assert _read(name, _ctx() | {"reference": glm}) is None


def test_the_adapter_reads_the_share_of_tokens_with_a_held_pair():
    from deeplearning4j_tpu import monitor
    system = manifest.load_module("systems", "dl4j_fit_lfm2_moe")
    system._CFG.update({"num_experts_per_tok": 4})
    held = monitor.counter("moe_tokens_with_held_pair_total", "",
                           labels=("layer",))
    routed = monitor.counter("moe_tokens_routed_total", "",
                             labels=("layer", "held"))
    before = system.tokens_with_held_pair_share()
    held.inc(422, layer="test-a")
    routed.inc(500, layer="test-a", held="yes")
    routed.inc(3500, layer="test-a", held="no")
    if before is None:              # nothing else has counted yet
        assert system.tokens_with_held_pair_share() == pytest.approx(0.422)
    else:
        assert 0 < system.tokens_with_held_pair_share() <= 1


def test_flops_and_least_times_against_hand_counts():
    cell = manifest.Cell(M, CELL)
    ref, cfg = manifest.load_module("references", cell.config_name), \
        cell.config
    t = 8192
    conv = 2048 * 6144 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert (conv, attn) == (16_777_216, 10_485_760)
    experts = 2048 * 64 + 3 * 2048 * 1536 * 4 * 8 / 64
    per_token = 4 * conv + attn + 3 * 2048 * 11776 + 4 * experts \
        + 2048 * 8192
    mixing = (t * (t + 1) / 2) * 32 * (64 + 64)
    assert ref.train_flops_per_example(cfg) == pytest.approx(
        6 * (per_token * t + mixing))
    # 39.9 TFLOP a step of four sequences: operator projections 38 %, the
    # dense MLP 36 %, held experts 10 %, head 8 %, attention scores 8 %
    assert 4 * ref.train_flops_per_example(cfg) == pytest.approx(39.9e12,
                                                                 rel=0.01)
    shares = ref.flops_shares(cfg)
    assert [round(100 * shares[k]) for k in (
        "operator projections", "dense MLP", "held experts", "head",
        "attention scores")] == [38, 36, 10, 8, 8]
    gqa = ref.gqa_attn_min_seconds(cfg, PEAKS, 4)
    assert gqa["flops_s"] * 197e12 == pytest.approx(
        4 * 6 * (t * (t + 1) / 2) * 32 * 128)
    # q and the output at 32 heads, k and v at 8, read or written once a
    # pass, two passes, bf16
    assert gqa["bytes_s"] * 819e9 == pytest.approx(
        4 * 2 * t * (2 * 32 + 2 * 8) * 64 * 2)
    assert gqa["least_s"] == gqa["flops_s"] > gqa["bytes_s"]
    sconv = ref.shortconv_min_seconds(cfg, PEAKS, 4)
    # 4 layers x 32,768 tokens x 11 rows of 2,048 bf16: B, C, u read and
    # one row written; its gradient and B, C, u read, three gradients
    # written
    assert sconv["bytes_s"] * 819e9 == pytest.approx(
        4 * 4 * t * 11 * 2048 * 2)
    assert sconv["least_s"] == sconv["bytes_s"] > sconv["flops_s"]
    least = ref.experts_min_seconds(cfg, PEAKS, 16384.0)
    assert least["flops_s"] * 197e12 == pytest.approx(
        9 * 2 * 16384 * 2048 * 1536)
    import jax
    import numpy as np
    shapes = jax.tree_util.tree_leaves(
        ref.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s in shapes) == cfg["parameters"] \
        == 469284992
    # the tied matrix is counted once: there is no head leaf
    assert "head" not in ref.param_shapes(cfg)
    assert [ref.stage_of(cfg, leaf) for leaf in (
        "['embed']['W']", "['layer3']['ffn']['Wr']", "['norm']['gamma']",
        "['layer1']['attn']['q_norm']", "['layer0']['attn']['conv']")] == [
            "head", "layer3", "head", "layer1", "layer0"]
    assert ref.layer_kinds(cfg) == [
        ("conv", "dense"), ("full_attention", "experts"),
        ("conv", "experts"), ("conv", "experts"), ("conv", "experts")]


def test_the_cell_reports_what_its_issue_named():
    cell = manifest.Cell(M, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"train_examples_per_s",
                                                    "setup_s"}
    assert cell.chips == 1 and cell.traffic["plan"] is None
    assert cell.traffic_name == "fit-tokens-8k-b4"
    ref = manifest.load_module("references", cell.config_name)
    t = cell.traffic
    assert (t["batch"], ref.seq_length(cell.config)) == (4, 8192)
    assert (t["scan_steps"], t["check_steps"], t["segment_steps"],
            t["pool_batches"]) == (2, 2, 10, 20)
    # at least these: a later PR may add a metric to the cell
    assert {m["name"] for m in cell.per_layer} >= set(NEW) | set(SHARED) | {
        "fit_segment_median_rate", "fit_window_rate_ratio", "fit_host_share",
        "fit_data_wait_share", "train_mfu_pct", "fit_device_idle_share"}
    assert not {m["name"] for m in cell.per_layer} & {
        "train_step_device_ms", "conv_roofline", "kda_share",
        "kda_scan_roofline", "mla_attn_roofline", "mtp_share",
        "mla_proj_share"}
    layers = {"gqa_attn_roofline": "kernels", "shortconv_roofline": "kernels",
              "shortconv_share": "compiled step",
              "moe_tokens_held_share": "compiled step"}
    for m in M["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] \
                and m["moves"] == "train_examples_per_s" \
                and m["layer"] == layers[m["name"]]
        if m["name"] in NEW + SHARED:
            assert CELL in m["workloads"] and os.path.exists(os.path.join(
                manifest.BENCH_DIR, "metrics", m["name"] + ".py"))
    # appended, nothing the manifest had moved: the new entries are last
    assert M["workloads"][-1]["name"] == CELL \
        and M["configs"][-1]["name"] == "lfm2-24b-a2b"
    assert [m["name"] for m in M["per_layer"][-4:]] == list(NEW)
    assert len(cell.entry["why"]) <= 200
    # the other cells' traffic file is as it was
    assert manifest.load_json("traffic", "fit-tokens-8k-b2.json")["batch"] \
        == 2


def test_the_configuration_file_against_the_catalog_row():
    """Every number of the catalog's ``config`` under the same key; what
    differs is in ``reduced`` with the published count beside it; no width
    is reduced; the free choices are ``assumed``."""
    cfg = manifest.Cell(M, CELL).config
    layer_types = ["conv", "conv", "full_attention"] \
        + ["conv", "conv", "conv", "full_attention"] * 9 + ["conv"]
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "layer_types": layer_types,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    entry = next(c for c in M["configs"] if c["name"] == cfg["name"])
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value and type(cfg[key]) is type(value), key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-24B-A2B")
        assert row["config"] == published
        assert entry["source"].startswith(row["source_url"] + " ")
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 8, 8192)
    assert len(cfg["layer_types"]) == 40 and cfg["first_layer"] == 1
    assert cfg["experts_held"] == [0, 8] and cfg["router_experts"] == 64
    assert "8 chips share each layer" in cfg["deployment"] \
        and "2,048 rows a step" in cfg["deployment"] \
        and "exactly zero" in cfg["deployment"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json "
        "one of 8 chips sharing each layer, layers 1-5")
    assert len(entry["source"]) <= 200
    assert entry["file"] == "benchmark/configs/lfm2-24b-a2b.json"
    for key in ("tie_word_embeddings", "qk_norm", "rope_layout",
                "conv_layout", "renormalisation_eps", "expert_bias",
                "router_dtype", "optimizer", "init_scales", "weights_seed",
                "learning_rate", "host_batch"):
        assert key in cfg["assumed"], key
    assert cfg["learning_rate"] <= 1e-5 and cfg["tie_word_embeddings"]
    assert set(cfg["limits"]["stage_momentum_gap"]) == {
        "layer0", "layer1", "layer2", "layer3", "layer4", "head"}
    assert set(cfg["limits_reasons"]) >= set(cfg["limits"])
    # the rehearsal changes sizes only, never the mechanisms
    assert not set(cfg["rehearsal"]) & {
        "layer_types", "first_layer", "num_dense_layers", "conv_L_cache",
        "num_experts_per_tok", "rope_parameters", "routed_scaling_factor"}
    assert cfg["rehearsal"]["num_attention_heads"] \
        // cfg["rehearsal"]["num_key_value_heads"] == 4


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
    """The reference with a fault put in the program's place: one of a
    step's two sequences left out, query head h on key head h % 2, the
    taps reversed, q and k not normed, not rotated, the routers without
    the renormalisation of the kept scores."""
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
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["attempted"] >= 2 and result["device"]["platform"] == "cpu"
    assert set(result["counts"]["metrics_read"]) >= {
        "moe_tokens_held_share", "moe_rows_walked_over_live",
        "moe_expert_load_max_over_mean", "fit_segment_median_rate",
        "fit_host_share"}
    out = os.path.join(str(tmp_path), CELL, "seed-2147483999-trace-1")
    with open(os.path.join(out, "check.json")) as f:
        check = json.load(f)
    assert len(check["program"]["losses"]) == 2
    assert set(check["program"]["momentum"]) \
        == set(check["reference"]["momentum"])
    assert "[check] stage_momentum_gap.layer1" in done.stdout
