"""`glm-4.7-flash` and its cell `glm-4.7-flash-fit-8k-1chip`: the three new
readers on a made trace and made counters, the reference's FLOP and
least-time functions against hand counts, the configuration file against
the catalog row it was cut from, the float8 control and the four planted
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
CELL = "glm-4.7-flash-fit-8k-1chip"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("mtp_share", "mla_proj_share", "moe_rows_walked_over_live")
SHARED = ("lm_step_device_ms", "mla_attn_roofline", "moe_experts_roofline",
          "moe_dispatch_share", "optimizer_share",
          "moe_expert_load_max_over_mean")


def _cell():
    return manifest.Cell(M, CELL).rehearsal()


def _trace():
    """Two whole runs of a step program of two steps and the head of a
    third that the profiler's end cut; a step spends 0.06 s in the trunk's
    flash kernels and 0.02 in the module's, 0.03 in the trunk's
    projections, 0.01 in the module's, 0.01 rotating, 0.02 in the module's
    second head, 0.05 in the optimizer and 0.20 elsewhere in the trunk."""
    t = xplane.Trace.__new__(xplane.Trace)
    ops, at = [], 1.0
    per_step = [("flash_fwd.7", 0.06), ("flash_fwd.9", 0.02),
                ("fusion.1", 0.03), ("fusion.2", 0.01), ("fusion.3", 0.01),
                ("fusion.4", 0.02), ("fusion.9", 0.05), ("fusion.10", 0.20)]
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
    "flash_fwd.7": "jit(kstep)/while/body/checkpoint/mla/attn/flash_fwd",
    "flash_fwd.9":
        "jit(kstep)/while/body/jvp(mtp)/checkpoint/mla/attn/flash_fwd",
    "fusion.1": "jit(kstep)/while/body/transpose(jvp(mla/proj))/dot",
    "fusion.2": "jit(kstep)/while/body/transpose(jvp(mtp))/mla/proj/dot",
    "fusion.3": "jit(kstep)/while/body/checkpoint/mla/rope/mul",
    "fusion.4": "jit(kstep)/while/body/jvp(mtp)/head/loss/dot",
    "fusion.9": "jit(kstep)/while/body/opt/update/add",
}


def _ctx(system=None):
    cell = manifest.Cell(M, CELL)
    system = system or types.SimpleNamespace(
        STEP_PROGRAM="jit_kstep", op_scopes=lambda: SCOPES,
        expert_rows_per_step=lambda: {
            name: 8192.0 for name in ("layer1", "layer2", "layer3", "layer4",
                                      "mtp_block")},
        expert_load_max_over_mean=lambda: 2.5,
        expert_rows_walked_over_live=lambda: 2.0)
    return {"cell": cell, "trace": _trace(), "system": system,
            "reference": manifest.load_module("references",
                                              cell.config_name),
            "peaks": PEAKS, "batch": 2, "steps_per_call": 2}


def _read(name, ctx):
    return manifest.load_module("metrics", name).read(ctx)


def test_the_new_readers_on_a_made_trace(capsys):
    ctx = _ctx()
    # everything under "mtp", whatever its inner scope: 0.02 + 0.01 + 0.02
    # of a step's 0.40 s
    assert _read("mtp_share", ctx) == pytest.approx(12.5)
    # projections and rotation, the trunk's and the module's: 0.05 of 0.40
    assert _read("mla_proj_share", ctx) == pytest.approx(12.5)
    assert "'mla/proj': 40.0, 'mla/rope': 10.0" in capsys.readouterr().out
    assert _read("moe_rows_walked_over_live", ctx) == 2.0
    # the shared readers still count the module's ops by their inner scope
    least = ctx["reference"].mla_attn_min_seconds(ctx["cell"].config, PEAKS,
                                                  2)
    assert _read("mla_attn_roofline", ctx) == pytest.approx(
        100 * least["least_s"] / 0.08)
    assert _read("optimizer_share", ctx) == pytest.approx(12.5)
    assert _read("lm_step_device_ms", ctx) == pytest.approx(400.0)


def test_a_program_without_the_scopes_gives_the_readers_nothing():
    """The parent of the PR that added them, or another configuration's
    adapter: no map, no counters, and no reader raises."""
    bare = types.SimpleNamespace(STEP_PROGRAM="jit_kstep")
    for name in NEW:
        assert _read(name, _ctx(bare)) is None
    for name in NEW[:2]:
        assert _read(name, _ctx() | {"trace": None}) is None
    # a program that has the map but not these scopes reads zero shares
    # of nothing it has: the Kimi adapter's scopes hold no "mtp"
    other = types.SimpleNamespace(
        STEP_PROGRAM="jit_kstep",
        op_scopes=lambda: {"fusion.9": "jit(kstep)/opt/update/add"})
    assert _read("mtp_share", _ctx(other)) == 0.0


def test_the_adapter_reads_rows_walked_over_live_from_the_counters():
    from deeplearning4j_tpu import monitor
    system = manifest.load_module("systems", "dl4j_fit_glm_moe_lite")
    walked = monitor.counter("moe_rows_walked_total", "",
                             labels=("layer",))
    routed = monitor.counter("moe_tokens_routed_total", "",
                             labels=("layer", "held"))
    before = system.expert_rows_walked_over_live()
    walked.inc(4096, layer="test-a")
    walked.inc(4096, layer="test-b")
    routed.inc(2048, layer="test-a", held="yes")
    routed.inc(2048, layer="test-b", held="yes")
    routed.inc(50000, layer="test-a", held="no")
    if before is None:              # nothing else has counted yet
        assert system.expert_rows_walked_over_live() == 2.0
    else:
        assert system.expert_rows_walked_over_live() > 0


def test_flops_and_least_times_against_hand_counts():
    cell = manifest.Cell(M, CELL)
    ref, cfg = manifest.load_module("references", cell.config_name), \
        cell.config
    t = 8192
    attn = 2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448 \
        + 20 * 256 * 2048
    assert attn == 21_757_952
    experts = 2048 * 64 + 3 * 2048 * 1536 + 3 * 2048 * 1536 * 4 * 8 / 64
    head = 2048 * 19360
    per_token = 5 * attn + 3 * 2048 * 10240 + 4 * experts + head \
        + (2 * 2048 * 2048 + attn + experts + head)
    mixing = 6 * (t * (t + 1) / 2) * 20 * (256 + 256)
    assert ref.train_flops_per_example(cfg) == pytest.approx(
        6 * (per_token * t + mixing))
    # 59 TFLOP a step of two sequences, 42 % of it the six latent
    # attentions' scores and weighted values (their projections 22 %, the
    # two heads 13 %, the experts 12 %, the dense layer 10 %)
    assert 2 * ref.train_flops_per_example(cfg) == pytest.approx(59.4e12,
                                                                 rel=0.01)
    assert 6 * mixing / ref.train_flops_per_example(cfg) \
        == pytest.approx(0.42, abs=0.01)
    mla = ref.mla_attn_min_seconds(cfg, PEAKS, 2)
    assert mla["flops_s"] * 197e12 == pytest.approx(
        2 * 6 * 6 * (t * (t + 1) / 2) * 20 * 512)
    assert mla["least_s"] == mla["flops_s"] > mla["bytes_s"]
    least = ref.experts_min_seconds(cfg, PEAKS, 8192.0)
    # 3 products x (1 forward + 2 backward) x 2 x rows x 2048 x 1536
    assert least["flops_s"] * 197e12 == pytest.approx(
        9 * 2 * 8192 * 2048 * 1536)
    assert least["least_s"] == least["flops_s"] > least["bytes_s"]
    import jax
    import numpy as np
    shapes = jax.tree_util.tree_leaves(
        ref.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s in shapes) == cfg["parameters"] \
        == 706518528
    # a shared leaf is counted once: no second embedding, no second head
    assert not {"mtp_embed", "mtp_head"} & set(ref.param_shapes(cfg))
    assert [ref.stage_of(cfg, leaf) for leaf in (
        "['embed']['W']", "['layer3']['ffn']['Wr']", "['norm']['gamma']",
        "['head']['W']", "['mtp_proj']['W']",
        "['mtp_block']['attn']['Wqa']")] == [
            "embed", "layer3", "head", "head", "mtp", "mtp"]


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
    assert {m["name"] for m in cell.per_layer} >= set(NEW) | set(SHARED) | {
        "fit_segment_median_rate", "fit_window_rate_ratio", "fit_host_share",
        "fit_data_wait_share", "train_mfu_pct", "fit_device_idle_share"}
    assert not {m["name"] for m in cell.per_layer} & {
        "train_step_device_ms", "conv_roofline", "kda_share",
        "kda_scan_roofline"}
    for m in M["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] \
                and m["moves"] == "train_examples_per_s" \
                and m["layer"] == "compiled step"
        if m["name"] in NEW + SHARED:
            assert CELL in m["workloads"] and os.path.exists(os.path.join(
                manifest.BENCH_DIR, "metrics", m["name"] + ".py"))
    assert len(cell.entry["why"]) <= 200


def test_the_configuration_file_against_the_catalog_row():
    """Every number of the catalog's ``config`` under the same key; what
    differs is in ``reduced`` with the published count beside it; no width
    is reduced; the free choices are ``assumed``."""
    cfg = manifest.Cell(M, CELL).config
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    entry = next(c for c in M["configs"] if c["name"] == cfg["name"])
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value and type(cfg[key]) is type(value), key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 8, 19360)
    assert cfg["experts_held"] == [0, 8] and cfg["router_experts"] == 64
    assert "8 chips share each layer" in cfg["deployment"] \
        and "LAST stage" in cfg["deployment"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json "
        "one of 8 chips sharing each layer, layers 0-4 and the MTP module")
    assert entry["file"] == "benchmark/configs/glm-4.7-flash.json"
    for key in ("rope_pairs", "e_score_correction_bias", "mtp_loss_weight",
                "mtp_hidden", "mtp_concatenation", "router_dtype",
                "optimizer", "init_scales", "weights_seed", "learning_rate",
                "host_batch"):
        assert key in cfg["assumed"], key
    assert cfg["learning_rate"] <= 1e-5 and cfg["mtp_loss_weight"] == 0.1
    assert set(cfg["limits"]["stage_momentum_gap"]) == {
        "embed", "layer0", "layer1", "layer2", "layer3", "layer4", "mtp",
        "head"}
    assert "limits_reasons" in cfg
    # the rehearsal changes sizes only, never the mechanisms
    assert not set(cfg["rehearsal"]) & {
        "num_nextn_predict_layers", "first_k_dense_replace",
        "num_experts_per_tok", "rope_theta", "routed_scaling_factor"}


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


@pytest.mark.parametrize("fault", ["half_batch", "no_rope", "mtp_unshifted",
                                   "no_renorm"])
def test_a_planted_fault_changes_the_result_and_fails_the_limits(small,
                                                                  fault):
    """The reference with a fault put in the program's place: one of a
    step's two sequences left out, the rotation left out, the MTP branch
    fed the token the trunk saw, the routers without the renormalisation
    of the kept scores."""
    cfg, ref, pool, sound = small
    assert fault in ref.FAULTS and len(ref.FAULTS) == 4
    bad = _followed(ref, cfg, pool, fault=fault)
    # (at the rehearsal's widths the scores are nearly flat, so the
    # rotation left out moves the first moments and not the printed loss)
    assert bad["losses"] != sound["losses"] or fault == "no_rope"
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
    # both losses reached the fit loop's one fetch
    assert "[check] stage_momentum_gap.mtp" in done.stdout
