"""`kimi-linear-48b-a3b` and its cell `kimi-linear-fit-8k-1chip`: the new
readers on a made trace, the float8 control of the new reference and the
three planted faults failing the configuration's limits at a small size, a
broken path that comes out as not correct, the configuration file against
the catalog row it was cut from, and the CPU rehearsal of the cell from its
files' ``rehearsal`` keys."""
import json
import os
import types

import pytest

from benchmark.lib import checks, manifest, scopes, xplane

M = manifest.load_manifest()
CELL = "kimi-linear-fit-8k-1chip"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("kda_scan_roofline", "mla_attn_roofline", "moe_experts_roofline",
           "kda_share", "moe_dispatch_share", "optimizer_share",
           "moe_expert_load_max_over_mean", "lm_step_device_ms")


def _cell():
    return manifest.Cell(M, CELL).rehearsal()


def _trace():
    """Two whole runs of a step program of two steps and the head of a
    third that the profiler's end cut; a step spends 0.10 s in the KDA
    recurrences, 0.04 in KDA's projections, 0.06 in the latent attention's
    kernels, 0.02 in the grouped products and 0.01 in their activation,
    0.03 routing and moving rows, 0.05 in the optimizer and 0.09 under no
    scope."""
    t = xplane.Trace.__new__(xplane.Trace)
    ops, at = [], 1.0
    per_step = [("fusion.20", 0.10), ("fusion.21", 0.04),
                ("flash_fwd.7", 0.04), ("flash_bwd_dq.2", 0.02),
                ("ragged-dot-none.3", 0.02), ("multiply_fusion.1", 0.01),
                ("sort.4", 0.03), ("fusion.9", 0.05), ("fusion.10", 0.09)]
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
    "fusion.20": "jit(kstep)/while/body/checkpoint/kda/scan/while/body/dot",
    "fusion.21": "jit(kstep)/while/body/transpose(jvp(kda/proj))/mul",
    "multiply_fusion.1": "jit(kstep)/while/body/jvp(moe/experts)/mul",
    "flash_fwd.7": "jit(kstep)/while/body/jvp(mla/attn)/flash_fwd",
    "flash_bwd_dq.2":
        "jit(kstep)/while/body/transpose(jvp(mla/attn))/flash_bwd_dq",
    "sort.4": "jit(kstep)/while/body/jvp(moe/dispatch)/sort",
    "fusion.9": "jit(kstep)/while/body/opt/update/add",
    "ragged-dot-none.3": "ragged-dot-none",
}


def _ctx(system=None):
    cell = manifest.Cell(M, CELL)
    system = system or types.SimpleNamespace(
        STEP_PROGRAM="jit_kstep", op_scopes=lambda: SCOPES,
        expert_rows_per_step=lambda: {str(i): 4096.0 for i in (2, 3, 4, 5)},
        expert_load_max_over_mean=lambda: 2.5)
    return {"cell": cell, "trace": _trace(), "system": system,
            "reference": manifest.load_module("references",
                                              cell.config_name),
            "peaks": PEAKS, "batch": 2, "steps_per_call": 2}


def _read(name, ctx):
    return manifest.load_module("metrics", name).read(ctx)


def test_shares_by_scope_take_whole_runs_only():
    ctx = _ctx()
    # the third launch (10 ms, cut) is no whole run of the step program
    assert len(scopes.step_runs(ctx)) == 2
    took, whole, steps = scopes.seconds(
        ctx, lambda n, scope: "opt/update" in scope)
    assert (round(took, 6), round(whole, 6), steps) == (0.2, 1.6, 4)
    assert _read("optimizer_share", ctx) == pytest.approx(12.5)
    assert _read("moe_dispatch_share", ctx) == pytest.approx(7.5)
    assert _read("kda_share", ctx) == pytest.approx(35.0)
    assert _read("moe_expert_load_max_over_mean", ctx) == 2.5
    # 1.6 s of whole runs over their 4 steps; the cut launch is no step
    assert _read("lm_step_device_ms", ctx) == pytest.approx(400.0)


def test_rooflines_from_the_references_counts():
    ctx = _ctx()
    ref, cfg = ctx["reference"], ctx["cell"].config
    least = ref.experts_min_seconds(cfg, PEAKS, 4096.0)
    # 3 products x (1 forward + 2 backward) x 2 x rows x 2304 x 1024
    assert least["flops_s"] * 197e12 == pytest.approx(
        9 * 2 * 4096 * 2304 * 1024)
    # 512 rows an expert sit just above the chip's ridge (240 FLOP a
    # byte): the FLOPs bound it, the experts' matrices not far behind
    assert least["least_s"] == least["flops_s"] > least["bytes_s"] \
        > 0.5 * least["flops_s"]
    assert _read("moe_experts_roofline", ctx) == pytest.approx(
        100 * 4 * least["least_s"] / 0.03)
    kda = ref.kda_scan_min_seconds(cfg, PEAKS, 2)
    # 4 layers x 32 heads x 4 passes over 128 x 128 a token, x 3 x 2
    assert kda["flops_s"] * 197e12 == pytest.approx(
        6 * 4 * 4 * 32 * 128 * 128 * 2 * 8192)
    assert _read("kda_scan_roofline", ctx) == pytest.approx(
        100 * kda["least_s"] / 0.10)
    mla = ref.mla_attn_min_seconds(cfg, PEAKS, 2)
    assert mla["flops_s"] * 197e12 == pytest.approx(
        2 * 6 * (8192 * 8193 / 2) * 32 * (192 + 128))
    assert mla["least_s"] == mla["flops_s"] > mla["bytes_s"]
    assert _read("mla_attn_roofline", ctx) == pytest.approx(
        100 * mla["least_s"] / 0.06)
    for name in ("kda_scan_roofline", "mla_attn_roofline",
                 "moe_experts_roofline"):
        assert 0 < _read(name, ctx) < 100
    # model FLOPs: 37.9 TFLOP a step of two sequences, 602 M parameters
    assert 2 * ref.train_flops_per_example(cfg) == pytest.approx(
        37.9e12, rel=0.01)
    import jax
    shapes = jax.tree_util.tree_leaves(
        ref.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    n = sum(int(__import__("numpy").prod(s)) for s in shapes)
    assert n == cfg["parameters"] == 602433408


def test_a_program_without_the_scopes_gives_the_readers_nothing():
    """The parent of the PR that added them, or another configuration's
    adapter: no map, no counters, and no reader raises."""
    bare = types.SimpleNamespace(STEP_PROGRAM="jit_kstep")
    ctx = _ctx(bare)
    for name in READERS:
        # the step's device time needs the trace alone
        assert _read(name, ctx) is None or name == "lm_step_device_ms"
    for name in READERS:
        assert _read(name, _ctx() | {"trace": None}) is None or \
            name == "moe_expert_load_max_over_mean"
    resnet = manifest.Cell(M, "resnet50-fit-1chip")
    assert not {m["name"] for m in resnet.per_layer} & set(READERS)


def test_the_cell_reports_what_its_issue_named():
    cell = manifest.Cell(M, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"train_examples_per_s",
                                                    "setup_s"}
    assert cell.chips == 1 and cell.traffic["plan"] is None
    ref = manifest.load_module("references", cell.config_name)
    t = cell.traffic
    assert (t["batch"], ref.seq_length(cell.config)) == (2, 8192)
    assert (t["scan_steps"], t["check_steps"], t["segment_steps"],
            t["pool_batches"]) == (2, 2, 10, 20)
    assert t["trace"]["untraced_share"] == 0.8
    assert {m["name"] for m in cell.per_layer} == set(READERS) | {
        "fit_segment_median_rate", "fit_window_rate_ratio", "fit_host_share",
        "fit_data_wait_share", "train_mfu_pct", "fit_device_idle_share"}
    for m in M["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL] \
                and m["moves"] == "train_examples_per_s"
            assert os.path.exists(os.path.join(
                manifest.BENCH_DIR, "metrics", m["name"] + ".py"))


def test_the_configuration_file_against_the_catalog_row():
    """Every number of the catalog's ``config`` under the same key; what
    differs is in ``reduced`` with the published count beside it; no width
    is reduced; the free choices and the missing sizes are ``assumed``."""
    cfg = manifest.Cell(M, CELL).config
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "moe_intermediate_size": 1024, "moe_layer_freq": 1,
        "num_attention_heads": 32, "num_expert_group": 1,
        "num_experts": 256, "num_experts_per_token": 8,
        "num_hidden_layers": 27, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 163840, "model_max_length": 1048576}
    entry = next(c for c in M["configs"] if c["name"] == cfg["name"])
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 20480)
    la = cfg["linear_attn_config"]
    assert la == {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
                  "head_dim": 128,
                  "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17,
                                 18, 19, 21, 22, 23, 25, 26],
                  "num_heads": 32, "short_conv_kernel_size": 4}
    assert cfg["mla_use_nope"] is True and cfg["q_lora_rank"] is None
    assert cfg["moe_router_activation_func"] == "sigmoid"
    assert cfg["experts_held"] == [0, 8] and cfg["router_experts"] == 256
    assert "32 chips share each layer" in cfg["deployment"]
    assert entry["source"].startswith(
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json") and "one of 32 chips" in entry["source"]
    for key in ("learning_rate", "init_scales", "weights_seed", "kda_chunk",
                "kda_decay_rank", "output_gate", "head_dim",
                "e_score_correction_bias", "kda_init", "optimizer"):
        assert key in cfg["assumed"], key
    assert cfg["learning_rate"] <= 1e-5
    assert set(cfg["limits"]["stage_momentum_gap"]) == {
        "embed", "layer1", "layer2", "layer3", "layer4", "layer5", "head"}
    assert "limits_reasons" in cfg


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


@pytest.mark.parametrize("fault", ["half_batch", "kda_no_decay",
                                   "router_no_renorm"])
def test_a_planted_fault_fails_the_limits(small, fault):
    """The reference with a fault put in the program's place: one of a
    step's two sequences left out, the KDA layers without their decay, the
    routers without the renormalisation of the kept scores."""
    cfg, ref, pool, sound = small
    rows = _judged(cfg, ref, _followed(ref, cfg, pool, fault=fault), sound)
    assert any(v > limit for v, limit in rows.values()), rows


def test_a_broken_layer_is_not_correct(tmp_path, monkeypatch):
    """A whole run with the timed path broken underneath: an expert layer
    that ignores which experts it holds and computes every routed pair
    with one of its own (the other chips' work done here, wrongly)."""
    from benchmark.lib import train_cell
    from deeplearning4j_tpu.nn.layers import attention
    real = attention.MoEFeedForward.experts

    def every_pair_is_mine(self, params, h, idx, w):
        lo, hi = self._held()
        return real(self, params, h, lo + idx % (hi - lo), w)

    monkeypatch.setattr(attention.MoEFeedForward, "experts",
                        every_pair_is_mine)
    result, _, _ = train_cell.run(_cell(), 5, 2.0, False, str(tmp_path),
                                  0.0)
    assert result["correct"] is False


def test_the_cell_rehearses_on_the_cpu(tmp_path):
    from benchmark.lib import train_cell
    result, e2e, _ = train_cell.run(_cell(), 2147483999, 2.0, False,
                                    str(tmp_path), 0.0)
    assert result["attempted"] >= 2 and e2e["train_examples_per_s"] > 0
    # at these widths the bf16 program reads some stages 5e-4 to 2e-3 off
    # the float32 reference, over the limits the chip's sizes set: a
    # rehearsal proves control flow, `correct` is decided on the chip
    with open(os.path.join(str(tmp_path), "check.json")) as f:
        assert len(json.load(f)["program"]["losses"]) == 2
