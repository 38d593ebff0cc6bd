"""The Xing4.0 family's zoo model through `ComputationGraph.fit()` against
its reference; the cut and its count, the scopes, counters and gauges,
checkpoints; see `_xing_common.py`."""
import json
import os

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import MultiDataSet
from deeplearning4j_tpu.models import Xing4LM

import _lm_common as lm
from _xing_common import CFG, FAMILY, REF, SYSTEM, T
from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _rows,
)

_CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs", "xing4.0-29b-a4b.json")


def _published():
    with open(_CONFIG) as f:
        return json.load(f)


# --------------------------------------------- the whole model through fit()
def test_the_cut_is_published_layers_1_to_5_on_four_streams():
    """The second leading dense layer once, then expert layers; every layer
    a `HyperConnectedBlock` between the two ends, latent attention under
    YaRN in each, the head untied, no multi-token-prediction module."""
    cfg = _published()
    assert cfg["first_layer"] == 1 and cfg["first_k_dense_replace"] == 2
    assert [REF._dense_layer(cfg, i) for i in range(5)] \
        == [True, False, False, False, False]
    assert REF.dense_layers(cfg) == REF.dense_layers(CFG) == 1
    net, _ = FAMILY.reader()
    assert net.conf.network_outputs == ("head",)
    assert set(net.params) == {"embed", "norm", "head", "layer0", "layer1",
                               "layer2"}
    assert [type(net.conf.vertices[v].vertex).__name__
            for v in ("streams", "sum")] == ["StreamsInVertex",
                                             "StreamsOutVertex"]
    for i, ffn in enumerate(("GatedMLP", "MoEFeedForward",
                             "MoEFeedForward")):
        block = net.conf.vertices[f"layer{i}"].vertex
        assert type(block).__name__ == "HyperConnectedBlock"
        assert (block.n_streams, block.sinkhorn_iters, block.hc_eps,
                tuple(block.res_clamp)) == (4, 20, 1e-6, (-30, 30))
        assert type(block.ffn).__name__ == ffn
        assert block.attn.rope_scaling == "yarn" and block.attn.rotate
        assert set(net.params[f"layer{i}"]) == {
            "attn", "ffn", "ln1", "ln2", "hc_attn", "hc_ffn"}
    # a factor of 1 or less: the family's plain rotation
    plain = Xing4LM(rope_factor=1.0).conf().vertices["layer0"].vertex
    assert plain.attn.rope_scaling is None


def test_the_configuration_holds_the_published_widths_and_the_count():
    """Every width as published; what is cut is a count and is listed; the
    parameters held by formula, leaf by leaf: the issue's 656,127,246 less
    the four expert layers' 64 correction biases, which the program keeps
    in the layers' state (not trained) and the issue counted."""
    cfg = _published()
    for key, width in (("hidden_size", 3584), ("intermediate_size", 9216),
                       ("moe_intermediate_size", 1024),
                       ("q_lora_rank", 768), ("kv_lora_rank", 512),
                       ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
                       ("v_head_dim", 128), ("num_experts_per_tok", 4),
                       ("router_experts", 64), ("hc_mult", 4),
                       ("hc_sinkhorn_iters", 20)):
        assert cfg[key] == width and key not in cfg["reduced"], key
    assert cfg["rope_scaling"]["factor"] == 64
    assert set(cfg["reduced"]) == set(cfg["published"]) - {"note"}
    for key in cfg["reduced"]:
        assert cfg[key] != cfg["published"][key], key
    count = lambda tree: sum(int(np.prod(s)) for s in jax.tree_util.
                             tree_leaves(tree, is_leaf=lambda s: isinstance(
                                 s, tuple)))
    shapes = REF.param_shapes(cfg)
    assert count(shapes) == REF.parameters(cfg) == cfg["parameters"] \
        == 656_127_246 - 4 * 64
    mappings = 2 * (4 * 3584 * 24 + 24 + 3)
    assert mappings == 2 * 344_091
    assert count(shapes["layer0"]["attn"]) == 7_767_296
    assert count(shapes["layer0"]) == 107_553_078 \
        == 7_767_296 + 3 * 3584 * 9216 + 2 * 3584 + mappings
    assert count(shapes["layer1"]) == 107_782_518 - 64 \
        == 7_767_296 + 3584 * 64 + 9 * 3 * 3584 * 1024 + 2 * 3584 + mappings
    assert count([shapes["embed"], shapes["norm"], shapes["head"]]) \
        == 117_444_096
    net, _ = FAMILY.reader()
    assert net.num_params() == count(REF.param_shapes(CFG)) \
        == REF.parameters(CFG)
    # the router's correction is state, one a router output, and not trained
    assert net.state["layer1"]["ffn"]["route_bias"].shape == (16,)


@pytest.mark.parametrize("how", [{"scan_steps": 2}, {"scan_steps": 1}])
def test_two_adamw_steps_through_fit_match_the_reference(how):
    """The cut model, two optimizer steps through `fit()` (scan-of-2 and
    per-call alike) against the reference's `train_steps`: the score,
    AdamW's first moment by stage, and the update, as the benchmark's
    `correct` compares them; ``phi`` is decayed as a matrix, ``bias`` and
    ``alpha`` are not."""
    prog, ref = lm.two_adamw_steps_match(FAMILY, how)
    for leaf in ("['layer1']['hc_ffn']['phi']",
                 "['layer1']['hc_ffn']['bias']",
                 "['layer2']['hc_attn']['alpha']"):
        assert abs(prog["update"][leaf] - ref["update"][leaf]) \
            < 1e-3 * ref["update"][leaf], leaf


def test_logits_loss_and_every_gradient_leaf_match_the_reference():
    lm.logits_match(FAMILY)
    state, _ = lm.every_gradient_leaf_matches(FAMILY)
    # the gauges of the step, a layer: the Sinkhorn steps' residue and how
    # evenly the sub-layers read the streams
    for i in range(3):
        gauges = state[f"layer{i}"]["mhc"]
        assert 0.0 < float(gauges["res_gap"]) < 0.2
        assert 0.5 < float(gauges["pre_entropy"]) < np.log(4)


def test_bfloat16_compute_stays_near_the_reference():
    """bf16 operands over float32 weights, as the cell runs: the score to
    half a percent of the float32 reference's, every stage's gradient
    norm to 3 %."""
    lm.bfloat16_stays_near(FAMILY)


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_a_planted_fault_moves_what_correct_compares(fault):
    """The nine faults the limits have to catch, at the test's sizes: half
    a batch, static mappings only, no Sinkhorn step (the exponential
    alone), one step, rows before columns, H_post without its 2, the
    first stream alone at the way out, no YaRN temperature, the plain
    frequencies. Each moves the score or a stage's first moment far more
    than float32 rounding."""
    assert REF.FAULTS == (
        "half_batch", "static_mappings", "no_sinkhorn", "one_iteration",
        "rows_first", "post_without_2", "out_first_stream",
        "no_yarn_temperature", "plain_frequencies")
    lm.a_planted_fault_moves(FAMILY, fault)


def test_the_streams_averaged_at_the_way_out_is_no_fault_anything_sees():
    """The issue's eighth fault, the streams averaged and not summed before
    the final norm, cannot fail a limit: RMSNorm divides the factor of 4
    out again (to ``rms_norm_eps``), so loss and gradient are the sound
    ones. The way out's planted fault is the first stream alone."""
    (ids, _, _), params, want, _, grads = lm.reference_gradient(FAMILY, 2)
    loss, g = jax.jit(jax.value_and_grad(lambda p: REF.loss_fn(
        CFG, p, ids, fault="streams_averaged")))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5 * float(
            np.abs(np.asarray(b)).max()))


def test_the_reference_takes_a_batch_one_sequence_at_a_time():
    """`train_steps` means the sequences' gradients; the batch's own
    gradient (one program over both sequences) gives the same step."""
    rows = _rows(5, 1)
    losses, m, _ = REF.train_steps(CFG, REF.make_params(CFG), rows)
    ids = REF.decode_tokens(CFG, rows[0][0])
    loss, g = jax.jit(jax.value_and_grad(
        lambda p: REF.loss_fn(CFG, p, ids)))(REF.make_params(CFG))
    np.testing.assert_allclose(losses[0], loss, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(m),
                    jax.tree_util.tree_leaves(g)):
        np.testing.assert_allclose(
            a, 0.1 * np.asarray(b), rtol=2e-4,
            atol=2e-6 * float(np.abs(np.asarray(b)).max()))


def test_checkpoint_round_trip_keeps_the_streams_and_the_gauges(tmp_path):
    from deeplearning4j_tpu.util.serialization import load_model, save_model
    net, cfg = FAMILY.net()
    ids, nxt, keep = FAMILY.example(cfg, _rows(7, 1)[0][0])
    net.fit([MultiDataSet((ids,), (nxt,), None, (keep,))] * 2, scan_steps=2)
    path = os.path.join(tmp_path, "xing.zip")
    save_model(net, path)
    back = load_model(path)
    assert back.conf.to_json() == net.conf.to_json()
    block = back.conf.vertices["layer1"].vertex
    assert (block.n_streams, block.sinkhorn_iters,
            tuple(block.res_clamp)) == (4, 20, (-30, 30))
    assert (block.attn.rope_scaling, block.attn.rope_factor,
            block.attn.rope_original_max_position) == ("yarn", 8, 32)
    assert tuple(block.ffn.experts_held) == (2, 6)
    assert back.conf.vertices["streams"].vertex.n_streams == 4
    for a, b in zip(jax.tree_util.tree_leaves(back.params),
                    jax.tree_util.tree_leaves(net.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(back.output(ids)),
                                  np.asarray(net.output(ids)))
    assert int(np.asarray(net.state["layer1"]["ffn"][
        "tokens_routed_total"]).sum()) == 2 * 2 * T * 2
    for a, b in zip(jax.tree_util.tree_leaves(back.state),
                    jax.tree_util.tree_leaves(net.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------- counters, ledger
def test_the_adapter_reads_the_counters_the_gauges_and_the_steps_scopes():
    """The expert counters through `ExpertLoadListener` (the block's state
    keeps its expert layer's under ``ffn``), the two gauges of every block,
    every part of the step under its scope."""
    from deeplearning4j_tpu import monitor
    with lm.fitted_under_the_ledger(FAMILY) as net:
        dump = monitor.dump()
        layers = {"layer1", "layer2"}
        rows = SYSTEM.expert_rows_per_step()
        assert set(rows) >= layers and SYSTEM.expert_load_max_over_mean()
        assert 1.0 <= SYSTEM.expert_rows_walked_over_live() <= 4.0
        tiers = {s["labels"]["tier"]
                 for s in dump["moe_dispatch_tier_total"]["series"]
                 if s["labels"]["layer"] in layers}
        assert tiers == {"1/2", "1/1"}
        gaps = {s["labels"]["layer"]: s["value"]
                for s in dump["mhc_res_gap"]["series"]}
        assert set(gaps) >= {"layer0", "layer1", "layer2"}
        assert SYSTEM.mhc_res_gap() == max(gaps.values()) > 0.0
        for layer, gap in gaps.items():
            np.testing.assert_allclose(
                gap, float(net.state[layer]["mhc"]["res_gap"]), rtol=1e-6)
        entropy = {s["labels"]["layer"]: s["value"]
                   for s in dump["mhc_pre_entropy"]["series"]}
        assert all(0.0 < v <= np.log(4) + 1e-6 for v in entropy.values())
        # forward, made again and backward all name their part
        scopes = SYSTEM.op_scopes()
        for part in ("mhc/pre", "mhc/sinkhorn", "mhc/post"):
            assert any(part in s and "transpose" in s
                       for s in scopes.values()), part


def test_the_counts_the_readers_need_come_from_the_configuration():
    """`train_flops_per_example` and the least times and bytes, reckoned
    from the published sizes and never from what the program ran."""
    from benchmark.lib.manifest import load_module
    cfg = _published()
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    flops = REF.train_flops_per_example(cfg)
    assert 14.0e12 < flops < 15.0e12
    # a sub-layer's mappings: the 24-column product and 24 C of sums
    assert REF._mapping_macs(cfg) == 14336 * 24 + 24 * 3584 == 430_080
    attn = REF.mla_attn_min_seconds(cfg, peaks, 1)
    assert attn["least_s"] == attn["flops_s"] > attn["bytes_s"]
    np.testing.assert_allclose(
        attn["flops_s"],
        5 * 3 * 2 * (8192 * 8193 / 2) * 4 * (192 + 128) / 197e12)
    experts = REF.experts_min_seconds(cfg, peaks, 512.0)
    assert experts["least_s"] == experts["bytes_s"]     # 512 rows: by bytes
    reader = load_module("metrics", "mhc_mix_roofline")
    least = reader.least_bytes(cfg, 1)
    # 5 layers x 8,192 tokens x (2 x 14 + 19 + 2 x 27) C x 2 bytes, and phi
    assert least["streams"] == 5 * 8192 * 101 * 3584 * 2
    assert least["phi"] == 10 * 4 * 2 * 14336 * 24
    assert least["bytes"] == least["streams"] + least["phi"]
    assert 29.6e9 < least["bytes"] < 29.8e9
    assert reader.least_bytes(cfg, 2)["streams"] == 2 * least["streams"]
