"""The Nemotron-H family's zoo model through `ComputationGraph.fit()`
against its reference; the cut, the scopes and counters, checkpoints; see
`_nemotron_common.py`."""
import json
import os

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import MultiDataSet
from deeplearning4j_tpu.models import NemotronHLM

import _lm_common as lm
from _nemotron_common import CFG, FAMILY, KINDS, REF, SYSTEM, T
from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _rows,
)

_CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs", "nemotron-3-super-120b-a12b.json")


def _published():
    with open(_CONFIG) as f:
        return json.load(f)


# --------------------------------------------- the whole model through fit()
def test_the_cut_is_published_layers_25_to_35():
    """One whole period of the published string, attention first, then
    experts and Mamba-2 five times in turn; every block ONE mixer behind a
    pre-norm, the head untied."""
    cfg = _published()
    assert len(cfg["hybrid_override_pattern"]) \
        == cfg["published"]["num_hidden_layers"] == 88
    assert "".join(REF.layer_kinds(cfg)) == "*EMEMEMEMEM"
    assert KINDS == list("*EMEME")
    net, _ = FAMILY.reader()
    assert net.conf.network_outputs == ("head",)
    assert set(net.params) == {"embed", "norm", "head"} | {
        f"layer{i}" for i in range(6)}
    for i, kind in enumerate(KINDS):
        block = net.conf.vertices[f"layer{i}"].vertex
        assert type(block).__name__ == "MixerBlock"
        assert type(block.mixer).__name__ == {
            "M": "Mamba2Mixer", "*": "MultiHeadAttention",
            "E": "MoEFeedForward"}[kind]
        assert set(net.params[f"layer{i}"]) == {"ln", "mixer"}
    attn = net.conf.vertices["layer0"].vertex.mixer
    assert not attn.use_rope and not attn.qk_norm and attn.causal
    with pytest.raises(ValueError, match="pattern"):
        NemotronHLM(pattern="M-E").conf()


def test_the_configuration_holds_the_published_widths_and_the_count():
    """Every width as published; what is cut is a count and is listed; the
    parameters held are the issue's 700,862,960, leaf by leaf."""
    cfg = _published()
    for key, width in (("hidden_size", 4096), ("mamba_head_dim", 64),
                       ("ssm_state_size", 128), ("head_dim", 128),
                       ("moe_intermediate_size", 2688),
                       ("moe_latent_size", 1024),
                       ("moe_shared_expert_intermediate_size", 5376),
                       ("conv_kernel", 4), ("chunk_size", 128),
                       ("num_experts_per_tok", 22), ("expand", 2),
                       ("router_experts", 512)):
        assert cfg[key] == width and key not in cfg["reduced"], key
    assert set(cfg["reduced"]) == set(cfg["published"]) - {"note"}
    for key in cfg["reduced"]:
        assert cfg[key] != cfg["published"][key], key
    count = lambda tree: sum(int(np.prod(s)) for s in jax.tree_util.
                             tree_leaves(tree, is_leaf=lambda s: isinstance(
                                 s, tuple)))
    shapes = REF.param_shapes(cfg)
    assert count(shapes) == cfg["parameters"] == 700_862_960
    assert count(shapes["layer2"]) == 13_708_592           # Mamba-2
    assert count(shapes["layer0"]) == 5_246_976            # attention
    assert count(shapes["layer1"]) == 54_530_048 + 8 * 5_505_024
    assert count([shapes["embed"], shapes["norm"], shapes["head"]]) \
        == 134_221_824
    net, _ = FAMILY.reader()
    assert net.num_params() == count(REF.param_shapes(CFG))


@pytest.mark.parametrize("how", [{"scan_steps": 2}, {"scan_steps": 1}])
def test_two_adamw_steps_through_fit_match_the_reference(how):
    """The cut model, two optimizer steps through `fit()` (scan-of-2 and
    per-call alike) against the reference's `train_steps`: the score,
    AdamW's first moment by stage, and the update, as the benchmark's
    `correct` compares them."""
    lm.two_adamw_steps_match(FAMILY, how)


def test_logits_loss_and_every_gradient_leaf_match_the_reference():
    lm.logits_match(FAMILY)
    lm.every_gradient_leaf_matches(FAMILY)


def test_bfloat16_compute_stays_near_the_reference():
    """bf16 operands over float32 weights, as the cell runs: the score to
    half a percent of the float32 reference's, every stage's gradient
    norm to 3 %."""
    lm.bfloat16_stays_near(FAMILY)


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_a_planted_fault_moves_what_correct_compares(fault):
    """The nine faults the limits have to catch, at the test's sizes: half
    a batch, no decay, a step size that does not scale the input, the norm
    over a head's channels, the gate behind the norm, experts on the
    stream's first channels instead of the latent, relu for relu^2, the
    scaling factor left out, query head h on key head h % 2. Each moves
    the score or a stage's first moment far more than float32 rounding."""
    assert REF.FAULTS == (
        "half_batch", "no_decay", "no_dt_input", "norm_over_head",
        "gate_after_norm", "no_latent_down", "relu_not_squared",
        "no_scaling", "kv_head_mod")
    lm.a_planted_fault_moves(FAMILY, fault)


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


def test_checkpoint_round_trip_keeps_the_mixers_and_the_counters(tmp_path):
    from deeplearning4j_tpu.util.serialization import load_model, save_model
    net, cfg = FAMILY.net()
    ids, nxt, keep = FAMILY.example(cfg, _rows(7, 1)[0][0])
    net.fit([MultiDataSet((ids,), (nxt,), None, (keep,))] * 2, scan_steps=2)
    path = os.path.join(tmp_path, "nemotron.zip")
    save_model(net, path)
    back = load_model(path)
    assert back.conf.to_json() == net.conf.to_json()
    mamba = back.conf.vertices["layer2"].vertex.mixer
    assert (mamba.n_heads, mamba.n_groups, mamba.state_dim, mamba.chunk) \
        == (4, 2, 16, 32)
    experts = back.conf.vertices["layer1"].vertex.mixer
    assert (experts.latent, experts.shared_hidden, experts.activation,
            experts.gated, tuple(experts.experts_held)) \
        == (16, 48, "relu2", False, (2, 6))
    for a, b in zip(jax.tree_util.tree_leaves(back.params),
                    jax.tree_util.tree_leaves(net.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(back.output(ids)),
                                  np.asarray(net.output(ids)))
    assert int(np.asarray(
        net.state["layer1"]["tokens_routed_total"]).sum()) == 2 * 2 * T * 4
    for a, b in zip(jax.tree_util.tree_leaves(back.state),
                    jax.tree_util.tree_leaves(net.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------- counters, ledger
def test_the_adapter_reads_the_counters_and_the_steps_scopes():
    """The expert counters at the router's width through
    `ExpertLoadListener` (a `MixerBlock`'s state is its mixer's), the row
    tiers walked, every part of the step under its scope, and the gauge
    that names the state-space scan's path."""
    from deeplearning4j_tpu import monitor
    with lm.fitted_under_the_ledger(FAMILY) as net:
        dump = monitor.dump()
        layers = {"layer1", "layer3", "layer5"}
        rows = SYSTEM.expert_rows_per_step()
        assert set(rows) >= layers and SYSTEM.expert_load_max_over_mean()
        assert 1.0 <= SYSTEM.expert_rows_walked_over_live() <= 4.0
        routed = {}
        for s in dump["moe_tokens_routed_total"]["series"]:
            if s["labels"]["layer"] in layers:
                routed[s["labels"]["layer"]] = routed.get(
                    s["labels"]["layer"], 0) + s["value"]
        for layer in layers:      # every (token, slot) pair is counted
            assert routed[layer] % (2 * T * 4) == 0 and routed[layer] > 0
            assert net.state[layer]["tokens_routed"].shape == (16,)
        tiers = {s["labels"]["tier"]
                 for s in dump["moe_dispatch_tier_total"]["series"]
                 if s["labels"]["layer"] in layers}
        assert tiers == {"1/2", "1/1"}
        assert [s["value"] for s in dump["ssd_scan_path"]["series"]] == [0]


def test_the_counts_the_readers_need_come_from_the_configuration():
    """`train_flops_per_example`, the shares of the cell's ``why`` and the
    three least times, reckoned from the published sizes and never from
    what the program ran."""
    cfg = _published()
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    step = 2 * REF.train_flops_per_example(cfg)
    assert 42.0e12 < step < 43.0e12       # x 4/3 with the forward made again
    shares = REF.flops_shares(cfg)
    assert abs(sum(shares.values()) - 1.0) < 1e-9
    pct = {k: round(100 * v) for k, v in shares.items()}
    assert pct["shared expert"] == 51 and pct["head"] == 16 \
        and pct["Mamba-2 projections"] == 16 \
        and pct["latent projections"] == 10, pct
    assert shares["state-space scan"] < 0.01 > shares["held experts"] / 3
    gqa = REF.gqa_attn_min_seconds(cfg, peaks, 2)
    assert gqa["least_s"] == gqa["flops_s"] > gqa["bytes_s"]
    np.testing.assert_allclose(
        gqa["flops_s"], 2 * 3 * 2 * (8192 * 8193 / 2) * 4 * 256 / 197e12)
    scan = REF.ssd_scan_min_seconds(cfg, peaks, 2)
    assert scan["least_s"] == scan["bytes_s"] > scan["flops_s"]
    # 5 layers x 2 sequences x 8,192 tokens x 3 passes' worth of x', B, C
    # (bf16), the step size and y (float32)
    np.testing.assert_allclose(
        scan["bytes_s"],
        5 * 2 * 8192 * 3 * ((1024 + 256) * 2 + 16 * 4 + 1024 * 4) / 819e9)
    experts = REF.experts_min_seconds(cfg, peaks, 704.0)
    assert experts["least_s"] == experts["bytes_s"]   # 704 rows: by bytes
    # TWO products an expert, 1024 x 2688 each
    np.testing.assert_allclose(
        experts["flops_s"], 2 * 3 * 2 * 704 * 1024 * 2688 / 197e12)
