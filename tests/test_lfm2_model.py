"""The LFM2 mixture-of-experts family's zoo model through
`ComputationGraph.fit()` against its reference; the tied leaf, the scopes
and counters, checkpoints; see `_lfm2_common.py`."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import MultiDataSet
from deeplearning4j_tpu.models import Lfm2MoeLM

import _lm_common as lm
from _lfm2_common import CFG, FAMILY, KINDS, REF, SYSTEM, T
from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _rows,
)


def _score(net, params, *example):
    return FAMILY.score(net, params, example)


# --------------------------------------------- the whole model through fit()
def test_the_cut_is_published_layers_one_to_five():
    """conv + dense, attention + experts, three times conv + experts: the
    ``num_hidden_layers`` entries of the published ``layer_types`` from
    ``first_layer`` on, the leading dense layer once."""
    assert KINDS == [("conv", "dense"), ("full_attention", "experts"),
                     ("conv", "experts"), ("conv", "experts"),
                     ("conv", "experts")]
    net, _ = FAMILY.net()
    assert net.conf.network_outputs == ("head",)
    assert net.conf.vertices["head"].params_of == "embed"
    assert "head" not in net.params
    assert set(net.params) == {"embed", "norm"} | {f"layer{i}"
                                                   for i in range(5)}
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeLM(layer_types=("conv", "window")).conf()


@pytest.mark.parametrize("how", [{"scan_steps": 2}, {"scan_steps": 1}])
def test_two_adamw_steps_through_fit_match_the_reference(how):
    """The cut model, two optimizer steps through `fit()` (scan-of-2 and
    per-call alike) against the reference's `train_steps`: the score,
    AdamW's first moment by stage, and the update, as the benchmark's
    `correct` compares them; the tied matrix is updated as ONE leaf,
    decayed once."""
    prog, ref = lm.two_adamw_steps_match(FAMILY, how)
    leaf = "['embed']['W']"
    assert REF.stage_of(CFG, leaf) == "head"
    assert abs(prog["update"][leaf] - ref["update"][leaf]) \
        < 1e-4 * ref["update"][leaf]


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
    """The six faults the limits have to catch, at the test's sizes: half
    a batch, query head h on key head h % 2, the taps reversed, no q/k
    norm, no rotation, a router without the renormalisation. Each moves
    the score far more than float32 rounding."""
    assert REF.FAULTS == ("half_batch", "kv_head_mod", "taps_reversed",
                          "no_qk_norm", "no_rope", "no_renorm")
    lm.a_planted_fault_moves(FAMILY, fault)


def test_the_reference_takes_a_batch_one_sequence_at_a_time():
    """`train_steps` means the sequences' gradients; the batch's own
    gradient (one program over both sequences) gives the same step."""
    rows = _rows(5, 1)
    losses, m, _ = REF.train_steps(CFG, REF.make_params(CFG), rows)
    ids = REF.decode_tokens(CFG, rows[0][0])
    params = REF.make_params(CFG)
    loss, g = jax.jit(jax.value_and_grad(
        lambda p: REF.loss_fn(CFG, p, ids)))(params)
    np.testing.assert_allclose(losses[0], loss, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(m),
                    jax.tree_util.tree_leaves(g)):
        np.testing.assert_allclose(
            a, 0.1 * np.asarray(b), rtol=2e-4,
            atol=2e-6 * float(np.abs(np.asarray(b)).max()))


# ------------------------------------------------------------ the tied leaf
def test_the_tied_matrix_is_one_leaf_with_the_sum_of_both_gradients():
    net, cfg = FAMILY.net()
    ids, nxt, keep = FAMILY.example(cfg, _rows(4, 1)[0][0])
    mu = SYSTEM.momentum(net)
    assert jax.tree_util.tree_structure(mu) \
        == jax.tree_util.tree_structure(net.params)
    assert net.num_params() == sum(
        int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            REF.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple)))
    report = {r.name: r for r in net.memory_report(
        2, with_compiled=False).layers}
    assert report["head"].params_bytes == 0
    g = jax.jit(jax.grad(lambda p: _score(net, p, ids, nxt, keep)))(
        net.params)
    # the embedding's use alone: the head reads a copy that gets no gradient
    frozen = jax.lax.stop_gradient(net.params["embed"]["W"])
    x = REF.hidden(cfg, net.params, ids).reshape(-1, 32)
    head_only = jax.grad(lambda e: REF._cross_entropy(
        x, e, jnp.asarray(nxt).reshape(-1), jnp.asarray(keep).reshape(-1),
        "highest"))(frozen)
    embed_only = g["embed"]["W"] - head_only
    assert float(jnp.abs(head_only).max()) > 1e-4
    used = np.zeros(cfg["vocab_size"], bool)
    used[np.asarray(ids).reshape(-1)] = True
    # rows no token of the batch embedded get the head's gradient alone
    np.testing.assert_allclose(np.asarray(embed_only)[~used], 0.0,
                               atol=2e-7)
    assert np.abs(np.asarray(embed_only)[used]).max() > 1e-5


def test_checkpoint_round_trip_keeps_the_tied_leaf_once(tmp_path):
    from deeplearning4j_tpu.util.serialization import load_model, save_model
    net, cfg = FAMILY.net()
    ids, nxt, keep = FAMILY.example(cfg, _rows(7, 1)[0][0])
    net.fit([MultiDataSet((ids,), (nxt,), None, (keep,))] * 2, scan_steps=2)
    path = os.path.join(tmp_path, "lfm2.zip")
    save_model(net, path)
    back = load_model(path)
    assert back.conf.to_json() == net.conf.to_json()
    assert back.conf.vertices["head"].params_of == "embed"
    assert back.conf.vertices["head"].vertex.tied_embedding
    attn = back.conf.vertices["layer1"].vertex.attn
    assert (attn.n_kv_heads, attn.qk_norm, attn.rope_base) == (2, True, 100.0)
    assert set(back.params) == set(net.params)
    for a, b in zip(jax.tree_util.tree_leaves(back.params),
                    jax.tree_util.tree_leaves(net.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(back.output(ids)),
                                  np.asarray(net.output(ids)))
    for a, b in zip(jax.tree_util.tree_leaves(back.state),
                    jax.tree_util.tree_leaves(net.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------- counters, ledger
def test_the_adapter_reads_the_counters_and_the_steps_scopes():
    from deeplearning4j_tpu import monitor
    before = {s["labels"]["layer"]: s["value"] for s in monitor.dump().get(
        "moe_tokens_with_held_pair_total", {}).get("series", [])}
    with lm.fitted_under_the_ledger(FAMILY) as net:
        dump = monitor.dump()
        layers = {"layer1", "layer2", "layer3", "layer4"}
        rows = SYSTEM.expert_rows_per_step()
        assert set(rows) >= layers and SYSTEM.expert_load_max_over_mean()
        assert 1.0 <= SYSTEM.expert_rows_walked_over_live() <= 4.0
        held = {s["labels"]["layer"]: s["value"] - before.get(
            s["labels"]["layer"], 0)
            for s in dump["moe_tokens_with_held_pair_total"]["series"]}
        assert set(held) >= layers
        tokens = 4 * 2 * T                    # four steps of 2 sequences
        for layer in layers:                  # 4 of 16 held, 2 a token
            assert 0.2 * tokens < held[layer] < 0.8 * tokens
            assert held[layer] == int(
                net.state[layer]["ffn"]["tokens_with_held_pair_total"])
        share = SYSTEM.tokens_with_held_pair_share()
        assert 0.2 < share < 0.8
        assert not any("moe/shared" in s
                       for s in SYSTEM.op_scopes().values())


def test_the_counts_the_readers_need_come_from_the_configuration():
    """`train_flops_per_example` and the three least times, reckoned
    from the published sizes and never from what the program ran."""
    import json
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "lfm2-24b-a2b.json")) as f:
        cfg = json.load(f)
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    step = 4 * REF.train_flops_per_example(cfg)
    assert 39.8e12 < step < 40.0e12
    shares = REF.flops_shares(cfg)
    assert abs(sum(shares.values()) - 1.0) < 1e-9
    assert shares["operator projections"] > shares["dense MLP"] \
        > shares["held experts"] > shares["head"]
    gqa = REF.gqa_attn_min_seconds(cfg, peaks, 4)
    assert gqa["least_s"] == gqa["flops_s"] > gqa["bytes_s"]
    # 4 sequences x 3 passes' worth x 2 FLOPs x T(T+1)/2 pairs x 32 heads
    # x (64 + 64)
    np.testing.assert_allclose(
        gqa["flops_s"], 4 * 3 * 2 * (8192 * 8193 / 2) * 32 * 128 / 197e12)
    conv = REF.shortconv_min_seconds(cfg, peaks, 4)
    assert conv["least_s"] == conv["bytes_s"] > conv["flops_s"]
    np.testing.assert_allclose(
        conv["bytes_s"], 4 * 4 * 8192 * 11 * 2048 * 2 / 819e9)
    experts = REF.experts_min_seconds(cfg, peaks, 2048.0)
    assert experts["least_s"] == experts["bytes_s"]   # 2,048 rows: by bytes
    assert REF.experts_min_seconds(cfg, peaks, 32768.0)["flops_s"] \
        > REF.experts_min_seconds(cfg, peaks, 32768.0)["bytes_s"]
