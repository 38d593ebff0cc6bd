"""The GLM-4.7-Flash family's zoo model through `ComputationGraph.fit()`
against its reference; the shared leaves, the weighted second output, the
scopes and counters, checkpoints; see `_glm_common.py`."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import MultiDataSet
from deeplearning4j_tpu.models import Glm4MoeLiteLM
from deeplearning4j_tpu.nn.graph import ComputationGraph

import _lm_common as lm
from _glm_common import CFG, FAMILY, REF, SYSTEM, T
from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _rows,
)


def _score(net, params, *example):
    return FAMILY.score(net, params, example)


# --------------------------------------------- the whole model through fit()
@pytest.mark.parametrize("how", [{"scan_steps": 2}, {"scan_steps": 1}])
def test_two_adamw_steps_through_fit_match_the_reference(how):
    """The cut model with its MTP module, two optimizer steps through
    `fit()` (scan-of-2 and per-call alike) against the reference's
    `train_steps`: the score (L_main + 0.3 L_mtp), AdamW's first moment by
    stage, and the update, as the benchmark's `correct` compares them; the
    shared embedding and head are updated as ONE leaf each, decayed once."""
    prog, ref = lm.two_adamw_steps_match(FAMILY, how)
    # leaf by leaf, the shared ones among them: decayed once, moved once
    for leaf in ("['embed']['W']", "['head']['W']"):
        assert abs(prog["update"][leaf] - ref["update"][leaf]) \
            < 1e-4 * ref["update"][leaf]


def test_loss_and_every_gradient_leaf_match_the_reference():
    lm.every_gradient_leaf_matches(FAMILY)
    # both parts, each against the reference's own
    net, cfg = FAMILY.reader()
    (ids, labels, keep), params, *_ = lm.reference_gradient(FAMILY, 2)
    parts = net._score_parts(params, net.state, (ids,), labels, None, keep,
                             True, jax.random.PRNGKey(0))[2]
    np.testing.assert_allclose(parts, REF.losses(cfg, params, ids),
                               rtol=2e-6)


def test_bfloat16_compute_stays_near_the_reference():
    """bf16 operands over float32 weights, as the cell runs: the score to
    half a percent of the float32 reference's, every stage's gradient
    norm to 3 %."""
    lm.bfloat16_stays_near(FAMILY)


def test_without_the_module_the_zoo_model_is_the_plain_trunk():
    """``num_nextn_predict_layers`` 0: one output, the trunk's leaves
    alone, and its loss is the reference's L_main of the same trunk."""
    net, cfg = FAMILY.net(num_nextn_predict_layers=0)
    assert net.conf.network_outputs == ("head",)
    assert not any(k.startswith("mtp_") for k in net.conf.vertices)
    ids, labels, keep = FAMILY.example(cfg, _rows(4, 1)[0][0])
    assert len(labels) == 1
    full = REF.make_params(CFG)
    assert set(net.params) == {k for k in full if not k.startswith("mtp_")}
    for a, b in zip(jax.tree_util.tree_leaves(net.params),
                    jax.tree_util.tree_leaves(
                        {k: full[k] for k in net.params})):
        np.testing.assert_array_equal(a, b)     # same seeded trunk
    main, mtp = REF.losses(CFG, full, ids)
    np.testing.assert_allclose(_score(net, net.params, ids, labels, keep),
                               main, rtol=2e-6)
    assert float(mtp) > 1.0
    np.testing.assert_allclose(REF.loss_fn(cfg, net.params, ids), main,
                               rtol=1e-7)


def test_planted_faults_move_what_correct_compares():
    """The four faults the limits have to catch, at the test's sizes: half
    a batch, attention without the rotation, the MTP branch fed the
    token the trunk saw, a router without the renormalisation. Each moves
    the score far more than float32 rounding."""
    assert REF.FAULTS == ("half_batch", "no_rope", "mtp_unshifted",
                          "no_renorm")
    for fault in REF.FAULTS:
        lm.a_planted_fault_moves(FAMILY, fault)


# ------------------------------------------------------------ the shared leaf
def _untied(net):
    """The same graph with the sharers holding copies of their owners'
    parameters: two untied uses of each matrix."""
    conf = net.conf
    twin = ComputationGraph(dataclasses.replace(conf, vertices={
        name: dataclasses.replace(vd, params_of=None)
        for name, vd in conf.vertices.items()})).init()
    twin.params = {**net.params, "mtp_embed": net.params["embed"],
                   "mtp_head": net.params["head"]}
    return twin


def test_a_shared_leaf_is_one_leaf_and_its_gradient_the_sum_of_both_uses():
    net, cfg = FAMILY.net()
    ids, labels, keep = FAMILY.example(cfg, _rows(4, 1)[0][0])
    shares = {name: vd.params_of for name, vd in net.conf.vertices.items()
              if vd.params_of}
    assert shares == {"mtp_embed": "embed", "mtp_head": "head"}
    # one entry in the params, in AdamW's moments and in the count
    assert not set(shares) & set(net.params)
    mu = SYSTEM.momentum(net)
    assert jax.tree_util.tree_structure(mu) \
        == jax.tree_util.tree_structure(net.params)
    assert net.num_params() == sum(
        int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            REF.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple)))
    assert set(net.state) >= set(shares)          # a sharer keeps its state
    report = {r.name: r for r in net.memory_report(
        2, with_compiled=False).layers}
    assert report["mtp_head"].params_bytes == 0 \
        and report["mtp_embed"].updater_state_bytes == 0
    assert sum(r.params_bytes for r in report.values()) \
        == 4 * net.num_params()
    assert "mtp_head" in net.summary()
    twin = _untied(net)
    assert twin.num_params() == net.num_params() \
        + net.params["embed"]["W"].size + net.params["head"]["W"].size
    tied = jax.jit(jax.grad(
        lambda p: _score(net, p, ids, labels, keep)))(net.params)
    loose = jax.jit(jax.grad(
        lambda p: _score(twin, p, ids, labels, keep)))(twin.params)
    for owner, sharer in (("embed", "mtp_embed"), ("head", "mtp_head")):
        both = loose[owner]["W"] + loose[sharer]["W"]
        assert float(jnp.abs(loose[sharer]["W"]).max()) > 1e-6
        np.testing.assert_allclose(tied[owner]["W"], both, rtol=1e-5,
                                   atol=1e-7 * float(jnp.abs(both).max()))
    for name in ("layer1", "mtp_block", "mtp_proj"):
        for a, b in zip(jax.tree_util.tree_leaves(tied[name]),
                        jax.tree_util.tree_leaves(loose[name])):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-9)


def test_a_sharer_must_name_a_layer_of_its_own_shapes():
    from deeplearning4j_tpu.nn.conf.base import InputType
    from deeplearning4j_tpu.nn.conf.network import GraphBuilder
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingSequenceLayer, RnnOutputLayer,
    )
    g = GraphBuilder().add_inputs("ids").set_input_types(
        InputType.recurrent(1, 8))
    g.add_layer("a", EmbeddingSequenceLayer(n_in=12, n_out=16), "ids")
    g.add_layer("b", EmbeddingSequenceLayer(n_in=13, n_out=16), "ids",
                params_of="a")
    g.add_layer("out", RnnOutputLayer(n_out=12, activation="softmax",
                                      loss="sparse_mcxent"), "b")
    g.set_outputs("out")
    with pytest.raises(ValueError, match="shapes differ"):
        ComputationGraph(g.build()).init()
    g.add_layer("b", EmbeddingSequenceLayer(n_in=12, n_out=16), "ids",
                params_of="nowhere")
    with pytest.raises(ValueError, match="not a layer vertex"):
        g.build()
    with pytest.raises(ValueError, match="output weights"):
        g.add_layer("b", EmbeddingSequenceLayer(n_in=12, n_out=16), "ids",
                    params_of="a").set_output_weights(1.0, 2.0).build()


def test_a_graph_without_sharing_keeps_its_configuration_as_it_was():
    """No new key in a saved configuration that uses none of it, so a
    model saved before loads and compares equal."""
    from deeplearning4j_tpu.models import ResNet50
    d = ResNet50(num_classes=10, input_shape=(32, 32, 3)).conf().to_dict()
    assert "output_weights" not in d
    assert all(set(v) == {"vertex", "inputs"}
               for v in d["vertices"].values())


def test_checkpoint_round_trip_saves_a_shared_leaf_once(tmp_path):
    from deeplearning4j_tpu.util.serialization import load_model, save_model
    net, cfg = FAMILY.net()
    ids, labels, keep = FAMILY.example(cfg, _rows(7, 1)[0][0])
    net.fit([MultiDataSet((ids,), labels, None, keep)] * 2, scan_steps=2)
    path = os.path.join(tmp_path, "glm.zip")
    save_model(net, path)
    back = load_model(path)
    assert back.conf.to_json() == net.conf.to_json()
    assert back.conf.output_weights == (1.0, 0.3)
    assert back.conf.vertices["mtp_head"].params_of == "head"
    assert back.conf.vertices["mtp_block"].scope == "mtp"
    assert set(back.params) == set(net.params)
    assert back.num_params() == net.num_params()
    for a, b in zip(jax.tree_util.tree_leaves(back.params),
                    jax.tree_util.tree_leaves(net.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(back.output(ids), net.output(ids)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the second head reads the first one's matrix after the reload too
    np.testing.assert_allclose(
        _score(back, back.params, ids, labels, keep),
        _score(net, net.params, ids, labels, keep), rtol=1e-6)


# ------------------------------------------------- targets at a sequence's end
def test_mtp_targets_and_masks_at_a_sequences_end():
    ids = np.arange(2 * 6).reshape(2, 6) + 1
    (nxt, nxt2), (keep, keep2) = Glm4MoeLiteLM.mtp_targets(ids)
    np.testing.assert_array_equal(nxt[0, :5], [2, 3, 4, 5, 6])
    np.testing.assert_array_equal(nxt2[0, :4], [3, 4, 5, 6])
    np.testing.assert_array_equal(keep[0], [1, 1, 1, 1, 1, 0])
    np.testing.assert_array_equal(keep2[0], [1, 1, 1, 1, 0, 0])
    for a, b in zip(jax.tree_util.tree_leaves(REF.targets(ids)),
                    jax.tree_util.tree_leaves(((nxt, nxt2), (keep, keep2)))):
        np.testing.assert_array_equal(a, b)
    # the branch embeds the token AFTER the one the trunk saw, zeros
    # where the sequence has run out
    from deeplearning4j_tpu.nn.conf.graph_vertices import (
        ShiftTimeSeriesVertex,
    )
    np.testing.assert_array_equal(
        ShiftTimeSeriesVertex(steps=1).apply(jnp.asarray(ids))[0],
        [2, 3, 4, 5, 6, 0])


def test_positions_without_a_target_reach_neither_loss():
    """What stands at the masked positions (the labels there, and the
    token a sequence's last position would have embedded) moves neither
    part of the score; a token that IS a target moves both."""
    net, cfg = FAMILY.net()
    ids, labels, keep = FAMILY.example(cfg, _rows(9, 1)[0][0])
    parts = lambda ids, labels: np.asarray(net._score_parts(
        net.params, net.state, (ids,), labels, None, keep, True,
        jax.random.PRNGKey(0))[2])
    base = parts(ids, labels)
    junk = tuple(np.where(k > 0, lab, 7) for lab, k in zip(labels, keep))
    np.testing.assert_array_equal(parts(ids, junk), base)
    moved = ids.copy()
    moved[:, -1] = (moved[:, -1] + 1) % cfg["vocab_size"]
    new_labels = FAMILY.example(cfg, _rows(9, 1)[0][0])[1]
    new_labels = tuple(np.where(
        np.roll(np.arange(T) == T - 1, -(i + 1))[None, :],
        np.roll(moved, -(i + 1), axis=1), lab)
        for i, lab in enumerate(new_labels))
    got = parts(moved, new_labels)
    assert abs(got[0] - base[0]) > 1e-6 and abs(got[1] - base[1]) > 1e-6
    # with the reference told the same ids, both parts agree again
    np.testing.assert_allclose(got, REF.losses(cfg, net.params, moved),
                               rtol=2e-6)


# ---------------------------------------------------------- counters, ledger
def test_the_adapter_reads_the_counters_the_losses_and_the_steps_scopes():
    from deeplearning4j_tpu import monitor
    with lm.fitted_under_the_ledger(FAMILY) as net:
        dump = monitor.dump()
        layers = {"layer1", "layer2", "mtp_block"}
        load = dump["moe_expert_load_max_over_mean"]["series"]
        assert {s["labels"]["layer"] for s in load} >= layers
        rows = SYSTEM.expert_rows_per_step()
        assert set(rows) >= layers and SYSTEM.expert_load_max_over_mean()
        tiers = {(s["labels"]["layer"], s["labels"]["tier"])
                 for s in dump["moe_dispatch_tier_total"]["series"]}
        assert tiers >= {(layer, tier) for layer in layers
                         for tier in ("1/2", "1/1")}
        # 4 held of 16: the small tier is half a dispatch's pairs, and
        # what was walked lies between the live pairs and all of them
        over = SYSTEM.expert_rows_walked_over_live()
        assert 1.0 <= over <= 4.0
        # the two losses a user of the module watches, from the one fetch
        parts = {s["labels"]["output"]: s["value"]
                 for s in dump["train_output_loss"]["series"]}
        assert set(parts) == {"head", "mtp_head"}
        np.testing.assert_allclose(
            parts["head"] + CFG["mtp_loss_weight"] * parts["mtp_head"],
            net.score(), rtol=1e-6)
        # the module's inner ops keep their inner scopes under its own,
        # forward and backward; the trunk's carry no "mtp"
        scopes = SYSTEM.op_scopes()
        inner = {m for m in FAMILY.scopes[:-1] for s in scopes.values()
                 if "mtp" in s and m in s}
        assert inner >= {"mla/attn", "mla/rope", "moe/experts", "head/loss"}
        assert any("transpose" in s and "mtp" in s for s in scopes.values())
        assert any("mla/attn" in s and "mtp" not in s
                   for s in scopes.values())


def test_the_per_call_paths_report_both_losses_too():
    from deeplearning4j_tpu import monitor
    net, cfg = FAMILY.net()
    ids, labels, keep = FAMILY.example(cfg, _rows(3, 1)[0][0])
    mds = MultiDataSet((ids,), labels, None, keep)
    want = np.asarray(net._score_parts(
        net.params, net.state, (ids,), labels, None, keep, True,
        jax.random.PRNGKey(0))[2])
    for how in ({"scan_steps": 1}, {"accumulate_steps": 2}):
        net, _ = FAMILY.net()
        net.fit([mds, mds], **how)
        parts = {s["labels"]["output"]: s["value"] for s in
                 monitor.dump()["train_output_loss"]["series"]}
        assert np.isfinite(net.score())
        if "accumulate_steps" in how:       # the mean of two equal batches
            np.testing.assert_allclose(
                [parts["head"], parts["mtp_head"]], want, rtol=1e-5)


@pytest.mark.parametrize("container", ["graph", "multilayer"])
def test_a_dropped_network_frees_its_parameters_at_once(container):
    """The compiled steps live in `net._steps` and trace through the net;
    they hold it weakly, so dropping the last reference frees the
    parameters (on a chip: their device memory, which the benchmark's
    reference needs next) without waiting for the cycle collector."""
    import gc
    import weakref
    from deeplearning4j_tpu.nn.conf.base import InputType
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    b = NeuralNetConfiguration.Builder().seed(1)
    out = OutputLayer(n_out=3, activation="softmax", loss="mcxent")
    if container == "graph":
        g = b.graph_builder().add_inputs("x").set_input_types(
            InputType.feed_forward(4))
        g.add_layer("d", DenseLayer(n_out=5), "x").add_layer("o", out, "d")
        net = ComputationGraph(g.set_outputs("o").build()).init()
    else:
        net = MultiLayerNetwork(b.list().layer(DenseLayer(n_out=5)).layer(
            out).set_input_type(InputType.feed_forward(4)).build()).init()
    x = np.random.default_rng(0).normal(size=(8, 4)).astype("float32")
    y = np.eye(3, dtype="float32")[np.arange(8) % 3]
    net.fit((x, y), scan_steps=2, epochs=2)
    net.fit((x, y), scan_steps=1)
    gc.collect()
    gc.disable()
    try:
        alive = weakref.ref(net)
        del net
        assert alive() is None
    finally:
        gc.enable()
