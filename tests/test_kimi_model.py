"""The hybrid LM through `fit()` against its reference, the adapter,
checkpoints and the engine's refusals; see `_kimi_common.py`."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterator import ExistingDataSetIterator
from deeplearning4j_tpu.models import TransformerLMMoE
from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import (
    MultiHeadAttention, MultiHeadLatentAttention, RMSNormLayer,
    RnnOutputLayer, TransformerBlock,
)

import _lm_common as lm
from _kimi_common import CFG, FAMILY, REF, SYSTEM, T
from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _rows,
)


# --------------------------------------------- the whole model through fit()
@pytest.mark.parametrize("how", [{"scan_steps": 2}, {"scan_steps": 1}])
def test_two_adamw_steps_through_fit_match_the_reference(how):
    """The cut model, two optimizer steps through `fit()` (scan-of-2 and
    per-call alike) against the reference's `train_steps`: the losses,
    AdamW's first moment by stage, and the norm of the update, as the
    benchmark's `correct` compares them."""
    lm.two_adamw_steps_match(FAMILY, how)


def test_weight_decay_is_on_matrices_only():
    from deeplearning4j_tpu.nn.updaters import AdamW
    import optax
    p = {"W": jnp.ones((3, 3)), "gamma": jnp.ones((3,))}
    g = jax.tree_util.tree_map(jnp.zeros_like, p)
    for only, want in ((True, 1.0), (False, 0.9)):
        tx = AdamW(1.0, weight_decay=0.1, decay_matrices_only=only).to_optax()
        up, _ = tx.update(g, tx.init(p), p)
        new = optax.apply_updates(p, up)
        np.testing.assert_allclose(new["W"], 0.9)
        np.testing.assert_allclose(new["gamma"], want)


def test_loss_and_every_gradient_leaf_match_the_reference():
    lm.every_gradient_leaf_matches(FAMILY)


def test_planted_faults_move_what_correct_compares():
    """The three faults the limits have to catch, at the test's sizes:
    half a batch, a KDA layer without its decay, a router without the
    renormalisation. Each moves the loss or a stage's first moment by
    far more than float32 rounding."""
    for fault in ("half_batch", "kda_no_decay", "router_no_renorm"):
        lm.a_planted_fault_moves(FAMILY, fault)


# -------------------------------------------------- what stays as it was
def test_defaults_keep_the_shapes_and_numbers_of_existing_models():
    old_style = MultiHeadAttention(n_out=32, n_heads=4, causal=True)
    p, _ = old_style.init(jax.random.PRNGKey(0), InputType.recurrent(32, 8))
    assert {k: v.shape for k, v in p.items()} == {
        k: (32, 32) for k in ("Wq", "Wk", "Wv", "Wo")}
    blk = TransformerBlock(n_out=32, n_heads=4)
    bp, state = blk.init(jax.random.PRNGKey(0), InputType.recurrent(32, 8))
    assert sorted(bp) == ["W1", "W2", "attn", "b1", "b2", "ln1", "ln2"] \
        and state == {}
    # same init keys: the block's attention is the layer's own init under
    # the block's second key, its MLP xavier under the third and fourth
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    np.testing.assert_array_equal(
        bp["attn"]["Wq"], old_style.init(ks[1], InputType.recurrent(32, 8))
        [0]["Wq"])
    from deeplearning4j_tpu.nn.initializers import get_initializer
    np.testing.assert_array_equal(
        bp["W1"], get_initializer("xavier")(ks[2], (32, 128), 32, 128,
                                            jnp.float32))
    # same numbers: LayerNorm, biased GELU MLP, rotary attention
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    y = blk.apply(bp, state, x)[0]
    ln = lambda a, q: (a - a.mean(-1, keepdims=True)) * jax.lax.rsqrt(
        a.var(-1, keepdims=True) + 1e-5) * q["gamma"] + q["beta"]
    a = x + dataclasses.replace(old_style).apply(
        bp["attn"], {}, ln(x, bp["ln1"]))[0]
    want = a + jax.nn.gelu(ln(a, bp["ln2"]) @ bp["W1"] + bp["b1"]) \
        @ bp["W2"] + bp["b2"]
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 16)) * 3 + 1
    layer = RMSNormLayer(epsilon=1e-5)
    p, _ = layer.init(jax.random.PRNGKey(0), InputType.recurrent(16, 5))
    p["gamma"] = jnp.linspace(0.5, 1.5, 16)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) \
        * p["gamma"]
    np.testing.assert_allclose(layer.apply(p, {}, x)[0], want, atol=1e-6)
    y16 = layer.apply(p, {}, x.astype(jnp.bfloat16))[0]
    assert y16.dtype == jnp.bfloat16
    np.testing.assert_allclose(y16.astype(jnp.float32), want, atol=3e-2)


@pytest.mark.parametrize("fits", [7, 16, 64])
def test_blocked_loss_is_the_whole_loss(fits, monkeypatch):
    """30 positions where the logits of ``fits`` fit the budget: blocks of
    4 and of 16 positions (the last one padded), and all at once."""
    from deeplearning4j_tpu.nn.layers import recurrent
    from deeplearning4j_tpu.nn.losses import sparse_mcxent
    monkeypatch.setattr(recurrent, "_LOSS_LIVE_BYTES", fits * 50 * 8)
    head = RnnOutputLayer(n_out=50, loss="sparse_mcxent", has_bias=False)
    p, _ = head.init(jax.random.PRNGKey(0), InputType.recurrent(12, 10))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 10, 12))
    y = jax.random.randint(jax.random.PRNGKey(2), (3, 10), 0, 50)
    mask = jnp.ones((3, 10)).at[:, -1].set(0.0)
    whole = lambda p, x: sparse_mcxent(y, x @ p["W"], mask=mask)
    blocked = lambda p, x: head.score(p, x, y, mask=mask)
    assert ("scan" in str(jax.make_jaxpr(blocked)(p, x))) == (fits < 30)
    np.testing.assert_allclose(blocked(p, x), whole(p, x), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(jax.grad(blocked, (0, 1))(p, x)),
                    jax.tree_util.tree_leaves(jax.grad(whole, (0, 1))(p, x))):
        np.testing.assert_allclose(a, b, atol=1e-6)


# ------------------------------------------------------------------- the feed
def test_byte_to_token_decode_and_its_zipf_table():
    cfg = {**CFG, "vocab_size": 20480}
    table = REF.zipf_table(20480, 1.0)
    assert table.shape == (65536,) and table.min() == 0 \
        and table.max() <= 20479 and np.all(np.diff(table) >= 0)
    # Zipf(1.0): id 0 has 1/H(20480) of the mass, id 1 half of it
    share = np.bincount(table, minlength=4)[:4] / 65536.0
    harmonic = np.sum(1.0 / np.arange(1, 20481))
    np.testing.assert_allclose(share, 1 / harmonic / np.arange(1, 5),
                               atol=2e-5)
    rows = _rows(4, 1)[0][0]
    ids = REF.decode_tokens(cfg, rows)
    assert ids.shape == (2, T) and ids.dtype == np.int32
    flat = rows.reshape(2, -1).astype(np.int64)
    u = flat[:, 0::2] + 256 * flat[:, 1::2]
    np.testing.assert_array_equal(ids, table[u])
    # the adapter's feed yields what the reference decodes, next-token
    # targets, and no target for the last position
    SYSTEM._CFG.update(cfg)
    (ds,) = list(SYSTEM.feed([(rows, None)]))
    np.testing.assert_array_equal(np.asarray(ds.features), ids)
    np.testing.assert_array_equal(np.asarray(ds.labels)[:, :-1], ids[:, 1:])
    np.testing.assert_array_equal(np.asarray(ds.labels_mask)[:, -1], 0)
    assert np.asarray(ds.labels_mask)[:, :-1].all()


def test_all_weights_come_from_weights_seed_and_none_from_the_runs():
    a, b = REF.make_params(CFG, 1), REF.make_params(CFG, 2)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    other = REF.make_params({**CFG, "weights_seed": 4})
    assert not np.array_equal(np.asarray(a["0"]["W"]),
                              np.asarray(other["0"]["W"]))
    # unit-RMS embedding rows, depth-scaled output projections
    assert abs(float(jnp.std(a["0"]["W"])) - 1.0) < 0.05
    assert abs(float(jnp.std(a["2"]["attn"]["Wo"])) - 0.1) < 0.01
    assert abs(float(jnp.std(a["2"]["attn"]["Wq"])) - 0.2) < 0.02
    assert REF.stage_of(CFG, "['0']['W']") == "embed"
    assert REF.stage_of(CFG, "['3']['ffn']['Wr']") == "layer3"
    assert REF.stage_of(CFG, "['6']['gamma']") == "head"


# ---------------------------------------------------------- counters, ledger
def test_the_adapter_reads_the_counters_and_the_steps_scopes():
    from deeplearning4j_tpu import monitor
    with lm.fitted_under_the_ledger(FAMILY):
        load = monitor.dump()["moe_expert_load_max_over_mean"]["series"]
        assert {s["labels"]["layer"] for s in load} >= {"2", "3", "4", "5"}
        assert all(1.0 <= s["value"] <= 8.0 for s in load)
        rows = SYSTEM.expert_rows_per_step()
        assert set(rows) >= {"2", "3", "4", "5"} \
            and SYSTEM.expert_load_max_over_mean()


# ----------------------------------------------------- checkpoints, serving
def test_zoo_model_checkpoint_round_trip(tmp_path):
    from deeplearning4j_tpu.util.serialization import load_model, save_model
    net, cfg = FAMILY.net()
    ids, nxt, keep = FAMILY.example(cfg, _rows(7, 1)[0][0])
    net.fit(ExistingDataSetIterator([DataSet(ids, nxt, None, keep)] * 2),
            scan_steps=2)
    path = os.path.join(tmp_path, "lm.zip")
    save_model(net, path)
    back = load_model(path)
    assert back.conf.to_json() == net.conf.to_json()
    assert back.layers[2].ffn.experts_held == (2, 6)
    assert isinstance(back.layers[4].attn, MultiHeadLatentAttention)
    np.testing.assert_array_equal(np.asarray(back.output(ids)),
                                  np.asarray(net.output(ids)))
    for a, b in zip(jax.tree_util.tree_leaves(back.params),
                    jax.tree_util.tree_leaves(net.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("layers,what", [
    ((1,), "KimiDeltaAttention attention"),
    ((4,), "MultiHeadLatentAttention attention"),
    ((2,), "MoEFeedForward feed-forward holding experts (2, 6)"),
])
def test_decode_engine_refuses_the_block_by_name(layers, what):
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import EmbeddingSequenceLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving.decode import DecodeConfig, DecodeEngine
    from deeplearning4j_tpu.serving.registry import ModelLoadError
    whole, _ = FAMILY.reader()
    b = NeuralNetConfiguration.Builder().list()
    b.layer(EmbeddingSequenceLayer(n_out=32, n_in=96))
    for i in layers:
        b.layer(whole.layers[i])
    b.layer(RnnOutputLayer(n_out=96, activation="softmax", loss="mcxent"))
    b.set_input_type(InputType.recurrent(1, T))
    net = MultiLayerNetwork(b.build()).init()
    with pytest.raises(ModelLoadError) as e:
        DecodeEngine(net, DecodeConfig(slots=2, page_size=16,
                                       max_context=128))
    assert "layer 1 is a TransformerBlock" in str(e.value)
    assert what in str(e.value), str(e.value)


def test_moe_lm_family_trains():
    """`TransformerLMMoE` (top-2 softmax GELU experts after every second
    block) rides the same expert layer."""
    net = TransformerLMMoE(vocab_size=16, seq_length=16, n_layers=2,
                           n_embd=32, n_heads=4, learning_rate=1e-2).init()
    assert net.params["3"]["W1"].shape == (8, 32, 128)
    x = (np.arange(64).reshape(4, 16) % 16).astype("float32")
    y = np.eye(16, dtype="float32")[np.roll(x, -1, 1).astype(int)]
    first = None
    for _ in range(15):
        net.fit((x, y), epochs=1, batch_size=4)
        first = first or net.score()
    assert net.score() < first
