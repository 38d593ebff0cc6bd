"""The Keye-VL-2.0 language model's zoo model through
`ComputationGraph.fit()` against its reference; the indexer's loss and how
it reaches the step, the counters, the shares of an expert layer; see
`_keye_common.py`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.models import KeyeVL2LM
from deeplearning4j_tpu.ops.dsa_attention import pairs_causal, pairs_selected

import _lm_common as lm
from _keye_common import CFG, FAMILY, REF, SYSTEM, T
from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _close, _rows,
)


# --------------------------------------------- the whole model through fit()
def test_the_model_is_the_language_model_alone():
    """Embedding, ``num_hidden_layers`` blocks that are all alike (sparse
    attention + experts), a final norm and an UNTIED head; the indexer's
    leaves sit under the attention's; no vision tower."""
    net, _ = FAMILY.net()
    assert net.conf.network_outputs == ("head",)
    assert set(net.params) == {"embed", "norm", "head"} | {
        f"layer{i}" for i in range(3)}
    attn = net.params["layer1"]["attn"]
    assert {k: v.shape for k, v in attn.items() if k != "indexer"} == {
        "Wq": (32, 64), "Wk": (32, 16), "Wv": (32, 16), "Wo": (64, 32),
        "q_norm": (8,), "k_norm": (8,)}
    assert {k: v.shape for k, v in attn["indexer"].items()} == {
        "Wq": (32, 32), "Wk": (32, 8), "Ww": (32, 4), "k_gamma": (8,),
        "k_beta": (8,)}
    assert net.params["head"]["W"].shape == (32, 96)
    assert "Wgate_s" not in net.params["layer0"]["ffn"]
    assert REF.stage_of(CFG, "['layer1']['attn']['indexer']['Ww']") \
        == "indexer"
    assert REF.stage_of(CFG, "['layer1']['attn']['Wq']") == "layer1"
    assert REF.stage_of(CFG, "['norm']['gamma']") == "head"
    assert "vision" not in " ".join(KeyeVL2LM().conf().vertices)


@pytest.mark.parametrize("how", [{"scan_steps": 2}, {"scan_steps": 1}])
def test_two_adamw_steps_through_fit_match_the_reference(how):
    """Two optimizer steps through `fit()` (scan-of-2 and per-call alike)
    against the reference's `train_steps`: the score (the cross-entropy
    alone), AdamW's first moment by stage, the indexers' among them, and
    the update, as the benchmark's `correct` compares them."""
    lm.two_adamw_steps_match(FAMILY, how)


def test_logits_losses_and_every_gradient_leaf_match_the_reference():
    """Float32 on both sides, so the selections agree: the logits, the
    cross-entropy, the indexer's loss of every layer (the layers' state),
    and EVERY leaf of the step's gradient element by element, which is
    that of CE + the sum of the layers' indexer losses while the score is
    CE."""
    lm.logits_match(FAMILY)
    state, want_kl = lm.every_gradient_leaf_matches(FAMILY)
    got_kl = [state[f"layer{i}"]["attn"]["indexer_kl"] for i in range(3)]
    np.testing.assert_allclose(got_kl, want_kl, rtol=2e-5)
    assert float(min(want_kl)) > 1e-3


def test_the_score_is_the_cross_entropy_and_the_gradient_has_both_losses():
    """`attach_auxiliary_loss`: with the indexers' losses weighed 0 the
    score is the same to the bit, the main weights' gradients too (they
    get nothing from that loss) and the indexers' gradients are exactly
    zero (they get nothing from the cross-entropy)."""
    net, cfg = FAMILY.net()
    off, _ = FAMILY.net(indexer_loss_coef=0.0)
    ids, nxt, keep = FAMILY.example(cfg, _rows(4, 1)[0][0][:1])
    params = REF.make_params(cfg)
    grad = lambda n: jax.jit(jax.value_and_grad(
        lambda p: FAMILY.score(n, p, (ids, nxt, keep))))(params)
    (l_on, g_on), (l_off, g_off) = grad(net), grad(off)
    assert float(l_on) == float(l_off)
    for i in range(3):
        a_on, a_off = (g[f"layer{i}"]["attn"] for g in (g_on, g_off))
        for name in ("Wq", "Wk", "Wv", "Wo", "q_norm", "k_norm"):
            np.testing.assert_array_equal(a_on[name], a_off[name])
        for name, leaf in a_off["indexer"].items():
            assert not np.any(np.asarray(leaf)), name
            assert np.any(np.asarray(a_on["indexer"][name])), name
    for name in ("embed", "head", "norm"):
        for a, b in zip(jax.tree_util.tree_leaves(g_on[name]),
                        jax.tree_util.tree_leaves(g_off[name])):
            np.testing.assert_array_equal(a, b)


def test_fit_reports_the_cross_entropy_and_publishes_the_counts():
    """`fit()`'s score is the reference's cross-entropy although the step
    trains the indexers too; `ExpertLoadListener` publishes the pairs the
    selections kept and chose among, exactly sum_t min(t + 1, topk) of
    T (T + 1) / 2 a sequence and layer, and the last indexer loss."""
    net, cfg = FAMILY.net()
    rows = FAMILY.rows(12, 2)
    stamps = SYSTEM.stamp_listener()
    net.set_listeners(stamps)
    before = SYSTEM.sparse_pairs() or (0.0, 0.0)
    p0 = jax.device_get(net.params["layer0"]["attn"]["indexer"]["Ww"])
    net.fit(SYSTEM.feed(rows), scan_steps=2)
    ce = [float(REF.losses(cfg, REF.make_params(cfg),
                           REF.decode_tokens(cfg, rows[0][0])[0])[0])]
    np.testing.assert_allclose(stamps.rows[0][1], ce[0], rtol=2e-6)
    assert np.abs(np.asarray(
        net.params["layer0"]["attn"]["indexer"]["Ww"]) - p0).max() > 1e-4
    kept, causal = SYSTEM.sparse_pairs()
    assert kept - before[0] == 3 * 2 * pairs_selected(T, 16) == 3 * 2 * 1928
    assert causal - before[1] == 3 * 2 * pairs_causal(T)
    assert pairs_selected(32768, 2048) == 65012736
    assert pairs_causal(32768) == 536887296
    kl = {s["labels"]["layer"]: s["value"] for s in
          monitor.dump()["dsa_indexer_kl"]["series"]}
    assert set(kl) >= {"layer0", "layer1", "layer2"}
    np.testing.assert_allclose(
        kl["layer1"], net.state["layer1"]["attn"]["indexer_kl"], rtol=1e-6)
    words = np.asarray(net.state["layer0"]["attn"]["pairs_causal_total"])
    assert words.dtype == np.uint32 and words.shape == (2,)
    assert int(words[0]) + (int(words[1]) << 32) == 2 * pairs_causal(T)


def test_bfloat16_compute_stays_near_the_reference():
    """bf16 operands over float32 weights, as the cell runs: the score to
    half a percent of the float32 reference's, every stage's gradient
    norm to 5 % (the bf16 program picks other keys where scores lie within
    rounding of the threshold)."""
    lm.bfloat16_stays_near(FAMILY)


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_a_planted_fault_moves_what_correct_compares(fault):
    """The eight faults the limits have to catch, at the test's sizes. Each
    moves the indexers' first moment or the losses far more than float32
    rounding; with the indexer's loss left out the indexers' moment is
    zero (the stage reads 1)."""
    assert REF.FAULTS == ("no_relu", "no_head_weights", "half_topk",
                          "sees_next", "no_indexer_loss", "kl_head0",
                          "kv_head_mod", "no_renorm")
    stage = lm.a_planted_fault_moves(FAMILY, fault)
    if fault == "no_indexer_loss":
        assert stage["indexer"] == 1.0


# ------------------------------------- the reference's blocks and prefixes
def _attention_at_once(cfg, p, x, fault=None):
    """The reference's attention with every query against every key at
    once and the key heads written out by query head: what its blocks of
    queries against prefixes of the keys have to equal."""
    t = x.shape[0]
    mh, kv, d = REF._heads(cfg)
    topk = cfg["sa_config"]["topk"] // (2 if fault == "half_topk" else 1)
    q, k, v = REF.attention_inputs(cfg, p, x)
    qi, ki, w = REF.indexer_inputs(cfg, p["indexer"], x)
    reach = 1 if fault == "sees_next" else 0
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None] + reach
    scores = REF.index_scores(qi, ki, w)
    ids = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), topk)[1]
    kept = jnp.zeros((t, t), bool).at[jnp.arange(t)[:, None],
                                      ids].set(True) & seen
    of_query = jnp.arange(mh) % kv if fault == "kv_head_mod" \
        else jnp.arange(mh) // (mh // kv)
    s = jnp.einsum("qhd,khd->hqk", q, k[:, of_query],
                   precision="highest") * d ** -0.5
    pr = jax.nn.softmax(jnp.where(kept[None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", pr, v[:, of_query],
                     precision="highest")
    target = jax.lax.stop_gradient(pr[0] if fault == "kl_head0"
                                   else jnp.mean(pr, axis=0))
    log_pi = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), axis=-1)
    kl = jnp.sum(jnp.where(kept, target * (
        jnp.log(jnp.maximum(target, 1e-37)) - log_pi), 0.0)) / t
    return jnp.matmul(out.reshape(t, -1), p["Wo"], precision="highest"), kl


@pytest.mark.parametrize("fault", [None, "sees_next", "kv_head_mod",
                                   "half_topk", "kl_head0"])
def test_the_references_blocks_and_key_prefixes_change_no_number(
        fault, monkeypatch):
    """Eight blocks of 16 queries, each against the keys up to the end of
    its quarter of the sequence, the heads grouped by key head: the
    output, the indexer's loss and every gradient are those of all
    queries against all keys at once, sound and under the faults that
    touch the blocks."""
    monkeypatch.setattr(REF, "Q_BLOCK", 16)
    assert REF.KEY_PREFIXES == 4
    p = REF.make_params(CFG)["layer1"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (T, 32))
    ct = jax.random.normal(jax.random.PRNGKey(4), (T, 32))

    def both(fn):
        def f(p, x):
            a, kl = fn(CFG, p, x, fault=fault)
            return jnp.sum(a * ct) + kl, (a, kl)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(
            p, x)

    (_, (a, kl)), got = both(REF.attention)
    (_, (a0, kl0)), want = both(_attention_at_once)
    _close(a, a0, 2e-6)
    np.testing.assert_allclose(kl, kl0, rtol=2e-6)
    assert float(kl0) > 1e-3
    for g, g0 in zip(jax.tree_util.tree_leaves(got),
                     jax.tree_util.tree_leaves(want)):
        _close(g, g0, 1e-5)


# ------------------------------------------------- the shares of a layer
def test_the_eight_shares_add_up_and_an_unheld_token_gets_exactly_zero():
    lm.the_eight_shares_add_up(FAMILY)
