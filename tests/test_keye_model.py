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
from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import MoEFeedForward
from deeplearning4j_tpu.ops.dsa_attention import pairs_causal, pairs_selected

from _keye_common import CFG, REF, STAGES, SYSTEM, T, _batch, _net
from _kimi_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _close, _rows,
)


def _score(net, params, ids, nxt, keep, state=None):
    return net._score_fn(params, net.state if state is None else state,
                         (ids,), (nxt,), None, (keep,), True,
                         jax.random.PRNGKey(0))


def _one(rows):
    """The harness's rows of ONE sequence (the cell's batch)."""
    return [(r[:1], y[:1]) for r, y in rows]


# --------------------------------------------- the whole model through fit()
def test_the_model_is_the_language_model_alone():
    """Embedding, ``num_hidden_layers`` blocks that are all alike (sparse
    attention + experts), a final norm and an UNTIED head; the indexer's
    leaves sit under the attention's; no vision tower."""
    net, _ = _net()
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
    from benchmark.lib import checks
    net, cfg = _net()
    rows = _one(_rows(11, 2))
    stamps = SYSTEM.stamp_listener()
    net.set_listeners(stamps)
    net.fit(SYSTEM.feed(rows), **how)
    losses = [loss for _, loss in stamps.rows]
    r_losses, r_m, r_params = REF.train_steps(cfg, REF.make_params(cfg),
                                              rows)
    np.testing.assert_allclose(losses, r_losses, rtol=2e-6)
    init = jax.device_get(REF.make_params(cfg))
    diff = lambda new: checks.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: np.asarray(a) - np.asarray(b), new, init))
    prog = {"losses": losses, "update": diff(net.params),
            "momentum": checks.leaf_norms(SYSTEM.momentum(net))}
    ref = {"losses": r_losses, "update": diff(r_params),
           "momentum": checks.leaf_norms(r_m)}
    limits = {"loss_gap": 2e-6, "head_momentum_gap": 1e-4,
              "head_update_gap": 1e-4, "update_norm_gap": 1e-5,
              "stage_momentum_gap": {s: 1e-4 for s in STAGES}}
    rows_ = checks.training_rows(prog, ref,
                                 lambda leaf: REF.stage_of(cfg, leaf), limits)
    assert len(rows_) == 4 + len(STAGES) and checks.verdict(rows_)
    assert checks.worst_leaf_gap(prog["update"], ref["update"]) < 1e-3


def test_logits_losses_and_every_gradient_leaf_match_the_reference():
    """Float32 on both sides, so the selections agree: the logits, the
    cross-entropy, the indexer's loss of every layer (the layers' state),
    and EVERY leaf of the step's gradient element by element, which is
    that of CE + the sum of the layers' indexer losses while the score is
    CE."""
    net, cfg = _net()
    ids, nxt, keep = _batch(cfg, _rows(4, 1)[0][0][:1])
    params = REF.make_params(cfg)
    want_logits = REF.logits(cfg, params, ids[0])
    np.testing.assert_allclose(net.output(ids)[0],
                               jax.nn.softmax(want_logits, axis=-1),
                               atol=2e-6)
    (got_l, (state, _)), got = jax.value_and_grad(
        lambda p: _score(net, p, ids, nxt, keep), has_aux=True)(params)
    (_, (want_ce, want_kl)), want = jax.value_and_grad(
        lambda p: REF.loss_fn(cfg, p, ids[0]), has_aux=True)(params)
    np.testing.assert_allclose(got_l, want_ce, rtol=2e-6)
    got_kl = [state[f"layer{i}"]["attn"]["indexer_kl"] for i in range(3)]
    np.testing.assert_allclose(got_kl, want_kl, rtol=2e-5)
    assert float(min(want_kl)) > 1e-3
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        scale = max(np.abs(np.asarray(b)).max(), 1e-7)
        assert np.abs(np.asarray(a - b)).max() <= 2e-4 * scale, \
            jax.tree_util.keystr(path)


def test_the_score_is_the_cross_entropy_and_the_gradient_has_both_losses():
    """`attach_auxiliary_loss`: with the indexers' losses weighed 0 the
    score is the same to the bit, the main weights' gradients too (they
    get nothing from that loss) and the indexers' gradients are exactly
    zero (they get nothing from the cross-entropy)."""
    net, cfg = _net()
    off, _ = _net(indexer_loss_coef=0.0)
    ids, nxt, keep = _batch(cfg, _rows(4, 1)[0][0][:1])
    params = REF.make_params(cfg)
    grad = lambda n: jax.value_and_grad(
        lambda p: _score(n, p, ids, nxt, keep)[0])(params)
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
    net, cfg = _net()
    rows = _one(_rows(12, 2))
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
    from benchmark.lib import checks
    net, cfg = _net(compute_dtype="bfloat16")
    ids, nxt, keep = _batch(cfg, _rows(4, 1)[0][0][:1])
    params = REF.make_params(cfg)
    got_l, got = jax.value_and_grad(
        lambda p: _score(net, p, ids, nxt, keep)[0])(params)
    (_, (want_l, _)), want = jax.value_and_grad(
        lambda p: REF.loss_fn(cfg, p, ids[0]), has_aux=True)(params)
    assert abs(float(got_l) - float(want_l)) < 5e-3 * float(want_l)
    gaps = checks.stage_gaps(checks.leaf_norms(got), checks.leaf_norms(want),
                             lambda leaf: REF.stage_of(cfg, leaf))
    assert set(gaps) == set(STAGES) and max(gaps.values()) < 5e-2, gaps


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_a_planted_fault_moves_what_correct_compares(fault):
    """The eight faults the limits have to catch, at the test's sizes. Each
    moves the indexers' first moment or the losses far more than float32
    rounding; with the indexer's loss left out the indexers' moment is
    zero (the stage reads 1)."""
    from benchmark.lib import checks
    assert REF.FAULTS == ("no_relu", "no_head_weights", "half_topk",
                          "sees_next", "no_indexer_loss", "kl_head0",
                          "kv_head_mod", "no_renorm")
    rows = _one(_rows(11, 2))
    sound = REF.train_steps(CFG, REF.make_params(CFG), rows)
    bad = REF.train_steps(CFG, REF.make_params(CFG), rows, fault=fault)
    loss = max(abs(a - b) / abs(b) for a, b in zip(bad[0], sound[0]))
    stage = checks.stage_gaps(checks.leaf_norms(bad[1]),
                              checks.leaf_norms(sound[1]),
                              lambda leaf: REF.stage_of(CFG, leaf))
    assert max(loss, *stage.values()) > 1e-3, (fault, loss, stage)
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
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(p, x)

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
    """`y = alike + sum over the chips of (what each chip's experts add)`:
    with a softmax router and no shared expert the eight shares' partial
    results of one expert layer add up to the uncut reference's whole
    layer, and a token none of whose experts a chip holds gets exactly
    zero from that chip."""
    cfg = {**CFG, "experts_held": [0, 16], "num_experts": 16}
    whole = REF.make_params(cfg)["layer1"]
    x = jax.random.normal(jax.random.PRNGKey(5), (T, 32))
    want, _ = REF.layer(cfg, whole, x)
    eps = cfg["rms_norm_eps"]
    a, _ = REF.attention(cfg, whole["attn"],
                         REF._rms(x, whole["ln1"]["gamma"], eps))
    alike = x + a
    normed = REF._rms(alike, whole["ln2"]["gamma"], eps)
    total = np.zeros((T, 32), np.float32)
    for lo in range(0, 16, 2):
        ffn = MoEFeedForward(
            n_out=32, n_experts=16, top_k=2, hidden=24, activation="swish",
            gated=True, has_bias=False, experts_held=(lo, lo + 2),
            router="softmax", n_shared=0, weight_init="normal")
        p = {"Wr": whole["ffn"]["Wr"],
             **{k: whole["ffn"][k][lo:lo + 2]
                for k in ("Wgate", "Wup", "Wdown")}}
        _, state = ffn.init(jax.random.PRNGKey(0),
                            InputType.recurrent(32, T))
        out, new = ffn.apply(p, state, normed[None])
        out = np.asarray(out[0])
        # one share alone is the reference told to hold the same experts
        share = REF.experts({**cfg, "experts_held": [lo, lo + 2],
                             "num_experts": 2}, p, normed)
        _close(out, share, 2e-5)
        idx, _ = REF.routing(cfg, whole["ffn"], normed)
        unheld = ~np.any((np.asarray(idx) >= lo)
                         & (np.asarray(idx) < lo + 2), axis=-1)
        assert unheld.any() and not np.any(out[unheld])
        assert int(new["tokens_with_held_pair_total"]) == int((~unheld).sum())
        total += out
    _close(alike + total, want, 2e-5)
