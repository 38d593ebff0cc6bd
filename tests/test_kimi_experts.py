"""The expert layer of the hybrid LM: the chip's share, the dispatch, the
routers and the routing counters; see `_kimi_common.py`."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import (
    GatedMLP, KimiDeltaAttention, MoEFeedForward, MultiHeadLatentAttention,
    RnnOutputLayer, TransformerBlock,
)

from _kimi_common import (  # noqa: F401 (the autouse fixture)
    CFG, KINDS, REF, SYSTEM, T, _budgets_at_the_tests_sizes, _close,
    _layer_params, _net, _rows,
)


# ------------------------------------------------------------ the chip's share
def _expert_block(lo, hi, attn_kind="kda"):
    attn = KimiDeltaAttention(n_out=32, n_heads=4, head_dim=16, low_rank=8,
                              chunk=32) if attn_kind == "kda" \
        else MultiHeadLatentAttention(n_out=32, n_heads=4, nope_dim=16,
                                      rope_dim=8, v_dim=16, kv_rank=24)
    ffn = MoEFeedForward(n_out=32, n_experts=8, top_k=2, hidden=24,
                         activation="swish", gated=True, has_bias=False,
                         experts_held=(lo, hi), router="sigmoid",
                         routed_scale=2.446, n_shared=1)
    return TransformerBlock(n_out=32, n_heads=4, norm="rms",
                            norm_epsilon=1e-5, has_bias=False, attn=attn,
                            ffn=ffn)


_PER_EXPERT = ("Wgate", "Wup", "Wdown")


@pytest.mark.parametrize("layer,attn_kind", [(2, "kda"), (4, "mla")])
def test_the_four_shares_add_up_to_the_uncut_layer(layer, attn_kind):
    """Each of 4 chips holds 2 of the 8 experts and computes `h + shared +
    sum over ITS experts`; what every chip computes alike (attention,
    residual, shared expert) counted once, the shares add up to the
    reference's whole layer."""
    cfg = {**CFG, "experts_held": [0, 8], "num_experts": 8}
    whole = REF.make_params(cfg)[str(layer)]
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    want = REF.layer(cfg, whole, x, KINDS[layer - 1])

    def run(lo, hi, zero_down=False):
        p = dict(whole, ffn={k: (v[lo:hi] if k in _PER_EXPERT else v)
                             for k, v in whole["ffn"].items()})
        if zero_down:
            p["ffn"]["Wdown"] = jnp.zeros_like(p["ffn"]["Wdown"])
        blk = _expert_block(lo, hi, attn_kind)
        _, state = blk.init(jax.random.PRNGKey(0),
                            InputType.recurrent(32, T))
        return blk.apply(p, state, x)[0]

    alike = run(0, 2, zero_down=True)          # no routed expert adds
    shares = [run(lo, lo + 2) for lo in (0, 2, 4, 6)]
    got = alike + sum(s - alike for s in shares)
    _close(got, want, 3e-5)
    # one share alone is the reference told to hold the same experts
    held = dict(whole, ffn={k: (v[2:4] if k in _PER_EXPERT else v)
                            for k, v in whole["ffn"].items()})
    _close(shares[1], REF.layer(cfg, held, x, KINDS[layer - 1],
                                held=(2, 4)), 3e-5)


def test_dense_first_layer_is_the_references():
    p = _layer_params(1)
    blk = TransformerBlock(
        n_out=32, n_heads=4, norm="rms", norm_epsilon=1e-5, has_bias=False,
        attn=KimiDeltaAttention(n_out=32, n_heads=4, head_dim=16,
                                low_rank=8, chunk=32),
        ffn=GatedMLP(n_out=32, hidden=48))
    p0, state = blk.init(jax.random.PRNGKey(0), InputType.recurrent(32, T))
    assert state == {} and p0["ffn"]["Wgate"].shape == (32, 48)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    _close(blk.apply(p, state, x)[0], REF.layer(CFG, p, x, KINDS[0]), 3e-5)


def test_no_pair_is_dropped_when_every_token_chooses_held_experts():
    """A router of zeros ties every score: every token takes experts 0 and
    1, both held, so every one of the N*k rows is in a group (the worst
    case the dispatch is sized for) and the result is the dense sum."""
    ffn = MoEFeedForward(n_out=16, n_experts=4, top_k=2, hidden=8,
                         activation="swish", gated=True, has_bias=False,
                         experts_held=(0, 2), router="sigmoid",
                         routed_scale=2.0)
    p, state = ffn.init(jax.random.PRNGKey(0), InputType.recurrent(16, 12))
    p["Wr"] = jnp.zeros_like(p["Wr"])
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 12, 16))
    y, state = ffn.apply(p, state, x)
    dense = sum(0.5 * 2.0 * (jax.nn.silu(x @ p["Wgate"][e])
                             * (x @ p["Wup"][e])) @ p["Wdown"][e]
                for e in (0, 1))
    np.testing.assert_allclose(y, dense, atol=1e-5)
    np.testing.assert_array_equal(state["tokens_routed"], [36, 36, 0, 0])
    # the layer has no capacity and no counter of dropped pairs: none can
    assert not any("drop" in k for k in state)
    assert not any("capacity" in f.name
                   for f in dataclasses.fields(MoEFeedForward))


def test_undefined_rows_of_a_grouped_product_reach_no_sum(monkeypatch):
    """Behind the last group a grouped product's rows are undefined: the
    CPU writes zeros there, the TPU's kernel nothing (whatever the buffer
    held). With NaN in every such row, of the products and of their
    transposes alike, the layer's result and gradients are the same
    finite numbers."""
    from deeplearning4j_tpu.nn.layers import attention
    real = attention._grouped_matmul

    def poison(a, sizes):
        rows = jnp.arange(a.shape[0])[:, None]
        return jnp.where(rows < sizes.sum(), a, jnp.nan)

    @jax.custom_vjp
    def poisoned(x, w, sizes):
        return poison(real(x, w, sizes), sizes)

    def fwd(x, w, sizes):
        return poisoned(x, w, sizes), (x, w, sizes)

    def bwd(res, g):
        x, w, sizes = res
        dx, dw = jax.vjp(lambda x, w: real(x, w, sizes), x, w)[1](g)
        return poison(dx, sizes), dw, None

    poisoned.defvjp(fwd, bwd)
    ffn = MoEFeedForward(n_out=16, n_experts=8, top_k=3, hidden=8,
                         activation="swish", gated=True, has_bias=False,
                         experts_held=(2, 5), router="sigmoid", n_shared=1)
    p, state = ffn.init(jax.random.PRNGKey(0), InputType.recurrent(16, 20))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 16))
    loss = lambda p, x: jnp.sum(ffn.apply(p, state, x)[0] ** 2)
    want = jax.value_and_grad(loss, (0, 1))(p, x)
    monkeypatch.setattr(attention, "_grouped_matmul", poisoned)
    got = jax.value_and_grad(loss, (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("top_k", [1, 2, 4])
def test_the_dispatch_is_the_dense_routing_it_replaced(top_k):
    """The layer this one replaced ran every expert on every token and
    weighted by the renormalised top-k softmax gates (GELU MLP with
    biases): same numbers, for the fields TransformerLMMoE uses."""
    ffn = MoEFeedForward(n_out=16, n_experts=4, top_k=top_k, mlp_ratio=2)
    p, state = ffn.init(jax.random.PRNGKey(2), InputType.recurrent(16, 10))
    p["b1"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3), p["b1"].shape)
    p["b2"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), p["b2"].shape)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 10, 16))
    y, _ = ffn.apply(p, state, x)
    gates = jax.nn.softmax(x @ p["Wr"], axis=-1)
    if top_k < 4:
        thresh = jax.lax.top_k(gates, top_k)[0][..., -1:]
        gates = jnp.where(gates >= thresh, gates, 0.0)
        gates = gates / gates.sum(-1, keepdims=True)
    h = jax.nn.gelu(jnp.einsum("btf,efh->bteh", x, p["W1"]) + p["b1"])
    dense = jnp.einsum("bteo,bte->bto",
                       jnp.einsum("bteh,eho->bteo", h, p["W2"]) + p["b2"],
                       gates)
    np.testing.assert_allclose(y, dense, atol=2e-5)


def test_sigmoid_router_renormalises_scales_and_takes_the_bias():
    ffn = MoEFeedForward(n_out=8, n_experts=6, top_k=3, gated=True,
                         has_bias=False, router="sigmoid",
                         routed_scale=2.446)
    p, state = ffn.init(jax.random.PRNGKey(0), InputType.recurrent(8, 5))
    assert state["route_bias"].shape == (6,) and "route_bias" not in p
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 8))
    idx, w = ffn.route(p, state, x)
    s = jax.nn.sigmoid((x @ p["Wr"]).reshape(10, 6))
    np.testing.assert_array_equal(idx, jax.lax.top_k(s, 3)[1])
    kept = jnp.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(w, kept / kept.sum(-1, keepdims=True) * 2.446,
                               atol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 2.446, atol=1e-5)
    # the reference routes alike
    r_idx, r_w = REF.routing({"num_experts_per_token": 3,
                              "routed_scaling_factor": 2.446}, p,
                             x.reshape(10, 8))
    np.testing.assert_array_equal(idx, r_idx)
    np.testing.assert_allclose(w, r_w, atol=1e-6)
    # the correction vector moves the choice, not the weights' source
    biased = {**state, "route_bias": jnp.zeros((6,)).at[5].set(10.0)}
    idx_b, w_b = ffn.route(p, biased, x)
    assert (idx_b[:, 0] == 5).all()
    np.testing.assert_allclose(
        w_b[:, 0], s[:, 5] / jnp.take_along_axis(s, idx_b, -1).sum(-1)
        * 2.446, atol=1e-6)
    with pytest.raises(ValueError, match="unknown router"):
        MoEFeedForward(n_out=8, router="tanh").init(
            jax.random.PRNGKey(0), InputType.recurrent(8, 5))


def test_softmax_router_is_a_softmax_over_the_kept_logits():
    ffn = MoEFeedForward(n_out=8, n_experts=6, top_k=3)
    p, state = ffn.init(jax.random.PRNGKey(0), InputType.recurrent(8, 5))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 8))
    idx, w = ffn.route(p, state, x)
    r = (x @ p["Wr"]).reshape(10, 6)
    want = jnp.take_along_axis(jax.nn.softmax(r, -1), idx, axis=-1)
    np.testing.assert_allclose(w, want / want.sum(-1, keepdims=True),
                               atol=1e-6)
    np.testing.assert_array_equal(idx, jax.lax.top_k(r, 3)[1])


# --------------------------------------------------------------- the counters
def _routed(before):
    """(token, expert) pairs ``moe_tokens_routed_total`` gained since the
    dump ``before``, by (layer, held)."""
    from deeplearning4j_tpu import monitor
    had = {tuple(sorted(s["labels"].items())): s["value"] for s in
           before.get("moe_tokens_routed_total", {}).get("series", [])}
    out = {}
    for s in monitor.dump()["moe_tokens_routed_total"]["series"]:
        key = tuple(sorted(s["labels"].items()))
        gained = s["value"] - had.get(key, 0)
        if gained:
            out[s["labels"]["layer"], s["labels"]["held"]] = gained
    return out


@pytest.mark.parametrize("how", [{"scan_steps": 2}, {"scan_steps": 1},
                                 {"accumulate_steps": 2}])
def test_every_fit_path_counts_every_steps_routing(how):
    """The layers count in their state, so the scan-of-K chunk, the
    per-call loop and gradient accumulation report alike: 4 batches of
    2 x 128 tokens, 2 experts a token, in each of the 4 expert layers;
    and the counts in the state equal a host count of the reference's
    routing."""
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.train.listeners import ExpertLoadListener
    net, cfg = _net(learning_rate=0.0, weight_decay=0.0)
    net.set_listeners(ExpertLoadListener())
    before = monitor.dump()
    rows = _rows(6, 4)
    net.fit(SYSTEM.feed(rows), **how)
    got = _routed(before)
    assert "1" not in net.state or "ffn" not in net.state["1"]
    for layer in ("2", "3", "4", "5"):
        assert got[layer, "yes"] + got[layer, "no"] == 4 * 256 * 2
        total = np.asarray(net.state[layer]["ffn"]["tokens_routed_total"])
        assert total.dtype == np.uint32 and total.sum() == 4 * 256 * 2
        assert got[layer, "yes"] == total[2:6].sum()
    # the host's count for layer 2 (rate 0: the weights stay the seed's)
    params = REF.make_params(cfg)
    want = np.zeros(8, np.int64)
    for r, _ in rows:
        x = params["0"]["W"][jnp.asarray(REF.decode_tokens(cfg, r))]
        h = REF.layer(cfg, params["1"], x, KINDS[0])
        p2 = params["2"]
        h = h + REF._kda(cfg, p2["attn"], REF._rms(
            h, p2["ln1"]["gamma"], 1e-5), "highest")
        n = REF._rms(h, p2["ln2"]["gamma"], 1e-5).reshape(-1, 32)
        want += np.bincount(np.asarray(REF.routing(cfg, p2["ffn"], n)[0])
                            .ravel(), minlength=8)
    np.testing.assert_array_equal(
        np.asarray(net.state["2"]["ffn"]["tokens_routed_total"]), want)
    # a second epoch publishes its own steps and no more
    before = monitor.dump()
    net.fit(SYSTEM.feed(_rows(6, 2)), **how)
    assert sum(_routed(before).values()) == 4 * 2 * 256 * 2


def test_a_graphs_expert_layer_counts_too():
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.nn.conf.network import (
        GraphBuilder, NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.layers import EmbeddingSequenceLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.train.listeners import ExpertLoadListener
    g = (GraphBuilder(NeuralNetConfiguration.Builder().seed(5)
                      .updater(Adam(1e-2)))
         .add_inputs("tokens").set_input_types(InputType.recurrent(1, 16)))
    g.add_layer("emb", EmbeddingSequenceLayer(n_in=12, n_out=16), "tokens")
    g.add_layer("moe", MoEFeedForward(n_out=16, n_experts=4, top_k=2,
                                      hidden=8, experts_held=(1, 3)), "emb")
    g.add_layer("head", RnnOutputLayer(n_out=12, activation="softmax",
                                       loss="mcxent"), "moe")
    g.set_outputs("head")
    net = ComputationGraph(g.build()).init()
    net.set_listeners(ExpertLoadListener())
    ids = np.arange(3 * 16).reshape(3, 16, 1) % 12
    y = np.eye(12, dtype="float32")[np.roll(ids[..., 0], -1, 1)]
    before = monitor.dump()
    net.fit(MultiDataSet((ids.astype("float32"),), (y,)), epochs=2)
    got = _routed(before)
    assert got["moe", "yes"] + got["moe", "no"] == 2 * 3 * 16 * 2
    total = np.asarray(net.state["moe"]["tokens_routed_total"])
    assert got["moe", "yes"] == total[1:3].sum()


def test_the_states_total_wraps_and_the_listener_takes_it_modulo():
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.train.listeners import ExpertLoadListener
    net, cfg = _net()
    near = jnp.full((8,), 2 ** 32 - 5, jnp.uint32)
    for layer in ("2", "3", "4", "5"):
        net.state[layer]["ffn"]["tokens_routed_total"] = near
    net.set_listeners(ExpertLoadListener())
    before = monitor.dump()
    net.fit(SYSTEM.feed(_rows(6, 1)), scan_steps=1)
    assert sum(_routed(before).values()) == 4 * 256 * 2
