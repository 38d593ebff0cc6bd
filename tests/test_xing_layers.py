"""The pieces the Xing4.0 family adds: YaRN's frequencies and temperature
in the rotated latent attention, the block on several residual streams, its
two ends, and the 8 head slices and 8 expert shares of one layer adding up
to the uncut reference layer; see `_xing_common.py`."""
import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.conf.graph_vertices import (
    StreamsInVertex, StreamsOutVertex,
)
from deeplearning4j_tpu.nn.layers import (
    GatedMLP, HyperConnectedBlock, MoEFeedForward, MultiHeadLatentAttention,
)
from deeplearning4j_tpu.nn.layers.attention import rms_norm
from deeplearning4j_tpu.nn.layers.linear_attention import (
    rope_pairs, yarn_frequencies,
)
from deeplearning4j_tpu.ops import mhc_mix

from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _close, with_gradients,
)
from _xing_common import CFG, REF, T

_CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs", "xing4.0-29b-a4b.json")


def _published():
    with open(_CONFIG) as f:
        return json.load(f)


def _yarn_of(cfg):
    rs = cfg["rope_scaling"]
    return yarn_frequencies(
        cfg["qk_rope_head_dim"], cfg["rope_theta"], factor=rs["factor"],
        original_max_position=rs["original_max_position_embeddings"],
        beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
        mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"])


def _mla(cfg, heads, **over):
    rs = cfg["rope_scaling"]
    return MultiHeadLatentAttention(**{**dict(
        n_out=cfg["hidden_size"], n_heads=heads,
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], kv_rank=cfg["kv_lora_rank"],
        q_rank=cfg["q_lora_rank"], rotate=True,
        rope_theta=cfg["rope_theta"], norm_epsilon=cfg["rms_norm_eps"],
        rope_scaling="yarn", rope_factor=rs["factor"],
        rope_original_max_position=rs["original_max_position_embeddings"],
        rope_beta_fast=rs["beta_fast"], rope_beta_slow=rs["beta_slow"],
        rope_mscale=rs["mscale"],
        rope_mscale_all_dim=rs["mscale_all_dim"]), **over})


# ----------------------------------------------------------------- YaRN
def test_yarn_at_the_published_keys_is_the_closed_form():
    """64 rotated dims at base 10,000, factor 64 over 4,096: pairs 0-10
    turn as they did, pairs 23-31 sixty-four times slower, the pairs
    between by the ramp; cos and sin keep their size (mscale =
    mscale_all_dim) and the softmax scale grows by (0.1 ln 64 + 1)^2."""
    cfg = _published()
    freqs, amplitude, temperature = _yarn_of(cfg)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    where = lambda turns: 64 * math.log(4096 / (turns * 2 * math.pi)) \
        / (2 * math.log(10000.0))
    assert (math.floor(where(32)), math.ceil(where(1))) == (10, 23)
    np.testing.assert_allclose(freqs[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(freqs[23:], plain[23:] / 64, rtol=1e-12)
    for j in range(11, 23):
        ramp = (j - 10) / 13
        np.testing.assert_allclose(
            freqs[j], plain[j] / 64 * ramp + plain[j] * (1 - ramp),
            rtol=1e-12)
    assert amplitude == 1.0
    np.testing.assert_allclose(temperature, (0.1 * math.log(64) + 1) ** 2,
                               rtol=1e-12)
    np.testing.assert_allclose(temperature, 1.4159 ** 2, rtol=1e-4)
    want_f, want_a, want_t = REF.yarn(cfg)
    np.testing.assert_allclose(freqs, want_f, rtol=1e-6)
    assert (amplitude, temperature) == (want_a, want_t)
    # mscale_all_dim 0 (a family member without it): the temperature
    # moves to cos and sin
    _, a0, t0 = yarn_frequencies(64, 10000.0, factor=64,
                                 original_max_position=4096, mscale=1.0,
                                 mscale_all_dim=0.0)
    np.testing.assert_allclose(a0, 0.1 * math.log(64) + 1)
    assert t0 == 1.0


def test_the_rotation_with_given_frequencies_is_the_references():
    freqs, amplitude, _ = _yarn_of(CFG)
    assert 0 < sum(f == p for f, p in zip(
        freqs, 100.0 ** (-np.arange(4) / 4.0))) < 4     # a blend, in part
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 8))
    got = rope_pairs(x, jnp.arange(40), 100.0, freqs, amplitude)
    want = REF.rotate(x, jnp.asarray(freqs, jnp.float32), amplitude)
    _close(got, jnp.concatenate([want[..., 0::2], want[..., 1::2]], -1),
           2e-6)
    plain = rope_pairs(x, jnp.arange(40), 100.0)
    assert float(jnp.abs(plain - got).max()) > 0.1


def test_latent_attention_under_yarn_is_the_references():
    """The layer and every parameter's gradient against the reference's
    attention at the test widths; without the temperature, and with the
    plain frequencies, it is another function: the two faults the
    benchmark plants."""
    p = REF.make_params(CFG)["layer1"]["attn"]
    mla = _mla(CFG, 4)
    p0, state = mla.init(jax.random.PRNGKey(0), InputType.recurrent(32, T))
    assert {k: v.shape for k, v in p0.items()} \
        == {k: v.shape for k, v in p.items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, T, 32))
    y, got = with_gradients(lambda p, x: mla.apply(p, state, x)[0], w,
                            (p, x))
    y_ref, want = with_gradients(lambda p, x: REF.attention(CFG, p, x), w,
                                 (p, x))
    _close(y, y_ref, 3e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(a - b)).max() <= 1e-4 * max(
            np.abs(np.asarray(b)).max(), 1e-7), jax.tree_util.keystr(path)
    cold = dataclasses.replace(mla, rope_mscale_all_dim=0.0,
                               rope_mscale=0.0)
    _close(cold.apply(p, state, x)[0],
           REF.attention(CFG, p, x, fault="no_yarn_temperature"), 3e-5)
    # a factor of 1 stretches nothing and warms nothing: the plain layer
    plain = dataclasses.replace(mla, rope_scaling=None)
    _close(_mla(CFG, 4, rope_factor=1.0).apply(p, state, x)[0],
           plain.apply(p, state, x)[0], 3e-5)
    for other in (cold, plain):
        assert float(jnp.abs(other.apply(p, state, x)[0] - y).max()) > 1e-3


def test_an_unknown_rope_scaling_is_refused_by_name():
    for bad in (dict(rope_scaling="linear"), dict(rope_scaling="yarn",
                                                  rotate=False)):
        layer = dataclasses.replace(_mla(CFG, 4), **bad)
        with pytest.raises(ValueError, match="rope_scaling"):
            layer.init(jax.random.PRNGKey(0), InputType.recurrent(32, T))


# ------------------------------------------------------- the block, the ends
def _block(ffn, **over):
    return HyperConnectedBlock(**{**dict(
        n_out=32, n_streams=4, attn=_mla(CFG, 4), ffn=ffn,
        norm_epsilon=CFG["rms_norm_eps"]), **over})


def test_the_ends_copy_one_stream_to_four_and_sum_four_to_one():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 8))
    wide = StreamsInVertex(n_streams=4).apply(x)
    assert wide.shape == (2, 6, 32)
    for j in range(4):
        np.testing.assert_array_equal(wide[..., 8 * j:8 * (j + 1)], x)
    np.testing.assert_allclose(StreamsOutVertex(n_streams=4).apply(wide),
                               4 * x, rtol=1e-6)
    assert StreamsInVertex(4).output_type(
        InputType.recurrent(8, 6)).shape == (6, 32)
    assert StreamsOutVertex(4).output_type(
        InputType.recurrent(32, 6)).shape == (6, 8)
    with pytest.raises(ValueError, match="streams"):
        StreamsOutVertex(3).output_type(InputType.recurrent(32, 6))


def test_a_fresh_block_is_all_but_a_plain_residual_and_refuses_one_stream():
    """As the papers start it: the static part decides, H_res is close to
    the identity and H_post to 1, so each stream leaves as it came plus
    the sub-layers' outputs; the state holds the two gauges."""
    block = _block(GatedMLP(n_out=32, hidden=48))
    params, state = block.init(jax.random.PRNGKey(0),
                               InputType.recurrent(128, T))
    assert set(params) == {"attn", "ffn", "ln1", "ln2", "hc_attn", "hc_ffn"}
    assert {k: v.shape for k, v in params["hc_ffn"].items()} \
        == {"phi": (128, 24), "bias": (24,), "alpha": (3,)}
    assert set(state) == {"mhc"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 128))
    y, new = block.apply(params, state, x)
    assert y.shape == x.shape
    assert 0.0 < float(new["mhc"]["res_gap"]) < 1e-2
    assert 1.2 < float(new["mhc"]["pre_entropy"]) <= math.log(4) + 1e-6
    hc = params["hc_attn"]
    _, _, h_post, h_res, _ = mhc_mix.pre(x.reshape(-1, 128), hc["phi"],
                                         hc["bias"], hc["alpha"],
                                         block.mix())
    assert float(jnp.abs(h_res - jnp.eye(4)[:, :, None]).max()) < 0.1
    assert float(jnp.abs(h_post - 1.0).max()) < 0.05
    with pytest.raises(ValueError, match="n_streams x n_out"):
        block.init(jax.random.PRNGKey(0), InputType.recurrent(32, T))


def test_a_block_is_the_references_layer_with_every_gradient():
    """Both kinds of layer of the cut (the dense MLP, the experts held in
    part) on four streams, against the reference's token-by-token form,
    with the gradient in every leaf and in the streams."""
    full = REF.make_params(CFG)
    experts = MoEFeedForward(
        n_out=32, n_experts=16, top_k=2, hidden=24, activation="swish",
        gated=True, has_bias=False, experts_held=(2, 6), router="sigmoid",
        routed_scale=2.0, n_shared=1, weight_init="normal")
    x = jax.random.normal(jax.random.PRNGKey(3), (2, T, 4, 32)) \
        * jnp.asarray([1.0, 0.5, 2.0, 1.5])[None, None, :, None]
    w = jax.random.normal(jax.random.PRNGKey(4), (2, T, 128))
    for name, ffn, dense in (("layer0", GatedMLP(n_out=32, hidden=48), True),
                             ("layer1", experts, False)):
        block, p = _block(ffn), full[name]
        _, state = block.init(jax.random.PRNGKey(0),
                              InputType.recurrent(128, T))
        y, got = with_gradients(
            lambda p, x: block.apply(p, state, x.reshape(2, T, 128))[0], w,
            (p, x))
        y_ref, want = with_gradients(
            lambda p, x: REF.layer(CFG, p, x, dense).reshape(2, T, 128), w,
            (p, x))
        _close(y, y_ref, 2e-5)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(got)[0],
                jax.tree_util.tree_leaves(want)):
            assert np.abs(np.asarray(a - b)).max() <= 2e-4 * max(
                np.abs(np.asarray(b)).max(), 1e-7), \
                (name, jax.tree_util.keystr(path))


# ------------------------------------------------------- the shares add up
def test_the_eight_head_slices_and_eight_expert_shares_add_up():
    """`layer = alike + sum over the 8 chips of (what each chip's heads add)
    ... + sum over the 8 chips of (what each chip's experts add)`: the
    mappings, the norms, the low-rank projections, the router and the
    shared expert are computed alike on every chip and counted once; each
    chip's slice of the attention (its heads' columns of W_qb and W_kvb,
    their rows of W_o) and its share of the experts is the program's layer
    at the slice's sizes, and is what the reference gives when told to
    hold the same; together they are the uncut reference layer."""
    cfg = {**CFG, "num_attention_heads": 8, "num_key_value_heads": 8,
           "experts_held": [0, 16], "n_routed_experts": 16}
    whole = REF.make_params(cfg)["layer1"]
    mix = _block(None).mix()
    t, eps = 64, cfg["rms_norm_eps"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, t, 4, 32))
    want = REF.layer(cfg, whole, x, False).reshape(t, 128)
    nope, rope, dv = 12, 8, 16
    attn = whole["attn"]

    def head_slice(c):
        cols = lambda w, width: w.reshape(w.shape[0], 8, width)[:, c]
        return {**attn, "Wqb": cols(attn["Wqb"], nope + rope),
                "Wkvb": cols(attn["Wkvb"], nope + dv),
                "Wo": attn["Wo"].reshape(8, dv, 32)[c]}

    rows = x.reshape(t, 128)
    hc = whole["hc_attn"]
    u, _, h_post, h_res, rows = mhc_mix.pre(rows, hc["phi"], hc["bias"],
                                            hc["alpha"], mix)
    h = rms_norm(u, whole["ln1"]["gamma"], eps)[None]
    mla = _mla(cfg, 1)
    y = 0.0
    for c in range(8):
        part = mla.apply(head_slice(c), {}, h)[0][0]
        _close(part, REF.attention(cfg, head_slice(c), h)[0], 3e-5)
        y = y + part
    _close(y, REF.attention(cfg, attn, h)[0], 3e-5)
    rows = mhc_mix.post(rows, y, h_res, h_post, mix)

    hc, ffn = whole["hc_ffn"], whole["ffn"]
    u, _, h_post, h_res, rows = mhc_mix.pre(rows, hc["phi"], hc["bias"],
                                            hc["alpha"], mix)
    h = rms_norm(u, whole["ln2"]["gamma"], eps)
    idx = np.asarray(REF.routing(cfg, ffn, h)[0])
    alike = MoEFeedForward(
        n_out=32, n_experts=16, top_k=2, hidden=24, activation="swish",
        gated=True, has_bias=False, router="sigmoid", routed_scale=2.0,
        n_shared=1, weight_init="normal").shared(ffn, h)
    y = alike
    for c in range(8):
        lo = 2 * c
        share = MoEFeedForward(
            n_out=32, n_experts=16, top_k=2, hidden=24, activation="swish",
            gated=True, has_bias=False, experts_held=(lo, lo + 2),
            router="sigmoid", routed_scale=2.0, n_shared=0,
            weight_init="normal")
        p = {"Wr": ffn["Wr"], **{k: ffn[k][lo:lo + 2]
                                 for k in ("Wgate", "Wup", "Wdown")}}
        _, state = share.init(jax.random.PRNGKey(0),
                              InputType.recurrent(32, t))
        part = np.asarray(share.apply(p, state, h[None])[0][0])
        _close(part, REF.experts({**cfg, "experts_held": [lo, lo + 2],
                                  "n_routed_experts": 2}, p, h,
                                 shared=False), 2e-5)
        unheld = ~np.any((idx >= lo) & (idx < lo + 2), axis=-1)
        assert unheld.any() and not np.any(part[unheld])
        y = y + part
    rows = mhc_mix.post(rows, y, h_res, h_post, mix)
    _close(rows, want, 3e-5)
