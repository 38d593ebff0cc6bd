"""The pieces the Nemotron-H family's zoo model is made of, each against
the benchmark reference's own function at tiny widths on the CPU in
float32: `Mamba2Mixer`, the expert layer with its routed experts in a
latent, position-free grouped-query attention, the block that is one mixer;
and the SHARES: the 8 head slices of a Mamba-2 layer, the 8 of an attention
and the 64 expert shares of a LatentMoE, the shared expert counted once,
add up to what the uncut reference gives for the whole layer. See
`_nemotron_common.py`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import (
    Mamba2Mixer, MixerBlock, MoEFeedForward, MultiHeadAttention,
)

import _lm_common as lm
from _nemotron_common import CFG, REF
from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _close,
)

F, T = CFG["hidden_size"], 128


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _x(seed, t=T, batch=2):
    return jax.random.normal(jax.random.PRNGKey(seed), (batch, t, F))


def _mixer_params(cfg, kind, seed=3):
    """The reference's seeded leaves of one mixer of ``kind`` under
    ``cfg`` (the taps' bias, the step sizes and the decays as the family
    draws them)."""
    one = {**cfg, "hybrid_override_pattern": kind, "first_layer": 0,
           "num_hidden_layers": 1, "weights_seed": seed}
    return REF.make_params(one)["layer0"]["mixer"]


def _mamba(cfg):
    return Mamba2Mixer(
        n_out=F, n_heads=cfg["mamba_num_heads"],
        head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
        state_dim=cfg["ssm_state_size"], conv_kernel=cfg["conv_kernel"],
        chunk=cfg["chunk_size"], norm_epsilon=cfg["layer_norm_epsilon"])


def _attention(cfg):
    return MultiHeadAttention(
        n_out=F, n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        causal=True, use_rope=False, has_bias=False, attention_impl="flash",
        block_size=cfg["attention_block"])


def _experts(cfg, **over):
    return MoEFeedForward(**{**dict(
        n_out=F, n_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_tok"],
        hidden=cfg["moe_intermediate_size"], activation="relu2",
        gated=False, has_bias=False,
        experts_held=tuple(cfg["experts_held"]), router="sigmoid",
        routed_scale=cfg["routed_scaling_factor"], n_shared=1,
        shared_hidden=cfg["moe_shared_expert_intermediate_size"],
        latent=cfg["moe_latent_size"]), **over})


def _state(layer, t=T):
    return layer.init(jax.random.PRNGKey(0), InputType.recurrent(F, t))[1]


# ------------------------------------------------- each mixer, and gradients
@pytest.mark.parametrize("kind,build,ref_fn", [
    ("M", _mamba, REF.mamba2), ("*", _attention, REF.attention),
    ("E", _experts, REF.latent_moe)], ids=["mamba2", "attention", "experts"])
def test_a_mixer_is_the_references_values_and_gradients(kind, build, ref_fn):
    layer, p, x = build(CFG), _mixer_params(CFG, kind), _x(1)
    state = _state(layer)
    w = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    (got, _), g_got = lm.with_gradients(
        lambda p, x: layer.apply(p, state, x, train=True), w, (p, x))
    want, g_want = lm.with_gradients(
        lambda p, x: ref_fn(CFG, p, x), w, (p, x))
    _close(got, want, 2e-5)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(g_got)[0],
            jax.tree_util.tree_leaves(g_want)):
        assert np.abs(np.asarray(a - b)).max() <= 2e-4 * max(
            np.abs(np.asarray(b)).max(), 1e-7), jax.tree_util.keystr(path)


def test_a_layers_own_init_has_the_references_leaves():
    """Same names, same shapes, whatever the kind; Mamba-2's own leaves
    start as the family draws them."""
    for kind, build in (("M", _mamba), ("*", _attention), ("E", _experts)):
        layer = build(CFG)
        p, _ = layer.init(jax.random.PRNGKey(1), InputType.recurrent(F, T))
        shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
        assert shapes(p) == shapes(_mixer_params(CFG, kind)), kind
    p, _ = _mamba(CFG).init(jax.random.PRNGKey(1), InputType.recurrent(F, T))
    step = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert (step >= 1e-3 * 0.999).all() and (step <= 0.1 * 1.001).all()
    a = np.exp(np.asarray(p["A_log"]))
    assert (a >= 1).all() and (a <= 16).all() and (np.asarray(p["D"]) == 1
                                                   ).all()
    assert not np.asarray(p["conv_b"]).any()


def test_a_block_is_one_mixer_behind_a_pre_norm():
    """``y = x + mixer(RMSNorm(x))``, the reference's `layer`; the block's
    state is its mixer's (where the listener reads the counters)."""
    cfg = {**CFG, "hybrid_override_pattern": "E", "first_layer": 0,
           "num_hidden_layers": 1}
    p = REF.make_params(cfg)["layer0"]
    block = MixerBlock(n_out=F, mixer=_experts(CFG), norm="rms",
                       norm_epsilon=CFG["layer_norm_epsilon"])
    own, state = block.init(jax.random.PRNGKey(0), InputType.recurrent(F, T))
    assert set(own) == {"ln", "mixer"} and "tokens_routed_total" in state
    x = _x(4)
    got, new = block.apply(p, state, x, train=True)
    _close(got, REF.layer(cfg, p, x, "E"), 2e-5)
    assert int(new["tokens_routed_total"].sum()) \
        == 2 * T * CFG["num_experts_per_tok"]
    with pytest.raises(ValueError, match="mixer"):
        MixerBlock(n_out=F).init(jax.random.PRNGKey(0),
                                 InputType.recurrent(F, T))


def test_relu2_is_the_square_of_relu():
    from deeplearning4j_tpu.nn.activations import get_activation
    x = jnp.linspace(-2.0, 2.0, 9)
    np.testing.assert_array_equal(get_activation("relu2")(x),
                                  jnp.maximum(x, 0.0) ** 2)


@pytest.mark.parametrize("over,match", [
    (dict(router="softmax"), "latent"),
    (dict(has_bias=True), "biases"),
    (dict(latent=None, has_bias=True), "shared expert")])
def test_what_the_expert_layer_does_not_build_is_refused_by_name(over,
                                                                 match):
    with pytest.raises(ValueError, match=match):
        _experts(CFG, **over).init(jax.random.PRNGKey(0),
                                   InputType.recurrent(F, T))


def test_a_gated_shared_expert_and_no_latent_are_what_they_were():
    """The fields the five other families set give the parent's leaves: a
    gated shared expert of ``n_shared * hidden``, experts on the stream."""
    layer = MoEFeedForward(n_out=F, n_experts=8, top_k=2, hidden=24,
                           activation="swish", gated=True, has_bias=False,
                           router="sigmoid", n_shared=2)
    p, _ = layer.init(jax.random.PRNGKey(0), InputType.recurrent(F, T))
    assert {k: v.shape for k, v in p.items()} == {
        "Wr": (F, 8), "Wgate": (8, F, 24), "Wup": (8, F, 24),
        "Wdown": (8, 24, F), "Wgate_s": (F, 48), "Wup_s": (F, 48),
        "Wdown_s": (48, F)}


# ------------------------------------------------------ the shares add up
#: the uncut layers at a small size: 16 Mamba-2 heads of 4 in 8 groups of
#: state 8 (a slice: 2 heads, 1 group); 16 query heads on 2 key/value heads
#: of 8 (a slice: 2 on 1); 64 experts routed over, 6 a token (a share: 1)
WHOLE = {**CFG, "mamba_num_heads": 16, "mamba_head_dim": 4, "n_groups": 8,
         "ssm_state_size": 8, "num_attention_heads": 16,
         "num_key_value_heads": 2, "router_experts": 64,
         "n_routed_experts": 64, "experts_held": [0, 64],
         "num_experts_per_tok": 6}


def test_the_eight_head_slices_of_a_mamba2_layer_add_up():
    """A tensor-parallel slice IS a smaller layer: one group with its
    heads, its columns of W_in, its taps and its rows of W_out; the gated
    norm is over a group's channels, so no slice needs another's. The
    slices' partial sums add up to the uncut reference's layer."""
    h, p_, g, n, inner, _ = REF._mamba(WHOLE)
    whole, x = _mixer_params(WHOLE, "M"), _x(5, t=64)
    want = REF.mamba2(WHOLE, whole, x)
    per, wide = h // g, inner // g
    cut = {**WHOLE, "mamba_num_heads": per, "n_groups": 1}
    layer = _mamba(cut)
    total = 0.0
    for s in range(g):
        ch = np.arange(s * wide, (s + 1) * wide)          # its x' and z
        st = np.arange(s * n, (s + 1) * n)                # its B, its C
        hd = np.arange(s * per, (s + 1) * per)            # its heads
        conv = np.concatenate([ch, inner + st, inner + g * n + st])
        cols = np.concatenate([ch, inner + conv,
                               2 * inner + 2 * g * n + hd])
        p = {"Win": whole["Win"][:, cols], "conv": whole["conv"][:, conv],
             "conv_b": whole["conv_b"][conv], "dt_bias": whole["dt_bias"][hd],
             "A_log": whole["A_log"][hd], "D": whole["D"][hd],
             "norm": whole["norm"][ch], "Wout": whole["Wout"][ch]}
        out, _ = layer.apply(p, {}, x, train=True)
        _close(out, REF.mamba2(cut, p, x), 2e-5)    # a slice is a layer
        total = total + out
    _close(total, want, 2e-5)


def test_the_eight_head_slices_of_an_attention_add_up():
    """4 slices read key/value head 0 and 4 read head 1, each with its own
    query heads and its rows of W_o."""
    mh, kv, d = REF._heads(WHOLE)
    whole, x = _mixer_params(WHOLE, "*"), _x(6)
    want = REF.attention(WHOLE, whole, x)
    per = mh // 8
    layer = _attention({**WHOLE, "num_attention_heads": per,
                        "num_key_value_heads": 1})
    total = 0.0
    for s in range(8):
        q = np.arange(s * per * d, (s + 1) * per * d)
        of = (s * per) // (mh // kv)
        k = np.arange(of * d, (of + 1) * d)
        p = {"Wq": whole["Wq"][:, q], "Wk": whole["Wk"][:, k],
             "Wv": whole["Wv"][:, k], "Wo": whole["Wo"][q]}
        total = total + layer.apply(p, {}, x, train=True)[0]
    _close(total, want, 2e-5)


def test_the_sixty_four_expert_shares_add_up_with_the_shared_expert_once():
    """``y = shared + sum over the chips of (what each chip's expert adds
    through W_up)``: router, latent projections and shared expert are whole
    on every chip, so each share's result holds the shared expert's part,
    which is counted ONCE; a token none of whose experts a chip holds gets
    the shared expert's part alone from that chip."""
    whole = _mixer_params(WHOLE, "E")
    x = _x(7, t=64, batch=1)
    flat = x.reshape(-1, F)
    want = REF.latent_moe(WHOLE, whole, x)
    shared = REF.shared_expert(WHOLE, whole, flat)
    idx = np.asarray(REF.routing(WHOLE, whole, flat)[0])
    total = np.zeros(flat.shape, np.float32)
    for e in range(64):
        layer = _experts(WHOLE, experts_held=(e, e + 1))
        p = {**whole, "W1": whole["W1"][e:e + 1], "W2": whole["W2"][e:e + 1]}
        out, _ = layer.apply(p, _state(layer, 64), x, train=True)
        part = np.asarray(out[0]) - np.asarray(shared)
        unheld = ~np.any(idx == e, axis=-1)
        assert np.abs(part[unheld]).max(initial=0.0) <= 1e-6
        total += part
    _close(shared + total, want[0], 2e-5)
    # 6 of 64 a token: every token counted six times over the shares
    assert (np.bincount(idx.reshape(-1), minlength=64).sum()
            == 6 * flat.shape[0])
