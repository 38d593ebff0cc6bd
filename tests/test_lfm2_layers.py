"""The pieces the LFM2 mixture-of-experts family forced: grouped key/value
heads, q/k norms and a rotation base in `MultiHeadAttention`, the gated
short convolution, an expert layer with no shared expert held in part, a
head tied to the embedding; see `_lfm2_common.py`."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import (
    GatedShortConv, MoEFeedForward, MultiHeadAttention, RnnOutputLayer,
    TransformerBlock,
)
from deeplearning4j_tpu.nn.layers.attention import (
    _merge_heads, _split_heads, dot_product_attention, rope,
)
from deeplearning4j_tpu.nn.layers.linear_attention import causal_conv

from _lfm2_common import CFG, KINDS, REF, T
from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _close, with_gradients,
)

_PER_EXPERT = ("Wgate", "Wup", "Wdown")


def _leaves_close(got, want, tol):
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(a - b)).max() <= tol * max(
            np.abs(np.asarray(b)).max(), 1e-7), jax.tree_util.keystr(path)


# ------------------------------------------------ the gated short convolution
def test_gated_short_convolution_against_a_loop_over_positions():
    """``y_t = (C_t * sum_j w_j * (B*u)_{t-j}) W_out`` written position
    by position with the two previous rows as its whole state, against
    the layer; zeros stand before position 0."""
    layer = GatedShortConv(n_out=16, conv_kernel=3)
    p, state = layer.init(jax.random.PRNGKey(0), InputType.recurrent(16, 24))
    assert {k: v.shape for k, v in p.items()} == {
        "Win": (16, 48), "conv": (3, 16), "Wout": (16, 16)}
    assert float(jnp.abs(p["conv"]).max()) <= 3 ** -0.5
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 16))
    got, _ = layer.apply(p, state, x)
    w = np.asarray(p["conv"])[::-1]          # w_j meets z_{t-j}
    want = np.zeros((2, 24, 16), np.float32)
    for b in range(2):
        rows = [np.zeros(16, np.float32)] * 2    # z_{t-1}, z_{t-2}
        for t in range(24):
            bcu = np.asarray(x[b, t] @ p["Win"])
            z = bcu[:16] * bcu[32:]
            c = w[0] * z + w[1] * rows[0] + w[2] * rows[1]
            want[b, t] = np.asarray((bcu[16:32] * c) @ p["Wout"])
            rows = [z, rows[0]]
    np.testing.assert_allclose(got, want, atol=2e-6)
    # causal: a later token moves no earlier position
    moved = x.at[:, 17].add(1.0)
    again, _ = layer.apply(p, state, moved)
    np.testing.assert_array_equal(again[:, :17], got[:, :17])
    assert float(jnp.abs(again[:, 17:20] - got[:, 17:20]).min(axis=-1)
                 .max()) > 1e-4
    np.testing.assert_array_equal(again[:, 20:], got[:, 20:])   # 3 taps
    with pytest.raises(NotImplementedError):
        layer.apply(p, state, x, mask=jnp.ones((2, 24)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gated_short_convolution_is_the_references(dtype):
    """The layer and every parameter's gradient against the reference's
    `short_conv` at the test widths; in bfloat16 the gates and taps still
    run in float32 (one rounding of the mixed row)."""
    p = REF.make_params(CFG)["layer0"]["attn"]
    layer = GatedShortConv(n_out=32, conv_kernel=3)
    p0, state = layer.init(jax.random.PRNGKey(0), InputType.recurrent(32, T))
    assert {k: v.shape for k, v in p0.items()} \
        == {k: v.shape for k, v in p.items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, T, 32))
    ref = lambda p, x: REF.short_conv(CFG, p, x)
    if dtype == "bfloat16":
        cast = lambda t: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), t)
        got = layer.apply(cast(p), state, cast(x))[0]
        assert got.dtype == jnp.bfloat16
        _close(got.astype(jnp.float32), ref(p, x), 3e-2)
        return
    run = lambda p, x: layer.apply(p, state, x)[0]
    _close(run(p, x), ref(p, x), 2e-6)
    got = jax.grad(lambda p, x: jnp.sum(run(p, x) * w), (0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(ref(p, x) * w), (0, 1))(p, x)
    _leaves_close(got, want, 2e-5)
    # the taps the other way round are another function: a planted fault
    bad = REF.short_conv(CFG, p, x, fault="taps_reversed")
    _close(run({**p, "conv": p["conv"][::-1]}, x), bad, 2e-6)
    assert float(jnp.abs(bad - run(p, x)).max()) > 1e-2


def test_kdas_short_convolution_and_lfm2s_are_one_helper():
    """`causal_conv` (the last tap meets the current position) serves
    KDA's q, k, v branches and the gated short convolution."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2))
    taps = jnp.asarray([[1.0, 10.0], [2.0, 20.0], [3.0, 30.0]])
    y = causal_conv(x, taps)
    np.testing.assert_allclose(y[0, 0], taps[2] * x[0, 0], rtol=1e-6)
    np.testing.assert_allclose(
        y[0, 5], taps[2] * x[0, 5] + taps[1] * x[0, 4] + taps[0] * x[0, 3],
        rtol=1e-6)


# ----------------------------------------------------- grouped-query attention
def _gqa(**over):
    return MultiHeadAttention(**{**dict(
        n_out=32, n_heads=8, n_kv_heads=2, causal=True, use_rope=True,
        rope_base=100.0, qk_norm=True, norm_epsilon=1e-5, has_bias=False,
        attention_impl="flash", block_size=32), **over})


def test_grouped_query_attention_is_the_references():
    """8 query heads on 2 key/value heads of 4, q and k normed over the
    head width before the rotation, base 100: the layer and every
    parameter's gradient against the reference's attention; each of the
    four faults the benchmark plants here is another function."""
    p = REF.make_params(CFG)["layer1"]["attn"]
    layer = _gqa()
    p0, state = layer.init(jax.random.PRNGKey(0), InputType.recurrent(32, T))
    assert {k: v.shape for k, v in p0.items()} \
        == {k: v.shape for k, v in p.items()}
    assert p0["Wk"].shape == (32, 8) and p0["q_norm"].shape == (4,)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, T, 32))
    run = lambda p, x: layer.apply(p, state, x)[0]
    ref = lambda p, x, fault=None: REF.attention(CFG, p, x, fault=fault)
    y, got = with_gradients(run, w, (p, x))
    y_ref, want = with_gradients(ref, w, (p, x))
    _close(y, y_ref, 3e-5)
    _leaves_close(got, want, 1e-4)
    for fault, twin in (("no_rope", dict(use_rope=False)),
                        ("no_qk_norm", dict(qk_norm=False))):
        off = dataclasses.replace(layer, **twin).apply(p, state, x)[0]
        _close(off, ref(p, x, fault), 3e-5)
        assert float(jnp.abs(off - y).max()) > 1e-3, fault
    assert float(jnp.abs(ref(p, x, "kv_head_mod") - y).max()) > 1e-3
    # every path of the layer groups alike (off the TPU: the XLA paths,
    # k and v repeated; the kernel itself: tests/test_flash_attention.py)
    for impl in ("dense", "blockwise"):
        _close(dataclasses.replace(layer, attention_impl=impl).apply(
            p, state, x)[0], y, 2e-5)
    masked = jnp.ones((2, T)).at[1, T - 9:].set(0.0)
    y = layer.apply(p, state, x, mask=masked)[0]
    assert float(jnp.abs(y[1, T - 9:]).max()) == 0.0


def test_query_head_h_reads_key_head_h_over_the_group():
    """Written out with the layer's own helpers: q head h against k, v
    head h // 4."""
    layer = _gqa(use_rope=False, qk_norm=False)
    p, state = layer.init(jax.random.PRNGKey(3), InputType.recurrent(32, 16))
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, 32))
    q = _split_heads(x @ p["Wq"], 8)
    k, v = _split_heads(x @ p["Wk"], 2), _split_heads(x @ p["Wv"], 2)
    heads = [dot_product_attention(q[:, :, h:h + 1], k[:, :, h // 4:h // 4 + 1],
                                   v[:, :, h // 4:h // 4 + 1], causal=True)
             for h in range(8)]
    want = _merge_heads(jnp.concatenate(heads, axis=2)) @ p["Wo"]
    _close(layer.apply(p, state, x)[0], want, 2e-6)
    with pytest.raises(ValueError, match="n_kv_heads"):
        _gqa(n_kv_heads=3).init(jax.random.PRNGKey(0),
                                InputType.recurrent(32, 16))


def _todays_layer(self, params, x):
    """`MultiHeadAttention.apply` of the parent of the PR that brought the
    key/value head count, the q/k norms and the rotation base, statement
    for statement (no bias, no mask, no dropout, the dense path)."""
    q = x @ params["Wq"]
    k = x @ params["Wk"]
    v = x @ params["Wv"]
    h = self.n_heads
    q, k, v = _split_heads(q, h), _split_heads(k, h), _split_heads(v, h)
    if self.use_rope:
        pos = (0 + jnp.arange(x.shape[1]))[None]
        q = rope(q, pos)
        k = rope(k, pos)
    out = dot_product_attention(q, k, v, mask=None, causal=self.causal,
                                dropout=0.0, rng=None)
    return _merge_heads(out) @ params["Wo"]


@pytest.mark.parametrize("use_rope", [True, False])
def test_at_its_defaults_the_layer_is_todays_bit_for_bit(use_rope):
    """``n_kv_heads`` None, ``qk_norm`` False, ``rope_base`` 1e4 (what
    `TransformerBlock` builds for `TransformerLM`): the parent's
    parameters from the same key, its result and gradients bit for bit,
    and its lowered program (the scopes are locations only)."""
    layer = MultiHeadAttention(n_out=32, n_heads=4, causal=True,
                               use_rope=use_rope)
    assert layer.n_kv_heads is None and not layer.qk_norm \
        and layer.rope_base == 10000.0
    key = jax.random.PRNGKey(3)
    p, state = layer.init(key, InputType.recurrent(32, 24))
    assert set(p) == {"Wq", "Wk", "Wv", "Wo"}
    from deeplearning4j_tpu.nn.initializers import get_initializer
    ks = jax.random.split(key, 4)
    for name, k in zip(("Wq", "Wk", "Wv", "Wo"), ks):
        np.testing.assert_array_equal(p[name], get_initializer("xavier")(
            k, (32, 32), 32, 32, jnp.float32))
    biased, _ = dataclasses.replace(layer, has_bias=True).init(
        key, InputType.recurrent(32, 24))
    assert {k: v.shape for k, v in biased.items() if k.startswith("b")} \
        == {b: (32,) for b in ("bq", "bk", "bv", "bo")}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32))

    def new(p, x):
        return jnp.sum(jnp.sin(layer.apply(p, state, x)[0]))

    def old(p, x):
        return jnp.sum(jnp.sin(_todays_layer(layer, p, x)))

    for a, b in zip(
            jax.tree_util.tree_leaves(jax.value_and_grad(new, (0, 1))(p, x)),
            jax.tree_util.tree_leaves(jax.value_and_grad(old, (0, 1))(p, x))):
        np.testing.assert_array_equal(a, b)
    text = lambda f: re.sub(r"loc\([^)]*\)", "", jax.jit(
        jax.grad(f, (0, 1))).lower(p, x).as_text())
    assert text(new).replace("jit_new", "") \
        == text(old).replace("jit_old", "")


# ------------------------------------------- experts with no shared expert
def _expert_block(lo, hi):
    ffn = MoEFeedForward(n_out=32, n_experts=16, top_k=2, hidden=24,
                         activation="swish", gated=True, has_bias=False,
                         experts_held=(lo, hi), router="sigmoid",
                         routed_scale=1.0, n_shared=0)
    return TransformerBlock(n_out=32, n_heads=8, norm="rms",
                            norm_epsilon=1e-5, has_bias=False,
                            attn=GatedShortConv(n_out=32, conv_kernel=3),
                            ffn=ffn)


def test_the_eight_shares_add_up_and_an_unheld_token_gets_exactly_zero():
    """Each of 8 chips holds 2 of the 16 experts and computes `h + sum
    over ITS experts`; with no shared expert what every chip computes
    alike is the operator and the residual, counted once, and the eight
    routed parts add up to the reference's whole layer. A token none of
    whose experts a chip holds gets EXACTLY zero from that chip's expert
    layer: only the residual goes on there."""
    cfg = {**CFG, "experts_held": [0, 16], "num_experts": 16}
    whole = REF.make_params(cfg)["layer2"]
    kind, ffn = KINDS[2]
    assert (kind, ffn) == ("conv", "experts")
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    want = REF.layer(cfg, whole, x, kind, ffn)

    def run(lo, hi):
        p = dict(whole, ffn={k: (v[lo:hi] if k in _PER_EXPERT else v)
                             for k, v in whole["ffn"].items()})
        blk = _expert_block(lo, hi)
        _, state = blk.init(jax.random.PRNGKey(0),
                            InputType.recurrent(32, T))
        y, new = blk.apply(p, state, x)
        return y, new["ffn"]

    # what every chip computes alike: the layer with no expert's output
    eps = CFG["norm_eps"]
    alike = x + REF.short_conv(
        cfg, whole["attn"], REF._rms(x, whole["ln1"]["gamma"], eps))
    shares = [run(lo, lo + 2) for lo in range(0, 16, 2)]
    got = alike + sum(y - alike for y, _ in shares)
    _close(got, want, 3e-5)
    assert float(jnp.abs(want - alike).max()) > 1e-2   # the experts matter
    # one share alone is the reference told to hold the same experts
    held = dict(whole, ffn={k: (v[4:6] if k in _PER_EXPERT else v)
                            for k, v in whole["ffn"].items()})
    _close(shares[2][0], REF.layer(cfg, held, x, kind, ffn, held=(4, 6)),
           3e-5)
    # the tokens with no held expert: exactly zero from the expert layer,
    # in the program and in the reference alike, and the counter has the rest
    normed = REF._rms(alike, whole["ln2"]["gamma"], eps).reshape(-1, 32)
    idx, _ = REF.routing(cfg, whole["ffn"], normed)
    without = ~np.any((np.asarray(idx) >= 4) & (np.asarray(idx) < 6), axis=-1)
    assert 0.3 < without.mean() < 0.95
    ffn = _expert_block(4, 6).ffn
    _, state = ffn.init(jax.random.PRNGKey(0), InputType.recurrent(32, T))
    out, new = ffn.apply(held["ffn"], state, normed.reshape(2, T, 32))
    out = np.asarray(out).reshape(-1, 32)
    assert np.abs(out[without]).max() == 0.0
    assert np.abs(out[~without]).max(axis=-1).min() > 0.0
    part = np.asarray(REF.experts({**cfg, "experts_held": [4, 6],
                                   "num_experts": 2}, held["ffn"], normed))
    assert np.abs(part[without]).max() == 0.0
    assert int(new["tokens_with_held_pair_total"]) == int((~without).sum())
    assert int(shares[2][1]["tokens_with_held_pair_total"]) \
        == int((~without).sum())


@pytest.mark.parametrize("held,kept", [((0, 8), True), ((8, 16), True),
                                       ((0, 32), True), (None, False),
                                       ((0, 64), False)])
def test_a_share_counts_its_tokens_with_a_held_pair(held, kept):
    """A layer that holds a share of its experts counts the tokens it
    adds anything to (uint32 in its state, beside `tokens_routed_total`);
    a layer that holds them all has no such counter: it would count every
    token."""
    ffn = MoEFeedForward(n_out=16, n_experts=64, top_k=4, hidden=8,
                         activation="swish", gated=True, has_bias=False,
                         experts_held=held, router="sigmoid")
    p, state = ffn.init(jax.random.PRNGKey(0), InputType.recurrent(16, 32))
    assert ("tokens_with_held_pair_total" in state) == kept
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
    _, new = ffn.apply(p, state, x)
    _, new = ffn.apply(p, new, x)
    if not kept:
        assert "tokens_with_held_pair_total" not in new
        return
    idx, _ = ffn.route(p, state, x)
    lo, hi = held
    with_pair = int(np.any((np.asarray(idx) >= lo) & (np.asarray(idx) < hi),
                           axis=-1).sum())
    assert new["tokens_with_held_pair_total"].dtype == jnp.uint32
    assert int(new["tokens_with_held_pair_total"]) == 2 * with_pair
    assert 0 < with_pair <= 64


# ------------------------------------------------------------- the tied head
def test_a_tied_head_projects_by_the_embeddings_transpose():
    """``tied_embedding``: W lies as the embedding's table does, (vocab,
    hidden); logits, the whole score and the blocked score are those of
    the untied head holding its transpose."""
    from deeplearning4j_tpu.nn.layers import recurrent
    tied = RnnOutputLayer(n_out=40, activation="softmax",
                          loss="sparse_mcxent", has_bias=False,
                          tied_embedding=True)
    loose = dataclasses.replace(tied, tied_embedding=False)
    p, _ = tied.init(jax.random.PRNGKey(0), InputType.recurrent(16, 12))
    assert p["W"].shape == (40, 16)
    twin = {"W": p["W"].T}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
    y = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, 40)
    keep = jnp.ones((2, 12)).at[:, -1].set(0.0)
    np.testing.assert_allclose(tied.apply(p, {}, x)[0],
                               loose.apply(twin, {}, x)[0], rtol=1e-6)
    score = lambda layer, p: layer.score(p, x, y, mask=keep)
    np.testing.assert_allclose(score(tied, p), score(loose, twin), rtol=1e-6)
    g = jax.grad(lambda p: score(tied, p))(p)["W"]
    g2 = jax.grad(lambda p: score(loose, p))(twin)["W"]
    np.testing.assert_allclose(g, g2.T, rtol=1e-5, atol=1e-8)
    whole = float(score(tied, p))
    budget = recurrent._LOSS_LIVE_BYTES
    try:                                  # blocks of 4 positions
        recurrent._LOSS_LIVE_BYTES = 4 * 40 * 8
        np.testing.assert_allclose(score(tied, p), whole, rtol=1e-6)
        np.testing.assert_allclose(
            jax.grad(lambda p: score(tied, p))(p)["W"], g, rtol=1e-5,
            atol=1e-8)
    finally:
        recurrent._LOSS_LIVE_BYTES = budget
