"""The expert layer of the hybrid LM: the chip's share, the dispatch, the
routers and the routing counters; see `_kimi_common.py`."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import (
    GatedMLP, KimiDeltaAttention, MoEFeedForward, MultiHeadLatentAttention,
    RnnOutputLayer, TransformerBlock,
)

from _kimi_common import CFG, FAMILY, KINDS, REF, SYSTEM, T, _layer_params
from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _close, _rows, jit_unoptimised,
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
        # compiled, a share a program (op by op KDA's unrolled tile is
        # thousands of dispatches)
        return jit_unoptimised(lambda p, x: blk.apply(p, state, x)[0])(p, x)

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
    ffn = MoEFeedForward(n_out=16, n_experts=8, top_k=2, hidden=8,
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
    np.testing.assert_array_equal(state["tokens_routed"],
                                  [36, 36, 0, 0, 0, 0, 0, 0])
    # all 72 pairs are held, so the dispatch walked its full tier (the
    # small one, twice the balanced load of 2 experts in 8, is half), and
    # counted it
    assert ffn.tier_names() == ("1/2", "1/1")
    np.testing.assert_array_equal(state["tier_hits"], [0, 1])
    assert state["tier_hits"].dtype == np.uint32
    assert int(state["rows_walked_total"]) == 36 * 2
    # the layer has no capacity and no counter of dropped pairs: none can
    assert not any("drop" in k for k in state)
    assert not any("capacity" in f.name
                   for f in dataclasses.fields(MoEFeedForward))


@pytest.mark.parametrize("n_experts,held,tier,rows", [
    (16, (2, 5), 1, 60), (32, (2, 3), 0, 7)])
def test_undefined_rows_of_a_grouped_product_reach_no_sum(
        monkeypatch, n_experts, held, tier, rows):
    """Behind the last group a grouped product's rows are undefined: the
    CPU writes zeros there, the TPU's kernel nothing (whatever the buffer
    held). With NaN in every such row, of the products and of their
    transposes alike, the layer's result and gradients are the same
    finite numbers: on the full tier (3 of 16 experts held and a choice
    bent towards them, so that the held pairs pass the small tier's 60
    rows: the 120 pairs in two parts of 60 rows) and on the small one (1
    of 32: 7 rows)."""
    from deeplearning4j_tpu.nn.layers import attention
    real = attention._grouped_matmul
    poisoned_rows = []

    def poison(a, sizes):
        rows = jnp.arange(a.shape[0])[:, None]
        poisoned_rows.append(a.shape[0])
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
    ffn = MoEFeedForward(n_out=16, n_experts=n_experts, top_k=3, hidden=8,
                         activation="swish", gated=True, has_bias=False,
                         experts_held=held, router="sigmoid", n_shared=1)
    p, state = ffn.init(jax.random.PRNGKey(0), InputType.recurrent(16, 20))
    if tier:        # most tokens choose the held experts: the whole tier
        state["route_bias"] = state["route_bias"].at[held[0]:held[1]].set(
            1.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 16))
    loss = lambda p, x: jnp.sum(ffn.apply(p, state, x)[0] ** 2)
    want = jax.value_and_grad(loss, (0, 1))(p, x)
    counted = ffn.apply(p, state, x)[1]
    np.testing.assert_array_equal(
        counted["tier_hits"], np.arange(len(ffn._tier_divisors())) == tier)
    assert 0 < counted["tokens_routed"][held[0]:held[1]].sum()
    # the tiers are jitted with the product as an argument: the trace made
    # above with the real one is not the poisoned one's
    monkeypatch.setattr(attention, "_grouped_matmul", poisoned)
    got = jax.value_and_grad(loss, (0, 1))(p, x)
    assert ffn._tiers(120) == ((60, 120) if tier
                               else (7, 15, 30, 60, 120))
    assert rows in poisoned_rows
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=1e-6)


# ------------------------------------------------------------------ the tiers
_TIER_N, _TIER_K = 32, 4          # 128 pairs a dispatch: tiers 8, 16 .. 128
_TIERS = (8, 16, 32, 64, 128)


@functools.lru_cache(maxsize=None)
def _tiered_and_whole(router, gated):
    """A layer that holds 8 of its 256 experts, and two compiled functions
    of (params, x, idx): the layer's result, counts and gradients under
    the routing ``idx`` with the router's own weights, through the tiers
    and through the full tier alone (the parent's dispatch)."""
    from deeplearning4j_tpu.nn.layers import attention
    ffn = MoEFeedForward(n_out=16, n_experts=256, top_k=_TIER_K, hidden=8,
                         activation="swish" if gated else "gelu",
                         gated=gated, has_bias=not gated,
                         experts_held=(4, 12), router=router,
                         routed_scale=1.7)
    p, state = ffn.init(jax.random.PRNGKey(0),
                        InputType.recurrent(16, _TIER_N))
    if not gated:
        p["b1"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3),
                                          p["b1"].shape)
        p["b2"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                          p["b2"].shape)

    def run(p, x, idx):
        def loss(p, x):
            out, counts = ffn.experts(p, x, idx, ffn.route(p, state, x)[1])
            return jnp.sum(out * jnp.cos(out)), (out, counts)
        (_, (out, counts)), grads = jax.value_and_grad(
            loss, (0, 1), has_aux=True)(p, x)
        return out, counts, grads

    def whole(p, x, idx):
        divisors = MoEFeedForward._tier_divisors
        MoEFeedForward._tier_divisors = lambda self: (1,)  # while tracing
        try:
            return run(p, x, idx)
        finally:
            MoEFeedForward._tier_divisors = divisors

    return ffn, p, jax.jit(run), jax.jit(whole)


def _routing_with(live, seed):
    """(_TIER_N, _TIER_K) expert ids, distinct within a token, of which
    exactly ``live`` lie among the held experts 4..11."""
    rng = np.random.default_rng(seed)
    per_token = np.zeros(_TIER_N, int)
    for _ in range(live):
        per_token[rng.choice(np.flatnonzero(per_token < _TIER_K))] += 1
    held, others = np.arange(4, 12), np.r_[0:4, 12:16]
    idx = np.stack([rng.permutation(np.r_[
        rng.choice(held, c, replace=False),
        rng.choice(others, _TIER_K - c, replace=False)])
        for c in per_token])
    assert ((idx >= 4) & (idx < 12)).sum() == live
    return jnp.asarray(idx, jnp.int32)


@pytest.mark.parametrize("live", [0, 1, 7, 8, 9, 63, 64, 65, 128])
@pytest.mark.parametrize("router,gated", [
    ("sigmoid", True), ("softmax", True), ("sigmoid", False),
    ("softmax", False)])
def test_every_tier_gives_the_full_tiers_result_and_gradients(router, gated,
                                                              live):
    """Around the small tier's edge (M - 1, M and M + 1 live pairs of the
    128), around the edge between the two parts the full tier walks its
    tokens in, and at both ends, the dispatch walks the smallest tier
    that holds its pairs, counts it, and gives what the full tier alone
    gives (the parent's dispatch): the result and the gradients of the
    inputs, the expert weights and the router."""
    ffn, p, tiered, whole = _tiered_and_whole(router, gated)
    assert ffn._tiers(_TIER_N * _TIER_K) == _TIERS
    x = jax.random.normal(jax.random.PRNGKey(live), (1, _TIER_N, 16))
    idx = _routing_with(live, seed=live)
    out, counts, grads = tiered(p, x, idx)
    want, base, want_grads = whole(p, x, idx)
    tier = sum(live > rows for rows in _TIERS[:-1])
    np.testing.assert_array_equal(counts["tier_hits"], np.arange(5) == tier)
    assert int(counts["rows_walked"]) == _TIERS[tier]
    # one tier counts neither tier nor rows; both count, alike, what was
    # routed and the tokens with a pair held here
    assert set(base) == {"tokens_routed", "tokens_with_held_pair"}
    for name in base:
        np.testing.assert_array_equal(counts[name], base[name])
    assert int(base["tokens_with_held_pair"]) <= min(live, _TIER_N)
    np.testing.assert_allclose(out, want, atol=2e-6)
    assert float(jnp.abs(want).max()) > 0.1 or not live
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=str(path))
    assert float(jnp.abs(want_grads[0]["Wr"]).max()) > 1e-3 or not live


@pytest.mark.parametrize("n_experts,held,top_k,routing", [
    (n_experts, held, top_k, routing)
    for n_experts, held in [(64, (5, 6)), (64, (8, 16)), (64, (3, 19)),
                            (24, (0, 24)), (40, (0, 40))]
    for top_k in (1, 4, 22)
    for routing in ("mixed", "mixed, whole blocks", "no pair held",
                    "every pair held", "one expert draws everything")
    # a layer that holds every expert holds every pair
    if held != (0, n_experts) or routing != "no pair held"])
def test_the_counts_are_bincounts(n_experts, held, top_k, routing):
    """The groups' sizes and the routed counts the dispatch takes from
    the product of the keys' one-hots are what `jnp.bincount` gave,
    exactly: 1, 8 and 16 experts held of 64, all 24 of 24 and all 40 of
    40 (41 buckets: a second digit), keys that fill whole blocks of 512
    and keys that do not."""
    from deeplearning4j_tpu.nn.layers.attention import _counts
    lo, hi = held
    e = hi - lo
    n = 512 if routing == "mixed, whole blocks" else 75
    pairs = n * top_k
    rng = np.random.default_rng(n_experts + 7 * e + top_k)
    others = np.r_[0:lo, hi:n_experts]
    flat = {
        "mixed": lambda: rng.integers(0, n_experts, pairs),
        "mixed, whole blocks": lambda: np.where(
            rng.random(pairs) < 0.3, rng.integers(lo, hi, pairs),
            rng.integers(0, n_experts, pairs)),
        "no pair held": lambda: rng.choice(others, pairs),
        "every pair held": lambda: rng.integers(lo, hi, pairs),
        "one expert draws everything": lambda: np.full(pairs, hi - 1),
    }[routing]()
    flat = jnp.asarray(flat, jnp.int32)
    local = jnp.where((flat >= lo) & (flat < hi), flat - lo, e)
    counts = jax.jit(_counts, static_argnums=1)
    for keys, buckets in ((local, e + 1), (flat, n_experts)):
        got = counts(keys, buckets)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(got, jnp.bincount(keys, length=buckets))


def _the_parents_apply(ffn, params, state, x):
    """`MoEFeedForward.apply` of a layer that holds every expert, as the
    parent of the PR that brought the tiers had it (one dispatch, no
    shared expert, no mask), statement for statement, with the
    `jnp.bincount`s the dispatch took its counts from: the independent
    reference the dispatch is held to, bit for bit."""
    from deeplearning4j_tpu.nn.activations import get_activation
    from deeplearning4j_tpu.nn.layers.attention import (
        _grouped_matmul, _rows_to_experts, _rows_to_tokens)
    self = ffn
    idx, w = self.route(params, state, x)
    shape = x.shape
    h = x.reshape(-1, shape[-1])
    lo, hi = self._held()
    e = hi - lo
    k = self.top_k
    n = h.shape[0]
    with jax.named_scope("moe/dispatch"):
        flat = idx.reshape(-1)
        here = (flat >= lo) & (flat < hi)
        local = jnp.where(here, flat - lo, e)
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        sizes = jnp.bincount(local, length=e + 1)[:e]
        routed = jnp.bincount(flat, length=self.n_experts)
        in_group = (jnp.arange(n * k) < sizes.sum())[:, None]
        if self.has_bias and not self.gated:
            of_row = jnp.minimum(local[order], e - 1)
        live = lambda a: jnp.where(in_group, a, 0).astype(h.dtype)
        xs = live(_rows_to_experts(h, order, inverse))
    with jax.named_scope("moe/experts"):
        act = get_activation(self.activation)
        if self.gated:
            mid = act(live(_grouped_matmul(xs, params["Wgate"], sizes))) \
                * live(_grouped_matmul(xs, params["Wup"], sizes))
        else:
            mid = _grouped_matmul(xs, params["W1"], sizes)
            if self.has_bias:
                mid = mid + params["b1"][of_row]
            mid = act(live(mid))
        ys = _grouped_matmul(
            live(mid), params["Wdown" if self.gated else "W2"], sizes)
        if self.has_bias and not self.gated:
            ys = ys + params["b2"][of_row]
        ys = live(ys)
    with jax.named_scope("moe/combine"):
        per_slot = _rows_to_tokens(ys, order, inverse).reshape(n, k, -1)
        wk = jnp.where(here.reshape(n, k), w, 0)
        out = jnp.einsum("nkf,nk->nf", per_slot, wk.astype(w.dtype),
                         preferred_element_type=w.dtype)
    out, routed = out.astype(h.dtype).reshape(shape), routed.astype(jnp.int32)
    return out, {**state, "tokens_routed": routed,
                 "tokens_routed_total": state["tokens_routed_total"]
                 + routed.astype(jnp.uint32)}


@pytest.mark.parametrize("conf", [
    dict(n_experts=4, top_k=2, mlp_ratio=2),
    dict(n_experts=6, top_k=3, hidden=8, gated=True, has_bias=False,
         activation="swish", router="sigmoid", experts_held=(0, 6))])
def test_a_layer_that_holds_every_expert_gives_the_parents_results(conf):
    """Such a layer holds every pair of every dispatch: it has one tier,
    builds no switch, keeps no tier counter and gives, forward and
    backward, what the parent's layer gave, bit for bit: the counts are
    the `jnp.bincount`s', so every tensor behind them is the same
    tensor, with no scatter-add to make them."""
    ffn = MoEFeedForward(n_out=16, **conf)
    p, state = ffn.init(jax.random.PRNGKey(2), InputType.recurrent(16, 10))
    assert set(state) - {"route_bias"} == {"tokens_routed",
                                           "tokens_routed_total"}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 10, 16))

    def program(apply):
        def run(p, state, x):
            loss = lambda p, x: (lambda out, new: (
                jnp.sum(out ** 2), (out, new)))(*apply(p, state, x))
            return jax.value_and_grad(loss, (0, 1), has_aux=True)(p, x)
        return jax.jit(run)

    got = program(ffn.apply)(p, state, x)
    want = program(functools.partial(_the_parents_apply, ffn))(p, state, x)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want), strict=True):
        assert a.dtype == b.dtype, path
        np.testing.assert_array_equal(a, b, err_msg=str(path))
    assert float(jnp.abs(want[1][0]["Wr"]).max()) > 1e-3
    text = jax.jit(ffn.apply).lower(p, state, x).as_text()
    assert "stablehlo.scatter" not in text and "stablehlo.case" not in text
    assert "stablehlo.scatter" in jax.jit(functools.partial(
        _the_parents_apply, ffn)).lower(p, state, x).as_text()
    shared = dataclasses.replace(ffn, experts_held=(1, 2))
    assert "stablehlo.case" in jax.jit(shared.apply).lower(
        *shared.init(jax.random.PRNGKey(2), InputType.recurrent(16, 10)),
        x).as_text()


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
def _gained(before, family, *labels):
    """What the counter ``family`` gained since the dump ``before``, by
    the values of ``labels``."""
    from deeplearning4j_tpu import monitor
    had = {tuple(sorted(s["labels"].items())): s["value"] for s in
           before.get(family, {}).get("series", [])}
    out = {}
    for s in monitor.dump()[family]["series"]:
        key = tuple(sorted(s["labels"].items()))
        gained = s["value"] - had.get(key, 0)
        if gained:
            out[tuple(s["labels"][name] for name in labels)] = gained
    return out


def _routed(before):
    """(token, expert) pairs ``moe_tokens_routed_total`` gained since the
    dump ``before``, by (layer, held)."""
    return _gained(before, "moe_tokens_routed_total", "layer", "held")


def _tiers_and_rows(before, state, layer, rows):
    """The dispatches of ``layer`` since the dump ``before`` by tier, as
    its state counted and the listener published them, the rows they
    walked checked against the tiers' ``rows``."""
    hits = np.asarray(state["tier_hits"], np.int64)
    assert state["tier_hits"].dtype == np.uint32
    assert int(state["rows_walked_total"]) == int((hits * rows).sum())
    tiers = _gained(before, "moe_dispatch_tier_total", "layer", "tier")
    assert [tiers.get((layer, name), 0)
            for name in ("1/2", "1/1")] == list(hits)
    assert _gained(before, "moe_rows_walked_total", "layer")[layer,] \
        == int(state["rows_walked_total"])
    return hits


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
    # 4 held of 16 routed over: the small tier is half the pairs (4 of 8,
    # the files' common size, has the whole tier alone)
    # (KDA chunks of 16, one block a tile: the step compiles in two thirds
    # of the time, and the tile is `test_kimi_attention.py`'s to hold)
    net, cfg = FAMILY.net(learning_rate=0.0, weight_decay=0.0, router_experts=16,
                    kda_chunk=16)
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
        # 4 batches in 4 dispatches of 64 tokens, 128 pairs each of which
        # about a quarter are held: every dispatch counts the tier it
        # walked
        hits = _tiers_and_rows(before, net.state[layer]["ffn"], layer,
                               (64, 128))
        assert hits.sum() == 4 * 4 and hits[0] > 0
    # the host's count for layer 2 (rate 0: the weights stay the seed's)
    params = REF.make_params(cfg)
    want = np.zeros(16, np.int64)
    for r, _ in rows:
        x = params["0"]["W"][jnp.asarray(REF.decode_tokens(cfg, r))]
        h = REF.layer(cfg, params["1"], x, KINDS[0])
        p2 = params["2"]
        h = h + REF._kda(cfg, p2["attn"], REF._rms(
            h, p2["ln1"]["gamma"], 1e-5), "highest")
        n = REF._rms(h, p2["ln2"]["gamma"], 1e-5).reshape(-1, 32)
        want += np.bincount(np.asarray(REF.routing(cfg, p2["ffn"], n)[0])
                            .ravel(), minlength=16)
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
    g.add_layer("moe", MoEFeedForward(n_out=16, n_experts=8, top_k=2,
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
    # one dispatch a step of 48 tokens x 2: tiers of 48 and 96 rows
    assert _tiers_and_rows(before, net.state["moe"], "moe",
                           (48, 96)).sum() == 2


def test_the_states_total_wraps_and_the_listener_takes_it_modulo():
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.train.listeners import ExpertLoadListener
    # the net of the per-call case above, so that its step is compiled once
    # for both (the second finds it in the compile cache)
    net, cfg = FAMILY.net(learning_rate=0.0, weight_decay=0.0, router_experts=16,
                    kda_chunk=16)
    near = jax.device_put(jnp.full((16,), 2 ** 32 - 5, jnp.uint32),
                          jax.devices()[0])
    for layer in ("2", "3", "4", "5"):
        net.state[layer]["ffn"]["tokens_routed_total"] = near
    net.set_listeners(ExpertLoadListener())
    before = monitor.dump()
    net.fit(SYSTEM.feed(_rows(6, 1)), scan_steps=1)
    assert sum(_routed(before).values()) == 4 * 256 * 2
