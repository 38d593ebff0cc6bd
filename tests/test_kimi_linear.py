"""The hybrid LM (`KimiLinearLM`: Kimi Delta Attention three layers to one
of position-free latent attention, a dense SwiGLU layer then sigmoid-routed
SwiGLU experts held in part beside a shared expert, RMSNorm, a blocked
sparse loss) against the benchmark's plain reference at tiny widths on the
CPU in float32, and the pieces it is made of.

The reference (`benchmark/references/kimi-linear-48b-a3b.py`) imports
nothing of the program; weights are the reference's seeded ones.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib.manifest import load_module
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterator import ExistingDataSetIterator
from deeplearning4j_tpu.models import TransformerLMMoE
from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import (
    GatedMLP, KimiDeltaAttention, MoEFeedForward, MultiHeadAttention,
    MultiHeadLatentAttention, RMSNormLayer, RnnOutputLayer, TransformerBlock,
)
from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
from deeplearning4j_tpu.nn.layers.linear_attention import kda_chunked

REF = load_module("references", "kimi-linear-48b-a3b")
SYSTEM = load_module("systems", "dl4j_fit_kimi_linear")

#: the published keys at widths a CPU test can run: the five layers of the
#: cut (KDA+dense, KDA, KDA, MLA, KDA with experts), T = 128 over KDA chunks
#: of 32, 8 experts routed over of which 4 are held, 2 a token
CFG = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 24,
    "num_attention_heads": 4, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "q_lora_rank": None,
    "mla_use_nope": True, "hidden_act": "silu", "moe_layer_freq": 1,
    "linear_attn_config": {"full_attn_layers": [4], "head_dim": 16,
                           "kda_layers": [1, 2, 3, 5], "num_heads": 4,
                           "short_conv_kernel_size": 4},
    "kda_low_rank": 8, "kda_chunk": 32,
    "first_k_dense_replace": 1, "num_hidden_layers": 5,
    "router_experts": 8, "num_experts": 4, "experts_held": [2, 6],
    "num_experts_per_token": 2, "num_shared_experts": 1,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "num_expert_group": 1, "routed_scaling_factor": 2.446,
    "vocab_size": 96, "rms_norm_eps": 1e-5,
    "image_size": 8, "channels": 4, "num_classes": 1, "zipf_s": 1.0,
    "attention_block": 32,
    "updater": "adamw", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
    "epsilon": 1e-8, "weight_decay": 0.1, "weights_seed": 3,
    "embedding_std": 1.0, "matrix_std": 0.2, "out_proj_std": 0.1,
    "compute_dtype": None, "gradient_checkpointing": True,
}
T = REF.seq_length(CFG)        # 128
KINDS = REF.layer_kinds(CFG)


@pytest.fixture(autouse=True)
def _budgets_at_the_tests_sizes(monkeypatch):
    """The layers work out from their shapes how much goes through at
    once; at the tests' sizes everything would. The budgets are cut so
    that the whole model (2 x 128 tokens, 8 (sequence, head) pairs) takes
    the paths the cell's sizes take: 2 groups of pairs, 4 dispatches of 64
    tokens, loss blocks of 64 positions."""
    from deeplearning4j_tpu.nn.layers import (
        attention, linear_attention, recurrent,
    )
    monkeypatch.setattr(linear_attention, "_SCAN_LIVE_BYTES",
                        4 * 20 * 128 * 16 * 4)
    monkeypatch.setattr(attention, "_DISPATCH_LIVE_BYTES",
                        64 * 2 * (2 * 32 + 2 * 24) * 4)
    monkeypatch.setattr(recurrent, "_LOSS_LIVE_BYTES", 64 * 96 * 8)


def _rows(seed, n, batch=2):
    rng = np.random.default_rng(seed)
    return [(np.frombuffer(rng.bytes(batch * 8 * 8 * 4), np.uint8).reshape(
        batch, 8, 8, 4), np.zeros((batch, 1), np.float32))
        for _ in range(n)]


def _net(cfg=CFG, **over):
    cfg = {**cfg, **over}
    return SYSTEM.build(cfg, REF.make_params(cfg)), cfg


def _batch(cfg, rows):
    ids = REF.decode_tokens(cfg, rows)
    nxt, keep = REF.targets(ids)
    return ids, nxt, keep


def _close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-30)


# ------------------------------------------------------------------------ KDA
def _kda_inputs(t, seed=0, b=2, h=3, dk=8, dv=8, decay=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    return (unit(jax.random.normal(ks[0], (b, t, h, dk))),
            unit(jax.random.normal(ks[1], (b, t, h, dk))),
            jax.random.normal(ks[2], (b, t, h, dv)),
            -decay * jnp.exp(jax.random.normal(ks[3], (b, t, h, dk)) - 1),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))))


@pytest.mark.parametrize("t,chunk", [(64, 32), (50, 32), (130, 64),
                                     (16, 16), (96, 8)])
def test_kda_chunked_is_the_token_recurrence(t, chunk, monkeypatch):
    """Output, final state and the gradient of every input, at sequence
    lengths that are and are not a multiple of the chunk."""
    args = _kda_inputs(t)
    o, s = kda_chunked(*args, chunk=chunk)
    o_ref, s_ref = REF.kda_recurrence(*args, segment=16)
    _close(o, o_ref, 2e-5)
    _close(s, s_ref, 2e-5)
    # the (sequence, head) pairs in 3 groups, one after another: the same
    from deeplearning4j_tpu.nn.layers import linear_attention
    monkeypatch.setattr(linear_attention, "_SCAN_LIVE_BYTES",
                        2 * 40 * t * 8 * 4)
    for got, want in zip(kda_chunked(*args, chunk=chunk), (o, s)):
        _close(got, want, 1e-6)
    w = jax.random.normal(jax.random.PRNGKey(7), o.shape)
    loss = lambda fn: lambda *a: (lambda o, s: jnp.sum(o * w)
                                  + jnp.sum(s * s))(*fn(*a))
    got = jax.grad(loss(lambda *a: kda_chunked(*a, chunk=chunk)),
                   (0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(lambda *a: REF.kda_recurrence(*a, segment=16)),
                    (0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
        _close(a, b, 5e-5)


def test_kda_chunked_takes_no_positive_exponent():
    """A decay of e^-300 a step: every exponent the chunked form takes is
    <= 0, so nothing overflows and the numbers are the recurrence's."""
    args = _kda_inputs(64, decay=300.0)
    assert float(args[3].min()) < -200
    o, s = kda_chunked(*args, chunk=32)
    o_ref, s_ref = REF.kda_recurrence(*args, segment=16)
    assert np.isfinite(np.asarray(o)).all()
    _close(o, o_ref, 2e-5)
    _close(s, s_ref, 2e-5)
    g = jax.grad(lambda *a: jnp.sum(kda_chunked(*a, chunk=32)[0]),
                 (0, 1, 2, 3, 4))(*args)
    assert all(np.isfinite(np.asarray(x)).all() for x in g)


def test_kda_chunked_hands_a_state_over():
    """Two calls, the second given the first's state, are one call."""
    args = _kda_inputs(96)
    o, s = kda_chunked(*args, chunk=32)
    head = [a[:, :40] for a in args]
    tail = [a[:, 40:] for a in args]
    o1, s1 = kda_chunked(*head, chunk=32)
    o2, s2 = kda_chunked(*tail, chunk=32, initial_state=s1)
    _close(jnp.concatenate([o1, o2], axis=1), o, 2e-5)
    _close(s2, s, 2e-5)


def _layer_params(i):
    return REF.make_params(CFG)[str(i)]


@pytest.mark.parametrize("t", [128, 77])
def test_kda_layer_and_every_parameters_gradient(t):
    """The layer (projections, convolutions, gates, chunked recurrence,
    gated norm, output) against the reference's, with the gradient of
    every parameter and of the input."""
    p = _layer_params(2)["attn"]
    kda = KimiDeltaAttention(n_out=32, n_heads=4, head_dim=16, low_rank=8,
                             chunk=32)
    p0, _ = kda.init(jax.random.PRNGKey(0), InputType.recurrent(32, t))
    assert {k: v.shape for k, v in p0.items()} == \
        {k: v.shape for k, v in p.items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, t, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, t, 32))
    prog = lambda p, x: jnp.sum(kda.apply(p, {}, x)[0] * w)
    ref = lambda p, x: jnp.sum(REF._kda(CFG, p, x, "highest") * w)
    _close(kda.apply(p, {}, x)[0], REF._kda(CFG, p, x, "highest"), 2e-5)
    got, want = jax.grad(prog, (0, 1))(p, x), jax.grad(ref, (0, 1))(p, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(a - b)).max() <= 1e-4 * max(
            np.abs(np.asarray(b)).max(), 1e-30), path


def test_kda_init_follows_the_familys_convention():
    kda = KimiDeltaAttention(n_out=32, n_heads=4, head_dim=16)
    p, _ = kda.init(jax.random.PRNGKey(0), InputType.recurrent(32, 8))
    assert p["Wa_up"].shape == (16, 64)          # rank = head_dim
    a = np.exp(np.asarray(p["A_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert (dt >= 0.99e-3).all() and (dt <= 0.101).all()
    assert np.abs(np.asarray(p["conv_q"])).max() <= 0.5
    with pytest.raises(NotImplementedError, match="whole sequences"):
        kda.apply(p, {}, jnp.zeros((1, 8, 32)), mask=jnp.ones((1, 8)))
    with pytest.raises(ValueError, match="power of two"):
        kda_chunked(*_kda_inputs(8), chunk=24)


# ------------------------------------------------------------------------ MLA
def test_mla_layer_and_every_parameters_gradient():
    p = _layer_params(4)["attn"]
    mla = MultiHeadLatentAttention(n_out=32, n_heads=4, nope_dim=16,
                                   rope_dim=8, v_dim=16, kv_rank=24)
    p0, _ = mla.init(jax.random.PRNGKey(0), InputType.recurrent(32, T))
    assert {k: v.shape for k, v in p0.items()} == \
        {k: v.shape for k, v in p.items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, T, 32))
    _close(mla.apply(p, {}, x)[0], REF._mla(CFG, p, x, "highest"), 2e-5)
    got = jax.grad(lambda p, x: jnp.sum(mla.apply(p, {}, x)[0] * w),
                   (0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(REF._mla(CFG, p, x, "highest") * w),
                    (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b, 1e-4)


@pytest.mark.parametrize("t,block", [(256, 64), (200, 64)])
def test_flash_kernel_at_two_head_sizes_forward_and_backward(t, block):
    """q and k 192 wide a head, v 128 (the latent attention's sizes), the
    Pallas kernels in interpret mode against `dot_product_attention`."""
    from deeplearning4j_tpu.ops import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, t, 2, 192))
    k = jax.random.normal(ks[1], (1, t, 2, 192))
    v = jax.random.normal(ks[2], (1, t, 2, 128))
    w = jax.random.normal(ks[3], (1, t, 2, 128))
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, interpret=True)
    dense = lambda q, k, v: dot_product_attention(q, k, v, causal=True)
    assert flash(q, k, v).shape == (1, t, 2, 128)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)
    with pytest.raises(ValueError, match="head width"):
        flash_attention(q, k[..., :128], v, causal=True, interpret=True)


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


# --------------------------------------------- the whole model through fit()
def _follow(net, cfg, rows, how):
    stamps = SYSTEM.stamp_listener()
    net.set_listeners(stamps)
    net.fit(SYSTEM.feed(rows), **how)
    return [loss for _, loss in stamps.rows]


@pytest.mark.parametrize("how", [{"scan_steps": 2}, {"scan_steps": 1}])
def test_two_adamw_steps_through_fit_match_the_reference(how):
    """The cut model, two optimizer steps through `fit()` (scan-of-2 and
    per-call alike) against the reference's `train_steps`: the losses,
    AdamW's first moment by stage, and the norm of the update, as the
    benchmark's `correct` compares them."""
    from benchmark.lib import checks
    net, cfg = _net()
    rows = _rows(11, 2)
    losses = _follow(net, cfg, rows, how)
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
              "stage_momentum_gap": {s: 1e-4 for s in (
                  "embed", "layer1", "layer2", "layer3", "layer4", "layer5",
                  "head")}}
    rows_ = checks.training_rows(prog, ref,
                                 lambda leaf: REF.stage_of(cfg, leaf), limits)
    assert len(rows_) == 11 and checks.verdict(rows_)
    # gains, per-head scalars and biases are not decayed; matrices are
    assert checks.worst_leaf_gap(prog["update"], ref["update"]) < 1e-3


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
    net, cfg = _net()
    ids, nxt, keep = _batch(cfg, _rows(4, 1)[0][0])
    params = REF.make_params(cfg)

    def program(p):
        return net._score_fn(p, net.state, ids, nxt, None, keep, True,
                             jax.random.PRNGKey(0))[0]

    got_l, got = jax.value_and_grad(program)(params)
    want_l, want = jax.value_and_grad(
        lambda p: REF.loss_fn(cfg, p, ids))(params)
    np.testing.assert_allclose(got_l, want_l, rtol=2e-6)
    flat_w = jax.tree_util.tree_leaves(want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            flat_w):
        scale = max(np.abs(np.asarray(b)).max(), 1e-7)
        assert np.abs(np.asarray(a - b)).max() <= 2e-4 * scale, \
            jax.tree_util.keystr(path)


def test_planted_faults_move_what_correct_compares():
    """The three faults the limits have to catch, at the test's sizes:
    half a batch, a KDA layer without its decay, a router without the
    renormalisation. Each moves the loss or a stage's first moment by
    far more than float32 rounding."""
    rows = _rows(11, 2)
    sound = REF.train_steps(CFG, REF.make_params(CFG), rows)
    for fault in ("half_batch", "kda_no_decay", "router_no_renorm"):
        bad = REF.train_steps(CFG, REF.make_params(CFG), rows, fault=fault)
        gap = max(abs(a - b) / abs(b) for a, b in zip(bad[0], sound[0]))
        assert gap > 1e-4, (fault, gap)


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
SCOPES = ("kda/proj", "kda/scan", "kda/out", "mla/proj", "mla/attn",
          "moe/route", "moe/dispatch", "moe/experts", "moe/shared",
          "moe/combine", "head/loss", "opt/update")


def test_the_adapter_reads_the_counters_and_the_steps_scopes():
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.monitor import xla
    net, cfg = _net()
    net.set_listeners(SYSTEM.stamp_listener())
    xla.enable_ledger()
    try:
        net.fit(SYSTEM.feed(_rows(6, 4)), scan_steps=2)
        load = monitor.dump()["moe_expert_load_max_over_mean"]["series"]
        assert {s["labels"]["layer"] for s in load} >= {"2", "3", "4", "5"}
        assert all(1.0 <= s["value"] <= 8.0 for s in load)
        rows = SYSTEM.expert_rows_per_step()
        assert set(rows) >= {"2", "3", "4", "5"} \
            and SYSTEM.expert_load_max_over_mean()
        scopes = SYSTEM.op_scopes()
        seen = {m for m in SCOPES if any(m in s for s in scopes.values())}
        assert seen == set(SCOPES), set(SCOPES) - seen
    finally:
        xla.disable_ledger()
        xla.clear_ledger()


# ----------------------------------------------------- checkpoints, serving
def test_zoo_model_checkpoint_round_trip(tmp_path):
    from deeplearning4j_tpu.util.serialization import load_model, save_model
    net, cfg = _net()
    ids, nxt, keep = _batch(cfg, _rows(7, 1)[0][0])
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
    whole, _ = _net()
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
