"""The block-diffusion LM's zoo model through `ComputationGraph.fit()`
against its reference: losses and gradients over two steps, bf16 compute,
the planted faults, the shares of an expert layer, no leak from the clean
copy of a row's own block; see `_sdar_common.py`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import SdarMoeLM
from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import MoEFeedForward

import _lm_common as lm
from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _rows,
)
from _sdar_common import CFG, FAMILY, L, REF


# --------------------------------------------- the whole model through fit()
def test_the_model_is_the_training_form_alone():
    """Embedding, ``num_hidden_layers`` blocks that are all alike (dense
    attention under the rule + experts), the slice to the noisy half, a
    final norm and an UNTIED weighted head over a stream of 2L ids."""
    net, _ = FAMILY.net()
    assert net.conf.network_outputs == ("head",)
    assert set(net.params) == {"embed", "norm", "head"} | {
        f"layer{i}" for i in range(3)}
    assert {k: v.shape for k, v in net.params["layer1"]["attn"].items()} == {
        "Wq": (32, 64), "Wk": (32, 16), "Wv": (32, 16), "Wo": (64, 32),
        "q_norm": (8,), "k_norm": (8,)}
    assert net.params["head"]["W"].shape == (32, 96)
    assert "Wgate_s" not in net.params["layer0"]["ffn"]
    assert net.conf.vertices["noisy"].vertex.steps == L
    assert net.conf.vertices["head"].vertex.weighted
    kinds = net._resolve_types()
    assert tuple(kinds["layer2"].shape) == (2 * L, 32)
    assert tuple(kinds["norm"].shape) == (L, 32)
    assert REF.stage_of(CFG, "['layer1']['attn']['Wq']") == "layer1"
    assert REF.stage_of(CFG, "['norm']['gamma']") == "head"
    assert REF.mask_token_id(CFG) == CFG["mask_token_id"] == 95
    assert int(REF.decode_tokens(CFG, _rows(3, 1)[0][0]).max()) < 95
    assert SdarMoeLM().conf().vertices["layer0"].vertex.attn \
        .block_diffusion == 4


@pytest.mark.parametrize("how", [{"scan_steps": 2}, {"scan_steps": 1}])
def test_two_adamw_steps_through_fit_match_the_reference(how):
    """Two optimizer steps through `fit()` (scan-of-2 and per-call alike)
    behind the async feed with the program's own pre-processor, against
    the reference's `train_steps` with its own copy of the noise: the
    score (the weighted denoising loss), AdamW's first moment by stage
    and the update, as the benchmark's `correct` compares them."""
    lm.two_adamw_steps_match(FAMILY, how)


def test_logits_loss_and_every_gradient_leaf_match_the_reference():
    """Float32 on both sides: the noisy half's logits, the weighted loss
    of a batch of two sequences and EVERY leaf of its gradient element by
    element."""
    assert lm.logits_match(FAMILY).shape == (2, L, 96)
    lm.every_gradient_leaf_matches(FAMILY)


def test_the_loss_is_the_weighted_sum_over_the_count_of_tokens():
    """``(1 / (N L)) sum_seq sum_masked CE / t``: written out from the
    reference's logits and the weights."""
    net, cfg = FAMILY.net()
    stream, y, w = FAMILY.example(cfg, _rows(6, 1)[0][0])
    params = REF.make_params(cfg)
    total = 0.0
    for i in range(2):
        z = np.asarray(REF.logits(cfg, params, stream[i]), np.float64)
        z = z - z.max(-1, keepdims=True)
        nll = np.log(np.exp(z).sum(-1)) - z[np.arange(L), y[i]]
        total += float(np.sum(w[i] * nll))
    np.testing.assert_allclose(FAMILY.score(net, params, (stream, y, w)),
                               total / (2 * L), rtol=2e-6)
    assert 0.3 < float((w > 0).mean()) < 0.7


def test_bfloat16_compute_stays_near_the_reference():
    """bf16 operands over float32 weights, as the cell runs: the score to
    half a percent of the float32 reference's, every stage's gradient norm
    to 5 %."""
    lm.bfloat16_stays_near(FAMILY)


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_a_planted_fault_moves_what_correct_compares(fault):
    """The eight faults the limits have to catch, at the test's sizes:
    each moves a loss or a stage's first moment far more than float32
    rounding."""
    assert REF.FAULTS == (
        "plain_causal", "sees_own_clean_block", "noisy_sees_noisy_past",
        "positions_run_on", "no_weight", "mean_over_masked",
        "loss_on_clean_half", "kv_head_mod")
    lm.a_planted_fault_moves(FAMILY, fault)


def test_no_leak_through_the_model():
    """The loss's gradient with respect to the CLEAN copy of a noisy
    row's own block's embeddings is exactly zero through the attention:
    with the loss on one block's rows alone, the embedded clean rows of
    that block and of every later one get nothing, the earlier ones
    something."""
    net, cfg = FAMILY.net()
    stream, y, w = FAMILY.example(cfg, _rows(4, 1)[0][0][:1])
    params = REF.make_params(cfg)
    blk = 9
    only = np.zeros_like(w)
    only[0, 4 * blk:4 * blk + 4] = 1.0
    # distinct ids in the clean half, so that an embedding row is one
    # stream row
    stream = stream.copy()
    stream[0, L:] = np.arange(L) % 90

    def loss(table):
        return FAMILY.score(net, {**params, "embed": {"W": table}},
                            (stream, y, only))

    # rows of the table only the clean half reads
    noisy_ids = set(stream[0, :L].tolist())
    g = np.asarray(jax.jit(jax.grad(loss))(params["embed"]["W"]))
    for pos in range(L):
        tok = int(stream[0, L + pos])
        if tok in noisy_ids or pos >= 90:
            continue
        if pos >= 4 * blk:
            assert not np.any(g[tok]), pos
    assert any(np.any(g[int(stream[0, L + pos])])
               for pos in range(4 * blk) if pos < 90)


# ------------------------------------------------- the shares of a layer
def test_the_eight_shares_add_up_and_an_unheld_token_gets_exactly_zero():
    lm.the_eight_shares_add_up(FAMILY)


# ------------------------------------------------- the ladder of row tiers
@pytest.mark.parametrize("n_experts,held,divisors", [
    (128, (0, 16), (4, 2, 1)),              # the cell's layer
    (64, (8, 16), (4, 2, 1)),               # whichever eight of 64
    (256, (0, 8), (16, 8, 4, 2, 1)),
    (32, (0, 4), (4, 2, 1)),                # the rehearsal's
    (16, (2, 6), (2, 1)),                   # these tests' model
    (64, (0, 32), (1,)),                    # half held: the one tier
    (64, None, (1,)),
])
def test_the_ladder_has_a_tier_at_every_doubling(n_experts, held, divisors):
    ffn = MoEFeedForward(n_out=16, n_experts=n_experts, top_k=4, hidden=8,
                         activation="swish", gated=True, has_bias=False,
                         experts_held=held, router="softmax")
    assert ffn._tier_divisors() == divisors
    assert ffn.tier_names() == tuple(f"1/{d}" for d in divisors)
    assert ffn._tiers(4096) == tuple(4096 // d for d in divisors)
    _, state = ffn.init(jax.random.PRNGKey(0), InputType.recurrent(16, 8))
    if len(divisors) > 1:
        assert state["tier_hits"].shape == (len(divisors),)
    else:
        assert "tier_hits" not in state


@pytest.mark.parametrize("bent,tier", [(0, 0), (2, 1), (8, 2)])
def test_every_rung_of_the_ladder_drops_no_pair(bent, tier):
    """16 of 128 held, top-8: a balanced router's held pairs fit the
    quarter, a router bent towards two held experts the half, one bent
    towards eight the whole; the result is the dense sum over the held
    experts on every rung, and the rung walked is the one counted."""
    ffn = MoEFeedForward(n_out=16, n_experts=128, top_k=8, hidden=8,
                         activation="swish", gated=True, has_bias=False,
                         experts_held=(0, 16), router="softmax",
                         weight_init="normal")
    p, state = ffn.init(jax.random.PRNGKey(0), InputType.recurrent(16, 64))
    # every token's choice bent towards the first ``bent`` held experts
    p = {**p, "Wr": p["Wr"].at[:, :bent].set(0.0)}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 16))
    x = x.at[..., 0].set(0.0)
    if bent:
        x = x.at[..., 0].set(5.0)
        p["Wr"] = p["Wr"].at[0, :bent].set(4.0)
    y, new = ffn.apply(p, state, x)
    idx, w = ffn.route(p, state, x)
    flat, dense = x.reshape(128, 16), jnp.zeros((128, 16))
    for e in range(16):
        w_e = jnp.sum(jnp.where(idx.reshape(128, 8) == e,
                                w.reshape(128, 8), 0.0), axis=-1)
        dense = dense + w_e[:, None] * (
            (jax.nn.silu(flat @ p["Wgate"][e]) * (flat @ p["Wup"][e]))
            @ p["Wdown"][e])
    np.testing.assert_allclose(y.reshape(128, 16), dense, rtol=1e-5,
                               atol=1e-5)
    held = int(new["tokens_routed"][:16].sum())
    # the tests' byte budget cuts the 128 tokens into equal dispatches
    n = int(np.asarray(new["tier_hits"]).sum())
    tiers = ffn._tiers(128 * 8 // n)
    assert tiers[1] == 2 * tiers[0] and tiers[2] == 4 * tiers[0]
    np.testing.assert_array_equal(new["tier_hits"],
                                  n * (np.arange(3) == tier))
    assert int(new["rows_walked_total"]) == n * tiers[tier] >= held
    assert tier == 0 or held > n * tiers[tier - 1]
