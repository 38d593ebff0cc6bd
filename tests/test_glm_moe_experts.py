"""The expert layer as the GLM-4.7-Flash family holds it: one chip's share
of eight, the small row tier that follows from the share; see
`_glm_common.py`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import (
    MoEFeedForward, MultiHeadLatentAttention, TransformerBlock,
)

from _glm_common import CFG, REF, T
from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _close,
)

_PER_EXPERT = ("Wgate", "Wup", "Wdown")


def _expert_block(lo, hi):
    attn = MultiHeadLatentAttention(
        n_out=32, n_heads=4, nope_dim=12, rope_dim=4, v_dim=16, kv_rank=16,
        q_rank=12, rotate=True, rope_theta=100.0)
    ffn = MoEFeedForward(n_out=32, n_experts=16, top_k=2, hidden=24,
                         activation="swish", gated=True, has_bias=False,
                         experts_held=(lo, hi), router="sigmoid",
                         routed_scale=1.8, n_shared=1)
    return TransformerBlock(n_out=32, n_heads=4, norm="rms",
                            norm_epsilon=1e-5, has_bias=False, attn=attn,
                            ffn=ffn)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Each of 8 chips holds 2 of the 16 experts and computes `h + shared
    + sum over ITS experts`; what every chip computes alike (attention,
    residual, shared expert) counted once, the eight routed parts add up
    to the reference's whole layer: the cut is a share of the uncut
    model, not another model."""
    cfg = {**CFG, "experts_held": [0, 16], "n_routed_experts": 16}
    whole = REF.make_params(cfg)["layer1"]
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    want = REF.layer(cfg, whole, x, False)

    def run(lo, hi, zero_down=False):
        p = dict(whole, ffn={k: (v[lo:hi] if k in _PER_EXPERT else v)
                             for k, v in whole["ffn"].items()})
        if zero_down:
            p["ffn"]["Wdown"] = jnp.zeros_like(p["ffn"]["Wdown"])
        blk = _expert_block(lo, hi)
        _, state = blk.init(jax.random.PRNGKey(0),
                            InputType.recurrent(32, T))
        return blk.apply(p, state, x)[0]

    alike = run(0, 2, zero_down=True)          # no routed expert adds
    shares = [run(lo, lo + 2) for lo in range(0, 16, 2)]
    got = alike + sum(s - alike for s in shares)
    _close(got, want, 3e-5)
    assert float(jnp.abs(want - alike).max()) > 1e-2   # the experts matter
    # one share alone is the reference told to hold the same experts
    held = dict(whole, ffn={k: (v[4:6] if k in _PER_EXPERT else v)
                            for k, v in whole["ffn"].items()})
    _close(shares[2], REF.layer(cfg, held, x, False, held=(4, 6)), 3e-5)
    # and without the shared expert, the routed part alone
    routed = REF.layer(cfg, held, x, False, held=(4, 6), shared=False)
    assert float(jnp.abs(shares[2] - routed).max()) > 1e-3


@pytest.mark.parametrize("n_experts,held,divisors,names", [
    (256, (0, 8), (16, 8, 4, 2, 1),              # the Kimi cell's layer
     ("1/16", "1/8", "1/4", "1/2", "1/1")),
    (64, (0, 8), (4, 2, 1), ("1/4", "1/2", "1/1")),     # the GLM cell's
    (64, (8, 16), (4, 2, 1), ("1/4", "1/2", "1/1")),    # whichever eight
    (32, (0, 4), (4, 2, 1), ("1/4", "1/2", "1/1")),     # GLM's rehearsal
    (16, (2, 6), (2, 1), ("1/2", "1/1")),        # these tests' model
    (64, (0, 32), (1,), ("1/1",)),               # half: twice that is all
    (64, (0, 40), (1,), ("1/1",)),
    (64, None, (1,), ("1/1",)),                  # every expert held
])
def test_the_small_tier_follows_from_the_share_held(n_experts, held,
                                                    divisors, names):
    """Twice the balanced load of the share: ``n_experts // (2 * held)`` as
    the divisor of a dispatch's pairs, and every halving of it down to
    the whole; a layer whose doubled share is the whole, or that holds
    every expert, has the one tier, no switch and no tier counter."""
    ffn = MoEFeedForward(n_out=16, n_experts=n_experts, top_k=4, hidden=8,
                         activation="swish", gated=True, has_bias=False,
                         experts_held=held, router="sigmoid")
    assert ffn._tier_divisors() == divisors
    assert ffn.tier_names() == names
    assert ffn._tiers(4096) == tuple(4096 // d for d in divisors)
    _, state = ffn.init(jax.random.PRNGKey(0), InputType.recurrent(16, 8))
    if len(divisors) > 1:
        assert state["tier_hits"].shape == (len(divisors),)
    else:
        assert "tier_hits" not in state and "rows_walked_total" not in state


@pytest.mark.parametrize("live", ["balanced", "all"])
def test_eight_of_sixty_four_walk_a_quarter_and_drop_no_pair(live):
    """The GLM cell's share at small widths: with a balanced router the
    held pairs (an eighth) fit the quarter tier, which is walked and
    counted; with every token's every choice held here they do not, the
    whole is walked, and no pair is dropped: the result is the dense sum
    over the held experts either way."""
    ffn = MoEFeedForward(n_out=16, n_experts=64, top_k=4, hidden=8,
                         activation="swish", gated=True, has_bias=False,
                         experts_held=(8, 16), router="sigmoid",
                         routed_scale=1.8)
    p, state = ffn.init(jax.random.PRNGKey(0), InputType.recurrent(16, 32))
    if live == "all":       # the choice bent towards four held experts
        state["route_bias"] = state["route_bias"].at[9:13].set(1.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
    y, new = ffn.apply(p, state, x)
    idx, w = ffn.route(p, state, x)
    dense = jnp.zeros((64, 16))
    flat = x.reshape(64, 16)
    for e in range(8, 16):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        dense = dense + w_e[:, None] * (
            (jax.nn.silu(flat @ p["Wgate"][e - 8]) * (flat @ p["Wup"][e - 8]))
            @ p["Wdown"][e - 8])
    np.testing.assert_allclose(y.reshape(64, 16), dense, atol=2e-6)
    held = int(new["tokens_routed"][8:16].sum())
    assert int(new["tokens_routed"].sum()) == 64 * 4
    if live == "all":
        assert held == 256
        np.testing.assert_array_equal(new["tier_hits"], [0, 0, 1])
        assert int(new["rows_walked_total"]) == 256
    else:
        assert 0 < held <= 64
        np.testing.assert_array_equal(new["tier_hits"], [1, 0, 0])
        assert int(new["rows_walked_total"]) == 64
