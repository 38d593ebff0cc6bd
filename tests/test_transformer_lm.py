"""TransformerLM coverage (VERDICT §2.8 gap): fit smoke + the paged-KV
greedy-decode parity proof.

The parity contract the decode runtime (serving/decode.py) ships under:

- full-sequence recompute is BITWISE the model's own output() (the same
  primitive calls as the stock layers), and a bucket-padded PREFILL's
  logits match it to a few float32 ulp (padding is masked out exactly,
  but a sum over a bucket's keys is associated otherwise than one over
  the prompt's: `test_prefill_logits_bitwise_equal_full_recompute`);
- each DECODE step's logits match full-sequence recompute to within a few
  float32 ulp (XLA picks a different matmul reduction strategy for
  1-token queries than for full sequences — same math, different
  rounding order), and the GREEDY TOKEN SEQUENCE is exactly equal — the
  product-level guarantee that the paged cache never changes what the
  model says.
"""
import numpy as np
import pytest

import jax

from deeplearning4j_tpu.models.transformer import TransformerLM
from deeplearning4j_tpu.serving.decode import DecodeConfig, DecodeEngine


@pytest.fixture(scope="module")
def tiny_lm():
    net = TransformerLM(vocab_size=32, seq_length=32, n_layers=2,
                        n_embd=32, n_heads=4, learning_rate=3e-3,
                        seed=11).init()
    return net


def test_transformer_lm_fit_smoke(tiny_lm):
    """A few steps of next-token training must run and reduce the loss
    (the quick-gate sibling of the slow test_transformer_lm_trains)."""
    rs = np.random.RandomState(0)
    x = rs.randint(0, 32, (16, 32)).astype("float32")
    y = np.eye(32, dtype="float32")[(x.astype(int) + 1) % 32]
    losses = []
    for _ in range(6):
        tiny_lm.fit((x, y), epochs=1, batch_size=8)
        losses.append(tiny_lm.score())
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses


@pytest.fixture(scope="module")
def engine(tiny_lm):
    eng = DecodeEngine(tiny_lm,
                       DecodeConfig(slots=2, page_size=8, seed=3),
                       name="parity-lm")
    eng.warm()
    return eng


def test_prefill_logits_bitwise_equal_full_recompute(tiny_lm, engine):
    """Bucket-padded prefill against the unpadded full recompute: the same
    greedy token, the logits to a few float32 ulp; the full recompute and
    the model's own output() (post-softmax) bit for bit.

    Not equal to the last bit, by rounding and not by the path: op by op
    every number up to the first block's attention weights is bitwise equal
    (the padded keys' weights are exact zeros); the first to differ is
    ``weights @ v``, the sum over the KEYS: XLA:CPU associates a
    contraction over a bucket's 8 keys otherwise than one over 5, and a
    third of the block's outputs move by one ulp (compiled, the masked
    program's `exp` is fused otherwise too: 3 of 100 by an ulp even at a
    full bucket). Two blocks, the norm and the head grow it to 3.25 ulp of
    the largest logit (7.7e-7 at 2.7); the bound is 16."""
    prompt = np.array([3, 7, 1, 9, 4], np.int32)      # pads 5 -> bucket 8
    slot = engine.cache.admit(len(prompt))
    try:
        tok, logits = engine.prefill(slot, prompt, 0.0, 0)
        full = engine.logits_full(prompt[None])[0, len(prompt) - 1]
        few_ulp = lambda x: 16 * float(np.spacing(np.abs(x).max()))
        np.testing.assert_allclose(logits, full, rtol=0, atol=few_ulp(full))
        # what IS exact: the greedy token, and the unpadded recompute
        # against the MODEL's forward (the same primitive calls)
        assert tok == int(np.argmax(full)) == int(np.argmax(logits))
        ref = np.asarray(tiny_lm.output(
            prompt[None].astype("float32")))[0, len(prompt) - 1]
        assert np.array_equal(np.asarray(jax.nn.softmax(full)), ref)
        # (a probability moves by less than its logits do)
        np.testing.assert_allclose(np.asarray(jax.nn.softmax(logits)), ref,
                                   rtol=0, atol=few_ulp(full))
    finally:
        engine.cache.release(slot)


def test_greedy_decode_parity_with_full_recompute(tiny_lm, engine):
    """24 greedy tokens through the paged-KV incremental forward produce
    the exact token sequence of per-step full recompute, with per-step
    logits equal to a few float32 ulp."""
    prompt = np.array([5, 2, 8, 1], np.int32)
    slot = engine.cache.admit(len(prompt))
    try:
        tok, _ = engine.prefill(slot, prompt, 0.0, 0)
        seq = list(prompt) + [tok]
        for _ in range(24):
            toks, act, logits = engine.step()
            assert act[slot]
            full = engine.logits_full(np.array([seq], np.int32))[0, -1]
            np.testing.assert_allclose(logits[slot], full, rtol=0,
                                       atol=2e-5)
            # the product-level contract: greedy tokens NEVER diverge —
            # against the engine oracle and against the model itself
            assert int(toks[slot]) == int(np.argmax(full))
            ref = np.asarray(tiny_lm.output(
                np.array([seq], "float32")))[0, -1]
            assert int(toks[slot]) == int(np.argmax(ref))
            seq.append(int(toks[slot]))
    finally:
        engine.cache.release(slot)


def test_decode_crosses_page_boundaries(engine):
    """Generation that spans several 8-token pages keeps appending into
    freshly allocated pages (the on-demand allocator engages)."""
    prompt = np.array([1, 2, 3, 4, 5, 6, 7], np.int32)   # page 0 almost full
    slot = engine.cache.admit(len(prompt))
    try:
        pages_before = engine.cache.describe()["pages_used"]
        engine.prefill(slot, prompt, 0.0, 0)
        for _ in range(10):                              # crosses 8 and 16
            _, act, _ = engine.step()
            assert act[slot]
        assert engine.cache.describe()["pages_used"] > pages_before
        assert int(engine.cache.seq_lens[slot]) == len(prompt) + 10
    finally:
        engine.cache.release(slot)
    assert engine.cache.describe()["pages_used"] == 0
