"""What the files of tests of the block-diffusion LM share
(`test_sdar_model.py`, `test_block_diffusion_attention.py`,
`test_denoise.py`: one file a worker under `--dist loadfile`): the
published keys at test widths, the family's record and the brute-force
visibility; the byte budgets, the host rows and the checks' bodies are
`_lm_common.py`'s.

The zoo model (`SdarMoeLM`: grouped-query attention whose heads are wider
than the stream's share, q/k-normed, rotated at positions that restart at
the clean half, DENSE under the block-diffusion rule over the stream
[noisy ; clean]; softmax-routed SwiGLU experts held in part with NO shared
expert; a slice to the noisy half, RMSNorm, an untied head, a denoising
loss weighted by 1 / t over blocks of positions) against the benchmark's
plain reference at tiny widths on the CPU in float32, and the pieces it is
made of.

The reference (`benchmark/references/sdar-30b-a3b-chat.py`) imports nothing
of the program; weights are the reference's seeded ones, the noise its own
copy of the rule.
"""
import numpy as np

from benchmark.lib.manifest import load_module

from _lm_common import Family, score_is_the_loss

REF = load_module("references", "sdar-30b-a3b-chat")
SYSTEM = load_module("systems", "dl4j_fit_sdar_moe")

#: the published keys at widths a CPU test can run: L = 128 (a stream of
#: 256 rows), blocks of 4, 8 query heads on 2 key/value heads of 8 (twice
#: the stream's 32 together, as the published 32 x 128 are twice 2,048),
#: 16 experts routed over of which 4 are held, 2 a token, a rotation that
#: turns far inside 128 positions; the kernels' block 32 (4 tiles a half)
CFG = {
    "hidden_size": 32, "head_dim": 8, "moe_intermediate_size": 24,
    "num_attention_heads": 8, "num_key_value_heads": 2,
    "num_hidden_layers": 3, "attention_bias": False, "hidden_act": "silu",
    "mlp_only_layers": [], "decoder_sparse_step": 1,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "rope_theta": 100.0, "rope_scaling": None,
    "router_experts": 16, "num_experts": 4, "experts_held": [2, 6],
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "vocab_size": 96, "mask_token_id": 95, "block_length": 4,
    "noise_t_min": 1e-3, "rms_norm_eps": 1e-6,
    "image_size": 8, "channels": 4, "num_classes": 1, "zipf_s": 1.0,
    "attention_block": 32,
    "updater": "adamw", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
    "epsilon": 1e-8, "weight_decay": 0.1, "weights_seed": 7,
    "embedding_std": 0.2, "matrix_std": 0.2, "out_proj_std": 0.1,
    "compute_dtype": None, "gradient_checkpointing": True,
}
L = REF.seq_length(CFG)        # 128


def _example(ref, cfg, rows, seed=5, first=0):
    """(stream, targets, weights) of one host batch, the reference's."""
    return ref.targets(cfg, ref.decode_tokens(cfg, rows), seed, first)


def _mean_loss(cfg, params, example):
    """The reference takes a sequence; a batch's loss is their mean."""
    stream, y, w = example
    return sum(REF.loss_fn(cfg, params, *one) for one in zip(stream, y, w)) \
        / len(stream)


#: an example is (stream, targets, weights); the reference's logits are the
#: first sequence's; bfloat16 on ONE sequence, to 5 %; a fault may show in a
#: stage's moment alone; an expert layer takes the stream's 2L rows
FAMILY = Family(
    ref=REF, system=SYSTEM, cfg=CFG,
    stages=("embed", "layer0", "layer1", "layer2", "head"),
    ref_loss=score_is_the_loss(_mean_loss), example_of=_example,
    ref_logits=lambda cfg, params, example: REF.logits(
        cfg, params, example[0][0])[None],
    bf16_sequences=1, bf16_stage_gap=5e-2, fault_gap=1e-3,
    fault_by_stage=True, layer_rows=2 * L)


def brute_force_visible(length, block):
    """(2L, 2L) bool from the three clauses, row by row and column by
    column: what every geometry, kernel and XLA path is held to."""
    i = np.arange(2 * length)
    noisy, blk = i < length, (i % length) // block
    ni, nj = noisy[:, None], noisy[None, :]
    bi, bj = blk[:, None], blk[None, :]
    return (ni & nj & (bj == bi)) | (ni & ~nj & (bj < bi)) \
        | (~ni & ~nj & (bj <= bi))
