"""What the three files of tests of the hybrid LM share
(`test_kimi_attention.py`, `test_kimi_experts.py`, `test_kimi_model.py`:
one file a worker under `--dist loadfile`, ROADMAP D12): the published
keys at test widths, the byte budgets, the seeded rows.

The hybrid LM (`KimiLinearLM`: Kimi Delta Attention three layers to one
of position-free latent attention, a dense SwiGLU layer then sigmoid-routed
SwiGLU experts held in part beside a shared expert, RMSNorm, a blocked
sparse loss) against the benchmark's plain reference at tiny widths on the
CPU in float32, and the pieces it is made of.

The reference (`benchmark/references/kimi-linear-48b-a3b.py`) imports
nothing of the program; weights are the reference's seeded ones.
"""
import numpy as np
import pytest

from benchmark.lib.manifest import load_module

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



def _layer_params(i):
    return REF.make_params(CFG)[str(i)]
