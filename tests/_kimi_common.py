"""What the three files of tests of the hybrid LM share
(`test_kimi_attention.py`, `test_kimi_experts.py`, `test_kimi_model.py`:
one file a worker under `--dist loadfile`, ROADMAP D12): the published
keys at test widths and the family's record; the byte budgets, the seeded
rows and the checks' bodies are `_lm_common.py`'s.

The hybrid LM (`KimiLinearLM`: Kimi Delta Attention three layers to one
of position-free latent attention, a dense SwiGLU layer then sigmoid-routed
SwiGLU experts held in part beside a shared expert, RMSNorm, a blocked
sparse loss) against the benchmark's plain reference at tiny widths on the
CPU in float32, and the pieces it is made of.

The reference (`benchmark/references/kimi-linear-48b-a3b.py`) imports
nothing of the program; weights are the reference's seeded ones.
"""
from benchmark.lib.manifest import load_module

from _lm_common import Family, score_is_the_loss

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

#: a `MultiLayerNetwork`: ids, labels and mask as they are
FAMILY = Family(
    ref=REF, system=SYSTEM, cfg=CFG,
    stages=("embed", "layer1", "layer2", "layer3", "layer4", "layer5",
            "head"),
    ref_loss=score_is_the_loss(
        lambda cfg, params, example: REF.loss_fn(cfg, params, example[0])),
    operands=lambda example: example,
    scopes=("kda/proj", "kda/scan", "kda/out", "mla/proj", "mla/attn",
            "moe/route", "moe/dispatch", "moe/experts", "moe/shared",
            "moe/combine", "head/loss", "opt/update"))


def _layer_params(i):
    return REF.make_params(CFG)[str(i)]
