"""What the three files of tests of the GLM-4.7-Flash family share
(`test_glm_moe_attention.py`, `test_glm_moe_experts.py`,
`test_glm_moe_model.py`: one file a worker under `--dist loadfile`): the
published keys at test widths and the family's record; the byte budgets,
the host rows and the checks' bodies are `_lm_common.py`'s.

The zoo model (`Glm4MoeLiteLM`: rotated latent attention with a low-rank
query in every layer, a dense SwiGLU layer then sigmoid-routed SwiGLU
experts held in part beside a shared expert, RMSNorm, a multi-token-
prediction module that reads the trunk's embedding and head, two blocked
sparse losses) against the benchmark's plain reference at tiny widths on
the CPU in float32, and the pieces it is made of.

The reference (`benchmark/references/glm-4.7-flash.py`) imports nothing of
the program; weights are the reference's seeded ones.
"""
from benchmark.lib.manifest import load_module

from _lm_common import Family, score_is_the_loss

REF = load_module("references", "glm-4.7-flash")
SYSTEM = load_module("systems", "dl4j_fit_glm_moe_lite")

#: the published keys at widths a CPU test can run: three layers of the
#: cut (dense, experts, experts) and the MTP module, T = 128, 16 experts
#: routed over of which 4 are held (a small tier of half the pairs), 2 a
#: token, a rotation that turns far inside 128 positions
CFG = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 24,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 12,
    "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
    "v_head_dim": 16, "rope_theta": 100.0, "rope_scaling": None,
    "partial_rotary_factor": 1, "attention_bias": False,
    "hidden_act": "silu", "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "num_nextn_predict_layers": 1,
    "router_experts": 16, "n_routed_experts": 4, "experts_held": [2, 6],
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "topk_method": "noaux_tc", "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": 1.8,
    "tie_word_embeddings": False, "vocab_size": 96, "rms_norm_eps": 1e-5,
    "image_size": 8, "channels": 4, "num_classes": 1, "zipf_s": 1.0,
    "attention_block": 32, "mtp_loss_weight": 0.3,
    "updater": "adamw", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
    "epsilon": 1e-8, "weight_decay": 0.1, "weights_seed": 5,
    "embedding_std": 1.0, "matrix_std": 0.2, "out_proj_std": 0.1,
    "compute_dtype": None, "gradient_checkpointing": True,
}
T = REF.seq_length(CFG)        # 128


def _example(ref, cfg, rows):
    """(ids, the label arrays, their masks) of one host batch."""
    ids = ref.decode_tokens(cfg, rows)
    labels, keep = ref.targets(ids)
    n = 1 + cfg["num_nextn_predict_layers"]
    return ids, labels[:n], keep[:n]


#: two weighted outputs: the labels and masks are tuples already
FAMILY = Family(
    ref=REF, system=SYSTEM, cfg=CFG,
    stages=("embed", "layer0", "layer1", "layer2", "mtp", "head"),
    ref_loss=score_is_the_loss(
        lambda cfg, params, example: REF.loss_fn(cfg, params, example[0])),
    example_of=_example,
    operands=lambda example: ((example[0],), example[1], example[2]),
    scopes=("mla/proj", "mla/rope", "mla/attn", "moe/route", "moe/dispatch",
            "moe/experts", "moe/shared", "moe/combine", "mlp/gated",
            "head/loss", "opt/update", "mtp"))
