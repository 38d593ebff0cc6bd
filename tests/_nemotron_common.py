"""What the files of tests of the Nemotron-H family share
(`test_nemotron_h_layers.py`, `test_nemotron_h_model.py`: one file a worker
under `--dist loadfile`): the published keys at test widths and the family's
record; the byte budgets, the host rows and the checks' bodies are
`_lm_common.py`'s.

The zoo model (`NemotronHLM`: blocks of ONE mixer behind a pre-norm, Mamba-2
state-space layers, position-free grouped-query attention, sigmoid-routed
un-gated ReLU^2 experts in a latent of the stream beside a ReLU^2 shared
expert on it, an untied head, a blocked sparse loss) against the benchmark's
plain reference at tiny widths on the CPU in float32, and the pieces it is
made of.

The reference (`benchmark/references/nemotron-3-super-120b-a12b.py`) imports
nothing of the program; weights are the reference's seeded ones.
"""
from benchmark.lib.manifest import load_module

from _lm_common import Family, score_is_the_loss

REF = load_module("references", "nemotron-3-super-120b-a12b")
SYSTEM = load_module("systems", "dl4j_fit_nemotron_h")

#: the published keys at widths a CPU test can run: six blocks (attention,
#: then experts and Mamba-2 in turn) out of a string that starts as the
#: published one does, T = 128 in 4 chunks of 32, 4 Mamba-2 heads of 8 in 2
#: groups of state 16, 4 query heads on 2 key/value heads of 8, 16 experts
#: routed over of which 4 are held (a small tier of half the pairs), 4 a
#: token, in a latent of 16
CFG = {
    "hidden_size": 32, "hybrid_override_pattern": "MEM*EMEMEM*E",
    "first_layer": 3, "num_hidden_layers": 6,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 32,
    "use_conv_bias": True, "mamba_proj_bias": False,
    "mamba_hidden_act": "silu", "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "attention_bias": False, "use_bias": False,
    "router_experts": 16, "n_routed_experts": 4, "experts_held": [2, 6],
    "num_experts_per_tok": 4, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": 2.5, "n_shared_experts": 1,
    "moe_intermediate_size": 24, "moe_latent_size": 16,
    "moe_shared_expert_intermediate_size": 48, "mlp_hidden_act": "relu2",
    "mlp_bias": False, "num_nextn_predict_layers": 0,
    "tie_word_embeddings": False, "vocab_size": 96,
    "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5,
    "image_size": 8, "channels": 4, "num_classes": 1, "zipf_s": 1.0,
    "attention_block": 32,
    "updater": "adamw", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
    "epsilon": 1e-8, "weight_decay": 0.1, "weights_seed": 7,
    "embedding_std": 0.2, "matrix_std": 0.2, "conv_std": 0.29,
    "out_proj_std": 0.1,
    "compute_dtype": None, "gradient_checkpointing": True,
}
T = REF.seq_length(CFG)        # 128
KINDS = REF.layer_kinds(CFG)   # * E M E M E

FAMILY = Family(
    ref=REF, system=SYSTEM, cfg=CFG,
    stages=("embed", "layer0", "layer1", "layer2", "layer3", "layer4",
            "layer5", "head"),
    ref_loss=score_is_the_loss(
        lambda cfg, params, example: REF.loss_fn(cfg, params, example[0])),
    ref_logits=lambda cfg, params, example: REF.logits(cfg, params,
                                                       example[0]),
    fault_by_stage=True,
    scopes=("ssd/proj", "ssd/conv", "ssd/scan", "ssd/out", "mha/proj",
            "mha/attn", "moe/route", "moe/latent", "moe/dispatch",
            "moe/experts", "moe/shared", "moe/combine", "head/loss",
            "opt/update"))
