"""What the files of tests of the LFM2 mixture-of-experts family share
(`test_lfm2_layers.py`, `test_lfm2_model.py`: one file a worker under
`--dist loadfile`): the published keys at test widths and the family's
record; the byte budgets, the host rows and the checks' bodies are
`_lm_common.py`'s.

The zoo model (`Lfm2MoeLM`: gated short convolutions three to one with
q/k-normed, rotated grouped-query attention, a dense SwiGLU layer then
sigmoid-routed SwiGLU experts held in part with NO shared expert, RMSNorm,
a head tied to the embedding, a blocked sparse loss) against the
benchmark's plain reference at tiny widths on the CPU in float32, and the
pieces it is made of.

The reference (`benchmark/references/lfm2-24b-a2b.py`) imports nothing of
the program; weights are the reference's seeded ones.
"""
from benchmark.lib.manifest import load_module

from _lm_common import Family, score_is_the_loss

REF = load_module("references", "lfm2-24b-a2b")
SYSTEM = load_module("systems", "dl4j_fit_lfm2_moe")

#: the published keys at widths a CPU test can run: the five layers of the
#: cut (conv + dense, attention + experts, three times conv + experts) out
#: of a list that starts as the published one does, T = 128, 8 query heads
#: on 2 key/value heads of 4, 16 experts routed over of which 4 are held
#: (a small tier of half the pairs), 2 a token, a rotation that turns far
#: inside 128 positions
CFG = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 24,
    "num_attention_heads": 8, "num_key_value_heads": 2,
    "conv_L_cache": 3, "conv_bias": False,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv", "full_attention"],
    "first_layer": 1, "num_hidden_layers": 5, "num_dense_layers": 1,
    "rope_parameters": {"rope_theta": 100.0, "rope_type": "default"},
    "router_experts": 16, "num_experts": 4, "experts_held": [2, 6],
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1,
    "tie_word_embeddings": True, "vocab_size": 96, "norm_eps": 1e-5,
    "image_size": 8, "channels": 4, "num_classes": 1, "zipf_s": 1.0,
    "attention_block": 32,
    "updater": "adamw", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
    "epsilon": 1e-8, "weight_decay": 0.1, "weights_seed": 7,
    "embedding_std": 0.2, "matrix_std": 0.2, "conv_std": 0.33,
    "out_proj_std": 0.1,
    "compute_dtype": None, "gradient_checkpointing": True,
}
T = REF.seq_length(CFG)        # 128
KINDS = REF.layer_kinds(CFG)

FAMILY = Family(
    ref=REF, system=SYSTEM, cfg=CFG,
    stages=("layer0", "layer1", "layer2", "layer3", "layer4", "head"),
    ref_loss=score_is_the_loss(
        lambda cfg, params, example: REF.loss_fn(cfg, params, example[0])),
    ref_logits=lambda cfg, params, example: REF.logits(cfg, params,
                                                       example[0]),
    scopes=("sconv/proj", "sconv/mix", "mha/proj", "mha/norm", "mha/rope",
            "mha/attn", "moe/route", "moe/dispatch", "moe/experts",
            "moe/combine", "mlp/gated", "head/loss", "opt/update"))
