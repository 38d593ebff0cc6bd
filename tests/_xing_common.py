"""What the files of tests of the Xing4.0 family share
(`test_xing_layers.py`, `test_xing_model.py`: one file a worker under
`--dist loadfile`): the published keys at test widths and the family's
record; the byte budgets, the host rows and the checks' bodies are
`_lm_common.py`'s.

The zoo model (`Xing4LM`: a residual path of four streams that every
sub-layer reads and writes through Sinkhorn-projected mappings, around
YaRN-rotated latent attention with a low-rank query, a dense SwiGLU layer
then sigmoid-routed SwiGLU experts held in part beside a shared expert, an
untied head) against the benchmark's plain reference at tiny widths on the
CPU in float32, and the pieces it is made of.

The reference (`benchmark/references/xing4.0-29b-a4b.py`) imports nothing
of the program; weights are the reference's seeded ones.
"""
from benchmark.lib.manifest import load_module

from _lm_common import Family, score_is_the_loss

REF = load_module("references", "xing4.0-29b-a4b")
SYSTEM = load_module("systems", "dl4j_fit_xing4")

#: the published keys at widths a CPU test can run: three layers of the cut
#: (published layer 1, dense, then two expert layers), T = 128, four streams
#: of 32, 16 experts routed over of which 4 are held (a small tier of half
#: the pairs), 2 a token, a YaRN whose ramp lies inside the 4 pairs (low 0,
#: high 2 over an original length of 32) and turns far inside 128 positions
CFG = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 24,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 12,
    "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_theta": 100.0,
    "rope_scaling": {"beta_fast": 4, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32, "type": "yarn"},
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "attention_bias": False, "hidden_act": "silu",
    "first_k_dense_replace": 2, "first_layer": 1, "moe_layer_freq": 1,
    "num_hidden_layers": 3, "num_nextn_predict_layers": 0,
    "router_experts": 16, "n_routed_experts": 4, "experts_held": [2, 6],
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "routed_scaling_factor": 2, "tie_word_embeddings": False,
    "vocab_size": 96, "rms_norm_eps": 1e-6,
    "image_size": 8, "channels": 4, "num_classes": 1, "zipf_s": 1.0,
    "attention_block": 32,
    "updater": "adamw", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
    "epsilon": 1e-8, "weight_decay": 0.1, "weights_seed": 5,
    "embedding_std": 1.0, "matrix_std": 0.2, "out_proj_std": 0.1,
    "hc_phi_std": 128 ** -0.5, "hc_alpha": [1.0, 1.0, 4.0],
    "hc_res_diagonal": 2.0,
    "compute_dtype": None, "gradient_checkpointing": True,
}
T = REF.seq_length(CFG)        # 128

FAMILY = Family(
    ref=REF, system=SYSTEM, cfg=CFG,
    stages=("embed", "layer0", "layer1", "layer2", "head"),
    ref_loss=score_is_the_loss(
        lambda cfg, params, example: REF.loss_fn(cfg, params, example[0])),
    ref_logits=lambda cfg, params, example: REF.logits(cfg, params,
                                                       example[0]),
    fault_by_stage=True,
    # behind the embedding the four streams are copies: whatever mixes
    # them among themselves (the first sub-layer's H_res, whose rows sum to
    # 1) changes nothing, so its 16 columns of phi, its 16 biases and
    # a_res get a gradient of rounding noise (1e-10), which Adam divides by
    # its own size
    update_norm_gap=1e-4,
    noise_leaves=tuple(f"['layer0']['hc_attn']['{leaf}']"
                       for leaf in ("phi", "bias", "alpha")),
    scopes=("mhc/pre", "mhc/sinkhorn", "mhc/post", "mhc/io", "mla/proj",
            "mla/rope", "mla/attn", "moe/route", "moe/dispatch",
            "moe/experts", "moe/shared", "moe/combine", "mlp/gated",
            "head/loss", "opt/update"))
