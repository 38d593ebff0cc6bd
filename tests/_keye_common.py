"""What the files of tests of the Keye-VL-2.0 language model share
(`test_keye_model.py`, `test_dsa_attention.py`: one file a worker under
`--dist loadfile`): the published keys at test widths and the family's
record; the byte budgets, the host rows and the checks' bodies are
`_lm_common.py`'s.

The zoo model (`KeyeVL2LM`: grouped-query attention whose heads are wider
than the stream's share, q/k-normed, rotated by three rows of positions,
SPARSE under a learned indexer trained by its own loss; softmax-routed
SwiGLU experts held in part with NO shared expert; RMSNorm, an untied
head, a blocked sparse loss) against the benchmark's plain reference at
tiny widths on the CPU in float32, and the pieces it is made of.

The reference (`benchmark/references/keye-vl-2.0-30b-a3b.py`) imports
nothing of the program; weights are the reference's seeded ones.
"""
from benchmark.lib.manifest import load_module

from _lm_common import Family

REF = load_module("references", "keye-vl-2.0-30b-a3b")
SYSTEM = load_module("systems", "dl4j_fit_keye_vl2")

#: the published keys at widths a CPU test can run: T = 128, 8 query heads
#: on 2 key/value heads of 8 (twice the stream's 32 together, as the
#: published 32 x 128 are twice 2,048), 4 rotary frequencies shared out
#: 1/1/2, an indexer of 4 heads of 8 that keeps 16 keys a query (so 112 of
#: the 128 rows select), 16 experts routed over of which 4 are held, 2 a
#: token, a rotation that turns far inside 128 positions
CFG = {
    "hidden_size": 32, "head_dim": 8, "moe_intermediate_size": 24,
    "num_attention_heads": 8, "num_key_value_heads": 2,
    "num_hidden_layers": 3, "attention_bias": False, "hidden_act": "silu",
    "mlp_only_layers": [], "decoder_sparse_step": 1,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "rope_theta": 100.0,
    "rope_scaling": {"mrope_section": [1, 1, 2], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 32,
                  "q_chunk_size": 32, "topk": 16},
    "router_experts": 16, "num_experts": 4, "experts_held": [2, 6],
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "vocab_size": 96, "rms_norm_eps": 1e-6, "indexer_loss_coef": 1.0,
    "image_size": 8, "channels": 4, "num_classes": 1, "zipf_s": 1.0,
    "attention_block": 32,
    "updater": "adamw", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
    "epsilon": 1e-8, "weight_decay": 0.1, "weights_seed": 7,
    "embedding_std": 0.2, "matrix_std": 0.2, "out_proj_std": 0.1,
    "compute_dtype": None, "gradient_checkpointing": True,
}
T = REF.seq_length(CFG)        # 128

#: ONE sequence a batch (the cell's), which the reference takes alone; its
#: `loss_fn` gives (CE + the indexers' losses, (CE, those losses by layer));
#: the bf16 program picks other keys where scores lie within rounding of
#: the threshold (5 %), and a fault may show in the indexers' moment alone
FAMILY = Family(
    ref=REF, system=SYSTEM, cfg=CFG,
    stages=("embed", "layer0", "layer1", "layer2", "indexer", "head"),
    ref_loss=lambda cfg, params, example: REF.loss_fn(cfg, params,
                                                      example[0][0]),
    ref_logits=lambda cfg, params, example: REF.logits(
        cfg, params, example[0][0])[None],
    sequences=1, bf16_stage_gap=5e-2, fault_gap=1e-3, fault_by_stage=True)
