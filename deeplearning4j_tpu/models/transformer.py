"""TransformerLM — the long-context flagship model family.

No architecture analog in the DL4J zoo (its sequence model is
TextGenerationLSTM, `zoo/model/TextGenerationLSTM.java`); this is the
TPU-native successor: a decoder-only transformer LM designed around the
mesh —

- dp  : batch over "data" (ParallelWrapper),
- tp  : Megatron-style tensor parallelism over "model" via sharding rules
        (column-parallel Wq/Wk/Wv/W1, row-parallel Wo/W2 — XLA inserts the
        matched all-reduce pair),
- sp  : ring attention over "seq" (ContextParallelTrainer),
- ep  : MoE expert dim over "model" (MoEFeedForward stacks experts on a
        leading axis; a chip of an expert-parallel layout is told which
        experts it holds).

`KimiLinearLM` is the hybrid family: RMSNorm, no biases, Kimi Delta
Attention (a chunked gated delta rule) three layers to one of position-free
latent attention by two layout lists, a dense SwiGLU first layer and then
sigmoid-routed SwiGLU experts beside a shared expert.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.models.zoo import ZooModel
from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (
    EmbeddingSequenceLayer, LayerNormLayer, MoEFeedForward, RMSNormLayer,
    RnnOutputLayer, TransformerBlock,
)
from deeplearning4j_tpu.nn.updaters import AdamW
from deeplearning4j_tpu.parallel.mesh import MODEL_AXIS
from deeplearning4j_tpu.parallel.sharding import ShardingRules


@dataclasses.dataclass
class TransformerLM(ZooModel):
    """Decoder-only LM: token embedding -> n_layers TransformerBlocks
    (optionally interleaved MoE FFN blocks) -> LN -> tied-untied softmax head.

    Defaults sized for quick experiments; scale n_embd/n_layers/seq_length
    for real runs (keep n_embd a multiple of 128 for MXU tiling)."""
    vocab_size: int = 1024
    seq_length: int = 256
    n_layers: int = 4
    n_embd: int = 256
    n_heads: int = 8
    mlp_ratio: int = 4
    causal: bool = True
    use_rope: bool = True
    moe_every: int = 0          # 0 = dense; k>0 = every k-th block is MoE
    n_experts: int = 8
    dropout: float = 0.0
    learning_rate: float = 3e-4
    seed: int = 123
    attention_impl: str = "dense"
    block_size: int = 512

    def conf(self):
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(AdamW(self.learning_rate))
             .grad_clip_norm(1.0)
             .list())
        b.layer(EmbeddingSequenceLayer(n_out=self.n_embd,
                                       n_in=self.vocab_size))
        for i in range(self.n_layers):
            b.layer(TransformerBlock(
                n_out=self.n_embd, n_heads=self.n_heads,
                mlp_ratio=self.mlp_ratio, causal=self.causal,
                use_rope=self.use_rope,
                attention_dropout=self.dropout,
                residual_dropout=self.dropout,
                attention_impl=self.attention_impl,
                block_size=self.block_size))
            if self.moe_every and (i + 1) % self.moe_every == 0:
                b.layer(MoEFeedForward(n_out=self.n_embd,
                                       n_experts=self.n_experts,
                                       mlp_ratio=self.mlp_ratio))
        b.layer(LayerNormLayer())
        b.layer(RnnOutputLayer(n_out=self.vocab_size, activation="softmax",
                               loss="mcxent"))
        b.set_input_type(InputType.recurrent(1, self.seq_length))
        return b.build()

    @staticmethod
    def sharding_rules() -> ShardingRules:
        """Megatron tp + ep rules for the stack above. Paths look like
        "1/attn/Wq" (block params are nested dicts)."""
        return ShardingRules((
            # attention: column-parallel QKV, row-parallel output
            (r".*/attn/W[qkv]$", P(None, MODEL_AXIS)),
            (r".*/attn/Wo$", P(MODEL_AXIS, None)),
            # MoE (3D, leading expert dim): expert parallelism over "model".
            # Listed before the dense rules — spec_for skips a rule whose
            # spec is longer than the leaf's ndim, so 2D kernels fall through.
            (r".*/W1$", P(MODEL_AXIS, None, None)),
            (r".*/W2$", P(MODEL_AXIS, None, None)),
            # dense MLP: column-parallel up, row-parallel down
            (r".*/W1$", P(None, MODEL_AXIS)),
            (r".*/W2$", P(MODEL_AXIS, None)),
            # embedding: vocab-sharded
            (r"^0/W$", P(MODEL_AXIS, None)),
        ))


@dataclasses.dataclass
class TransformerLMMoE(TransformerLM):
    """Expert-parallel variant: every 2nd block followed by a top-2 MoE FFN."""
    moe_every: int = 2
    n_experts: int = 8


@dataclasses.dataclass
class KimiLinearLM(ZooModel):
    """Decoder-only hybrid LM of Moonshot's Kimi-Linear family: token
    embedding -> pre-norm blocks (RMSNorm, no biases) -> RMSNorm -> untied
    head with a sparse (integer-label) cross-entropy over blocks of
    positions.

    The layer pattern is two lists of 1-based layer numbers, as the
    published config.json's ``linear_attn_config`` has them: the layers of
    ``kda_layers`` attend by `KimiDeltaAttention`, those of
    ``full_attn_layers`` by position-free `MultiHeadLatentAttention`; the
    first ``n_layers`` layers are built. The first ``first_k_dense``
    layers have a SwiGLU MLP of width ``dense_hidden``, the others the
    expert layer: a sigmoid router over ``n_experts``, ``top_k`` a token,
    weights renormalised and scaled by ``routed_scale``, one shared
    expert; ``experts_held`` is the range of experts this chip holds (None:
    all). Defaults: the published shape cut to widths a CPU test can run;
    `benchmark/configs/kimi-linear-48b-a3b.json` holds the published
    sizes."""
    vocab_size: int = 1024
    seq_length: int = 256
    n_embd: int = 128
    n_layers: int = 5
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5)
    full_attn_layers: Tuple[int, ...] = (4,)
    n_heads: int = 4
    kda_head_dim: int = 32
    conv_kernel: int = 4
    kda_chunk: int = 64
    kda_low_rank: int = 0
    nope_dim: int = 32
    rope_dim: int = 16
    v_dim: int = 32
    kv_rank: int = 64
    first_k_dense: int = 1
    dense_hidden: int = 512
    n_experts: int = 16
    top_k: int = 2
    expert_hidden: int = 64
    n_shared: int = 1
    routed_scale: float = 2.446
    experts_held: Optional[Tuple[int, int]] = None
    rms_norm_eps: float = 1e-5
    learning_rate: float = 1e-4
    beta1: float = 0.9
    beta2: float = 0.95
    epsilon: float = 1e-8
    weight_decay: float = 0.1
    compute_dtype: Optional[str] = None
    gradient_checkpointing: bool = True
    seed: int = 123
    block_size: int = 512

    def conf(self):
        from deeplearning4j_tpu.nn.layers.attention import GatedMLP
        from deeplearning4j_tpu.nn.layers.linear_attention import (
            KimiDeltaAttention, MultiHeadLatentAttention,
        )
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(AdamW(self.learning_rate, beta1=self.beta1,
                            beta2=self.beta2, epsilon=self.epsilon,
                            weight_decay=self.weight_decay,
                            decay_matrices_only=True))
             .gradient_checkpointing(self.gradient_checkpointing))
        if self.compute_dtype:
            b = b.compute_dtype(self.compute_dtype)
        b = b.list()
        b.layer(EmbeddingSequenceLayer(n_out=self.n_embd,
                                       n_in=self.vocab_size))
        kda = KimiDeltaAttention(
            n_out=self.n_embd, n_heads=self.n_heads,
            head_dim=self.kda_head_dim, conv_kernel=self.conv_kernel,
            chunk=self.kda_chunk,
            low_rank=self.kda_low_rank, norm_epsilon=self.rms_norm_eps,
            weight_init="normal")
        mla = MultiHeadLatentAttention(
            n_out=self.n_embd, n_heads=self.n_heads, nope_dim=self.nope_dim,
            rope_dim=self.rope_dim, v_dim=self.v_dim, kv_rank=self.kv_rank,
            norm_epsilon=self.rms_norm_eps, block_size=self.block_size,
            weight_init="normal")
        dense = GatedMLP(n_out=self.n_embd, hidden=self.dense_hidden,
                         weight_init="normal")
        experts = MoEFeedForward(
            n_out=self.n_embd, n_experts=self.n_experts, top_k=self.top_k,
            hidden=self.expert_hidden, activation="swish", gated=True,
            has_bias=False, experts_held=self.experts_held,
            router="sigmoid", routed_scale=self.routed_scale,
            n_shared=self.n_shared, weight_init="normal")
        for layer in range(1, self.n_layers + 1):
            if (layer in self.kda_layers) == (layer in self.full_attn_layers):
                raise ValueError(f"layer {layer} has to be in exactly one "
                                 "of kda_layers and full_attn_layers")
            b.layer(TransformerBlock(
                n_out=self.n_embd, n_heads=self.n_heads, norm="rms",
                norm_epsilon=self.rms_norm_eps, has_bias=False,
                attn=kda if layer in self.kda_layers else mla,
                ffn=dense if layer <= self.first_k_dense else experts))
        b.layer(RMSNormLayer(epsilon=self.rms_norm_eps))
        b.layer(RnnOutputLayer(n_out=self.vocab_size, activation="softmax",
                               loss="sparse_mcxent", has_bias=False,
                               weight_init="normal"))
        b.set_input_type(InputType.recurrent(1, self.seq_length))
        return b.build()
