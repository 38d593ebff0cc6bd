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

`Glm4MoeLiteLM` is the GLM-4.7-Flash family (``glm4_moe_lite``): rotated
latent attention with a low-rank query in every layer, the same expert
layer, and a multi-token-prediction module that shares the embedding and
the head with the trunk: a `ComputationGraph` with two weighted outputs.

`Lfm2MoeLM` is the LFM2 mixture-of-experts family (``lfm2_moe``): gated
short convolutions three layers to one of grouped-query attention (q/k
normed, rotated) by the published list of layer types, leading dense SwiGLU
layers and then sigmoid-routed SwiGLU experts with NO shared expert, the
head tied to the embedding: a `ComputationGraph` whose head reads the
embedding's leaf.

`KeyeVL2LM` is the language model of Kwai-Keye's Keye-VL-2.0 family
(``KeyeVL2``; the vision tower is NOT here): grouped-query attention whose
heads are wider than the stream's share (``head_dim``), q/k-normed, rotated
with the frequencies shared out among three rows of positions, SPARSE: a
learned indexer picks the keys each query attends and is trained by its own
loss; softmax-routed SwiGLU experts with no shared one in every layer; an
untied head.

`NemotronHLM` is NVIDIA's Nemotron-H family (``nemotron_h``): every block
ONE mixer behind a pre-norm, named by a pattern string: Mamba-2 state-space
layers, position-free grouped-query attention, and expert layers whose
routed ReLU^2 experts live in a latent of the stream beside a ReLU^2 shared
expert on it; an untied head.

`Xing4LM` is XingChen's Xing4.0 family (``xing4_0``): the residual path is
``n_streams`` streams wide (manifold-constrained hyper-connections:
`HyperConnectedBlock` between `StreamsInVertex` and `StreamsOutVertex`), around
YaRN-rotated latent attention with a low-rank query and the
sigmoid-routed SwiGLU experts beside a shared one; an untied head.
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


@dataclasses.dataclass
class Glm4MoeLiteLM(ZooModel):
    """Decoder-only LM of Zhipu's GLM-4.7-Flash family (``model_type``
    ``glm4_moe_lite``): token embedding -> pre-norm blocks (RMSNorm, no
    biases) -> RMSNorm -> untied head, sparse cross-entropy over blocks of
    positions, as a `ComputationGraph` over one input of token ids.

    Every layer attends by `MultiHeadLatentAttention` with a low-rank
    query (``q_rank``) and rotated positions (``rope_theta``, all
    ``rope_dim`` dims); the first ``first_k_dense`` layers have a SwiGLU
    MLP of width ``dense_hidden``, the others the expert layer (sigmoid
    router over ``n_experts``, ``top_k`` a token, renormalised and scaled
    by ``routed_scale``, one shared expert; ``experts_held``: the range
    this chip holds, None for all).

    ``mtp_layers`` (0 or 1, the published ``num_nextn_predict_layers``)
    adds DeepSeek-V3's multi-token-prediction module, every vertex of it
    under the scope ``mtp``: for position i, ``W_eh [RMSNorm(Emb(t_{i+1}))
    ; RMSNorm(h_i)]`` with ``h_i`` the last block's output (before the
    final norm) and ``Emb`` the TRUNK's embedding (vertex ``mtp_embed``
    reads the parameters of ``embed``), one more block, a norm, and the
    TRUNK's head (``mtp_head`` reads ``head``'s) scored against
    ``t_{i+2}``. The net then has two outputs and `fit()` wants two label
    arrays with their masks: next tokens (none for a sequence's last
    position) and next-next tokens (none for the last two); the score is
    ``L_main + mtp_loss_weight * L_mtp``. With ``mtp_layers=0`` it is the
    plain trunk with one output.

    Defaults: the published shape cut to widths a CPU test can run;
    `benchmark/configs/glm-4.7-flash.json` holds the published sizes."""
    vocab_size: int = 1024
    seq_length: int = 256
    n_embd: int = 128
    n_layers: int = 3
    n_heads: int = 4
    q_rank: int = 48
    kv_rank: int = 32
    nope_dim: int = 24
    rope_dim: int = 8
    v_dim: int = 32
    rope_theta: float = 1e6
    first_k_dense: int = 1
    dense_hidden: int = 512
    n_experts: int = 16
    top_k: int = 4
    expert_hidden: int = 64
    n_shared: int = 1
    routed_scale: float = 1.8
    experts_held: Optional[Tuple[int, int]] = None
    mtp_layers: int = 1
    mtp_loss_weight: float = 0.1
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
        from deeplearning4j_tpu.nn.conf.graph_vertices import (
            MergeVertex, ShiftTimeSeriesVertex,
        )
        from deeplearning4j_tpu.nn.layers.attention import (
            GatedMLP, LinearProjection,
        )
        from deeplearning4j_tpu.nn.layers.linear_attention import (
            MultiHeadLatentAttention,
        )
        if self.mtp_layers not in (0, 1):
            raise ValueError("mtp_layers: 0 or 1 (the family publishes one "
                             "multi-token-prediction module)")
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(AdamW(self.learning_rate, beta1=self.beta1,
                            beta2=self.beta2, epsilon=self.epsilon,
                            weight_decay=self.weight_decay,
                            decay_matrices_only=True))
             .gradient_checkpointing(self.gradient_checkpointing))
        if self.compute_dtype:
            b = b.compute_dtype(self.compute_dtype)
        g = b.graph_builder().add_inputs("ids").set_input_types(
            InputType.recurrent(1, self.seq_length))
        mla = MultiHeadLatentAttention(
            n_out=self.n_embd, n_heads=self.n_heads, nope_dim=self.nope_dim,
            rope_dim=self.rope_dim, v_dim=self.v_dim, kv_rank=self.kv_rank,
            q_rank=self.q_rank, rotate=True, rope_theta=self.rope_theta,
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
        block = lambda ffn: TransformerBlock(
            n_out=self.n_embd, n_heads=self.n_heads, norm="rms",
            norm_epsilon=self.rms_norm_eps, has_bias=False, attn=mla, ffn=ffn)
        norm = RMSNormLayer(epsilon=self.rms_norm_eps)
        embed = EmbeddingSequenceLayer(n_out=self.n_embd,
                                       n_in=self.vocab_size)
        head = RnnOutputLayer(n_out=self.vocab_size, activation="softmax",
                              loss="sparse_mcxent", has_bias=False,
                              weight_init="normal")
        g.add_layer("embed", embed, "ids")
        last = "embed"
        for i in range(self.n_layers):
            g.add_layer(f"layer{i}",
                        block(dense if i < self.first_k_dense else experts),
                        last)
            last = f"layer{i}"
        g.add_layer("norm", norm, last)
        g.add_layer("head", head, "norm")
        if not self.mtp_layers:
            return g.set_outputs("head").build()
        mtp = dict(scope="mtp")
        g.add_vertex("mtp_ids", ShiftTimeSeriesVertex(steps=1), "ids", **mtp)
        g.add_layer("mtp_embed", embed, "mtp_ids", params_of="embed", **mtp)
        g.add_layer("mtp_enorm", norm, "mtp_embed", **mtp)
        g.add_layer("mtp_hnorm", norm, last, **mtp)
        g.add_vertex("mtp_merge", MergeVertex(), "mtp_enorm", "mtp_hnorm",
                     **mtp)
        g.add_layer("mtp_proj", LinearProjection(
            n_out=self.n_embd, weight_init="normal"), "mtp_merge", **mtp)
        g.add_layer("mtp_block", block(experts), "mtp_proj", **mtp)
        g.add_layer("mtp_norm", norm, "mtp_block", **mtp)
        g.add_layer("mtp_head", head, "mtp_norm", params_of="head", **mtp)
        return (g.set_outputs("head", "mtp_head")
                .set_output_weights(1.0, self.mtp_loss_weight).build())

    @staticmethod
    def mtp_targets(ids):
        """The two label arrays and masks `fit()` takes for (B, T) token
        ids with the module on: ``((next, next-next), (keep, keep2))``,
        float32 0/1 masks; the last position of a sequence has no next
        token and the last two no next-next one."""
        import numpy as np
        ids = np.asarray(ids)
        keep = np.ones(ids.shape, np.float32)
        keep[:, -1:] = 0.0
        keep2 = np.ones(ids.shape, np.float32)
        keep2[:, -2:] = 0.0
        return ((np.roll(ids, -1, axis=1), np.roll(ids, -2, axis=1)),
                (keep, keep2))


@dataclasses.dataclass
class Lfm2MoeLM(ZooModel):
    """Decoder-only LM of Liquid AI's LFM2 mixture-of-experts family
    (``model_type`` ``lfm2_moe``): token embedding -> pre-norm blocks
    (RMSNorm, no biases) -> RMSNorm -> a head TIED to the embedding
    (``logits = h E^T``: vertex ``head`` reads the parameters of
    ``embed``, one leaf in the params and in the updater's state), sparse
    cross-entropy over blocks of positions, as a `ComputationGraph` over
    one input of token ids.

    ``layer_types`` names each layer's operator as the published
    config.json does: ``"conv"`` is a `GatedShortConv` of ``conv_kernel``
    taps, ``"full_attention"`` a `MultiHeadAttention` of ``n_heads`` query
    heads on ``n_kv_heads`` key/value heads, q and k RMS-normed over the
    head width before the rotation (``rope_theta``, the halves of a head
    rotated against each other), causal, on the fused kernel where there
    is a TPU. The first ``num_dense_layers`` layers have a SwiGLU MLP of
    width ``dense_hidden``, the others the expert layer: a sigmoid router
    over ``n_experts``, ``top_k`` a token chosen by the scores plus a
    non-trained bias, weights renormalised and scaled by ``routed_scale``,
    no shared expert; ``experts_held`` is the range of experts this chip
    holds (None: all), and a token none of whose experts is held gets
    exactly zero from the layer.

    Defaults: the published shape cut to widths a CPU test can run;
    `benchmark/configs/lfm2-24b-a2b.json` holds the published sizes."""
    vocab_size: int = 1024
    seq_length: int = 256
    n_embd: int = 128
    layer_types: Tuple[str, ...] = ("conv", "full_attention", "conv", "conv",
                                    "conv")
    n_heads: int = 8
    n_kv_heads: int = 2
    conv_kernel: int = 3
    rope_theta: float = 1e6
    num_dense_layers: int = 1
    dense_hidden: int = 512
    n_experts: int = 16
    top_k: int = 4
    expert_hidden: int = 64
    routed_scale: float = 1.0
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
        from deeplearning4j_tpu.nn.layers.attention import (
            GatedMLP, MultiHeadAttention,
        )
        from deeplearning4j_tpu.nn.layers.linear_attention import (
            GatedShortConv,
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
        g = b.graph_builder().add_inputs("ids").set_input_types(
            InputType.recurrent(1, self.seq_length))
        operators = {
            "conv": GatedShortConv(n_out=self.n_embd,
                                   conv_kernel=self.conv_kernel,
                                   weight_init="normal"),
            "full_attention": MultiHeadAttention(
                n_out=self.n_embd, n_heads=self.n_heads,
                n_kv_heads=self.n_kv_heads, causal=True, use_rope=True,
                rope_base=self.rope_theta, qk_norm=True,
                norm_epsilon=self.rms_norm_eps, has_bias=False,
                attention_impl="flash", block_size=self.block_size,
                weight_init="normal"),
        }
        dense = GatedMLP(n_out=self.n_embd, hidden=self.dense_hidden,
                         weight_init="normal")
        experts = MoEFeedForward(
            n_out=self.n_embd, n_experts=self.n_experts, top_k=self.top_k,
            hidden=self.expert_hidden, activation="swish", gated=True,
            has_bias=False, experts_held=self.experts_held,
            router="sigmoid", routed_scale=self.routed_scale, n_shared=0,
            weight_init="normal")
        g.add_layer("embed", EmbeddingSequenceLayer(
            n_out=self.n_embd, n_in=self.vocab_size), "ids")
        last = "embed"
        for i, kind in enumerate(self.layer_types):
            if kind not in operators:
                raise ValueError(f"layer_types[{i}] = {kind!r}: 'conv' or "
                                 "'full_attention'")
            g.add_layer(f"layer{i}", TransformerBlock(
                n_out=self.n_embd, n_heads=self.n_heads, norm="rms",
                norm_epsilon=self.rms_norm_eps, has_bias=False,
                attn=operators[kind],
                ffn=dense if i < self.num_dense_layers else experts), last)
            last = f"layer{i}"
        g.add_layer("norm", RMSNormLayer(epsilon=self.rms_norm_eps), last)
        g.add_layer("head", RnnOutputLayer(
            n_out=self.vocab_size, activation="softmax",
            loss="sparse_mcxent", has_bias=False, tied_embedding=True),
            "norm", params_of="embed")
        return g.set_outputs("head").build()


@dataclasses.dataclass
class KeyeVL2LM(ZooModel):
    """Decoder-only LANGUAGE MODEL of Kwai-Keye's Keye-VL-2.0 family
    (``model_type`` ``KeyeVL2``; the vision tower and image input are not
    part of it: text ids in, the three rows of rotary positions coincide):
    token embedding -> pre-norm blocks (RMSNorm, no biases) -> RMSNorm ->
    an UNTIED head, sparse cross-entropy over blocks of positions, as a
    `ComputationGraph` over one input of token ids.

    Every layer: `MultiHeadAttention` of ``n_heads`` query heads on
    ``n_kv_heads`` key/value heads, ``head_dim`` wide whatever the stream's
    width, q and k RMS-normed over the head width before the rotation
    (``rope_theta``; ``mrope_section`` shares the ``head_dim / 2``
    frequencies out among the time, height and width rows), causal and
    SPARSE: a `LightningIndexer` of ``indexer_heads`` heads of
    ``indexer_head_dim`` on one key head scores the keys of every query,
    the ``topk`` highest are attended (exactly those), and the indexer is
    trained by ``indexer_loss_coef`` times its Kullback-Leibler loss
    through `attach_auxiliary_loss` (the score ``fit()`` reports stays the
    cross-entropy). Then the expert layer: a softmax router over
    ``n_experts``, the ``top_k`` largest kept and renormalised, SwiGLU
    experts of ``expert_hidden``, NO shared expert; ``experts_held`` is
    the range of experts this chip holds (None: all), and a token none of
    whose experts is held gets exactly zero from the layer.

    Defaults: the published shape cut to widths a CPU test can run;
    `benchmark/configs/keye-vl-2.0-30b-a3b.json` holds the published
    sizes."""
    vocab_size: int = 1024
    seq_length: int = 256
    n_embd: int = 128
    n_layers: int = 2
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 32
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (4, 6, 6)
    indexer_heads: int = 4
    indexer_head_dim: int = 16
    topk: int = 64
    indexer_loss_coef: float = 1.0
    n_experts: int = 16
    top_k: int = 4
    expert_hidden: int = 64
    experts_held: Optional[Tuple[int, int]] = None
    rms_norm_eps: float = 1e-6
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
        from deeplearning4j_tpu.nn.layers.attention import (
            LightningIndexer, MultiHeadAttention,
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
        g = b.graph_builder().add_inputs("ids").set_input_types(
            InputType.recurrent(1, self.seq_length))
        attention = MultiHeadAttention(
            n_out=self.n_embd, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim, causal=True,
            use_rope=True, rope_base=self.rope_theta,
            rope_sections=tuple(self.mrope_section), qk_norm=True,
            norm_epsilon=self.rms_norm_eps, has_bias=False,
            attention_impl="flash", block_size=self.block_size,
            weight_init="normal",
            indexer=LightningIndexer(
                n_heads=self.indexer_heads, head_dim=self.indexer_head_dim,
                topk=self.topk, rope_base=self.rope_theta,
                norm_epsilon=self.rms_norm_eps,
                loss_coef=self.indexer_loss_coef, weight_init="normal"))
        experts = MoEFeedForward(
            n_out=self.n_embd, n_experts=self.n_experts, top_k=self.top_k,
            hidden=self.expert_hidden, activation="swish", gated=True,
            has_bias=False, experts_held=self.experts_held,
            router="softmax", n_shared=0, weight_init="normal")
        g.add_layer("embed", EmbeddingSequenceLayer(
            n_out=self.n_embd, n_in=self.vocab_size), "ids")
        last = "embed"
        for i in range(self.n_layers):
            g.add_layer(f"layer{i}", TransformerBlock(
                n_out=self.n_embd, n_heads=self.n_heads, norm="rms",
                norm_epsilon=self.rms_norm_eps, has_bias=False,
                attn=attention, ffn=experts), last)
            last = f"layer{i}"
        g.add_layer("norm", RMSNormLayer(epsilon=self.rms_norm_eps), last)
        g.add_layer("head", RnnOutputLayer(
            n_out=self.vocab_size, activation="softmax",
            loss="sparse_mcxent", has_bias=False, weight_init="normal"),
            "norm")
        return g.set_outputs("head").build()


@dataclasses.dataclass
class SdarMoeLM(ZooModel):
    """Block-diffusion LM of JetLM's SDAR family (``model_type``
    ``sdar_moe``) in its TRAINING form, as a `ComputationGraph` over one
    input: the stream ``[noisy copy ; clean copy]`` of ``2 *
    seq_length`` token ids that `data.denoise.BlockDiffusionPreProcessor`
    makes of ``seq_length`` ids. Token embedding -> pre-norm blocks
    (RMSNorm, no biases) -> the first ``seq_length`` rows (the noisy
    half: `TimeSliceVertex`; the head over the clean half would be thrown
    away) -> RMSNorm (a norm of rows: the same numbers as norming before
    the slice) -> an UNTIED head whose score is the denoising loss: row i
    of the noisy half predicts token i ITSELF (no shift), the
    cross-entropy at a masked position weighted by ``1 / t`` of its
    block's noise level, summed and divided by the count of positions
    (`RnnOutputLayer(weighted=True)`), over blocks of positions.

    Every layer: `MultiHeadAttention` of ``n_heads`` query heads on
    ``n_kv_heads`` key/value heads, ``head_dim`` wide whatever the
    stream's width, q and k RMS-normed over the head width before the
    rotation (``rope_theta``; positions restart at the clean half), DENSE
    under the block-diffusion rule at ``block_length``: a noisy block sees
    itself both ways and the clean copies of the blocks before it, the
    clean copy is block-causal
    (`nn/layers/attention.py::block_diffusion_visible`; on a TPU the
    flash kernels walk only the tiles the rule leaves visible). Then the
    expert layer `KeyeVL2LM` has: a softmax router over ``n_experts``,
    the ``top_k`` largest kept and renormalised, SwiGLU experts of
    ``expert_hidden``, NO shared expert; ``experts_held`` is the range of
    experts this chip holds (None: all), and a token none of whose
    experts is held gets exactly zero from the layer.

    Generation (a block denoised over several passes against a cache of
    clean blocks) is not part of it. Defaults: the published shape cut to
    widths a CPU test can run; `benchmark/configs/sdar-30b-a3b-chat.json`
    holds the published sizes."""
    vocab_size: int = 1024
    seq_length: int = 128
    block_length: int = 4
    n_embd: int = 128
    n_layers: int = 2
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 32
    rope_theta: float = 1e6
    n_experts: int = 16
    top_k: int = 4
    expert_hidden: int = 64
    experts_held: Optional[Tuple[int, int]] = None
    rms_norm_eps: float = 1e-6
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
        from deeplearning4j_tpu.nn.conf.graph_vertices import TimeSliceVertex
        from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(AdamW(self.learning_rate, beta1=self.beta1,
                            beta2=self.beta2, epsilon=self.epsilon,
                            weight_decay=self.weight_decay,
                            decay_matrices_only=True))
             .gradient_checkpointing(self.gradient_checkpointing))
        if self.compute_dtype:
            b = b.compute_dtype(self.compute_dtype)
        g = b.graph_builder().add_inputs("ids").set_input_types(
            InputType.recurrent(1, 2 * self.seq_length))
        attention = MultiHeadAttention(
            n_out=self.n_embd, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            block_diffusion=self.block_length, use_rope=True,
            rope_base=self.rope_theta, qk_norm=True,
            norm_epsilon=self.rms_norm_eps, has_bias=False,
            attention_impl="flash", block_size=self.block_size,
            weight_init="normal")
        experts = MoEFeedForward(
            n_out=self.n_embd, n_experts=self.n_experts, top_k=self.top_k,
            hidden=self.expert_hidden, activation="swish", gated=True,
            has_bias=False, experts_held=self.experts_held,
            router="softmax", n_shared=0, weight_init="normal")
        g.add_layer("embed", EmbeddingSequenceLayer(
            n_out=self.n_embd, n_in=self.vocab_size), "ids")
        last = "embed"
        for i in range(self.n_layers):
            g.add_layer(f"layer{i}", TransformerBlock(
                n_out=self.n_embd, n_heads=self.n_heads, norm="rms",
                norm_epsilon=self.rms_norm_eps, has_bias=False,
                attn=attention, ffn=experts), last)
            last = f"layer{i}"
        g.add_vertex("noisy", TimeSliceVertex(steps=self.seq_length), last)
        g.add_layer("norm", RMSNormLayer(epsilon=self.rms_norm_eps), "noisy")
        g.add_layer("head", RnnOutputLayer(
            n_out=self.vocab_size, activation="softmax",
            loss="sparse_mcxent", has_bias=False, weight_init="normal",
            weighted=True), "norm")
        return g.set_outputs("head").build()


@dataclasses.dataclass
class NemotronHLM(ZooModel):
    """Decoder-only LM of NVIDIA's Nemotron-H family (``model_type``
    ``nemotron_h``): token embedding -> blocks that are ONE mixer behind a
    pre-norm each (`MixerBlock`: ``y = x + mixer(RMSNorm(x))``, no biases
    but the convolution's) -> RMSNorm -> an UNTIED head, sparse
    cross-entropy over blocks of positions, as a `ComputationGraph` over
    one input of token ids.

    ``pattern`` names each block's mixer as the published
    ``hybrid_override_pattern`` does: ``M`` a `Mamba2Mixer`
    (``mamba_heads`` heads of ``mamba_head_dim`` in ``mamba_groups`` groups
    of state ``state_dim``, taps of ``conv_kernel`` with a bias, chunks of
    ``chunk``), ``*`` a `MultiHeadAttention` of ``n_heads`` query heads on
    ``n_kv_heads`` key/value heads of ``head_dim``, causal, with NO
    rotation and no q/k norm (the state-space layers carry the order), on
    the fused kernel where there is a TPU, ``E`` the expert layer: a
    sigmoid router over ``n_experts`` on the stream, ``top_k`` a token
    chosen by the scores plus a non-trained bias, weights renormalised and
    scaled by ``routed_scale``; un-gated ReLU^2 experts of
    ``expert_hidden`` inside a latent of ``latent`` (``x W_down`` in,
    their weighted sum through ``W_up`` out); ONE un-gated ReLU^2 shared
    expert of ``shared_hidden`` on the stream. ``experts_held`` is the
    range of experts this chip holds (None: all). A tensor-parallel slice
    of a Mamba-2 or attention layer is that layer with the slice's heads
    and groups: the sizes say it, no option does.

    The family's multi-token-prediction module is not built. Defaults: the
    published shape cut to widths a CPU test can run;
    `benchmark/configs/nemotron-3-super-120b-a12b.json` holds the
    published sizes."""
    vocab_size: int = 1024
    seq_length: int = 256
    n_embd: int = 128
    pattern: str = "*EMEME"
    mamba_heads: int = 4
    mamba_head_dim: int = 16
    mamba_groups: int = 1
    state_dim: int = 32
    conv_kernel: int = 4
    chunk: int = 128
    n_heads: int = 4
    n_kv_heads: int = 1
    head_dim: int = 32
    n_experts: int = 16
    top_k: int = 4
    expert_hidden: int = 64
    latent: int = 64
    shared_hidden: int = 256
    routed_scale: float = 1.0
    experts_held: Optional[Tuple[int, int]] = None
    dt_min: float = 1e-3
    dt_max: float = 0.1
    dt_floor: float = 1e-4
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
        from deeplearning4j_tpu.nn.layers.attention import (
            MixerBlock, MultiHeadAttention,
        )
        from deeplearning4j_tpu.nn.layers.linear_attention import Mamba2Mixer
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(AdamW(self.learning_rate, beta1=self.beta1,
                            beta2=self.beta2, epsilon=self.epsilon,
                            weight_decay=self.weight_decay,
                            decay_matrices_only=True))
             .gradient_checkpointing(self.gradient_checkpointing))
        if self.compute_dtype:
            b = b.compute_dtype(self.compute_dtype)
        g = b.graph_builder().add_inputs("ids").set_input_types(
            InputType.recurrent(1, self.seq_length))
        mixers = {
            "M": Mamba2Mixer(
                n_out=self.n_embd, n_heads=self.mamba_heads,
                head_dim=self.mamba_head_dim, n_groups=self.mamba_groups,
                state_dim=self.state_dim, conv_kernel=self.conv_kernel,
                chunk=self.chunk, norm_epsilon=self.rms_norm_eps,
                dt_min=self.dt_min, dt_max=self.dt_max,
                dt_floor=self.dt_floor, weight_init="normal"),
            "*": MultiHeadAttention(
                n_out=self.n_embd, n_heads=self.n_heads,
                n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
                causal=True, use_rope=False, has_bias=False,
                attention_impl="flash", block_size=self.block_size,
                weight_init="normal"),
            "E": MoEFeedForward(
                n_out=self.n_embd, n_experts=self.n_experts,
                top_k=self.top_k, hidden=self.expert_hidden,
                activation="relu2", gated=False, has_bias=False,
                experts_held=self.experts_held, router="sigmoid",
                routed_scale=self.routed_scale, n_shared=1,
                shared_hidden=self.shared_hidden, latent=self.latent,
                weight_init="normal"),
        }
        g.add_layer("embed", EmbeddingSequenceLayer(
            n_out=self.n_embd, n_in=self.vocab_size), "ids")
        last = "embed"
        for i, kind in enumerate(self.pattern):
            if kind not in mixers:
                raise ValueError(f"pattern[{i}] = {kind!r}: 'M' (Mamba-2), "
                                 "'*' (attention) or 'E' (experts)")
            g.add_layer(f"layer{i}", MixerBlock(
                n_out=self.n_embd, mixer=mixers[kind], norm="rms",
                norm_epsilon=self.rms_norm_eps), last)
            last = f"layer{i}"
        g.add_layer("norm", RMSNormLayer(epsilon=self.rms_norm_eps), last)
        g.add_layer("head", RnnOutputLayer(
            n_out=self.vocab_size, activation="softmax",
            loss="sparse_mcxent", has_bias=False, weight_init="normal"),
            "norm")
        return g.set_outputs("head").build()


@dataclasses.dataclass
class Xing4LM(ZooModel):
    """Decoder-only LM of XingChen's Xing4.0 family (``model_type``
    ``xing4_0``): token embedding -> ``n_streams`` copies of it
    (`StreamsInVertex`) -> `HyperConnectedBlock`s, each two sub-layers that read
    and write the streams through learned, input-dependent mappings
    (manifold-constrained hyper-connections: ``sinkhorn_iters`` Sinkhorn
    steps a sub-layer with ``hc_eps`` in the sums, the 4 x 4 logits
    clamped to ``res_clamp``) -> the streams' sum (`StreamsOutVertex`) ->
    RMSNorm -> an UNTIED head, sparse cross-entropy over blocks of
    positions, as a `ComputationGraph` over one input of token ids.

    Every layer attends by `MultiHeadLatentAttention` with a low-rank
    query (``q_rank``), rotated by YaRN's frequencies and temperature
    (``rope_factor`` over ``rope_original_max_position``, ``rope_beta_fast``
    / ``_slow``, ``rope_mscale`` / ``_all_dim``; ``rope_factor`` 1 or less:
    the plain rotation); the first ``first_k_dense`` layers have a SwiGLU
    MLP of width ``dense_hidden``, the others the expert layer (sigmoid
    router over ``n_experts``, ``top_k`` a token, renormalised and scaled
    by ``routed_scale``, ``n_shared`` shared experts; ``experts_held``: the
    range this chip holds, None for all). A tensor-parallel slice of the
    attention is the layer at the slice's ``n_heads``: the low-rank
    projections and their norms are whole on every chip, ``Wqb``, ``Wkvb``
    and ``Wo`` hold the slice's heads.

    The family's multi-token-prediction module is not built. Defaults: the
    published shape cut to widths a CPU test can run;
    `benchmark/configs/xing4.0-29b-a4b.json` holds the published sizes."""
    vocab_size: int = 1024
    seq_length: int = 256
    n_embd: int = 128
    n_layers: int = 3
    n_streams: int = 4
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    res_clamp: Tuple[float, float] = (-30.0, 30.0)
    n_heads: int = 4
    q_rank: int = 48
    kv_rank: int = 32
    nope_dim: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    first_k_dense: int = 1
    dense_hidden: int = 512
    n_experts: int = 16
    top_k: int = 4
    expert_hidden: int = 64
    n_shared: int = 1
    routed_scale: float = 2.0
    experts_held: Optional[Tuple[int, int]] = None
    rms_norm_eps: float = 1e-6
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
        from deeplearning4j_tpu.nn.conf.graph_vertices import (
            StreamsInVertex, StreamsOutVertex,
        )
        from deeplearning4j_tpu.nn.layers.attention import GatedMLP
        from deeplearning4j_tpu.nn.layers.hyper_connection import (
            HyperConnectedBlock,
        )
        from deeplearning4j_tpu.nn.layers.linear_attention import (
            MultiHeadLatentAttention,
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
        g = b.graph_builder().add_inputs("ids").set_input_types(
            InputType.recurrent(1, self.seq_length))
        yarn = self.rope_factor > 1
        mla = MultiHeadLatentAttention(
            n_out=self.n_embd, n_heads=self.n_heads, nope_dim=self.nope_dim,
            rope_dim=self.rope_dim, v_dim=self.v_dim, kv_rank=self.kv_rank,
            q_rank=self.q_rank, rotate=True, rope_theta=self.rope_theta,
            norm_epsilon=self.rms_norm_eps, block_size=self.block_size,
            weight_init="normal", rope_scaling="yarn" if yarn else None,
            rope_factor=self.rope_factor,
            rope_original_max_position=self.rope_original_max_position,
            rope_beta_fast=self.rope_beta_fast,
            rope_beta_slow=self.rope_beta_slow,
            rope_mscale=self.rope_mscale,
            rope_mscale_all_dim=self.rope_mscale_all_dim)
        dense = GatedMLP(n_out=self.n_embd, hidden=self.dense_hidden,
                         weight_init="normal")
        experts = MoEFeedForward(
            n_out=self.n_embd, n_experts=self.n_experts, top_k=self.top_k,
            hidden=self.expert_hidden, activation="swish", gated=True,
            has_bias=False, experts_held=self.experts_held,
            router="sigmoid", routed_scale=self.routed_scale,
            n_shared=self.n_shared, weight_init="normal")
        block = lambda ffn: HyperConnectedBlock(
            n_out=self.n_embd, n_streams=self.n_streams, attn=mla, ffn=ffn,
            norm="rms", norm_epsilon=self.rms_norm_eps,
            sinkhorn_iters=self.sinkhorn_iters, hc_eps=self.hc_eps,
            res_clamp=tuple(self.res_clamp))
        g.add_layer("embed", EmbeddingSequenceLayer(
            n_out=self.n_embd, n_in=self.vocab_size), "ids")
        g.add_vertex("streams", StreamsInVertex(n_streams=self.n_streams),
                     "embed")
        last = "streams"
        for i in range(self.n_layers):
            g.add_layer(f"layer{i}",
                        block(dense if i < self.first_k_dense else experts),
                        last)
            last = f"layer{i}"
        g.add_vertex("sum", StreamsOutVertex(n_streams=self.n_streams), last)
        g.add_layer("norm", RMSNormLayer(epsilon=self.rms_norm_eps), "sum")
        g.add_layer("head", RnnOutputLayer(
            n_out=self.vocab_size, activation="softmax",
            loss="sparse_mcxent", has_bias=False, weight_init="normal"),
            "norm")
        return g.set_outputs("head").build()
