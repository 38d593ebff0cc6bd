"""The system under test for ``nemotron-3-super-120b-a12b``: the zoo's
``NemotronHLM`` at the configuration's sizes as a ``ComputationGraph`` of
single-mixer blocks (Mamba-2, position-free grouped-query attention,
LatentMoE), trained through ``fit()``. Everything the benchmark takes from
the program for this configuration is here: how to build the network from
the configuration file and hand it the seeded weights, how the harness's
host bytes become the token batches a user's iterator would yield, where
AdamW keeps its first moment, and what the program's counters and its
compiled-step ledger say to the per-layer readers."""
from __future__ import annotations

import jax

from benchmark.lib.manifest import load_module

# the import the parent of the PR that brought this configuration fails
# at, at once: it has no such zoo model
from deeplearning4j_tpu.models import NemotronHLM

STEP_PROGRAM = "jit_kstep"        # the scan-of-K program's name in a trace
_LEDGER_NAME = "graph/scan_step"  # the same program in the program's ledger
_REF = load_module("references", "nemotron-3-super-120b-a12b")
_CFG = {}                          # the configuration build() was given

# what does not depend on the model is the first LM adapters': where AdamW
# keeps its first moment, the stamping `ExpertLoadListener`, the goodput
# ledger's totals, the expert counters, the step's op -> scope map
_LM = load_module("systems", "dl4j_fit_glm_moe_lite")
trained, momentum, make_plan = _LM.trained, _LM.momentum, _LM.make_plan
stamp_listener = _LM.stamp_listener
fit_seconds_by_category = _LM.fit_seconds_by_category
expert_rows_per_step = _LM.expert_rows_per_step
expert_load_max_over_mean = _LM.expert_load_max_over_mean
expert_rows_walked_over_live = _LM.expert_rows_walked_over_live
op_scopes = _LM.op_scopes


def network(cfg: dict):
    """An initialised ``ComputationGraph`` at the configuration's sizes
    (the zoo's own weights)."""
    assert cfg["updater"] == "adamw"
    # the one sigmoid router the expert layer has: renormalised, one group,
    # the choice by the scores plus a correction that is not trained
    assert cfg["norm_topk_prob"] and cfg["n_group"] == cfg["topk_group"] == 1
    assert cfg["mlp_hidden_act"] == "relu2" and not cfg["mlp_bias"]
    assert cfg["mamba_hidden_act"] == "silu" and cfg["use_conv_bias"]
    assert not (cfg["mamba_proj_bias"] or cfg["attention_bias"]
                or cfg["use_bias"] or cfg["tie_word_embeddings"])
    assert cfg["n_shared_experts"] == 1
    assert cfg["num_nextn_predict_layers"] == 0     # the module is not built
    assert cfg["layer_norm_epsilon"] == cfg["norm_eps"]
    return NemotronHLM(
        vocab_size=cfg["vocab_size"], seq_length=_REF.seq_length(cfg),
        n_embd=cfg["hidden_size"], pattern="".join(_REF.layer_kinds(cfg)),
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], mamba_groups=cfg["n_groups"],
        state_dim=cfg["ssm_state_size"], conv_kernel=cfg["conv_kernel"],
        chunk=cfg["chunk_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        latent=cfg["moe_latent_size"],
        shared_hidden=cfg["moe_shared_expert_intermediate_size"],
        routed_scale=cfg["routed_scaling_factor"],
        experts_held=tuple(cfg["experts_held"]),
        dt_min=cfg["time_step_min"], dt_max=cfg["time_step_max"],
        dt_floor=cfg["time_step_floor"],
        rms_norm_eps=cfg["layer_norm_epsilon"],
        learning_rate=cfg["learning_rate"], beta1=cfg["beta1"],
        beta2=cfg["beta2"], epsilon=cfg["epsilon"],
        weight_decay=cfg["weight_decay"],
        compute_dtype=cfg["compute_dtype"],
        gradient_checkpointing=cfg["gradient_checkpointing"],
        block_size=cfg["attention_block"]).init()


def build(cfg: dict, params: dict):
    """The network holding the benchmark's seeded float32 weights (same
    names, same shapes)."""
    net = network(cfg)
    shapes = lambda t: jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), str(a.dtype)), t)
    if shapes(net.params) != shapes(params):
        raise SystemExit("benchmark: the zoo's NemotronHLM and the "
                         "configuration file disagree on the parameters")
    # committed to the chip from the start: a step leaves its results
    # committed, and a net whose leaves start uncommitted compiles its step
    # a SECOND time at the second call (70 s and 72 MB of compile cache at
    # these sizes)
    net.params, net.opt_state, net.state = jax.device_put(
        (params, net.opt_state, net.state), jax.devices()[0])
    _CFG.clear()
    _CFG.update(cfg)
    return net


def feed(batches, plan=None):
    """The data iterator a user hands to ``fit()``: token batches behind
    the async prefetch. ``batches`` are the harness's (uint8 rows, one-hot)
    pairs; a row's bytes are its token ids as uint16 on disk would be, the
    reference's ``decode_tokens`` reads them, the one-hot is ignored, and
    the targets are the next token (none for a sequence's last position).
    A traced run also switches the program's compiled-step ledger on,
    which keeps the step's op -> scope map for the readers."""
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.data.async_iterator import AsyncDataSetIterator
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.data.iterator import DataSetIterator
    if monitor.tracing_enabled():
        monitor.xla.enable_ledger()

    class TokenBatches(DataSetIterator):
        def __iter__(self):
            for rows, _ in batches:
                ids = _REF.decode_tokens(_CFG, rows)
                nxt, keep = _REF.targets(ids)
                yield MultiDataSet((ids,), (nxt,), None, (keep,))

    device = None if plan is None else plan.batch_sharding()
    return AsyncDataSetIterator(TokenBatches(), device=device)
