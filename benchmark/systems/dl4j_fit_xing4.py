"""The system under test for ``xing4.0-29b-a4b``: the zoo's ``Xing4LM`` at
the configuration's sizes as a ``ComputationGraph`` (a residual path of four
streams, every sub-layer reading and writing it through Sinkhorn-projected
mappings, around YaRN-rotated latent attention and sigmoid-routed experts
held in part), trained through ``fit()``. Everything the benchmark takes
from the program for this configuration is here: how to build the network
from the configuration file and hand it the seeded weights, how the
harness's host bytes become the token batches a user's iterator would
yield, where AdamW keeps its first moment, and what the program's counters
and its compiled-step ledger say to the per-layer readers."""
from __future__ import annotations

import jax

from benchmark.lib.manifest import load_module

# the import the parent of the PR that brought this configuration fails
# at, at once: it has no such zoo model
from deeplearning4j_tpu.models import Xing4LM

STEP_PROGRAM = "jit_kstep"        # the scan-of-K program's name in a trace
_REF = load_module("references", "xing4.0-29b-a4b")
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
    assert cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"]
    assert cfg["topk_method"] == "noaux_tc"
    assert cfg["n_group"] == cfg["topk_group"] == cfg["moe_layer_freq"] == 1
    assert cfg["hidden_act"] == "silu" and not cfg["attention_bias"]
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert not cfg["tie_word_embeddings"]
    assert cfg["num_nextn_predict_layers"] == 0     # the module is not built
    scaling = cfg["rope_scaling"]
    assert scaling["type"] == "yarn"
    return Xing4LM(
        vocab_size=cfg["vocab_size"], seq_length=_REF.seq_length(cfg),
        n_embd=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_streams=cfg["hc_mult"], sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=cfg["hc_eps"],
        res_clamp=(cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]),
        n_heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]), rope_factor=scaling["factor"],
        rope_original_max_position=scaling[
            "original_max_position_embeddings"],
        rope_beta_fast=scaling["beta_fast"],
        rope_beta_slow=scaling["beta_slow"], rope_mscale=scaling["mscale"],
        rope_mscale_all_dim=scaling["mscale_all_dim"],
        first_k_dense=_REF.dense_layers(cfg),
        dense_hidden=cfg["intermediate_size"],
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        n_shared=cfg["n_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"],
        experts_held=tuple(cfg["experts_held"]),
        rms_norm_eps=cfg["rms_norm_eps"],
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
        raise SystemExit("benchmark: the zoo's Xing4LM and the "
                         "configuration file disagree on the parameters")
    # committed to the chip from the start: a step leaves its results
    # committed, and a net whose leaves start uncommitted compiles its step
    # a SECOND time at the second call
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


# ------------------------------------------- for the per-layer readers
def mhc_res_gap():
    """The program's gauge ``mhc_res_gap{layer}`` at the layer where it is
    largest: how far a row or column sum of H_res is from 1 after the
    Sinkhorn steps, the last step the fit loop saw; None without it."""
    from deeplearning4j_tpu import monitor
    series = monitor.dump().get("mhc_res_gap", {}).get("series", [])
    return max((s["value"] for s in series), default=None)
