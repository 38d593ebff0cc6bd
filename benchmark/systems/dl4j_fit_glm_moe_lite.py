"""The system under test for ``glm-4.7-flash``: the zoo's ``Glm4MoeLiteLM``
at the configuration's sizes as a ``ComputationGraph`` with two outputs (the
main model's head and the multi-token-prediction module's, over one shared
matrix), trained through ``fit()``. Everything the benchmark takes from the
program for this configuration is here: how to build the network from the
configuration file and hand it the seeded weights, how the harness's host
bytes become the token batches a user's iterator would yield, where AdamW
keeps its first moment, and what the program's counters and its
compiled-step ledger say to the per-layer readers."""
from __future__ import annotations

import jax

from benchmark.lib.manifest import load_module

# the import the parent of the PR that brought this configuration fails
# at, at once: it has no such zoo model
from deeplearning4j_tpu.models import Glm4MoeLiteLM

STEP_PROGRAM = "jit_kstep"        # the scan-of-K program's name in a trace
_LEDGER_NAME = "graph/scan_step"  # the same program in the program's ledger
_REF = load_module("references", "glm-4.7-flash")
_CFG = {}                          # the configuration build() was given

# what does not depend on the container or the model is the first LM
# adapter's: where AdamW keeps its first moment, the stamping
# `ExpertLoadListener`, the goodput ledger's totals, the expert counters
_LM = load_module("systems", "dl4j_fit_kimi_linear")
trained, momentum, make_plan = _LM.trained, _LM.momentum, _LM.make_plan
stamp_listener = _LM.stamp_listener
fit_seconds_by_category = _LM.fit_seconds_by_category
expert_rows_per_step = _LM.expert_rows_per_step
expert_load_max_over_mean = _LM.expert_load_max_over_mean


def network(cfg: dict):
    """An initialised ``ComputationGraph`` at the configuration's sizes
    (the zoo's own weights)."""
    assert cfg["updater"] == "adamw"
    # the one sigmoid router the expert layer has: renormalised, one group,
    # the choice by the scores plus a correction that is not trained
    assert cfg["topk_method"] == "noaux_tc" and cfg["norm_topk_prob"]
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    assert cfg["hidden_act"] == "silu" and not cfg["attention_bias"]
    assert cfg["partial_rotary_factor"] == 1 and cfg["rope_scaling"] is None
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert not cfg["tie_word_embeddings"]
    return Glm4MoeLiteLM(
        vocab_size=cfg["vocab_size"], seq_length=_REF.seq_length(cfg),
        n_embd=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        first_k_dense=cfg["first_k_dense_replace"],
        dense_hidden=cfg["intermediate_size"],
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        n_shared=cfg["n_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"],
        experts_held=tuple(cfg["experts_held"]),
        mtp_layers=cfg["num_nextn_predict_layers"],
        mtp_loss_weight=cfg["mtp_loss_weight"],
        rms_norm_eps=cfg["rms_norm_eps"],
        learning_rate=cfg["learning_rate"], beta1=cfg["beta1"],
        beta2=cfg["beta2"], epsilon=cfg["epsilon"],
        weight_decay=cfg["weight_decay"],
        compute_dtype=cfg["compute_dtype"],
        gradient_checkpointing=cfg["gradient_checkpointing"],
        block_size=cfg["attention_block"]).init()


def build(cfg: dict, params: dict):
    """The network holding the benchmark's seeded float32 weights (same
    names, same shapes: the shared embedding and head once)."""
    net = network(cfg)
    shapes = lambda t: jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), str(a.dtype)), t)
    if shapes(net.params) != shapes(params):
        raise SystemExit("benchmark: the zoo's Glm4MoeLiteLM and the "
                         "configuration file disagree on the parameters")
    net.params = params
    _CFG.clear()
    _CFG.update(cfg)
    return net


def feed(batches, plan=None):
    """The data iterator a user hands to ``fit()``: token batches behind
    the async prefetch. ``batches`` are the harness's (uint8 rows, one-hot)
    pairs; a row's bytes are its token ids as uint16 on disk would be, the
    reference's ``decode_tokens`` reads them, the one-hot is ignored, and
    the two label arrays are the next token (none for a sequence's last
    position) and, for the multi-token-prediction output, the one after it
    (none for the last two). A traced run also switches the program's
    compiled-step ledger on, which keeps the step's op -> scope map for
    the readers."""
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
                labels, keep = _REF.targets(ids)
                n = 1 + _CFG["num_nextn_predict_layers"]
                yield MultiDataSet((ids,), labels[:n], None, keep[:n])

    device = None if plan is None else plan.batch_sharding()
    return AsyncDataSetIterator(TokenBatches(), device=device)


# ------------------------------------------- for the per-layer readers
def op_scopes():
    """{compiled instruction name: op_name} of the step program, from the
    program's ledger; None where the program keeps no such map."""
    from deeplearning4j_tpu.monitor import xla
    recs = [r for r in xla.records() if r.name == _LEDGER_NAME]
    scopes = getattr(recs[-1], "op_scopes", None) if recs else None
    return scopes or None


def expert_rows_walked_over_live():
    """Rows the expert layers' dispatches walked (gathered, multiplied and
    summed back: those of the row tiers they took) over the (token,
    expert) pairs held here, all layers together:
    ``moe_rows_walked_total`` over ``moe_tokens_routed_total{held="yes"}``;
    None without the counters."""
    from deeplearning4j_tpu import monitor
    dump = monitor.dump()
    total = lambda family, **labels: sum(
        s["value"] for s in dump.get(family, {}).get("series", [])
        if all(s["labels"].get(k) == v for k, v in labels.items()))
    walked = total("moe_rows_walked_total")
    live = total("moe_tokens_routed_total", held="yes")
    return walked / live if walked and live else None
