"""The system under test for the GPT-NeoX-family configurations: the
in-tree ``TransformerLM`` stack served by ``ServedLM`` (``DecodeScheduler``
over a ``DecodeEngine`` and its paged KV cache) behind ``ModelServer``'s
HTTP front end, in this process. Everything the benchmark takes from the
program for such a configuration is here."""
from __future__ import annotations

MODEL = "chat"
#: the programs' names in a profiler trace
DECODE_PROGRAM = "jit__decode_fn"
CHUNK_PROGRAM = "jit__chunk_fn"
PREFILL_PROGRAM = "jit__prefill_fn"


class _Weights:
    """What ``DecodeEngine`` reads of a model (``conf``, ``layers``,
    ``params``), holding the seeded weights until the engine takes them.
    The engine copies the tree it is given; handing the tree over instead
    of keeping a second reference lets the first copy go before the page
    pool is allocated, which is what fits 5.6 GB of weights and an 8 GB
    pool on one chip."""

    def __init__(self, net, params):
        self.conf, self.layers = net.conf, net.layers
        self._params = params

    @property
    def params(self):
        p, self._params = self._params, None
        return p


class _Registry:
    """The part of ``ModelRegistry`` that ``ModelServer`` uses, over
    servables that are already built."""

    def __init__(self, models: dict):
        self._models = dict(models)

    def get(self, name):
        return self._models.get(name)

    def names(self):
        return sorted(self._models)

    def all_ready(self):
        return all(m.status == "ready" for m in self._models.values())

    def describe(self):
        return {"models": [m.describe() for m in self._models.values()]}

    def shutdown(self, drain=True, timeout=30.0):
        models, self._models = list(self._models.values()), {}
        for m in models:
            m.shutdown(drain=drain, timeout=timeout)


def program_tree(w: dict) -> dict:
    """The benchmark's weights under the names the in-tree stack uses:
    layer 0 the embedding, 1..n the blocks, then the final LayerNorm and
    the output layer. EMPTIES ``w``: the caller's tree must not keep the
    arrays alive once the engine has copied them."""
    tree = {"0": {"W": w.pop("embed")}}
    layers = w.pop("layers")
    n = len(layers)
    for i in range(n):
        lw = layers[i]
        layers[i] = None
        tree[str(i + 1)] = {
            "ln1": lw["ln1"],
            "attn": {k: lw[k] for k in ("Wq", "Wk", "Wv", "Wo")},
            "ln2": lw["ln2"], "W1": lw["W1"], "b1": lw["b1"],
            "W2": lw["W2"], "b2": lw["b2"]}
    tree[str(n + 1)] = w.pop("final_ln")
    tree[str(n + 2)] = w.pop("head")
    return tree


class Serving:
    def __init__(self, served, server):
        self.served, self.server = served, server
        self.url = f"{server.url}/v1/models/{MODEL}/generate"
        self.slots = served.cfg.slots

    def close(self):
        """Stop the listener and the scheduler and free the engine."""
        self.server.drain(timeout=5.0)
        self.served = self.server = None


def serve(cfg: dict, weights: dict) -> Serving:
    from deeplearning4j_tpu.models.transformer import TransformerLM
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving.decode import DecodeConfig, ServedLM
    from deeplearning4j_tpu.serving.server import ModelServer
    assert cfg["model_type"] == "gpt_neox" and cfg["rotary_pct"] == 1.0 \
        and not cfg["use_parallel_residual"] \
        and not cfg["tie_word_embeddings"]
    sv = cfg["serving"]
    zoo = TransformerLM(
        vocab_size=cfg["vocab_size"],
        seq_length=cfg["max_position_embeddings"],
        n_layers=cfg["num_hidden_layers"], n_embd=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        mlp_ratio=cfg["intermediate_size"] // cfg["hidden_size"],
        use_rope=True)
    # not init(): float32 parameters and AdamW state for 2.8 B weights do
    # not fit the chip, and serving needs neither
    net = MultiLayerNetwork(zoo.conf())
    decode = DecodeConfig(
        slots=sv["slots"], page_size=sv["page_size"],
        max_context=sv["max_context"], pool_pages=sv["pool_pages"],
        prefill_buckets=tuple(sv["prefill_buckets"]),
        quantize={"bfloat16": "bf16"}[cfg["serve_dtype"]],
        queue_limit=sv["queue_limit"], prefix_cache=sv["prefix_cache"])
    served = ServedLM(MODEL, _Weights(net, program_tree(weights)),
                      "benchmark-seeded", decode=decode)
    server = ModelServer(_Registry({MODEL: served}), port=0,
                         default_deadline_s=600.0)
    return Serving(served, server)


def decode_time_totals() -> dict:
    """Cumulative seconds of the scheduler loop by category
    (``admission``/``step_compute``/``page_stall``/``idle``)."""
    from deeplearning4j_tpu.monitor import goodput
    return dict(goodput.decode_totals().get(MODEL, {}))
