"""Memory reports: analytic per-layer estimates + exact compiled HBM truth.

Parity target: DL4J `nn/conf/memory/LayerMemoryReport.java:22` and
`NetworkMemoryReport.java` — analytic fixed/variable memory estimation per
layer. The TPU build EXCEEDS the reference here: alongside the analytic
estimate it reports the exact numbers XLA's compiler assigns to the jitted
training step (`jit(...).lower(...).compile().memory_analysis()`), which is
ground truth for HBM on device — something the JVM reference cannot see.

Analytic model (per layer):
    params          = bytes of the layer's parameter leaves
    updater_state   = bytes of the optimizer-state leaves tied to the layer
    activations     = batch x output_type.flat_size x dtype (forward)
    working (train) = 2x activations (forward + gradient wrt activations,
                      the dominant autodiff residency; XLA fuses the rest)
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import numpy as np


def _tree_bytes(tree) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if not hasattr(leaf, "shape"):
            leaf = np.asarray(leaf)
        total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


@dataclasses.dataclass
class LayerMemoryReport:
    """One layer/vertex row (DL4J LayerMemoryReport analog)."""
    name: str
    layer_type: str
    params_bytes: int
    updater_state_bytes: int
    activation_bytes: int          # inference-time output residency
    working_bytes: int             # training-time (fwd + bwd residual)

    @property
    def total_train_bytes(self) -> int:
        return (self.params_bytes + self.updater_state_bytes +
                self.working_bytes)

    @property
    def total_inference_bytes(self) -> int:
        return self.params_bytes + self.activation_bytes


@dataclasses.dataclass
class NetworkMemoryReport:
    """Whole-network aggregation (DL4J NetworkMemoryReport analog) plus the
    XLA compiled-step truth when available."""
    layers: List[LayerMemoryReport]
    batch_size: int
    input_bytes: int
    compiled: Optional[Dict[str, int]] = None   # exact, from XLA

    @property
    def total_params_bytes(self) -> int:
        return sum(r.params_bytes for r in self.layers)

    @property
    def total_updater_bytes(self) -> int:
        return sum(r.updater_state_bytes for r in self.layers)

    @property
    def total_activation_bytes(self) -> int:
        return sum(r.activation_bytes for r in self.layers)

    @property
    def total_train_bytes(self) -> int:
        """Analytic peak-residency estimate for one training step."""
        return (self.input_bytes + self.total_params_bytes +
                self.total_updater_bytes +
                sum(r.working_bytes for r in self.layers) +
                # gradient buffer the updater consumes (params-sized)
                self.total_params_bytes)

    @property
    def total_inference_bytes(self) -> int:
        return (self.input_bytes + self.total_params_bytes +
                max((r.activation_bytes for r in self.layers), default=0))

    @property
    def compiled_total_bytes(self) -> Optional[int]:
        # one peak-residency formula across memory_report, the program
        # ledger, and bench rows
        from deeplearning4j_tpu.monitor.xla import hbm_peak
        return hbm_peak(self.compiled)

    def summary(self) -> str:
        lines = [f"{'layer':<24}{'type':<22}{'params':>12}{'updater':>12}"
                 f"{'acts':>12}{'train':>12}"]
        for r in self.layers:
            lines.append(f"{r.name:<24}{r.layer_type:<22}"
                         f"{r.params_bytes:>12,}{r.updater_state_bytes:>12,}"
                         f"{r.activation_bytes:>12,}"
                         f"{r.total_train_bytes:>12,}")
        lines.append(f"analytic train total (batch={self.batch_size}): "
                     f"{self.total_train_bytes:,} bytes")
        if self.compiled:
            lines.append(f"XLA compiled step: {self.compiled} "
                         f"(total {self.compiled_total_bytes:,} bytes)")
        return "\n".join(lines)


def _scratch_bytes(layer, in_t, out_t, batch_size, dtype_size) -> int:
    """Layer-specific working scratch beyond activations: convolution
    lowering materializes im2col-style column buffers of
    batch x out_h x out_w x kernel_area x c_in (forward and again for the
    backward pass) — the same term DL4J's ConvolutionLayer memory report
    models as its working memory."""
    kernel = getattr(layer, "kernel", None)
    if kernel is None or len(getattr(out_t, "shape", ())) != 3 \
            or "onvolution" not in type(layer).__name__:
        return 0     # pooling lowers to reduce_window — no col buffer
    out_h, out_w = out_t.shape[0], out_t.shape[1]
    c_in = in_t.shape[2] if len(in_t.shape) == 3 else in_t.features
    col = batch_size * out_h * out_w * kernel[0] * kernel[1] * c_in
    return 2 * col * dtype_size          # forward + backward col buffers


def _split_opt_state_bytes(opt_state, params) -> Dict[str, int]:
    """Bytes of optimizer state attributable to each top-level param key.

    optax state mirrors the params pytree inside each transform's leaves;
    matching on the top-level key structure is enough for per-layer
    attribution (anything unmatchable lands under '__other__')."""
    per_key = {k: 0 for k in params}
    other = 0

    def walk(node):
        nonlocal other
        if isinstance(node, dict) and set(node.keys()) == set(params.keys()):
            for k in node:
                per_key[k] += _tree_bytes(node[k])
            return
        if isinstance(node, (tuple, list)):
            for c in node:
                walk(c)
            return
        if hasattr(node, "_fields"):            # NamedTuple state
            for c in node:
                walk(c)
            return
        if isinstance(node, dict):
            for c in node.values():
                walk(c)
            return
        other += _tree_bytes(node)

    walk(opt_state)
    per_key["__other__"] = other
    return per_key


def build_memory_report(net, batch_size: int,
                        with_compiled: bool = True) -> NetworkMemoryReport:
    """Analytic + compiled memory report for a MultiLayerNetwork or
    ComputationGraph (exposed as net.memory_report(batch_size))."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    if net.params is None:
        raise RuntimeError("init() the network before memory_report()")
    is_graph = isinstance(net, ComputationGraph)
    dtype_size = np.dtype(net._compute_dtype).itemsize
    opt_split = _split_opt_state_bytes(net.opt_state, net.params)

    rows = []
    if is_graph:
        types = net._vertex_types or net._resolve_types()
        net._vertex_types = types
        input_bytes = sum(batch_size * t.flat_size * dtype_size
                          for t in net.conf.input_types)
        for name in net._topo:
            vd = net.conf.vertices[name]
            out_t = types[name]
            in_t = types[vd.inputs[0]]
            act = batch_size * out_t.flat_size * dtype_size
            # a vertex that reads another's parameters (`params_of`)
            # holds none: a shared leaf is counted once, at its owner
            p_bytes = _tree_bytes(net.params.get(name, {}))
            scratch = _scratch_bytes(vd.vertex, in_t, out_t, batch_size,
                                     dtype_size)
            rows.append(LayerMemoryReport(
                name=name, layer_type=type(vd.vertex).__name__,
                params_bytes=p_bytes,
                updater_state_bytes=opt_split.get(name, 0),
                activation_bytes=act, working_bytes=2 * act + scratch))
    else:
        types = net._resolve_types()     # per-layer INPUT types
        input_bytes = batch_size * net.conf.input_type.flat_size * dtype_size
        for i, layer in enumerate(net.layers):
            out_t = layer.output_type(types[i])
            act = batch_size * out_t.flat_size * dtype_size
            key = str(i)
            scratch = _scratch_bytes(layer, types[i], out_t, batch_size,
                                     dtype_size)
            rows.append(LayerMemoryReport(
                name=key, layer_type=type(layer).__name__,
                params_bytes=_tree_bytes(net.params.get(key, {})),
                updater_state_bytes=opt_split.get(key, 0),
                activation_bytes=act, working_bytes=2 * act + scratch))

    compiled = None
    if with_compiled:
        compiled = _compiled_step_memory(net, batch_size, is_graph)
    return NetworkMemoryReport(layers=rows, batch_size=batch_size,
                               input_bytes=input_bytes, compiled=compiled)


def _read_memory_analysis(compiled):
    """Capability-probe seam: the one call that can legitimately fail on a
    backend without memory_analysis support (tests monkeypatch this to
    simulate such a backend)."""
    return compiled.memory_analysis()


def _count_unavailable():
    """The degraded path is counted, not silent: visible on /metrics as
    xla_analysis_unavailable_total{kind="memory"}."""
    from deeplearning4j_tpu.monitor import xla as xla_ledger
    xla_ledger.analysis_unavailable("memory")


def _compiled_step_memory(net, batch_size, is_graph) -> Optional[Dict[str, int]]:
    """Lower + compile one training step and read XLA's memory analysis.

    Lowering errors propagate (a signature/shape bug here must be loud,
    not reported as a backend limitation); only the memory_analysis
    capability probe itself degrades to None."""
    import logging

    import jax.numpy as jnp
    if is_graph:
        x = tuple(jnp.zeros((batch_size,) + t.shape, net._compute_dtype)
                  for t in net.conf.input_types)
        y = []
        for o in net.conf.network_outputs:
            t = (net._vertex_types or net._resolve_types())[o]
            y.append(jnp.zeros((batch_size,) + t.shape,
                               net._compute_dtype))
        y = tuple(y)
    else:
        types = net._resolve_types()
        out_t = net.layers[-1].output_type(types[-1])
        x = jnp.zeros((batch_size,) + net.conf.input_type.shape,
                      net._compute_dtype)
        y = jnp.zeros((batch_size,) + out_t.shape, net._compute_dtype)
    from deeplearning4j_tpu.nn.fit_loop import compiled_step
    lowered = compiled_step(net, "step").lower(
        net.params, net.opt_state, net.state, x, y, None, None,
        jax.random.PRNGKey(0), None)
    try:
        ma = _read_memory_analysis(lowered.compile())
    except Exception as e:      # backend without memory_analysis support
        _count_unavailable()
        logging.getLogger("deeplearning4j_tpu").warning(
            "compiled memory analysis unavailable on this backend: %r", e)
        return None
    if ma is None:
        _count_unavailable()
        return None
    # shared attr parsing with the program ledger (one spelling to drift)
    from deeplearning4j_tpu.monitor.xla import hbm_stats
    return hbm_stats(ma)
