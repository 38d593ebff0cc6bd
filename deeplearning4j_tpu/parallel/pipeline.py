"""Pipeline parallelism — the layer stack sharded over the mesh.

No DL4J analog (SURVEY.md §2.5 lists pipeline parallelism as ABSENT in the
reference) — this is TPU-native capability beyond the reference, like the
tensor/sequence/expert axes. GPipe-style schedule expressed the XLA way:

- the homogeneous transformer torso (a contiguous run of identical
  `TransformerBlock`s) is stacked into one pytree with a leading layer
  axis and sharded over the mesh "stage" axis — each device holds L/S
  blocks' parameters (the memory win pipeline parallelism exists for);
- the batch splits into M microbatches; each pipeline tick every stage
  runs its blocks (a `lax.scan` over its local sub-stack) and hands its
  activation to the next stage with `lax.ppermute` over "stage";
- after M + S - 1 ticks the last stage holds every microbatch's output;
  a masked psum broadcasts them so the (replicated) head computes the
  loss identically everywhere;
- the BACKWARD pipeline comes from autodiff: the transpose of `ppermute`
  is the reverse ring, so `jax.grad` of the scheduled forward IS the
  reverse-schedule backward — no hand-written backward pass, unlike
  every framework that schedules backward microbatches by hand.

Embedding/head ("pre"/"post") run replicated outside the pipelined torso:
they are a few percent of FLOPs/params in any deep stack. Bubble fraction
is the GPipe (S-1)/(M+S-1); pick n_microbatches >= 2*S to amortize.

Composes with the "data" axis (dp x pp): batch microbatches are
data-sharded like any ParallelWrapper batch.

Restrictions (checked at build): the block run must be contiguous,
identical confs, length divisible by the stage count; block-internal
dropout is not applied on this path (TransformerLM defaults to 0).
"""
from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS, STAGE_AXIS, MeshConfig, build_mesh,
)

log = logging.getLogger("deeplearning4j_tpu")


class PipelineParallelTrainer:
    """dp x pp trainer for TransformerLM-shape MultiLayerNetworks.

    Usage:
        mesh = build_mesh(MeshConfig(data=2, stage=4))
        trainer = PipelineParallelTrainer(net, mesh, n_microbatches=8)
        trainer.fit((X, Y), epochs=1, batch_size=32)
    """

    def __init__(self, model, mesh: Optional[Mesh] = None,
                 n_microbatches: Optional[int] = None):
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        if not isinstance(model, MultiLayerNetwork):
            raise ValueError("pipeline parallelism drives a "
                             "MultiLayerNetwork (TransformerLM shape)")
        if model.params is None:
            model.init()
        if mesh is None:
            mesh = build_mesh(MeshConfig(data=1, stage=len(jax.devices())))
        self.mesh = mesh
        self.stages = mesh.shape[STAGE_AXIS]
        self.data_degree = mesh.shape[DATA_AXIS]
        if self.stages < 2:
            raise ValueError("mesh needs a 'stage' axis of >= 2 for "
                             "pipeline parallelism")
        # locate the homogeneous block torso
        names = [type(l).__name__ for l in model.layers]
        block_idx = [i for i, n in enumerate(names)
                     if n == "TransformerBlock"]
        if not block_idx:
            raise ValueError("no TransformerBlock run to pipeline; "
                             "pipeline parallelism needs a homogeneous "
                             "block stack (TransformerLM shape)")
        if block_idx != list(range(block_idx[0], block_idx[-1] + 1)):
            raise ValueError("TransformerBlock run must be contiguous")
        confs = {model.layers[i] for i in block_idx}
        if len(confs) != 1:
            raise ValueError("pipelined blocks must share one identical "
                             f"conf; found {len(confs)} distinct")
        if len(block_idx) % self.stages:
            raise ValueError(
                f"{len(block_idx)} blocks not divisible by "
                f"{self.stages} stages")
        self.block_idx = block_idx
        self.block_conf = model.layers[block_idx[0]]
        self.pre_idx = list(range(0, block_idx[0]))
        self.post_idx = list(range(block_idx[-1] + 1, len(model.layers)))
        if not self.post_idx or \
                not hasattr(model.layers[self.post_idx[-1]], "score"):
            raise ValueError("last layer must be an output layer")
        if model._compute_dtype != model._param_dtype:
            raise ValueError(
                "pipeline path runs layers on uncast parameters; "
                "compute_dtype must equal the param dtype here (mixed "
                "precision pp is not implemented)")
        # layer state updates are discarded by the pipelined step — reject
        # stateful layers (e.g. BatchNorm running stats) rather than let
        # their statistics silently stay at init values
        for i, layer in enumerate(model.layers):
            if model.state.get(str(i)):
                raise ValueError(
                    f"layer {i} ({type(layer).__name__}) carries state; "
                    "the pp step does not thread state updates — use "
                    "stateless stacks (LN-based transformers)")
        # dropout inside the pipelined torso is not implemented (blocks
        # run with rng=None) — reject rather than silently train without
        dcfg = self.block_conf
        if getattr(dcfg, "attention_dropout", 0.0) or \
                getattr(dcfg, "residual_dropout", 0.0) or \
                getattr(dcfg, "dropout", 0.0):
            raise ValueError("pipelined TransformerBlocks must have "
                             "dropout 0 (the pp path applies no dropout)")
        for i in self.pre_idx + self.post_idx:
            if getattr(model.layers[i], "dropout", 0.0):
                raise ValueError("pre/post layers must have dropout 0 on "
                                 "the pipeline path")
        self.model = model
        self.n_microbatches = n_microbatches or 2 * self.stages
        self._step = None

    # ---------------------------------------------------------------- build
    def _build_step(self):
        net = self.model
        tx = net._tx
        mesh = self.mesh
        S = self.stages
        M = self.n_microbatches
        block = self.block_conf
        pre_layers = [net.layers[i] for i in self.pre_idx]
        post_layers = [net.layers[i] for i in self.post_idx]
        head = post_layers[-1]
        blocks_per_stage = len(self.block_idx) // S

        def make_torso(with_mask):
            def torso(stacked, hm, fm):
                """shard_map body: stacked (L/S, ...) per device, hm
                (M, mb, T, D) + fm (M, mb, T) data-sharded. Returns the
                last stage's outputs, broadcast."""
                s = jax.lax.axis_index(STAGE_AXIS)

                def run_stage(h, m):
                    def body(carry, p_block):
                        y, _ = block.apply(p_block, {}, carry, train=True,
                                           rng=None, mask=m)
                        return y, None
                    out, _ = jax.lax.scan(body, h, stacked)
                    return out

                zeros = jnp.zeros_like(hm[0])
                state = zeros
                outs = jnp.zeros_like(hm)
                perm = [(i, (i + 1) % S) for i in range(S)]
                # every stage processes microbatch t-s at tick t, so the
                # mask must travel WITH the activation: rotate it too.
                # Bubble ticks carry an all-ONES mask: their outputs are
                # discarded, but an all-zero mask would NaN the softmax
                # and 0 * NaN in the VJP would poison real gradients.
                mstate = None if fm is None else jnp.ones_like(fm[0])
                for t in range(M + S - 1):
                    feed = hm[t] if t < M else zeros
                    inp = jnp.where(s == 0, feed, state)
                    if fm is None:
                        m = None
                    else:
                        mfeed = fm[t] if t < M else jnp.ones_like(fm[0])
                        m = jnp.where(s == 0, mfeed, mstate)
                    out = run_stage(inp, m)
                    k = t - (S - 1)
                    if 0 <= k < M:
                        outs = outs.at[k].set(out)
                    state = jax.lax.ppermute(out, STAGE_AXIS, perm)
                    if fm is not None:
                        mstate = jax.lax.ppermute(m, STAGE_AXIS, perm)
                # only the last stage's buffer is meaningful; broadcast it
                # so the replicated head sees identical activations
                return jax.lax.psum(
                    jnp.where(s == S - 1, outs, jnp.zeros_like(outs)),
                    STAGE_AXIS)

            if with_mask:
                return jax.shard_map(
                    torso, mesh=mesh,
                    in_specs=(P(STAGE_AXIS), P(None, DATA_AXIS),
                              P(None, DATA_AXIS)),
                    out_specs=P(None, DATA_AXIS), check_vma=False)
            inner = jax.shard_map(
                lambda stacked, hm: torso(stacked, hm, None), mesh=mesh,
                in_specs=(P(STAGE_AXIS), P(None, DATA_AXIS)),
                out_specs=P(None, DATA_AXIS), check_vma=False)
            return lambda stacked, hm, fm: inner(stacked, hm)

        from deeplearning4j_tpu.nn.regularization import (
            apply_constraints, constraint_map, has_constraints,
        )
        layer_map = constraint_map(net)
        constrained = has_constraints(net.layers)

        def loss_fn(params, state_nn, x, y, fmask, lmask, rng):
            # --- pre (replicated): embedding etc.
            h = x
            for i, layer in zip(self.pre_idx, pre_layers):
                h, _ = layer.apply(params[str(i)], state_nn.get(str(i), {}),
                                   h, train=True, rng=None, mask=fmask)
            B, T, D = h.shape
            if B % M:
                raise ValueError(f"batch {B} not divisible by "
                                 f"{M} microbatches")
            hm = h.reshape(M, B // M, T, D)
            fm = None if fmask is None else fmask.reshape(M, B // M, T)
            # --- torso (pipelined): stack block params along a layer axis
            stacked = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves),
                *[params[str(i)] for i in self.block_idx])
            outs = make_torso(fmask is not None)(stacked, hm, fm)
            h = outs.reshape(B, T, D)
            # --- post (replicated): trailing norm + head score; the loss
            # mask follows MultiLayerNetwork._score_fn (lmask, else fmask)
            for i, layer in zip(self.post_idx[:-1], post_layers[:-1]):
                h, _ = layer.apply(params[str(i)], state_nn.get(str(i), {}),
                                   h, train=True, rng=None, mask=fmask)
            out_mask = lmask if lmask is not None else fmask
            loss = head.score(params[str(self.post_idx[-1])], h, y,
                              train=True, rng=None, mask=out_mask)
            reg = jnp.asarray(0.0, jnp.float32)
            for i, layer in enumerate(net.layers):
                reg = reg + layer.regularization_score(params[str(i)])
            return loss.astype(jnp.float32) + reg

        def step(params, opt_state, state_nn, x, y, fmask, lmask, rng):
            loss, grads = jax.value_and_grad(loss_fn)(
                params, state_nn, x, y, fmask, lmask, rng)
            updates, new_opt = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            if constrained:    # same post-update projection as net.fit
                new_params = apply_constraints(layer_map, new_params)
            return new_params, new_opt, loss

        return jax.jit(step, donate_argnums=(0, 1))

    # ------------------------------------------------------------------ fit
    def _check_batch(self, b):
        mb = b // self.n_microbatches
        if b % self.n_microbatches or mb % self.data_degree:
            raise ValueError(
                f"batch {b} must split into {self.n_microbatches} "
                f"microbatches whose size is divisible by the data "
                f"degree {self.data_degree} (got microbatch {mb})")

    def fit(self, data, epochs: int = 1, batch_size: int = 32):
        net = self.model
        # donated-buffer safety (util/params.owned_leaf): the pipeline
        # step donates params/opt_state — host-sourced leaves (restored
        # checkpoints, imports, user numpy) must be XLA-owned before the
        # first donation, or XLA frees memory it does not own (the PR-3
        # serde-resume segfault class)
        from deeplearning4j_tpu.util import params as param_util
        net.params = param_util.own_tree(net.params)
        net.opt_state = param_util.own_tree(net.opt_state)
        source = net._as_iterator(data, batch_size)
        rng = jax.random.PRNGKey(net.conf.seed + 777)
        if self._step is None:
            self._step = {}
        for _ in range(epochs):
            for lst in net.listeners:
                lst.on_epoch_start(net, net.epoch_count)
            for ds in source:
                rng, sub = jax.random.split(rng)
                self._check_batch(int(np.shape(ds.features)[0]))
                fm = None if ds.features_mask is None else \
                    jnp.asarray(np.asarray(ds.features_mask))
                lm = None if ds.labels_mask is None else \
                    jnp.asarray(np.asarray(ds.labels_mask))
                sig = (fm is not None, lm is not None)
                if sig not in self._step:
                    self._step[sig] = self._build_step()
                net.params, net.opt_state, loss = self._step[sig](
                    net.params, net.opt_state, net.state,
                    jnp.asarray(np.asarray(ds.features), net._compute_dtype),
                    jnp.asarray(np.asarray(ds.labels), net._compute_dtype),
                    fm, lm, sub)
                # graftlint: disable=host-sync-in-hot-path -- the step's ONE budgeted loss fetch (the deliberate per-iteration sync; PERF.md)
                net._score = float(loss)
                for lst in net.listeners:
                    lst.iteration_done(net, net.iteration_count,
                                       net.epoch_count, net._score, 0.0,
                                       int(np.shape(ds.features)[0]))
                net.iteration_count += 1
            for lst in net.listeners:
                lst.on_epoch_end(net, net.epoch_count)
            net.epoch_count += 1
            source.reset()
        net._steps = {}
        net._output_fn = None
        return net
