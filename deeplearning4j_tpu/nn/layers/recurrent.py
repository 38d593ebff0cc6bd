"""Recurrent layer family — lax.scan over time, stateful streaming inference.

Parity targets (semantics, not code):
- LSTM / GravesLSTM <- DL4J nn/conf/layers/{LSTM,GravesLSTM}.java; shared math
  nn/layers/recurrent/LSTMHelpers.java (gemm at :206-212,522; cuDNN helper
  CudnnLSTMHelper.java). GravesLSTM adds peephole connections
  (Graves 2013 variant). Here forward is ONE fused gemm per step inside
  lax.scan — the input projection for all timesteps is hoisted out of the
  scan as a single (B*T, in)x(in, 4H) MXU matmul.
- GravesBidirectionalLSTM, Bidirectional wrapper <- nn/conf/layers/...
- SimpleRnn <- nn/conf/layers/SimpleRnn.java
- RnnOutputLayer / RnnLossLayer <- time-distributed loss heads
- LastTimeStep, MaskZeroLayer <- nn/conf/layers/{recurrent,util} wrappers
- rnn_step: single-step stateful inference (MultiLayerNetwork.rnnTimeStep,
  MultiLayerNetwork.java:2806)

Masking follows DL4J semantics (LSTMHelpers.java:355-357): a (B, T) 0/1 mask;
masked steps output zeros and zero the cell/hidden state.

Activations: (batch, time, features) — DL4J is (batch, features, time); the
TPU-native layout keeps features in lanes (last dim = 128-lane axis).

Gate order convention: [i, f, g, o] (input, forget, cell-candidate, output).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.conf.base import InputType, Kind, LayerConf, register_layer
from deeplearning4j_tpu.nn.initializers import get_initializer
from deeplearning4j_tpu.nn.losses import get_loss


def _lstm_scan(xw, h0, c0, R, b, gate_act, cell_act, peep=None, mask=None):
    """Scan an LSTM over time.

    xw: (B, T, 4H) precomputed input projections (input gemm hoisted out of
        the scan — one big MXU matmul instead of T small ones).
    R: (H, 4H) recurrent weights. b: (4H,). peep: optional dict with pi,pf,po
    (H,) peephole weights (GravesLSTM). mask: optional (B, T).
    Returns (hs: (B,T,H), (hT, cT)).
    """
    H = R.shape[0]
    ga = get_activation(gate_act)
    ca = get_activation(cell_act)

    def step(carry, inp):
        h_prev, c_prev = carry
        if mask is not None:
            x_t, m_t = inp
        else:
            x_t = inp
        z = x_t + h_prev @ R + b
        zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
        if peep is not None:
            zi = zi + c_prev * peep["pi"]
            zf = zf + c_prev * peep["pf"]
        i = ga(zi)
        f = ga(zf)
        g = ca(zg)
        c = f * c_prev + i * g
        if peep is not None:
            zo = zo + c * peep["po"]
        o = ga(zo)
        h = o * ca(c)
        if mask is not None:
            m = m_t[:, None]
            h = jnp.where(m > 0, h, 0.0)
            c = jnp.where(m > 0, c, 0.0)
        return (h, c), h

    xs = jnp.swapaxes(xw, 0, 1)                     # (T, B, 4H)
    if mask is not None:
        ms = jnp.swapaxes(mask, 0, 1)               # (T, B)
        (hT, cT), hs = lax.scan(step, (h0, c0), (xs, ms))
    else:
        (hT, cT), hs = lax.scan(step, (h0, c0), xs)
    return jnp.swapaxes(hs, 0, 1), (hT, cT)


@register_layer
@dataclasses.dataclass(frozen=True)
class LSTM(LayerConf):
    """Standard LSTM (no peepholes), DL4J nn/conf/layers/LSTM.java."""
    n_out: int = 0
    n_in: Optional[int] = None
    activation: str = "tanh"            # cell/candidate activation
    gate_activation: str = "sigmoid"    # DL4J gateActivationFunction
    weight_init: str = "xavier"
    forget_gate_bias_init: float = 1.0  # DL4J forgetGateBiasInit

    peephole: bool = False

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.shape[0]
        return InputType(Kind.RNN, (t, self.n_out))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        n_in = self.n_in or input_type.features
        H = self.n_out
        k1, k2, k3 = jax.random.split(key, 3)
        w_init = get_initializer(self.weight_init)
        b = jnp.zeros((4 * H,), dtype)
        # forget-gate bias init (gate order i,f,g,o -> second block)
        b = b.at[H:2 * H].set(self.forget_gate_bias_init)
        params = {
            "W": w_init(k1, (n_in, 4 * H), n_in, 4 * H, dtype),
            "R": w_init(k2, (H, 4 * H), H, 4 * H, dtype),
            "b": b,
        }
        if self.peephole:
            params["pi"] = jnp.zeros((H,), dtype)
            params["pf"] = jnp.zeros((H,), dtype)
            params["po"] = jnp.zeros((H,), dtype)
        return params, {}

    def _peep(self, params):
        if not self.peephole:
            return None
        return {"pi": params["pi"], "pf": params["pf"], "po": params["po"]}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        hs, _ = self.apply_seq(params, x, None, train=train, rng=rng, mask=mask)
        return hs, state

    def rnn_step(self, params, x_t, carry):
        """Single-step stateful inference (rnnTimeStep). x_t: (B, n_in);
        carry: (h, c) or None."""
        B = x_t.shape[0]
        H = self.n_out
        if carry is None:
            carry = (jnp.zeros((B, H), x_t.dtype), jnp.zeros((B, H), x_t.dtype))
        xw = (x_t @ params["W"])[:, None, :]
        hs, new_carry = _lstm_scan(xw, carry[0], carry[1], params["R"],
                                   params["b"], self.gate_activation,
                                   self.activation, peep=self._peep(params))
        return hs[:, 0, :], new_carry

    def apply_seq(self, params, x, carry, *, train=False, rng=None, mask=None):
        """Sequence forward with explicit initial state — the primitive behind
        truncated BPTT (doTruncatedBPTT, MultiLayerNetwork.java:1315-1317) and
        rnnTimeStep. Returns (y, final_carry)."""
        x = self.maybe_dropout_input(x, train, rng)
        B = x.shape[0]
        H = self.n_out
        if carry is None:
            carry = (jnp.zeros((B, H), x.dtype), jnp.zeros((B, H), x.dtype))
        xw = x @ params["W"]
        hs, new_carry = _lstm_scan(xw, carry[0], carry[1], params["R"],
                                   params["b"], self.gate_activation,
                                   self.activation, peep=self._peep(params),
                                   mask=mask)
        return hs, new_carry


@register_layer
@dataclasses.dataclass(frozen=True)
class GravesLSTM(LSTM):
    """LSTM with peephole connections (Graves 2013), DL4J GravesLSTM.java."""
    peephole: bool = True


@register_layer
@dataclasses.dataclass(frozen=True)
class SimpleRnn(LayerConf):
    """Vanilla RNN: h_t = act(x_t W + h_{t-1} R + b). DL4J SimpleRnn.java."""
    n_out: int = 0
    n_in: Optional[int] = None
    activation: str = "tanh"
    weight_init: str = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.shape[0]
        return InputType(Kind.RNN, (t, self.n_out))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        n_in = self.n_in or input_type.features
        H = self.n_out
        k1, k2 = jax.random.split(key)
        w_init = get_initializer(self.weight_init)
        return {
            "W": w_init(k1, (n_in, H), n_in, H, dtype),
            "R": w_init(k2, (H, H), H, H, dtype),
            "b": jnp.zeros((H,), dtype),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        hs, _ = self.apply_seq(params, x, None, train=train, rng=rng, mask=mask)
        return hs, state

    def rnn_step(self, params, x_t, carry):
        B = x_t.shape[0]
        H = self.n_out
        act = get_activation(self.activation)
        h_prev = carry if carry is not None else jnp.zeros((B, H), x_t.dtype)
        h = act(x_t @ params["W"] + params["b"] + h_prev @ params["R"])
        return h, h

    def apply_seq(self, params, x, carry, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        B, T, _ = x.shape
        H = self.n_out
        act = get_activation(self.activation)
        xw = x @ params["W"] + params["b"]
        h0 = carry if carry is not None else jnp.zeros((B, H), x.dtype)

        def step(h_prev, inp):
            if mask is not None:
                x_t, m_t = inp
            else:
                x_t = inp
            h = act(x_t + h_prev @ params["R"])
            if mask is not None:
                m = m_t[:, None]
                h = jnp.where(m > 0, h, 0.0)
            return h, h

        xs = jnp.swapaxes(xw, 0, 1)
        if mask is not None:
            ms = jnp.swapaxes(mask, 0, 1)
            hT, hs = lax.scan(step, h0, (xs, ms))
        else:
            hT, hs = lax.scan(step, h0, xs)
        return jnp.swapaxes(hs, 0, 1), hT


@register_layer
@dataclasses.dataclass(frozen=True)
class GRU(LayerConf):
    """Gated recurrent unit. The reference has no GRU (DL4J of this vintage
    ships LSTM/GravesLSTM/SimpleRnn only); this exists for Keras-import
    coverage and stands alone as a layer. Gate order z (update), r (reset),
    candidate h — Keras weight-layout compatible, including the
    `reset_after` variant with its separate recurrent bias."""
    n_out: int = 0
    n_in: Optional[int] = None
    activation: str = "tanh"
    gate_activation: str = "sigmoid"
    weight_init: str = "xavier"
    reset_after: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.shape[0]
        return InputType(Kind.RNN, (t, self.n_out))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        n_in = self.n_in or input_type.features
        H = self.n_out
        k1, k2 = jax.random.split(key)
        w_init = get_initializer(self.weight_init)
        b_shape = (2, 3 * H) if self.reset_after else (3 * H,)
        return {
            "W": w_init(k1, (n_in, 3 * H), n_in, 3 * H, dtype),
            "R": w_init(k2, (H, 3 * H), H, 3 * H, dtype),
            "b": jnp.zeros(b_shape, dtype),
        }, {}

    def _cell(self, params, xw_t, h_prev):
        """One step given precomputed input projections xw_t (B, 3H)."""
        H = self.n_out
        ga = get_activation(self.gate_activation)
        ca = get_activation(self.activation)
        xz, xr, xh = jnp.split(xw_t, 3, axis=-1)
        if self.reset_after:
            hw = h_prev @ params["R"] + params["b"][1]
            hz, hr, hh = jnp.split(hw, 3, axis=-1)
            z = ga(xz + hz)
            r = ga(xr + hr)
            cand = ca(xh + r * hh)
        else:
            # candidate uses (r*h) @ R_h, so only the z|r blocks of R are
            # needed against h_prev — skip the wasted third-gemm columns
            hw = h_prev @ params["R"][:, :2 * H]
            hz, hr = jnp.split(hw, 2, axis=-1)
            z = ga(xz + hz)
            r = ga(xr + hr)
            cand = ca(xh + (r * h_prev) @ params["R"][:, 2 * H:])
        return z * h_prev + (1.0 - z) * cand

    def _input_proj(self, params, x):
        ib = params["b"][0] if self.reset_after else params["b"]
        return x @ params["W"] + ib

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        hs, _ = self.apply_seq(params, x, None, train=train, rng=rng,
                               mask=mask)
        return hs, state

    def rnn_step(self, params, x_t, carry):
        B = x_t.shape[0]
        h_prev = carry if carry is not None \
            else jnp.zeros((B, self.n_out), x_t.dtype)
        h = self._cell(params, self._input_proj(params, x_t[:, None])[:, 0],
                       h_prev)
        return h, h

    def apply_seq(self, params, x, carry, *, train=False, rng=None,
                  mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        B, T, _ = x.shape
        xw = self._input_proj(params, x)      # hoisted input gemm
        h0 = carry if carry is not None \
            else jnp.zeros((B, self.n_out), x.dtype)

        def step(h_prev, inp):
            if mask is not None:
                xw_t, m_t = inp
            else:
                xw_t = inp
            h = self._cell(params, xw_t, h_prev)
            if mask is not None:
                h = jnp.where(m_t[:, None] > 0, h, 0.0)
            return h, h

        xs = jnp.swapaxes(xw, 0, 1)
        if mask is not None:
            ms = jnp.swapaxes(mask, 0, 1)
            hT, hs = lax.scan(step, h0, (xs, ms))
        else:
            hT, hs = lax.scan(step, h0, xs)
        return jnp.swapaxes(hs, 0, 1), hT

@register_layer
@dataclasses.dataclass(frozen=True)
class Bidirectional(LayerConf):
    """Bidirectional wrapper (DL4J nn/conf/layers/recurrent/Bidirectional.java).
    Runs the wrapped RNN forward and on the time-reversed sequence, then
    combines per `mode`: concat | add | mul | ave."""
    layer: Optional[LayerConf] = None
    mode: str = "concat"

    def output_type(self, input_type: InputType) -> InputType:
        inner = self.layer.output_type(input_type)
        if self.mode == "concat":
            if inner.kind == Kind.FF:   # e.g. Bidirectional(LastTimeStep(..))
                return InputType.feed_forward(2 * inner.shape[0])
            t, f = inner.shape
            return InputType(Kind.RNN, (t, 2 * f))
        return inner

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        fwd, _ = self.layer.init(k1, input_type, dtype)
        bwd, _ = self.layer.init(k2, input_type, dtype)
        return {"fwd": fwd, "bwd": bwd}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        r1 = r2 = None
        if rng is not None:
            r1, r2 = jax.random.split(rng)
        yf, _ = self.layer.apply(params["fwd"], {}, x, train=train, rng=r1, mask=mask)
        xr = jnp.flip(x, axis=1)
        mr = jnp.flip(mask, axis=1) if mask is not None else None
        yb, _ = self.layer.apply(params["bwd"], {}, xr, train=train, rng=r2, mask=mr)
        if yb.ndim == 3:    # rank-2 when the inner layer is LastTimeStep
            yb = jnp.flip(yb, axis=1)
        if self.mode == "concat":
            y = jnp.concatenate([yf, yb], axis=-1)
        elif self.mode == "add":
            y = yf + yb
        elif self.mode == "mul":
            y = yf * yb
        elif self.mode == "ave":
            y = 0.5 * (yf + yb)
        else:
            raise ValueError(f"Unknown Bidirectional mode {self.mode}")
        return y, state


@register_layer
@dataclasses.dataclass(frozen=True)
class GravesBidirectionalLSTM(Bidirectional):
    """DL4J GravesBidirectionalLSTM = Bidirectional(concat, GravesLSTM)."""
    n_out: int = 0
    n_in: Optional[int] = None

    def __post_init__(self):
        if self.layer is None:
            object.__setattr__(self, "layer",
                               GravesLSTM(n_out=self.n_out, n_in=self.n_in))


#: the float32 logits of an LM head and their gradient may take this much;
#: more positions are scored in blocks (`RnnOutputLayer.score`)
_LOSS_LIVE_BYTES = 512 << 20


@register_layer
@dataclasses.dataclass(frozen=True)
class RnnOutputLayer(LayerConf):
    """Time-distributed dense + loss (DL4J RnnOutputLayer): applies the same
    (F_in -> n_out) projection at every step; loss averaged over unmasked steps.

    With ``loss="sparse_mcxent"`` and a softmax head whose float32
    logits and their gradient (positions x vocabulary x 4 bytes, twice)
    would pass `_LOSS_LIVE_BYTES`, the score is taken over blocks of
    positions, the largest power of two that stays under it: each block's
    logits are made in float32, reduced to its summed loss and made again
    in the backward pass, so the logits of all positions never exist at
    once (an LM head over a long sequence). Same number as the whole
    score up to float32 summation order.

    ``tied_embedding`` keeps ``W`` as an embedding keeps its table,
    ``(n_out, n_in)``, and projects by its transpose: the head of a model
    that ties the two, a graph vertex reading the embedding's leaf
    (``add_layer(..., params_of="embed")``).

    ``weighted`` reads the label mask as per-position loss WEIGHTS (any
    float32, 0 where a position has no target) and normalises by the
    COUNT of positions, not by the mask's sum: ``score = sum_i w_i loss_i
    / positions``, whole and blocked alike (a denoising loss weighted by
    ``1 / t``: `data.denoise.BlockDiffusionPreProcessor` makes the
    weights). Without a label mask every weight is 1."""
    n_out: int = 0
    n_in: Optional[int] = None
    activation: str = "softmax"
    loss: str = "mcxent"
    weight_init: str = "xavier"
    has_bias: bool = True
    tied_embedding: bool = False
    weighted: bool = False

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.shape[0]
        return InputType(Kind.RNN, (t, self.n_out))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        n_in = self.n_in or input_type.features
        w_init = get_initializer(self.weight_init)
        shape = (self.n_out, n_in) if self.tied_embedding \
            else (n_in, self.n_out)
        params = {"W": w_init(key, shape, n_in, self.n_out, dtype)}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), dtype)
        return params, {}

    def preout(self, params, x, train=False, rng=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = x @ (params["W"].T if self.tied_embedding else params["W"])
        if self.has_bias:
            y = y + params["b"]
        return y

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("dense"):
            return get_activation(self.activation)(self.preout(params, x, train, rng)), state

    def score(self, params, x, labels, *, train=False, rng=None, mask=None):
        if self.loss == "sparse_mcxent" and self.activation == "softmax":
            # positions a block: the largest power of two whose logits
            # and their gradient stay under the budget
            acc = jnp.promote_types(jnp.float32, x.dtype).itemsize
            most = max(_LOSS_LIVE_BYTES // (2 * acc * self.n_out), 1)
            if most < x.size // x.shape[-1]:
                return self._blocked_score(
                    params, x, labels, train, rng, mask,
                    1 << (most.bit_length() - 1))
        with jax.named_scope("head/loss"):
            z = self.preout(params, x, train, rng)
            score = get_loss(self.loss)(labels, z, self.activation,
                                        mask=mask)
            if self.weighted and mask is not None:
                # the loss divided by the weights' sum (at least 1): undo
                # that, divide by the positions
                total = jnp.maximum(jnp.sum(mask.astype(score.dtype)), 1.0)
                score = score * total / (z.size // z.shape[-1])
            return score

    def _blocked_score(self, params, x, labels, train, rng, mask, blk):
        with jax.named_scope("head/loss"):
            x = self.maybe_dropout_input(x, train, rng)
            f = x.shape[-1]
            x = x.reshape(-1, f)
            n = x.shape[0]
            labels = labels.reshape(n).astype(jnp.int32)
            keep = jnp.ones((n,), jnp.float32) if mask is None \
                else mask.reshape(n).astype(jnp.float32)
            pad = (-n) % blk
            if pad:
                x = jnp.pad(x, ((0, pad), (0, 0)))
                labels, keep = jnp.pad(labels, (0, pad)), jnp.pad(keep,
                                                                  (0, pad))
            acc_t = jnp.promote_types(jnp.float32, x.dtype)

            @jax.checkpoint
            def block_loss(w, b, xb, yb, kb):
                z = jnp.dot(xb, w.T if self.tied_embedding else w,
                            preferred_element_type=acc_t)
                if b is not None:
                    z = z + b.astype(acc_t)
                nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
                    z, yb[:, None], axis=-1)[:, 0]
                return jnp.sum(nll * kb.astype(acc_t))

            def body(total, blk_in):
                return total + block_loss(params["W"], params.get("b"),
                                          *blk_in), None

            total, _ = jax.lax.scan(
                body, jnp.zeros((), acc_t),
                (x.reshape(-1, blk, f), labels.reshape(-1, blk),
                 keep.reshape(-1, blk)))
            if self.weighted:
                return total / n
            return total / jnp.maximum(jnp.sum(keep), 1.0).astype(acc_t)


@register_layer
@dataclasses.dataclass(frozen=True)
class RnnLossLayer(LayerConf):
    """Parameter-free time-distributed loss (DL4J RnnLossLayer)."""
    activation: str = "identity"
    loss: str = "mse"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return get_activation(self.activation)(x), state

    def score(self, params, x, labels, *, train=False, rng=None, mask=None):
        return get_loss(self.loss)(labels, x, self.activation, mask=mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class LastTimeStep(LayerConf):
    """Wraps an RNN layer and emits only the last (unmasked) step's output
    (DL4J nn/conf/layers/recurrent/LastTimeStep.java)."""
    layer: Optional[LayerConf] = None

    def output_type(self, input_type: InputType) -> InputType:
        inner = self.layer.output_type(input_type)
        return InputType.feed_forward(inner.shape[1])

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        return self.layer.init(key, input_type, dtype)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        y, new_state = self.layer.apply(params, state, x, train=train, rng=rng,
                                        mask=mask)
        if mask is None:
            return y[:, -1, :], new_state
        # index of last unmasked step per example; (mask * (t+1)).argmax
        # handles any mask layout (valid-prefix AND the valid-suffix masks
        # produced by Bidirectional's time flip), not just ALIGN_START
        T = y.shape[1]
        pos = jnp.where(mask > 0, jnp.arange(1, T + 1, dtype=jnp.int32), 0)
        idx = jnp.argmax(pos, axis=1).astype(jnp.int32)
        out = jnp.take_along_axis(y, idx[:, None, None], axis=1)[:, 0, :]
        return out, new_state


@register_layer
@dataclasses.dataclass(frozen=True)
class MaskZeroLayer(LayerConf):
    """Zeroes timesteps whose input equals `mask_value`, building a mask for
    the wrapped RNN (DL4J nn/layers/recurrent/MaskZeroLayer.java)."""
    layer: Optional[LayerConf] = None
    mask_value: float = 0.0

    def output_type(self, input_type: InputType) -> InputType:
        return self.layer.output_type(input_type)

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        return self.layer.init(key, input_type, dtype)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        step_is_masked = jnp.all(x == self.mask_value, axis=-1)
        derived = jnp.where(step_is_masked, 0.0, 1.0)
        if mask is not None:
            derived = derived * mask
        return self.layer.apply(params, state, x, train=train, rng=rng,
                                mask=derived)
