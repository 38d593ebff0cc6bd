"""Pallas flash attention — fused online-softmax attention for TPU.

The hot-op counterpart of `nn/layers/attention.py:dot_product_attention`
(reference anchor: the cuDNN fused-attention seam the reference reaches
through its helper classes). One Pallas kernel computes a q-block's output
while streaming K/V blocks through VMEM with the running-max/denominator
recurrence, so the (Tq, Tk) score matrix never materializes in HBM — the
same memory shape as `parallel/ring.py:blockwise_attention`, but fused
into a single kernel (no per-block XLA op dispatch, scores stay in
registers/VMEM, MXU does the two matmuls back to back).

Semantics match dot_product_attention exactly (tested):
- (B, T, H, D) layout, f32 accumulation, 1/sqrt(D) scaling; v (and the
  output) may have another head width than q and k, and any widths the
  blocks fit VMEM at (the latent attentions: 192-wide q.k with 128-wide
  v, position-free; 256-wide q.k with 256-wide v, rotated: both compile
  for the v5e at blocks of 512, `tests/test_tpu_lowering.py`); k and v
  may have FEWER heads than q (grouped-query attention: query head ``h``
  reads key/value head ``h // group``, ``group = H // H_kv``). The K/V
  block index maps do the grouping, so k and v stay at their own head
  count in HBM; in the backward one key head's dk and dv accumulate over
  the ``group`` query heads that read it, inside the kernel (64-wide
  heads, 32 on 8, compile for the v5e at blocks of 512 too);
- optional causal masking; key blocks wholly above the diagonal are
  neither fetched nor computed, forward and backward;
- or, in its place, the visibility rule of block-diffusion training over a
  stream ``[noisy copy ; clean copy]`` (``block_diffusion``): ONE
  description of a rule (`_Geometry`: which tiles are empty, interior or
  edge, the step -> tile maps of the q-side and k-side passes, the in-tile
  predicate) serves both, and the kernels and `_masked_scores` are the
  same; a q tile's live k tiles are then two runs, not one from 0, and
  the maps are part of the rule (`_BlockDiffusion`);
- optional (B, Tk) 0/1 key-validity mask, fully-masked query rows emit 0;
- a tile pays for the masking it needs and no more, chosen from what the
  code can observe (whether a key mask was given, ``causal``, the tile's
  place against the diagonal) and from nothing a caller sets. Without a
  key mask the kernels take NO mask operand (none is built of ones) and
  lower two bodies: on a tile the diagonal crosses the scores
  are masked by position, on an interior tile (its last key visible to
  its first query: 120 of the 136 live tiles at 8,192 positions in
  blocks of 512) nothing is compared or selected, and nowhere are
  probabilities zeroed by select: every row has seen key 0 by the end of
  its first tile, so its running max is finite and ``exp(NEG - m)`` is 0
  already. With a key mask given (a caller's, or the wrapper's own for
  padded keys) one body applies it, the diagonal and the zeroing of
  fully-masked rows on every tile. The gauge ``flash_tile_share{kind}``
  (docs/OBSERVABILITY.md) says which bodies a traced call takes;
- the forward's running max and denominator stay in the layout the row
  reductions give them (``_STAT_LANES``) from tile to tile and are turned
  into the (block_q,) row of the log-sum-exp once a q block;
- backward pass: true flash backward — the probabilities are recomputed
  from the saved per-row log-sum-exp, so the score matrix never
  materializes in either direction; cross-attention shapes (tq != tk)
  included. ONE Pallas kernel (`_bwd_kernel`, named ``flash_bwd_dq``: the
  forward's walk, k innermost) makes a tile's scores, probabilities,
  ``dp`` and ``ds`` a single time and takes dv, dq and dk from them: five
  products a tile. dq of a q block stays in VMEM over its row of tiles;
  dk and dv, which gather over the q blocks, are float32 sums of the
  WHOLE key head in VMEM (16 MiB at 16,384 keys of 128 + 128), cast and
  written once a key head, so the backward holds no buffer in HBM but its
  three results, and every sum is made in the order of the pair below,
  bit for bit. A call whose sums do not fit (`_RESIDENT_SUM_BYTES`,
  reckoned from its shapes) takes two passes instead, each of which makes
  the tile again (``flash_bwd_dq`` over key blocks; ``flash_bwd_dkv`` over
  query blocks): seven products a tile. The gauge ``flash_bwd_kernels``
  says which a traced call takes. The two residuals that are the forward
  kernel's own results (output and log-sum-exp) carry the containers'
  keep-name (`ops.REMAT_KEEP`): a block rematerialised under gradient
  checkpointing holds them and does not run the forward kernel a second
  time.

Off-TPU the kernel runs under `interpret=True` (numerically identical,
slow); on a TPU it compiles or the call raises.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import REMAT_KEEP
from deeplearning4j_tpu.util.platform import is_tpu_backend

NEG = -1e30


class _Geometry:
    """The visibility rule of a call as integer arithmetic on tiles: which
    (q block, k block) tiles hold a visible score and in what order a pass
    walks them, on traced block indices: in a BlockSpec index map (so that
    an empty tile is never fetched) and inside a kernel (so that it is
    never computed). ONE description serves every rule; a rule says

    - for the q-side passes (forward, the fused backward, the pair's dq),
      which k tile a q tile's step ``st`` of the innermost grid axis reads
      (`k_tile`), whether that step is live (`k_live`) and what the index
      map fetches (`k_index`: a dead step repeats the last live tile, no
      new DMA); ``k_steps`` is the axis' length;
    - the same for the k-side pass (the pair's dk/dv): `q_tile`, `q_live`,
      `q_index`, ``q_steps``;
    - whether a live tile is interior (every score visible: nothing is
      compared or selected) or an edge (`interior`), ``edges`` saying
      whether the rule has edge tiles at all, and the predicate on
      absolute rows and columns an edge tile is masked by (`visible`);
    - in Python integers, where a call is traced: how many tiles the
      passes walk by kind (`tile_counts`) and how many tiles hold a
      visible pair at all (`tiles_with_a_pair`, counted tile by tile from
      the predicate's corners, not from the walk).

    This class is the rule of a call that is ``causal`` (query i sees key
    j iff ``j <= i``) or has none. For q block ``qi`` the live k blocks
    are ``0 .. k_hi(qi)``; for k block ``kj`` the live q blocks are
    ``q_lo(kj) .. nq - 1``; a step IS the tile's distance from the low
    end of the run."""

    def __init__(self, causal, block_q, block_k, nq, nk):
        self.causal = causal
        self.bq, self.bk, self.nq, self.nk = block_q, block_k, nq, nk
        self.edges = bool(causal)
        self.k_steps, self.q_steps = nk, nq

    def k_hi(self, qi):
        if not self.causal:
            return qi * 0 + self.nk - 1
        return jnp.minimum(((qi + 1) * self.bq - 1) // self.bk, self.nk - 1)

    def q_lo(self, kj):
        if not self.causal:
            return kj * 0
        return jnp.minimum((kj * self.bk) // self.bq, self.nq - 1)

    # the q-side passes: step -> k tile
    def k_tile(self, qi, st):
        return st

    def k_live(self, qi, st, kj):
        return kj <= self.k_hi(qi)

    def k_index(self, qi, st):
        return jnp.minimum(st, self.k_hi(qi))

    # the k-side pass: step -> q tile; the third axis runs over (query
    # head of the group, step), which ``grp`` takes apart
    def q_tile(self, kj, st, grp):
        return self.q_lo(kj) + grp.step(st)

    def q_live(self, kj, st, grp, qi):
        return qi <= self.nq - 1

    def q_index(self, kj, st, grp):
        return jnp.minimum(self.q_lo(kj) + grp.step(st), self.nq - 1)

    def interior(self, qi, kj):
        """A causal call's tile whose last key is visible to its first
        query: no score of it is masked by the diagonal. (A call that is
        not causal has no other tiles.)"""
        return (kj + 1) * self.bk - 1 <= qi * self.bq

    def visible(self, qi, kj):
        """(block_q, block_k) bool of an edge tile: the rule on absolute
        rows and columns."""
        qpos = qi * self.bq + jax.lax.broadcasted_iota(
            jnp.int32, (self.bq, self.bk), 0)
        kpos = kj * self.bk + jax.lax.broadcasted_iota(
            jnp.int32, (self.bq, self.bk), 1)
        return qpos >= kpos

    def tile_counts(self):
        """(interior, edge) tiles the q-side pass walks for one (batch,
        head) row, in Python integers (where a call is traced, not inside
        a kernel)."""
        if not self.causal:
            return self.nq * self.nk, 0
        live = interior = 0
        for qi in range(self.nq):
            row = min(((qi + 1) * self.bq - 1) // self.bk, self.nk - 1) + 1
            live += row
            interior += sum(self.interior(qi, kj) for kj in range(row))
        return interior, live - interior

    def tiles_with_a_pair(self):
        """Tiles of one (batch, head) row in which some query sees some
        key, counted tile by tile: what a walk cannot go below."""
        if not self.causal:
            return self.nq * self.nk
        return sum(kj * self.bk <= (qi + 1) * self.bq - 1
                   for qi in range(self.nq) for kj in range(self.nk))


class _BlockDiffusion(_Geometry):
    """The rule of block-diffusion training over a stream of ``2 L`` rows
    ``[noisy copy ; clean copy]`` (``noisy(i) = i < L``, ``pos(i) = i mod
    L``, ``blk(i) = pos(i) // block``): row i sees row j iff

    - both are noisy and ``blk(j) == blk(i)`` (a noisy block sees itself,
      both directions), or
    - i is noisy, j clean and ``blk(j) < blk(i)`` (and the clean copies of
      the blocks before it), or
    - both are clean and ``blk(j) <= blk(i)`` (block-causal);

    a clean row never sees a noisy one. ``L`` is a whole number of q
    blocks and of k blocks, so a tile lies in one quadrant. A q tile's
    live k tiles are NOT one run from 0: a noisy q tile reads the noisy
    tiles that hold its own blocks and then the clean tiles from the
    clean half's first up to the last block before its last row's; a clean
    q tile reads the clean tiles up to its last row's block. A clean k
    tile is read by two runs of q tiles (the noisy rows of later blocks,
    the clean rows from its first block on), a noisy one by the noisy q
    tiles of its own blocks. The step -> tile maps below walk exactly
    those tiles; the own tiles come first, so that every row has seen a
    key by the end of its first tile when the blocks are equal. At L =
    8,192, blocks of 512 and ``block`` 4 a (sequence, head) walks 288
    tiles of 1,024: 240 interior, 48 edge (16 of them the noisy half's
    diagonal), in 17 steps a q tile and 32 a k tile."""

    def __init__(self, block, block_q, block_k, nq, nk):
        super().__init__(False, block_q, block_k, nq, nk)
        self.block, self.edges = int(block), True
        self.hq, self.hk = nq // 2, nk // 2          # tiles a half
        self.length = self.hq * block_q
        # Python integers: the grid's third axes are static
        self.k_steps = int(max(sum(self._k_runs(qi, np)[1::2])
                               for qi in range(nq)))
        self.q_steps = int(max(sum(self._q_runs(kj, np)[1::2])
                               for kj in range(nk)))

    def _blocks(self, tile, half, size, xp):
        """(the tile is in the noisy half, the blocks of its first and of
        its last position)."""
        noisy = tile < half
        p0 = (tile - xp.where(noisy, 0, half)) * size
        return noisy, p0 // self.block, (p0 + size - 1) // self.block

    def _k_runs(self, qi, xp):
        """(first tile, tiles) of the q tile's own run of noisy k tiles
        and of its run of clean ones: integers with ``xp`` numpy, traced
        with ``xp`` jax.numpy."""
        b, last = self.block, self.length - 1
        noisy, b0, b1 = self._blocks(qi, self.hq, self.bq, xp)
        own = (b0 * b) // self.bk
        n_own = xp.where(
            noisy, xp.minimum(b1 * b + b - 1, last) // self.bk - own + 1, 0)
        n_clean = xp.where(
            noisy, (b1 * b + self.bk - 1) // self.bk,
            xp.minimum(b1 * b + b - 1, last) // self.bk + 1)
        return own, n_own, self.hk, n_clean

    def _q_runs(self, kj, xp):
        """(first tile, tiles) of the two runs of q tiles that read the k
        tile: of a noisy one the noisy q tiles of its blocks and nothing;
        of a clean one the noisy q tiles from the block after its first
        on, and the clean q tiles from its first block on."""
        b, last = self.block, self.length - 1
        noisy, c0, c1 = self._blocks(kj, self.hk, self.bk, xp)
        own = (c0 * b) // self.bq
        first = xp.where(noisy, own, ((c0 + 1) * b) // self.bq)
        n_first = xp.where(
            noisy, xp.minimum(c1 * b + b - 1, last) // self.bq - own + 1,
            xp.maximum(self.hq - first, 0))
        return (first, n_first, self.hq + own,
                xp.where(noisy, 0, self.hq - own))

    @staticmethod
    def _walk(runs, st, xp):
        """(the tile of step ``st`` of two runs walked one after the
        other, the step is live)."""
        first, n_first, second, n_second = runs
        tile = xp.where(st < n_first, first + st, second + st - n_first)
        return tile, st < n_first + n_second

    def k_tile(self, qi, st):
        return self._walk(self._k_runs(qi, jnp), st, jnp)[0]

    def k_live(self, qi, st, kj):
        return self._walk(self._k_runs(qi, jnp), st, jnp)[1]

    def k_index(self, qi, st):
        runs = self._k_runs(qi, jnp)
        return self._walk(runs, jnp.minimum(st, runs[1] + runs[3] - 1),
                          jnp)[0]

    def q_tile(self, kj, st, grp):
        return self._walk(self._q_runs(kj, jnp), grp.step(st), jnp)[0]

    def q_live(self, kj, st, grp, qi):
        return self._walk(self._q_runs(kj, jnp), grp.step(st), jnp)[1]

    def q_index(self, kj, st, grp):
        runs = self._q_runs(kj, jnp)
        return self._walk(
            runs, jnp.minimum(grp.step(st), runs[1] + runs[3] - 1), jnp)[0]

    def interior(self, qi, kj, xp=jnp):
        """Every query of the tile sees every key of it (the tile is
        live, so it is not a clean q tile on a noisy k tile)."""
        q_noisy, b0, b1 = self._blocks(qi, self.hq, self.bq, xp)
        k_noisy, c0, c1 = self._blocks(kj, self.hk, self.bk, xp)
        one_block = xp.logical_and(xp.logical_and(b0 == b1, c0 == c1),
                                   b0 == c0)
        return xp.where(k_noisy, one_block,
                        c1 <= b0 - xp.where(q_noisy, 1, 0))

    def visible(self, qi, kj):
        shape = (self.bq, self.bk)
        q_noisy, k_noisy = qi < self.hq, kj < self.hk
        row = (qi - jnp.where(q_noisy, 0, self.hq)) * self.bq \
            + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        col = (kj - jnp.where(k_noisy, 0, self.hk)) * self.bk \
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        # positions are not negative: the truncating quotient is the floor
        rb = jax.lax.div(row, jnp.full_like(row, self.block))
        cb = jax.lax.div(col, jnp.full_like(col, self.block))
        # noisy on noisy: the row's own block; noisy on clean: the blocks
        # before it; clean on clean: up to its own
        # (the quadrant as 0/1 integers: no vector is selected by a scalar)
        own = jnp.where(k_noisy, 1, 0)
        before = jnp.where(q_noisy, 1, 0) * (1 - own)
        return jnp.logical_and(cb <= rb - before,
                               cb >= rb * own + (own - 1))

    def tile_counts(self):
        interior = live = 0
        for qi in range(self.nq):
            runs = self._k_runs(qi, np)
            for st in range(int(runs[1] + runs[3])):
                live += 1
                interior += bool(self.interior(
                    qi, self._walk(runs, st, np)[0], np))
        return interior, live - interior

    def tiles_with_a_pair(self):
        """From the three clauses at a tile's corner blocks: noisy on
        noisy, the blocks overlap; noisy on clean, the tile's first block
        lies before the q tile's last; clean on clean, not after it."""
        n = 0
        for qi in range(self.nq):
            q_noisy, b0, b1 = self._blocks(qi, self.hq, self.bq, np)
            for kj in range(self.nk):
                k_noisy, c0, c1 = self._blocks(kj, self.hk, self.bk, np)
                if q_noisy and k_noisy:
                    n += bool(c0 <= b1 and b0 <= c1)
                elif q_noisy:
                    n += bool(c0 < b1)
                elif not k_noisy:
                    n += bool(c0 <= b1)
        return n


def _geometry(rule, block_q, block_k, nq, nk):
    """The geometry of a call's rule: ``False`` (none), ``True`` (causal)
    or ``("block_diffusion", block)``."""
    if isinstance(rule, tuple):
        return _BlockDiffusion(rule[1], block_q, block_k, nq, nk)
    return _Geometry(rule, block_q, block_k, nq, nk)


class _Group:
    """Grouped key/value heads as integer arithmetic on grid indices:
    ``group`` query heads read one key/value head. Rows of q are
    ``b * H + h``, rows of k and v ``b * H_kv + h // group``, which is
    ``row // group``. In the pair's dk/dv pass the grid's rows are those of
    k and its third axis runs over (query head of the group, step); in
    the fused backward the rows are those of k too and the query head of
    the group is a grid axis of its own (`head_row`). ``group`` 1 leaves every
    index as it was, so that a call with as many key heads as query heads
    lowers to the text it had before there were groups."""

    def __init__(self, group, nq):
        self.n, self.nq = group, nq      # nq: the k-side pass' steps a head

    def kv_row(self, q_row):
        return q_row if self.n == 1 else q_row // self.n

    def head_row(self, kv_row, head):
        return kv_row if self.n == 1 else kv_row * self.n + head

    def q_row(self, kv_row, st):
        return kv_row if self.n == 1 else kv_row * self.n + st // self.nq

    def step(self, st):
        return st if self.n == 1 else st % self.nq


def _masked_scores(q, k, kmask, qi, kj, *, geom, scale, diagonal):
    """Scaled masked scores for one (q block, k block) tile — the ONE
    copy of the masking semantics, shared by the forward kernel and the
    backward recomputation, whatever the call's rule. Operands keep their
    dtype (bf16 operands run the MXU at its bf16 rate); the product
    accumulates in float32. Where nothing is to mask nothing is done: no
    select without a key mask (``kmask`` None), no positions and no
    select on a tile that is no edge of the rule (``diagonal`` False: the
    causal diagonal does not cross it, every block of it is visible)."""
    s = scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    if kmask is not None:
        s = jnp.where(kmask[None, :] > 0, s, NEG)
    if diagonal:
        s = jnp.where(geom.visible(qi, kj), s, NEG)
    return s


def _on_live_tile(live, qi, kj, *, geom, masked, body):
    """Run ``body(diagonal)`` on a live tile, traced once for each kind of
    tile the call has. Without a key mask a call under a rule has two: an
    edge tile (the causal diagonal crosses it) is masked by position, an
    interior one not at all. A key mask is applied on every tile, as the
    rule is then."""
    if masked or not geom.edges:
        pl.when(live)(lambda: body(geom.edges))
        return
    interior = geom.interior(qi, kj)
    pl.when(jnp.logical_and(live, interior))(lambda: body(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(interior)))(
        lambda: body(True))


def _key_mask(mask_ref):
    """The (block_k,) key mask of the tile, from what a kernel's operands
    hold between its inputs and its outputs: one block, or nothing."""
    return mask_ref[0][0, 0] if mask_ref else None


#: the forward kernel's running max and denominator are (block_q,
#: _STAT_LANES) scratch, a row's value in every lane: the layout a row
#: reduction that keeps its dimension broadcasts to, and the one the score
#: tile is read against. On the chip at blocks of 512 (PERF.md section 5,
#: PR 35): 1.3-1.65 us a tile less than (block_q,) scratch, which is turned
#: from a value a sublane to a value a lane and back on every tile; a
#: (block_q, 1) scratch is 0.26-0.75 us a tile slower than this one
_STAT_LANES = 128


def _lanes(x, n):
    """A row statistic (rows, _STAT_LANES) against a tile n lanes wide."""
    w = x.shape[1]
    if n <= w:
        return x if n == w else x[:, :n]
    x = jnp.tile(x, (1, -(-n // w)))
    return x if n % w == 0 else x[:, :n]


def _attn_kernel(q_ref, k_ref, v_ref, *rest, geom, scale: float):
    """Grid (B*H, q_blocks, k_blocks), k innermost: each step folds ONE
    (block_k, D) K/V tile into the running (m, l, acc) scratch — only one
    K and one V tile are VMEM-resident at a time, so sequence length is
    not bounded by VMEM. ``rest`` begins with the key mask's block where
    the call has a key mask. The running max and denominator keep the
    layout the row reductions give them, a value a row; they become the
    (block_q,) row of ``lse`` once a q block, in ``_finish``."""
    *mask_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    step = pl.program_id(2)
    kj = geom.k_tile(qi, step)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _step(diagonal):
        v = v_ref[0]
        s = _masked_scores(q_ref[0], k_ref[0], _key_mask(mask_ref), qi, kj,
                           geom=geom, scale=scale, diagonal=diagonal)
        m = m_scr[...]
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, s.shape[1]))
        alpha = jnp.exp(m - m_new)
        if mask_ref:
            # exp(NEG - NEG) == 1 for all-masked rows: zero those terms.
            # Without a key mask every row has seen key 0 by the end of
            # its first tile: m is finite and exp(NEG - m) is 0 already
            p = jnp.where(s > NEG / 2, p, 0.0)
            alpha = jnp.where(m > NEG / 2, alpha, 0.0)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * _lanes(alpha, v.shape[1]) \
            + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    # empty tiles (wholly above the causal diagonal) are neither fetched
    # (the index map repeats the last live tile) nor computed
    _on_live_tile(geom.k_live(qi, step, kj), qi, kj, geom=geom,
                  masked=bool(mask_ref), body=_step)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        m = m_scr[...]
        l = l_scr[...]
        acc = acc_scr[...]
        if mask_ref:
            l = jnp.maximum(l, 1e-30)       # a fully-masked row's is 0
        out = acc / _lanes(l, acc.shape[1])
        # log-sum-exp per q row, the backward residual
        lse = m + jnp.log(l)
        if mask_ref:
            # a fully-masked row emits 0, and +NEG -> +inf for its lse so
            # that exp(s - lse) vanishes there in the bwd
            out = jnp.where(_lanes(m, acc.shape[1]) <= NEG / 2, 0.0, out)
            lse = jnp.where(m <= NEG / 2, -NEG, lse)
        o_ref[0] = out.astype(o_ref.dtype)
        # the one turn from a value a row to the (block_q,) row of lanes
        lse_ref[0, 0] = lse.max(-1)


def _mask_operand(mask, block_k, index):
    """([operand], [its BlockSpec]) of a key mask, and nothing twice where
    the call has none: no array of ones is built, no block fetched. A
    rank-2 operand carries a singleton MIDDLE dim: the Mosaic lowering
    requires the last TWO block dims to divide (8, 128) or equal the
    array dims, so a (1, block) block on a (b, t) array is rejected
    (second-to-last = 1 != b); as (b, 1, t) with (1, 1, block) blocks the
    trailing pair is (1==1, block%128==0) — valid, same bytes."""
    if mask is None:
        return [], []
    b, tk = mask.shape
    return ([mask.astype(jnp.float32).reshape(b, 1, tk)],
            [pl.BlockSpec((1, 1, block_k), index)])


def _head_rows(x):
    """(B, T, H, D) -> (B*H, T, D): one grid row per (batch, head)."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _flash_call(q, k, v, mask, rule, block_q: int, block_k: int,
                interpret: bool):
    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[3]
    scale = 1.0 / float(d) ** 0.5
    geom = _geometry(rule, block_q, block_k, tq // block_q, tk // block_k)
    grp = _Group(h // k.shape[2], geom.q_steps)
    kidx = geom.k_index
    masks, mask_specs = _mask_operand(
        mask, block_k, lambda bh, qi, kj: (bh // h, 0, kidx(qi, kj)))

    out, lse = pl.pallas_call(
        functools.partial(_attn_kernel, geom=geom, scale=scale),
        grid=(b * h, geom.nq, geom.k_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, kj: (grp.kv_row(bh), kidx(qi, kj),
                                             0)),
            pl.BlockSpec((1, block_k, dv),
                         lambda bh, qi, kj: (grp.kv_row(bh), kidx(qi, kj),
                                             0)),
            *mask_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, kj: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(_head_rows(q), _head_rows(k), _head_rows(v), *masks)
    return (out.reshape(b, h, tq, dv).transpose(0, 2, 1, 3),
            lse.reshape(b * h, tq))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, mask, rule, block_q, block_k, interpret):
    return _flash_call(q, k, v, mask, rule, block_q, block_k, interpret)


def _flash_fwd(q, k, v, mask, rule, block_q, block_k, interpret):
    out, lse = _flash_call(q, k, v, mask, rule, block_q, block_k,
                           interpret)
    # the two residuals that are the kernel's own results: a block
    # rematerialised under the containers' gradient checkpointing keeps
    # them, so that its second forward pass does not run the kernel again
    # (q, k and v it makes again from the projections). The identity
    # outside a `jax.checkpoint` and under one with no policy.
    out = checkpoint_name(out, REMAT_KEEP)
    lse = checkpoint_name(lse, REMAT_KEEP)
    return (out, lse), (q, k, v, mask, out, lse)


def _bwd_scores(q, k, kmask, lse_row, qi, kj, *, geom, scale, diagonal):
    """Recompute the softmax probabilities p = exp(s - lse) for one
    (q block, k block) tile via the shared masked-scores helper. Without
    a key mask every row's lse is finite, so a score the diagonal masked
    gives exp(NEG - lse) == 0 by itself."""
    s = _masked_scores(q, k, kmask, qi, kj, geom=geom, scale=scale,
                       diagonal=diagonal)
    p = jnp.exp(s - lse_row[:, None])
    return p if kmask is None else jnp.where(s > NEG / 2, p, 0.0)


def _bwd_ds(p, do, v, delta_row):
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p * (dp - delta_row[:, None])


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                geom, scale):
    """The backward in ONE walk. Grid (B*H_kv, group, q_blocks, k_steps), k
    innermost (the forward's walk, the query heads of a group an outer
    axis): a tile's scores, probabilities, ``dp`` and ``ds`` are made once
    and feed dv, dq and dk. dq of the q block stays in its scratch over the
    row of tiles. dk and dv gather over the q blocks and the group's heads,
    the OUTER axes: the float32 sums of the WHOLE key head are VMEM scratch
    ``(k_blocks, block_k, d)`` and ``(k_blocks, block_k, dv)``, zeroed at
    the key head's first step and cast once into the output blocks, which
    the key head alone indexes, at its last. A k block's contributions
    arrive in the order (query head of the group, q block ascending), the
    k-major pass' order: the three gradients are that pair's bit for bit."""
    *mask_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = rest
    head, qi, step = (pl.program_id(n) for n in (1, 2, 3))
    heads, steps = pl.num_programs(1), pl.num_programs(3)
    kj = geom.k_tile(qi, step)
    q_block = head * geom.nq + qi           # of the key head's walk

    def over_k_blocks(fn):
        jax.lax.fori_loop(0, geom.nk, lambda j, _: fn(j), None)

    @pl.when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

        @pl.when(q_block == 0)
        def _():
            def zero(j):
                dk_scr[j] = jnp.zeros(dk_scr.shape[1:], dk_scr.dtype)
                dv_scr[j] = jnp.zeros(dv_scr.shape[1:], dv_scr.dtype)

            over_k_blocks(zero)

    def _step(diagonal):
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        p = _bwd_scores(q, k, _key_mask(mask_ref), lse_ref[0, 0], qi, kj,
                        geom=geom, scale=scale, diagonal=diagonal)
        dv_scr[kj] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = _bwd_ds(p, do, v_ref[0], delta_ref[0, 0]).astype(k.dtype)
        dq_scr[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[kj] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _on_live_tile(geom.k_live(qi, step, kj), qi, kj, geom=geom,
                  masked=bool(mask_ref), body=_step)

    @pl.when(step == steps - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)

        @pl.when(q_block == heads * geom.nq - 1)
        def _():
            def cast(j):
                dk_ref[0, j] = dk_scr[j].astype(dk_ref.dtype)
                dv_ref[0, j] = dv_scr[j].astype(dv_ref.dtype)

            over_k_blocks(cast)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   geom, scale):
    """The pair's q-side pass: `_bwd_kernel` without the key side."""
    *mask_ref, dq_ref, dq_scr = rest      # the key mask's block, if any
    qi = pl.program_id(1)
    step = pl.program_id(2)
    kj = geom.k_tile(qi, step)

    @pl.when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _step(diagonal):
        k = k_ref[0]
        p = _bwd_scores(q_ref[0], k, _key_mask(mask_ref), lse_ref[0, 0], qi,
                        kj, geom=geom, scale=scale, diagonal=diagonal)
        ds = _bwd_ds(p, do_ref[0], v_ref[0], delta_ref[0, 0])
        dq_scr[...] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _on_live_tile(geom.k_live(qi, step, kj), qi, kj, geom=geom,
                  masked=bool(mask_ref), body=_step)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    geom, grp, scale):
    """The pair's k-side pass. Grid (B*H_kv, k_blocks, group * q_steps):
    one key head's tile stays in the scratch while the third axis runs over
    the query heads of its group and, for each, over the live q blocks."""
    *mask_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    kj = pl.program_id(1)
    step = pl.program_id(2)
    qi = geom.q_tile(kj, step, grp)

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _step(diagonal):
        q, do = q_ref[0], do_ref[0]
        p = _bwd_scores(q, k_ref[0], _key_mask(mask_ref), lse_ref[0, 0], qi,
                        kj, geom=geom, scale=scale, diagonal=diagonal)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = _bwd_ds(p, do, v_ref[0], delta_ref[0, 0])
        dk_scr[...] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _on_live_tile(geom.q_live(kj, step, grp, qi), qi, kj, geom=geom,
                  masked=bool(mask_ref), body=_step)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


#: the kernels' VMEM limit where one asks for more than the compiler's
#: default scope (the v5e has 128 MiB)
_VMEM_BYTES = 100 * 2 ** 20
#: the fused backward holds the float32 sums of ONE key head's dk and dv
#: and their double-buffered output blocks in VMEM: ``T_k (d + dv) (4 + 2
#: itemsize)`` bytes, the widths in whole lanes. A call takes it where
#: that is at most this, which leaves 36 MiB of `_VMEM_BYTES` to the
#: tiles (at blocks of 512 and 256 wide the compiler counts 5 MiB:
#: double-buffered operands, the (512, 512) float32 score tile and its
#: like); past it a call keeps the pair of kernels, whose sums are a
#: block long. In bfloat16 the budget admits 32,768 keys at 128 + 128
#: wide (SDAR's 16,384 take 32 MiB; LFM2's 8,192 at 64 + 64, a lane tile
#: each, 16), 21,845 at 192 + 128 (Kimi's 8,192: 24 MiB), 16,384 at 256 +
#: 256 (GLM's 8,192: 32 MiB)
_RESIDENT_SUM_BYTES = 64 * 2 ** 20


def _backward_is_fused(tk, d, dv, dtype):
    """Whether a call's backward is the one fused kernel: its key-side
    sums fit `_RESIDENT_SUM_BYTES`. From the call's shapes alone."""
    lanes = sum(-(-width // _STAT_LANES) * _STAT_LANES for width in (d, dv))
    return tk * lanes * (4 + 2 * jnp.dtype(dtype).itemsize) \
        <= _RESIDENT_SUM_BYTES


def _flash_bwd(rule, block_q, block_k, interpret, res, g):
    """True flash backward: p is recomputed from the saved LSE, so the
    score matrix never materializes, matching the forward's memory shape,
    and a tile the rule leaves empty (above the causal diagonal) is
    skipped as in the forward. ONE Pallas kernel walks the live tiles once
    for dq, dk and dv (`_bwd_kernel`) where one key head's float32 sums
    fit VMEM (`_backward_is_fused`); a longer call takes two passes that
    each make the tile again (dq over k blocks; dk/dv over q blocks)."""
    q, k, v, mask, out, lse = res
    g, g_lse = g                  # cotangents of (out, lse)
    b, tq, h, d = q.shape
    tk, hk, dv = k.shape[1], k.shape[2], v.shape[3]
    scale = 1.0 / float(d) ** 0.5
    geom = _geometry(rule, block_q, block_k, tq // block_q, tk // block_k)
    grp = _Group(h // hk, geom.q_steps)
    # delta_i = rowsum(dO * O) (the softmax-jacobian diagonal term).
    # The LSE output is differentiable too: d lse_i / d s_ij = p_ij, so
    # its cotangent folds in as ds = p * (dp - (delta - g_lse)) — no
    # kernel change, just an effective delta.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                # (B, T, H)
    # (g_lse is always instantiated — zeros when lse was unused; XLA
    # folds the subtraction away in that case)
    delta = delta - g_lse.astype(jnp.float32).reshape(b, h, tq) \
        .transpose(0, 2, 1)
    gh = _head_rows(g.astype(q.dtype))
    qh, kh, vh = _head_rows(q), _head_rows(k), _head_rows(v)
    # singleton middle dims on the rank-2 operands (lse/delta/mask) — see
    # `_mask_operand`: (1, 1, block) trailing pairs satisfy the Mosaic
    # (8, 128)-or-equal block constraint where (1, block) cannot
    dh = delta.transpose(0, 2, 1).reshape(b * h, 1, tq)
    lse3 = lse.reshape(b * h, 1, tq)
    operands = (qh, kh, vh, gh, lse3, dh)

    common = dict(geom=geom, scale=scale)
    kidx = geom.k_index
    q_spec = lambda ix: pl.BlockSpec((1, block_q, d), ix)
    do_spec = lambda ix: pl.BlockSpec((1, block_q, dv), ix)
    row_spec = lambda ix: pl.BlockSpec((1, 1, block_q), ix)
    k_spec = lambda ix: pl.BlockSpec((1, block_k, d), ix)
    v_spec = lambda ix: pl.BlockSpec((1, block_k, dv), ix)
    back = lambda a, t: a.reshape(b, -1, t,
                                  a.shape[-1]).transpose(0, 2, 1, 3)

    if _backward_is_fused(tk, d, dv, k.dtype):
        # rows of k; the query heads of a group are the second axis
        at_q = lambda bh, hd, qi, st: (grp.head_row(bh, hd), qi, 0)
        at_row = lambda bh, hd, qi, st: (grp.head_row(bh, hd), 0, qi)
        at_k = lambda bh, hd, qi, st: (bh, kidx(qi, st), 0)
        masks, mask_specs = _mask_operand(
            mask, block_k, lambda bh, hd, qi, st: (bh // hk, 0,
                                                   kidx(qi, st)))
        # a key head's whole gradient is ONE output block, by k blocks
        head_spec = lambda width: pl.BlockSpec(
            (1, geom.nk, block_k, width),
            lambda bh, hd, qi, st: (bh, 0, 0, 0))
        # the name is the q-side pass' (it is the kernel that makes dq;
        # fused, it makes dk and dv as well): the benchmark's
        # `attn_fwd_runs_per_bwd` counts the backward's runs by it
        dq, dk, dv_ = pl.pallas_call(
            functools.partial(_bwd_kernel, **common),
            grid=(b * hk, grp.n, geom.nq, geom.k_steps),
            in_specs=[
                q_spec(at_q), k_spec(at_k), v_spec(at_k), do_spec(at_q),
                row_spec(at_row), row_spec(at_row), *mask_specs,
            ],
            out_specs=[q_spec(at_q), head_spec(d), head_spec(dv)],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
                jax.ShapeDtypeStruct((b * hk, geom.nk, block_k, d), k.dtype),
                jax.ShapeDtypeStruct((b * hk, geom.nk, block_k, dv),
                                     v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((geom.nk, block_k, d), jnp.float32),
                pltpu.VMEM((geom.nk, block_k, dv), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_BYTES),
            interpret=interpret,
            name="flash_bwd_dq",
        )(*operands, *masks)
        return back(dq, tq), back(dk, tk), back(dv_, tk), None

    at_q = lambda bh, qi, kj: (bh, qi, 0)
    at_row = lambda bh, qi, kj: (bh, 0, qi)
    at_k = lambda bh, qi, kj: (grp.kv_row(bh), kidx(qi, kj), 0)
    masks, mask_specs = _mask_operand(
        mask, block_k, lambda bh, qi, kj: (bh // h, 0, kidx(qi, kj)))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(b * h, geom.nq, geom.k_steps),
        in_specs=[
            q_spec(at_q), k_spec(at_k), v_spec(at_k), do_spec(at_q),
            row_spec(at_row), row_spec(at_row), *mask_specs,
        ],
        out_specs=q_spec(at_q),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(*operands, *masks)

    # rows of k; the third axis: (query head of the group, step)
    qidx = lambda kj, st: geom.q_index(kj, st, grp)
    at_q = lambda bh, kj, st: (grp.q_row(bh, st), qidx(kj, st), 0)
    at_row = lambda bh, kj, st: (grp.q_row(bh, st), 0, qidx(kj, st))
    at_k = lambda bh, kj, st: (bh, kj, 0)
    masks, mask_specs = _mask_operand(
        mask, block_k, lambda bh, kj, st: (bh // hk, 0, kj))
    dk, dv_ = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, grp=grp, **common),
        grid=(b * hk, geom.nk, grp.n * geom.q_steps),
        in_specs=[
            q_spec(at_q), k_spec(at_k), v_spec(at_k), do_spec(at_q),
            row_spec(at_row), row_spec(at_row), *mask_specs,
        ],
        out_specs=[k_spec(at_k), v_spec(at_k)],
        out_shape=[
            jax.ShapeDtypeStruct((b * hk, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * hk, tk, dv), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*operands, *masks)
    return back(dq, tq), back(dk, tk), back(dv_, tk), None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _publish_tile_shares(geom, masked):
    """The gauges ``flash_tile_share{kind}``, shares of the tiles the
    call's kernels walk by the body they run on them, and
    ``flash_tiles_walked_over_live``, those tiles over the tiles in which
    the rule lets some query see some key; set where the call is traced
    (the last call traced is the one that shows)."""
    from deeplearning4j_tpu.monitor import metrics
    interior, diagonal = geom.tile_counts()
    live = interior + diagonal
    metrics.gauge(
        "flash_tiles_walked_over_live",
        "Tiles the flash attention call traced last fetches and computes "
        "over the (q block, k block) tiles that hold a visible pair under "
        "its rule: 1.0 when no empty tile is walked").set(
            live / geom.tiles_with_a_pair())
    counts = {"interior": 0 if masked else interior,
              "diagonal": 0 if masked else diagonal,
              "key_masked": live if masked else 0}
    gauge = metrics.gauge(
        "flash_tile_share",
        "Shares (%) of the live (q block, k block) tiles of the flash "
        "attention call traced last, by the body its kernels run on them: "
        "interior (nothing masked), diagonal (an edge tile of the rule: "
        "masked by position), key_masked (the call has a key mask: applied "
        "on every tile)",
        labels=("kind",))
    for kind, n in counts.items():
        gauge.set(100.0 * n / live, kind=kind)


def _publish_backward_kernels(fused):
    """The gauge ``flash_bwd_kernels``, set where the call is traced as
    the tile shares are."""
    from deeplearning4j_tpu.monitor import metrics
    metrics.gauge(
        "flash_bwd_kernels",
        "Pallas kernels the backward of the flash attention call traced "
        "last walks its tiles with: 1 (one fused kernel makes dq, dk and "
        "dv; one key head's float32 sums fit VMEM) or 2 (the pair: a call "
        "too long for that)").set(1 if fused else 2)


def flash_attention(q, k, v, *, mask=None, causal: bool = False,
                    block_diffusion: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    return_lse: bool = False):
    """Fused flash attention on (B, T, H, D); see module docstring.

    Sequence lengths are padded to the block size internally (padded keys
    are mask-excluded; padded query rows are sliced off).

    ``v`` (and so the output) may have another head width than ``q`` and
    ``k``; ``k`` and ``v`` may have fewer heads than ``q``, a divisor of
    its count: query head ``h`` reads key/value head ``h // (H // H_kv)``
    and neither is repeated in HBM. With ``causal``, key tiles wholly above the diagonal are neither
    fetched nor computed, forward and backward.

    ``block_diffusion`` (a block length) is the other visibility rule,
    that of block-diffusion training (`_BlockDiffusion`): q, k and v are
    the stream ``[noisy copy ; clean copy]`` of ``2 L`` rows, a noisy
    block sees itself both ways and the clean copies of the blocks before
    it, the clean copy is block-causal and never sees a noisy row. Only
    the tiles that hold a visible pair are fetched and computed, only the
    edge tiles among them masked. ``L`` has to be a whole number of
    blocks of the kernels (``block_q``, ``block_k``: each at most ``L``)
    and of ``block_diffusion``; the rule takes no key mask and is not
    ``causal`` besides.

    return_lse=True additionally returns the per-row log-sum-exp
    ((B, T, H), the softmax normalizer in log space) so partial results
    over DIFFERENT key shards can be merged exactly:
        m = max(lse1, lse2); w_i = exp(lse_i - m)
        out = (w1*out1 + w2*out2) / (w1 + w2); lse = m + log(w1 + w2)
    — the composition rule ring/context parallelism uses across chips.
    The LSE output is fully differentiable (its cotangent folds into the
    backward's delta term), so merged results train correctly through
    plain autodiff of the merge arithmetic."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    if k.shape[3] != d or h % k.shape[2] or v.shape[:3] != k.shape[:3]:
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}: k needs "
                         "q's head width and a head count that divides "
                         "q's, v k's length and heads")
    if interpret is None:
        interpret = not is_tpu_backend()
    rule = bool(causal)
    if block_diffusion is not None:
        half = tq // 2
        block_q, block_k = min(block_q or 128, half), min(block_k or 128,
                                                          half)
        if causal or mask is not None or tq != tk or tq % 2 or \
                block_diffusion < 1 or half % block_diffusion or \
                half % block_q or half % block_k:
            raise ValueError(
                f"block_diffusion {block_diffusion} over q {q.shape}, k "
                f"{k.shape} in blocks of {block_q} x {block_k}: the rule "
                "takes a stream of twice a whole number of its blocks and "
                "of the kernels' blocks as q and as k, no key mask and no "
                "causal rule besides")
        rule = ("block_diffusion", int(block_diffusion))
    block_q = min(block_q or 128, max(tq, 1))
    block_k = min(block_k or 128, max(tk, 1))
    pq = (-tq) % block_q
    pk = (-tk) % block_k
    if mask is None and pk:
        mask = jnp.ones((b, tk), q.dtype)
    if pq or pk:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        if mask is not None:
            mask = jnp.pad(mask, ((0, 0), (0, pk)))
    _publish_tile_shares(
        _geometry(rule, block_q, block_k, q.shape[1] // block_q,
                  k.shape[1] // block_k), masked=mask is not None)
    _publish_backward_kernels(
        _backward_is_fused(k.shape[1], d, v.shape[3], k.dtype))
    out, lse = _flash(q, k, v, mask, rule, block_q, block_k, interpret)
    if not return_lse:
        return out[:, :tq]
    b, _, h, d = q.shape
    lse = lse.reshape(b, h, -1).transpose(0, 2, 1)[:, :tq]
    # kernel-internal fully-masked-row sentinel (+inf, needed by its own
    # backward) -> large-NEGATIVE lse at the public boundary, so the
    # documented merge rule gives those rows zero weight directly
    lse = jnp.where(lse >= -NEG / 10, jnp.asarray(NEG, lse.dtype), lse)
    return out[:, :tq], lse
