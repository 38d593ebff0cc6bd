"""Learned sparse attention (a "lightning indexer" picks each query's keys).

For query ``t`` the indexer scores every key ``s <= t``,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32),

the ``min(t + 1, topk)`` keys with the largest ``I[t, s]`` are kept (ties at
the edge go to the lower ``s``, `jax.lax.top_k`'s order; EXACT, no
approximate selection), every query head attends those keys only (grouped
key/value heads: query head ``h`` reads key head ``h // group``), and the
indexer is trained by the Kullback-Leibler divergence of its softmax over
the kept keys from the attention's own probabilities, averaged over the
heads and detached:

    p[t, s]  = mean_h softmax_{s in S_t}(q[t, h] . k[s] / sqrt(D))[s]
    kl[t]    = sum_{s in S_t} p[t, s] * (log p[t, s]
                                         - log softmax_{S_t}(I[t, .])[s])

`sparse_attention` returns the weighted values, ``kl`` a query and the
kept pairs a sequence. Its gradient splits cleanly: q, k and v get theirs
from the output alone (``p`` is detached in ``kl``), the indexer's three
inputs from ``kl`` alone (the selection is not differentiated).

A selection is handed on as two numbers a query, never as ids: the value
``tau`` of its ``topk``-th largest score and the position ``cut`` of the
last key admitted AT that value; key ``s`` is kept iff ``I > tau`` or
``I == tau and s <= cut`` (`keep_mask`). That is `lax.top_k`'s set to the
key, ties included, at 8 bytes a query.

On a TPU five Pallas kernels do the work, none holding a score matrix in
HBM beyond 2,048 query rows of the indexer's:

- ``dsa_index``: the indexer's scores by (q block, k block) tile, written
  as order-preserving int32 keys, 2,048 query rows at a time;
- ``dsa_select``: for a block of rows held in VMEM, a bisection on the
  keys' bits for ``tau`` (32 counts a row), a second one on the position
  for ``cut`` where a row has ties at ``tau``, and the log-sum-exp of the
  kept scores;
- ``dsa_attn_fwd``: flash attention over every causal tile with ONE more
  mask: the kernel makes the indexer's tile again (the same tile function
  on the same tile shape, so bit for bit the one ``tau`` was taken from)
  and keeps `keep_mask`; all query heads of a tile in one grid step, so
  that the tile's mask is made once for them all; output, log-sum-exp and
  the count of kept pairs a query;
- ``dsa_kl_fwd``: ``kl`` a query from the saved log-sum-exps;
- ``dsa_attn_bwd``: the flash backward under the same mask in ONE walk
  over the causal tiles (a tile's mask, probabilities and ``ds`` made
  once for dq, dk and dv), and, from the probabilities it makes again
  anyway, the gradient of ``kl`` into the indexer's ``qI``, ``w`` and
  ``kI``; the key side's sums gather in float32 buffers in HBM.

The tile's orientation follows what a kernel's sums contract over.
``dsa_index``, ``dsa_select`` and ``dsa_attn_fwd`` hold it Q-MAJOR,
(bq, bk): the forward's statistics are RUNNING ones, made by reductions
along the lanes that leave them lane-broadcast already, and its one sum
``p . v`` contracts over the keys, plain as it stands (key-major would
make it the product with a transposed operand). ``dsa_attn_bwd`` and
``dsa_kl_fwd`` hold it KEY-MAJOR, (bk, bq), keys down the sublanes and
queries along the lanes: the statistics they read are SAVED ones, a lane
vector a head ((B, H, 1, T): ``lse``, ``delta``), which such a tile takes
as they lie where a q-major one turned each into a column a head and
tile; and the backward's key-side sums (dv, dk, dki) contract over the
queries, the tile's lanes: plain products, where a q-major tile gave two
of them a head a transposed left operand (the query side's dq and dqi
gather transposed, plain as well: `_bwd_kernel`). The mask of both stays
THE set ``tau`` was taken from: they make the q-major tile by the same
`_tile_mask` and turn the finished (scores, kept) once a tile
(`_key_major`), never the indexer's products in another order.

What the backward needs again and is dear to make (output, log-sum-exp,
``tau``, ``cut``, the indexer's log-sum-exp) carries `ops.REMAT_KEEP`.

Off the TPU, and at lengths the tiles do not divide, the same mathematics
runs as plain XLA over blocks of queries (`_sparse_attention_xla`), which
is also what the kernels are tested against (interpreted).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import REMAT_KEEP
from deeplearning4j_tpu.ops.flash_attention import (
    NEG, _Geometry, _STAT_LANES, _VMEM_BYTES, _lanes,
)
from deeplearning4j_tpu.util.platform import is_tpu_backend

_INT_MIN = -2 ** 31
_LANES = 128
#: a q block of the kernels holds EVERY query head of its rows in VMEM for
#: a tile (so that the tile's mask is made once for them all): the most
#: rows whose q, output gradient and q gradient (double-buffered, compute
#: dtype) and float32 accumulator stay under this. On the v5e at 32 heads
#: of 128 and 32,768 positions, k blocks of 512 (my chip runs, PR 38): q
#: blocks of 128 / 256 / 512 rows take 665 / 535 / 485 ms a layer and step
_Q_BLOCK_BYTES = 40 * 2 ** 20
#: rows `dsa_select` bisects at once (their keys, T a row, in VMEM)
_SELECT_ROWS = 64
#: query rows whose indexer scores are in HBM at once (T keys each, int32)
_INDEX_ROWS = 2048


# ------------------------------------------------------- the tile functions
def _sortable(x):
    """float32 -> int32 whose signed order is the floats' (no NaN here)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ 0x7fffffff, b)


def _unsortable(key):
    b = jnp.where(key < 0, key ^ 0x7fffffff, key)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _index_heads(qi, ki, j):
    """Head ``j``'s products of a tile: qi (Hi, bq, Di) . ki (bk, Di)."""
    return jax.lax.dot_general(qi[j], ki, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def index_tile(qi, ki, wi):
    """The indexer's scores of one tile, float32: ``qi`` (Hi, bq, Di) and
    ``ki`` (bk, Di) in the compute dtype, ``wi`` (bq, Hi) float32. The
    heads are summed in their order from +0.0, so a score is never -0.0
    and equal scores are equal keys. The ONE copy: the kernel that takes
    ``tau`` and the kernels that compare with it run this on one tile
    shape."""
    acc = jnp.zeros((qi.shape[1], ki.shape[0]), jnp.float32)
    for j in range(qi.shape[0]):
        acc = acc + wi[:, j:j + 1] * jnp.maximum(_index_heads(qi, ki, j),
                                                 0.0)
    return acc


def _positions(q0, k0, bq, bk):
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return qpos, kpos


def keep_mask(scores, tau, cut, qpos, kpos):
    """Which keys of a tile a query keeps: ``scores`` (bq, bk) float32,
    ``tau`` and ``cut`` (bq, 1), positions (bq, bk)."""
    kept = (scores > tau) | ((scores == tau) & (kpos <= cut))
    return kept & (kpos <= qpos)


def _fold(x, combine=jnp.add):
    """(rows, n * 128) -> (rows, 128): the lane groups combined (summed:
    whole vector registers, no move across lanes)."""
    out = x[:, :_LANES]
    for i in range(1, x.shape[1] // _LANES):
        out = combine(out, x[:, i * _LANES:(i + 1) * _LANES])
    return out


# ------------------------------------------------------------- the kernels
def _index_kernel(off_ref, qi_ref, ki_ref, wi_ref, key_ref, *, bq, bk):
    """Grid (B, rows / bq, nk): the tile's scores as sortable keys, a key
    above the diagonal the least int32 (no score maps to it)."""
    r, kj = pl.program_id(1), pl.program_id(2)
    q0 = off_ref[0] + r * bq

    @pl.when(kj * bk <= q0 + bq - 1)
    def _():
        s = index_tile(qi_ref[0], ki_ref[0], wi_ref[0])
        qpos, kpos = _positions(q0, kj * bk, bq, bk)
        key_ref[0, 0] = jnp.where(kpos <= qpos, _sortable(s), _INT_MIN)


def _select_kernel(off_ref, key_ref, tau_ref, cut_ref, lse_ref, *, rows, bk,
                   nk, topk, t_bits):
    """Grid (B, chunk rows / rows): ``key_ref`` (1, nk, rows, bk) holds the
    rows' keys, tile by tile. ``tau``'s key is the largest value with at
    least K keys at or above it, K = min(t + 1, topk), built from the top
    bit down; ``cut`` the position of the last of the K - (keys above)
    ties admitted, lowest position first."""
    q0 = off_ref[0] + pl.program_id(1) * rows
    live = jnp.minimum((q0 + rows - 1) // bk, nk - 1) + 1
    t = q0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    want = jnp.minimum(t + 1, topk)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)

    def over_tiles(fn, init, combine):
        def body(j, acc):
            return combine(acc, _fold(fn(key_ref[0, j], j), combine))
        return jax.lax.fori_loop(0, live, body, init)

    def count(pred):
        zero = jnp.zeros((rows, _LANES), jnp.int32)
        acc = over_tiles(lambda x, j: pred(x, j).astype(jnp.int32), zero,
                         jnp.add)
        return acc.sum(-1, keepdims=True)

    found = jnp.zeros((rows, 1), jnp.int32)     # bits of (key ^ INT_MIN)
    for bit in range(31, -1, -1):
        cand = found | (_INT_MIN if bit == 31 else 1 << bit)
        at_or_above = count(lambda x, j, c=cand ^ _INT_MIN: x >= c)
        found = jnp.where(at_or_above >= want, cand, found)
    tau = found ^ _INT_MIN
    above = count(lambda x, j: x > tau)
    ties = count(lambda x, j: x == tau)
    need = want - above                      # ties admitted, 1 .. ties

    def last_tie():
        pos = jnp.zeros((rows, 1), jnp.int32)
        for bit in range(t_bits - 1, -1, -1):
            cand = pos | (1 << bit)
            before = count(lambda x, j, c=cand:
                           (x == tau) & (j * bk + lane < c))
            pos = jnp.where(before < need, cand, pos)
        return pos

    # a row without a tie to break admits every key at tau
    cut = jax.lax.cond(jnp.max(ties - need) > 0, last_tie,
                       lambda: jnp.full((rows, 1), nk * bk, jnp.int32))

    top = over_tiles(lambda x, j: x, jnp.full((rows, _LANES), _INT_MIN,
                                              jnp.int32), jnp.maximum)
    top = _unsortable(top.max(-1, keepdims=True))

    def kept_exp(x, j):
        kept = (x > tau) | ((x == tau) & (j * bk + lane <= cut))
        return jnp.where(kept, jnp.exp(_unsortable(x) - top), 0.0)

    total = over_tiles(kept_exp, jnp.zeros((rows, _LANES), jnp.float32),
                       jnp.add).sum(-1, keepdims=True)
    tau_ref[0] = _unsortable(tau)
    cut_ref[0] = cut
    lse_ref[0] = top + jnp.log(total)


def _tile_mask(qi_ref, ki_ref, wi_ref, tau_ref, cut_ref, q0, k0):
    """(scores, kept) of the tile at rows ``q0``.., keys ``k0``..."""
    s = index_tile(qi_ref[0], ki_ref[0], wi_ref[0])
    qpos, kpos = _positions(q0, k0, *s.shape)
    return s, keep_mask(s, tau_ref[0], cut_ref[0], qpos, kpos)


def _scores(a, b, kept, scale):
    """Scaled scores of one head's tile, ``a . b^T`` (rows of ``a`` down
    the tile: (q, k) q-major, (k, q) key-major), ``-inf`` off the kept
    keys: a probability made from them is 0 there by itself, also in a row
    that has met no kept key yet (its running max is the finite `NEG`)."""
    s = scale * jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    return jnp.where(kept, s, -jnp.inf)


def _fwd_kernel(q_ref, k_ref, v_ref, qi_ref, ki_ref, wi_ref, tau_ref,
                cut_ref, o_ref, lse_ref, cnt_ref, m_scr, l_scr, acc_scr,
                cnt_scr, *, geom, scale, heads, group):
    """Grid (B, nq, nk), k innermost: every query head of the q block
    folds the tile into its running (m, l, acc) under the tile's one
    mask. A row may meet tiles with no kept key before its first kept
    one: its scores are ``-inf`` there and its running max stays `NEG`."""
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        cnt_scr[...] = jnp.zeros_like(cnt_scr)

    @pl.when(kj <= geom.k_hi(qi))
    def _tile():
        _, kept = _tile_mask(qi_ref, ki_ref, wi_ref, tau_ref, cut_ref,
                             qi * geom.bq, kj * geom.bk)
        cnt_scr[...] += _fold(kept.astype(jnp.int32))

        def head(h, _):
            g = h // group
            v = v_ref[0, g]
            s = _scores(q_ref[0, h], k_ref[0, g], kept, scale)
            m = m_scr[h]
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, s.shape[1]))
            alpha = jnp.exp(m - m_new)
            m_scr[h] = m_new
            l_scr[h] = l_scr[h] * alpha + p.sum(-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * _lanes(alpha, v.shape[1]) \
                + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return 0

        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        def head(h, _):
            l = l_scr[h]
            o_ref[0, h] = (acc_scr[h] / _lanes(l, acc_scr.shape[2])) \
                .astype(o_ref.dtype)
            lse_ref[0, h, 0] = (m_scr[h] + jnp.log(l)).max(-1)
            return 0

        jax.lax.fori_loop(0, heads, head, 0)
        cnt_ref[0] = cnt_scr[...].sum(-1, keepdims=True)


def _key_major(s, kept):
    """A q-major tile's (scores, kept) turned KEY-MAJOR, (bk, bq): keys
    down the sublanes, queries along the lanes. The mask stays the set
    ``tau`` was taken from by construction: it is `_tile_mask`'s own
    (`index_tile` on the one tile shape, as in `dsa_index` and
    `dsa_attn_fwd`), moved and never made again; ONE transpose a tile for
    all the heads (a score is finite, so ``> -inf`` is the mask; the
    scores come back 0 off the kept keys)."""
    s = jnp.where(kept, s, -jnp.inf).T
    kept = s > -jnp.inf
    return jnp.where(kept, s, 0.0), kept


def _down(x):
    """(rows, n) -> (1, n): the sum down the sublanes, a lane vector."""
    return jnp.sum(x, axis=0, keepdims=True)


def _mean_probs(q_ref, k_ref, lse_ref, kept, scale, heads, group,
                each=None):
    """The KEY-MAJOR tile's probabilities (bk, bq) averaged over the query
    heads, from the saved log-sum-exps; ``each(h, g, p)`` sees every
    head's on the way. ``lse_ref`` (1, H, 1, bq) holds a head's as a lane
    vector, which a key-major tile reads as it lies (a row handed down
    the sublanes); a q-major tile had to turn it into a column, a head and
    tile."""
    def head(h, acc):
        g = h // group
        p = jnp.exp(_scores(k_ref[0, g], q_ref[0, h], kept, scale)
                    - lse_ref[0, h])
        if each is not None:
            each(h, g, p)
        return acc + p

    total = jax.lax.fori_loop(0, heads, head,
                              jnp.zeros(kept.shape, jnp.float32))
    return total * (1.0 / heads)


def _kl_kernel(q_ref, k_ref, qi_ref, ki_ref, wi_ref, tau_ref, cut_ref,
               lse_ref, lsei_ref, kl_ref, acc_scr, *, geom, scale, heads,
               group):
    """Grid (B, nq, nk), k innermost; the tile KEY-MAJOR (`_key_major`):
    every statistic a query (``lse``, ``lsei`` (1, 1, 1, bq), the sum
    ``kl`` itself) is a lane vector and stays one."""
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(kj <= geom.k_hi(qi))
    def _tile():
        s, kept = _key_major(*_tile_mask(
            qi_ref, ki_ref, wi_ref, tau_ref, cut_ref, qi * geom.bq,
            kj * geom.bk))
        p = _mean_probs(q_ref, k_ref, lse_ref, kept, scale, heads, group)
        # p is 0 off the kept keys: 0 * (finite) there
        acc_scr[...] += _down(p * (jnp.log(jnp.maximum(p, 1e-37))
                                   - (s - lsei_ref[0, 0])))

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        kl_ref[0, 0] = acc_scr[...]


def _index_grad(s, kept, p, lsei_ref, gkl_ref):
    """d kl / d scores of the key-major tile, times the cotangent of its
    queries (both (1, 1, 1, bq))."""
    pi = jnp.where(kept, jnp.exp(s - lsei_ref[0, 0]), 0.0)
    return gkl_ref[0, 0] * (pi - p)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi_ref,
                ki_ref, wi_ref, wit_ref, tau_ref, cut_ref, lsei_ref, gkl_ref,
                dq_ref, dqi_ref, dwi_ref, dk_hbm, dv_hbm, dki_hbm, dq_scr,
                dqi_scr, dwi_scr, dk_scr, dv_scr, dki_scr, kt_scr, sem, *,
                geom, scale, heads, group):
    """Grid (B, nq, nk), k innermost, every axis sequential: a tile's
    mask, probabilities and ``ds`` are made ONCE and feed all six
    gradients.

    The tile is KEY-MAJOR, (bk, bq) (`_key_major`): ``p^T`` and ``ds^T``
    are what the tile IS, so the key side's sums dv += p^T . do, dk +=
    ds^T . q and dki += g^T . qI_j are plain products (bk rows stream
    over a latched operand), ``lse`` and ``delta`` (1, H, 1, bq), the
    indexer's ``lsei``, ``kl``'s cotangent and the head weights
    ``wit_ref`` (1, Hi, 1, bq) are read along the lanes as they lie, and
    dwi gathers as the lane vector it leaves as. The query side's sums,
    which contract over the tile's rows, gather TRANSPOSED, plain too:
    dq^T (D, bq) += k^T . ds^T and dqi_j^T (Di, bq) += kI^T . g, with
    ``k^T`` and ``kI^T`` made once a KEY head and tile (``kt_scr``: the
    head loop picks a key head's by its index) and the sums turned once a
    q block (`_finish`). So no product of the head loop has a transposed
    left operand, where a q-major tile had two, and two lane -> sublane
    turns of the statistics a head and tile beside them (PERF.md section
    5, PR 42).

    dq, dqi and dwi of the q block stay in VMEM scratch over
    its row of tiles. dk, dv and dki of a k block gather over the q
    blocks, the OUTER axis: they are float32 buffers in HBM (``dk_hbm``
    (B, Hk, T, D), ``dv_hbm``, ``dki_hbm`` (B, T, Di padded to whole
    lanes: a copy moves whole lane tiles), never BlockSpec-pipelined),
    which a tile finds in one of two VMEM slots (``kj % 2``; zeros at
    the k block's FIRST q block, so the buffers need no zeroing), adds to
    in the order the q blocks come (ascending, float32) and copies back.

    The read-after-write on those buffers is ordered by explicit copies
    that are WAITED on, not by the grid's pipeline: tile ``kj`` asks for
    tile ``kj + 1``'s sums once its heads are done (after waiting for
    the copy back of tile ``kj - 1``, whose slot they take; a row's first
    tile asks for its own) and waits for its own before its first sum;
    the row's last step waits for every copy back still in flight. So
    when a q block's first tile starts, nothing an earlier q block wrote
    is still on its way (q block 0 has ONE live tile: k block 0 is
    written at step (0, 0) and read at the very next, (1, 0)), and within
    a row no two tiles share a k block. The copy in runs under the
    indexer's gradient and the next tile's mask, the copy back under the
    next tile (on the v5e, alone at the Keye cell's shapes, PR 39: 216.2
    ms with the copies, 215.7 without; 222.8 asked for at the tile's own
    start)."""
    b, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    k_hi = geom.k_hi(qi)
    plain = (((1,), (0,)), ((), ()))
    transposed = (((1,), (1,)), ((), ()))     # a . b^T: a key-major tile

    def copies(j, back):
        """The three copies of k block ``j``'s sums, HBM -> its VMEM slot
        or ``back``."""
        slot, rows = j % 2, pl.ds(j * geom.bk, geom.bk)
        pairs = ((dk_hbm.at[b, :, rows], dk_scr.at[slot]),
                 (dv_hbm.at[b, :, rows], dv_scr.at[slot]),
                 (dki_hbm.at[b, rows], dki_scr.at[slot]))
        return [pltpu.make_async_copy(*(pair[::-1] if back else pair),
                                      sem.at[int(back), slot, n])
                for n, pair in enumerate(pairs)]

    def wait_back(j):
        for copy in copies(j, True):
            copy.wait()

    def summed_before(j):
        """Whether an earlier q block saw k block ``j``'s keys."""
        return qi != geom.q_lo(j)

    def fetch(j):
        """k block ``j``'s sums so far into its slot: zeros at the first
        q block that sees its keys, a copy from HBM after that."""
        @pl.when(jnp.logical_not(summed_before(j)))
        def _():
            for scr in (dk_scr, dv_scr, dki_scr):
                scr[j % 2] = jnp.zeros(scr.shape[1:], scr.dtype)

        @pl.when(summed_before(j))
        def _():
            for copy in copies(j, False):
                copy.start()

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        dqi_scr[...] = jnp.zeros_like(dqi_scr)
        dwi_scr[...] = jnp.zeros_like(dwi_scr)
        fetch(kj)

    @pl.when(kj <= k_hi)
    def _tile():
        slot = kj % 2
        s, kept = _key_major(*_tile_mask(
            qi_ref, ki_ref, wi_ref, tau_ref, cut_ref, qi * geom.bq,
            kj * geom.bk))
        for n in range(kt_scr.shape[0]):
            kt_scr[n] = k_ref[0, n].T

        @pl.when(summed_before(kj))
        def _():
            for copy in copies(kj, False):
                copy.wait()

        def each(h, g, p):
            q, do = q_ref[0, h], do_ref[0, h]
            dv_scr[slot, g] += jax.lax.dot_general(
                p.astype(do.dtype), do, plain,
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v_ref[0, g], do, transposed,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, h])).astype(q.dtype)
            dq_scr[h] += scale * jax.lax.dot_general(
                kt_scr[g], ds, plain, preferred_element_type=jnp.float32)
            dk_scr[slot, g] += scale * jax.lax.dot_general(
                ds, q, plain, preferred_element_type=jnp.float32)

        p = _mean_probs(q_ref, k_ref, lse_ref, kept, scale, heads, group,
                        each)

        @pl.when(kj < k_hi)
        def _():
            @pl.when(kj >= 1)
            def _():
                wait_back(kj - 1)

            fetch(kj + 1)

        di = _index_grad(s, kept, p, lsei_ref, gkl_ref)
        ki = ki_ref[0]
        kit = ki.T
        for j in range(qi_ref.shape[1]):
            a = jax.lax.dot_general(ki, qi_ref[0, j], transposed,
                                    preferred_element_type=jnp.float32)
            dwi_scr[j] += _down(di * jnp.maximum(a, 0.0))
            g = jnp.where(a > 0, di * wit_ref[0, j], 0.0).astype(ki.dtype)
            dqi_scr[j] += jax.lax.dot_general(
                kit, g, plain, preferred_element_type=jnp.float32)
            dki_scr[slot, :, :ki.shape[1]] += jax.lax.dot_general(
                g, qi_ref[0, j], plain, preferred_element_type=jnp.float32)
        for copy in copies(kj, True):
            copy.start()

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        @pl.when(k_hi >= 1)
        def _():
            wait_back(k_hi - 1)

        wait_back(k_hi)

        def head(h, _):
            dq_ref[0, h] = dq_scr[h].T.astype(dq_ref.dtype)
            return 0

        jax.lax.fori_loop(0, heads, head, 0)
        for j in range(dqi_scr.shape[0]):
            dqi_ref[0, j] = dqi_scr[j].T.astype(dqi_ref.dtype)
        dwi_ref[0] = dwi_scr[...]


# ------------------------------------------------------- calling the kernels
def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_BYTES)


def _heads_first(x):
    """(B, T, H, D) -> (B, H, T, D)."""
    return x.transpose(0, 2, 1, 3)


def _select_chunk(t: int, bk: int) -> int:
    """Query rows whose scores are in HBM at once: whole k blocks, at most
    `_INDEX_ROWS`."""
    nk = t // bk
    return bk * max(g for g in (4, 2, 1)
                    if nk % g == 0 and g * bk <= max(_INDEX_ROWS, bk))


def _index_keys(off, qih, ki, wi, bq, bk, interpret):
    """The indexer's scores of query rows ``off[0]`` .. (a chunk of them)
    against all keys as sortable int32 keys, (B, nk, chunk, bk): tile
    (j, r) holds rows r against keys j * bk ..; tiles wholly above the
    diagonal are not written."""
    b, hi, t, di = qih.shape
    nk, chunk = t // bk, _select_chunk(t, bk)
    with jax.named_scope("dsa/index"):
        return pl.pallas_call(
            functools.partial(_index_kernel, bq=bq, bk=bk),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(b, chunk // bq, nk),
                in_specs=[
                    pl.BlockSpec((1, hi, bq, di), lambda i, r, j, o:
                                 (i, 0, o[0] // bq + r, 0)),
                    pl.BlockSpec((1, bk, di), lambda i, r, j, o: (i, j, 0)),
                    pl.BlockSpec((1, bq, hi), lambda i, r, j, o:
                                 (i, o[0] // bq + r, 0)),
                ],
                out_specs=pl.BlockSpec((1, 1, bq, bk),
                                       lambda i, r, j, o: (i, j, r, 0))),
            out_shape=jax.ShapeDtypeStruct((b, nk, chunk, bk), jnp.int32),
            compiler_params=_params("parallel", "parallel", "parallel"),
            interpret=interpret, name="dsa_index",
        )(off, qih, ki, wi)


def _select(qih, ki, wi, topk, bq, bk, interpret):
    """(tau, cut, log-sum-exp of the kept scores), (B, T, 1) each, from
    qih (B, Hi, T, Di), ki (B, T, Di), wi (B, T, Hi): the scores of
    `_INDEX_ROWS` query rows at a time."""
    b, _, t, _ = qih.shape
    nk, chunk = t // bk, _select_chunk(t, bk)
    rows = min(_SELECT_ROWS, bq)
    t_bits = max((t - 1).bit_length(), 1)        # of a key's position

    def one(off):
        keys = _index_keys(off, qih, ki, wi, bq, bk, interpret)
        with jax.named_scope("dsa/select"):
            col = pl.BlockSpec((1, rows, 1), lambda i, r, o: (i, r, 0))
            like = lambda dt: jax.ShapeDtypeStruct((b, chunk, 1), dt)
            return pl.pallas_call(
                functools.partial(_select_kernel, rows=rows, bk=bk, nk=nk,
                                  topk=topk, t_bits=t_bits),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1, grid=(b, chunk // rows),
                    in_specs=[pl.BlockSpec((1, nk, rows, bk),
                                           lambda i, r, o: (i, 0, r, 0))],
                    out_specs=[col, col, col]),
                out_shape=[like(jnp.float32), like(jnp.int32),
                           like(jnp.float32)],
                compiler_params=_params("parallel", "parallel"),
                interpret=interpret, name="dsa_select",
            )(off, keys)

    offs = (jnp.arange(t // chunk, dtype=jnp.int32) * chunk)[:, None]
    tau, cut, lse = jax.lax.map(one, offs)
    join = lambda a: a.transpose(1, 0, 2, 3).reshape(b, t, 1)
    return join(tau), join(cut), join(lse)


class _Specs:
    """The BlockSpecs of the kernels whose grid is (B, q blocks, k blocks):
    a step above the diagonal repeats the row's last live k block (no new
    DMA)."""

    def __init__(self, geom, h, hk, hi, d, dv, di):
        bq, bk = geom.bq, geom.bk
        kx = lambda i, j: jnp.minimum(j, geom.k_hi(i))
        at_q = lambda b, i, j: (b, 0, i, 0)
        at_k = lambda b, i, j: (b, 0, kx(i, j), 0)
        self.q = pl.BlockSpec((1, h, bq, d), at_q)
        self.o = pl.BlockSpec((1, h, bq, dv), at_q)
        self.k = pl.BlockSpec((1, hk, bk, d), at_k)
        self.v = pl.BlockSpec((1, hk, bk, dv), at_k)
        self.qi = pl.BlockSpec((1, hi, bq, di), at_q)
        self.ki = pl.BlockSpec((1, bk, di), lambda b, i, j: (b, kx(i, j), 0))
        self.wi = pl.BlockSpec((1, bq, hi), lambda b, i, j: (b, i, 0))
        self.col = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
        # a value a query along the LANES, (B, n, 1, T): one a query head
        # (lse, delta), one an indexer head (wi for the key-major tile,
        # dwi), one (lsei, kl and its cotangent)
        rows = lambda n: pl.BlockSpec((1, n, 1, bq),
                                      lambda b, i, j: (b, 0, 0, i))
        self.row, self.irow, self.one = rows(h), rows(hi), rows(1)


def _rows(a):
    """A value a query (B, T, n) as lane vectors, (B, n, 1, T)."""
    return a.transpose(0, 2, 1)[:, :, None, :]


def _setup(qh, kh, vh, qih, bq, bk):
    b, h, t, d = qh.shape
    hk, dv = kh.shape[1], vh.shape[3]
    hi, di = qih.shape[1], qih.shape[3]
    geom = _Geometry(True, bq, bk, t // bq, t // bk)
    common = dict(geom=geom, scale=1.0 / float(d) ** 0.5, heads=h,
                  group=h // hk)
    return geom, common, (h, hk, hi, d, dv, di)


def _forward_kernels(qh, kh, vh, qih, ki, wi, topk, bq, bk, interpret):
    """(out (B, H, T, Dv), lse (B, H, 1, T), tau, cut, lsei, kl, kept),
    the last five (B, T, 1)."""
    b, h, t, d = qh.shape
    geom, common, dims = _setup(qh, kh, vh, qih, bq, bk)
    dv = dims[4]
    sp = _Specs(geom, *dims)
    tau, cut, lsei = _select(qih, ki, wi, topk, bq, bk, interpret)
    f32 = jnp.float32
    with jax.named_scope("dsa/attn"):
        out, lse, kept = pl.pallas_call(
            functools.partial(_fwd_kernel, **common),
            grid=(b, geom.nq, geom.nk),
            in_specs=[sp.q, sp.k, sp.v, sp.qi, sp.ki, sp.wi, sp.col, sp.col],
            out_specs=[sp.o, sp.row, sp.col],
            out_shape=[jax.ShapeDtypeStruct((b, h, t, dv), qh.dtype),
                       jax.ShapeDtypeStruct((b, h, 1, t), f32),
                       jax.ShapeDtypeStruct((b, t, 1), jnp.int32)],
            scratch_shapes=[pltpu.VMEM((h, bq, _STAT_LANES), f32),
                            pltpu.VMEM((h, bq, _STAT_LANES), f32),
                            pltpu.VMEM((h, bq, dv), f32),
                            pltpu.VMEM((bq, _LANES), jnp.int32)],
            compiler_params=_params("parallel", "parallel", "arbitrary"),
            interpret=interpret, name="dsa_attn_fwd",
        )(qh, kh, vh, qih, ki, wi, tau, cut)
    with jax.named_scope("dsa/kl"):
        kl = pl.pallas_call(
            functools.partial(_kl_kernel, **common),
            grid=(b, geom.nq, geom.nk),
            in_specs=[sp.q, sp.k, sp.qi, sp.ki, sp.wi, sp.col, sp.col,
                      sp.row, sp.one],
            out_specs=sp.one,
            out_shape=jax.ShapeDtypeStruct((b, 1, 1, t), f32),
            scratch_shapes=[pltpu.VMEM((1, bq), f32)],
            compiler_params=_params("parallel", "parallel", "arbitrary"),
            interpret=interpret, name="dsa_kl_fwd",
        )(qh, kh, qih, ki, wi, tau, cut, lse, _rows(lsei)).reshape(b, t, 1)
    return out, lse, tau, cut, lsei, kl, kept


def _rounded(x, dtype):
    """Float32 ``x`` in ``dtype``, the rounding KEPT: a plain cast XLA may
    skip where the consumer widens again (`xla_allow_excess_precision`;
    the rotation's backward does), and the step's numbers are to be those
    of a kernel that wrote ``dtype`` itself."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant).astype(dtype)


def _backward_kernels(res, g_out, g_kl, bq, bk, interpret):
    qh, kh, vh, qih, ki, wi, out, lse, tau, cut, lsei = res
    b, h, t, d = qh.shape
    geom, common, dims = _setup(qh, kh, vh, qih, bq, bk)
    _, hk, hi, _, dv, di = dims
    f32 = jnp.float32
    sp = _Specs(geom, *dims)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    dip = -(-di // _LANES) * _LANES
    with jax.named_scope("dsa/attn"):
        delta = jnp.sum(g_out.astype(f32) * out.astype(f32),
                        axis=-1)[:, :, None, :]           # (B, H, 1, T)
        dq, dqi, dwi, dk, dv_, dki = pl.pallas_call(
            functools.partial(_bwd_kernel, **common),
            grid=(b, geom.nq, geom.nk),
            in_specs=[sp.q, sp.k, sp.v, sp.o, sp.row, sp.row, sp.qi, sp.ki,
                      sp.wi, sp.irow, sp.col, sp.col, sp.one, sp.one],
            out_specs=[sp.q, sp.qi, sp.irow, hbm, hbm, hbm],
            out_shape=[jax.ShapeDtypeStruct(qh.shape, qh.dtype),
                       jax.ShapeDtypeStruct(qih.shape, qih.dtype),
                       jax.ShapeDtypeStruct((b, hi, 1, t), f32),
                       jax.ShapeDtypeStruct(kh.shape, f32),
                       jax.ShapeDtypeStruct(vh.shape, f32),
                       jax.ShapeDtypeStruct((b, t, dip), f32)],
            scratch_shapes=[pltpu.VMEM((h, d, bq), f32),
                            pltpu.VMEM((hi, di, bq), f32),
                            pltpu.VMEM((hi, 1, bq), f32),
                            pltpu.VMEM((2, hk, bk, d), f32),
                            pltpu.VMEM((2, hk, bk, dv), f32),
                            pltpu.VMEM((2, bk, dip), f32),
                            pltpu.VMEM((hk, d, bk), kh.dtype),
                            pltpu.SemaphoreType.DMA((2, 2, 3))],
            compiler_params=_params("arbitrary", "arbitrary", "arbitrary"),
            interpret=interpret, name="dsa_attn_bwd",
        )(qh, kh, vh, g_out.astype(qh.dtype), lse, delta, qih, ki, wi,
          _rows(wi), tau, cut, _rows(lsei), _rows(g_kl.astype(f32)))
        dk, dv_, dki = (_rounded(a, like.dtype) for a, like in (
            (dk, kh), (dv_, vh), (dki[..., :di], ki)))
    return dq, dk, dv_, dqi, dki, dwi[:, :, 0, :].transpose(0, 2, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _sparse(qh, kh, vh, qih, ki, wi, topk, bq, bk, interpret):
    out, _, _, _, _, kl, kept = _forward_kernels(
        qh, kh, vh, qih, ki, wi, topk, bq, bk, interpret)
    return out, kl, kept


def _sparse_fwd(qh, kh, vh, qih, ki, wi, topk, bq, bk, interpret):
    out, lse, tau, cut, lsei, kl, kept = _forward_kernels(
        qh, kh, vh, qih, ki, wi, topk, bq, bk, interpret)
    # what the backward needs again and a rematerialised block is not to
    # make again: the kernels' own results
    out, lse, tau, cut, lsei, kl = (
        checkpoint_name(a, REMAT_KEEP)
        for a in (out, lse, tau, cut, lsei, kl))
    return (out, kl, kept), (qh, kh, vh, qih, ki, wi, out, lse, tau, cut,
                             lsei)


def _sparse_bwd(topk, bq, bk, interpret, res, g):
    g_out, g_kl, _ = g
    return _backward_kernels(res, g_out, g_kl, bq, bk, interpret)


_sparse.defvjp(_sparse_fwd, _sparse_bwd)


# ------------------------------------------------------------ the XLA path
def index_scores(qi, ki, wi):
    """The indexer's scores of query rows qi (B, Tq, Hi, Di), wi (B, Tq,
    Hi) against ALL keys ki (B, T, Di): (B, Tq, T) float32, the heads
    summed in their order (as `index_tile` sums them)."""
    a = jnp.einsum("bqhd,bkd->bhqk", qi, ki,
                   preferred_element_type=jnp.float32)
    acc = jnp.zeros(a.shape[:1] + a.shape[2:], jnp.float32)
    for j in range(a.shape[1]):
        # the ReLU's slope at exactly 0 is 0, here as in the kernels
        acc = acc + wi[:, :, j, None].astype(jnp.float32) \
            * jnp.where(a[:, j] > 0, a[:, j], 0.0)
    return acc


def select(scores, q0, topk):
    """(tau, cut) (..., Tq, 1) of causal rows ``q0``.. of ``scores`` (...,
    Tq, T) by `lax.top_k`'s order: the value of the K-th largest score
    among the keys ``s <= t``, K = min(t + 1, topk), and the position of
    the last key admitted at that value (lower positions first). The
    scores hold no -0.0 (`index_tile`), which `lax.top_k` would order
    below +0.0."""
    tq, t = scores.shape[-2:]
    qpos = q0 + jnp.arange(tq)[:, None]
    kpos = jnp.arange(t)[None, :]
    seen = jnp.where(kpos <= qpos, scores, -jnp.inf)
    top = jax.lax.top_k(seen, min(topk, t))[0]
    want = jnp.minimum(qpos + 1, topk)
    tau = jnp.take_along_axis(
        top, jnp.broadcast_to(want - 1, top.shape[:-1] + (1,)), axis=-1)
    need = want - jnp.sum(seen > tau, axis=-1, keepdims=True)
    ties = jnp.cumsum(seen == tau, axis=-1)
    cut = jnp.argmax(ties >= need, axis=-1)[..., None].astype(jnp.int32)
    return tau, cut


def _sparse_attention_xla(q, k, v, qi, ki, wi, topk, block):
    """`sparse_attention` as plain XLA, a block of queries at a time
    against all keys; differentiated as it stands."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    scale = 1.0 / float(d) ** 0.5
    stop = jax.lax.stop_gradient
    block = min(block, t)
    pad = (-t) % block
    with jax.named_scope("dsa/attn"):
        kf = jnp.repeat(k, group, axis=2) if group > 1 else k
        vf = jnp.repeat(v, group, axis=2) if group > 1 else v
        rows = lambda a: jnp.pad(
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape(
                (b, -1, block) + a.shape[2:]).swapaxes(0, 1)

    @jax.checkpoint
    def one(q0, qb, qib, wib):
        with jax.named_scope("dsa/index"):
            s_i = index_scores(qib, ki, wib)              # (B, blk, T)
        with jax.named_scope("dsa/select"):
            qpos = q0 + jnp.arange(block)[:, None]
            kpos = jnp.arange(t)[None, :]
            tau, cut = select(stop(s_i), q0, topk)
            kept = keep_mask(stop(s_i), tau, cut, qpos, kpos)
        with jax.named_scope("dsa/attn"):
            s = scale * jnp.einsum("bqhd,bkhd->bhqk", qb, kf,
                                   preferred_element_type=jnp.float32)
            p = jax.nn.softmax(jnp.where(kept[:, None], s, NEG), axis=-1)
            p = jnp.where(kept[:, None], p, 0.0)
            out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), vf)
        with jax.named_scope("dsa/kl"):
            target = stop(jnp.mean(p, axis=1))
            log_pi = jax.nn.log_softmax(jnp.where(kept, s_i, NEG), axis=-1)
            kl = jnp.sum(target * (jnp.log(jnp.maximum(target, 1e-37))
                                   - jnp.where(kept, log_pi, 0.0)), axis=-1)
        return out, kl, jnp.sum(kept, axis=-1, dtype=jnp.int32)

    with jax.named_scope("dsa/attn"):
        starts = jnp.arange(0, t + pad, block)
        out, kl, kept = jax.lax.map(lambda a: one(*a),
                                    (starts, rows(q), rows(qi), rows(wi)))
        join = lambda a: a.swapaxes(0, 1).reshape(
            (b, -1) + a.shape[3:])[:, :t]
        return join(out), join(kl), join(kept)


# --------------------------------------------------------------- the entry
def kernel_blocks(t: int, block_k: int, heads: int, width: int):
    """(q block, k block) of the kernels at length ``t`` for ``heads``
    query heads of ``width``, or None where the tiles do not divide the
    length (the XLA path runs then)."""
    bk = min(block_k, t)
    if bk % _LANES or t % bk:
        return None
    fits = [r for r in (512, 256, 128, 64, 32, 16, 8)
            if bk % r == 0 and r * heads * width * 16 <= _Q_BLOCK_BYTES]
    return (fits[0], bk) if fits else None


def sparse_attention(q, k, v, qi, ki, wi, *, topk: int, block_k: int = 512,
                     kernels: Optional[bool] = None,
                     interpret: Optional[bool] = None):
    """Causal attention of q (B, T, H, D) on the ``topk`` keys a query's
    indexer picks among k, v (B, T, Hk, D): indexer queries qi (B, T, Hi,
    Di), its ONE key head ki (B, T, Di), its head weights wi (B, T, Hi)
    float32 (see the module's docstring). Returns ``(out (B, T, H, Dv),
    kl (B, T) float32, kept (B, T) int32)``: the indexer's divergence a
    query and the pairs it kept.

    ``kernels`` None: the Pallas kernels on a TPU where the tiles divide
    ``T`` (there an eligible call compiles or raises), plain XLA
    elsewhere; True with ``interpret`` is how the tests run them."""
    b, t, h, d = q.shape
    if k.shape[3] != d or h % k.shape[2] or v.shape[:3] != k.shape[:3]:
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}: k needs "
                         "q's head width and a head count that divides "
                         "q's, v k's length and heads")
    blocks = kernel_blocks(t, block_k, h, max(d, v.shape[3]))
    if kernels is None:
        kernels = is_tpu_backend() and blocks is not None
    if not kernels:
        return _sparse_attention_xla(q, k, v, qi, ki, wi, topk, block_k)
    if blocks is None:
        raise ValueError(f"the kernels' tiles do not divide T = {t}")
    if interpret is None:
        interpret = not is_tpu_backend()
    with jax.named_scope("dsa/attn"):
        heads_first = [_heads_first(a) for a in (q, k, v, qi)]
    out, kl, kept = _sparse(*heads_first, ki, wi.astype(jnp.float32), topk,
                            *blocks, interpret)
    with jax.named_scope("dsa/attn"):
        return _heads_first(out), kl[..., 0], kept[..., 0]


def pairs_causal(t: int) -> int:
    """Pairs (t, s <= t) of one sequence."""
    return t * (t + 1) // 2


def pairs_selected(t: int, topk: int) -> int:
    """Pairs an exact selection keeps of one sequence: sum_t min(t + 1,
    topk)."""
    full = min(t, topk)
    return full * (full + 1) // 2 + (t - full) * topk

