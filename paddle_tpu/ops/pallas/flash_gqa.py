"""Causal flash attention at d_head 64, head-major and grouped-query:
forward and backward kernels that block heads in PAIRS.

Operands stay as the projections emit them, q (N, T, H*64) and k, v
(N, T, Hkv*64), H a multiple of Hkv: nothing is transposed at the
kernel boundary and k, v are never repeated in HBM.  One head's 64
lanes are half a lane tile, which Mosaic does not take as a block
(`flash_attention.py`, layout "nthd", refused this width), so every
block is a whole 128-lane tile holding TWO heads side by side:

- a q / o / do / dq tile is query heads (2p, 2p+1), a k / v / dk / dv
  tile is key/value heads (2c, 2c+1);
- a tile meets another in a matmul that contracts or emits all 128
  lanes, with the lanes of the head that does not take part zeroed
  first: `[q0 | q1] . [k | 0]^T` is `q0 k^T`, and `p0 [v | 0] + p1
  [0 | v]` is `[o0 | o1]`.  On a 128-wide MXU a 64-deep contraction
  leaves half the array idle anyway, so the zeros cost no pass;
- the key/value head a query pair reads sits in the left or the right
  half of its tile (G = H / Hkv query heads read one key/value head;
  G is 1 or even, so both heads of a pair read the same tile): the
  half is copied to both halves with one lane rotation by 64
  (`_both_halves`), and the lane masks above do the rest.  No value is
  ever sliced at a lane offset that is not a tile.

Forward: grid (N*Hkv, q blocks, k blocks): a step is ONE key/value head
and the G/2 query pairs that read it (a q / o tile G/2 lane tiles wide;
at G = 1 a step is one pair, each head reading its own), so a key/value
tile is fetched, and its head spread over both halves, once for all of
them.  q is scaled (a power of two rides on it exactly; any other scale
multiplies the scores) and masked a head, `[q0 | 0]` and `[0 | q1]`,
once a query tile into scratch, and meets `[k | k]` and `[v | v]`
whole: no lane mask on a key or value tile, no multiply a score.  Tiles
are 1024 x 1024 where T is a whole number of them, else 512 x 512
(`default_blocks`: from the shape alone, for both passes; at 8192 rows
4.7 ms a call where 512 x 512 tiles and a step a pair took 7.8: PR 56,
`tools/time_kernel.py flash_gqa --sweep`).  Blocks above the diagonal are
skipped and their DMA with them (the index maps clamp to the last block
that is needed); only the blocks the diagonal crosses build and apply
the causal mask.  The soft-max statistics are the (N*H, 8, T)
sublane-replicated form of `flash_attention.py`.

The backward pass is ONE kernel where the sequence allows it, grid
(N*Hkv/2, G query tiles, k blocks, q blocks): s, p, dp and ds once a
block pair and dq, dk and dv from them (five score-sized matmuls a
head where two kernels run seven; the soft-max's vector work once).
dq sums over the KEY blocks and dk / dv over the query blocks AND the G
query tiles that read a key/value tile, which no grid order visits
consecutively for both.  So the query tile lies OUTSIDE the key blocks
(as `flash_mla.py`'s head pair does) and every sum is held full-length
in float32 VMEM scratch: dq of one query tile (T, 128), each block
leaving for HBM on the step that completes it (its last key block, the
diagonal's; the output's index map moves on only then, so Pallas never
writes a half-summed block back and no partial of dq reaches HBM), and
dk, dv of the key/value tile (T, 128) each, written during the tile's
last query tile, Hkv heads wide, once.  With the tile outside, a dq
block's index stays put from the step that wrote it to the step that
writes the next: inside the key blocks it would come back after other
tiles' blocks had used the buffer, and a block would have to be
written again from its sum on every pass.  That is three float32 tiles,
1.5 KiB, a position whatever G is (12 MiB at 8192): the shape alone
chooses (`fused_backward_fits`: T * 1.5 KiB within
`FUSED_ACCUMULATOR_BUDGET`; no option, attribute or environment
variable), and a longer sequence takes the two kernels that hold blocks
only, dk/dv on the grid (N*Hkv/2, k blocks, G query tiles, q blocks)
and dq on the forward's, each recomputing the scores.  Both paths add
the same terms in the same order: the same bits out.  The single kernel
is passed to `pallas_call` under the name `flash_gqa_dkv`: it is that
kernel grown by dq's dot, and the benchmark's closed list of kernels
(`benchmarks/kernel_counts_lfm2.py`) knows that name; `flash_gqa_dq`
exists on the two-kernel path only.  `observe.monitoring` counts the
backward passes traced as `flash_gqa_backward_fused` / `_split`.

At d_head 64 every score-sized matmul has a 64 on its contraction or
its output side and is a WHOLE 128-lane MXU pass, so the passes the MXU
executes are twice the dense count: 2 forward, 5 backward a head (7 on
the two kernels).

Self-attention, causal, no bias, T a whole number of blocks: what a
decoder layer asks.  `flash_attention.py pallas_flash_attention`
sends head-major calls at d_head 64 here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import keep_residuals
from .flash_attention import (_SOFTMAX_BWD_PER_SCORE, _SOFTMAX_FWD_PER_SCORE,
                              _io_bytes)

HEAD_DIM = 64
LANES = 128
# a side of either pass's square tile (the statistics are block-free, so
# the passes need not agree, but the same rule serves both): the first
# where T is a whole number of them, the second elsewhere
DEFAULT_BLOCK = 1024
FALLBACK_BLOCK = 512
NEG_INF = -1e30
# what a kernel may claim of v5e's 128 MiB of VMEM (two heads' blocks of
# float32 scores and the single backward kernel's accumulators pass
# Mosaic's default 16 MiB); Mosaic's verdict:
# tests/test_chip_compile_flash_attention.py
_VMEM_LIMIT = 100 << 20
# The single backward kernel holds float32 sums of a whole sequence: dq
# of one query tile, dk and dv of its key/value tile, 3 x 128 lanes =
# 1.5 KiB a position.  They may take this much; a longer sequence goes
# to the two kernels, which hold blocks only
FUSED_ACCUMULATOR_BUDGET = 32 << 20


def default_blocks(t):
    """(block_q, block_k) of a sequence of `t` positions, for the
    forward and the backward pass: from the shape alone, never from an
    option."""
    whole = t % min(DEFAULT_BLOCK, t) == 0
    return (DEFAULT_BLOCK if whole else FALLBACK_BLOCK,) * 2


def fused_backward_fits(t):
    """Whether the backward pass of a sequence of `t` positions is the
    single kernel: from the shape alone, never from an option."""
    return t * 4 * 3 * LANES <= FUSED_ACCUMULATOR_BUDGET


# -- kernel cost registry: dense-equivalent, as flash_attention.py ----------

def _scores(operand_shapes):
    (n, t, hd), _ = operand_shapes[0]
    return n * (hd // HEAD_DIM) * t * t


def _fwd_cost(operand_shapes, result_shapes):
    flops = _scores(operand_shapes) * (4.0 * HEAD_DIM
                                       + _SOFTMAX_FWD_PER_SCORE)
    return flops, _io_bytes(operand_shapes, result_shapes)


def _dq_flops(operand_shapes):
    return _scores(operand_shapes) * (2.0 * HEAD_DIM
                                      + 0.375 * _SOFTMAX_BWD_PER_SCORE)


def _dkv_cost(operand_shapes, result_shapes):
    # dk, dv and the shared dp dot, as flash_attention.py splits them;
    # the kernel of this name that emits dq too (the single backward
    # kernel) does dq's work as well
    flops = _scores(operand_shapes) * (6.0 * HEAD_DIM
                                       + 0.625 * _SOFTMAX_BWD_PER_SCORE)
    if len(result_shapes) == 3:
        flops += _dq_flops(operand_shapes)
    return flops, _io_bytes(operand_shapes, result_shapes)


def _dq_cost(operand_shapes, result_shapes):
    return _dq_flops(operand_shapes), _io_bytes(operand_shapes, result_shapes)


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("flash_gqa_fwd", _fwd_cost)
    register_kernel_cost("flash_gqa_dkv", _dkv_cost)
    register_kernel_cost("flash_gqa_dq", _dq_cost)


_register_costs()


def _pallas_call(*args, **kw):
    from jax.experimental.pallas import tpu as pltpu

    from . import pallas_call

    return pallas_call(
        *args, compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT), **kw)


# -- what the kernels share -------------------------------------------------

def _left(rows):
    """(rows, 128) bool: the lanes of a tile's first head."""
    return jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1) < HEAD_DIM


def _swap_halves(x):
    """`x` (rows, 128) with its two heads' lanes exchanged.  Mosaic
    rotates 32-bit lanes only, so a bfloat16 tile goes through
    float32 (exact)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x.astype(jnp.float32), HEAD_DIM, 1).astype(x.dtype)


def _both_halves(x, half):
    """`x` (rows, 128) holds two key/value heads; return the tile with
    head `half` (0 or 1, a traced scalar) in BOTH halves.  `half` None:
    the pair of query heads reads one head each (H == Hkv), and the
    tile is right as it lies."""
    if half is None:
        return x
    lane_half = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) // HEAD_DIM
    kept = jnp.where(lane_half == half, x, jnp.zeros_like(x))
    return kept + _swap_halves(kept)


def _of_head(x, j):
    """`x` with the lanes of head `1 - j` zeroed."""
    left = _left(x.shape[0])
    return jnp.where(left if j == 0 else ~left, x, jnp.zeros_like(x))


def _per_head(a, b):
    """(rows, 128): head 0's lanes from `a`, head 1's from `b`; each
    (rows, 128) or (rows, 1)."""
    rows = max(a.shape[0], b.shape[0])
    return jnp.where(_left(rows), a, b)


def _causal(rows_at, cols_at, shape, rows_are_q):
    """bool `shape`: query position >= key position.  The scores are
    (q, k) forward and (k, q) backward."""
    r = rows_at + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = cols_at + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return r >= c if rows_are_q else c >= r


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                               preferred_element_type=jnp.float32)


# -- forward ----------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, q_scr, m_scr, l_scr,
                acc_scr, *, scale, block_q, block_k, half_of):
    """One key/value head against the `pairs` query pairs that read it:
    q / o tiles (block_q, pairs * 128), the statistics of their 2 *
    pairs heads.  `q_scr` (2 * pairs, block_q, 128) holds each head's q
    scaled, the other head's lanes zeroed, for the query tile's whole
    row of key blocks."""
    from jax.experimental import pallas as pl

    qb, kb = pl.program_id(1), pl.program_id(2)
    half = half_of(pl.program_id(0))
    pairs = acc_scr.shape[0]
    # a power of two rides on q exactly; any other scale on the scores
    on_q, on_s = (scale, 1.0) if math.frexp(scale)[0] == 0.5 else (1.0, scale)

    def lanes(i):
        return pl.ds(i * LANES, LANES)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        for i in range(pairs):
            q = q_ref[0, :, lanes(i)]
            if on_q != 1.0:
                q = q * jnp.asarray(on_q, q.dtype)
            for j in (0, 1):
                q_scr[2 * i + j] = _of_head(q, j)

    def _compute(masked):
        k = _both_halves(k_ref[0], half)
        v = _both_halves(v_ref[0], half)
        if masked:
            keep = _causal(qb * block_q, kb * block_k, (block_q, block_k),
                           True)
        for i in range(pairs):
            alpha, pv = [], []
            for h in (2 * i, 2 * i + 1):
                s = _dot(q_scr[h], k, ((1,), (1,)))
                if on_s != 1.0:
                    s = s * on_s
                if masked:
                    s = jnp.where(keep, s, NEG_INF)
                m_prev = m_scr[h]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                a = jnp.exp(m_prev - m_new)
                l_scr[h] = a * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
                m_scr[h] = m_new
                alpha.append(a)
                # [o | o] of the head: its half is taken below
                pv.append(_dot(p.astype(v.dtype), v, ((1,), (0,))))
            acc_scr[i] = (acc_scr[i] * _per_head(*alpha) + _per_head(*pv))

    # a block that holds a score; one the diagonal crosses (its last key
    # lies past its first query) masks, one below it need not
    runs = (qb + 1) * block_q > kb * block_k
    crosses = (kb + 1) * block_k - 1 > qb * block_q
    pl.when(runs & crosses)(functools.partial(_compute, True))
    pl.when(runs & jnp.logical_not(crosses))(
        functools.partial(_compute, False))

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        for i in range(pairs):
            o_ref[0, :, lanes(i)] = (
                acc_scr[i] / _per_head(l_scr[2 * i], l_scr[2 * i + 1])
            ).astype(o_ref.dtype)
            for h in (2 * i, 2 * i + 1):
                lse = (m_scr[h] + jnp.log(l_scr[h]))[:, 0]
                lse_ref[h] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


# -- backward ---------------------------------------------------------------
#
# p = exp(s - lse), dv = p^T do, dp = do v^T, ds = p (dp - delta) with
# delta = rowsum(do o), dq = scale ds k, dk = scale ds^T q.  Scores are
# held (k, q) so that lse and delta broadcast along lanes, as in
# flash_attention.py.

def _p_ds(q, k, v, do, o, lse_ref, scale, keep):
    """Per head of the pair: (p, ds), each (block_k, block_q) float32.
    `k`, `v` hold the pair's key/value head in both halves."""
    dd = do.astype(jnp.float32) * o.astype(jnp.float32)
    out = []
    for j in (0, 1):
        s = _dot(_of_head(k, j), q, ((1,), (1,))) * scale
        p = jnp.where(keep, jnp.exp(s - lse_ref[j, 0][None, :]), 0.0)
        dp = _dot(_of_head(v, j), do, ((1,), (1,)))
        delta = jnp.sum(_of_head(dd, j), axis=1)[None, :]
        out.append((p, p * (dp - delta)))
    return out


def _add_dk_dv(p_ds, q, do, half, dk_scr, dv_scr, scale):
    """The block pair's part of dk and dv into their float32 sums;
    `p_ds` as `_p_ds` gives it."""
    (p0, ds0), (p1, ds1) = p_ds
    dv = _per_head(_dot(p0.astype(do.dtype), do, ((1,), (0,))),
                   _dot(p1.astype(do.dtype), do, ((1,), (0,))))
    dk = _per_head(_dot(ds0.astype(q.dtype), q, ((1,), (0,))),
                   _dot(ds1.astype(q.dtype), q, ((1,), (0,)))) * scale
    if half is not None:
        # both query heads read head `half`: their sum, in its lanes
        lane_half = jax.lax.broadcasted_iota(
            jnp.int32, dv.shape, 1) // HEAD_DIM
        dv = jnp.where(lane_half == half, dv + _swap_halves(dv), 0.0)
        dk = jnp.where(lane_half == half, dk + _swap_halves(dk), 0.0)
    dv_scr[:] += dv
    dk_scr[:] += dk


def _add_dq(p_ds, k, dq_scr, scale):
    """The block pair's part of dq into its float32 sum: dq[q, d] =
    scale * sum_k ds[k, q] k[k, d], in the head's lanes.  `k` holds the
    pair's key/value head in both halves."""
    (_, ds0), (_, ds1) = p_ds
    dq_scr[:] += scale * (
        _dot(ds0.astype(k.dtype), _of_head(k, 0), ((0,), (0,)))
        + _dot(ds1.astype(k.dtype), _of_head(k, 1), ((0,), (0,))))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, block_q, block_k, halves):
    from jax.experimental import pallas as pl

    kb, r, qb = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    first = (r == 0) & (qb == 0)
    last = (r == pl.num_programs(2) - 1) & (qb == pl.num_programs(3) - 1)
    # the G query tiles of this key/value pair: the first half of them
    # read its first head, the rest its second
    half = None if halves is None else r // halves

    @pl.when(first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when((qb + 1) * block_q > kb * block_k)
    def _compute():
        q, do = q_ref[0], do_ref[0]
        keep = _causal(kb * block_k, qb * block_q, (block_k, block_q), False)
        p_ds = _p_ds(
            q, _both_halves(k_ref[0], half), _both_halves(v_ref[0], half),
            do, o_ref[0], lse_ref, scale, keep)
        _add_dk_dv(p_ds, q, do, half, dk_scr, dv_scr, scale)

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, dq_scr,
               *, scale, block_q, block_k, half_of):
    from jax.experimental import pallas as pl

    qb, kb = pl.program_id(1), pl.program_id(2)
    half = half_of(pl.program_id(0))

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when((qb + 1) * block_q > kb * block_k)
    def _compute():
        k = _both_halves(k_ref[0], half)
        keep = _causal(kb * block_k, qb * block_q, (block_k, block_q), False)
        p_ds = _p_ds(
            q_ref[0], k, _both_halves(v_ref[0], half), do_ref[0], o_ref[0],
            lse_ref, scale, keep)
        _add_dq(p_ds, k, dq_scr, scale)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, dk_ref,
                dv_ref, dq_acc, dk_acc, dv_acc, *, scale, block_q, block_k,
                halves, last_k):
    """The whole backward pass, grid (N*Hkv/2, query tile, kb, qb): p
    and ds once a block pair, dq, dk and dv from them, each added as
    the kernel that holds blocks only adds it.  dq sums over the key
    blocks in `dq_acc` (nq, block_q, 128), the tile's whole sequence,
    each block leaving for HBM on the step that completes it, the
    diagonal's (`last_k`); dk and dv sum over the query blocks and the
    query tiles (the OUTER axis here) in `dk_acc`, `dv_acc` (nk,
    block_k, 128), written during the last tile."""
    from jax.experimental import pallas as pl

    r, kb, qb = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    half = None if halves is None else r // halves

    @pl.when((r == 0) & (qb == 0))
    def _init():
        dk_acc[kb] = jnp.zeros(dk_acc.shape[1:], dk_acc.dtype)
        dv_acc[kb] = jnp.zeros(dv_acc.shape[1:], dv_acc.dtype)

    @pl.when(kb == 0)       # every query block meets key block 0, first
    def _init_dq():
        dq_acc[qb] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

    @pl.when((qb + 1) * block_q > kb * block_k)
    def _compute():
        q, do = q_ref[0], do_ref[0]
        k = _both_halves(k_ref[0], half)
        keep = _causal(kb * block_k, qb * block_q, (block_k, block_q), False)
        p_ds = _p_ds(q, k, _both_halves(v_ref[0], half), do, o_ref[0],
                     lse_ref, scale, keep)
        _add_dk_dv(p_ds, q, do, half, dk_acc.at[kb], dv_acc.at[kb], scale)
        _add_dq(p_ds, k, dq_acc.at[qb], scale)

    @pl.when((r == pl.num_programs(1) - 1) & (qb == pl.num_programs(3) - 1))
    def _finalize():
        dk_ref[0] = dk_acc[kb].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[kb].astype(dv_ref.dtype)

    @pl.when(kb == last_k(qb))
    def _finalize_dq():
        dq_ref[0] = dq_acc[qb].astype(dq_ref.dtype)


# -- geometry and the calls -------------------------------------------------

class _Geometry:
    """Where the tiles of a (N, T, H*64 | Hkv*64) call lie."""

    def __init__(self, q, k, n_head, n_kv_head, block_q, block_k):
        n, t, hd = q.shape
        group = n_head // max(n_kv_head, 1)
        if (hd != n_head * HEAD_DIM or k.shape != (n, t, n_kv_head * HEAD_DIM)
                or group * n_kv_head != n_head):
            raise ValueError(
                f"flash_gqa: q {q.shape} / k {k.shape} are not {n_head} / "
                f"{n_kv_head} heads of {HEAD_DIM} over one sequence")
        if n_kv_head % 2 or (group > 1 and group % 2):
            raise NotImplementedError(
                f"flash_gqa blocks heads in pairs: {n_kv_head} key/value "
                f"heads must be even, and {group} query heads a key/value "
                f"head 1 or even")
        self.n, self.t, self.group = n, t, group
        self.q_pairs, self.kv_pairs = n_head // 2, n_kv_head // 2
        self.tiles = group              # query tiles a key/value tile
        # query pairs a key/value HEAD: what a forward grid step holds
        self.head_pairs = max(group // 2, 1)
        # the first `halves` of them read the tile's first head, the
        # rest its second; None: each head of a pair reads its own
        self.halves = None if group == 1 else group // 2
        self.block_q, self.block_k = min(block_q, t), min(block_k, t)
        if t % self.block_q or t % self.block_k:
            raise ValueError(f"flash_gqa: T {t} is not a whole number of "
                             f"{self.block_q} / {self.block_k} blocks")
        self.nq, self.nk = t // self.block_q, t // self.block_k

    def kv_half(self, g, pairs=1):
        """Which half of its key/value tile the `pairs` query pairs of
        step `g` (of the grid's N*H/2 / pairs) read; None when each head
        reads its own."""
        if self.group == 1:
            return None
        return ((g * pairs % self.q_pairs) // (self.group // 2)) % 2

    def last_k(self, qb):
        return ((qb + 1) * self.block_q - 1) // self.block_k

    def first_q(self, kb):
        return (kb * self.block_k) // self.block_q

    def by_query_block(self, pairs=1):
        """Specs of a grid (N*H/2 / pairs, qb, kb): the q-side tile of
        step g's `pairs` query pairs (neighbours that read one
        key/value tile), that tile, the pairs' statistics."""
        from jax.experimental import pallas as pl

        steps, grp = self.q_pairs // pairs, self.group
        return {"q": pl.BlockSpec((1, self.block_q, pairs * LANES),
                                  lambda g, a, b: (g // steps, a, g % steps)),
                "kv": pl.BlockSpec(
                    (1, self.block_k, LANES),
                    lambda g, a, b: (g // steps,
                                     jnp.minimum(b, self.last_k(a)),
                                     (g % steps) * pairs // grp)),
                "stat": pl.BlockSpec((2 * pairs, 8, self.block_q),
                                     lambda g, a, b: (g, 0, a))}

    def by_key_block(self, tile_outside=False):
        """Specs of the grid (N*Hkv/2, kb, query tile, qb), or of
        (N*Hkv/2, query tile, kb, qb) with the tile outside the key
        blocks (the single backward kernel's, which adds the specs of
        its dq and dk / dv blocks)."""
        from jax.experimental import pallas as pl

        qp, kvp, tiles = self.q_pairs, self.kv_pairs, self.tiles
        bq, bk = self.block_q, self.block_k

        def spec(shape, at):
            """`at(g, kb, r, qb)` in the grid's own order."""
            if tile_outside:
                return pl.BlockSpec(shape,
                                    lambda g, r, kb, qb: at(g, kb, r, qb))
            return pl.BlockSpec(shape, at)

        def q_time(kb, qb):
            return jnp.maximum(qb, self.first_q(kb))

        def tile(g, r):
            return (g % kvp) * tiles + r

        def dq_at(g, kb, r, qb):
            # the query block last completed, or being completed: the
            # blocks before first_q(kb) met their last key block in an
            # earlier pass, those before first_q(kb + 1) meet it in
            # this one.  The index moves on only on the step that
            # writes the next block, so no half-summed block is ever
            # what Pallas writes back
            done = jnp.minimum(jnp.maximum(qb, self.first_q(kb) - 1),
                               self.first_q(kb + 1) - 1)
            return (g // kvp, jnp.maximum(done, 0), tile(g, r))

        def dkv_at(g, kb, r, qb):
            # written during the last tile only; block 0 waits till then
            return (g // kvp, jnp.where(r == tiles - 1, kb, 0), g % kvp)

        return {"q": spec((1, bq, LANES),
                          lambda g, kb, r, qb: (g // kvp, q_time(kb, qb),
                                                tile(g, r))),
                "kv": spec((1, bk, LANES),
                           lambda g, kb, r, qb: (g // kvp, kb, g % kvp)),
                "stat": spec((2, 8, bq),
                             lambda g, kb, r, qb: (
                                 (g // kvp) * qp + tile(g, r), 0,
                                 q_time(kb, qb))),
                "dq": spec((1, bq, LANES), dq_at),
                "dkv": spec((1, bk, LANES), dkv_at)}


def _stat_shape(geo):
    return jax.ShapeDtypeStruct((geo.n * geo.q_pairs * 2, 8, geo.t),
                                jnp.float32)


def _flash_fwd(q, k, v, scale, geo):
    from jax.experimental.pallas import tpu as pltpu

    bq, bk, pairs = geo.block_q, geo.block_k, geo.head_pairs
    s = geo.by_query_block(pairs)
    kern = functools.partial(
        _fwd_kernel, scale=scale, block_q=bq, block_k=bk,
        half_of=functools.partial(geo.kv_half, pairs=pairs))
    return _pallas_call(
        kern, name="flash_gqa_fwd",
        grid=(geo.n * geo.q_pairs // pairs, geo.nq, geo.nk),
        in_specs=[s["q"], s["kv"], s["kv"]],
        out_specs=[s["q"], s["stat"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), _stat_shape(geo)],
        scratch_shapes=[pltpu.VMEM((2 * pairs, bq, LANES), q.dtype),
                        pltpu.VMEM((2 * pairs, bq, 1), jnp.float32),
                        pltpu.VMEM((2 * pairs, bq, 1), jnp.float32),
                        pltpu.VMEM((pairs, bq, LANES), jnp.float32)],
    )(q, k, v)


def _flash_bwd_split(q, k, v, o, lse8, do, scale, geo):
    """dk / dv and dq by a kernel each: every score twice."""
    from jax.experimental.pallas import tpu as pltpu

    bq, bk = geo.block_q, geo.block_k
    s = geo.by_key_block()
    dkv = functools.partial(_dkv_kernel, scale=scale, block_q=bq, block_k=bk,
                            halves=geo.halves)
    dk, dv = _pallas_call(
        dkv, name="flash_gqa_dkv",
        grid=(geo.n * geo.kv_pairs, geo.nk, geo.tiles, geo.nq),
        in_specs=[s["q"], s["kv"], s["kv"], s["q"], s["q"], s["stat"]],
        out_specs=[s["kv"], s["kv"]],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bk, LANES), jnp.float32)] * 2,
    )(q, k, v, do, o, lse8)

    s = geo.by_query_block()
    dqk = functools.partial(_dq_kernel, scale=scale, block_q=bq, block_k=bk,
                            half_of=geo.kv_half)
    dq = _pallas_call(
        dqk, name="flash_gqa_dq",
        grid=(geo.n * geo.q_pairs, geo.nq, geo.nk),
        in_specs=[s["q"], s["kv"], s["kv"], s["q"], s["q"], s["stat"]],
        out_specs=s["q"],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32)],
    )(q, k, v, do, o, lse8)
    return dq, dk, dv


def _flash_bwd_fused(q, k, v, o, lse8, do, scale, geo):
    """dq, dk and dv by ONE kernel, named `flash_gqa_dkv`: it is that
    kernel grown by dq's dot, and the name is the one the benchmark's
    closed list of kernels knows."""
    from jax.experimental.pallas import tpu as pltpu

    bq, bk = geo.block_q, geo.block_k
    s = geo.by_key_block(tile_outside=True)
    kern = functools.partial(_bwd_kernel, scale=scale, block_q=bq, block_k=bk,
                             halves=geo.halves, last_k=geo.last_k)
    return _pallas_call(
        kern, name="flash_gqa_dkv",
        grid=(geo.n * geo.kv_pairs, geo.tiles, geo.nk, geo.nq),
        in_specs=[s["q"], s["kv"], s["kv"], s["q"], s["q"], s["stat"]],
        out_specs=[s["dq"], s["dkv"], s["dkv"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((geo.nq, bq, LANES), jnp.float32),
                        pltpu.VMEM((geo.nk, bk, LANES), jnp.float32),
                        pltpu.VMEM((geo.nk, bk, LANES), jnp.float32)],
    )(q, k, v, do, o, lse8)


def _flash_bwd(q, k, v, o, lse8, do, scale, geo):
    from ...observe.monitoring import runtime_stats

    fused = fused_backward_fits(geo.t)
    runtime_stats.record_flash_backward("flash_gqa", fused)
    bwd = _flash_bwd_fused if fused else _flash_bwd_split
    return bwd(q, k, v, o, lse8, do, scale, geo)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, n_head, n_kv_head, blocks):
    return _flash_vjp_fwd(q, k, v, scale, n_head, n_kv_head, blocks)[0]


def _flash_vjp_fwd(q, k, v, scale, n_head, n_kv_head, blocks):
    geo = _Geometry(q, k, n_head, n_kv_head, *blocks)
    o, lse8 = keep_residuals(*_flash_fwd(q, k, v, scale, geo))
    return o, (q, k, v, o, lse8)


def _flash_vjp_bwd(scale, n_head, n_kv_head, blocks, res, do):
    q, k, v, o, lse8 = res
    geo = _Geometry(q, k, n_head, n_kv_head, *blocks)
    return _flash_bwd(q, k, v, o, lse8, do, scale, geo)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_gqa(q, k, v, n_head, n_kv_head, scale=None, block_q=None,
              block_k=None):
    """Causal self-attention of q (N, T, n_head*64) over k, v
    (N, T, n_kv_head*64): query head j reads key/value head
    j // (n_head / n_kv_head).  Returns (N, T, n_head*64).  A block
    size given holds for both passes; left out, the sequence's length
    chooses it (`default_blocks`)."""
    if scale is None:
        scale = HEAD_DIM ** -0.5
    blocks = tuple(int(given or own) for given, own in zip(
        (block_q, block_k), default_blocks(q.shape[1])))
    return _flash(q, k, v, float(scale), int(n_head), int(n_kv_head), blocks)
