"""Causal flash attention over a low-rank latent's heads: a score
contracts 192 lanes, 128 unrotated and 64 rotary, a value has 128, and
the rotary key is ONE head that every query head reads.

Operands stay as the projections emit them, head-major and unpadded:

    q_nope (N, T, H*128)   k_nope (N, T, H*128)   v (N, T, H*128)
    q_rope (N, T, H*64)    k_rope (N, T, 64)

    s_ij = (q_nope_i . k_nope_j + q_rope_i . k_rope_j) * scale
    o    = causal_softmax(s) v                      (N, T, H*128)

Nothing is transposed at the kernel boundary, the rotary key is never
repeated over the heads in HBM, and v is never padded to the score's
width.  A head's 64 rotary lanes are half a lane tile, which Mosaic
does not take as a block (`flash_gqa.py`), so every grid step works on
a PAIR of heads: a q_nope / k_nope / v / o block is two whole tiles
(one a head), a q_rope block is one tile holding the pair's rotary
parts side by side, and the rotary key's block is the whole 64-lane
minor dim.  The key is copied into both halves of a tile once a block
(`_twice`), and the rotary score of head j is a 128-deep contraction
with the other head's lanes zeroed (a 64-deep one leaves half the MXU
idle anyway).  The rotary key's gradient is summed over all H heads in
VMEM: head j's part lands in half j of a tile, the tile is accumulated
over the pairs and the query blocks, and the halves are folded once at
the end (`_fold`).

Blocks above the diagonal are skipped and their DMA with them; the mask
is applied on the blocks the diagonal crosses only.  The soft-max
statistics are the (N*H, 8, T) sublane-replicated form of
`flash_attention.py`.  Forward: grid (N*H/2, q blocks, k blocks).

The backward pass is ONE kernel where the sequence allows it, grid
(N, H/2 pairs, k blocks, q blocks): s, p, dp and ds once a block and
all five gradients from them.  dk_nope / dv sum over the query blocks,
the inner axis, in block scratch, written once a (pair, key block).
dq sums over the KEY blocks, which no grid order visits consecutively
beside dk's, so it is held full-length in VMEM: a float32 accumulator
of the pair's whole sequence, (T, 256) + (T, 128), each block leaving
for HBM on the step that completes it (its last key block, the
diagonal's; the output's index map moves on only then, so Pallas never
writes a half-summed block back).  The rotary key's gradient sums over
the pairs too, the axis outside the key blocks, so it is a full-length
(T, 128) float32 scratch as well, folded and written during the last
pair.  That is 2 KiB of accumulators a position (16.8 MB at 8192): the
shape alone chooses (`fused_backward_fits`: T * 2 KiB within
`FUSED_ACCUMULATOR_BUDGET`; no option, attribute or environment
variable), and a longer sequence takes the two kernels that hold blocks
only, dk/dv on the grid (N, k blocks, pairs, q blocks) and dq on the
forward's, each recomputing the scores.  The single kernel is passed to
`pallas_call` under the name `flash_mla_dkv`: it is that kernel grown
by dq's two dots, and the benchmark's closed list of kernels
(`benchmarks/kernel_counts_joyai.py`) knows that name; `flash_mla_dq`
exists on the two-kernel path only.  `observe.monitoring` counts the
backward passes traced as `flash_mla_backward_fused` / `_split`.

Matmul lanes a score pair, dense-equivalent: 320 forward + 832
backward (two kernels: 320 + 640 + 512, s and dp twice).  What the MXU
passes is more, because a 64-lane rotary operand is a whole 128-lane
pass: 384 + 1024 (two kernels: 384 + 768 + 640).

Self-attention, causal, no bias, T a whole number of blocks, H even.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import keep_residuals
from .flash_attention import (_SOFTMAX_BWD_PER_SCORE, _SOFTMAX_FWD_PER_SCORE,
                              _io_bytes)
from .flash_gqa import NEG_INF, _causal, _dot, _of_head

NOPE_DIM = 128          # unrotated lanes of a score, and a value's width
ROPE_DIM = 64           # rotary lanes of a score
QK_DIM = NOPE_DIM + ROPE_DIM
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# what a kernel may claim of v5e's 128 MiB of VMEM; the verdict is
# Mosaic's (tests/test_chip_compile_kernels.py)
_VMEM_LIMIT = 100 << 20
# The single backward kernel holds float32 accumulators of a pair's
# whole sequence: dq_nope 256 + dq_rope 128 + dk_rope 128 lanes, 2 KiB a
# position.  They may take this much; a longer sequence goes to the two
# kernels, which hold blocks only
FUSED_ACCUMULATOR_BUDGET = 32 << 20


def flash_mla_takes(nope, rope, value):
    """Whether the kernels run heads of `nope` + `rope` score lanes and
    `value` value lanes: from the shape alone."""
    return (nope, rope, value) == (NOPE_DIM, ROPE_DIM, NOPE_DIM)


def fused_backward_fits(t):
    """Whether the backward pass of a sequence of `t` positions is the
    single kernel: from the shape alone, never from an option."""
    return t * 4 * (2 * NOPE_DIM + 4 * ROPE_DIM) <= FUSED_ACCUMULATOR_BUDGET


# -- kernel cost registry: dense-equivalent, as flash_attention.py ----------

def _scores(operand_shapes):
    (n, t, hd), _ = operand_shapes[0]
    return n * (hd // NOPE_DIM) * t * t


def _fwd_cost(operand_shapes, result_shapes):
    flops = _scores(operand_shapes) * (2.0 * QK_DIM + 2.0 * NOPE_DIM
                                       + _SOFTMAX_FWD_PER_SCORE)
    return flops, _io_bytes(operand_shapes, result_shapes)


def _dq_flops(operand_shapes):
    return _scores(operand_shapes) * (2.0 * QK_DIM
                                      + 0.375 * _SOFTMAX_BWD_PER_SCORE)


def _dkv_cost(operand_shapes, result_shapes):
    # dk, dv and the shared dp dot, as flash_attention.py splits them;
    # the kernel of this name that emits all five gradients (the
    # single backward kernel) does dq's work too
    flops = _scores(operand_shapes) * (2.0 * QK_DIM + 4.0 * NOPE_DIM
                                       + 0.625 * _SOFTMAX_BWD_PER_SCORE)
    if len(result_shapes) == 5:
        flops += _dq_flops(operand_shapes)
    return flops, _io_bytes(operand_shapes, result_shapes)


def _dq_cost(operand_shapes, result_shapes):
    return _dq_flops(operand_shapes), _io_bytes(operand_shapes, result_shapes)


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("flash_mla_fwd", _fwd_cost)
    register_kernel_cost("flash_mla_dkv", _dkv_cost)
    register_kernel_cost("flash_mla_dq", _dq_cost)


_register_costs()


def _pallas_call(*args, **kw):
    from jax.experimental.pallas import tpu as pltpu

    from . import pallas_call

    return pallas_call(
        *args, compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT), **kw)


# -- what the three kernels share -------------------------------------------

def _twice(kr):
    """The rotary key block (rows, 64) in both halves of a tile."""
    return jnp.concatenate([kr, kr], axis=1)


def _fold(tile):
    """(rows, 128) -> (rows, 64): the sum of a tile's two halves."""
    return tile[:, :ROPE_DIM] + tile[:, ROPE_DIM:]


def _head(j):
    """The lanes of head `j` of a pair in a two-tile block."""
    from jax.experimental import pallas as pl

    return pl.ds(j * NOPE_DIM, NOPE_DIM)


def _on_needed_blocks(qb, kb, block_q, block_k, body):
    """Run `body(masked)` where the (qb, kb) block holds a score at or
    below the diagonal: with the mask where the diagonal crosses it,
    without where it lies wholly below."""
    from jax.experimental import pallas as pl

    needed = (qb + 1) * block_q > kb * block_k
    crossed = qb * block_q < (kb + 1) * block_k - 1

    @pl.when(needed & crossed)
    def _masked():
        body(True)

    @pl.when(needed & ~crossed)
    def _whole():
        body(False)


# -- forward ----------------------------------------------------------------

def _fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, block_q, block_k):
    from jax.experimental import pallas as pl

    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute(masked):
        qr = qr_ref[0]
        k2 = _twice(kr_ref[0])
        keep = masked and _causal(qb * block_q, kb * block_k,
                                  (block_q, block_k), True)
        for j in (0, 1):
            lanes = _head(j)
            s = (_dot(qn_ref[0, :, lanes], kn_ref[0, :, lanes], ((1,), (1,)))
                 + _dot(_of_head(qr, j), k2, ((1,), (1,)))) * scale
            if masked:
                s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            a = jnp.exp(m_prev - m_new)
            l_scr[j] = a * l_scr[j] + jnp.sum(p, axis=1, keepdims=True)
            m_scr[j] = m_new
            v = v_ref[0, :, lanes]
            acc_scr[:, lanes] = (acc_scr[:, lanes] * a
                                 + _dot(p.astype(v.dtype), v, ((1,), (0,))))

    _on_needed_blocks(qb, kb, block_q, block_k, compute)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        for j in (0, 1):
            lanes = _head(j)
            o_ref[0, :, lanes] = (acc_scr[:, lanes] / l_scr[j]
                                  ).astype(o_ref.dtype)
            lse = (m_scr[j] + jnp.log(l_scr[j]))[:, 0]
            lse_ref[j] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


# -- backward ---------------------------------------------------------------
#
# p = exp(s - lse), dv = p^T do, dp = do v^T, ds = p (dp - delta) with
# delta = rowsum(do o), dq = scale ds k, dk = scale ds^T q.  Scores are
# held (k, q) so that lse and delta broadcast along lanes, as in
# flash_attention.py.

def _p_ds(j, qn_ref, qr, kn_ref, k2, v_ref, do_ref, o_ref, lse_ref, scale,
          keep):
    """Head j of the pair: (p, ds), each (block_k, block_q) float32.
    `qr` is the pair's rotary tile, `k2` the rotary key in both
    halves; `keep` None where the block lies below the diagonal."""
    lanes = _head(j)
    do = do_ref[0, :, lanes]
    s = (_dot(kn_ref[0, :, lanes], qn_ref[0, :, lanes], ((1,), (1,)))
         + _dot(_of_head(k2, j), qr, ((1,), (1,)))) * scale
    p = jnp.exp(s - lse_ref[j, 0][None, :])
    if keep is not None:
        p = jnp.where(keep, p, 0.0)
    dp = _dot(v_ref[0, :, lanes], do, ((1,), (1,)))
    delta = jnp.sum(do.astype(jnp.float32)
                    * o_ref[0, :, lanes].astype(jnp.float32), axis=1)[None, :]
    return p, p * (dp - delta)


def _add_dk_dv(j, p, ds, qn_ref, qr, do_ref, dkn_scr, dkr_scr, dv_scr):
    """Head j's part of dk_nope, dk_rope and dv into their float32
    sums; (p, ds) as `_p_ds` gives them."""
    lanes = _head(j)
    do, qn = do_ref[0, :, lanes], qn_ref[0, :, lanes]
    dv_scr[:, lanes] += _dot(p.astype(do.dtype), do, ((1,), (0,)))
    ds = ds.astype(qn.dtype)
    dkn_scr[:, lanes] += _dot(ds, qn, ((1,), (0,)))
    # head j's part of the one rotary key's gradient, in half j
    dkr_scr[:] += _dot(ds, _of_head(qr, j), ((1,), (0,)))


def _add_dq(j, ds, kn_ref, k2, dqn_scr, dqr_scr):
    """Head j's part of dq_nope and dq_rope into their float32 sums:
    dq[q, d] = scale * sum_k ds[k, q] k[k, d]."""
    lanes = _head(j)
    kn = kn_ref[0, :, lanes]
    ds = ds.astype(kn.dtype)
    dqn_scr[:, lanes] += _dot(ds, kn, ((0,), (0,)))
    dqr_scr[:] += _dot(ds, _of_head(k2, j), ((0,), (0,)))


def _dkv_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, o_ref,
                lse_ref, dkn_ref, dkr_ref, dv_ref, dkn_scr, dkr_scr, dv_scr,
                *, scale, block_q, block_k):
    from jax.experimental import pallas as pl

    kb, r, qb = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    last_q = qb == pl.num_programs(3) - 1

    @pl.when(qb == 0)
    def _init():
        dkn_scr[:] = jnp.zeros_like(dkn_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when((qb == 0) & (r == 0))
    def _init_rope():
        dkr_scr[:] = jnp.zeros_like(dkr_scr)

    def compute(masked):
        qr = qr_ref[0]
        k2 = _twice(kr_ref[0])
        keep = _causal(kb * block_k, qb * block_q, (block_k, block_q),
                       False) if masked else None
        for j in (0, 1):
            p, ds = _p_ds(j, qn_ref, qr, kn_ref, k2, v_ref, do_ref, o_ref,
                          lse_ref, scale, keep)
            _add_dk_dv(j, p, ds, qn_ref, qr, do_ref, dkn_scr, dkr_scr, dv_scr)

    _on_needed_blocks(qb, kb, block_q, block_k, compute)

    @pl.when(last_q)
    def _finalize():
        dkn_ref[0] = (dkn_scr[:] * scale).astype(dkn_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(last_q & (r == pl.num_programs(2) - 1))
    def _finalize_rope():
        dkr_ref[0] = (_fold(dkr_scr[:]) * scale).astype(dkr_ref.dtype)


def _dq_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, o_ref, lse_ref,
               dqn_ref, dqr_ref, dqn_scr, dqr_scr, *, scale, block_q,
               block_k):
    from jax.experimental import pallas as pl

    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dqn_scr[:] = jnp.zeros_like(dqn_scr)
        dqr_scr[:] = jnp.zeros_like(dqr_scr)

    def compute(masked):
        qr = qr_ref[0]
        k2 = _twice(kr_ref[0])
        keep = _causal(kb * block_k, qb * block_q, (block_k, block_q),
                       False) if masked else None
        for j in (0, 1):
            _, ds = _p_ds(j, qn_ref, qr, kn_ref, k2, v_ref, do_ref, o_ref,
                          lse_ref, scale, keep)
            _add_dq(j, ds, kn_ref, k2, dqn_scr, dqr_scr)

    _on_needed_blocks(qb, kb, block_q, block_k, compute)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        dqn_ref[0] = (dqn_scr[:] * scale).astype(dqn_ref.dtype)
        dqr_ref[0] = (dqr_scr[:] * scale).astype(dqr_ref.dtype)


def _bwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, o_ref,
                lse_ref, dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref,
                dqn_acc, dqr_acc, dkr_acc, dkn_scr, dv_scr, *, scale,
                block_q, block_k, last_k):
    """The whole backward pass, grid (N, pair, kb, qb): p and ds once a
    block, all five gradients from them.  dk_nope / dv are summed over
    the query blocks in block scratch as in `_dkv_kernel`; dq, which
    sums over the key blocks (the OUTER axis here), in a float32
    accumulator of the pair's whole sequence (`dqn_acc`, `dqr_acc`:
    (nq, block_q, .)), each block leaving for HBM on the step that
    completes it, the diagonal's (`last_k`); the rotary key's gradient,
    which sums over the pairs too, in `dkr_acc` (nk, block_k, 128),
    folded and written during the last pair."""
    from jax.experimental import pallas as pl

    r, kb, qb = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    last_q = qb == pl.num_programs(3) - 1

    @pl.when(qb == 0)
    def _init():
        dkn_scr[:] = jnp.zeros_like(dkn_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when((qb == 0) & (r == 0))
    def _init_rope():
        dkr_acc[kb] = jnp.zeros(dkr_acc.shape[1:], dkr_acc.dtype)

    @pl.when(kb == 0)       # every query block meets key block 0, first
    def _init_dq():
        dqn_acc[qb] = jnp.zeros(dqn_acc.shape[1:], dqn_acc.dtype)
        dqr_acc[qb] = jnp.zeros(dqr_acc.shape[1:], dqr_acc.dtype)

    def compute(masked):
        qr = qr_ref[0]
        k2 = _twice(kr_ref[0])
        keep = _causal(kb * block_k, qb * block_q, (block_k, block_q),
                       False) if masked else None
        for j in (0, 1):
            p, ds = _p_ds(j, qn_ref, qr, kn_ref, k2, v_ref, do_ref, o_ref,
                          lse_ref, scale, keep)
            _add_dk_dv(j, p, ds, qn_ref, qr, do_ref, dkn_scr,
                       dkr_acc.at[kb], dv_scr)
            _add_dq(j, ds, kn_ref, k2, dqn_acc.at[qb], dqr_acc.at[qb])

    _on_needed_blocks(qb, kb, block_q, block_k, compute)

    @pl.when(last_q)
    def _finalize():
        dkn_ref[0] = (dkn_scr[:] * scale).astype(dkn_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(last_q & (r == pl.num_programs(1) - 1))
    def _finalize_rope():
        dkr_ref[0] = (_fold(dkr_acc[kb]) * scale).astype(dkr_ref.dtype)

    @pl.when(kb == last_k(qb))
    def _finalize_dq():
        dqn_ref[0] = (dqn_acc[qb] * scale).astype(dqn_ref.dtype)
        dqr_ref[0] = (dqr_acc[qb] * scale).astype(dqr_ref.dtype)


# -- geometry and the calls -------------------------------------------------

class _Geometry:
    """Where the blocks of a call lie: `pairs` head pairs over `nq` x
    `nk` blocks of one sequence."""

    def __init__(self, q_nope, q_rope, k_nope, k_rope, v, block_q, block_k):
        n, t, hd = q_nope.shape
        heads = hd // NOPE_DIM
        if (hd != heads * NOPE_DIM or k_nope.shape != q_nope.shape
                or v.shape != q_nope.shape
                or q_rope.shape != (n, t, heads * ROPE_DIM)
                or k_rope.shape != (n, t, ROPE_DIM)):
            raise ValueError(
                f"flash_mla: q_nope {q_nope.shape}, q_rope {q_rope.shape}, "
                f"k_nope {k_nope.shape}, k_rope {k_rope.shape}, v {v.shape} "
                f"are not H heads of {NOPE_DIM} + {ROPE_DIM} over one "
                f"sequence with one rotary key head")
        if heads % 2:
            raise NotImplementedError(
                f"flash_mla blocks heads in pairs: {heads} heads are odd")
        self.n, self.t, self.pairs = n, t, heads // 2
        self.block_q, self.block_k = min(block_q, t), min(block_k, t)
        if t % self.block_q or t % self.block_k:
            raise ValueError(f"flash_mla: T {t} is not a whole number of "
                             f"{self.block_q} / {self.block_k} blocks")
        self.nq, self.nk = t // self.block_q, t // self.block_k

    def last_k(self, qb):
        return ((qb + 1) * self.block_q - 1) // self.block_k

    def first_q(self, kb):
        return (kb * self.block_k) // self.block_q

    def by_query_block(self):
        """Specs of a grid (N*H/2, qb, kb): q-side blocks (two tiles of
        q_nope / o, one of q_rope), key-side blocks (two tiles, and the
        rotary key), the pair's statistics."""
        from jax.experimental import pallas as pl

        hp, bq, bk = self.pairs, self.block_q, self.block_k

        def q_at(g, a, b):
            return (g // hp, a, g % hp)

        def k_at(g, a, b):
            return (g // hp, jnp.minimum(b, self.last_k(a)), g % hp)

        def kr_at(g, a, b):
            return (g // hp, jnp.minimum(b, self.last_k(a)), 0)

        return {"qn": pl.BlockSpec((1, bq, 2 * NOPE_DIM), q_at),
                "qr": pl.BlockSpec((1, bq, 2 * ROPE_DIM), q_at),
                "kn": pl.BlockSpec((1, bk, 2 * NOPE_DIM), k_at),
                "kr": pl.BlockSpec((1, bk, ROPE_DIM), kr_at),
                "stat": pl.BlockSpec((2, 8, bq), lambda g, a, b: (g, 0, a))}

    def by_key_block(self, pair_outside=False):
        """Specs of the grid (N, kb, pair, qb), or of (N, pair, kb, qb)
        with the pair outside the key blocks (the single backward
        kernel's, which adds the specs of its dq and dk_rope blocks)."""
        from jax.experimental import pallas as pl

        hp, bq, bk = self.pairs, self.block_q, self.block_k

        def spec(shape, at):
            """`at(n, kb, r, qb)` in the grid's own order."""
            if pair_outside:
                return pl.BlockSpec(shape,
                                    lambda n, r, kb, qb: at(n, kb, r, qb))
            return pl.BlockSpec(shape, at)

        def q_at(n, kb, r, qb):
            return (n, jnp.maximum(qb, self.first_q(kb)), r)

        def dq_at(n, kb, r, qb):
            # the query block last completed, or being completed: the
            # blocks before first_q(kb) met their last key block in an
            # earlier pass, those before first_q(kb + 1) meet it in
            # this one.  The index moves on only on the step that
            # writes the next block, so no half-summed block is ever
            # what Pallas writes back
            done = jnp.minimum(jnp.maximum(qb, self.first_q(kb) - 1),
                               self.first_q(kb + 1) - 1)
            return (n, jnp.maximum(done, 0), r)

        def dkr_at(n, kb, r, qb):
            # written during the last pair only; block 0 waits till then
            return (n, jnp.where(r == hp - 1, kb, 0), 0)

        return {"qn": spec((1, bq, 2 * NOPE_DIM), q_at),
                "qr": spec((1, bq, 2 * ROPE_DIM), q_at),
                "kn": spec((1, bk, 2 * NOPE_DIM),
                           lambda n, kb, r, qb: (n, kb, r)),
                "kr": spec((1, bk, ROPE_DIM),
                           lambda n, kb, r, qb: (n, kb, 0)),
                "stat": spec(
                    (2, 8, bq),
                    lambda n, kb, r, qb: (n * hp + r, 0,
                                          jnp.maximum(qb, self.first_q(kb)))),
                "dqn": spec((1, bq, 2 * NOPE_DIM), dq_at),
                "dqr": spec((1, bq, 2 * ROPE_DIM), dq_at),
                "dkr": spec((1, bk, ROPE_DIM), dkr_at)}


def _flash_fwd(qn, qr, kn, kr, v, scale, geo):
    from jax.experimental.pallas import tpu as pltpu

    s = geo.by_query_block()
    bq = geo.block_q
    kern = functools.partial(_fwd_kernel, scale=scale, block_q=bq,
                             block_k=geo.block_k)
    return _pallas_call(
        kern, name="flash_mla_fwd",
        grid=(geo.n * geo.pairs, geo.nq, geo.nk),
        in_specs=[s["qn"], s["qr"], s["kn"], s["kr"], s["kn"]],
        out_specs=[s["qn"], s["stat"]],
        out_shape=[jax.ShapeDtypeStruct(qn.shape, qn.dtype),
                   jax.ShapeDtypeStruct((geo.n * geo.pairs * 2, 8, geo.t),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((2, bq, 1), jnp.float32),
                        pltpu.VMEM((2, bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 2 * NOPE_DIM), jnp.float32)],
    )(qn, qr, kn, kr, v)


def _flash_bwd_split(qn, qr, kn, kr, v, o, lse8, do, scale, geo):
    """dk / dv and dq by a kernel each: every score twice."""
    from jax.experimental.pallas import tpu as pltpu

    bq, bk = geo.block_q, geo.block_k
    f32 = jnp.float32
    s = geo.by_key_block()
    dkv = functools.partial(_dkv_kernel, scale=scale, block_q=bq, block_k=bk)
    dkn, dkr, dv = _pallas_call(
        dkv, name="flash_mla_dkv",
        grid=(geo.n, geo.nk, geo.pairs, geo.nq),
        in_specs=[s["qn"], s["qr"], s["kn"], s["kr"], s["kn"], s["qn"],
                  s["qn"], s["stat"]],
        out_specs=[s["kn"], s["kr"], s["kn"]],
        out_shape=[jax.ShapeDtypeStruct(kn.shape, kn.dtype),
                   jax.ShapeDtypeStruct(kr.shape, kr.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, 2 * NOPE_DIM), f32),
                        pltpu.VMEM((bk, 2 * ROPE_DIM), f32),
                        pltpu.VMEM((bk, 2 * NOPE_DIM), f32)],
    )(qn, qr, kn, kr, v, do, o, lse8)

    s = geo.by_query_block()
    dqk = functools.partial(_dq_kernel, scale=scale, block_q=bq, block_k=bk)
    dqn, dqr = _pallas_call(
        dqk, name="flash_mla_dq",
        grid=(geo.n * geo.pairs, geo.nq, geo.nk),
        in_specs=[s["qn"], s["qr"], s["kn"], s["kr"], s["kn"], s["qn"],
                  s["qn"], s["stat"]],
        out_specs=[s["qn"], s["qr"]],
        out_shape=[jax.ShapeDtypeStruct(qn.shape, qn.dtype),
                   jax.ShapeDtypeStruct(qr.shape, qr.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, 2 * NOPE_DIM), f32),
                        pltpu.VMEM((bq, 2 * ROPE_DIM), f32)],
    )(qn, qr, kn, kr, v, do, o, lse8)
    return dqn, dqr, dkn, dkr, dv


def _flash_bwd_fused(qn, qr, kn, kr, v, o, lse8, do, scale, geo):
    """All five gradients by ONE kernel, named `flash_mla_dkv`: it is
    that kernel grown by the two dq dots, and the name is the one the
    benchmark's closed list of kernels knows."""
    from jax.experimental.pallas import tpu as pltpu

    bq, bk = geo.block_q, geo.block_k
    f32 = jnp.float32
    s = geo.by_key_block(pair_outside=True)
    kern = functools.partial(_bwd_kernel, scale=scale, block_q=bq,
                             block_k=bk, last_k=geo.last_k)
    return _pallas_call(
        kern, name="flash_mla_dkv",
        grid=(geo.n, geo.pairs, geo.nk, geo.nq),
        in_specs=[s["qn"], s["qr"], s["kn"], s["kr"], s["kn"], s["qn"],
                  s["qn"], s["stat"]],
        out_specs=[s["dqn"], s["dqr"], s["kn"], s["dkr"], s["kn"]],
        out_shape=[jax.ShapeDtypeStruct(qn.shape, qn.dtype),
                   jax.ShapeDtypeStruct(qr.shape, qr.dtype),
                   jax.ShapeDtypeStruct(kn.shape, kn.dtype),
                   jax.ShapeDtypeStruct(kr.shape, kr.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((geo.nq, bq, 2 * NOPE_DIM), f32),
                        pltpu.VMEM((geo.nq, bq, 2 * ROPE_DIM), f32),
                        pltpu.VMEM((geo.nk, bk, 2 * ROPE_DIM), f32),
                        pltpu.VMEM((bk, 2 * NOPE_DIM), f32),
                        pltpu.VMEM((bk, 2 * NOPE_DIM), f32)],
    )(qn, qr, kn, kr, v, do, o, lse8)


def _flash_bwd(qn, qr, kn, kr, v, o, lse8, do, scale, geo):
    from ...observe.monitoring import runtime_stats

    fused = fused_backward_fits(geo.t)
    runtime_stats.record_flash_backward("flash_mla", fused)
    bwd = _flash_bwd_fused if fused else _flash_bwd_split
    return bwd(qn, qr, kn, kr, v, o, lse8, do, scale, geo)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(qn, qr, kn, kr, v, scale, block_q, block_k):
    return _flash_vjp_fwd(qn, qr, kn, kr, v, scale, block_q, block_k)[0]


def _flash_vjp_fwd(qn, qr, kn, kr, v, scale, block_q, block_k):
    geo = _Geometry(qn, qr, kn, kr, v, block_q, block_k)
    o, lse8 = keep_residuals(*_flash_fwd(qn, qr, kn, kr, v, scale, geo))
    return o, (qn, qr, kn, kr, v, o, lse8)


def _flash_vjp_bwd(scale, block_q, block_k, res, do):
    qn, qr, kn, kr, v, o, lse8 = res
    geo = _Geometry(qn, qr, kn, kr, v, block_q, block_k)
    return _flash_bwd(qn, qr, kn, kr, v, o, lse8, do, scale, geo)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_mla(q_nope, q_rope, k_nope, k_rope, v, scale=None,
              block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Causal self-attention of H heads whose score is
    q_nope . k_nope + q_rope . k_rope (128 + 64 lanes) and whose value
    is 128 wide; `k_rope` (N, T, 64) is the one rotary key every head
    reads.  Returns (N, T, H*128)."""
    if scale is None:
        scale = QK_DIM ** -0.5
    return _flash(q_nope, q_rope, k_nope, k_rope, v, float(scale),
                  int(block_q), int(block_k))
