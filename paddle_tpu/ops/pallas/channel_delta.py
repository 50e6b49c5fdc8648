"""Chunked delta rule whose decay is a key LANE's own (Kimi Delta
Attention, Kimi Team, "Kimi Linear", arXiv:2510.26692), forward and
backward (Pallas, TPU), and the XLA lowering of the same chunks.

The recurrence, a head at a time, with a state S (Dk, Dv) in float32
that starts at 0 and a log decay g_t (Dk,) <= 0, one number a lane:

    S'_t = Diag(exp(g_t)) S_{t-1}
    u_t  = beta_t (v_t - S'_t^T k_t)        (the delta rule's write)
    S_t  = S'_t + k_t u_t^T
    o_t  = S_t^T q_t

`gated_delta.py`'s recurrence is the case g_t constant over the lanes.
The benchmark's reference and the tests write it as a `lax.scan` over
positions.  What runs is its chunked form (chunks of C = 64 positions).
beta is folded into the operands first, kb = beta k and vb = beta v (in
the operands' dtype: two tensors XLA makes, whose gradients carry
beta's), so no kernel reads a (position, head) scalar.  Within a chunk
gamma_i = sum_{j<=i} g_j, a (C, Dk) matrix:

    A_ij = sum_d kb_i[d] k_j[d] exp(gamma_i[d] - gamma_j[d])   (i > j)
    P_ij = sum_d  q_i[d] k_j[d] exp(gamma_i[d] - gamma_j[d])   (i >= j)
    M = (I + A)^-1;  W = M (Kb * exp(gamma));  U = M Vb
    V' = U - W S                                  (S enters the chunk)
    O  = (Q * exp(gamma)) S + P V'
    S <- Diag(exp(gamma_C)) S + (K * exp(gamma_C - gamma))^T V'

The decay sits INSIDE the contraction of A and P, and exp(-gamma_j)
alone overflows, so neither is one product of two pre-scaled matrices
(`decayed_products`).  For ANY r with j < r <= i the decay splits as
exp(gamma_i - gamma_r) exp(gamma_r - gamma_j), both exponents <= 0
because gamma falls, so a chunk's pairs are halved: a LEVEL of
half-width w (32, 16, .. ) takes the pairs that share a block of 2 w
positions with i in its lower and j in its upper half (the highest bit
in which i and j differ is w), r the block's midpoint, as ONE MXU
product for the whole chunk: all rows scaled by exp(-|gamma - gamma_r|)
(one tile serves rows and columns), rounded to the operands' dtype after
the scaling, the level's pairs picked out of the result.  The backward
halves all the way down (levels 32 .. 1 and the undecayed diagonal as
one more product); the forward stops at diagonal sub-blocks of `SUB` = 4
positions, which it takes column by column on the VPU,
exp(gamma_i - gamma_j) for the rows at or below j alone (timed on the
chip, PR 66: its last two levels cost more than four columns; the
backward's columns cost twice the forward's).  No exponent is ever
positive.

Two parts, five kernels and one reference lowering of each part.

**The chunk-local part** is a batch over all chunks.  `chunk_operands`
is its XLA lowering (the (C, C, Dk) decay tensor written out, which XLA
differentiates): the kernels' test reference and the fall-back.  The
kernels: grid (batch x PAIR of heads, blocks of chunks); q, k, kb, vb
and g are read from the op's (N, T, H x 128) layout by lane block, two
heads (256 lanes) a step, so that the two heads' (C, C) matrices lie
side by side in one float32 tile and `gated_delta._inverse_side_by_side`
(forward substitution in blocks) inverts both at once.  gamma is a
product with a 0 / 1 triangle at "highest" inside the kernel: no float32
(N, T, H x 128) tensor but g itself is in HBM.

* `channel_delta_inverse` reads q, k, kb, g; writes (I + A)^-1 (float32,
  two heads a tile) and P (the operands' dtype): kb and q are stacked
  into one product a level and share its exponentials and the columns'.
  Made BEFORE the `custom_vjp` that holds the other two, on constants,
  and NAMED (`ops/pallas keep_residuals`): a recompute segment keeps
  both, so its backward pass neither solves nor takes the decayed
  products a second time.
* `channel_delta_operands_fwd` reads q, k, kb, vb, g and the inverse;
  writes W, U, Q exp(gamma), K exp(gamma_C - gamma).  MXU work only.
* `channel_delta_operands_bwd` reads the same and the five cotangents
  (P's too); returns dq, dk, dkb, dvb and dg (float32).  The inverse's
  rule is dA = -M^T dM M^T.  gamma's gradient is q * dq + kb * dkb -
  k * dk over the parts that come through A and P (a pair (i, j) gives
  +x to gamma_i and -x to gamma_j, lane by lane), and dg its suffix sum,
  again a product with a triangle.

With `raw` (a `gated_delta.RawQK`: what the op hands them since PR 69)
the three read q and k AS THE PROJECTION WROTE THEM, QKV (N, T, W) twice
by lane block, and kb = beta x the RAW k: a chunk takes the l2norm of
its rows as it reads them (`_unit_heads`; kb takes k's 1 / norm
there) and `_operands_bwd` returns the gradients of
the raw lanes and of the raw kb (`gated_delta._raw_gradient`; what
reaches k's norm through kb rides k's).  Unit q and k (no `raw`) remain
the tests', the references' and `tools/time_kernel.py`'s entry.

**The sequential part** reads S: grid (batch x head, blocks of chunks),
the state TRANSPOSED, (Dv, Dk) float32, in VMEM scratch across the grid
(so that the decay of a chunk, a row of Dk lanes, scales it along the
lanes), four MXU dots a chunk (`channel_delta_fwd`); o is written into
the op's (N, T, H x 128) layout by lane block.  Operands of the dots are
the operands' dtype (bfloat16 under AMP); accumulation, S, gamma, every
exponential and the substitution float32.  Backward: a custom VJP
around the sequential part alone.  The forward rule's kernel also
writes the state that ENTERS each chunk; the backward kernel
(`channel_delta_bwd`) walks the blocks in reverse carrying dS, rebuilds
V' from the saved state, and emits dW, dU, d(Q exp gamma), d(K exp ..),
dP and d exp(gamma_C) (a row of Dk lanes a chunk).  `scan_xla` is its
XLA lowering.

Which runs is the shape's alone (`kernel_takes`: Dk = Dv = 128, an
even number of heads, whole blocks of 8 chunks).  `runtime_stats.channel_delta_calls` / `_chunks`
count the scan kernels' calls traced and their chunks x heads,
`channel_delta_operand_calls` / `_operand_chunks` those of the
chunk-local kernels (the inverse kernel included): a part that fell back
reads 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# the chunk arithmetic's structure is `gated_delta.py`'s: its pure
# functions and its kernels' plumbing serve both files
from .gated_delta import (CHUNK, _HI, _Q_SCALE, RawQK, _block_chunks,
                          _chunk_rows, _dot, _dot_hi, _for_each_chunk,
                          _inverse_side_by_side, _lower, _pallas_call,
                          _l2norm, _params, _raw_gradient, _rows, _suffix_sum,
                          _tile_iotas, ranged_bytes, unit_lower_inverse,
                          unit_q_and_k)
from .head_norm import lane_range_gradient

# positions of a diagonal sub-block the forward takes by columns: where
# its halving stops (the backward's goes all the way: `decayed_products`)
SUB = 4
HEAD_DIM = 128          # the kernels' Dk and Dv
PAIR = 2                # heads a grid step of the chunk-local kernels
# chunks a grid step of the chunk-local kernels: 256 rows of every
# operand a DMA (the backward kernel holds eleven operands and five
# results, double-buffered: 7 MB of VMEM at 4, tuned nowhere yet)
OPERAND_BLOCK_CHUNKS = 4
_NEG = -1e30            # an exponent that gives 0 (never +inf - inf)
_LOG2E = 1.4426950408889634


def kernel_takes(heads, dk, dv, t):
    """Whether the Pallas kernels run a call: from the shape alone
    (heads of 128 x 128 in pairs; the scan kernels' row of decays a
    chunk comes in blocks of 8 chunks, or all of them)."""
    chunks = -(-t // CHUNK)
    return ((dk, dv) == (HEAD_DIM, HEAD_DIM) and heads % PAIR == 0
            and (chunks % 8 == 0 or chunks < 8))


# -- kernel cost registry (observe/cost.py) ----------------------------
#
# What each kernel computes once, by its operands' shapes.  The scan
# kernels as `gated_delta.py` counts its own (forward three products of
# 2 C Dk Dv and one of 2 C C Dv a chunk and head; backward six and two).
# The chunk-local ones by head and position: a decayed product is 2 C Dk
# a row (the levels' MXU products and the forward's VPU columns pick
# every pair of the lower triangle once: C Dk multiply-adds a row at the
# full square, half of it real; what a level's product computes beside
# its own pairs is not work).

def _scan_dims(operand_shapes):
    (bh, t, dk), _ = operand_shapes[0]
    return bh, t, dk, operand_shapes[1][0][2]


def scan_fwd_cost(operand_shapes, result_shapes):
    bh, t, dk, dv = _scan_dims(operand_shapes)
    return bh * t * (3 * 2.0 * dk * dv + 2.0 * CHUNK * dv), None


def scan_bwd_cost(operand_shapes, result_shapes):
    bh, t, dk, dv = _scan_dims(operand_shapes)
    return bh * t * (6 * 2.0 * dk * dv + 2 * 2.0 * CHUNK * dv), None


def _operand_cost(a_row, operand_shapes, result_shapes):
    """`a_row` FLOP a position and head, by kb (the third operand: q
    and k may come inside the projection, and count as their lanes)."""
    (n, t, width), _ = operand_shapes[2]
    return n * t * (width // HEAD_DIM) * a_row, ranged_bytes(
        operand_shapes, result_shapes, width)


def inverse_cost(operand_shapes, result_shapes):
    """A and P (a decayed product each) and the substitution's C^2 / 3
    multiply-adds a row."""
    return _operand_cost(
        2 * 2.0 * CHUNK * HEAD_DIM + 2.0 * CHUNK * CHUNK / 3,
        operand_shapes, result_shapes)


def operands_fwd_cost(operand_shapes, result_shapes):
    """W and U."""
    return _operand_cost(2 * 2.0 * CHUNK * HEAD_DIM, operand_shapes,
                         result_shapes)


def operands_bwd_cost(operand_shapes, result_shapes):
    """dM (two products), dkbg, dvb, the two decayed products' row and
    column sides (four), of 2 C D a row; the inverse's gradient, two of
    2 C C."""
    return _operand_cost(
        8 * 2.0 * CHUNK * HEAD_DIM + 2 * 2.0 * CHUNK * CHUNK,
        operand_shapes, result_shapes)


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("channel_delta_fwd", scan_fwd_cost)
    register_kernel_cost("channel_delta_bwd", scan_bwd_cost)
    register_kernel_cost("channel_delta_inverse", inverse_cost)
    register_kernel_cost("channel_delta_operands_fwd", operands_fwd_cost)
    register_kernel_cost("channel_delta_operands_bwd", operands_bwd_cost)


_register_costs()


# -- the batch part (XLA) ----------------------------------------------

def chunk_operands(q, k, kb, vb, g):
    """What the sequential part reads, for every chunk at once.  q, k,
    kb (N, T, H, Dk), vb (N, T, H, Dv) in one dtype, g (N, T, H, Dk)
    float32, T a whole number of chunks.  Returns W, Qg, Kd (N*H, T,
    Dk), U (N*H, T, Dv) and P (N*H, T, C) in vb's dtype, in the order
    W, U, Qg, Kd, P, and exp(gamma_C) (N*H, T / C, Dk) float32."""
    n, t, h, dk = k.shape
    dv = vb.shape[3]
    nc, c, dt, f32 = t // CHUNK, CHUNK, vb.dtype, jnp.float32

    def chunks(x):          # (N, T, H, D) -> (N, H, nc, C, D) float32
        return jnp.moveaxis(x.reshape(n, nc, c, h, x.shape[3]), 3,
                            1).astype(f32)

    gc = chunks(g)
    gamma = jnp.cumsum(gc, axis=-2)
    # what is left of the chunk after each position, summed as it is
    rest = _suffix_sum(gc, -2) - gc
    decay = jnp.exp(jnp.where(
        _lower(c)[..., None],
        gamma[..., :, None, :] - gamma[..., None, :, :], -jnp.inf))
    qf, kf, kbf = chunks(q), chunks(k), chunks(kb)
    a = jnp.where(_lower(c, strict=True),
                  jnp.einsum("...id,...jd,...ijd->...ij", kbf, kf, decay),
                  0.0)
    p = jnp.einsum("...id,...jd,...ijd->...ij", qf, kf, decay).astype(dt)
    m = unit_lower_inverse(a).astype(dt)
    e = jnp.exp(gamma)
    w = jnp.einsum("...ij,...jd->...id", m, (kbf * e).astype(dt),
                   preferred_element_type=f32).astype(dt)
    u = jnp.einsum("...ij,...jd->...id", m, chunks(vb).astype(dt),
                   preferred_element_type=f32).astype(dt)
    qg = (qf * e).astype(dt)
    kd = (kf * jnp.exp(rest)).astype(dt)
    flat = lambda x, d: x.reshape(n * h, t, d)  # noqa: E731
    return (flat(w, dk), flat(u, dv), flat(qg, dk), flat(kd, dk),
            flat(p, c), jnp.exp(gamma[..., -1, :]).reshape(n * h, nc, dk))


# -- the batch part, as the kernels run it -----------------------------

def _sub_row(x, j, span):
    """(C, D) -> (C, D): row i holds row j of i's span of `span` rows."""
    lax = jax.lax
    d = x.shape[1]
    return lax.concatenate([
        lax.broadcast_in_dim(lax.slice(x, (s + j, 0), (s + j + 1, d)),
                             (span, d), (0, 1))
        for s in range(0, CHUNK, span)], 0)


def _square_iotas():
    row = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    return row, col


def _levels(least):
    """The levels' half-widths, rising: `least` up to C / 2."""
    return [least << n for n in range((CHUNK // (2 * least)).bit_length())]


def _pair_levels():
    """(C, C) int32: i ^ j where i > j, whose highest bit is the
    half-width of the level that takes the pair (i in the lower half and
    j in the upper half of one block of twice that many positions); 0
    where i = j, -1 above the diagonal."""
    row, col = _square_iotas()
    return jnp.where(row > col, row ^ col, jnp.where(row == col, 0, -1))


def _level_decay(gamma, w):
    """exp(-|gamma_i - gamma_m|) (C, D), m the midpoint of i's block of
    2 w positions: what the level's rows (i >= m) take as
    exp(gamma_i - gamma_m) and its columns (j < m) as
    exp(gamma_m - gamma_j), one tile for both.  gamma falls, so that is
    what it is, and no exponent is positive whatever the rounding."""
    span = max(2 * w, 8)    # whole sublane groups are broadcast
    mid = _sub_row(gamma, span - w, span)
    if span > 2 * w:        # several blocks a sublane group
        sub = jax.lax.broadcasted_iota(jnp.int32, gamma.shape, 0) & (span - 1)
        for m in reversed(range(w, span - w, 2 * w)):
            mid = jnp.where(sub < m + w, _sub_row(gamma, m, span), mid)
    # (exp as the chip takes it, 2^x: the sign rides on the constant)
    return jnp.exp2(jnp.abs(gamma - mid) * -_LOG2E)


def decayed_products(rows, k, gamma, dt):
    """[sum_d x_i[d] k_j[d] exp(gamma_i[d] - gamma_j[d]) for i >= j and
    0 above the diagonal, (C, C) float32] for x in `rows`.  float32
    (C, D) operands of one head's chunk; the MXU's in `dt`."""
    f32 = jnp.float32
    row, col = _square_iotas()
    d = k.shape[1]
    # the diagonal sub-blocks of SUB positions, column by column
    sub = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, d), 0) & (SUB - 1)
    base = row - (row & (SUB - 1))
    out = [jnp.zeros((CHUNK, CHUNK), f32) for _ in rows]
    for j in range(SUB):
        # exp(gamma_i - gamma_j) for the rows at or below j, 0 above
        ke = _sub_row(k, j, SUB) * jnp.exp(
            jnp.where(sub >= j, gamma - _sub_row(gamma, j, SUB), _NEG))
        at = col == base + j
        out = [jnp.where(at, jnp.sum(x * ke, axis=1, keepdims=True), acc)
               for x, acc in zip(rows, out)]
    # the rest a level a product, each level's pairs picked out of its own
    pairs = _pair_levels()
    for w in _levels(SUB):
        e = _level_decay(gamma, w)
        xr = jnp.concatenate([x * e for x in rows], 0)
        s = _dot(xr.astype(dt), (k * e).astype(dt), ((1,), (1,)))
        out = [jnp.where(pairs >= w, s[i * CHUNK:(i + 1) * CHUNK], acc)
               for i, acc in enumerate(out)]
    return out


def decayed_products_bwd(rows, cts, k, gamma, dt):
    """The gradients of `decayed_products` given the (C, C) cotangents
    `cts` (0 above the diagonal): ([dx for x in rows], dk), float32
    (C, D).  gamma's is sum_x x * dx - k * dk, the caller's.  Levels all
    the way down, and the diagonal (no decay) as one more."""
    pairs = _pair_levels()
    count = len(rows)

    def level(on, xs, ks):
        c = jnp.concatenate([jnp.where(on, ct, 0.0) for ct in cts],
                            0).astype(dt)
        return (_dot(c, ks.astype(dt), ((1,), (0,))),
                _dot(c, jnp.concatenate(xs, 0).astype(dt), ((0,), (0,))))

    dxr, dk = level(pairs == 0, rows, k)
    for w in _levels(1):
        e = _level_decay(gamma, w)
        a, b = level((pairs >> (w.bit_length() - 1)) == 1,
                     [x * e for x in rows], k * e)
        dxr, dk = dxr + a * jnp.concatenate([e] * count, 0), dk + b * e
    return [dxr[i * CHUNK:(i + 1) * CHUNK] for i in range(count)], dk


def _triangles():
    """[i >= t] and [t > i], (C, C) float32: gamma = upto @ g and what
    is left after a position = after @ g."""
    row, col = _square_iotas()
    one, zero = jnp.float32(1.0), jnp.float32(0.0)
    return jnp.where(row >= col, one, zero), jnp.where(col > row, one, zero)


def _head(x, h):
    return x[:, h * HEAD_DIM:(h + 1) * HEAD_DIM]


def _unit_heads(q, k, kb, raw):
    """[(q, k, kb, q's rstd, k's rstd) a head of the pair], float32
    (C, 128) and (C, 1), of a chunk's (C, 256) blocks as they were read.
    Without `raw` the blocks hold unit q and k and kb = beta k (the
    rstds are None).  With it they hold the projection's q and k and kb
    = beta times the RAW k, and the l2norm is taken here
    (`gated_delta._l2norm`), kb taking k's 1 / norm.  A chunk at a
    time, NOT a grid step's rows at once into scratch as
    `gated_delta._head_rows` does: these kernels' chunks hide the
    reduction behind gamma's products, and the three (256, 256) float32
    buffers cost more than they saved (the layer's four calls 11.69 ms
    this way, 11.98 that way, 13.75 on unit operands with their six
    `head_norm_*` calls: my chip runs, PR 69, calls 1 and 2)."""
    f32 = jnp.float32
    heads = []
    for h in range(PAIR):
        qh, kh, kbh = (_head(x, h).astype(f32) for x in (q, k, kb))
        q_rstd = k_rstd = None
        if raw:
            qh, q_rstd = _l2norm(qh, _Q_SCALE)
            kh, k_rstd = _l2norm(kh)
            kbh = kbh * k_rstd
        heads.append((qh, kh, kbh, q_rstd, k_rstd))
    return heads


def _inverse_kernel(q_ref, k_ref, kb_ref, g_ref, m_ref, p_ref, *,
                    block_chunks, raw):
    iotas = _tile_iotas()
    row, col, _ = iotas
    upto, _ = _triangles()

    def chunk(c):
        r = _chunk_rows(c)
        gamma = _dot_hi(upto, g_ref[0, r, :], ((1,), (0,)))
        a = []
        for h, (q, k, kb, _, _) in enumerate(_unit_heads(
                q_ref[0, r, :], k_ref[0, r, :], kb_ref[0, r, :], raw)):
            ah, ph = decayed_products([kb, q], k, _head(gamma, h),
                                      k_ref.dtype)
            a.append(ah)
            p_ref[h, r, :] = ph.astype(p_ref.dtype)
        a = jnp.where(row > col, jnp.concatenate(a, axis=1), 0.0)
        m_ref[0, r, :] = _inverse_side_by_side(a, iotas)

    _for_each_chunk(block_chunks, chunk)


def _operands_fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, m_ref, w_ref,
                         u_ref, qg_ref, kd_ref, *, block_chunks, raw):
    upto, after = _triangles()

    def chunk(c):
        r = _chunk_rows(c)
        g = g_ref[0, r, :]
        dt = k_ref.dtype
        e2 = jnp.exp(_dot_hi(upto, g, ((1,), (0,))))
        left = jnp.exp(_dot_hi(after, g, ((1,), (0,))))
        m = m_ref[0, r, :].astype(dt)
        for h, (q, k, kb, _, _) in enumerate(_unit_heads(
                q_ref[0, r, :], k_ref[0, r, :], kb_ref[0, r, :], raw)):
            e = _head(e2, h)
            mh = m[:, h * CHUNK:(h + 1) * CHUNK]
            w_ref[h, r, :] = _dot(mh, (kb * e).astype(dt),
                                  ((1,), (0,))).astype(dt)
            u_ref[h, r, :] = _dot(mh, _head(vb_ref[0, r, :], h),
                                  ((1,), (0,))).astype(dt)
            qg_ref[h, r, :] = (q * e).astype(dt)
            kd_ref[h, r, :] = (k * _head(left, h)).astype(dt)

    _for_each_chunk(block_chunks, chunk)


def _operands_bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, m_ref, dw_ref,
                         du_ref, dqg_ref, dkd_ref, dp_ref, dq_ref, dk_ref,
                         dkb_ref, dvb_ref, dg_ref, *, block_chunks, raw):
    f32 = jnp.float32
    row, col = _square_iotas()
    upto, after = _triangles()

    def chunk(c):
        r = _chunk_rows(c)
        g = g_ref[0, r, :]
        dt = k_ref.dtype
        gamma2 = _dot_hi(upto, g, ((1,), (0,)))
        e2 = jnp.exp(gamma2)
        left2 = jnp.exp(_dot_hi(after, g, ((1,), (0,))))
        vb2, m2 = vb_ref[0, r, :], m_ref[0, r, :]
        dq, dk, dkb, dvb, dgamma, drest = [], [], [], [], [], []
        for h, (q, k, kb, q_rstd, k_rstd) in enumerate(_unit_heads(
                q_ref[0, r, :], k_ref[0, r, :], kb_ref[0, r, :], raw)):
            gamma, e, left = (_head(x, h) for x in (gamma2, e2, left2))
            vb = _head(vb2, h)
            m = m2[:, h * CHUNK:(h + 1) * CHUNK]
            md = m.astype(dt)
            dw, du = dw_ref[h, r, :], du_ref[h, r, :]
            dqg = dqg_ref[h, r, :].astype(f32)
            dkd = dkd_ref[h, r, :].astype(f32)
            kbg = kb * e
            dm = (_dot(dw, kbg.astype(dt), ((1,), (1,)))
                  + _dot(du, vb, ((1,), (1,))))
            dkbg = _dot(md, dw, ((0,), (0,)))
            dvb.append(_dot(md, du, ((0,), (0,))))
            # the inverse's gradient, dA = -M^T dM M^T
            da = -_dot_hi(m, _dot_hi(dm, m, ((1,), (1,))), ((0,), (0,)))
            da = jnp.where(row > col, da, 0.0)
            dp = jnp.where(row >= col, dp_ref[h, r, :].astype(f32), 0.0)
            (dkb_a, dq_p), dk_ap = decayed_products_bwd(
                [kb, q], [da, dp], k, gamma, dt)
            dqh, dkh = dqg * e + dq_p, dkd * left + dk_ap
            dkbh = dkbg * e + dkb_a
            if raw:     # the l2norm's rule; kb = (beta k) / |k| rides k's
                dqh = _raw_gradient(dqh, q, q_rstd, _Q_SCALE)
                dkh = _raw_gradient(
                    dkh, k, k_rstd,
                    through=jnp.sum(dkbh * kb, axis=1, keepdims=True))
                dkbh = dkbh * k_rstd
            dq.append(dqh)
            dk.append(dkh)
            dkb.append(dkbh)
            dgamma.append(dkbg * kbg + dqg * q * e + kb * dkb_a + q * dq_p
                          - k * dk_ap)
            drest.append(dkd * k * left)
        wide = lambda xs: jnp.concatenate(xs, axis=1)  # noqa: E731
        dq_ref[0, r, :] = wide(dq).astype(dt)
        dk_ref[0, r, :] = wide(dk).astype(dt)
        dkb_ref[0, r, :] = wide(dkb).astype(dt)
        dvb_ref[0, r, :] = wide(dvb).astype(dt)
        dg_ref[0, r, :] = (_dot_hi(upto, wide(dgamma), ((0,), (0,)))
                           + _dot_hi(after, wide(drest), ((0,), (0,))))

    _for_each_chunk(block_chunks, chunk)


def _operand_block_chunks(nc):
    return max(b for b in range(1, OPERAND_BLOCK_CHUNKS + 1) if nc % b == 0)


def _operand_specs(pairs, bc, raw=None):
    """A pair of heads' lanes of (N, T, H x 128): q's and k's (inside
    the arrays `raw` describes) and the others'; their (I + A)^-1 side
    by side; their rows of (N H, T, width)."""
    from jax.experimental import pallas as pl

    rows = bc * CHUNK

    def lanes(start=0):
        first = start // (PAIR * HEAD_DIM)
        return pl.BlockSpec((1, rows, PAIR * HEAD_DIM),
                            lambda b, i: (b // pairs, i, first + b % pairs))

    inverse = pl.BlockSpec((1, rows, PAIR * CHUNK), lambda b, i: (b, i, 0))

    def heads(width):
        return pl.BlockSpec((PAIR, rows, width), lambda b, i: (b, i, 0))

    return (lanes(raw.q) if raw else lanes(), lanes(raw.k) if raw else lanes(),
            lanes(), inverse, heads(HEAD_DIM), heads(CHUNK))


def _operand_grid(kb, raw):
    """By kb, which is (N, T, H x 128) whatever arrays hold q and k."""
    n, t, width = kb.shape
    pairs, nc = width // (PAIR * HEAD_DIM), t // CHUNK
    if raw and (raw.heads, raw.dim, raw.q % (PAIR * HEAD_DIM),
                raw.k % (PAIR * HEAD_DIM)) != (PAIR * pairs, HEAD_DIM, 0, 0):
        raise ValueError(f"channel_delta kernels: {raw} is not kb's "
                         f"{PAIR * pairs} heads of {HEAD_DIM} lanes in pairs")
    bc = _operand_block_chunks(nc)
    return n, t, pairs, bc, (n * pairs, nc // bc)


_STATIC = ("raw", "interpreted")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _inverse_call(q, k, kb, g, raw=None, interpreted=False):
    """(I + A)^-1 (float32, two heads a tile) and P of every chunk."""
    n, t, pairs, bc, grid = _operand_grid(kb, raw)
    query, key, lanes, inverse, _, square = _operand_specs(pairs, bc, raw)
    return _pallas_call(
        functools.partial(_inverse_kernel, block_chunks=bc, raw=bool(raw)),
        name="channel_delta_inverse", grid=grid,
        in_specs=[query, key, lanes, lanes], out_specs=[inverse, square],
        out_shape=[
            jax.ShapeDtypeStruct((n * pairs, t, PAIR * CHUNK), jnp.float32),
            jax.ShapeDtypeStruct((n * pairs * PAIR, t, CHUNK), kb.dtype)],
        compiler_params=_params(),
    )(q, k, kb, g)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _operands_fwd_call(q, k, kb, vb, g, m, raw=None, interpreted=False):
    n, t, pairs, bc, grid = _operand_grid(kb, raw)
    query, key, lanes, inverse, wide, _ = _operand_specs(pairs, bc, raw)
    flat = jax.ShapeDtypeStruct((n * pairs * PAIR, t, HEAD_DIM), kb.dtype)
    return _pallas_call(
        functools.partial(_operands_fwd_kernel, block_chunks=bc,
                          raw=bool(raw)),
        name="channel_delta_operands_fwd", grid=grid,
        in_specs=[query, key] + [lanes] * 3 + [inverse],
        out_specs=[wide] * 4, out_shape=[flat] * 4,
        compiler_params=_params(),
    )(q, k, kb, vb, g, m)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _operands_bwd_call(q, k, kb, vb, g, m, dw, du, dqg, dkd, dp, raw=None,
                       interpreted=False):
    """(dq, dk, dkb, dvb, dg), kb's shape each; dq, dk and dkb the unit
    vectors', or with `raw` the projection's lanes' and the raw kb's."""
    n, t, pairs, bc, grid = _operand_grid(kb, raw)
    query, key, lanes, inverse, wide, square = _operand_specs(pairs, bc, raw)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return _pallas_call(
        functools.partial(_operands_bwd_kernel, block_chunks=bc,
                          raw=bool(raw)),
        name="channel_delta_operands_bwd", grid=grid,
        in_specs=[query, key] + [lanes] * 3 + [inverse] + [wide] * 4
        + [square],
        out_specs=[lanes] * 5,
        out_shape=[like(kb), like(kb), like(kb), like(vb), like(g)],
        compiler_params=_params(),
    )(q, k, kb, vb, g, m, dw, du, dqg, dkd, dp)


def _record_operands(kb):
    """Count a call of a chunk-local kernel where it is traced (outside
    the jitted call, which is traced once a shape); gives the interpret
    gate, which keys that call's cache."""
    from ...observe.monitoring import runtime_stats
    from . import interpret

    n, t, width = kb.shape
    runtime_stats.record_channel_delta_operands(
        n * (width // HEAD_DIM) * (t // CHUNK))
    return interpret()


def chunk_inverses(q, k, kb, g, raw=None):
    """(I + A)^-1 and P of every chunk by `channel_delta_inverse`, NAMED:
    a recompute segment keeps both (`ops/pallas keep_residuals`).
    Constants of differentiation here: `operands_kernel`'s backward
    kernel holds the inverse's rule and P's, and returns what flows
    through them with dq, dk, dkb and dg."""
    from . import CHANNEL_DELTA_RESIDUALS, keep_residuals

    stop = jax.lax.stop_gradient
    return keep_residuals(
        *_inverse_call(stop(q), stop(k), stop(kb), stop(g), raw=raw,
                       interpreted=_record_operands(kb)),
        names=CHANNEL_DELTA_RESIDUALS)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def operands_kernel(q, k, kb, vb, g, m, p, raw=None):
    """`chunk_operands` less exp(gamma_C) by the Pallas kernels.  q, k,
    kb, vb (N, T, H x 128), g the same in float32, (m, p) =
    `chunk_inverses(q, k, kb, g)`.  With `raw` (a `gated_delta.RawQK`)
    q and k are the arrays that hold the projection's and kb is beta
    times the RAW k: the kernels take the l2norm of all three."""
    return _operands_vjp_fwd(q, k, kb, vb, g, m, p, raw)[0]


def _operands_vjp_fwd(q, k, kb, vb, g, m, p, raw):
    w, u, qg, kd = _operands_fwd_call(q, k, kb, vb, g, m, raw=raw,
                                      interpreted=_record_operands(kb))
    return (w, u, qg, kd, p), (q, k, kb, vb, g, m, p)


def _operands_vjp_bwd(raw, res, cts):
    q, k, kb, vb, g, m, p = res
    # m's and p's own cotangents are none: their parts are in the five
    dq, dk, *grads = _operands_bwd_call(
        q, k, kb, vb, g, m, *(c.astype(vb.dtype) for c in cts), raw=raw,
        interpreted=_record_operands(kb))
    if raw:
        dq, dk = (lane_range_gradient(dq, q.shape[-1], raw.q),
                  lane_range_gradient(dk, k.shape[-1], raw.k))
    return (dq, dk, *grads, jnp.zeros_like(m), jnp.zeros_like(p))


operands_kernel.defvjp(_operands_vjp_fwd, _operands_vjp_bwd)


def chunk_operands_kernel(q, k, kb, vb, g, raw=None):
    """`chunk_operands` on (N, T, H x 128) operands where `kernel_takes`
    the heads: the same six results, the chunks' matrices and gamma
    never in HBM.  `raw`: as `operands_kernel`."""
    n, t, width = kb.shape
    h = width // HEAD_DIM
    # exp(gamma_C) is XLA's: a sum over each chunk of g, and its gradient
    # (the rows are split, never the lanes: no tile moves)
    last = jnp.exp(jnp.sum(g.reshape(n, t // CHUNK, CHUNK, width), axis=2))
    last = jnp.moveaxis(last.reshape(n, t // CHUNK, h, HEAD_DIM), 2,
                        1).reshape(n * h, t // CHUNK, HEAD_DIM)
    return operands_kernel(q, k, kb, vb, g,
                           *chunk_inverses(q, k, kb, g, raw), raw) + (last,)


# -- the sequential part -----------------------------------------------

def _chunk_step(st, w, u, qg, kd, p, dec):
    """One chunk of one head: (the state that leaves, O (C, Dv)
    float32).  `st` S^T (Dv, Dk) float32, `dec` (1, Dk); the dots read
    the state in the operands' dtype."""
    dt = w.dtype
    sb = st.astype(dt)
    vp = (u.astype(jnp.float32) - _dot(w, sb, ((1,), (1,)))).astype(dt)
    o = _dot(qg, sb, ((1,), (1,))) + _dot(p, vp, ((1,), (0,)))
    return st * dec + _dot(vp, kd, ((0,), (0,))), o


def scan_xla(w, u, qg, kd, p, dec):
    """O (N*H, T, Dv) of the chunk operands: `_chunk_step` under a
    `lax.scan` over the chunks, every head at once."""
    bh, t, dk = w.shape
    dv, nc = u.shape[2], t // CHUNK

    def by_chunk(x):        # (BH, T, D) -> (nc, BH, C, D)
        return jnp.moveaxis(x.reshape(bh, nc, CHUNK, x.shape[2]), 1, 0)

    def step(st, xs):
        return jax.vmap(_chunk_step)(st, *xs)

    xs = tuple(by_chunk(x) for x in (w, u, qg, kd, p)) \
        + (jnp.moveaxis(dec, 1, 0)[:, :, None, :],)
    _, o = jax.lax.scan(step, jnp.zeros((bh, dv, dk), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1).reshape(bh, t, dv).astype(u.dtype)


def _fwd_kernel(w_ref, u_ref, qg_ref, kd_ref, p_ref, dec_ref, o_ref,
                *rest, block_chunks):
    from jax.experimental import pallas as pl

    s_scr = rest[-1]
    states = rest[0] if len(rest) == 2 else None

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    for c in range(block_chunks):
        r = _rows(c)
        st = s_scr[...]
        if states is not None:      # the state that enters the chunk
            states[0, c * HEAD_DIM:(c + 1) * HEAD_DIM, :] = st.astype(
                states.dtype)
        st, o = _chunk_step(st, w_ref[0, r, :], u_ref[0, r, :],
                            qg_ref[0, r, :], kd_ref[0, r, :], p_ref[0, r, :],
                            dec_ref[0, c:c + 1, :])
        s_scr[...] = st
        o_ref[0, r, :] = o.astype(o_ref.dtype)


def _bwd_kernel(w_ref, u_ref, qg_ref, kd_ref, p_ref, dec_ref, s_ref, do_ref,
                dw_ref, du_ref, dqg_ref, dkd_ref, dp_ref, ddec_ref, ds_scr,
                *, block_chunks):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    f32 = jnp.float32
    for c in reversed(range(block_chunks)):
        r = _rows(c)
        w, qg, kd, p = (w_ref[0, r, :], qg_ref[0, r, :], kd_ref[0, r, :],
                        p_ref[0, r, :])
        do = do_ref[0, r, :]
        dt = w.dtype
        st = s_ref[0, c * HEAD_DIM:(c + 1) * HEAD_DIM, :]   # S^T (Dv, Dk)
        ds = ds_scr[...]
        dsb = ds.astype(dt)
        vp = (u_ref[0, r, :].astype(f32)
              - _dot(w, st, ((1,), (1,)))).astype(dt)
        dvp = (_dot(p, do, ((0,), (0,)))
               + _dot(kd, dsb, ((1,), (1,)))).astype(dt)
        dp_ref[0, r, :] = _dot(do, vp, ((1,), (1,))).astype(dp_ref.dtype)
        dqg_ref[0, r, :] = _dot(do, st, ((1,), (0,))).astype(dqg_ref.dtype)
        dkd_ref[0, r, :] = _dot(vp, dsb, ((1,), (0,))).astype(dkd_ref.dtype)
        du_ref[0, r, :] = dvp.astype(du_ref.dtype)
        dw_ref[0, r, :] = (-_dot(dvp, st, ((1,), (0,)))).astype(dw_ref.dtype)
        ddec_ref[0, c:c + 1, :] = jnp.sum(ds * st.astype(f32), axis=0,
                                          keepdims=True)
        ds_scr[...] = (ds * dec_ref[0, c:c + 1, :]
                       + _dot(do, qg, ((0,), (0,)))
                       - _dot(dvp, w, ((0,), (0,))))


def _specs(heads, bc, time):
    """Blocks of (N H, T, ..) operands a head; the head's lanes of the
    op's (N, T, H x 128) layout (o and its cotangent)."""
    from jax.experimental import pallas as pl

    def tile(rows, lanes):
        return pl.BlockSpec((1, rows, lanes), lambda b, i: (b, time(i), 0))

    return (tile(bc * CHUNK, HEAD_DIM), tile(bc * CHUNK, CHUNK),
            tile(bc, HEAD_DIM), tile(bc * HEAD_DIM, HEAD_DIM),
            pl.BlockSpec((1, bc * CHUNK, HEAD_DIM),
                         lambda b, i: (b // heads, time(i), b % heads)))


def _scan_fwd_call(w, u, qg, kd, p, dec, heads, keep_states):
    from jax.experimental.pallas import tpu as pltpu

    from ...observe.monitoring import runtime_stats

    bh, t, _ = w.shape
    nc = t // CHUNK
    bc = _block_chunks(nc)
    runtime_stats.record_channel_delta(bh * nc)
    wide, narrow, row, state, lanes = _specs(heads, bc, lambda i: i)
    out_specs = [lanes]
    out_shape = [jax.ShapeDtypeStruct((bh // heads, t, heads * HEAD_DIM),
                                      u.dtype)]
    if keep_states:
        out_specs.append(state)
        out_shape.append(jax.ShapeDtypeStruct((bh, nc * HEAD_DIM, HEAD_DIM),
                                              w.dtype))
    return _pallas_call(
        functools.partial(_fwd_kernel, block_chunks=bc),
        name="channel_delta_fwd", grid=(bh, nc // bc),
        in_specs=[wide, wide, wide, wide, narrow, row],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((HEAD_DIM, HEAD_DIM), jnp.float32)],
        compiler_params=_params(),
    )(w, u, qg, kd, p, dec)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def scan_kernel(w, u, qg, kd, p, dec, heads):
    """`scan_xla` by the Pallas kernels (Dk = Dv = 128), its result in
    the op's layout: (N, T, H x 128)."""
    return _scan_fwd_call(w, u, qg, kd, p, dec, heads, False)[0]


def _scan_vjp_fwd(w, u, qg, kd, p, dec, heads):
    o, states = _scan_fwd_call(w, u, qg, kd, p, dec, heads, True)
    return o, (w, u, qg, kd, p, dec, states)


def _scan_vjp_bwd(heads, res, do):
    from jax.experimental.pallas import tpu as pltpu

    from ...observe.monitoring import runtime_stats

    w, u, qg, kd, p, dec, states = res
    bh, t, _ = w.shape
    nc = t // CHUNK
    bc = _block_chunks(nc)
    nb = nc // bc
    runtime_stats.record_channel_delta(bh * nc)
    wide, narrow, row, state, lanes = _specs(heads, bc,
                                             lambda i: nb - 1 - i)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    return tuple(_pallas_call(
        functools.partial(_bwd_kernel, block_chunks=bc),
        name="channel_delta_bwd", grid=(bh, nb),
        in_specs=[wide, wide, wide, wide, narrow, row, state, lanes],
        out_specs=[wide, wide, wide, wide, narrow, row],
        out_shape=[like(w), like(u), like(qg), like(kd), like(p), like(dec)],
        scratch_shapes=[pltpu.VMEM((HEAD_DIM, HEAD_DIM), jnp.float32)],
        compiler_params=_params(),
    )(w, u, qg, kd, p, dec, states, do.astype(u.dtype)))


scan_kernel.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def head_spread(x, lanes):
    """(.., H) -> (.., H x lanes): a head's number on each of its lanes,
    as a product with a 0 / 1 matrix at "highest".  NOT a broadcast to
    (.., H, lanes) and a reshape: on the chip that reshape re-lays the
    whole tensor (its tiles hold 8 rows x 128 lanes, the reshaped one's
    8 heads x 128 lanes: 134 MB a float32 operand at 8192 x 4096, PR
    65).  `times_beta` alone reads it; a head's lane SUMS are
    `head_norm.py`'s."""
    return jnp.dot(x, _heads_matrix(x.shape[-1], lanes, x.dtype).T,
                   precision=_HI)


def _heads_matrix(heads, lanes, dtype):
    return jnp.repeat(jnp.eye(heads, dtype=dtype), lanes, axis=0)


def channel_delta_rule(q, k, v, g, beta, use_kernel=False, raw=None):
    """O (N, T, H x Dv) of the recurrence at the top of this file.  q, k
    (N, T, H x Dk), v (N, T, H x Dv) in one dtype, heads side by side;
    g (N, T, H x Dk) float32, the log decay a key lane (<= 0); beta
    (N, T, H) float32.  A T that is no whole number of chunks is padded
    with positions that write nothing (beta 0, no decay).  `use_kernel`:
    the Pallas kernels (`kernel_takes` the heads), else the XLA
    lowering of the same chunks.  `raw` (a `gated_delta.RawQK`): q and k
    are not the unit vectors but the (N, T, W) arrays that hold the
    projection's, and the rule takes their l2norm: in the chunk-local
    kernels, through `gated_delta.unit_q_and_k` for the XLA lowering."""
    n, t, h = beta.shape
    dv = v.shape[2] // h
    if raw and not use_kernel:
        q, k = unit_q_and_k(q, k, raw)
        raw = None
    dk = raw.dim if raw else k.shape[2] // h
    if ((not raw and (q.shape != k.shape or k.shape != (n, t, h * dk)))
            or (raw and raw.heads != h)
            or v.shape != (n, t, h * dv) or g.shape != (n, t, h * dk)):
        raise ValueError(
            f"channel_delta_rule: q {q.shape}, k {k.shape}, v {v.shape}, g "
            f"{g.shape}, beta {beta.shape} are not {h} heads side by side, "
            f"a decay a key lane and a beta a head")
    if use_kernel and not kernel_takes(h, dk, dv, t):
        raise NotImplementedError(
            f"channel_delta_rule: the kernels take an even number of heads "
            f"of {HEAD_DIM}, not {h} of {dk} / {dv}")
    dt, f32 = v.dtype, jnp.float32
    q, k = q.astype(dt), k.astype(dt)

    def times_beta(x, d):
        return (x.astype(f32) * head_spread(beta.astype(f32), d)).astype(dt)

    # (of the raw k where the kernels take the l2norm: kb takes it there)
    kb = times_beta(k[..., raw.k:raw.k + h * dk] if raw else k, dk)
    vb = times_beta(v, dv)
    g = g.astype(f32)
    tail = -t % CHUNK
    if tail:
        q, k, kb, vb, g = (jnp.pad(x, ((0, 0), (0, tail), (0, 0)))
                           for x in (q, k, kb, vb, g))
    if use_kernel:
        o = scan_kernel(*chunk_operands_kernel(q, k, kb, vb, g, raw), h)
    else:
        heads = lambda x, d: x.reshape(n, t + tail, h, d)  # noqa: E731
        o = scan_xla(*chunk_operands(heads(q, dk), heads(k, dk),
                                     heads(kb, dk), heads(vb, dv),
                                     heads(g, dk)))
        o = jnp.moveaxis(o.reshape(n, h, t + tail, dv), 1, 2).reshape(
            n, t + tail, h * dv)
    return o[:, :t]
