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

Grids: forward (N*H/2, q blocks, k blocks); dq the same; dk/dv
(N*Hkv/2, k blocks, G query tiles, q blocks), summing the G tiles'
contributions in scratch, so the group's gradients meet in VMEM and
dk, dv are written once, Hkv heads wide.  Blocks above the diagonal
are skipped and their DMA with them (the index maps clamp to the last
block that is needed).  The soft-max statistics are the (N*H, 8, T)
sublane-replicated form of `flash_attention.py`.

Self-attention, causal, no bias, T a whole number of blocks: what a
decoder layer asks.  `flash_attention.py pallas_flash_attention`
sends head-major calls at d_head 64 here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import (_SOFTMAX_BWD_PER_SCORE, _SOFTMAX_FWD_PER_SCORE,
                              _io_bytes)

HEAD_DIM = 64
LANES = 128
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


# -- kernel cost registry: dense-equivalent, as flash_attention.py ----------

def _scores(operand_shapes):
    (n, t, hd), _ = operand_shapes[0]
    return n * (hd // HEAD_DIM) * t * t


def _fwd_cost(operand_shapes, result_shapes):
    flops = _scores(operand_shapes) * (4.0 * HEAD_DIM
                                       + _SOFTMAX_FWD_PER_SCORE)
    return flops, _io_bytes(operand_shapes, result_shapes)


def _dkv_cost(operand_shapes, result_shapes):
    flops = _scores(operand_shapes) * (6.0 * HEAD_DIM
                                       + 0.625 * _SOFTMAX_BWD_PER_SCORE)
    return flops, _io_bytes(operand_shapes, result_shapes)


def _dq_cost(operand_shapes, result_shapes):
    flops = _scores(operand_shapes) * (2.0 * HEAD_DIM
                                       + 0.375 * _SOFTMAX_BWD_PER_SCORE)
    return flops, _io_bytes(operand_shapes, result_shapes)


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("flash_gqa_fwd", _fwd_cost)
    register_kernel_cost("flash_gqa_dkv", _dkv_cost)
    register_kernel_cost("flash_gqa_dq", _dq_cost)


_register_costs()


def _pallas_call(*args, **kw):
    from . import pallas_call

    return pallas_call(*args, **kw)


# -- what the three kernels share -------------------------------------------

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

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, block_q, block_k, half_of):
    from jax.experimental import pallas as pl

    qb, kb = pl.program_id(1), pl.program_id(2)
    half = half_of(pl.program_id(0))

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when((qb + 1) * block_q > kb * block_k)
    def _compute():
        q = q_ref[0]
        k = _both_halves(k_ref[0], half)
        v = _both_halves(v_ref[0], half)
        keep = _causal(qb * block_q, kb * block_k, (block_q, block_k), True)
        alpha, pv = [], []
        for j in (0, 1):
            s = _dot(q, _of_head(k, j), ((1,), (1,))) * scale
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            a = jnp.exp(m_prev - m_new)
            l_scr[j] = a * l_scr[j] + jnp.sum(p, axis=1, keepdims=True)
            m_scr[j] = m_new
            alpha.append(a)
            pv.append(_dot(p.astype(v.dtype), _of_head(v, j), ((1,), (0,))))
        acc_scr[:] = (acc_scr[:] * _per_head(alpha[0], alpha[1])
                      + pv[0] + pv[1])

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / _per_head(l_scr[0], l_scr[1])
                    ).astype(o_ref.dtype)
        for j in (0, 1):
            lse = (m_scr[j] + jnp.log(l_scr[j]))[:, 0]
            lse_ref[j] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


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
        (p0, ds0), (p1, ds1) = _p_ds(
            q, _both_halves(k_ref[0], half), _both_halves(v_ref[0], half),
            do, o_ref[0], lse_ref, scale, keep)
        dv = _per_head(_dot(p0.astype(do.dtype), do, ((1,), (0,))),
                       _dot(p1.astype(do.dtype), do, ((1,), (0,))))
        dk = _per_head(_dot(ds0.astype(q.dtype), q, ((1,), (0,))),
                       _dot(ds1.astype(q.dtype), q, ((1,), (0,)))) * scale
        if half is not None:
            # both query heads read head `half`: their sum, in its lanes
            lane_half = jax.lax.broadcasted_iota(
                jnp.int32, dv.shape, 1) // HEAD_DIM
            dv = jnp.where(lane_half == half,
                           dv + _swap_halves(dv), 0.0)
            dk = jnp.where(lane_half == half,
                           dk + _swap_halves(dk), 0.0)
        dv_scr[:] += dv
        dk_scr[:] += dk

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
        (_, ds0), (_, ds1) = _p_ds(
            q_ref[0], k, _both_halves(v_ref[0], half), do_ref[0], o_ref[0],
            lse_ref, scale, keep)
        # dq[q, d] = scale * sum_k ds[k, q] k[k, d], in the head's lanes
        dq_scr[:] += scale * (
            _dot(ds0.astype(k.dtype), _of_head(k, 0), ((0,), (0,)))
            + _dot(ds1.astype(k.dtype), _of_head(k, 1), ((0,), (0,))))

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


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
        self.block_q, self.block_k = min(block_q, t), min(block_k, t)
        if t % self.block_q or t % self.block_k:
            raise ValueError(f"flash_gqa: T {t} is not a whole number of "
                             f"{self.block_q} / {self.block_k} blocks")
        self.nq, self.nk = t // self.block_q, t // self.block_k

    def kv_half(self, g):
        """Which half of its key/value tile query pair `g` (of the
        grid's N*H/2) reads; None when each head reads its own."""
        if self.group == 1:
            return None
        return ((g % self.q_pairs) // (self.group // 2)) % 2

    def last_k(self, qb):
        return ((qb + 1) * self.block_q - 1) // self.block_k

    def first_q(self, kb):
        return (kb * self.block_k) // self.block_q

    def by_query_pair(self, block, tsel, lanes=LANES):
        """Specs of a grid (N*H/2, a, b): the q-side tile of pair g and
        the key/value tile it reads."""
        from jax.experimental import pallas as pl

        qp, grp = self.q_pairs, self.group
        q_side = pl.BlockSpec(
            (1, block, lanes), lambda g, a, b: (g // qp, tsel(a, b), g % qp))
        kv_side = pl.BlockSpec(
            (1, block, lanes),
            lambda g, a, b: (g // qp, tsel(a, b), (g % qp) // grp))
        return q_side, kv_side


def _stat_shape(geo):
    return jax.ShapeDtypeStruct((geo.n * geo.q_pairs * 2, 8, geo.t),
                                jnp.float32)


def _flash_fwd(q, k, v, scale, geo):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bq, bk = geo.block_q, geo.block_k
    q_spec, _ = geo.by_query_pair(bq, lambda a, b: a)
    _, kv_spec = geo.by_query_pair(
        bk, lambda a, b: jnp.minimum(b, geo.last_k(a)))
    stat_spec = pl.BlockSpec((2, 8, bq), lambda g, a, b: (g, 0, a))
    kern = functools.partial(_fwd_kernel, scale=scale, block_q=bq,
                             block_k=bk, half_of=geo.kv_half)
    return _pallas_call(
        kern, name="flash_gqa_fwd",
        grid=(geo.n * geo.q_pairs, geo.nq, geo.nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, stat_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), _stat_shape(geo)],
        scratch_shapes=[pltpu.VMEM((2, bq, 1), jnp.float32),
                        pltpu.VMEM((2, bq, 1), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32)],
    )(q, k, v)


def _flash_bwd(q, k, v, o, lse8, do, scale, geo):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bq, bk, grp = geo.block_q, geo.block_k, geo.group
    qp, kvp = geo.q_pairs, geo.kv_pairs
    tiles = max(grp, 1)            # query tiles a key/value pair

    # dk, dv: grid (N*Hkv/2, kb, query tile of the pair, qb)
    def q_time(kb, qb):
        return jnp.maximum(qb, geo.first_q(kb))

    q_spec = pl.BlockSpec(
        (1, bq, LANES),
        lambda g, kb, r, qb: (g // kvp, q_time(kb, qb),
                              (g % kvp) * tiles + r))
    kv_spec = pl.BlockSpec((1, bk, LANES),
                           lambda g, kb, r, qb: (g // kvp, kb, g % kvp))
    stat_spec = pl.BlockSpec(
        (2, 8, bq),
        lambda g, kb, r, qb: ((g // kvp) * qp + (g % kvp) * tiles + r, 0,
                              q_time(kb, qb)))
    dkv = functools.partial(
        _dkv_kernel, scale=scale, block_q=bq, block_k=bk,
        halves=None if grp == 1 else grp // 2)
    dk, dv = _pallas_call(
        dkv, name="flash_gqa_dkv",
        grid=(geo.n * kvp, geo.nk, tiles, geo.nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, stat_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bk, LANES), jnp.float32)] * 2,
    )(q, k, v, do, o, lse8)

    # dq: grid (N*H/2, qb, kb)
    q_spec, _ = geo.by_query_pair(bq, lambda a, b: a)
    _, kv_spec = geo.by_query_pair(
        bk, lambda a, b: jnp.minimum(b, geo.last_k(a)))
    stat_spec = pl.BlockSpec((2, 8, bq), lambda g, a, b: (g, 0, a))
    dqk = functools.partial(_dq_kernel, scale=scale, block_q=bq, block_k=bk,
                            half_of=geo.kv_half)
    dq = _pallas_call(
        dqk, name="flash_gqa_dq",
        grid=(geo.n * qp, geo.nq, geo.nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, stat_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32)],
    )(q, k, v, do, o, lse8)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, n_head, n_kv_head, block_q, block_k):
    geo = _Geometry(q, k, n_head, n_kv_head, block_q, block_k)
    return _flash_fwd(q, k, v, scale, geo)[0]


def _flash_vjp_fwd(q, k, v, scale, n_head, n_kv_head, block_q, block_k):
    geo = _Geometry(q, k, n_head, n_kv_head, block_q, block_k)
    o, lse8 = _flash_fwd(q, k, v, scale, geo)
    return o, (q, k, v, o, lse8)


def _flash_vjp_bwd(scale, n_head, n_kv_head, block_q, block_k, res, do):
    q, k, v, o, lse8 = res
    geo = _Geometry(q, k, n_head, n_kv_head, block_q, block_k)
    return _flash_bwd(q, k, v, o, lse8, do, scale, geo)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_gqa(q, k, v, n_head, n_kv_head, scale=None,
              block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Causal self-attention of q (N, T, n_head*64) over k, v
    (N, T, n_kv_head*64): query head j reads key/value head
    j // (n_head / n_kv_head).  Returns (N, T, n_head*64)."""
    if scale is None:
        scale = HEAD_DIM ** -0.5
    return _flash(q, k, v, float(scale), int(n_head), int(n_kv_head),
                  int(block_q), int(block_k))
