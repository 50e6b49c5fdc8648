"""Bidirectional flash attention over a PACKED row axis (Pallas, TPU):
row i may read key j only where `segment_ids[i] == segment_ids[j]`.
A segment is a RUN of consecutive rows of one id and its bounds are DATA
(a device array of the step; an id that comes again after another is
another segment: `segment_runs`, in both lowerings); a negative id is a
padding row, which reads nothing, is read by nobody, and whose output
and gradients are 0.  What
a native-resolution vision tower runs: the patches of a step's images
side by side on one row axis, attention all-to-all INSIDE an image.

Operands head-major (N, P, H * d) as a projection emits them,
`segment_ids` (N, P) int32.  Heads of a lane count Mosaic cannot
address as a tile of the head-major array (72: a slab of 72 lanes at
offset 72 h is no tile) are laid out at the next multiple of 128 with
zero lanes behind them, around the kernels: a contraction of 72 fills a
128-deep MXU pass either way, the zero lanes add nothing to a score and
carry no gradient, and the price is the q / k / v / o bytes at
128 / 72.  That layout is ONE Pallas pass an array each way
(`head_lanes.py`, wherever `lane_kernels_take` the shape; PR 74), and
with `rotary` q and k turn over (row, column) pairs inside it; as XLA
compositions (`_to_lane_tiles`, `_from_lane_tiles`, the caller's
`_rope`: the lowering of every other shape and the kernels' reference)
the same passes cost `kimivl-8k` four times the kernels' time.

**A list of visits made from a device array.**  Because segments are
contiguous, the keys a query TILE may read are one run of key tiles:
from the tile where the segment of its first valid row begins to the
tile where the segment of its last valid row ends.  `visit_table`
computes every tile's run in XLA from `segment_ids` (two cumulative
scans over P int32 and a few reductions a tile) and lays the runs of
all tiles end to end as ONE flat list, a column a visit (the outer
tile, the inner tile, FIRST / LAST of the outer tile's run, whether the
visit is REAL, and for the backward pass the dq tile its output holds
and whether the visit opens or completes it), which both kernels take
as a scalar-prefetched table (`_Band.visits` of `flash_attention.py` is
the same shell over a table made on the host from the shape; this one
is made on the device from data, and its LENGTH is what the shape
bounds).  The list's tail, past the step's real visits, names the last
real visit's tiles again, so nothing is fetched and nothing computed.
Tiles are square (`block` x `block`): "some segment touches both tiles"
is then a symmetric relation, the key-major list the backward pass
walks is the query-major list with the roles exchanged, and ONE table
serves both passes.

**A visit costs what it allows.**  A visit fetches whole tiles, but
its products are made a pair of SUB-BLOCKS (`SUB_BLOCK` rows, 256) at a
time, and only for the pairs that share a segment: `sub_table`, the
second scalar-prefetched table, holds the lowest and the highest
segment among each sub-block's valid rows and, where all its rows are
one segment's, which.  A pair wholly inside one segment runs with no
compare and no select, one that a boundary or a padding row crosses is
masked, one that shares nothing is skipped.  So the work of a call
follows the ALLOWED pairs in units of 256 x 256 and not the tiles its
bounds happen to cross: images whose row counts are multiples of 256
cost the same in every order on the row axis (PR 73: with whole tiles
of 1024 a product, the drawn order of a step's sixteen images moved the
visited tiles of a head from 78 to 104 and the step's time with them).

The list's static length (`visit_bound`): a segment of L rows touches
at most L / b + 2 tiles, and the rows of a tile's run are the rows of
the segments that touch it, so the runs of all tiles together hold at
most P (M / b + 2) rows for segments of at most M = `max_segment_rows`
rows, and each run at most two tiles more than its rows fill:
P / b x (M / b + 4) visits (12 a tile at M = 4096, b = 512, where the
rectangle of a tile's longest possible run, (b + 2 M) / b + 1, is 18
and a whole rectangle of the row axis 48).  `max_segment_rows` None:
the bound is the rectangle's.  Segments LONGER than
`max_segment_rows` may need more visits than the list holds; the tiles
past its end would be left unwritten, so `visit_table` says which row
axes' lists were cut and their whole output is NaN (a loss that is not
finite, not attention over what memory held).

Backward under `custom_vjp`, ONE kernel (`flash_attention.py`'s layout
(A), `_bwd_kernel`: key-major, dk and dv tile accumulators, dq of the
head's whole row axis in float32 scratch, a dq tile leaving on the
visit that completes it), p and ds once a pair of sub-blocks (the
terms of `flash_attention._bwd_p_ds`), five score-sized dots.  BOTH
kernels hold their scores (keys, queries): a query's statistics along
the lanes.  Scores, soft-max and delta float32; the dots take their
operands in the dtype they arrive in and accumulate float32.

`segment_attention_takes` (the call's shapes alone: whole tiles, lanes
a multiple of 128 once laid out, the dq accumulator within
`flash_attention.FUSED_ACCUMULATOR_BUDGET`) chooses between the kernels
and `segment_attention_xla`, the same masked attention as XLA
differentiates it, blocked over query tiles so that a score block fits:
the fall-back and the kernels' test reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import head_lanes, interpret, keep_residuals, pallas_call
from . import DECLARED_AT_CALL, register_kernel_cost
from .flash_attention import (_VMEM_LIMIT, FUSED_ACCUMULATOR_BUDGET, NEG_INF,
                              _add_dk_dv, _add_dq, _dot, _init_softmax,
                              _vmem_params)

# Square tiles, forward and backward (one table serves both passes):
# 1024 where the row axis is whole tiles of 1024, else 512
# (`default_block`), their products in sub-blocks of 256.  Alone on v5e
# at 24576 rows, 16 heads of 72 lanes laid out at 128, sixteen segments
# of 256 .. 4096 rows, bf16, ms a call forward / forward + backward
# (PERF.md, PR 73), a whole tile a product and over four drawn orders of
# the segments: 1024 x 1024 7.70-8.89 / 20.61-24.34 (80-100 visits a
# head), 512 x 512 11.72 / 25.56; a pair of sub-blocks a product, five
# orders: 12.37-12.46 / 26.44-26.62 (16.57-16.66 / 30.61-30.80 with the
# forward's scores (queries, keys): a reduce along the lanes a row and
# 256 keys).  Slower than the best order of whole tiles, and the SAME in
# every order, which is what a step's p95 over drawn batches needs.  The
# whole rectangle through `flash_attention` with a key bias 49.6 / 149.7,
# the XLA lowering below 167.6 / 423.9.  The soft-max's vector work a
# score, not the MXU, bounds a product at 72 (128) lanes a head
DEFAULT_BLOCK = 512
LARGE_BLOCK = 1024
SUB_BLOCK = 256
# the rows of the table of visits, and of the table of sub-blocks
V_A, V_B, V_FIRST, V_LAST, V_REAL, V_HELD, V_B_FIRST, V_B_LAST = range(8)
S_LOW, S_HIGH, S_ONE = range(3)

for _kernel in ("fwd", "bwd"):
    register_kernel_cost("flash_segment_" + _kernel, DECLARED_AT_CALL)


def visit_bound(rows, block, max_segment_rows=None):
    """The static length of the list of visits of ONE row axis of
    `rows` rows in tiles of `block`: see the module's docstring."""
    tiles = rows // block
    if max_segment_rows is None or max_segment_rows >= rows:
        return tiles * tiles
    return tiles * min(tiles, -(-int(max_segment_rows) // block) + 4)


def _one_table(seg, block, length):
    p = seg.shape[0]
    tiles = p // block
    i32 = jnp.int32
    idx = jnp.arange(p, dtype=i32)
    valid = seg >= 0
    opens = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    closes = jnp.concatenate([seg[1:] != seg[:-1], jnp.ones((1,), bool)])
    # the first and the last row of each row's segment
    start = jax.lax.cummax(jnp.where(opens, idx, 0))
    end = jax.lax.cummin(jnp.where(closes, idx, p - 1), reverse=True)
    by_tile = lambda x: x.reshape(tiles, block)    # noqa: E731
    lo = jnp.min(by_tile(jnp.where(valid, start, p)), axis=1)
    hi = jnp.max(by_tile(jnp.where(valid, end, -1)), axis=1)
    own = jnp.arange(tiles, dtype=i32)
    empty = hi < 0              # a tile of padding rows visits itself
    first = jnp.where(empty, own, lo // block)
    last = jnp.where(empty, own, hi // block)
    count = last - first + 1
    ends = jnp.cumsum(count)
    begins = ends - count
    v = jnp.arange(length, dtype=i32)
    real = v < ends[-1]
    a = jnp.minimum(jnp.searchsorted(ends, v, side="right").astype(i32),
                    tiles - 1)
    step = v - begins[a]
    a = jnp.where(real, a, tiles - 1)
    b = jnp.where(real, first[a] + step, last[tiles - 1])
    # the visit that completes inner tile t: its last outer tile's
    completes = begins[last] + own - first[last]
    held = jnp.minimum(jnp.searchsorted(completes, v, side="left")
                       .astype(i32), tiles - 1)
    table = jnp.stack([
        a, b, real & (step == 0), real & (step == count[a] - 1), real,
        held, real & (a == first[b]), real & (a == last[b])]).astype(i32)
    return table, jnp.minimum(ends[-1], length).astype(i32), ends[-1] > length


def visit_table(segment_ids, block, length):
    """((8, N * length) int32 table, (N,) int32 real visits, (N,) bool
    whether a row axis needs more visits than `length`: its list is cut)
    of `segment_ids` (N, P): the runs of all tiles end to end, a row
    axis after the other; see the module's docstring."""
    tables, visits, cut = jax.vmap(
        lambda seg: _one_table(seg, block, length))(segment_ids)
    n = segment_ids.shape[0]
    return (jnp.transpose(tables, (1, 0, 2)).reshape(8, n * length),
            visits, cut)


def sub_table(seg, sub):
    """(3, N * P / sub) int32 of the RUNS `seg` (N, P) (`segment_runs`:
    they never fall along a row axis), a column a block of `sub` rows, a
    row axis after the other: the lowest and the highest run among the
    block's valid rows (low above high where it has none) and, where
    every row of it is ONE run's, which (else -1).  Two blocks share a
    segment iff neither's lowest run is above the other's highest."""
    n, p = seg.shape
    blocks = seg.reshape(n, p // sub, sub)
    high = jnp.max(blocks, axis=2)
    low = jnp.min(jnp.where(blocks >= 0, blocks, jnp.iinfo(jnp.int32).max),
                  axis=2)
    one = jnp.where(jnp.min(blocks, axis=2) == high, high, -1)
    return jnp.stack([low, high, one]).reshape(3, -1).astype(jnp.int32)


# -- the kernels --------------------------------------------------------------

def _sub_pairs(subs, first_a, first_b, per_tile, sub):
    """[(rows of tile A, rows of tile B, masked, whole)] over the pairs
    of sub-blocks of a visit's two tiles, whose first sub-blocks are
    columns `first_a` and `first_b` of `subs`: scalars that say whether
    the pair is crossed by a boundary or a padding row, or lies wholly
    inside one segment; neither where it shares none."""
    def read(first):
        return [[subs[row, first + i] for row in (S_LOW, S_HIGH, S_ONE)]
                for i in range(per_tile)]

    pairs = []
    for i, (low_a, high_a, one_a) in enumerate(read(first_a)):
        for j, (low_b, high_b, one_b) in enumerate(read(first_b)):
            whole = (one_a >= 0) & (one_a == one_b)
            shares = (low_a <= high_b) & (low_b <= high_a)
            pairs.append((slice(i * sub, (i + 1) * sub),
                          slice(j * sub, (j + 1) * sub),
                          shares & jnp.logical_not(whole), whole))
    return pairs


def _fwd_kernel(visits, subs, q_ref, k_ref, v_ref, col_ref, row_ref, o_ref,
                lse_ref, m_scr, l_scr, acc_scr, *, scale, heads, length,
                block, sub, rows):
    """Query-major: an online soft-max, a visit a grid step, a pair of
    sub-blocks a score block.  Scores are (keys, queries), as the
    backward kernel has them, so a query's running max and sum lie along
    the LANES (a 256-wide score block amortises nothing of a reduce
    along the lanes a row: 16.6 ms a call against 8.8, PR 73) and the
    accumulator is (d, queries), turned once, when the tile leaves.
    `col_ref` (b, 1): the KEY tile's ids down the sublanes; `row_ref`
    (1, b): the query tile's along the lanes."""
    from jax.experimental import pallas as pl

    n = pl.program_id(0) // heads
    v = pl.program_id(1) + n * length
    pl.when(visits[V_FIRST, v] == 1)(functools.partial(
        _init_softmax, m_scr, l_scr, acc_scr))

    def compute(at, keys, masked):
        s = _dot(k_ref[0, keys], q_ref[0, at], ((1,), (1,))) * scale
        if masked:
            s = jnp.where(col_ref[0, keys] == row_ref[0, :, at], s, NEG_INF)
        m_prev = m_scr[:, at]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, at] = alpha * l_scr[:, at] + jnp.sum(p, axis=0,
                                                      keepdims=True)
        values = v_ref[0, keys]
        acc_scr[:, at] = acc_scr[:, at] * alpha + _dot(
            values, p.astype(values.dtype), ((0,), (0,)))
        m_scr[:, at] = m_new

    @pl.when(visits[V_REAL, v] == 1)
    def _visit():
        per_tile = block // sub
        first = n * (rows // sub)
        for at, keys, masked, whole in _sub_pairs(
                subs, first + visits[V_A, v] * per_tile,
                first + visits[V_B, v] * per_tile, per_tile, sub):
            pl.when(masked)(functools.partial(compute, at, keys, True))
            pl.when(whole)(functools.partial(compute, at, keys, False))

    @pl.when(visits[V_LAST, v] == 1)
    def _write():
        l = jnp.maximum(l_scr[:], 1e-30)
        # a padding row's output is 0 whatever its masked scores summed to
        o = jnp.where(row_ref[0] >= 0, acc_scr[:] / l, 0.0)
        o_ref[0] = o.T.astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_scr[:] + jnp.log(l),
                                      lse_ref.shape[1:])


def _bwd_kernel(visits, subs, q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                col_ref, row_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc,
                dv_acc, *, scale, heads, length, block, sub, rows):
    """Key-major: p and ds once a pair of sub-blocks (the terms of
    `flash_attention._bwd_p_ds`), dk and dv into the key tile's
    accumulators, dq into the whole row axis's; a dq tile leaves on the
    visit that completes it.  `col_ref` (b, 1): the KEY tile's ids;
    `row_ref` (1, b): the query tile's (scores are (keys, queries))."""
    from jax.experimental import pallas as pl

    n = pl.program_id(0) // heads
    v = pl.program_id(1) + n * length
    qb = visits[V_B, v]

    @pl.when(visits[V_FIRST, v] == 1)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(visits[V_B_FIRST, v] == 1)
    def _init_dq():
        dq_acc[qb] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

    def compute(keys, at, masked):
        q, do, o = q_ref[0, at], do_ref[0, at], o_ref[0, at]
        k = k_ref[0, keys]
        p = jnp.exp(_dot(k, q, ((1,), (1,))) * scale
                    - lse_ref[0, 0, at][None, :])
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=1)[None, :]
        ds = p * (_dot(v_ref[0, keys], do, ((1,), (1,))) - delta)
        if masked:
            allowed = col_ref[0, keys] == row_ref[0, :, at]
            p, ds = jnp.where(allowed, p, 0.0), jnp.where(allowed, ds, 0.0)
        _add_dk_dv(p, ds, q, do, dk_acc, dv_acc, scale, at=keys)
        _add_dq(ds, k, dq_acc, scale, at=(qb, at))

    @pl.when(visits[V_REAL, v] == 1)
    def _visit():
        per_tile = block // sub
        first = n * (rows // sub)
        for keys, at, masked, whole in _sub_pairs(
                subs, first + visits[V_A, v] * per_tile, first + qb * per_tile,
                per_tile, sub):
            pl.when(masked)(functools.partial(compute, keys, at, True))
            pl.when(whole)(functools.partial(compute, keys, at, False))

    @pl.when(visits[V_LAST, v] == 1)
    def _write():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(visits[V_B_LAST, v] == 1)
    def _write_dq():
        dq_ref[0] = dq_acc[qb].astype(dq_ref.dtype)


def _specs(block, d, heads, length):
    """The block specs of a (N, P, H * d) operand's tile and of the two
    id layouts, at the table's row `at`; and the statistic's."""
    from jax.experimental import pallas as pl

    def at(row):
        return lambda g, v, visits: visits[row, v + (g // heads) * length]

    def tile(row):
        return pl.BlockSpec((1, block, d), lambda g, v, visits, subs: (
            g // heads, at(row)(g, v, visits), g % heads))

    def col(row):
        return pl.BlockSpec((1, block, 1), lambda g, v, visits, subs: (
            g // heads, at(row)(g, v, visits), 0))

    def lane(row):
        return pl.BlockSpec((1, 1, block), lambda g, v, visits, subs: (
            g // heads, 0, at(row)(g, v, visits)))

    def stat(row):
        return pl.BlockSpec((1, 8, block), lambda g, v, visits, subs: (
            g, 0, at(row)(g, v, visits)))

    return tile, col, lane, stat


def _fwd_vmem_params(block, sub, d, itemsize):
    """`pallas_call` keywords of the forward kernel: a VMEM limit where
    its tiles twice over, its accumulator and two float32 score blocks
    (a pair of sub-blocks each) come near Mosaic's default 16 MiB of
    scoped VMEM, nothing where they fit (at tiles of 1024 in sub-blocks
    of 256: float32 5.0 MiB, bfloat16 3.0 by this count; a call that
    names a limit is scheduled differently, `flash_attention.py`)."""
    from jax.experimental.pallas import tpu as pltpu

    tiles = 2 * 2 * 2 * block * d * itemsize
    if tiles + 4 * block * d + 2 * 4 * sub * sub <= 12 << 20:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=_VMEM_LIMIT)}


def _cost(q, heads, visits, block, dots, passes):
    """The call's declared cost: `dots` score-sized products a visit of
    the list's bound (what the grid may run), and `passes` over a
    head-major operand like `q`."""
    from jax.experimental import pallas as pl

    n, _, hd = q.shape
    return {"cost_estimate": pl.CostEstimate(
        flops=int(2 * dots * n * visits * block * block * hd),
        transcendentals=int(n * heads * visits * block * block),
        bytes_accessed=int(passes * q.size * q.dtype.itemsize))}


@functools.partial(jax.jit, static_argnames=("scale", "heads", "block",
                                             "sub", "length", "interpret"))
def _forward(q, k, v, col, row, table, subs, *, scale, heads, block, sub,
             length, interpret):
    from jax.experimental.pallas import tpu as pltpu

    n, p, hd = q.shape
    d = hd // heads
    tile, colspec, lane, stat = _specs(block, d, heads, length)
    return pallas_call(
        functools.partial(_fwd_kernel, scale=scale, heads=heads,
                          length=length, block=block, sub=sub, rows=p),
        name="flash_segment_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n * heads, length),
            in_specs=[tile(V_A), tile(V_B), tile(V_B), colspec(V_B),
                      lane(V_A)],
            out_specs=[tile(V_A), stat(V_A)],
            scratch_shapes=[pltpu.VMEM((1, block), jnp.float32),
                            pltpu.VMEM((1, block), jnp.float32),
                            pltpu.VMEM((d, block), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((n * heads, 8, p), jnp.float32)],
        interpret=interpret,
        **_cost(q, heads, length, block, 2, 4),
        **_fwd_vmem_params(block, sub, d, q.dtype.itemsize),
    )(table, subs, q, k, v, col, row)


@functools.partial(jax.jit, static_argnames=("scale", "heads", "block",
                                             "sub", "length", "interpret"))
def _backward(q, k, v, do, o, lse8, col, row, table, subs, *, scale, heads,
              block, sub, length, interpret):
    from jax.experimental.pallas import tpu as pltpu

    n, p, hd = q.shape
    d = hd // heads
    tile, colspec, lane, stat = _specs(block, d, heads, length)
    like = jax.ShapeDtypeStruct(q.shape, q.dtype)
    return pallas_call(
        functools.partial(_bwd_kernel, scale=scale, heads=heads,
                          length=length, block=block, sub=sub, rows=p),
        name="flash_segment_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n * heads, length),
            # outer tile A: the key tile; inner tile B: the query tile
            in_specs=[tile(V_B), tile(V_A), tile(V_A), tile(V_B),
                      tile(V_B), stat(V_B), colspec(V_A), lane(V_B)],
            out_specs=[tile(V_HELD), tile(V_A), tile(V_A)],
            scratch_shapes=[
                pltpu.VMEM((p // block, block, d), jnp.float32),
                pltpu.VMEM((block, d), jnp.float32),
                pltpu.VMEM((block, d), jnp.float32)]),
        out_shape=[like, like, like],
        interpret=interpret,
        **_cost(q, heads, length, block, 5, 8),
        **_vmem_params(p * d * 4, sub, sub),
    )(table, subs, q, k, v, do, o, lse8, col, row)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13))
def _flash_segment(q, k, v, rotary, col, row, table, subs, cut, scale, heads,
                   block, sub, length):
    return _flash_segment_fwd(q, k, v, rotary, col, row, table, subs, cut,
                              scale, heads, block, sub, length)[0]


def _flash_segment_fwd(q, k, v, rotary, col, row, table, subs, cut, scale,
                       heads, block, sub, length):
    """Operands and result at the heads' OWN lanes, q and k UNTURNED
    where `rotary` is given: the kernels' 128-lane layout is made around
    each call (`_to_kernel_layout`), the turn with it, so what a
    recompute segment keeps (the output and the logsumexp) is kept at 72
    lanes a head, not 128.  A row axis whose list was `cut` is NaN."""
    o, lse8 = _forward(
        *_to_kernel_layout((q, k, v), heads, rotary, 2), col, row, table,
        subs, scale=scale, heads=heads, block=block, sub=sub, length=length,
        interpret=interpret())
    o, = _from_kernel_layout((o,), heads, q.shape[-1])
    o = jnp.where(cut[:, None, None], jnp.nan, o)
    o, lse8 = keep_residuals(o, lse8)
    return o, (q, k, v, rotary, col, row, table, subs, o, lse8)


def _flash_segment_bwd(scale, heads, block, sub, length, res, do):
    q, k, v, rotary, col, row, table, subs, o, lse8 = res
    grads = _backward(
        *_to_kernel_layout((q, k, v), heads, rotary, 2),
        *_to_kernel_layout((do.astype(q.dtype), o), heads),
        lse8, col, row, table, subs, scale=scale, heads=heads,
        block=block, sub=sub, length=length, interpret=interpret())
    grads = _from_kernel_layout(grads, heads, q.shape[-1], rotary, 2)
    none = [np.zeros(shape, jax.dtypes.float0) for shape in (
        col.shape, row.shape, table.shape, subs.shape,
        q.shape[:1])]                                        # .., cut
    return (*(g.astype(x.dtype) for g, x in zip(grads, (q, k, v))),
            jax.tree.map(jnp.zeros_like, rotary), *none)


_flash_segment.defvjp(_flash_segment_fwd, _flash_segment_bwd)


# -- the op's two lowerings ---------------------------------------------------

def _to_kernel_layout(xs, heads, rotary=None, turned=0):
    """Arrays (N, P, H * d) at the lanes the kernels read, the first
    `turned` of them turned by `rotary`: `head_lanes`' kernel where it
    takes the shape, the XLA composition elsewhere (which is handed
    nothing to turn: `lane_kernels_take`)."""
    _, p, hd = xs[0].shape
    if head_lanes.head_lanes_take(p, heads, hd // heads):
        return head_lanes.to_tiles(xs, heads, rotary, turned)
    assert rotary is None
    return tuple(_to_lane_tiles(x, heads) for x in xs)


def _from_kernel_layout(xs, heads, lanes, rotary=None, turned=0):
    """The mirror, back to `lanes` = H * d; what turns, turns back."""
    if head_lanes.head_lanes_take(xs[0].shape[1], heads, lanes // heads):
        return head_lanes.from_tiles(xs, heads, lanes // heads, rotary,
                                     turned)
    assert rotary is None
    return tuple(_from_lane_tiles(x, heads, lanes) for x in xs)


def lane_kernels_take(rows, heads, d):
    """Whether a call's layout, and with it the rotary turn of its q and
    k, is `head_lanes`' kernels' (by the shape alone); elsewhere the
    layout is XLA's and the caller turns."""
    return (segment_attention_takes(rows, heads, d)
            and head_lanes.head_lanes_take(rows, heads, d))


def _to_lane_tiles(x, heads):
    """(N, P, H * d) -> (N, P, H * D), D the next multiple of 128, each
    head's lanes first and zeros behind them; as it is where D == d.
    The layout as XLA makes it: where `head_lanes` does not take the
    shape, and its kernels' reference."""
    n, p, hd = x.shape
    d = hd // heads
    wide = head_lanes.lane_tiles(d)
    if wide == d:
        return x
    return jnp.pad(x.reshape(n, p, heads, d),
                   ((0, 0), (0, 0), (0, 0), (0, wide - d))
                   ).reshape(n, p, heads * wide)


def _from_lane_tiles(x, heads, lanes):
    """The inverse: (N, P, H * D) -> (N, P, `lanes`), a head's own lanes."""
    n, p, wide = x.shape
    if wide == lanes:
        return x
    return x.reshape(n, p, heads, wide // heads)[
        ..., :lanes // heads].reshape(n, p, lanes)


def default_block(rows):
    """The square tile of a row axis of `rows` rows, by the shape alone."""
    return LARGE_BLOCK if rows % LARGE_BLOCK == 0 else DEFAULT_BLOCK


def segment_attention_takes(rows, heads, d):
    """Whether the kernels take the call, by its shapes alone: whole
    square tiles, and the backward kernel's dq accumulator (the whole
    row axis of one head, float32, at the lanes the head is laid out
    at) within the single backward kernel's budget."""
    return (rows % DEFAULT_BLOCK == 0 and rows >= DEFAULT_BLOCK
            and 4 * rows * head_lanes.lane_tiles(d)
            <= FUSED_ACCUMULATOR_BUDGET)


def tiles_total(n, rows, heads, block=None):
    """The tiles of the whole rectangle, every head's: what a kernel
    that masks and skips nothing would run."""
    return n * heads * (rows // (block or default_block(rows))) ** 2


def segment_runs(segment_ids):
    """(N, P) int32: the number of each row's RUN of consecutive equal
    ids along its row axis, -1 at a padding row (a negative id)."""
    seg = segment_ids.astype(jnp.int32)
    opens = jnp.concatenate([jnp.ones_like(seg[:, :1], bool),
                             seg[:, 1:] != seg[:, :-1]], axis=1)
    return jnp.where(seg < 0, -1, jnp.cumsum(opens, axis=1, dtype=jnp.int32)
                     - 1)


def flash_segment(q, k, v, segment_ids, n_head, scale=None,
                  max_segment_rows=None, block=None, sub_block=None,
                  rotary=None):
    """(Out (N, P, H * d), tiles visited (1,) int32: the real visits of
    the forward pass's list x heads, a device value): the kernels.
    `block` and `sub_block` (tests, and the timing's sweeps): the square
    tile a visit fetches and the sub-block its products are made in,
    `default_block(P)` and `SUB_BLOCK` within it.  `rotary` (cos, sin)
    (N, P, 1, d / 2) float32, where `lane_kernels_take` the shape: q and
    k arrive UNTURNED and turn over pairs (`ops/decoder.py rope` with
    `Positions`) inside the pass that lays them out."""
    n, p, hd = q.shape
    d = hd // n_head
    block = block or default_block(p)
    sub = sub_block or min(block, SUB_BLOCK)
    if block % sub:
        raise ValueError(f"flash_segment: tiles of {block} rows are not "
                         f"whole sub-blocks of {sub}")
    scale = d ** -0.5 if scale is None else scale
    length = visit_bound(p, block, max_segment_rows)
    seg = segment_runs(segment_ids)
    table, visits, cut = visit_table(seg, block, length)
    # a padding row matches nobody: not another padding row either
    col = seg[:, :, None]
    row = jnp.where(seg < 0, -2, seg)[:, None, :]
    if rotary is not None:
        if not head_lanes.head_lanes_take(p, n_head, d):
            raise ValueError(f"flash_segment: no kernel turns {n_head} "
                             f"heads of {d} lanes over {p} rows")
        rotary = head_lanes.tables(*rotary, d)
    o = _flash_segment(q, k, v, rotary, col, row, table, sub_table(seg, sub),
                       cut, float(scale), n_head, block, sub, length)
    return o, (jnp.sum(visits) * n_head).astype(jnp.int32).reshape(1)


def segment_attention_xla(q, k, v, segment_ids, n_head, scale=None,
                          block=DEFAULT_BLOCK):
    """The same masked attention as XLA differentiates it: a soft-max in
    float32 over the keys of the row's own segment, a block of query
    rows at a time (`lax.map`) so that the (H, block, P) scores fit."""
    n, p, hd = q.shape
    d = hd // n_head
    scale = d ** -0.5 if scale is None else scale
    block = block if p % block == 0 else p
    seg = segment_runs(segment_ids)
    f32 = jnp.float32

    def heads(x):
        return x.reshape(n, -1, n_head, d)

    kh, vh = heads(k), heads(v)

    def rows(args):
        qb, segb = args             # (N, block, H, d), (N, block)
        s = jnp.einsum("nqhd,nkhd->nhqk", qb, kh,
                       preferred_element_type=f32) * scale
        allowed = (segb[:, :, None] == seg[:, None, :]) \
            & (segb[:, :, None] >= 0)
        s = jnp.where(allowed[:, None], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        ob = jnp.einsum("nhqk,nkhd->nqhd", pr.astype(v.dtype), vh,
                        preferred_element_type=f32)
        return jnp.where((segb >= 0)[:, :, None, None], ob, 0.0)

    rows = jax.checkpoint(rows)     # a block's scores are made again
    qs = jnp.moveaxis(heads(q).reshape(n, p // block, block, n_head, d), 1, 0)
    segs = jnp.moveaxis(seg.reshape(n, p // block, block), 1, 0)
    o = jax.lax.map(rows, (qs, segs))
    return jnp.moveaxis(o, 0, 1).reshape(n, p, hd).astype(q.dtype)
