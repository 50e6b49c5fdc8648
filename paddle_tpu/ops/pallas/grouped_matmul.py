"""Grouped matmul over sorted rows: the expert matmuls of the dropless
routed-expert op (`ops/moe_dropless.py`).

`grouped_matmul(lhs (M, K), rhs (G, K, N), group_sizes (G,)) -> (M, N)`:
rows `[sum(sizes[:g]), sum(sizes[:g + 1]))` of `lhs` meet `rhs[g]`, as
`jax.lax.ragged_dot` has it, and rows past `sum(group_sizes)` come out
as exact ZEROS (a ragged dot says nothing of them).  Three kernels, one
name (`ragged_dot`, the name the records and the benchmark's readers
already know the product by), tied by a `custom_vjp`:

- forward and dX (`_rows_kept`): grid (N tiles, visits, K tiles).  The
  row tiles are walked group by group, as
  `jax.experimental.pallas.ops.tpu.megablox` walks them: a visit is one
  (group, row tile) pair, a tile that straddles two groups is visited
  once for each, and a visit stores its group's rows only (the first
  visit of a tile zeroes the others).  What a visit reads, writes and
  masks comes from ONE scalar-prefetched table (`_visits`), worked out
  once a row buffer for the six kernels that share it.  dX is the same
  kernel with the weight read transposed through its index map,
  contracted on its last axis in the kernel, never materialised.  The
  row tiles past the groups' sum follow as visits that read nothing
  and store zeros;
- dW (`_rows_contracted`): grid (N tiles, K tiles, visits), `(M, K) x
  (M, N) -> (G, K, N)`, the rows contracted group by group into a
  float32 block that leaves for HBM when the group changes.  An empty
  group is visited once, to write its zeros.

WORK FOLLOWS THE REAL ROWS: the number of visits is data (the grid's
bound is dynamic), `cdiv(rows, tile)` and at most one more a group; an
empty group costs nothing forward and one visit backward; every
row in one group works and costs what it costs.  Operands go to the MXU
in their own dtype, sums are float32 in VMEM, one cast on the way out.

TILES COME FROM THE SHAPES (`tiles_for`; no flag, no attribute, no
environment variable): 128 rows, and of the whole 128-multiples that
divide K and N the pair within the default scoped VMEM (no
`vmem_limit_bytes`) that moves least between HBM and VMEM.
Where it fits, a group's whole (K, N) weight stays in VMEM while the
group's row tiles pass (read once a group, not once a row tile); next
N is cut and K kept whole, which keeps that.  A width that is no
multiple of 128, or rows that no tile divides, keep
`jax.lax.ragged_dot`; `observe.monitoring` counts the
products traced each way (`grouped_matmuls_kernel` / `_xla`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

KERNEL = "ragged_dot"
LANES = 128
# the row tile: 128 rows, or the largest smaller half that divides the
# buffer.  Timed alone on the chip at the four cells' shapes (PERF.md,
# PR 40): 512 rows lost to 256 wherever both fit (a tile that straddles
# a group's edge is computed once for each group), 256 beat 128 by 3%
# where a group holds 2000 rows and lost by 6% where it holds 500; but
# Mosaic unrolls a tile's matmul, a kernel's code doubles with its rows
# (the slowest cell's executable 386 -> 519 MB at 256: +2.8 s to read it
# back from the cache in every warm start), and a step holds one for
# every layer, row buffer and product
DEFAULT_BLOCK_ROWS = 128
MIN_BLOCK_ROWS = 16
# what one call may take of Mosaic's default 16 MiB of scoped VMEM by
# `_kept_bytes` / `_contracted_bytes`, which count the pipeline's blocks
# as Mosaic does and its temporaries from above (calibrated against the
# compiler's own refusals); the verdict is Mosaic's
# (tests/test_chip_compile_cells.py)
VMEM_BUDGET = (16 << 20) - (1 << 19)


def _register_costs():
    from . import register_kernel_cost
    from ...observe.cost import ragged_dot_cost

    register_kernel_cost(KERNEL, ragged_dot_cost)


_register_costs()


def _pallas_call(*args, **kw):
    from . import pallas_call

    return pallas_call(*args, name=KERNEL, **kw)


# -- the tile rule ----------------------------------------------------------

def _cuts(width):
    """The 128-multiples that divide `width`, largest first."""
    units = width // LANES
    return [LANES * u for u in range(units, 0, -1) if units % u == 0]


def _row_tile(m):
    """The row tile of an `m`-row buffer, or None."""
    tm = DEFAULT_BLOCK_ROWS
    while tm >= MIN_BLOCK_ROWS and m % tm:
        tm //= 2
    return tm if tm >= MIN_BLOCK_ROWS else None


def _kept_bytes(tm, tk, tn, k, itemsize):
    """VMEM of one `_rows_kept` call: lhs, rhs and out blocks twice
    (the pipeline's two buffers), the float32 product and what the
    store makes of it (three tiles at most), and the float32 sum where
    K is cut."""
    blocks = 2 * itemsize * (tm * tk + tk * tn + tm * tn)
    # float32 rows under a "highest" product (the parity scripts'): the
    # row tile's bfloat16 parts (calibrated: Mosaic took 16.33 MiB for
    # (128, 2304, 512) where the lines above count 12.5, and takes every
    # tiling the five earlier cells' float32 shapes get: PR 65)
    parts = 12 * tm * tk if itemsize == 4 else 0
    return blocks + parts + 4 * tm * tn * (3 if tk == k else 4)


def _kept_traffic(m, k, n, groups, tm, tk, tn):
    """Elements one call moves between HBM and VMEM: the rows once an
    N tile, a group's weight once while it stays (K whole) or once a
    visit, the result once."""
    weights = (groups if tk == k else m // tm + groups) * k * n
    return m * k * (n // tn) + weights + m * n


def _contracted_bytes(tm, tk, tn, k, itemsize):
    """VMEM of one `_rows_contracted` call: the blocks twice, the
    float32 sum and product, the masked and the transposed operand."""
    del k
    blocks = 2 * itemsize * (tm * tk + tm * tn + tk * tn)
    return blocks + 2 * 4 * tk * tn + 2 * itemsize * tm * max(tk, tn)


def _contracted_traffic(m, k, n, groups, tm, tk, tn):
    del tm
    return m * k * (n // tn) + m * n * (k // tk) + groups * k * n


def _tiling(m, k, n, groups, itemsize, nbytes, traffic):
    """(tm, tk, tn): of the tilings within the budget the one that
    moves least; None where none is."""
    tm = _row_tile(m)
    fits = [(traffic(m, k, n, groups, tm, tk, tn), (tm, tk, tn))
            for tk in _cuts(k) for tn in _cuts(n)
            if nbytes(tm, tk, tn, k, itemsize) <= VMEM_BUDGET]
    return min(fits)[1] if fits else None


@functools.lru_cache(maxsize=None)
def tiles_for(m, k, n, groups, itemsize):
    """The (tm, tk, tn) of the three kernels of `(m, k) x (groups, k,
    n)`: forward, dX (its K is the product's N) and dW; or None where
    the rule cannot tile the shape.  From the shape alone."""
    if k % LANES or n % LANES or _row_tile(m) is None:
        return None
    tilings = (
        _tiling(m, k, n, groups, itemsize, _kept_bytes, _kept_traffic),
        _tiling(m, n, k, groups, itemsize, _kept_bytes, _kept_traffic),
        _tiling(m, k, n, groups, itemsize, _contracted_bytes,
                _contracted_traffic))
    return None if None in tilings else tilings


# -- the visits -------------------------------------------------------------

# rows of the visit tables (scalar prefetch, one table a kernel): what a
# kernel would else work out from the groups' offsets on every grid step
# is worked out here once, for all the kernels that walk the groups.  A
# kernel is traced and lowered once a SHAPE, 18 a share's step, in every
# warm start, and an equation less in it is one less 18 times
K_TILE, K_READ_TILE, K_READ_GROUP, K_LO, K_HI, K_FIRST = range(6)
C_TILE, C_GROUP, C_LO, C_HI, C_OPENS, C_CLOSES = range(6)


@functools.partial(jax.jit, static_argnames=("m", "tm"))
def _visits(group_sizes, m, tm):
    """The (group, row tile) pairs the kernels' grids walk over an
    `m`-row buffer, in order: `(kept (6, V), contracted (6, V), counts
    (3,))`, int32 tables with V = tiles + G static; how many of a
    table's visits are made is data (`counts`; the others never are).
    A group with rows is visited once for every row tile it touches;
    `LO` and `HI` bound the group's rows inside the tile.

    For the kernel that keeps the rows the tiles wholly past the
    groups' sum follow as visits of no group (it must write them):
    `LO` = `HI` = 0, and `READ_TILE` / `READ_GROUP`, what the index
    maps read, are those of the last visit that multiplied, so a visit
    of the tail fetches nothing; `FIRST` marks the first visit of a
    tile, which zeroes the rows of no group (yet).  For the kernel that
    contracts the rows an empty group gets one visit (it must write ITS
    zeros), and `OPENS` / `CLOSES` mark a group's first and last visit.

    The tables serve every buffer of FEWER rows that holds the groups'
    rows as well (a share's three row buffers, `row_visits`): only the
    tail is shorter there, so `counts` = (the first kernel's visits
    before the tail, the row tiles the groups touch, the second
    kernel's visits) and a kernel adds its own buffer's tail."""
    g = group_sizes.shape[0]
    tiles_m = m // tm
    # (`lax` and not `jnp` throughout: `jnp.where`, `clip`, `cumsum` and
    # `//` are jitted functions or a dozen equations of care each, and
    # this function is traced and lowered in every warm start)
    sizes = group_sizes.astype(jnp.int32)
    ends = jax.lax.cumsum(sizes)
    starts = ends - sizes
    first_tile = jax.lax.div(starts, jnp.int32(tm))
    last_tile = jax.lax.div(ends + (tm - 1), jnp.int32(tm))
    touched = last_tile - first_tile
    used = last_tile[g - 1:]                            # (1,)
    has_rows = sizes > 0
    v = jax.lax.iota(jnp.int32, tiles_m + g)
    zero, one = jnp.zeros(1, jnp.int32), jnp.ones(1, jnp.int32)

    def walk(first_tile, starts, ends, count):
        # visit -> its group, row tile and the group's rows, by a
        # (V, G) mask and one sum (no `repeat`, no gather)
        upto = jax.lax.cumsum(count)
        before = upto - count
        mine = jnp.logical_and(v[:, None] >= before, v[:, None] < upto)
        of_group = jnp.stack([jax.lax.iota(jnp.int32, count.shape[0]),
                              first_tile - before, starts, ends])
        shape = (4,) + mine.shape
        group, tile, start, end = jnp.sum(jax.lax.select(
            jnp.broadcast_to(mine, shape),
            jnp.broadcast_to(of_group[:, None, :], shape),
            jnp.zeros(shape, jnp.int32)), axis=2)
        tile = jax.lax.clamp(0, tile + v, tiles_m - 1)
        row0 = tile * tm
        return (group, tile, jax.lax.clamp(0, start - row0, tm),
                jax.lax.clamp(0, end - row0, tm), upto[-1])

    def changes(x):
        return (x[1:] != x[:-1]).astype(jnp.int32)

    # the tail rides as group G, of no rows, from the first unused tile
    group, tile, lo, hi, kept_count = walk(
        jnp.concatenate([first_tile, used]),
        jnp.concatenate([starts, zero]), jnp.concatenate([ends, zero]),
        jnp.concatenate([jax.lax.select(has_rows, touched,
                                        jnp.zeros_like(touched)),
                         tiles_m - used]))
    is_tail = group == g
    last_group = jnp.max(jax.lax.select(
        has_rows, jax.lax.iota(jnp.int32, g), jnp.zeros_like(sizes)))
    kept = jnp.concatenate([
        tile,
        jax.lax.select(is_tail, jnp.broadcast_to(
            jax.lax.max(used - 1, zero), tile.shape), tile),
        jax.lax.select(is_tail, jnp.broadcast_to(last_group, group.shape),
                       group),
        lo, hi, one, changes(tile)]).reshape(6, -1)
    group, tile, lo, hi, count = walk(
        first_tile, starts, ends,
        jax.lax.select(has_rows, touched, jnp.ones_like(touched)))
    contracted = jnp.concatenate([
        tile, group, lo, hi, one, changes(group),
        jax.lax.max(jnp.concatenate([changes(group), one]),
                    (v == count - 1).astype(jnp.int32))]).reshape(6, -1)
    return kept, contracted, jnp.stack([
        kept_count - (tiles_m - used[0]), used[0], count])


def _rows_mask(table, lo, hi, v, tm, shape):
    """`shape` bool: the tile's rows [lo, hi) of visit `v`."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return jnp.broadcast_to(jnp.logical_and(rows >= table[lo, v],
                                            rows < table[hi, v]), shape)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                               preferred_element_type=jnp.float32)


# -- (M, K) x (G, K, N) -> (M, N): forward, and dX with `transposed` --------

def _kept_kernel(visits, lhs_ref, rhs_ref, out_ref, *acc, tm, tiles_k,
                 transposed):
    from jax.experimental import pallas as pl

    v, kb = pl.program_id(1), pl.program_id(2)

    def store(x):
        # the group's rows; the tile's first visit writes the rows of
        # no group (yet) as zeros, a later one keeps what the earlier
        # ones wrote.  (`lax.select`, here and below: `jnp.where` is a
        # jitted function of its own, traced and lowered in every
        # kernel)
        kept = jax.lax.select(
            jnp.broadcast_to(visits[K_FIRST, v] == 1, x.shape),
            jnp.zeros_like(x), out_ref[...].astype(jnp.float32))
        out_ref[...] = jax.lax.select(
            _rows_mask(visits, K_LO, K_HI, v, tm, x.shape), x,
            kept).astype(out_ref.dtype)

    def multiply():
        part = _dot(lhs_ref[...], rhs_ref[...],
                    ((1,), (1,)) if transposed else ((1,), (0,)))
        if tiles_k == 1:
            store(part)
            return
        acc_ref, = acc

        @pl.when(kb == 0)
        def _():
            acc_ref[...] = part

        @pl.when(kb > 0)
        def _():
            acc_ref[...] += part

        @pl.when(kb == tiles_k - 1)
        def _():
            store(acc_ref[...])

    def zeros():
        out_ref[...] = jnp.zeros_like(out_ref)

    # (a two-armed cond, not a `when` an arm: an arm costs set-up)
    jax.lax.cond(visits[K_HI, v] == 0, zeros, multiply)


# jitted: a program's layers share their shapes, so a kernel is traced
# and lowered once a shape and called from every layer (megablox's are
# jitted for the same reason; an 8-layer share's step holds 264 calls of
# 18 kernels)
@functools.partial(jax.jit, static_argnames=("tiling", "transposed"))
def _rows_kept(lhs, rhs, visits, tiling, transposed=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    tm, tk, tn = tiling
    tiles_k, tiles_n = k // tk, n // tn
    visits, _, counts = visits
    # the groups' visits, and this buffer's row tiles past them
    count = counts[0] + jax.lax.max(m // tm - counts[1], 0)

    def k_of(kb, v, visits):
        # (a visit of the tail stays on the last K block fetched)
        if tiles_k == 1:
            return 0
        return jnp.where(visits[K_HI, v] == 0, tiles_k - 1, kb)

    def lhs_map(nb, v, kb, visits):
        return visits[K_READ_TILE, v], k_of(kb, v, visits)

    def rhs_map(nb, v, kb, visits):
        if transposed:
            return visits[K_READ_GROUP, v], nb, k_of(kb, v, visits)
        return visits[K_READ_GROUP, v], k_of(kb, v, visits), nb

    def out_map(nb, v, kb, visits):
        return visits[K_TILE, v], nb

    kernel = functools.partial(_kept_kernel, tm=tm, tiles_k=tiles_k,
                               transposed=transposed)
    return _pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles_n, count, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_map),
                pl.BlockSpec((None, tn, tk) if transposed
                             else (None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=([] if tiles_k == 1 else
                            [pltpu.VMEM((tm, tn), jnp.float32)])),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(visits, lhs, rhs)


# -- (M, K) x (M, N) -> (G, K, N): dW ---------------------------------------

def _contracted_kernel(visits, lhs_ref, rhs_ref, out_ref, acc_ref, *, tm,
                       mask_lhs):
    from jax.experimental import pallas as pl

    v = pl.program_id(2)

    @pl.when(visits[C_OPENS, v] == 1)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # one operand's rows of other groups zeroed, the narrower one's (on
    # every visit: a second dot for the tiles wholly inside a group
    # would double the kernel's code for a select it saves; an empty
    # group's one visit adds zeros)
    lhs, rhs = lhs_ref[...], rhs_ref[...]
    if mask_lhs:
        lhs = jax.lax.select(
            _rows_mask(visits, C_LO, C_HI, v, tm, lhs.shape), lhs,
            jnp.zeros_like(lhs))
    else:
        rhs = jax.lax.select(
            _rows_mask(visits, C_LO, C_HI, v, tm, rhs.shape), rhs,
            jnp.zeros_like(rhs))
    acc_ref[...] += _dot(lhs, rhs, ((0,), (0,)))

    @pl.when(visits[C_CLOSES, v] == 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("groups", "tiling", "out_dtype"))
def _rows_contracted(lhs, rhs, visits, groups, tiling, out_dtype):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = rhs.shape[1]
    tm, tk, tn = tiling
    _, visits, counts = visits

    def tile_of(v, visits):
        # (an empty group past the rows of this buffer sits on a tile
        # that a longer buffer has)
        return jax.lax.min(visits[C_TILE, v], m // tm - 1)

    kernel = functools.partial(_contracted_kernel, tm=tm, mask_lhs=tk <= tn)
    return _pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tn, k // tk, counts[2]),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda nb, kb, v, visits: (tile_of(v, visits),
                                                        kb)),
                pl.BlockSpec((tm, tn),
                             lambda nb, kb, v, visits: (tile_of(v, visits),
                                                        nb))],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda nb, kb, v, visits: (visits[C_GROUP, v], kb, nb)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(visits, lhs, rhs)


# -- the product and its gradient -------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _product(lhs, rhs, visits, tilings):
    return _rows_kept(lhs, rhs, visits, tilings[0])


def _product_fwd(lhs, rhs, visits, tilings):
    return _product(lhs, rhs, visits, tilings), (lhs, rhs, visits)


def _product_bwd(tilings, res, g):
    lhs, rhs, visits = res
    g = g.astype(lhs.dtype)
    dlhs = _rows_kept(g, rhs, visits, tilings[1], transposed=True)
    drhs = _rows_contracted(lhs, g, visits, rhs.shape[0], tilings[2],
                            rhs.dtype)
    return dlhs, drhs, None


_product.defvjp(_product_fwd, _product_bwd)


def row_visits(group_sizes, rows):
    """What `grouped_matmul` takes as `visits`: the visit tables of
    `group_sizes` over a buffer of `rows` rows, for every product over
    that buffer or a shorter one that holds the groups' rows (a layer's
    three products, forward and backward, in each of a share's row
    buffers: worked out once a layer, traced and lowered once a
    program).  None where `rows` is no whole number of default row
    tiles: a product then works out its own."""
    if rows % DEFAULT_BLOCK_ROWS:
        return None
    return _visits(group_sizes, m=rows, tm=DEFAULT_BLOCK_ROWS)


def grouped_matmul(lhs, rhs, group_sizes, visits=None):
    """`jax.lax.ragged_dot(lhs, rhs, group_sizes)` with zeros for the
    rows past the groups' sum: (M, K) x (G, K, N) -> (M, N) in the
    operands' dtype; `visits`: `row_visits(group_sizes, rows)` for some
    `rows` >= M, if the caller has it.  The Pallas kernels where
    `tiles_for` tiles the shape; where it does not, the ragged dot
    between two masks: those rows zero going in (which zeroes their
    gradient: a ragged dot says nothing of them) and coming out."""
    from ...observe.monitoring import runtime_stats

    dtype = jnp.result_type(lhs.dtype, rhs.dtype)
    lhs, rhs = lhs.astype(dtype), rhs.astype(dtype)
    (m, k), (g, _, n) = lhs.shape, rhs.shape
    tilings = tiles_for(m, k, n, g, jnp.dtype(dtype).itemsize)
    runtime_stats.record_grouped_matmul(tilings is not None)
    if tilings is None:
        mine = (jnp.arange(m, dtype=jnp.int32) < jnp.sum(group_sizes))[:, None]
        return jnp.where(mine, jax.lax.ragged_dot(
            jnp.where(mine, lhs, 0), rhs, group_sizes), 0)
    tm = tilings[0][0]
    if visits is None or tm != DEFAULT_BLOCK_ROWS:
        visits = _visits(group_sizes, m=m, tm=tm)
    assert visits[0].shape[1] >= m // tm + g, (visits[0].shape, m, g)
    # jax keys a jitted function's trace on the mesh context, which
    # is None while a forward pass is traced and the EMPTY mesh in a
    # backward pass: naming the mesh that holds makes them one key,
    # so a recomputed product meets its forward kernel traced
    # (tests/test_grouped_matmul.py holds jax to it)
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        return _product(lhs, rhs, visits, tilings)
