"""Rows -> tokens: what a share-holding expert layer does with its
sorted rows on the way back to token order, as one Pallas kernel.

    out[t] = sum over the rows r < n whose token is t of c[r] * vals[r]

`vals` (R, D) is the layer's row buffer (`ops/moe_dropless.py
_held_rows`: the down projection's rows forward, the gradient rows
backward), `c` (R,) float32 the rows' routing weights or absent (1),
the sum float32, `out` (T, D) float32 or, where the caller wants the
sum rounded once on its way out (the gradient of a bfloat16 input),
narrower.  A token has at most k such
rows and most tokens of a share have none: the composition this
replaces gathered one row for EVERY (token, expert) pair, T x k of
them, to sum the eighth or the thirty-second it holds.

Two steps, both on R rows:

- `by_token` and `order_of` (`token_order`: both): the rows are put in
  TOKEN order, a sort of R keys that carries the rows' numbers and
  weights along (a row at or past `n` gets the key T and sorts last;
  one sort of a layer's T x k sorted rows serves each of its row
  buffers, a prefix), after which a tile of consecutive tokens reads
  ONE range of rows.  The (token tile, row chunk) pairs that meet are
  a scalar-prefetched table made the way the grouped matmuls' is
  (`grouped_matmul._visits`: compares and sums, no scatter), and how
  many there are is data: the grid's bound is dynamic, `T / tile + R /
  chunk` at most;
- the kernel: a visit reads one chunk of token-ordered rows and adds
  `selection @ rows` into the tile's float32 block, `selection[i, j]` =
  `c[j]` where row j's token is the tile's i-th and 0 elsewhere.  The
  block stays in VMEM while the visits stay on the tile.

The weights stay float32: the selection is split into three bfloat16
matrices that add up to it exactly (8 + 8 + 8 bits), so that bfloat16
rows meet them in three exact MXU passes summed in float32; without
weights the selection is 0 / 1 and one pass; float32 rows go through
one product at `Precision.HIGHEST`.  The selection product is no model
arithmetic (`mfu`'s numerator does not see it) and the kernel
registers bytes only.

`rows_to_tokens_takes` says from the shape alone whether the kernel
runs a call; `moe_dropless` keeps its composition elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

KERNEL = "rows_to_tokens"
LANES = 128
# tokens a tile, rows a chunk: a share's tile of 128 tokens holds ~128
# rows, so a visit is one square MXU pass a lane group and the work is
# ~3 x 2 x 128 x R x D; timed alone on the chip against (256, 128), (256,
# 256), (512, 256) (PR 50; `tools/time_kernel.py share_rows --sweep ...`)
TOKEN_TILE = 128
ROW_CHUNK = 128
V_TILE, V_CHUNK, V_FIRST = range(3)


def rows_to_tokens_cost(operand_shapes, result_shapes):
    return 0.0, None


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost(KERNEL, rows_to_tokens_cost)


_register_costs()


def rows_to_tokens_takes(r, t, d, token_tile=TOKEN_TILE, row_chunk=ROW_CHUNK):
    """Whether the kernel runs (R, D) rows of T tokens, from the shape
    alone: whole lane groups, whole token tiles, whole row chunks."""
    return d % LANES == 0 and t % token_tile == 0 and r % row_chunk == 0


@functools.partial(jax.jit, static_argnames=("t",))
def _by_token(tokens, n, weights, t):
    r = tokens.shape[0]
    rows = lax.iota(jnp.int32, r)
    # ONE sort carries the rows' numbers, and their weights, along with
    # their tokens: a gather of R scalars costs more than the sort
    # (0.2 ms against 0.02 at R = 24,576, in a step's trace)
    return lax.sort(
        (lax.select(rows < n, tokens.astype(jnp.int32),
                    jnp.full((r,), t, jnp.int32)), rows)
        + (() if weights is None else (weights.astype(jnp.float32),)),
        num_keys=1)


@functools.partial(jax.jit, static_argnames=(
    "rows", "t", "token_tile", "row_chunk"))
def _order_of(by_token, rows, t, token_tile, row_chunk):
    keys, perm, *carried = [x[:rows] for x in by_token]
    tiles, chunks = t // token_tile, rows // row_chunk
    # rows before each tile's end: a compare and a sum, no scatter
    ends = (lax.iota(jnp.int32, tiles) + 1) * token_tile
    hi = jnp.sum(keys[:, None] < ends[None, :], axis=0, dtype=jnp.int32)
    lo = jnp.concatenate([jnp.zeros(1, jnp.int32), hi[:-1]])
    first_chunk = lax.min(lax.div(lo, jnp.int32(row_chunk)), chunks - 1)
    touched = lax.max(
        lax.div(hi + (row_chunk - 1), jnp.int32(row_chunk)) - first_chunk, 1)
    upto = lax.cumsum(touched)
    before = upto - touched
    v = lax.iota(jnp.int32, tiles + chunks)
    mine = jnp.logical_and(v[:, None] >= before, v[:, None] < upto)
    of_tile = jnp.stack([lax.iota(jnp.int32, tiles), first_chunk - before,
                         before])
    shape = (3,) + mine.shape
    tile, chunk, opened = jnp.sum(lax.select(
        jnp.broadcast_to(mine, shape),
        jnp.broadcast_to(of_tile[:, None, :], shape),
        jnp.zeros(shape, jnp.int32)), axis=2)
    visits = jnp.stack([tile, lax.clamp(0, chunk + v, chunks - 1),
                        (v == opened).astype(jnp.int32)])
    lanes = (chunks, 1, row_chunk)
    return (perm, keys.reshape(lanes), visits, upto[-1],
            carried[0].reshape(lanes) if carried else None)


def _one_trace():
    """jax keys a jitted function's trace on the mesh context, which is
    None while a forward pass is traced and the EMPTY mesh in a backward
    pass: naming the mesh that holds makes them one key (`grouped_matmul`
    says more)."""
    return jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh())


def by_token(tokens, n, t, weights=None):
    """The rows of a buffer sorted by token, the sort that `order_of`
    works from: `tokens` (R,) int32 is each row's token, only the rows
    below `n` count (a row at or past `n` gets the key T and, the sort
    being stable, keeps its place among its like: the first R' entries
    are a permutation of the first R' rows for every n <= R' <= R, so
    ONE sort serves every shorter buffer of the same rows), `weights`
    (R,) float32 are the rows' weights if a sum will want them.  `(keys
    (R,), perm (R,)[, weights in that order])`."""
    with _one_trace():
        return tuple(_by_token(tokens, n, weights, t))


def order_of(sorted_rows, rows, t, token_tile=TOKEN_TILE,
             row_chunk=ROW_CHUNK):
    """What `rows_to_tokens` takes as `order`, for the first `rows` rows
    of what `by_token` sorted: `(perm (rows,), keys (rows / chunk, 1,
    chunk), visits (3, V), count, weights in token order or None)`:
    `perm` lists the rows by token, `keys` their tokens in that order (T
    for a row that does not count), and `visits` the (token tile, row
    chunk) pairs the kernel's grid walks, `count` of them: a tile is
    visited once for every chunk its rows touch, at least once (it must
    write its zeros), and `V_FIRST` marks a tile's first visit."""
    with _one_trace():
        return _order_of(tuple(sorted_rows), rows, t, token_tile, row_chunk)


def token_order(tokens, n, t, weights=None, token_tile=TOKEN_TILE,
                row_chunk=ROW_CHUNK):
    """`order_of` the whole of `by_token(tokens, n, t, weights)`."""
    return order_of(by_token(tokens, n, t, weights), tokens.shape[0], t,
                    token_tile, row_chunk)


def _split(x):
    """Three bfloat16 arrays that add up to float32 `x` exactly."""
    parts = []
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        parts.append(part)
        x = x - part.astype(jnp.float32)
    return parts


def _kernel(visits, keys_ref, *refs, token_tile, weighted):
    from jax.experimental import pallas as pl

    refs = list(refs)
    weights_ref = refs.pop(0) if weighted else None
    # the float32 sum is the output's own block, or scratch where the
    # output is narrower
    vals_ref, out_ref, acc_ref = (refs + refs[-1:])[:3]
    v = pl.program_id(0)
    rel = keys_ref[...] - visits[V_TILE, v] * token_tile     # (1, chunk)
    shape = (token_tile, rel.shape[1])
    hit = lax.broadcasted_iota(jnp.int32, shape, 0) == jnp.broadcast_to(
        rel, shape)
    if weighted:
        sel = lax.select(hit, jnp.broadcast_to(weights_ref[...], shape),
                         jnp.zeros(shape, jnp.float32))
    else:
        sel = hit.astype(jnp.float32)
    exact = vals_ref.dtype == jnp.float32
    if exact:
        sels = [sel]
    elif weighted:
        sels = _split(sel)
    else:
        sels = [sel.astype(vals_ref.dtype)]
    vals = vals_ref[...]
    part = sum(lax.dot_general(
        one, vals, (((1,), (0,)), ((), ())),
        # (said, not left to the caller's default: Mosaic refuses an
        # fp32 contraction of bf16 operands)
        precision=lax.Precision.HIGHEST if exact else lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32) for one in sels)
    # (the tile's first visit starts from zeros: `select`, as the
    # grouped matmuls store, not a `when` an arm.  One product the whole
    # width: a loop over lane slabs was 7-15 % slower alone on the chip,
    # and the kernel's code is 90-160 KB either way)
    kept = lax.select(jnp.broadcast_to(visits[V_FIRST, v] == 1, part.shape),
                      jnp.zeros_like(part), acc_ref[...])
    acc_ref[...] = kept + part
    if out_ref is not acc_ref:
        # every visit, not the tile's last alone: the block leaves VMEM
        # when the tile changes, holding the whole sum
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


# jitted, as the grouped matmuls are: a program's layers share their
# shapes, so the kernel is traced and lowered once a shape
@functools.partial(jax.jit, static_argnames=(
    "t", "token_tile", "out_dtype", "interpreted"))
def _call(vals, weights, keys, visits, count, t, token_tile,
          out_dtype=jnp.float32, interpreted=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from . import pallas_call

    del interpreted         # (the trace's key: `pallas_call` reads it)
    d = vals.shape[1]
    row_chunk = keys.shape[2]
    operands = [keys] + ([] if weights is None else [weights]) + [vals]

    def of_chunk(v, visits):
        return visits[V_CHUNK, v], 0, 0

    lane_row = pl.BlockSpec((None, 1, row_chunk), of_chunk)
    return pallas_call(
        functools.partial(_kernel, token_tile=token_tile,
                          weighted=weights is not None),
        name=KERNEL,
        out_shape=jax.ShapeDtypeStruct((t, d), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(count,),
            in_specs=[lane_row] * (len(operands) - 1) + [
                pl.BlockSpec((row_chunk, d),
                             lambda v, visits: (visits[V_CHUNK, v], 0))],
            out_specs=pl.BlockSpec((token_tile, d),
                                   lambda v, visits: (visits[V_TILE, v], 0)),
            scratch_shapes=([] if out_dtype == jnp.float32 else
                            [pltpu.VMEM((token_tile, d), jnp.float32)])),
        # (within Mosaic's default scoped VMEM: a `vmem_limit_bytes` of
        # 64 MiB added 196 MB to a share layer's planned temporaries)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(visits, *operands)


def rows_to_tokens(vals, order, t, weighted=False, out_dtype=jnp.float32,
                   token_tile=TOKEN_TILE):
    """(T, D): `out[t] = sum_r weights[r] * vals[r]` over the rows of
    token t that count, summed in float32 and stored as `out_dtype`.
    `vals` (R, D) in the buffer's own
    order; `order`: `token_order(tokens, n, t[, weights])` of the same
    buffer; `weighted`: by the weights it carries (float32), else by 1.
    A row that does not count is multiplied by 0: it may hold anything
    FINITE (the grouped matmuls write such rows as zeros)."""
    from . import interpret

    perm, keys, visits, count, weights = order
    if weighted and weights is None:
        raise ValueError("rows_to_tokens: the order carries no weights")
    # token order: ONE gather of R rows
    with _one_trace():
        return _call(vals[perm], weights if weighted else None, keys, visits,
                     count, t, token_tile, jnp.dtype(out_dtype),
                     interpreted=interpret())
