"""The ops a second tower brings (`models/vision_tower.py`): attention
confined to the segments of a packed row axis (`segment_attention`), a
learnt position table read through bicubic taps (`table_interp`) and
the merge of a tower's output rows into the decoder's embedded stream
(`image_merge`).  `rope` over two axes is the `rope` op's own
(`ops/decoder.py`, its `Positions` input); `segment_attention` takes the
same input and turns its own Q and K by it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op
from .common import first, opt_in, out


@register_op("segment_attention")
def segment_attention(ctx, ins, attrs):
    """Bidirectional attention over a packed row axis: row i reads the
    rows j with SegmentIds[i] == SegmentIds[j], all of them and no
    others.  Q, K, V (N, P, H * d) head-major as a projection emits
    them, SegmentIds (N, P) int32: a segment is a RUN of consecutive
    rows of one id (an id that comes again after another is another
    segment), a negative id a padding row (reads nothing, is read by
    nobody, Out 0, gradient 0).  `max_segment_rows` (None: P) bounds a
    segment's rows and with it the kernels' list of visits; where longer
    segments need more visits than the list holds, that row axis's Out
    is NaN.  Scores and soft-max float32; Out in Q's dtype.

    Positions (N, P, 2) int32, a (row, column) a row, with `theta`: Q
    and K arrive UNTURNED and the op turns them, the `rope` op's
    two-axis turn to the letter (pair 2m by column x f_m, pair 2m + 1 by
    row x f_m, f_m = theta^(-4m/d); float32 from Q to one rounding).
    Without it, nothing turns.

    Two lowerings, chosen by the shape alone
    (`flash_segment.segment_attention_takes`): the kernels of
    `ops/pallas/flash_segment.py` (heads of 72 lanes laid out at 128
    around them), or `segment_attention_xla`.  Where the kernels run,
    their layout and the turn inside it are ONE Pallas pass an array
    each way wherever `flash_segment.lane_kernels_take` the shape
    (`ops/pallas/head_lanes.py`), XLA's pad, slice and `_rope`
    elsewhere.  TilesVisited /
    TilesTotal (1,) int32: state the op adds the forward pass's visited
    tiles (data) and the whole rectangle's to (0 on the XLA lowering);
    `runtime_stats.flash_segment_calls` / `_xla_calls` /
    `_tiles_total` count the calls traced, `flash_segment_lane_kernel_calls`
    / `_lane_xla_calls` those of them whose layout the kernels made and
    XLA made (neither: heads of whole tiles that do not turn)."""
    from ..core.shape_inference import inferring_shapes
    from ..observe.monitoring import runtime_stats
    from . import decoder
    from .pallas import flash_segment as fs

    q, k, v = first(ins, "Q"), first(ins, "K"), first(ins, "V")
    seg = first(ins, "SegmentIds")
    heads = int(attrs["n_head"])
    n, p, hd = q.shape
    if hd % heads or k.shape != q.shape or v.shape != q.shape \
            or seg.shape != (n, p):
        raise ValueError(
            f"segment_attention: Q {q.shape}, K {k.shape}, V {v.shape} are "
            f"not {heads} heads over SegmentIds {seg.shape}")
    scale = attrs.get("scale")
    limit = attrs.get("max_segment_rows")
    d = hd // heads
    kernel = fs.segment_attention_takes(p, heads, d)
    lanes = fs.lane_kernels_take(p, heads, d)
    total = fs.tiles_total(n, p, heads) if kernel else 0
    rotary = None
    positions = opt_in(ins, "Positions")
    if positions is not None:
        if d % 4 or positions.shape != (n, p, 2):
            raise ValueError(
                f"segment_attention: Positions {positions.shape} over "
                f"heads of {d} lanes are not a (row, column) a row of "
                f"{(n, p)} over heads of a multiple of 4 lanes")
        # looked up at the call: whoever replaces it rounds both lowerings
        rotary = decoder._cos_sin_two_axes(
            positions, d, float(attrs.get("theta", 10000.0)))
        if not lanes:
            q, k = (decoder._rope(x, None, *rotary, heads, pairs=True)
                    for x in (q, k))
            rotary = None
    # who lays the lanes out for the kernels: nobody where heads are
    # whole tiles and nothing turns
    laid_out = kernel and (d % 128 != 0 or rotary is not None)
    if not inferring_shapes():
        runtime_stats.record_flash_segment(
            kernel, total, lane_kernels=lanes if laid_out else None)
    if kernel:
        o, visited = fs.flash_segment(q, k, v, seg, heads, scale, limit,
                                      rotary=rotary)
    else:
        o = fs.segment_attention_xla(q, k, v, seg, heads, scale)
        visited = jnp.zeros((1,), jnp.int32)
    outs = out(Out=o)
    for slot, add in (("TilesVisited", visited),
                      ("TilesTotal", jnp.full((1,), total, jnp.int32))):
        state = opt_in(ins, slot)
        if state is not None:
            outs[slot + "Out"] = [state + jax.lax.stop_gradient(add)]
    return outs


def _record_image_feed(patches, rows):
    """`runtime_stats.image_patches` / `image_rows`: what a STEP's trace
    reads of a second tower's input (the patches on the packed axis) and
    hands the decoder (the rows that enter its stream)."""
    from ..core.shape_inference import inferring_shapes
    from ..observe.monitoring import runtime_stats

    if not inferring_shapes():
        runtime_stats.record_image_feed(patches, rows)


def dense_taps(taps, weights, size):
    """(R, size) float32: row r holds Weights[r, k] at column
    Taps[r, k], summed over k (taps of a row may coincide: clamped
    borders)."""
    cols = jnp.arange(size, dtype=jnp.int32)
    return sum(jnp.where(taps[:, k, None] == cols, weights[:, k, None], 0.0)
               for k in range(taps.shape[1]))


@jax.custom_vjp
def _interp(table, taps, weights):
    return _interp_fwd(table, taps, weights)[0]


def _interp_fwd(table, taps, weights):
    # folded over the taps: (R, D) at a time, never (R, K, D)
    y = sum(weights[:, k, None] * jnp.take(table, taps[:, k], axis=0)
            for k in range(taps.shape[1]))
    return y, (taps, weights, table.shape[0])


def _interp_bwd(res, g):
    taps, weights, size = res
    # the same weighting scattered back, as ONE product with the 0 / w
    # matrix of the taps (a scatter of R x K rows serialises)
    d_table = jnp.einsum("rs,rd->sd", dense_taps(taps, weights, size)
                         .astype(g.dtype), g,
                         preferred_element_type=jnp.float32)
    return (d_table, np.zeros(taps.shape, jax.dtypes.float0),
            jnp.zeros_like(weights))


_interp.defvjp(_interp_fwd, _interp_bwd)


@register_op("table_interp")
def table_interp(ctx, ins, attrs):
    """Out[n, p] = sum_k Weights[n, p, k] * Table[Taps[n, p, k]]: a
    learnt position table (S, D) (a (H, W, D) table's rows flattened)
    read through K taps a row, float32.  What bicubic interpolation of
    the table to an image's grid is for the patch at (y, x): 16 taps
    (4 rows x 4 columns) whose indices and weights depend on (y, x, h,
    w) only, geometry a collator computes, no parameter.  Folded over
    the taps: no (P, K, D) array exists.  The gradient of Table is the
    same weighting scattered back, as one product with the taps' 0 / w
    matrix; Taps and Weights take none."""
    table = first(ins, "Table").astype(jnp.float32)
    taps, weights = first(ins, "Taps"), first(ins, "Weights")
    if taps.shape != weights.shape or taps.ndim != 3 or table.ndim != 2:
        raise ValueError(f"table_interp: Table {table.shape}, Taps "
                         f"{taps.shape}, Weights {weights.shape}")
    n, p, k = taps.shape
    _record_image_feed(n * p, 0)
    y = _interp(table, taps.reshape(n * p, k).astype(jnp.int32),
                weights.reshape(n * p, k).astype(jnp.float32))
    return out(Out=y.reshape(n, p, table.shape[1]))


@jax.custom_vjp
def _merge(x, rows, is_image):
    return _merge_fwd(x, rows, is_image)[0]


def _merge_fwd(x, rows, is_image):
    # the r-th placeholder of a sequence takes the r-th row
    at = jnp.clip(jnp.cumsum(is_image, axis=1, dtype=jnp.int32) - 1, 0,
                  rows.shape[1] - 1)
    taken = jnp.take_along_axis(rows, at[:, :, None], axis=1)
    y = jnp.where(is_image[:, :, None], taken.astype(x.dtype), x)
    # (0, R) of the rows' dtype: their count and dtype, no bytes
    return y, (is_image, jnp.zeros((0, rows.shape[1]), rows.dtype))


def _merge_bwd(res, g):
    is_image, like = res
    count = like.shape[1]
    # the matching scatter, read as a gather: a row's gradient is the
    # stream's at the placeholder that took it (none: 0)
    ends = jnp.cumsum(is_image, axis=1, dtype=jnp.int32)
    r = jnp.arange(count, dtype=jnp.int32)
    at = jax.vmap(lambda e: jnp.searchsorted(e, r + 1, side="left"))(ends)
    has = r[None, :] < ends[:, -1:]
    at = jnp.minimum(at, is_image.shape[1] - 1)
    d_rows = jnp.where(has[:, :, None],
                       jnp.take_along_axis(g, at[:, :, None], axis=1), 0.0)
    return (jnp.where(is_image[:, :, None], 0.0, g).astype(g.dtype),
            d_rows.astype(like.dtype),
            np.zeros(is_image.shape, jax.dtypes.float0))


_merge.defvjp(_merge_fwd, _merge_bwd)


@register_op("image_merge")
def image_merge(ctx, ins, attrs):
    """Out = where(Tokens == `placeholder`, Rows[cumsum - 1], X): X
    (N, T, D) the embedded token stream, Rows (N, R, D) a second tower's
    output rows, Tokens (N, T) the ids; the r-th placeholder of a
    sequence takes the r-th row.  X's gradient is the stream's at the
    other positions, Rows' the stream's at the placeholder that took the
    row (a gather by that placeholder's position)."""
    x, rows = first(ins, "X"), first(ins, "Rows")
    tokens = first(ins, "Tokens")
    if tokens.ndim == 3:
        tokens = tokens[..., 0]
    if rows.ndim != 3 or rows.shape[0] != x.shape[0] \
            or rows.shape[2] != x.shape[2] or tokens.shape != x.shape[:2]:
        raise ValueError(f"image_merge: X {x.shape}, Rows {rows.shape}, "
                         f"Tokens {tokens.shape}")
    _record_image_feed(0, rows.shape[0] * rows.shape[1])
    return out(Out=_merge(x, rows, tokens == int(attrs["placeholder"])))
