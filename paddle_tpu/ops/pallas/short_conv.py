"""The short causal depthwise convolution as two Pallas kernels.

    conv[t] = sum_j Filter[:, j] * z[t - (L-1) + j]          (z[<0] = 0)

    "silu":  z = X,      Out = silu(conv [+ Bias])   X (N, T, D)
    gated:   z = B * u,  Out = C * conv              X = BCu (N, T, 3D)

`ops/decoder.py short_conv` is the op; this is its lowering for the
shapes `short_conv_kernel_takes`, the `jax.checkpoint`-ed composition
there the lowering of every other shape and the reference of both.
Same arithmetic: tiles in X's dtype, float32 in VMEM, X's dtype out;
the residuals are (X, Filter), nothing is kept in between.

XLA writes that composition's float32 intermediates to HBM (the padded
copy, one float32 tensor a tap between the backward's fusions): 1.6 GB
forward and 7.5 GB backward at (1, 16384, 8192) x 4 taps for the 0.54
and 0.81 GB the algorithm needs (PERF.md, PR 46).  Here a grid step
holds a (rows, channels) tile; the L - 1 rows before it come from a
VMEM carry of the previous row tile (forward, row tiles in order) or
from a 16-row halo block of X (backward, row tiles in REVERSE order,
which carries the first rows of the later tile's dconv the other way:
dz[t] = sum_j Filter[:, j] * dconv[t + (L-1) - j]).  The taps are
sublane rotations of a chunk with its 16 halo rows.  The filter's
gradient adds up in the kernel's own output block, eight sublanes a
tap, over the row tiles; XLA sums those eight and the batch.

Gated form: B, C and u are the three lane slabs of ONE full-width tile
of `BCu`, and dB, dC, du those of one tile of its gradient: no slice
and no concatenation of `BCu` in HBM.  So its channel tile is D, and
the rule takes it only while that tile fits VMEM.

A Bias (D,) of the "silu" form (a state-space mixer's convolution)
rides as one more row of the filter operand, and its gradient as one
more tap's worth of the filter's gradient block; without one both
calls are what they were.

Kernel names `short_conv_fwd` / `short_conv_bwd`, registered costs in
bytes (no MXU work).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HALO = 16               # rows before / after a chunk: a bf16 tile's rows
ROW_TILE = 512          # rows a grid step ("silu")
CHANNEL_TILE = 1024     # lanes a grid step ("silu"; gated: D)
GATED_ROW_TILE = 256
ROW_CHUNK = 64          # rows a loop body
LANE_GROUP = 256        # lanes a loop body
# what a grid step's double-buffered tiles may take of v5e's 128 MiB
VMEM_BUDGET = 48 << 20
VMEM_LIMIT = 96 << 20


def _tiles(t, d, gated, row_tile=None, channel_tile=None):
    """(row tile, channel tile) of a call, or None where T or D has no
    whole tile."""
    if d % 128:
        return None
    if gated:
        td = d
    else:
        td = channel_tile or CHANNEL_TILE
        while td > 128 and d % td:
            td //= 2
        if d % td:
            return None
    tr = row_tile or (GATED_ROW_TILE if gated else ROW_TILE)
    while tr > 64 and t % tr:
        tr //= 2
    if t % tr:
        return None
    return tr, td


def short_conv_kernel_takes(t, d, taps, gated=False, itemsize=2):
    """Whether the kernels run a call, from its shape alone: whole
    tiles of rows and of 128-lane channels, the taps within a halo
    and, gated, a full-width tile that fits VMEM."""
    tiles = _tiles(t, d, gated)
    if tiles is None or not 1 <= taps - 1 <= 8:
        return False
    tr, td = tiles
    # backward, double-buffered: X, dX (wide), dy (one slab)
    wide = 3 if gated else 1
    return 2 * (2 * wide + 1) * tr * td * itemsize <= VMEM_BUDGET


# -- kernel cost registry (observe/cost.py) ----------------------------
#
# Bytes once each, no MXU FLOP: forward X and Out, which is the default
# model (operands and results once); backward X, dOut and dX (the halo
# operand is X again and is not counted a second time).

def fwd_cost(operand_shapes, result_shapes):
    return 0.0, None


def bwd_cost(operand_shapes, result_shapes):
    x, _halo, dy, w = operand_shapes
    return 0.0, float(sum(item * math.prod(dims) for dims, item in
                          [x, dy, w] + list(result_shapes)))


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("short_conv_fwd", fwd_cost)
    register_kernel_cost("short_conv_bwd", bwd_cost)


_register_costs()


# -- the kernels -------------------------------------------------------

def _rows(ext, start, size):
    return lax.slice_in_dim(ext, start, start + size, axis=0)


def _behind(ext, taps, size):
    """The chunk's rows t - (taps-1-j), j ascending, of `ext` (a chunk
    behind its HALO rows), by sublane rotation (unaligned loads from a
    float32 scratch timed 10-17 % slower at the cells' shapes: my chip
    runs, PR 46)."""
    from jax.experimental.pallas import tpu as pltpu

    return [_rows(pltpu.roll(ext, s, 0) if s else ext, HALO, size)
            for s in range(taps - 1, -1, -1)]


def _ahead(ext, taps, size):
    """The chunk's rows t + (taps-1-j) of `ext` (a chunk before HALO
    rows)."""
    from jax.experimental.pallas import tpu as pltpu

    return [_rows(pltpu.roll(ext, ext.shape[0] - s, 0) if s else ext, 0, size)
            for s in range(taps - 1, -1, -1)]


def _weighted(w, shifted):
    """sum_j w[j] * shifted[j], j ascending as the composition adds."""
    acc = w[0] * shifted[0]
    for wj, zj in zip(w[1:], shifted[1:]):
        acc = acc + wj * zj
    return acc


def _lane_group(td):
    lg = LANE_GROUP
    while td % lg:
        lg //= 2
    return lg


def _slab(g, lg, k, td):
    """Lane group g of slab k of a tile of slabs td wide (ungated: the
    one slab)."""
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(k * td + g * lg, lg), lg)


def _loader(g, lg, td, gated):
    """z = X, or B * u, of some rows of a lane group in float32."""
    f32 = jnp.float32
    b, u = _slab(g, lg, 0, td), _slab(g, lg, 2, td)
    if not gated:
        return lambda ref, rows: ref[0, rows, b].astype(f32)
    return lambda ref, rows: (ref[0, rows, b].astype(f32)
                              * ref[0, rows, u].astype(f32))


def _fwd_kernel(x_ref, w_ref, o_ref, tail_ref, *, taps, gated, rc,
                biased=False):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    tr, td = o_ref.shape[1], o_ref.shape[2]
    lg = _lane_group(td)

    @pl.when(pl.program_id(2) == 0)
    def _first_rows_read_zeros():
        tail_ref[...] = jnp.zeros(tail_ref.shape, f32)

    # the lane groups are a loop, not an unrolled `for`: a traced and
    # lowered body each cost the step's set-up ~0.1 s a kernel (PR 46)
    def group(g, carry):
        lanes, c_lanes = _slab(g, lg, 0, td), _slab(g, lg, 1, td)
        w = [w_ref[j:j + 1, lanes] for j in range(taps)]
        load = _loader(g, lg, td, gated)

        def chunk(c, carry):
            rows = pl.ds(pl.multiple_of(c * rc, rc), rc)
            z = load(x_ref, rows)
            ext = lax.concatenate([tail_ref[:, lanes], z], 0)
            tail_ref[:, lanes] = _rows(z, rc - HALO, HALO)
            conv = _weighted(w, _behind(ext, taps, rc))
            if biased:
                conv = conv + w_ref[taps:taps + 1, lanes]
            if gated:
                y = x_ref[0, rows, c_lanes].astype(f32) * conv
            else:
                y = conv * lax.logistic(conv)
            o_ref[0, rows, lanes] = y.astype(o_ref.dtype)
            return carry

        return lax.fori_loop(0, tr // rc, chunk, carry)

    lax.fori_loop(0, td // lg, group, 0)


def _bwd_kernel(x_ref, halo_ref, dy_ref, w_ref, dx_ref, dw_ref, head_ref, *,
                taps, gated, rc, biased=False):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    tr, td = dy_ref.shape[1], dy_ref.shape[2]
    lg = _lane_group(td)
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when(step == 0)
    def _last_rows_feed_nothing_later():
        head_ref[...] = jnp.zeros(head_ref.shape, f32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, f32)

    # the row tiles come in reverse: the last step holds rows 0.., whose
    # halo block is a stand-in for the zeros before row 0
    before = (step != steps - 1).astype(f32)

    def group(g, carry):
        b_lanes, c_lanes, u_lanes = (_slab(g, lg, k, td) for k in range(3))
        lanes = b_lanes         # of a one-slab tile: dy, the carries, dw
        w = [w_ref[j:j + 1, lanes] for j in range(taps)]
        load = _loader(g, lg, td, gated)

        def chunk(rows, prev):
            ext = lax.concatenate([prev, load(x_ref, rows)], 0)
            shifted = _behind(ext, taps, rc)
            conv = _weighted(w, shifted)
            if biased:
                conv = conv + w_ref[taps:taps + 1, lanes]
            dy = dy_ref[0, rows, lanes].astype(f32)
            if gated:
                dconv = dy * x_ref[0, rows, c_lanes].astype(f32)
                dx_ref[0, rows, c_lanes] = (dy * conv).astype(dx_ref.dtype)
            else:
                sig = lax.logistic(conv)
                dconv = dy * (sig * (1.0 + conv * (1.0 - sig)))
            for j in range(taps):
                eight = lax.reshape(dconv * shifted[j],
                                    (rc // 8, 8, dconv.shape[1]))
                dw_ref[0, j, :, lanes] += lax.reduce_sum(eight, [0])
            if biased:      # the bias's gradient: one more "tap" of ones
                dw_ref[0, taps, :, lanes] += lax.reduce_sum(
                    lax.reshape(dconv, (rc // 8, 8, dconv.shape[1])), [0])
            ext = lax.concatenate([dconv, head_ref[:, lanes]], 0)
            head_ref[:, lanes] = _rows(dconv, 0, HALO)
            dz = _weighted(w, _ahead(ext, taps, rc))
            if gated:
                dx_ref[0, rows, b_lanes] = (
                    dz * x_ref[0, rows, u_lanes].astype(f32)
                ).astype(dx_ref.dtype)
                dx_ref[0, rows, u_lanes] = (
                    dz * x_ref[0, rows, b_lanes].astype(f32)
                ).astype(dx_ref.dtype)
            else:
                dx_ref[0, rows, lanes] = dz.astype(dx_ref.dtype)

        def later_chunk(i, carry):
            start = pl.multiple_of((tr // rc - 1 - i) * rc, rc)
            chunk(pl.ds(start, rc),
                  load(x_ref, pl.ds(pl.multiple_of(start - HALO, HALO),
                                    HALO)))
            return carry

        lax.fori_loop(0, tr // rc - 1, later_chunk, 0)
        chunk(pl.ds(0, rc), load(halo_ref, pl.ds(0, HALO)) * before)
        return carry

    lax.fori_loop(0, td // lg, group, 0)


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _geometry(x, w, gated, row_tile, channel_tile):
    n, t, width = x.shape
    d, taps = w.shape
    wide = 3 if gated else 1
    tiles = _tiles(t, d, gated, row_tile, channel_tile)
    if width != wide * d or tiles is None:
        raise ValueError(f"short_conv kernel: X {x.shape}, Filter {w.shape}"
                         f" ({'gated' if gated else 'silu'}) has no tiling")
    tr, td = tiles
    return n, t, d, taps, wide, tr, td, min(ROW_CHUNK, tr)


_STATIC = ("gated", "row_tile", "channel_tile", "interpreted")


def _filter_rows(w, bias):
    """The filter operand: a row a tap, (taps, D) float32, and the bias
    as one row more where there is one."""
    rows = w.astype(jnp.float32).T
    if bias is None:
        return rows, {}
    return jnp.concatenate([rows, bias.astype(jnp.float32)[None]]), \
        {"biased": True}


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(x, w, gated, row_tile=None, channel_tile=None,
              interpreted=False, bias=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from . import pallas_call

    n, t, d, taps, wide, tr, td, rc = _geometry(x, w, gated, row_tile,
                                                channel_tile)
    rows, biased = _filter_rows(w, bias)
    return pallas_call(
        functools.partial(_fwd_kernel, taps=taps, gated=gated, rc=rc,
                          **biased),
        name="short_conv_fwd", grid=(n, d // td, t // tr),
        in_specs=[pl.BlockSpec((1, tr, wide * td), lambda b, c, r: (b, r, c)),
                  pl.BlockSpec((rows.shape[0], td), lambda b, c, r: (0, c))],
        out_specs=pl.BlockSpec((1, tr, td), lambda b, c, r: (b, r, c)),
        out_shape=jax.ShapeDtypeStruct((n, t, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((HALO, td), jnp.float32)],
        compiler_params=_params(),
    )(x, rows)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(x, w, dy, gated, row_tile=None, channel_tile=None,
              interpreted=False, bias=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from . import pallas_call

    n, t, d, taps, wide, tr, td, rc = _geometry(x, w, gated, row_tile,
                                                channel_tile)
    last, per = t // tr - 1, tr // HALO
    rows, biased = _filter_rows(w, bias)
    held = rows.shape[0]    # the taps, and the bias's row

    def tile(width):
        return pl.BlockSpec((1, tr, width),
                            lambda b, c, r: (b, last - r, c))

    dx, dw = pallas_call(
        functools.partial(_bwd_kernel, taps=taps, gated=gated, rc=rc,
                          **biased),
        name="short_conv_bwd", grid=(n, d // td, t // tr),
        in_specs=[tile(wide * td),
                  pl.BlockSpec((1, HALO, wide * td), lambda b, c, r: (
                      b, jnp.maximum((last - r) * per - 1, 0), c)),
                  tile(td),
                  pl.BlockSpec((held, td), lambda b, c, r: (0, c))],
        out_specs=[tile(wide * td),
                   pl.BlockSpec((1, held, 8, td),
                                lambda b, c, r: (b, 0, 0, c))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((n, held, 8, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((HALO, td), jnp.float32)],
        compiler_params=_params(),
    )(x, x, dy, rows)
    dw = jnp.sum(dw, axis=(0, 2))
    if bias is None:
        return dx, dw.T.astype(w.dtype)
    return dx, dw[:taps].T.astype(w.dtype), dw[taps].astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def short_conv_kernel(x, w, gated=False, row_tile=None, channel_tile=None):
    """Out of the op by the kernels.  x (N, T, D) or `BCu` (N, T, 3D),
    w (D, L); the tiles are the module's unless a caller that times
    them says otherwise."""
    from . import interpret

    return _fwd_call(x, w, gated, row_tile, channel_tile,
                     interpreted=interpret())


def _vjp_fwd(x, w, gated, row_tile, channel_tile):
    return short_conv_kernel(x, w, gated, row_tile, channel_tile), (x, w)


def _vjp_bwd(gated, row_tile, channel_tile, res, dy):
    from . import interpret

    x, w = res
    return _bwd_call(x, w, dy.astype(x.dtype), gated, row_tile,
                     channel_tile, interpreted=interpret())


short_conv_kernel.defvjp(_vjp_fwd, _vjp_bwd)


@jax.custom_vjp
def biased_conv_kernel(x, w, bias):
    """silu(conv(x) + bias) by the same kernels: x (N, T, D), w (D, L),
    bias (D,)."""
    from . import interpret

    return _fwd_call(x, w, False, interpreted=interpret(), bias=bias)


def _biased_vjp_fwd(x, w, bias):
    return biased_conv_kernel(x, w, bias), (x, w, bias)


def _biased_vjp_bwd(res, dy):
    from . import interpret

    x, w, bias = res
    return _bwd_call(x, w, dy.astype(x.dtype), False,
                     interpreted=interpret(), bias=bias)


biased_conv_kernel.defvjp(_biased_vjp_fwd, _biased_vjp_bwd)
