"""The layout an attention kernel reads, made from what a projection
wrote, and taken back to it, as two Pallas kernels: ONE pass over each
array each way, q's and k's rotary turn over two axes inside it.

A projection writes (N, P, H * d), head after head on the lanes.  A
kernel that blocks a head needs its lanes to be whole 128-lane tiles: a
head of 72 is laid out at 128, its lanes first and zeros behind them
(`flash_segment.py`, whose docstring says why the zeros cost nothing
there).  As XLA compositions that layout is a pad of a (.., H, d) view
and a slice back, and the turn before it a float32 pass over a
(.., d / 2, 2) view: a minor dimension of 72, 36 or 2 is a re-lay on
this chip, and `kimivl-8k` paid ~90 ms a step for them around kernels
of 180 (PERF.md, PR 73 and PR 74).

`to_tiles`: a row tile a grid step, every array of the call in it (q, k
and v; do and o).  A head's d lanes lie in at most two of the row's
128-lane tiles at a static offset (d h mod 128): the two tiles are
joined by a select on the lane index, ONE lane rotation brings the
head's first lane to lane 0, a second select puts zeros behind lane d.
`from_tiles` is the mirror: one rotation a head, and a tile of the
narrow array leaves when the heads that fill it have passed.  Where d
is a multiple of 128 nothing moves, and an array that does not turn
never enters a kernel.

The turn (`ops/decoder.py rope` with `Positions`: a head's lanes are
d / 2 consecutive PAIRS, pair i turned by its own angle), on a head's
slab, as `ops/pallas/rope.py` has rotate-half:

    y = x * C + where(odd lane, roll(x, +1), roll(x, -1)) * S

C (N, P, D) float32 the cosines, each twice; S the sines, each twice,
negative on a pair's first lane; both 0 behind lane d, so the lanes
behind a head stay exactly 0 and the wrap of a rotation meets a 0.  The
backward kernel turns by the negative angle (the same tables, the
second term subtracted), which is the turn's transpose.  Products and
sums are float32 from the operand's dtype to ONE rounding at the write,
the tables float32.  `tables` makes C and S from cos and sin as
`ops/decoder.py _cos_sin_two_axes` returns them: whoever calls looks
that function up through its module, so a caller that replaces it (the
parity script's bfloat16 control) rounds what these kernels read.

`head_lanes_take` (the shape alone): heads of at most 128 lanes or of
whole tiles, H * d whole tiles, whole row tiles.  Kernel names
`head_lanes_to_tiles` / `head_lanes_from_tiles`, costs in bytes (no
model FLOP).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import interpret, pallas_call, register_kernel_cost
from .rope import rope_cost

LANES = 128
# rows a grid step (the DMA's block) and rows a loop body (a head's slab
# in registers beside the tables' rows).  Alone on v5e at 24576 rows x
# 16 heads of 72, bf16, ms a call (my chip runs, PR 74): q, k, v to tiles
# with two turns 1.65 / 1.15 / 0.86 / 0.85 / 0.85 at bodies of 32 / 64 /
# 128 / 256 / 512 rows (497 MB: 0.84 at the 590 GB/s a plain pass
# reaches), three gradients back with two turns 1.22 / 0.85 / 0.84 /
# 0.82 at 64 .. 512; the row tile moves nothing (256 .. 2048).  Both
# lane moves on the MXU instead (0 / 1 matrices, the turn's partner
# baked in or rolled): 0.85-0.87, the same pass of the same bytes.  The
# heads are UNROLLED: as a loop with the head's offset a scalar (a
# dynamic rotation, both tiles always loaded) the same passes take 1.73
# and 1.71 at 128 rows, 1.04 / 1.08 at 256, and the cell's step 666 ms
# for 643, though the step's set-up is 3 s shorter (a recompute segment
# lowers its own copy of a forward pass's kernel, eight a step)
ROW_TILE = 512
ROW_CHUNK = 128
VMEM_LIMIT = 96 << 20

for _kernel in ("to_tiles", "from_tiles"):
    register_kernel_cost("head_lanes_" + _kernel, rope_cost)


def lane_tiles(d):
    """The lanes a head of d is laid out at: the next multiple of 128."""
    return -(-d // LANES) * LANES


def _row_tile(rows):
    tr = ROW_TILE
    while tr >= ROW_CHUNK:
        if rows % tr == 0:
            return tr
        tr //= 2
    return None


def head_lanes_take(rows, heads, d):
    """Whether the kernels make a call's layout, by its shape alone."""
    return ((d <= LANES or d % LANES == 0) and (heads * d) % LANES == 0
            and _row_tile(rows) is not None)


def tables(cos, sin, d):
    """(C, S) (N, P, D) float32 of the module's text from cos, sin
    (N, P, 1, d / 2), D = `lane_tiles(d)`."""
    n, p = cos.shape[:2]
    behind = ((0, 0), (0, 0), (0, lane_tiles(d) - d))

    def lanes(x):       # each twice, side by side
        return jnp.repeat(x.reshape(n, p, d // 2), 2, axis=-1)

    sign = jnp.tile(jnp.asarray([-1.0, 1.0], jnp.float32), d // 2)
    return (jnp.pad(lanes(cos.astype(jnp.float32)), behind),
            jnp.pad(lanes(sin.astype(jnp.float32)) * sign, behind))


# -- the kernels --------------------------------------------------------------

def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _turn(x, c, s, sign):
    from jax.experimental.pallas import tpu as pltpu

    width = x.shape[1]
    partner = jnp.where(_lane(x.shape) % 2 == 1, pltpu.roll(x, 1, 1),
                        pltpu.roll(x, width - 1, 1))
    return x * c + sign * (partner * s)


def _chunks(refs, arrays, turned, body):
    """`body(rows, operands, results, C, S)` a chunk of the row tile:
    refs are `arrays` operands, C and S where any turns, `arrays`
    results."""
    from jax.experimental import pallas as pl

    ins, tabs, outs = (refs[:arrays], refs[arrays:len(refs) - arrays],
                       refs[len(refs) - arrays:])

    def chunk(i, carry):
        rows = pl.ds(pl.multiple_of(i * ROW_CHUNK, ROW_CHUNK), ROW_CHUNK)
        c, s = (t[0, rows] for t in tabs) if turned else (None, None)
        body(rows, ins, outs, c, s)
        return carry

    jax.lax.fori_loop(0, outs[0].shape[1] // ROW_CHUNK, chunk, 0)


def _to_tiles_kernel(*refs, heads, d, turned, arrays):
    """Narrow operands to wide results; the first `turned` turn."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    wide = lane_tiles(d)

    def body(rows, ins, outs, c, s):
        for a, (x_ref, o_ref) in enumerate(zip(ins, outs)):
            for h in range(heads):
                tile, at = divmod(h * d, LANES)
                if wide == d:
                    x = x_ref[0, rows, h * d:(h + 1) * d].astype(f32)
                else:
                    x = x_ref[0, rows,
                              tile * LANES:(tile + 1) * LANES].astype(f32)
                    lane = _lane(x.shape)
                    if at + d > LANES:
                        x = jnp.where(lane >= at, x, x_ref[
                            0, rows, (tile + 1) * LANES:(tile + 2) * LANES
                        ].astype(f32))
                    if at:
                        x = pltpu.roll(x, LANES - at, 1)
                    x = jnp.where(lane < d, x, 0.0)
                if a < turned:
                    x = _turn(x, c, s, 1.0)
                o_ref[0, rows, h * wide:(h + 1) * wide] = x.astype(
                    o_ref.dtype)

    _chunks(refs, arrays, turned, body)


def _from_tiles_kernel(*refs, heads, d, turned, arrays):
    """Wide operands to narrow results; the first `turned` turn by the
    negative angle."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    wide = lane_tiles(d)

    def body(rows, ins, outs, c, s):
        for a, (x_ref, o_ref) in enumerate(zip(ins, outs)):
            held = None         # the narrow tile the heads are filling
            for h in range(heads):
                x = x_ref[0, rows, h * wide:(h + 1) * wide].astype(f32)
                if a < turned:
                    x = _turn(x, c, s, -1.0)
                if wide == d:
                    o_ref[0, rows, h * d:(h + 1) * d] = x.astype(o_ref.dtype)
                    continue
                tile, at = divmod(h * d, LANES)
                lane = _lane(x.shape)
                if at:
                    x = pltpu.roll(x, at, 1)
                here = (lane >= at) & (lane < at + d)
                held = jnp.where(here, x, 0.0 if held is None else held)
                if at + d >= LANES:
                    o_ref[0, rows, tile * LANES:(tile + 1) * LANES] = \
                        held.astype(o_ref.dtype)
                    held = (jnp.where(lane < at + d - LANES, x, 0.0)
                            if at + d > LANES else None)

    _chunks(refs, arrays, turned, body)


@functools.partial(jax.jit, static_argnames=(
    "heads", "d", "turned", "widen", "interpret"))
def _call(xs, rotary, *, heads, d, turned, widen, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, p, _ = xs[0].shape
    tr = _row_tile(p)
    wide = lane_tiles(d)
    into, out_of = (heads * wide, heads * d) if widen else (
        heads * d, heads * wide)

    def block(lanes):
        return pl.BlockSpec((1, tr, lanes), lambda b, r: (b, r, 0))

    tabs = tuple(rotary) if turned else ()
    return pallas_call(
        functools.partial(_to_tiles_kernel if widen else _from_tiles_kernel,
                          heads=heads, d=d, turned=turned, arrays=len(xs)),
        name="head_lanes_to_tiles" if widen else "head_lanes_from_tiles",
        grid=(n, p // tr),
        in_specs=[block(out_of)] * len(xs) + [block(wide)] * len(tabs),
        out_specs=[block(into)] * len(xs),
        out_shape=[jax.ShapeDtypeStruct((n, p, into), x.dtype) for x in xs],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
    )(*xs, *tabs)


def _moved(xs, heads, d, rotary, turned, widen):
    xs = tuple(xs)
    turned = turned if rotary is not None else 0
    # where a head is whole tiles only the arrays that turn are touched
    through = len(xs) if lane_tiles(d) != d else turned
    if not through:
        return xs
    return tuple(_call(xs[:through], rotary, heads=heads, d=d, turned=turned,
                       widen=widen, interpret=interpret())) + xs[through:]


def to_tiles(xs, heads, rotary=None, turned=0):
    """Arrays (N, P, H * d) -> (N, P, H * D), D = `lane_tiles(d)`: a
    head's lanes first, zeros behind; with `rotary` (`tables`) the first
    `turned` of them turned."""
    return _moved(xs, heads, xs[0].shape[2] // heads, rotary, turned, True)


def from_tiles(xs, heads, d, rotary=None, turned=0):
    """The mirror, (N, P, H * D) -> (N, P, H * d); with `rotary` the
    first `turned` turned by the negative angle (the turn's transpose:
    what takes the gradient of `to_tiles`' result to its operand's)."""
    return _moved(xs, heads, d, rotary, turned, False)
