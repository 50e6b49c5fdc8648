"""Dropout keep-masks drawn on the TPU's own generator.

`jax.random.bernoulli` is threefry2x32: 20 rounds of add / rotate /
xor on the vector unit for every 32 random bits, 21 ps an element on a
v5e where writing the mask's byte takes 1.2 ps (PERF.md, PR 25).  The
chip has a hardware generator that Pallas reaches (`pltpu.prng_seed`,
`pltpu.prng_random_bits`): this kernel seeds it from the op's key and
the block's index, draws the block as 32-bit words in VMEM, compares
ALL 32 bits of each word with `round((1 - p) * 2**32)` and writes one
byte an element.  P(keep) is 1 - p to 2**-32; nothing is read from HBM.

The generator has no CPU rule (`interpret=True` raises, and
`pltpu.InterpretParams()` "draws" zeros), so this kernel is never
interpreted: `ops/nn.py dropout` asks the package's `interpret()` gate
before it imports this module, and keeps `jax.random.bernoulli` there
and wherever `dropout_keep_mask` returns None.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ...parallel.collectives import compat_shard_map
from ...parallel.mesh import get_exec_context
from . import pallas_call, register_kernel_cost

# elements a grid step draws: the ~0.35 us a grid step costs and the
# re-seeding are small beside the block's own 2 us, and the two int8
# output buffers are 2 MiB of VMEM
BLOCK_ELEMS = 1 << 20
# elements an inner-loop trip draws and packs: on a v5e a trip of 32
# rows of 256 runs at 2.7 ps an element, one of 512 rows at 1.8 (the
# byte's HBM write is 1.2), and past this the gain is under 3%
CHUNK_ELEMS = 1 << 17


def _largest(rows: int, unit: int, limit: int) -> int:
    """Largest multiple of `unit` that divides `rows` (itself one) and
    is at most `limit`, or `unit`."""
    m = max(unit, min(rows, limit) // unit * unit)
    while rows % m:
        m -= unit
    return m


def tiling(rows: int, last: int):
    """(rows a grid step writes, rows an inner-loop trip draws) for a
    `(rows, last)` mask, or None where the kernel does not take the
    shape: the last dimension must be whole 128-lane tiles and the rows
    whole int8 (32, 128) tiles."""
    if last <= 0 or last % 128 or rows <= 0 or rows % 32:
        return None
    chunk = _largest(rows, 32, CHUNK_ELEMS // last)
    return _largest(rows, chunk, BLOCK_ELEMS // last), chunk


def _mapping(shape):
    """(mesh, axis) to map the call over, (None, None) for a plain call,
    or None where the kernel may not be called under the mesh being
    traced.  GSPMD cannot partition a custom call: left alone every
    chip would draw the whole global mask, so under a mesh the call
    goes inside a fully manual shard_map over the batch axis, and only
    there."""
    ectx = get_exec_context()
    large = [] if ectx is None else [
        a for a, n in ectx.mesh.shape.items() if n > 1]
    if not large:
        return None, None
    axis = ectx.batch_axis
    if large != [axis] or shape[0] % ectx.mesh.shape[axis]:
        return None
    # the explicit grad_sync step already runs its ops inside a
    # shard_map over the data axes: its body keeps the other path
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return ectx.mesh, axis


_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def threefry_fold_in(k0, k1, data):
    """The two words of `jax.random.fold_in((k0, k1), data)`, i.e.
    threefry2x32 of the block (0, data) under the key, on int32 scalars
    (wrapping adds, logical shifts): some hundred scalar operations a
    grid step, so that each block seeds the chip's generator with 64
    bits as far from its neighbour's as from another op's."""
    def rotl(x, r):
        return (x << r) | jax.lax.shift_right_logical(x, 32 - r)

    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    x0, x1 = ks[0], data + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = x0 ^ rotl(x1, r)
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + (i + 1)
    return x0, x1


def _kernel(seed_ref, out_ref, *, threshold, chunk, chunks):
    # seeds: the key's two words and the index of this call's first
    # block among the blocks of the WHOLE mask (a rank's offset under a
    # mesh).  The hardware takes two words: fold the block's index into
    # the key as `fold_in` would, so no two blocks, ranks, ops or steps
    # share a seed
    pltpu.prng_seed(*threefry_fold_in(
        seed_ref[0], seed_ref[1], seed_ref[2] + pl.program_id(0)))
    rows, last = chunk
    q = rows // 4

    def draw(c, carry):
        bits = pltpu.prng_random_bits((rows, last))     # int32
        # uniform bits are uniform as signed words too: exactly
        # `threshold + 2**31` of the 2**32 values lie below it
        keep = (bits < threshold).astype(jnp.int32)
        # four words' verdicts into the four bytes of one: every byte
        # is still one whole 32-bit draw, and the bitcast is free where
        # a 32-bit to 8-bit convert would relayout
        word = (keep[:q] | (keep[q:2 * q] << 8) | (keep[2 * q:3 * q] << 16)
                | (keep[3 * q:] << 24))
        r0 = pl.multiple_of(c * rows, rows)
        out_ref[pl.ds(r0, rows), :] = pltpu.bitcast(word, jnp.int8)
        return carry

    jax.lax.fori_loop(0, chunks, draw, 0)


def _threshold(p: float) -> int:
    """round((1 - p) * 2**32) as the signed word the kernel compares
    with (see `_kernel`); clamped so that no p > 0 rounds to 'always'."""
    t = min(max(int(round((1.0 - p) * 2.0 ** 32)), 0), 2 ** 32 - 1)
    return t - 2 ** 31


@functools.partial(jax.jit, static_argnames=("shape", "p"))
def _draw(seeds, shape, p):
    """int8 mask of `shape` (1 = keep) from int32 `seeds` = the key's
    two words and the index of the first block.  Jitted so that a step
    with 38 masks of two shapes traces and lowers two kernels, not 38
    (3 s of a warm `setup_s` otherwise)."""
    rows, last = math.prod(shape[:-1]), shape[-1]
    b, c = tiling(rows, last)
    kernel = functools.partial(_kernel, threshold=_threshold(p),
                               chunk=(c, last), chunks=b // c)
    mask = pallas_call(
        kernel, name="dropout_mask",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // b,),
            in_specs=[],
            out_specs=pl.BlockSpec((b, last), lambda i, seeds: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((rows, last), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(seeds)
    return mask.reshape(shape)


def dropout_keep_mask(key, p: float, shape):
    """Boolean keep-mask of `shape`, Bernoulli(1 - p) an element, a
    function of (key, p, position) alone; or None where the kernel does
    not engage and the caller keeps `jax.random.bernoulli`.  Decided
    from what the trace can see: the key, the shape, the executing
    mesh (the backend is the caller's gate: never call this where
    `ops.pallas.interpret()` holds)."""
    shape = tuple(int(d) for d in shape)
    if len(shape) < 2:
        return None
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    if key.shape != (2,):       # the seed is threefry's two words
        return None
    mapping = _mapping(shape)
    if mapping is None:
        return None
    mesh, axis = mapping
    ranks = 1 if mesh is None else mesh.shape[axis]
    local = (shape[0] // ranks,) + shape[1:]
    rows = math.prod(local[:-1])
    tiles = tiling(rows, local[-1])
    if tiles is None:
        return None
    words = jax.lax.bitcast_convert_type(key, jnp.int32)
    if mesh is None:
        return _draw(jnp.pad(words, (0, 1)), shape, p) != 0
    blocks = rows // tiles[0]

    def per_rank(words):
        first = jax.lax.axis_index(axis).astype(jnp.int32) * blocks
        return _draw(jnp.concatenate([words, first[None]]), local, p)

    return compat_shard_map(
        per_rank, mesh, (P(),),
        P(axis, *[None] * (len(shape) - 1)))(words) != 0


# no arithmetic the MFU convention counts; the bytes are the default
# model's: the mask written once (and the seeds' few words)
register_kernel_cost("dropout_mask", lambda operands, results: (0.0, None))
