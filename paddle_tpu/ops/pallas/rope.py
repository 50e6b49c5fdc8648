"""The q / k preparation of an attention layer as two Pallas kernels:
RMS-norm a head, then turn it (rotate-half), in one pass.

    xhat = x * rsqrt(mean_head(x^2) + eps) * scale
    y    = xhat * C + roll(xhat, +R/2) * Sa + roll(xhat, -R/2) * Sb

`ops/decoder.py rope` is the op; this is its lowering for the shapes
`rope_kernel_takes`, the composition there the lowering of every other
shape and the reference of both.  Same arithmetic: tiles in X's dtype,
float32 in VMEM, X's dtype out; the residuals are X and the scale,
nothing is kept in between: the backward kernel recomputes a head's
rstd and xhat from X, turns dy by the negative angle (the same tables
with the sines' sign changed), applies the norm's backward a head and
adds the scale's gradient up over the row grid.

X is the head-grouped (N, T, H*D) projection that
`flash_attention(layout="nthd")` takes, in (row tile, H*D) blocks; a
grid step walks its heads, each a (row tile, D) slab of whole 128-lane
groups, so the mean a head is one lane reduction and the turn one or
two lane rotations.  The tables are (T, D) float32 OPERANDS made
outside the kernel from the op's cos and sin (T, R/2): C holds the
cosines twice and 1 on the lanes R.. that do not turn; Sa the sines on
lanes R/2..R (whose partner is R/2 lanes down), Sb their negatives on
lanes 0..R/2 (partner R/2 lanes up), both 0 elsewhere, so a partial
head needs no slice and no concatenation.  Where the whole head turns
the two rotations are one (+D/2 and -D/2 meet) and Sa + Sb one table.
One traced kernel a shape serves every layer whatever its frequencies.

Only a call that norms: a bare turn is one elementwise pass that XLA
fuses into copies it makes anyway, and as a kernel of its own it cost
the looped cell 1.1 % of its step (192 calls of 4096 x 2048 a step:
11.8 ms of kernels for XLA's 7.6; PERF.md, PR 48).

Kernel names `rope_fwd` / `rope_bwd`, registered costs in bytes (no
MXU work).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# rows a grid step, and a loop body (one head of the tile): the chain
# load - reduce - rsqrt - rotate - store is ~165 ns long whatever it
# carries, and a body of 64 rows is all latency (3.5 x the time of one
# of 512: my chip runs, PR 48)
ROW_TILE = 512
# what a grid step's double-buffered tiles may take of v5e's 128 MiB
VMEM_BUDGET = 48 << 20
VMEM_LIMIT = 96 << 20


def _row_tile(t, width, itemsize, row_tile=None):
    """Rows a grid step of a call, or None where T has no whole tile
    that fits: backward, double-buffered, X, dy and dX."""
    tr = row_tile or ROW_TILE
    while tr >= 16:
        if t % tr == 0 and 2 * 3 * tr * width * itemsize <= VMEM_BUDGET:
            return tr
        tr //= 2
    return None


def rope_kernel_takes(t, n_head, head_dim, rotary_dim=None, interleave=False,
                      normed=True, itemsize=2):
    """Whether the kernels run a call, from its shape and attrs alone:
    a norm to fold, rotate-half over an even part of heads of whole
    128-lane groups, whole row tiles that fit VMEM.  A bare turn, pairs
    (`interleave`), a head of 64, a decode step's single row stay on
    the composition."""
    rotary = rotary_dim or head_dim
    if (not normed or interleave or head_dim % 128 or rotary % 2
            or not 0 < rotary <= head_dim):
        return False
    return _row_tile(t, n_head * head_dim, itemsize) is not None


# -- kernel cost registry (observe/cost.py) ----------------------------
#
# Bytes once each, no MXU FLOP, which is the default model (operands
# and results once): forward X, the tables, the scale and Out; backward
# X, dOut, the tables, the scale, dX and the scale's partial sums.

def rope_cost(operand_shapes, result_shapes):
    return 0.0, None


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("rope_fwd", rope_cost)
    register_kernel_cost("rope_bwd", rope_cost)


_register_costs()


# -- the tables ----------------------------------------------------------

def tables(cos, sin, head_dim):
    """((shift, ...), C, (S, ...)): the lane rotations of a head and the
    (T, D) float32 tables of the module's text, from cos and sin
    (T, R/2)."""
    half = cos.shape[1]
    rest = head_dim - 2 * half
    zero = jnp.zeros_like(sin)
    if not rest:
        return ((half,), jnp.concatenate([cos, cos], 1),
                (jnp.concatenate([-sin, sin], 1),))
    still = jnp.zeros((cos.shape[0], rest), cos.dtype)
    return ((half, head_dim - half),
            jnp.concatenate([cos, cos, still + 1.0], 1),
            (jnp.concatenate([zero, sin, still], 1),
             jnp.concatenate([-sin, zero, still], 1)))


# -- the kernels -------------------------------------------------------

def _head_mean(x, d):
    return jnp.sum(x, axis=1, keepdims=True) * (1.0 / d)


def _fwd_kernel(x_ref, s_ref, c_ref, *refs, d, shifts, eps):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sin_refs, o_ref = refs[:-1], refs[-1]

    # a loop, not an unrolled `for`: a traced and lowered body each
    # cost the step's set-up (PR 46)
    def head(h, carry):
        lanes = pl.ds(pl.multiple_of(h * d, d), d)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        x = x * lax.rsqrt(_head_mean(x * x, d) + eps) * s_ref[...]
        y = x * c_ref[...]
        for shift, sn in zip(shifts, sin_refs):
            y = y + pltpu.roll(x, shift, 1) * sn[...]
        o_ref[0, :, lanes] = y.astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, o_ref.shape[2] // d, head, 0)


def _bwd_kernel(x_ref, dy_ref, s_ref, c_ref, *refs, d, shifts, eps):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    sin_refs, dx_ref, ds_ref = refs[:-2], refs[-2], refs[-1]
    tr = dx_ref.shape[1]

    def head(h, acc):
        lanes = pl.ds(pl.multiple_of(h * d, d), d)
        dy = dy_ref[0, :, lanes].astype(f32)
        g = dy * c_ref[...]             # the turn's transpose: -angle
        for shift, sn in zip(shifts, sin_refs):
            g = g - pltpu.roll(dy, shift, 1) * sn[...]
        x = x_ref[0, :, lanes].astype(f32)
        rstd = lax.rsqrt(_head_mean(x * x, d) + eps)
        xn = x * rstd
        gx = g * xn
        scale = s_ref[...]
        dx = rstd * (g * scale - xn * _head_mean(gx * scale, d))
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        return acc + lax.reduce_sum(lax.reshape(gx, (tr // 8, 8, d)), [0])

    acc = lax.fori_loop(0, dx_ref.shape[2] // d, head, jnp.zeros((8, d), f32))

    @pl.when(pl.program_id(1) == 0)
    def _first_row_tile():
        ds_ref[...] = jnp.zeros(ds_ref.shape, f32)

    ds_ref[0] += acc


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _geometry(x, n_head, row_tile):
    n, t, width = x.shape
    d = width // n_head
    tr = _row_tile(t, width, x.dtype.itemsize, row_tile)
    if d * n_head != width or d % 128 or tr is None:
        raise ValueError(f"rope kernel: X {x.shape} of {n_head} heads has "
                         f"no tiling")
    return n, t, width, d, tr


def _specs(tr, width, d, n_tables):
    """The block of X's kind, and those of the scale and the tables."""
    from jax.experimental import pallas as pl

    tile = pl.BlockSpec((1, tr, width), lambda b, r: (b, r, 0))
    table = pl.BlockSpec((tr, d), lambda b, r: (r, 0))
    return tile, [pl.BlockSpec((1, d), lambda b, r: (0, 0))] + [
        table] * n_tables


_STATIC = ("n_head", "eps", "row_tile", "interpreted")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(x, scale, cos, sin, n_head, eps, row_tile=None,
              interpreted=False):
    from . import pallas_call

    n, t, width, d, tr = _geometry(x, n_head, row_tile)
    shifts, c, sins = tables(cos, sin, d)
    tile, others = _specs(tr, width, d, 1 + len(sins))
    return pallas_call(
        functools.partial(_fwd_kernel, d=d, shifts=shifts, eps=eps),
        name="rope_fwd", grid=(n, t // tr),
        in_specs=[tile] + others, out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_params(),
    )(x, scale.reshape(1, d), c, *sins)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(x, scale, cos, sin, dy, n_head, eps, row_tile=None,
              interpreted=False):
    from jax.experimental import pallas as pl

    from . import pallas_call

    n, t, width, d, tr = _geometry(x, n_head, row_tile)
    shifts, c, sins = tables(cos, sin, d)
    tile, others = _specs(tr, width, d, 1 + len(sins))
    dx, dscale = pallas_call(
        functools.partial(_bwd_kernel, d=d, shifts=shifts, eps=eps),
        name="rope_bwd", grid=(n, t // tr),
        in_specs=[tile, tile] + others,
        out_specs=[tile, pl.BlockSpec((1, 8, d), lambda b, r: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((n, 8, d), jnp.float32)],
        compiler_params=_params(),
    )(x, dy, scale.reshape(1, d), c, *sins)
    return dx, jnp.sum(dscale, axis=(0, 1)).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def rope_kernel(x, scale, cos, sin, n_head, eps=1e-5, row_tile=None):
    """Out of the op by the kernels.  x (N, T, H*D); scale (D,) float32,
    what a normalised head is multiplied by; cos, sin (T, R/2) float32,
    the factor on them already.  The row tile is the module's unless a
    caller that times it says otherwise."""
    from . import interpret

    return _fwd_call(x, scale, cos, sin, n_head, eps, row_tile,
                     interpreted=interpret())


def _vjp_fwd(x, scale, cos, sin, n_head, eps, row_tile):
    return rope_kernel(x, scale, cos, sin, n_head, eps, row_tile), (
        x, scale, cos, sin)


def _vjp_bwd(n_head, eps, row_tile, res, dy):
    from . import interpret

    x, scale, cos, sin = res
    dx, dscale = _bwd_call(x, scale, cos, sin, dy.astype(x.dtype), n_head,
                           eps, row_tile, interpreted=interpret())
    return dx, dscale, jnp.zeros_like(cos), jnp.zeros_like(sin)


rope_kernel.defvjp(_vjp_fwd, _vjp_bwd)
