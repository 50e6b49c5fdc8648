"""A head's lane statistic on the flat tensor, as two Pallas kernels.

    s = sum_head(x^2)                       (a head: 128 lanes side by side)
    y = x * rsqrt(s / denom + eps) [* scale] [* squash(gate)] [* constant]

`denom` 1 and `eps` 1e-6 is the l2norm of a delta rule's q and k
(`constant` their Dk^-1/2) wherever the rule's chunk-local kernels do
not take it themselves (`gated_delta.py RawQK`, PR 69: they share
`_rstd` below); `denom` 128 is `rms_norm(group_size=128)`,
alone or under silu(gate) / sigmoid(gate), with the one `scale` (128,)
the heads share.  `head_norm` below is what `ops/decoder.py` calls: the
kernels where `head_norm_takes` the shape, else `head_norm_xla`, the
composition over a (.., H, group) view, which is the lowering of every
other shape and the reference of both.  Same arithmetic: tiles in X's
dtype, float32 in VMEM, X's dtype out; the residuals are the inputs,
nothing is kept in between: the backward kernel recomputes a head's
rstd from X.

Why kernels: a head is one 128-lane block of a (rows, H x 128) tensor,
which a row tile holds as it lies.  XLA's two ways to the same sums are
both slow on the chip: the (.., H, 128) view of a float32 tensor is
re-laid (its tiles hold 8 rows x 128 lanes, the view's 8 HEADS x 128
lanes: 16.0 ms a step at 16384 rows x 16 heads, PR 49), and a product
with a 0 / 1 matrix at "highest" runs six bfloat16 passes to fill 32 of
the MXU's 128 result lanes (0.26 ms each, 88 a step at 8192 rows x 32
heads: PERF.md, PRs 65 and 68).

X may be a lane range of a wider array (`lanes`: q and k inside the
convolved QKV projection): the block's lane index picks it, no slice is
copied; X's gradient is padded back to the array's width, as a slice's
is.  A grid step holds a (row tile, lane tile) block of whole heads and
walks them; the scale's gradient leaves as eight sublanes of partial
sums a grid step that XLA adds up.

Kernel names `head_norm_fwd` / `head_norm_bwd`, registered costs in
bytes (no MXU work).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

GROUP = 128             # lanes a head: one lane tile
ROW_TILE = 512          # rows a grid step, and a loop body (one head)
LANE_TILE = 1024        # lanes a grid step
# what a grid step's double-buffered tiles may take of the 16 MiB Mosaic
# gives a kernel unasked (the loop body's float32 temporaries beside)
VMEM_BUDGET = 10 << 20

SQUASH = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}


class Form(NamedTuple):
    """What a call computes beside its operands: static, hashable."""
    start: int                  # X's first lane in its array
    width: int                  # X's lanes
    denom: float = 1.0
    eps: float = 1e-6
    constant: float = 1.0
    zero_centered: bool = False
    gate_activation: str = "silu"


def _tiles(rows, width, start, itemsize, n_tiles):
    """(row tile, lane tile) of a call that holds `n_tiles` blocks of
    X's kind a grid step, or None where the rows or the lanes have no
    whole tile."""
    lb = next((b for b in (LANE_TILE, 512, 256, GROUP)
               if width % b == 0 and start % b == 0), None)
    tr = ROW_TILE
    while lb and tr >= 16:
        if rows % tr == 0 and 2 * n_tiles * tr * lb * itemsize <= VMEM_BUDGET:
            return tr, lb
        tr //= 2
    return None


def head_norm_takes(group, width, rows, start=0):
    """Whether the kernels run a call, from its shape alone: heads of
    128 lanes that start on a lane tile, whole row tiles.  Another
    group size, a decode step's single row, a row count that is no
    multiple of 16 stay on the composition."""
    return (group == GROUP and rows > 0
            and _tiles(rows, width, start, 4, 5) is not None)


# -- kernel cost registry (observe/cost.py) ----------------------------
#
# Bytes once each, no MXU FLOP: what is read of an operand is at most
# the size of the result it shapes (X inside a wider array counts as
# its lane range, the scale as itself).

def head_norm_cost(operand_shapes, result_shapes):
    size = lambda shape: shape[1] * math.prod(shape[0])  # noqa: E731
    tile = size(result_shapes[0])
    return 0.0, float(sum(min(size(s), tile) for s in operand_shapes)
                      + sum(size(s) for s in result_shapes))


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("head_norm_fwd", head_norm_cost)
    register_kernel_cost("head_norm_bwd", head_norm_cost)


_register_costs()


# -- the composition ---------------------------------------------------

def _scale_of(scale, form):
    scale = scale.astype(jnp.float32)
    return 1.0 + scale if form.zero_centered else scale


def head_norm_xla(x, scale, gate, form, group=GROUP):
    """The module's formula over a (.., H, group) view: `x` the lane
    range itself, `scale` (group,) or None, `gate` x's shape or None."""
    f32 = jnp.float32
    split = x.shape[:-1] + (-1, group)
    xf = x.astype(f32).reshape(split)
    s = jnp.sum(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt((s if form.denom == 1 else s / form.denom) + form.eps)
    if scale is not None:
        y = y * _scale_of(scale, form)
    if gate is not None:
        y = y * SQUASH[form.gate_activation](gate.astype(f32).reshape(split))
    if form.constant != 1:
        y = y * form.constant
    return y.reshape(x.shape).astype(x.dtype)


# -- the kernels -------------------------------------------------------

def _head_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _rstd(x, form):
    return lax.rsqrt(_head_sum(x * x) * (1.0 / form.denom) + form.eps)


def _split(refs, flags):
    """The optional refs of a kernel's argument list, None where a flag
    is off, and the rest."""
    it = iter(refs)
    picked = [next(it) if flag else None for flag in flags]
    return picked, list(it)


def _fwd_kernel(x_ref, *refs, form, has_scale, has_gate):
    from jax.experimental import pallas as pl

    (s_ref, g_ref), (o_ref,) = _split(refs, (has_scale, has_gate))

    # a loop, not an unrolled `for`: a traced and lowered body each
    # cost the step's set-up (PR 46)
    def head(h, carry):
        lanes = pl.ds(pl.multiple_of(h * GROUP, GROUP), GROUP)
        x = x_ref[:, lanes].astype(jnp.float32)
        y = x * _rstd(x, form)
        if has_scale:
            y = y * s_ref[...]
        if has_gate:
            g = g_ref[:, lanes].astype(jnp.float32)
            sig = lax.logistic(g)
            y = y * (g * sig if form.gate_activation == "silu" else sig)
        if form.constant != 1:
            y = y * form.constant
        o_ref[:, lanes] = y.astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, o_ref.shape[1] // GROUP, head, 0)


def _bwd_kernel(x_ref, dy_ref, *refs, form, has_scale, has_gate):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    (s_ref, g_ref), outs = _split(refs, (has_scale, has_gate))
    dx_ref = outs[0]
    (dg_ref, ds_ref), _ = _split(outs[1:], (has_gate, has_scale))
    tr = dx_ref.shape[0]

    def head(h, acc):
        lanes = pl.ds(pl.multiple_of(h * GROUP, GROUP), GROUP)
        x = x_ref[:, lanes].astype(f32)
        rstd = _rstd(x, form)
        xn = x * rstd
        a = dy_ref[:, lanes].astype(f32)        # dY / d(xn ...), built up
        if form.constant != 1:
            a = a * form.constant
        if has_gate:
            g = g_ref[:, lanes].astype(f32)
            sig = lax.logistic(g)
            if form.gate_activation == "silu":
                squash, slope = g * sig, sig * (1.0 + g * (1.0 - sig))
            else:
                squash, slope = sig, sig * (1.0 - sig)
            dg = a * xn * slope
            a = a * squash
        if has_scale:
            acc = acc + lax.reduce_sum(
                lax.reshape(a * xn, (tr // 8, 8, GROUP)), [0])
            a = a * s_ref[...]
            if has_gate:
                dg = dg * s_ref[...]
        if has_gate:
            dg_ref[:, lanes] = dg.astype(dg_ref.dtype)
        dx = rstd * (a - xn * (_head_sum(a * xn) * (1.0 / form.denom)))
        dx_ref[:, lanes] = dx.astype(dx_ref.dtype)
        return acc

    acc = lax.fori_loop(0, dx_ref.shape[1] // GROUP, head,
                        jnp.zeros((8, GROUP), f32))
    if has_scale:
        ds_ref[0, 0] = acc


def _geometry(x, form, n_tiles):
    """(rows, width, row tile, lane tile, the block of X's kind in X's
    array, the same in an array of X's own width, the scale's)."""
    from jax.experimental import pallas as pl

    rows, width = x.shape[0], form.width
    tiles = _tiles(rows, width, form.start, x.dtype.itemsize, n_tiles)
    if tiles is None:
        raise ValueError(f"head_norm kernel: lanes {form.start} .. "
                         f"{form.start + width} of X {x.shape} have no "
                         f"tiling")
    tr, lb = tiles
    first = form.start // lb
    return (rows, width, tr, lb,
            pl.BlockSpec((tr, lb), lambda r, j: (r, first + j)),
            pl.BlockSpec((tr, lb), lambda r, j: (r, j)),
            pl.BlockSpec((1, GROUP), lambda r, j: (0, 0)))


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"))


_STATIC = ("form", "interpreted")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(x, scale, gate, form, interpreted=False):
    from . import pallas_call

    del interpreted         # (the trace's key: `pallas_call` reads it)
    flags = dict(has_scale=scale is not None, has_gate=gate is not None)
    rows, width, tr, lb, ranged, tile, row = _geometry(
        x, form, 2 + flags["has_gate"])
    operands, specs = [x], [ranged]
    if flags["has_scale"]:
        operands.append(_scale_of(scale, form).reshape(1, GROUP))
        specs.append(row)
    if flags["has_gate"]:
        operands.append(gate)
        specs.append(tile)
    return pallas_call(
        functools.partial(_fwd_kernel, form=form, **flags),
        name="head_norm_fwd", grid=(rows // tr, width // lb),
        in_specs=specs, out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, width), x.dtype),
        compiler_params=_params(),
    )(*operands)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(x, scale, gate, dy, form, interpreted=False):
    """(dX over X's lane range, dScale or None, dGate or None)."""
    from jax.experimental import pallas as pl

    from . import pallas_call

    del interpreted
    flags = dict(has_scale=scale is not None, has_gate=gate is not None)
    rows, width, tr, lb, ranged, tile, row = _geometry(
        x, form, 3 + 2 * flags["has_gate"])
    grid = (rows // tr, width // lb)
    operands, specs = [x, dy], [ranged, tile]
    out_specs, out_shape = [tile], [
        jax.ShapeDtypeStruct((rows, width), x.dtype)]
    if flags["has_scale"]:
        operands.append(_scale_of(scale, form).reshape(1, GROUP))
        specs.append(row)
    if flags["has_gate"]:
        operands.append(gate)
        specs.append(tile)
        out_specs.append(tile)
        out_shape.append(jax.ShapeDtypeStruct(gate.shape, gate.dtype))
    if flags["has_scale"]:
        out_specs.append(pl.BlockSpec((1, 1, 8, GROUP),
                                      lambda r, j: (r, j, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(grid + (8, GROUP),
                                              jnp.float32))
    outs = list(pallas_call(
        functools.partial(_bwd_kernel, form=form, **flags),
        name="head_norm_bwd", grid=grid,
        in_specs=specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=_params(),
    )(*operands))
    dscale = jnp.sum(outs.pop(), axis=(0, 1, 2)).astype(scale.dtype) \
        if flags["has_scale"] else None
    dgate = outs.pop() if flags["has_gate"] else None
    return outs[0], dscale, dgate


def _record(x):
    """Count a kernel call where a STEP traces it (outside the jitted
    call, which is traced once a shape; not where a Program build
    evaluates `rms_norm` for its shapes at the stand-in batch); gives
    the interpret gate, which keys that call's cache."""
    from ...core.shape_inference import inferring_shapes
    from ...observe.monitoring import runtime_stats
    from . import interpret

    if not inferring_shapes():
        runtime_stats.record_head_norm(x.shape[0])
    return interpret()


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def head_norm_kernel(x, scale, gate, form):
    """The module's formula by the kernels.  x (rows, W): the array that
    holds X at lanes `form.start` .. + `form.width`; scale (128,) or
    None; gate (rows, width) or None.  (rows, width) in x's dtype."""
    return _fwd_call(x, scale, gate, form, interpreted=_record(x))


def _vjp_fwd(x, scale, gate, form):
    return head_norm_kernel(x, scale, gate, form), (x, scale, gate)


def lane_range_gradient(dx, width, start):
    """The gradient of lanes `start` .. of an array `width` lanes wide
    as that array's: a slice's (zeros beside it)."""
    if dx.shape[-1] == width:
        return dx
    return jnp.pad(dx, ((0, 0),) * (dx.ndim - 1)
                   + ((start, width - start - dx.shape[-1]),))


def _vjp_bwd(form, res, dy):
    x, scale, gate = res
    dx, dscale, dgate = _bwd_call(x, scale, gate, dy.astype(x.dtype), form,
                                  interpreted=_record(x))
    return lane_range_gradient(dx, x.shape[1], form.start), dscale, dgate


head_norm_kernel.defvjp(_vjp_fwd, _vjp_bwd)


def head_norm(x, scale=None, gate=None, *, group=GROUP, lanes=None,
              denom=1.0, eps=1e-6, constant=1.0, zero_centered=False,
              gate_activation="silu"):
    """The module's formula over lanes `lanes` = (start, width) of x
    (.., W) (None: all of them), heads of `group` lanes; (.., width) in
    x's dtype.  The kernels where `head_norm_takes` the shape, else the
    composition on the sliced range."""
    if gate_activation not in SQUASH:
        raise NotImplementedError(
            f"head_norm: gate_activation {gate_activation!r} is not built")
    start, width = lanes or (0, x.shape[-1])
    rows = math.prod(x.shape[:-1])
    form = Form(start, width, float(denom), float(eps), float(constant),
                bool(zero_centered), gate_activation)
    if head_norm_takes(group, width, rows, start):
        y = head_norm_kernel(
            x.reshape(rows, x.shape[-1]), scale,
            None if gate is None else gate.reshape(rows, width), form)
        return y.reshape(x.shape[:-1] + (width,))
    return head_norm_xla(x[..., start:start + width] if lanes else x,
                         scale, gate, form, group)
