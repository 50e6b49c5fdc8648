"""The selective scan of a Mamba-1 state-space mixer (Gu & Dao,
arXiv:2312.00752), forward and backward (Pallas, TPU), and the XLA
lowering of the same recurrence.

For channel c and state n, with a state s (D, S) in float32 that starts
at 0:

    dt_t[c]   = softplus(Delta_t[c] + DeltaBias[c])
    s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n C_t[n] s_t[c, n] + D[c] u_t[c]

`Delta` is the step projection's output BEFORE its bias and the
softplus: the kernels add the float32 bias to it and take the softplus
in VMEM, so no float32 (N, T, D) step size, and no gradient of one, is
ever a tensor in HBM (168 MB each a layer at 8192 x 5120), and a
bfloat16 projection is rounded where it is small, not after a bias of
-7 has been added to it.  B and C are one group, shared by every
channel; the decay is a (channel, state) pair's own, so nothing here is
a matrix product: the work is the vector unit's, an exponential and a
multiply-add a (position, channel, state), 671 M of them a layer at
8192 x 5120 x 16.

The benchmark's reference and the tests write the recurrence as a
`lax.scan` over positions.  Two lowerings of it here, chosen by the
shape alone (`selective_scan_takes`: S = 16, T whole chunks of `CHUNK`
positions, D whole tiles of 128 channels):

**The kernels.**  Grid (batch, time chunk, channel tile), the last two
sequential.  A tile's state is (16 sublanes = states, 128 lanes =
channels) float32, two vector registers a 128 channels; the state of
ALL channel tiles of a sequence lives in a VMEM scratch (5120 x 16 x 4
B = 328 kB) from chunk to chunk, so the channel tiles can be the INNER
grid axis and what does not depend on the channel (B, C) is fetched
once a chunk.  A position's B_t and C_t arrive as a (16, 128) tile
whose lanes are all equal (`_lanes`: XLA broadcasts (N, T, 16) to
(N, T x 16, 128) in the operands' dtype, 34 MB each a layer in
bfloat16): the kernel multiplies it against the state as it lies and
no lane is moved in the loop.  dt and dt * u of a chunk are made once,
whole tiles at a time; the loop over the chunk's positions reads a row
of each, broadcast over the 16 sublanes.  `selective_scan_fwd` writes y
and the state that ENTERS each chunk (float32: (T / CHUNK) x D x 16 x
4 B = 10.5 MB a layer at 8192).  `selective_scan_bwd` walks the chunks
in reverse: it rebuilds the chunk's states from its entry state into
VMEM ((CHUNK + 1) x 16 rows), runs the adjoint recurrence

    a_t = C_t dy_t + exp(dt_{t+1} A) a_{t+1}              (a = dL/ds)

and writes dU and dDelta as the operands lie, dB and dC a lane (summed
over the channel tiles in VMEM; XLA sums the 128 lanes), and dA, dD and
dDeltaBias summed over time in blocks that stay in VMEM for a whole
sequence.  Float32 everywhere inside; u and y in the operands' dtype in
HBM.

**The XLA lowering** (`scan_xla`): a `lax.scan` over chunks of
`XLA_CHUNK` positions, the in-chunk part an `associative_scan` over
(decay, input) pairs under `jax.checkpoint`, which XLA differentiates:
it materialises (chunk, D, S) float32 pairs a chunk.  The fall-back for
shapes the kernels do not tile and the path the CPU presets run.

Tied by ONE `custom_vjp`, `selective_scan`.  Inside a recompute segment
the forward rule's two results are named (`ops/pallas keep_residuals`):
the segment keeps y and the entry states, so its backward pass runs
`selective_scan_bwd` on them and no forward scan a second time.
`runtime_stats.selective_scans_kernel` / `_xla` count the scans traced
each way, `selective_scan_chunks` the chunks x batch the kernels walk.
The benchmark finds the kernels by the PREFIX `selective_scan`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 256             # positions a grid step
CHANNEL_TILE = 512      # channels a grid step (forward)
BWD_CHANNEL_TILE = 256  # the backward loop carries twice the registers
STATE = 16              # the kernels' d_state: a bf16 tile's sublanes
LANES = 128
XLA_CHUNK = 64
VMEM_LIMIT = 100 << 20


def _tile(d, most):
    td = most
    while td > LANES and d % td:
        td //= 2
    return td


def selective_scan_takes(t, d, s):
    """Whether the kernels run a call: from the shape alone."""
    return s == STATE and t % CHUNK == 0 and d % LANES == 0


# softplus with the accuracy of its argument: log1p(z) for z = exp(-|x|)
# as its series where z is small (the chip's float32 log1p is good to
# 1e-4 only, and log(1 + z) loses z's low bits), log(1 + z) above
_SERIES_BELOW = 0.125
_SERIES_TERMS = 9


def _log1p_small(z):
    acc = jnp.full_like(z, (-1.0) ** (_SERIES_TERMS + 1) / _SERIES_TERMS)
    for k in range(_SERIES_TERMS - 1, 0, -1):
        acc = acc * z + (-1.0) ** (k + 1) / k
    return acc * z


def _softplus_value(x):
    z = jnp.exp(-jnp.abs(x))
    return jnp.maximum(x, 0.0) + jnp.where(
        z < _SERIES_BELOW, _log1p_small(z), jnp.log(1.0 + z))


@jax.custom_jvp
def softplus(x):
    """log(1 + exp(x)) in float32, good to float32 where it is 1e-4."""
    return _softplus_value(x)


@softplus.defjvp
def _softplus_jvp(primals, tangents):
    x, = primals
    return _softplus_value(x), tangents[0] * jax.nn.sigmoid(x)


# -- kernel cost registry (observe/cost.py) ----------------------------
#
# A state update (an exponential, two multiplies and an add, and the
# read-out's multiply-add) is counted as 8 FLOP a (position, channel,
# state), none of them on the MXU; the backward's adjoint recurrence
# and its rebuilt states as 3 x that.  Bytes: operands and results once
# (the default model).

def _updates(operand_shapes):
    (n, t, d), _ = operand_shapes[0]
    return float(n * t * d * STATE)


def fwd_cost(operand_shapes, result_shapes):
    return 8.0 * _updates(operand_shapes), None


def bwd_cost(operand_shapes, result_shapes):
    return 24.0 * _updates(operand_shapes), None


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("selective_scan_fwd", fwd_cost)
    register_kernel_cost("selective_scan_bwd", bwd_cost)


_register_costs()


# -- the XLA lowering --------------------------------------------------

def _chunk_xla(s, xs, a):
    """One chunk of every channel: s (N, D, S) float32 in, (the state
    that leaves, the chunk's sum_n C s (N, L, D))."""
    dt, du, b, c = xs                  # (N, L, D) x 2, (N, L, S) x 2
    decay = jnp.exp(dt[..., None] * a)              # (N, L, D, S)
    write = du[..., None] * b[:, :, None, :]

    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    cum, own = lax.associative_scan(combine, (decay, write), axis=1)
    states = cum * s[:, None] + own
    return states[:, -1], jnp.einsum("nlds,nls->nld", states, c)


def scan_xla(u, delta, a, b, c, d, delta_bias):
    """y (N, T, D) of the recurrence at the top of this file; a (D, S)
    the decay rates themselves (negative).  Float32 inside."""
    f32 = jnp.float32
    n, t, width = u.shape
    tail = -t % XLA_CHUNK
    uf = u.astype(f32)
    dt = softplus(delta.astype(f32) + delta_bias.astype(f32))
    operands = [dt, dt * uf, b.astype(f32), c.astype(f32)]
    if tail:        # positions that write nothing and do not decay
        operands = [jnp.pad(x, ((0, 0), (0, tail), (0, 0)))
                    for x in operands]
    nc = (t + tail) // XLA_CHUNK

    def by_chunk(x):                   # (N, T, W) -> (nc, N, L, W)
        return jnp.moveaxis(x.reshape(n, nc, XLA_CHUNK, x.shape[2]), 1, 0)

    step = jax.checkpoint(functools.partial(_chunk_xla, a=a.astype(f32)))
    _, y = lax.scan(step, jnp.zeros((n, width, a.shape[1]), f32),
                    tuple(by_chunk(x) for x in operands))
    y = jnp.moveaxis(y, 0, 1).reshape(n, t + tail, width)[:, :t]
    return (y + d.astype(f32) * uf).astype(u.dtype)


# -- the kernels -------------------------------------------------------

GROUP = 8               # positions a trip of a kernel's loop: a tile's rows


def _subtiles(td):
    return [slice(i * LANES, (i + 1) * LANES) for i in range(td // LANES)]


def _position(ref, t):
    """The (16, 128) tile of position t of a (1, L x 16, 128) block."""
    from jax.experimental import pallas as pl

    return ref[0, pl.ds(pl.multiple_of(t * STATE, STATE), STATE), :].astype(
        jnp.float32)


def _group(g):
    """The rows of positions 8 g .. 8 g + 7 of a (L, td) scratch: a
    whole float32 tile (Mosaic loads no single row at a dynamic
    index)."""
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)


def _over_states(tile, k):
    """Row k (static) of an (8, 128) tile over the 16 sublanes."""
    return jnp.broadcast_to(tile[k:k + 1, :], (STATE, LANES))


def _into_row(tile, k, column_sum):
    """`tile` (8, 128) with row k set to a (16, 128) product's sum over
    its sublanes."""
    row = lax.broadcasted_iota(jnp.int32, (GROUP, LANES), 0)
    return jnp.where(row == k, jnp.broadcast_to(
        jnp.sum(column_sum, axis=0, keepdims=True), (GROUP, LANES)), tile)


def _prologue(u_ref, dl_ref, bias_ref, dt_s, du_s):
    """dt and dt * u of the chunk into scratch; (u, Delta + bias)."""
    f32 = jnp.float32
    u = u_ref[0].astype(f32)
    x = dl_ref[0].astype(f32) + bias_ref[...]
    dt = _softplus_value(x)
    dt_s[...] = dt
    du_s[...] = dt * u
    return u, x


def _fwd_kernel(u_ref, dl_ref, bias_ref, a_ref, bb_ref, cb_ref, d_ref,
                y_ref, entry_ref, state, dt_s, du_s, y_s, *, chunk):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    td = y_ref.shape[2]
    j, c = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _a_sequence_starts_from_zero():
        state[c] = jnp.zeros((STATE, td), f32)

    entry_ref[0, 0] = state[c]
    u, _ = _prologue(u_ref, dl_ref, bias_ref, dt_s, du_s)
    tiles = _subtiles(td)
    a = [a_ref[:, lanes] for lanes in tiles]

    def trip(g, s):
        rows = _group(g)
        dt8 = [dt_s[rows, lanes] for lanes in tiles]
        du8 = [du_s[rows, lanes] for lanes in tiles]
        y8 = [jnp.zeros((GROUP, LANES), f32) for _ in tiles]
        s = list(s)
        for k in range(GROUP):
            t = g * GROUP + k
            bb, cb = _position(bb_ref, t), _position(cb_ref, t)
            for i in range(len(tiles)):
                s[i] = (jnp.exp(_over_states(dt8[i], k) * a[i]) * s[i]
                        + bb * _over_states(du8[i], k))
                y8[i] = _into_row(y8[i], k, cb * s[i])
        for i, lanes in enumerate(tiles):
            y_s[rows, lanes] = y8[i]
        return tuple(s)

    s = lax.fori_loop(0, chunk // GROUP, trip,
                      tuple(state[c, :, lanes] for lanes in tiles))
    for si, lanes in zip(s, tiles):
        state[c, :, lanes] = si
    y_ref[0] = (y_s[...] + d_ref[...] * u).astype(y_ref.dtype)


def _bwd_kernel(u_ref, dl_ref, bias_ref, a_ref, bb_ref, cb_ref, d_ref,
                entry_ref, dy_ref, du_ref, ddl_ref, dbb_ref, dcb_ref,
                da_ref, dvec_ref, adjoint, states, dt_s, du_s, g_s, r1_s,
                r2_s, *, chunk):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    td = du_ref.shape[2]
    j, c = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _nothing_follows_the_last_chunk():
        adjoint[c] = jnp.zeros((STATE, td), f32)
        da_ref[0, c] = jnp.zeros((STATE, td), f32)
        dvec_ref[0, c] = jnp.zeros((8, td), f32)

    @pl.when(c == 0)
    def _the_first_channel_tile_of_a_chunk():
        dbb_ref[...] = jnp.zeros(dbb_ref.shape, f32)
        dcb_ref[...] = jnp.zeros(dcb_ref.shape, f32)

    u, x = _prologue(u_ref, dl_ref, bias_ref, dt_s, du_s)
    g = dy_ref[0].astype(f32)
    g_s[...] = g
    tiles = _subtiles(td)
    a = [a_ref[:, lanes] for lanes in tiles]

    def state_rows(t):      # rows 16 (t + 1) .. hold s_t, rows 0 .. the
        return pl.ds(pl.multiple_of((t + 1) * STATE, STATE), STATE)  # entry

    # the chunk's states again, from the state that entered it
    states[0:STATE, :] = entry_ref[0, 0]

    def rebuild(grp, s):
        rows = _group(grp)
        dt8 = [dt_s[rows, lanes] for lanes in tiles]
        du8 = [du_s[rows, lanes] for lanes in tiles]
        s = list(s)
        for k in range(GROUP):
            t = grp * GROUP + k
            bb = _position(bb_ref, t)
            for i, lanes in enumerate(tiles):
                s[i] = (jnp.exp(_over_states(dt8[i], k) * a[i]) * s[i]
                        + bb * _over_states(du8[i], k))
                states[state_rows(t), lanes] = s[i]
        return tuple(s)

    lax.fori_loop(0, chunk // GROUP, rebuild,
                  tuple(states[0:STATE, lanes] for lanes in tiles))

    def adjoint_trip(i, carry):
        grp = chunk // GROUP - 1 - i
        rows = _group(grp)
        later, da = list(carry[0]), list(carry[1])
        dt8 = [dt_s[rows, lanes] for lanes in tiles]
        du8 = [du_s[rows, lanes] for lanes in tiles]
        g8 = [g_s[rows, lanes] for lanes in tiles]
        r1 = [jnp.zeros((GROUP, LANES), f32) for _ in tiles]
        r2 = [jnp.zeros((GROUP, LANES), f32) for _ in tiles]
        for k in reversed(range(GROUP)):
            t = grp * GROUP + k
            bb, cb = _position(bb_ref, t), _position(cb_ref, t)
            at = pl.ds(pl.multiple_of(t * STATE, STATE), STATE)
            dbb = dcb = None
            for n, lanes in enumerate(tiles):
                dt_b, g_b = _over_states(dt8[n], k), _over_states(g8[n], k)
                adj = cb * g_b + later[n]
                part_c = g_b * states[state_rows(t), lanes]
                part_b = adj * _over_states(du8[n], k)
                dcb = part_c if dcb is None else dcb + part_c
                dbb = part_b if dbb is None else dbb + part_b
                decay = jnp.exp(dt_b * a[n])
                h = adj * states[state_rows(t - 1), lanes] * decay
                r1[n] = _into_row(r1[n], k, adj * bb)
                r2[n] = _into_row(r2[n], k, h * a[n])
                da[n] = da[n] + h * dt_b
                later[n] = decay * adj
            dbb_ref[0, at, :] += dbb
            dcb_ref[0, at, :] += dcb
        for n, lanes in enumerate(tiles):
            r1_s[rows, lanes] = r1[n]
            r2_s[rows, lanes] = r2[n]
        return tuple(later), tuple(da)

    zeros = tuple(jnp.zeros((STATE, LANES), f32) for _ in tiles)
    later, da = lax.fori_loop(
        0, chunk // GROUP, adjoint_trip,
        (tuple(adjoint[c, :, lanes] for lanes in tiles), zeros))
    for n, lanes in enumerate(tiles):
        adjoint[c, :, lanes] = later[n]
        da_ref[0, c, :, lanes] += da[n]
    r1 = r1_s[...]
    dx = (u * r1 + r2_s[...]) * jax.nn.sigmoid(x)
    du_ref[0] = (dt_s[...] * r1 + d_ref[...] * g).astype(du_ref.dtype)
    ddl_ref[0] = dx.astype(ddl_ref.dtype)
    dvec_ref[0, c, 0:1, :] += jnp.sum(g * u, axis=0, keepdims=True)
    dvec_ref[0, c, 1:2, :] += jnp.sum(dx, axis=0, keepdims=True)


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _lanes(x):
    """B or C (N, T, 16) as the kernels read it: (N, T x 16, 128), a
    position's 16 numbers down the sublanes, every lane the same."""
    n, t, s = x.shape
    return jnp.broadcast_to(x[..., None], (n, t, s, LANES)).reshape(
        n, t * s, LANES)


def _specs(td, time):
    """Block specs of a grid (batch, chunk, channel tile): (a (CHUNK,
    td) tile of u's layout, a (1, td) row, the (16, td) rates, a
    chunk's (CHUNK x 16, 128) tile of B or C, a chunk's entry state)."""
    from jax.experimental import pallas as pl

    return (pl.BlockSpec((1, CHUNK, td), lambda b, j, c: (b, time(j), c)),
            pl.BlockSpec((1, td), lambda b, j, c: (0, c)),
            pl.BlockSpec((STATE, td), lambda b, j, c: (0, c)),
            pl.BlockSpec((1, CHUNK * STATE, LANES),
                         lambda b, j, c: (b, time(j), 0)),
            pl.BlockSpec((1, 1, STATE, td),
                         lambda b, j, c: (b, time(j), 0, c)))


@functools.partial(jax.jit, static_argnames=("interpreted",))
def _fwd_call(u, delta, a, b, c, d, delta_bias, interpreted=False):
    from jax.experimental.pallas import tpu as pltpu

    from . import pallas_call

    f32 = jnp.float32
    n, t, width = u.shape
    td = _tile(width, CHANNEL_TILE)
    nc, nd = t // CHUNK, width // td
    wide, row, rates, shared, entry = _specs(td, lambda j: j)
    tile = pltpu.VMEM((CHUNK, td), f32)
    return pallas_call(
        functools.partial(_fwd_kernel, chunk=CHUNK),
        name="selective_scan_fwd", grid=(n, nc, nd),
        in_specs=[wide, wide, row, rates, shared, shared, row],
        out_specs=[wide, entry],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((n, nc, STATE, width), f32)],
        scratch_shapes=[pltpu.VMEM((nd, STATE, td), f32), tile, tile, tile],
        compiler_params=_params(),
    )(u, delta, delta_bias.astype(f32).reshape(1, width), a.astype(f32).T,
      _lanes(b), _lanes(c), d.astype(f32).reshape(1, width))


@functools.partial(jax.jit, static_argnames=("interpreted",))
def _bwd_call(u, delta, a, b, c, d, delta_bias, entry, dy,
              interpreted=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from . import pallas_call

    f32 = jnp.float32
    n, t, width = u.shape
    s = a.shape[1]
    td = _tile(width, BWD_CHANNEL_TILE)
    nc, nd = t // CHUNK, width // td
    wide, row, rates, shared, entry_spec = _specs(
        td, lambda j: nc - 1 - j)

    def whole(rows):        # summed over a sequence, in VMEM throughout
        return pl.BlockSpec((1, nd, rows, td), lambda b, j, c: (b, 0, 0, 0))

    tile = pltpu.VMEM((CHUNK, td), f32)
    du, ddelta, dbb, dcb, da, dvec = pallas_call(
        functools.partial(_bwd_kernel, chunk=CHUNK),
        name="selective_scan_bwd", grid=(n, nc, nd),
        in_specs=[wide, wide, row, rates, shared, shared, row, entry_spec,
                  wide],
        out_specs=[wide, wide, shared, shared, whole(STATE), whole(8)],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(delta.shape, delta.dtype),
                   jax.ShapeDtypeStruct((n, t * s, LANES), f32),
                   jax.ShapeDtypeStruct((n, t * s, LANES), f32),
                   jax.ShapeDtypeStruct((n, nd, STATE, td), f32),
                   jax.ShapeDtypeStruct((n, nd, 8, td), f32)],
        scratch_shapes=[pltpu.VMEM((nd, STATE, td), f32),
                        pltpu.VMEM(((CHUNK + 1) * STATE, td), f32),
                        tile, tile, tile, tile, tile],
        compiler_params=_params(),
    )(u, delta, delta_bias.astype(f32).reshape(1, width), a.astype(f32).T,
      _lanes(b), _lanes(c), d.astype(f32).reshape(1, width), entry, dy)

    def channels(x):        # (N, nd, rows, td) summed over N -> (rows, D)
        return jnp.moveaxis(jnp.sum(x, axis=0), 0, 1).reshape(
            x.shape[2], width)

    dvec = channels(dvec)
    return (du, ddelta, channels(da).T.astype(a.dtype),
            jnp.sum(dbb, axis=-1).reshape(b.shape).astype(b.dtype),
            jnp.sum(dcb, axis=-1).reshape(c.shape).astype(c.dtype),
            dvec[0].astype(d.dtype), dvec[1].astype(delta_bias.dtype))


@jax.custom_vjp
def scan_kernel(u, delta, a, b, c, d, delta_bias):
    """`scan_xla` by the Pallas kernels (`selective_scan_takes`)."""
    return _vjp_fwd(u, delta, a, b, c, d, delta_bias)[0]


def _record(u):
    from ...observe.monitoring import runtime_stats
    from . import interpret

    runtime_stats.record_selective_scan(
        True, u.shape[0] * (u.shape[1] // CHUNK))
    return interpret()


def _vjp_fwd(u, delta, a, b, c, d, delta_bias):
    from . import SCAN_RESIDUALS, keep_residuals

    y, entry = keep_residuals(
        *_fwd_call(u, delta, a, b, c, d, delta_bias,
                   interpreted=_record(u)), names=SCAN_RESIDUALS)
    return y, (u, delta, a, b, c, d, delta_bias, entry)


def _vjp_bwd(res, dy):
    u, *_ = res
    return _bwd_call(*res, dy.astype(u.dtype), interpreted=_record(u))


scan_kernel.defvjp(_vjp_fwd, _vjp_bwd)


def selective_scan(u, delta, a, b, c, d, delta_bias):
    """y (N, T, D) of the recurrence at the top of this file.  u, delta
    (N, T, D); a (D, S) the decay rates (negative: -exp(A_log)); b, c
    (N, T, S); d, delta_bias (D,).  The kernels where
    `selective_scan_takes` the shape, else `scan_xla`."""
    n, t, width = u.shape
    s = a.shape[1]
    if delta.shape != u.shape or a.shape != (width, s) \
            or b.shape != (n, t, s) or c.shape != b.shape \
            or d.shape != (width,) or delta_bias.shape != (width,):
        raise ValueError(
            f"selective_scan: u {u.shape}, delta {delta.shape}, a "
            f"{a.shape}, b {b.shape}, c {c.shape}, d {d.shape}, delta_bias "
            f"{delta_bias.shape} are not D channels of S states over T "
            f"positions")
    if selective_scan_takes(t, width, s):
        return scan_kernel(u, delta, a, b, c, d, delta_bias)
    from ...observe.monitoring import runtime_stats

    runtime_stats.record_selective_scan(False, 0)
    return scan_xla(u, delta, a, b, c, d, delta_bias)
