"""The scan of a Mamba-2 state-space mixer (state-space duality, Dao &
Gu, arXiv:2405.21060) in its chunked matrix-product form, forward and
backward (Pallas, TPU), and the XLA lowering of the same chunks.

For head h with x_t[h] in R^P, a state S[h] (P, N) in float32 that
starts at 0, ONE decay a (position, head) and B_t, C_t in R^N shared by
the heads of a group:

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] B_t^T
    y_t[h] = S_t[h] C_t + D[h] x_t[h]

x, B and C arrive as the mixer's ONE convolution leaves them, side by
side in one array `xbc` (N, T, H P + 2 S) = [x | B | C] (71 MB a layer
in bfloat16 at 8192 x 4352): the scan's operand is the convolution's
result, whole, and its gradient is one array of that shape
(`scan_joint`; `ssd_scan` is the same call for operands that lie
apart, which it lays side by side first).  `dt` is the step AFTER its
bias and the softplus, float32 (N, T, H): a (position, head) scalar,
2 MB a layer at 8192 x 64, so the op (`ops/decoder.py ssd_scan`) makes
it in XLA where the softplus's gradient is autodiff's; `a` =
-exp(A_log) (H,).  The benchmark's
reference and the tests write the recurrence as a `lax.scan` over
positions.  What runs here is the paper's chunked form, chunks of L
positions, with a_t = dt_t A (<= 0) and gamma_i = sum_{j<=i} a_j inside
a chunk, so that no exponent is ever positive:

    G = C B^T                                  (L, L), ONCE a chunk
    Y_diag[h] = (G * exp(gamma_i - gamma_j)[i >= j]) (dt X[h])
                (the kernels: [i > j] by a product, i = j apart)
    Y_off[h]  = exp(gamma_i) * (C S_in[h]^T)
    S_out[h]  = exp(gamma_L) S_in[h] + (dt X[h] exp(gamma_L - gamma))^T B
    y = Y_diag + Y_off + D x

Two lowerings, chosen by the shape alone (`ssd_scan_takes`: heads of 64
lanes in whole blocks of `HEAD_BLOCK`, 128 states, ONE group, chunks of
`CHUNK` = 256 and T whole chunks):

**The kernels.**  Grid (batch, chunk, head block), the last two
sequential.  xBC is handed to the call THREE times, under three block
specs: a head block's 512 lanes of x (lane block h of 512), B's 128
lanes (lane block H P / 128 of 128) and C's (the next): no x, B or C
is cut out of xBC in HBM (the cut of x alone was 67 MB written and read
a layer, forward and recomputed, and the three gradients' glue as much
again backward).
Two heads of 64 lie side by side in a 128-lane tile, as
`flash_gqa.py` lays its pairs; a pair's states are ONE (N = 128, 128)
float32 tile (the state transposed: states down the sublanes, the
pair's 2 x 64 lanes across), and the states of ALL pairs of a sequence
live in a VMEM scratch (H / 2 x 64 kB = 2 MB) from chunk to chunk, so
the head blocks can be the INNER grid axis and what no head owns (G,
and backward G^T and the sum over heads of dG) is made once a chunk.
The per-(position, head) scalars arrive as XLA made them, float32,
twice: as columns (a chunk's positions down the sublanes: dt, gamma,
exp(gamma), exp(gamma_L - gamma)) and gamma again as rows (positions
across the lanes), so the (L, L) decay mask exp(gamma_i - gamma_j) of
a head is ONE broadcast subtraction, a select and an exponential in
VMEM and is never a tensor in HBM (537 MB a layer in float32 at 8192 x
64 otherwise).  B arrives as it lies in xBC and transposed (2 MB, cut
from xBC and turned by XLA), C too backward, so every product is a
plain or an NT matmul.  Matmul
operands are in the operands' dtype (bfloat16 under AMP, float32 at
"highest" otherwise), every accumulator, decay and the carried state
float32.  `ssd_scan_fwd` writes y and the state that ENTERS each chunk
(float32: T / L x H x 64 x 128 x 4 B = 67 MB a layer at 8192).
`ssd_scan_bwd` walks the chunks in reverse with dL/dS carried in VMEM,
rebuilds a chunk's masks (both orientations) and its local y, and
writes d xBC as ONE array in xBC's dtype: its output block is a
chunk's WHOLE 4352 lanes under an index that does not move with the
head block, so it stays in VMEM across a chunk's head blocks (2.2 MB
in bfloat16) and goes back to HBM once; a head block stores dx at its
own lanes (a dynamic, 128-aligned lane offset), dB and dC (SUMS over
the heads) are accumulated in float32 scratch across the head blocks
and rounded ONCE into the last 256 lanes at the last block, dG's part
by one product there.  Beside it the gradient of dt's direct uses and
of gamma (a (position, head) each; XLA turns them into d dt and dA by
a reversed cumulative sum a chunk) and dD's (1, lanes) partial sums a
chunk (XLA sums them).

    dgamma_j = dy_j . (Y_diag + Y_off)_j - dt_j x_j . dXdt_j
               [+ <dS_out, S_out> at a chunk's last position]
               (summed back over the chunk all but the pairs that
               straddle a position cancel: the two sides are made of
               operands rounded ALIKE, so that under bfloat16 operands
               what is left is float32's rounding and not bfloat16's)
    ddt_j    = x_j . dXdt_j + A (sum_{s >= j} dgamma_s)
    dXdt     = (G^T * mask^T) dY + exp(gamma_L - gamma) (B dS_out^T)

**The XLA lowering** (`scan_xla`): the same chunks as einsums under
`jax.checkpoint`, a `lax.scan` over the chunks for the carry, any chunk
length and any number of groups, T padded to whole chunks with
positions that neither write nor decay.  The fall-back for shapes the
kernels do not tile and the path the CPU presets run.

Tied by ONE `custom_vjp`, `scan_kernel`, under `jax.jit`.  Inside a
recompute segment the forward rule's two results AND its operand xBC
are named (`ops/pallas keep_residuals`, `SSD_RESIDUALS`): the segment
keeps y, the entry states and xBC (67 + 67 + 71 MB a layer), so its
backward pass runs `ssd_scan_bwd` on them, no forward scan a second
time and no convolution forward either: with the scan's operand the
convolution's own output, re-making that one residual was all the
second `short_conv_fwd` was for (the convolution's backward reads its
input and filter).  `runtime_stats.ssd_scans_kernel` / `_xla` count
the scans traced each way, `ssd_scan_chunks` the chunks x batch the
kernels walk.  The benchmark finds the kernels by the PREFIX `ssd_scan`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 256             # positions a grid step: the kernels' chunk
HEAD_DIM = 64           # lanes a head: two heads a 128-lane tile
STATE = 128             # states: a pair's state is a (128, 128) tile
HEAD_BLOCK = 8          # heads a grid step
LANES = 128
NEG = -1e30             # a masked exponent: exp gives 0
VMEM_LIMIT = 100 << 20
_HI = lax.Precision.HIGHEST
# the columns a (position, head) carries into the kernels, side by side
COL_DT, COL_GAMMA, COL_EXP, COL_REST = range(4)


def ssd_scan_takes(t, heads, head_dim, d_state, groups=1, chunk=CHUNK):
    """Whether the kernels run a call: from the shape alone."""
    return (head_dim == HEAD_DIM and d_state == STATE and groups == 1
            and chunk == CHUNK and t % CHUNK == 0
            and heads % HEAD_BLOCK == 0)


# -- kernel cost registry (observe/cost.py) ----------------------------
#
# The FLOP the kernels EXECUTE on the MXU, from the operands' shapes
# (xBC (N, T, H P + 2 S) first: x's width is what B and C leave): a
# chunk's G once, and a
# head's mask product, read-out and state update forward (2 L P (L + 2
# S) a head a chunk); backward the forward's two again, the three mask
# products (dG's, dXdt's and the rebuilt Y_diag) and the five state-side
# products, G and dG's two a chunk (G^T is a transpose).  The decay masks' vector
# work (an exponential, a select and two multiplies an entry) is not
# counted: `peaks.json` has no row for it.  Bytes: operands and results
# once (the default model, which counts xBC ONCE though three block
# specs read it: one buffer is one operand there).

def _chunk_products(operand_shapes):
    (n, t, width), _ = operand_shapes[0]
    heads = (width - 2 * STATE) // HEAD_DIM
    chunks = n * (t // CHUNK)
    shared = 2.0 * CHUNK * CHUNK * STATE
    mask = 2.0 * CHUNK * CHUNK * HEAD_DIM
    state = 2.0 * CHUNK * STATE * HEAD_DIM
    return chunks, heads, shared, mask, state


def fwd_cost(operand_shapes, result_shapes):
    chunks, heads, shared, mask, state = _chunk_products(operand_shapes)
    return chunks * (shared + heads * (mask + 2 * state)), None


def bwd_cost(operand_shapes, result_shapes):
    chunks, heads, shared, mask, state = _chunk_products(operand_shapes)
    return chunks * (3 * shared + heads * (3 * mask + 5 * state)), None


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("ssd_scan_fwd", fwd_cost)
    register_kernel_cost("ssd_scan_bwd", bwd_cost)


_register_costs()


# -- the XLA lowering --------------------------------------------------

def _chunk_xla(s, xs, p):
    """One chunk of every head: s (N, G, Hg, P, S) float32 in, (the
    state that leaves, the chunk's Y_diag + Y_off (N, L, G, Hg, P))."""
    xdt, gamma, b, c = xs       # (N,L,G,Hg,P) (N,L,G,Hg) (N,L,G,S) x 2
    length = gamma.shape[1]
    g = jnp.einsum("nigs,njgs->ngij", c, b, precision=p)
    diff = gamma[:, :, None] - gamma[:, None]          # (N, i, j, G, Hg)
    # a position's own term apart (`_decay_masks`): inside the mask its
    # gradient would add and take away the row's largest entry
    keep = (jnp.arange(length)[:, None]
            > jnp.arange(length)[None, :])[None, :, :, None, None]
    mask = jnp.exp(jnp.where(keep, diff, NEG))
    y = jnp.einsum("ngij,nijgh,njghp->nighp", g, mask, xdt, precision=p)
    y = y + jnp.sum(c * b, axis=-1)[..., None, None] * xdt
    y = y + jnp.exp(gamma)[..., None] * jnp.einsum(
        "nigs,nghps->nighp", c, s, precision=p)
    last = gamma[:, -1]                                 # (N, G, Hg)
    rest = jnp.exp(last[:, None] - gamma)
    s = jnp.exp(last)[..., None, None] * s + jnp.einsum(
        "njghp,njgs->nghps", xdt * rest[..., None], b, precision=p)
    return s, y


def scan_xla(x, dt, a, b, c, d, chunk=CHUNK, groups=1):
    """y (N, T, H P) of the recurrence at the top of this file.  x
    (N, T, H P); dt (N, T, H) the step itself; a (H,) the decay rates
    (negative); b, c (N, T, G S); d (H,).  Float32 inside."""
    f32 = jnp.float32
    n, t, width = x.shape
    heads = dt.shape[2]
    p, states = width // heads, b.shape[2] // groups
    tail = -t % chunk
    nc = (t + tail) // chunk
    xf = x.astype(f32).reshape(n, t, groups, heads // groups, p)
    dtf = dt.astype(f32).reshape(n, t, groups, heads // groups)
    operands = [xf * dtf[..., None], dtf * a.astype(f32).reshape(
        groups, heads // groups), b.astype(f32).reshape(n, t, groups, states),
        c.astype(f32).reshape(n, t, groups, states)]
    if tail:        # positions that write nothing and do not decay
        operands = [jnp.pad(v, ((0, 0), (0, tail)) + ((0, 0),) * (v.ndim - 2))
                    for v in operands]

    def by_chunk(v):                   # (N, T, ...) -> (nc, N, L, ...)
        return jnp.moveaxis(v.reshape((n, nc, chunk) + v.shape[2:]), 1, 0)

    xdt, step, b4, c4 = (by_chunk(v) for v in operands)
    gamma = jnp.cumsum(step, axis=2)
    body = jax.checkpoint(functools.partial(
        _chunk_xla, p=_HI if x.dtype == f32 else None))
    _, y = lax.scan(
        body, jnp.zeros((n, groups, heads // groups, p, states), f32),
        (xdt, gamma, b4, c4))
    y = jnp.moveaxis(y, 0, 1).reshape(n, t + tail, width)[:, :t]
    skip = jnp.repeat(d.astype(f32), p)
    return (y + skip * x.astype(f32)).astype(x.dtype)


# -- the kernels -------------------------------------------------------

def _dot(a, b, contract=((1,), (0,))):
    """Float32 out of operands in ONE dtype: at "highest" where they
    are float32 (Mosaic refuses it of bfloat16 operands)."""
    return lax.dot_general(
        a, b, ((contract[0], contract[1]), ((), ())),
        precision=_HI if a.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # contract both operands' lanes
_TN = ((0,), (0,))      # contract both operands' rows


def _left(rows):
    """bool (rows, 128): the lanes of a pair's first head."""
    return lax.broadcasted_iota(jnp.int32, (rows, LANES), 1) < HEAD_DIM


def _per_head(first, second, rows):
    """(rows, 128): the first head's lanes from `first`, the second's
    from `second`; each (rows, 128), (rows, 1) or (1, 128)."""
    return jnp.where(_left(rows), first, second)


def _column(cols, kind, h):
    """(L, 1) float32: column `kind` of head `h` of the block."""
    at = kind * HEAD_BLOCK + h
    return cols[:, at:at + 1]


def _pair_columns(cols, kind, k):
    """(L, 128): column `kind` of pair k's heads over their lanes."""
    return _per_head(_column(cols, kind, 2 * k),
                     _column(cols, kind, 2 * k + 1), cols.shape[0])


def _below(length, transposed=False):
    """bool (L, L): row > column (or column > row): the pairs (i, j), i
    the later position, STRICTLY off the diagonal."""
    i = lax.broadcasted_iota(jnp.int32, (length, length), 0)
    j = lax.broadcasted_iota(jnp.int32, (length, length), 1)
    return j > i if transposed else i > j


def _decay_masks(cols, rows, h, below, transposed=False):
    """exp(gamma_i - gamma_j)[i > j] of head h, (i, j) or (j, i), over
    `_below`'s triangle.  A position's own term (its mask is 1) is added
    apart, in float32 (`_own`): it is the largest of its row, carries no
    decay, and backward it would sit on both sides of the sum that
    gamma's gradient cancels."""
    gc, gr = _column(cols, COL_GAMMA, h), rows[h:h + 1, :]
    return jnp.exp(jnp.where(below, gr - gc if transposed else gc - gr, NEG))


def _own(b_ref, c_ref):
    """(L, 1) float32: G's diagonal, C_i . B_i."""
    return jnp.sum(c_ref[0].astype(jnp.float32)
                   * b_ref[0].astype(jnp.float32), axis=1, keepdims=True)


def _fwd_kernel(x_ref, cols_ref, rows_ref, last_ref, b_ref, bt_ref, c_ref,
                d_ref, y_ref, entry_ref, state, g_scr):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    j, hb = pl.program_id(1), pl.program_id(2)
    kind = x_ref.dtype
    pairs = HEAD_BLOCK // 2

    @pl.when(j == 0)
    def _a_sequence_starts_from_zero():
        for k in range(pairs):
            state[hb * pairs + k] = jnp.zeros((STATE, LANES), f32)

    @pl.when(hb == 0)
    def _what_every_head_of_a_chunk_shares():
        g_scr[...] = _dot(c_ref[0], bt_ref[0])

    cols, rows = cols_ref[0, 0], rows_ref[0, 0]
    g, c, bt = g_scr[...], c_ref[0], bt_ref[0]
    own = _own(b_ref, c_ref)
    length = cols.shape[0]
    below = _below(length)
    for k in range(pairs):
        lanes = slice(k * LANES, (k + 1) * LANES)
        at = hb * pairs + k
        s = state[at]
        entry_ref[0, 0, k] = s
        x = x_ref[0, :, lanes]
        xdt = x.astype(f32) * _pair_columns(cols, COL_DT, k)
        diag = [_dot((g * _decay_masks(cols, rows, 2 * k + i, below)).astype(
            kind), xdt.astype(kind)) for i in (0, 1)]
        y = (_per_head(*diag, length) + own * xdt
             + _pair_columns(cols, COL_EXP, k) * _dot(c, s.astype(kind))
             + d_ref[:, lanes] * x.astype(f32))
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        rest = xdt * _pair_columns(cols, COL_REST, k)
        state[at] = last_ref[0, 0, 0, k:k + 1, :] * s + _dot(
            bt, rest.astype(kind))


def _bwd_kernel(x_ref, cols_ref, rows_ref, last_ref, b_ref, bt_ref, c_ref,
                ct_ref, d_ref, entry_ref, next_ref, dy_ref, dxbc_ref, ddt_ref,
                dgamma_ref, dlast_ref, dd_ref, dstate, g_scr, gt_scr, dg_scr,
                db_scr, dc_scr):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    j, hb = pl.program_id(1), pl.program_id(2)
    kind = x_ref.dtype
    pairs = HEAD_BLOCK // 2
    wide = HEAD_BLOCK * HEAD_DIM
    width = dxbc_ref.shape[2] - 2 * STATE       # x's lanes of a chunk's dxBC

    @pl.when(j == 0)
    def _nothing_follows_the_last_chunk():
        for k in range(pairs):
            dstate[hb * pairs + k] = jnp.zeros((STATE, LANES), f32)

    @pl.when(hb == 0)
    def _what_every_head_of_a_chunk_shares():
        g_scr[...] = _dot(c_ref[0], bt_ref[0])
        gt_scr[...] = g_scr[...].T     # G^T to the bit: B C^T by a
        # product of its own may round an entry the other way
        dg_scr[...] = jnp.zeros(dg_scr.shape, f32)
        db_scr[...] = jnp.zeros(db_scr.shape, f32)
        dc_scr[...] = jnp.zeros(dc_scr.shape, f32)

    cols, rows = cols_ref[0, 0], rows_ref[0, 0]
    g, gt = g_scr[...], gt_scr[...]
    b, c, ct = b_ref[0], c_ref[0], ct_ref[0]
    own = _own(b_ref, c_ref)
    length = cols.shape[0]
    left = _left(length)
    below, above = _below(length), _below(length, True)
    head_at = lax.broadcasted_iota(jnp.int32, (length, HEAD_BLOCK), 1)
    ddt = jnp.zeros((length, HEAD_BLOCK), f32)
    dgamma = jnp.zeros((length, HEAD_BLOCK), f32)
    down = jnp.zeros((length, 1), f32)      # dL/dG's diagonal, this block
    for k in range(pairs):
        lanes = slice(k * LANES, (k + 1) * LANES)
        at = hb * pairs + k
        s_in, s_out = entry_ref[0, 0, k], next_ref[0, 0, k]
        ds = dstate[at]                     # dL/dS of the state that leaves
        x, dy = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        xf, dyf = x.astype(f32), dy.astype(f32)
        dt2 = _pair_columns(cols, COL_DT, k)
        grow = _pair_columns(cols, COL_EXP, k)
        rest = _pair_columns(cols, COL_REST, k)
        # the operands as the products read them: what the two sums
        # below cancel against each other must be rounded alike
        xdt_f = xf * dt2
        xdt = xdt_f.astype(kind)
        write = (xdt_f * rest).astype(kind)         # as the forward's
        ds_k = ds.astype(kind)
        diag, back = [], []
        for i in (0, 1):
            h = 2 * k + i
            mask = _decay_masks(cols, rows, h, below)
            diag.append(_dot((g * mask).astype(kind), xdt))
            mine = left if i == 0 else jnp.logical_not(left)
            dg_scr[...] += mask * _dot(
                jnp.where(mine, dy, jnp.zeros_like(dy)), xdt, _NT)
            back.append(_dot((gt * _decay_masks(
                cols, rows, h, above, True)).astype(kind), dy))
        local = _per_head(*diag, length) + grow * _dot(c, s_in.astype(kind))
        back = _per_head(*back, length)
        reads = _dot(b, ds_k)               # B dS^T, before its decay
        dxdt = back + own * dyf + rest * reads
        dxbc_ref[0, :, pl.ds(pl.multiple_of(hb * wide + k * LANES, LANES),
                             LANES)] = (
            dt2 * dxdt + d_ref[:, lanes] * dyf).astype(dxbc_ref.dtype)
        # a (position, head)'s two scalars: row sums over a head's lanes.
        # gamma's: each later output's pull on it less its own pull on
        # the earlier ones, a sum that cancels all but the pairs that
        # straddle a position once XLA has summed it back over the chunk
        pull = (dyf * local - xdt.astype(f32) * back
                - write.astype(f32) * reads)
        for i, (zg, zu) in enumerate(_halves(pull, xf * dxdt, left)):
            h = 2 * k + i
            ddt = jnp.where(head_at == h, zu, ddt)
            dgamma = jnp.where(head_at == h, zg, dgamma)
        dlast_ref[0, 0, 0, k:k + 1, :] = jnp.sum(
            ds_k.astype(f32) * s_out, axis=0, keepdims=True)
        dd_ref[0, 0, :, lanes] = jnp.sum(dyf * xf, axis=0, keepdims=True)
        down += jnp.sum(dyf * xdt_f, axis=1, keepdims=True)
        dyg = (dyf * grow).astype(kind)
        dc_scr[...] += _dot(dyg, s_in.astype(kind), _NT)
        db_scr[...] += _dot(write, ds_k, _NT)
        dstate[at] = last_ref[0, 0, 0, k:k + 1, :] * ds + _dot(ct, dyg)
    ddt_ref[0, 0] = ddt
    dgamma_ref[0, 0] = dgamma
    dc_scr[...] += down * b.astype(f32)
    db_scr[...] += down * c.astype(f32)

    @pl.when(hb == pl.num_programs(2) - 1)
    def _the_sum_over_heads_rounded_once_into_dxbc():
        dg = dg_scr[...].astype(kind)
        dxbc_ref[0, :, width:width + STATE] = (
            db_scr[...] + _dot(dg, c, _TN)).astype(dxbc_ref.dtype)
        dxbc_ref[0, :, width + STATE:] = (
            dc_scr[...] + _dot(dg, b)).astype(dxbc_ref.dtype)


def _halves(zg, zu, left):
    """The sums over each head's lanes of two (L, 128) products of a
    pair: [(g0, u0), (g1, u1)], each (L, 1)."""
    out = []
    for z in (zg, zu):
        first = jnp.sum(jnp.where(left, z, 0.0), axis=1, keepdims=True)
        out.append((first, jnp.sum(z, axis=1, keepdims=True) - first))
    return list(zip(*out))


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _scalars(dt, a):
    """What a (position, head) carries into the kernels, float32, made
    by XLA: (columns (N, H / HB, T, 4 HB): dt, gamma, exp(gamma),
    exp(gamma_L - gamma) of a block's heads side by side; gamma as rows
    (N, H / HB, HB, T); exp(gamma_L) a (chunk, pair) over the pair's
    lanes (N, T / L, H / HB, HB / 2, 128))."""
    n, t, heads = dt.shape
    nc, nb = t // CHUNK, heads // HEAD_BLOCK
    gamma = jnp.cumsum((dt * a).reshape(n, nc, CHUNK, heads), axis=2)
    last = gamma[:, :, -1:]
    cols = jnp.stack([dt, gamma.reshape(n, t, heads),
                      jnp.exp(gamma).reshape(n, t, heads),
                      jnp.exp(last - gamma).reshape(n, t, heads)], axis=2)
    cols = jnp.moveaxis(cols.reshape(n, t, 4, nb, HEAD_BLOCK), 3, 1)
    rows = jnp.moveaxis(gamma.reshape(n, t, nb, HEAD_BLOCK), 1, 3)
    leaves = jnp.repeat(jnp.exp(last[:, :, 0]), HEAD_DIM, axis=-1)
    return (cols.reshape(n, nb, t, 4 * HEAD_BLOCK), rows,
            leaves.reshape(n, nc, nb, HEAD_BLOCK // 2, LANES))


def _specs(time, heads):
    """Block specs of a grid (batch, chunk, head block): a head block's
    lanes of x (of xBC: block h of its 512-lane blocks; of y and dy as
    they lie), the columns, the rows, exp(gamma_L), B's lanes of xBC
    (the 128-lane block after x's) and C's (the next), B or C
    transposed, D's lanes, a chunk's entry states."""
    from jax.experimental import pallas as pl

    wide = HEAD_BLOCK * HEAD_DIM
    pairs = HEAD_BLOCK // 2
    after_x = heads * HEAD_DIM // STATE

    def slab(at):           # 128 lanes of xBC, the same for every head
        return pl.BlockSpec((1, CHUNK, STATE),
                            lambda b, j, h: (b, time(j), at))

    return (
        pl.BlockSpec((1, CHUNK, wide), lambda b, j, h: (b, time(j), h)),
        pl.BlockSpec((1, 1, CHUNK, 4 * HEAD_BLOCK),
                     lambda b, j, h: (b, h, time(j), 0)),
        pl.BlockSpec((1, 1, HEAD_BLOCK, CHUNK),
                     lambda b, j, h: (b, h, 0, time(j))),
        pl.BlockSpec((1, 1, 1, pairs, LANES),
                     lambda b, j, h: (b, time(j), h, 0, 0)),
        slab(after_x), slab(after_x + 1),
        pl.BlockSpec((1, STATE, CHUNK), lambda b, j, h: (b, 0, time(j))),
        pl.BlockSpec((1, wide), lambda b, j, h: (0, h)),
        pl.BlockSpec((1, 1, pairs, STATE, LANES),
                     lambda b, j, h: (b, time(j), h, 0, 0)))


def _transposed(xbc, at):
    """B's (or C's) 128 lanes from lane `at` of xBC, states down the
    sublanes: (N, S, T), 2 MB a layer, cut and turned by XLA."""
    return jnp.swapaxes(xbc[:, :, at:at + STATE], 1, 2)


@functools.partial(jax.jit, static_argnames=("interpreted",))
def _fwd_call(xbc, dt, a, d, interpreted=False):
    from jax.experimental.pallas import tpu as pltpu

    from . import pallas_call

    f32 = jnp.float32
    n, t, _ = xbc.shape
    heads = dt.shape[2]
    width = heads * HEAD_DIM
    nc, nb = t // CHUNK, heads // HEAD_BLOCK
    wide, col, row, last, b_lanes, c_lanes, shared_t, lane, entry = _specs(
        lambda j: j, heads)
    cols, rows, leaves = _scalars(dt.astype(f32), a.astype(f32))
    # ONE array under three block specs: x's, B's and C's lanes of xBC
    return pallas_call(
        _fwd_kernel, name="ssd_scan_fwd", grid=(n, nc, nb),
        in_specs=[wide, col, row, last, b_lanes, shared_t, c_lanes, lane],
        out_specs=[wide, entry],
        out_shape=[jax.ShapeDtypeStruct((n, t, width), xbc.dtype),
                   jax.ShapeDtypeStruct((n, nc, heads // 2, STATE, LANES),
                                        f32)],
        scratch_shapes=[pltpu.VMEM((heads // 2, STATE, LANES), f32),
                        pltpu.VMEM((CHUNK, CHUNK), f32)],
        compiler_params=_params(),
    )(xbc, cols, rows, leaves, xbc, _transposed(xbc, width), xbc,
      jnp.repeat(d.astype(f32), HEAD_DIM).reshape(1, width))


@functools.partial(jax.jit, static_argnames=("interpreted",))
def _bwd_call(xbc, dt, a, d, entry, dy, interpreted=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from . import pallas_call

    f32 = jnp.float32
    n, t, joint = xbc.shape
    heads = dt.shape[2]
    width = heads * HEAD_DIM
    nc, nb = t // CHUNK, heads // HEAD_BLOCK
    pairs = HEAD_BLOCK // 2

    def time(j):
        return nc - 1 - j

    (wide, col, row, last, b_lanes, c_lanes, shared_t, lane,
     entry_spec) = _specs(time, heads)
    # a chunk's WHOLE dxBC: the same block under every head block of a
    # chunk, so it stays in VMEM while they fill x's lanes and goes back
    # once, with dB and dC rounded into their lanes at the last
    whole = pl.BlockSpec((1, CHUNK, joint), lambda b, j, h: (b, time(j), 0))
    # the state that LEAVES a chunk enters the next (after the last
    # chunk nothing reads it: its cotangent is 0)
    leaves_spec = pl.BlockSpec(
        (1, 1, pairs, STATE, LANES),
        lambda b, j, h: (b, jnp.minimum(time(j) + 1, nc - 1), h, 0, 0))
    scalar = pl.BlockSpec((1, 1, CHUNK, HEAD_BLOCK),
                          lambda b, j, h: (b, h, time(j), 0))
    partial = pl.BlockSpec((1, 1, 1, HEAD_BLOCK * HEAD_DIM),
                           lambda b, j, h: (b, time(j), 0, h))
    dtf, af = dt.astype(f32), a.astype(f32)
    cols, rows, leaves = _scalars(dtf, af)
    tile = pltpu.VMEM((CHUNK, CHUNK), f32)
    summed = pltpu.VMEM((CHUNK, STATE), f32)    # dB, dC over a chunk's heads
    dxbc, ddt, dgamma, dlast, dd = pallas_call(
        _bwd_kernel, name="ssd_scan_bwd", grid=(n, nc, nb),
        in_specs=[wide, col, row, last, b_lanes, shared_t, c_lanes, shared_t,
                  lane, entry_spec, leaves_spec, wide],
        out_specs=[whole, scalar, scalar, last, partial],
        out_shape=[jax.ShapeDtypeStruct(xbc.shape, xbc.dtype),
                   jax.ShapeDtypeStruct((n, nb, t, HEAD_BLOCK), f32),
                   jax.ShapeDtypeStruct((n, nb, t, HEAD_BLOCK), f32),
                   jax.ShapeDtypeStruct((n, nc, nb, pairs, LANES), f32),
                   jax.ShapeDtypeStruct((n, nc, 1, width), f32)],
        scratch_shapes=[pltpu.VMEM((heads // 2, STATE, LANES), f32),
                        tile, tile, tile, summed, summed],
        compiler_params=_params(),
    )(xbc, cols, rows, leaves, xbc, _transposed(xbc, width), xbc,
      _transposed(xbc, width + STATE),
      jnp.repeat(d.astype(f32), HEAD_DIM).reshape(1, width), entry, entry,
      dy)

    def by_head(v):         # (N, H / HB, T, HB) -> (N, T / L, L, H)
        return jnp.moveaxis(v, 1, 2).reshape(n, nc, CHUNK, heads)

    # gamma_L's own cotangent lands on the chunk's last position; a
    # position's step reaches every later gamma of its chunk
    dgamma = by_head(dgamma).at[:, :, -1].add(
        jnp.sum(dlast.reshape(n, nc, heads, HEAD_DIM), axis=-1))
    da = jnp.flip(jnp.cumsum(jnp.flip(dgamma, 2), axis=2), 2).reshape(
        n, t, heads)
    ddt = by_head(ddt).reshape(n, t, heads) + da * af
    return (dxbc, ddt.astype(dt.dtype),
            jnp.sum(da * dtf, axis=(0, 1)).astype(a.dtype),
            jnp.sum(dd.reshape(-1, heads, HEAD_DIM),
                    axis=(0, 2)).astype(d.dtype))


@jax.custom_vjp
def scan_kernel(xbc, dt, a, d):
    """`scan_xla` of xBC's three slabs by the Pallas kernels
    (`ssd_scan_takes`): xbc (N, T, H P + 2 S) = [x | B | C] as the
    convolution leaves it; its gradient is ONE array of that shape."""
    return _vjp_fwd(xbc, dt, a, d)[0]


def _record(x):
    from ...observe.monitoring import runtime_stats
    from . import interpret

    runtime_stats.record_ssd_scan(True, x.shape[0] * (x.shape[1] // CHUNK))
    return interpret()


def _vjp_fwd(xbc, dt, a, d):
    from . import SSD_RESIDUALS, keep_residuals

    y, entry, xbc = keep_residuals(
        *_fwd_call(xbc, dt, a, d, interpreted=_record(xbc)), xbc,
        names=SSD_RESIDUALS)
    return y, (xbc, dt, a, d, entry)


def _vjp_bwd(res, dy):
    xbc, *_ = res
    return _bwd_call(*res, dy.astype(xbc.dtype), interpreted=_record(xbc))


scan_kernel.defvjp(_vjp_fwd, _vjp_bwd)


def scan_joint(xbc, dt, a, d, d_state=STATE, chunk=CHUNK, groups=1):
    """y (N, T, H P) of the recurrence at the top of this file on the
    operand as the mixer's convolution leaves it: xbc (N, T, H P + 2 G
    S) = [x | B | C]; dt (N, T, H) the step, after its bias and the
    softplus; a (H,) the decay rates (negative: -exp(A_log)); d (H,).
    The kernels where `ssd_scan_takes` the shape: they block x, B and C
    out of xbc's lanes and write its gradient as one array.  Else
    `scan_xla` on the three slices."""
    n, t, joint = xbc.shape
    heads = a.shape[0]
    width = joint - 2 * groups * d_state
    if dt.shape != (n, t, heads) or width <= 0 or width % heads \
            or heads % groups or d.shape != (heads,):
        raise ValueError(
            f"ssd_scan: xbc {xbc.shape}, dt {dt.shape}, a {a.shape}, d "
            f"{d.shape} are not H heads over T positions beside {groups} "
            f"groups of {d_state} states of B and C")
    if ssd_scan_takes(t, heads, width // heads, d_state, groups, chunk):
        return scan_kernel(xbc, dt, a, d)
    from ...observe.monitoring import runtime_stats

    runtime_stats.record_ssd_scan(False, 0)
    after_b = width + groups * d_state
    return scan_xla(xbc[..., :width], dt, a, xbc[..., width:after_b],
                    xbc[..., after_b:], d, chunk, groups)


def ssd_scan(x, dt, a, b, c, d, chunk=CHUNK, groups=1):
    """`scan_joint` of operands that lie apart: x (N, T, H P); b, c
    (N, T, G S), taken into x's dtype.  They are laid side by side once
    (autodiff cuts the joint gradient); the step's own path
    (`ops/decoder.py ssd_scan`) hands over xBC as it lies."""
    if b.shape[:2] != x.shape[:2] or b.shape[2] % groups \
            or c.shape != b.shape:
        raise ValueError(
            f"ssd_scan: x {x.shape}, b {b.shape}, c {c.shape} are not H "
            f"heads over T positions with {groups} groups of B and C")
    return scan_joint(
        jnp.concatenate([x, b.astype(x.dtype), c.astype(x.dtype)], axis=2),
        dt, a, d, b.shape[2] // groups, chunk, groups)
