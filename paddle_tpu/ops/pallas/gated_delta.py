"""Chunked gated delta rule: the scan of a linear-attention layer
(Yang, Kautz, Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464),
forward and backward (Pallas, TPU), and the XLA lowering of the same
chunks.

The recurrence, a value head at a time, with a state S (Dk, Dv) in
float32 that starts at 0:

    S'_t = exp(g_t) S_{t-1}                 (g_t <= 0: the decay)
    u_t  = beta_t (v_t - S'_t^T k_t)        (the delta rule's write)
    S_t  = S'_t + k_t u_t^T
    o_t  = S_t^T q_t

The benchmark's reference and the tests write that as a `lax.scan` over
positions.  What runs is its chunked form (chunks of C = 64 positions;
the identity is the paper's section 3 and Yang et al., arXiv:2406.06484).
Within a chunk gamma_i = sum_{j<=i} g_j, G_ij = exp(gamma_i - gamma_j)
for i >= j (no exponent is ever positive):

    A = strict_lower(diag(beta) (K K^T * G))
    T = (I + A)^-1 diag(beta);  W = T (K * exp(gamma));  U = T V
    V' = U - W S                                  (S enters the chunk)
    O  = (Q * exp(gamma)) S + lower_incl(Q K^T * G) V'
    S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

Two parts, five kernels and one reference lowering of each part.

**The chunk-local part**: everything that does not read S is a batch
over all the chunks: the cumulative decay, the two (C, C) products a
key head, the inverse, W, U, the two scaled copies of Q and K and
P = lower_incl(Q K^T * G).  `chunk_operands` is its XLA lowering, which
XLA differentiates: the reference the kernels are tested against, and
what runs where `operand_kernels_take` is false.  There every (C, C)
float32 matrix of a chunk is a tensor in HBM that the TPU's tiling pads
to 128 lanes, (I + A)^-1 is XLA's batched triangular solve
(`unit_lower_inverse`, with a VJP of its own, dA = -M^T dM M^T), and
`chunk_decay` has a VJP of its own too.  The kernels (since PR 45) are
the same arithmetic with a chunk's matrices in VMEM: grid (batch x KEY
head, blocks of chunks); a step holds a key head's q and k rows and the
TWO value heads that read it, their two (C, C) matrices side by side in
the 128 lanes of one float32 tile; q, k, v are read from the op's
(N, T, H x 128) layout by lane block, K K^T and Q K^T are one MXU
product a key head (the second head's copy is the MXU's, a product
against [k; k]).  The (C,)-vectors of a chunk (gamma, what is left of
the chunk after a position, beta, g) come as one (8, 128) float32 tile
a chunk and key head, which XLA makes (`_row_tiles`: a cumulative sum
and a suffix sum over 2 MB, whose gradients XLA has), and exp(gamma_C)
stays XLA's too.  Three kernels (two before PR 52, whose forward kernel
solved and multiplied in one):

* `gated_delta_inverse` reads k and the row tile, builds G, K K^T and A
  and writes (I + A)^-1 (float32, two heads a tile: 134 MB a layer at
  16384 positions x 32 heads).  Float32 forward substitution in blocks
  (`_inverse_side_by_side`): the diagonal blocks of `DIAGONAL_BLOCK`
  rows row by row on the VPU, all of them and both heads at once, then
  the blocks doubled by two MXU products a doubling at "highest".  A
  chain of dependent steps a chunk: 7.3 ms a layer alone at the cell's
  shape, of which nothing but the chain is 6.2, and 6.6 in the cell's
  step (my chip runs, PR 52).
* `gated_delta_operands_fwd` reads q, k, v, the row tile AND the
  inverse, rebuilds G and Q K^T and writes what the scan kernels read:
  W, U, Qg, Kd, P in the operands' dtype.  No substitution: 3.4 ms
  alone, 2.0 in the step.
* `gated_delta_operands_bwd` reads the same and the five cotangents,
  rebuilds G, K K^T and Q K^T, and returns dq and dk summed over the
  two value heads in VMEM, dv, and the row tile's gradient.  It keeps
  the two rules the XLA lowering has: dA = -M^T dM M^T, and the decay
  matrix's gradient summed over the pairs that straddle a position (it
  lands on the tile's `g` row, which the forward does not read;
  differentiating gamma_i - gamma_j term by term leaves their float32
  cancellation in a decay parameter's gradient).

The three take q and k either as unit vectors (N, T, Hk x 128) or, with
`raw` (a `RawQK`: what the op hands them since PR 69), AS THE PROJECTION
WROTE THEM: the convolved QKV (N, T, W), q's and k's lanes picked by the
block's lane index as `head_norm_fwd` picked them.  A grid step then
takes the l2norm of its rows (x * rsqrt(sum x^2 + 1e-6), q's times
Dk^-1/2; `head_norm.py`'s float32 arithmetic) into VMEM scratch before
its chunk loop (`_head_rows`), and `_operands_bwd` returns the gradient
of the RAW lanes (`_raw_gradient`: dx = (g - y <g, y>) * rstd, one more
lane reduction a row).  No unit q or k is in HBM, and no `head_norm_*`
call stands before the kernels (two forward and two backward passes a
layer and call site before).  Since PR 72 `raw` also says where v lies
(`RawQK.v`), and QKV is then the call's ONE operand (`operands_kernel`):
a key head's two value heads are block `raw.v / 256 + head` of its
lanes, no slice of QKV is made, and `gated_delta_operands_bwd` writes
ONE gradient, dQKV as QKV lies: the result stays in HBM (`pl.ANY`),
a grid step writes its key head's dq and dk and its two value heads' dv
into VMEM buffers and sends the three itself, as async copies, to lanes
`raw.q + 128 h`, `raw.k + 128 h` and `raw.v + 256 h` of its rows (two
slots of each buffer: a step awaits the copies of two steps before, a
key head's last step all in flight).  Every lane is written once,
rounded to the operands' dtype once, where XLA padded three gradients
to W lanes, added them in float32 and rounded the sum (adding zeros is
exact: the values are the same to the bit).  (One result array cannot
take three block specs; the other way, a row block's every lane
resident in VMEM across the key heads as `ssd_scan_bwd` holds d xBC,
was built and timed too: 0.12-0.15 ms a call slower, `PERF.md` "From PR
72".)  Without `raw.v` (v an array of its
own: the tests') dq and dk come back a key head's block each and
`lane_range_gradient` pads them to QKV's width as a slice's is.  Unit
operands remain the tests' and the references' entry; a shape the
kernels do not take goes through `unit_q_and_k` (`head_norm.py`) first.

The inverse is made BEFORE the `custom_vjp` that holds the other two
(`chunk_inverses`, on k and the row tile as constants: the backward
kernel's dk and row-tile gradient hold what flows through it), and is
named as it is made (`ops/pallas keep_residuals`): a recompute segment
keeps it, so the segment's backward pass runs `gated_delta_operands_fwd`
on the kept inverse and solves nothing a second time (in the step a
layer's recomputed forward is 2.0 ms for the 7.3 the one fused kernel
took, its forward pass 8.6 for 7.3); outside a segment the name is
inert.

**The sequential part**: what reads S is sequential over the chunks:
grid (batch x value head, blocks of `DEFAULT_BLOCK_CHUNKS` chunks), the
state in a (Dk, Dv) float32 VMEM scratch across the grid as
`recurrence.py` carries (h, c), four MXU dots a chunk
(`gated_delta_fwd`).  Operands of the dots are the operands' dtype
(bfloat16 under AMP), accumulation, S and gamma float32.  The five
operands come a value head at a time, (N Hv, T, ..), as the chunk-local
kernels write them; o leaves AS THE OP LAYS IT, (N, T, Hv x 128): a grid
step writes its head's 128 lanes of a row block (`_specs`, the last
block; `channel_delta.py`'s scan does the same), so nothing transposes
a head-major o and nothing re-lays it for the gated norm behind (nine
copies and six re-lays of 268 MB a step at 16384 positions x 32 heads
and three layers before PR 72).  Backward: a custom VJP around the sequential part alone.  The
forward rule's kernel also writes the state that ENTERS each chunk (in
the operands' dtype, which is what the dots read: 268 MB a layer at
16384 positions x 32 heads in bfloat16); the backward kernel
(`gated_delta_bwd`) reads dO through the same lane block, walks the
blocks in reverse carrying dS in scratch, rebuilds V' = U - W S from
the saved state (one dot), and emits dW, dU, d(Q exp gamma),
d(K exp ..), dP and d exp(gamma_C).  `scan_xla` is its XLA lowering,
the same chunk steps as a `lax.scan` that XLA differentiates; its o is
head-major, and `gated_delta_rule` moves it.

Which runs is the shape's alone: the scan kernels take Dk = Dv = 128
(`kernel_takes`), the chunk-operand kernels besides that two value
heads a key head (`operand_kernels_take`); any other head size and the
CPU presets run the XLA lowerings.  `runtime_stats.gated_delta_calls`
/ `_chunks` count the scan kernels' calls traced and their chunks x
heads, `gated_delta_operand_calls` / `_operand_chunks` the calls of
`gated_delta_operands_fwd` / `_bwd`, `gated_delta_inverse_calls` those
of `gated_delta_inverse` (traced where the layer is, once: a segment's
forward + backward counts 1 and 3): a part that fell back reads 0.
`gated_delta_flat_calls` counts, of both kinds, the calls whose blocks
address the op's own arrays: every scan call (o and dO by lane block)
and a chunk-operand call that took QKV whole (`RawQK.v`); the cell reads
9 + 9.  The
benchmark
finds the scan kernels by the PREFIXES `gated_delta_fwd` /
`gated_delta_bwd`: no other kernel's name may start with either.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .head_norm import Form, _rstd, head_norm, lane_range_gradient

CHUNK = 64
HEAD_DIM = 128          # the kernels' Dk and Dv
# chunks a grid step: 512 rows of every operand a DMA, 8 chunk steps
# unrolled in the body (tuned nowhere yet: retune here)
DEFAULT_BLOCK_CHUNKS = 8
_HI = jax.lax.Precision.HIGHEST


def kernel_takes(dk, dv):
    """Whether the Pallas kernels run a call: from the shape alone."""
    return (dk, dv) == (HEAD_DIM, HEAD_DIM)


class RawQK(NamedTuple):
    """Where q and k lie when a call hands them AS THE PROJECTION WROTE
    THEM, before the l2norm: arrays (N, T, W) that hold `heads` heads of
    `dim` lanes from lane `q` and from lane `k` on (the convolved QKV,
    as it lies: no slice of it is made).  The rule then takes
    q = l2norm(q) * dim^-1/2 and k = l2norm(k) itself (eps 1e-6, a
    head, float32): the chunk-local kernels in VMEM, on the 128-lane
    block of a head they hold anyway (`_head_rows`; the backward kernel
    returns the raw q's and k's gradient), any other lowering through
    `head_norm.py` first (`unit_q_and_k`).

    `v` (a lane, or None: v is an array of its own, as `channel_delta.py`
    hands it): v lies in that array too, `value_dim` (None: `dim`) lanes
    a value head from lane `v` on.  The call then has ONE operand, QKV,
    and one gradient, dQKV, which `gated_delta_operands_bwd` writes
    whole (`operands_kernel`)."""
    q: int
    k: int
    heads: int
    dim: int
    v: int | None = None
    value_dim: int | None = None


def unit_q_and_k(q, k, raw):
    """(l2norm(q) * dim^-1/2, l2norm(k)), each (N, T, heads x dim), of
    the arrays `raw` describes: `head_norm.py`'s kernels where a head
    is 128 lanes, its (.., H, dim) view elsewhere."""
    width = raw.heads * raw.dim
    return (head_norm(q, group=raw.dim, lanes=(raw.q, width),
                      constant=raw.dim ** -0.5),
            head_norm(k, group=raw.dim, lanes=(raw.k, width)))


# -- kernel cost registry (observe/cost.py) ----------------------------
#
# What the sequential part computes once, per chunk and head (the
# chunk-local part's kernels say theirs at `inverse_cost` and
# `operands_*_cost`): forward
# W S, Q S, P V', K^T V' (three of 2 C Dk Dv and one of 2 C C Dv);
# backward the forward's V' again is NOT credited, its eight products
# are (dV' two, dP, dQ, dK, dW, dS two: six of 2 C Dk Dv, two of
# 2 C C Dv).

def _scan_dims(operand_shapes):
    (bh, t, dk), _ = operand_shapes[0]
    dv = operand_shapes[1][0][2]
    return bh, t, dk, dv


def scan_fwd_cost(operand_shapes, result_shapes):
    bh, t, dk, dv = _scan_dims(operand_shapes)
    return bh * t * (3 * 2.0 * dk * dv + 2.0 * CHUNK * dv), None


def scan_bwd_cost(operand_shapes, result_shapes):
    bh, t, dk, dv = _scan_dims(operand_shapes)
    return bh * t * (6 * 2.0 * dk * dv + 2 * 2.0 * CHUNK * dv), None


def _operand_dims(operand_shapes, tile):
    """(batch x key heads, positions, q's and k's lanes) by the row
    tiles (operand `tile`: (N Hk, T / C, 8, 2C)), which say the heads
    whether q and k come alone or inside the projection."""
    (bk, nc, _, _), _ = operand_shapes[tile]
    return bk, nc * CHUNK, bk // operand_shapes[0][0][0] * HEAD_DIM


def ranged_bytes(operand_shapes, result_shapes, lanes, first=2, pair=False):
    """None (the default model: every buffer once) unless one of the
    `first` operands (q and k) is wider than `lanes`: read inside the
    projection, it counts as its lane range.  `pair`: so does the
    operand after them (v, two value heads a key head: twice the
    lanes)."""
    import math

    ranges = [lanes] * first + [2 * lanes] * pair
    if all(dims[-1] <= r for (dims, _), r in zip(operand_shapes, ranges)):
        return None
    ranges += [None] * (len(operand_shapes) + len(result_shapes))
    return float(sum(
        size * math.prod(dims[:-1]) * min(dims[-1], r or dims[-1])
        for (dims, size), r in zip(list(operand_shapes)
                                   + list(result_shapes), ranges)))


def inverse_cost(operand_shapes, result_shapes):
    """K K^T a key head and the substitution's C^3 / 3 multiply-adds a
    value head (two value heads a key head)."""
    bk, t, lanes = _operand_dims(operand_shapes, 1)
    return t * (bk * 2.0 * CHUNK * HEAD_DIM
                + 2 * bk * 2.0 * CHUNK * CHUNK / 3), ranged_bytes(
        operand_shapes, result_shapes, lanes, first=1)


def operands_fwd_cost(operand_shapes, result_shapes):
    """Q K^T a key head, W and U a value head."""
    bk, t, lanes = _operand_dims(operand_shapes, 3)
    return t * (bk * 2.0 * CHUNK * HEAD_DIM
                + 2 * bk * 2 * 2.0 * CHUNK * HEAD_DIM), ranged_bytes(
        operand_shapes, result_shapes, lanes, pair=True)


def operands_bwd_cost(operand_shapes, result_shapes):
    """A value head: dT (two), dk', dv, and its share of the four
    products that turn d(K K^T) and d(Q K^T) into dq and dk, of
    2 C C D; the inverse's gradient (two) and the decay's pair sums, of
    2 C^3.  The rebuilt K K^T and Q K^T are not credited."""
    bk, t, lanes = _operand_dims(operand_shapes, 3)
    return 2 * bk * t * (8 * 2.0 * CHUNK * HEAD_DIM
                         + 3 * 2.0 * CHUNK * CHUNK), ranged_bytes(
        operand_shapes, result_shapes, lanes, pair=True)


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("gated_delta_fwd", scan_fwd_cost)
    register_kernel_cost("gated_delta_bwd", scan_bwd_cost)
    register_kernel_cost("gated_delta_inverse", inverse_cost)
    register_kernel_cost("gated_delta_operands_fwd", operands_fwd_cost)
    register_kernel_cost("gated_delta_operands_bwd", operands_bwd_cost)


_register_costs()


# -- the batch part (XLA) ----------------------------------------------

@jax.custom_vjp
def unit_lower_inverse(a):
    """(I + A)^-1 of strictly lower triangular (.., C, C) float32 A, by
    XLA's triangular solve (forward substitution in blocks: stable
    whatever the keys; 8.1 ms for a layer's 8192 matrices on a v5e
    against 14.6 for the product (I - A)(I + A^2)...(I + A^32) of ten
    float32 matmuls, whose powers also grow where keys repeat; my chip
    run, PR 44).  A VJP of its own, dA = -M^T dM M^T: two matmuls, and
    M is all it keeps."""
    eye = jnp.eye(a.shape[-1], dtype=a.dtype)
    return jax.scipy.linalg.solve_triangular(
        eye + a, jnp.broadcast_to(eye, a.shape), lower=True,
        unit_diagonal=True)


def _inverse_fwd(a):
    m = unit_lower_inverse(a)
    return m, m


def _inverse_bwd(m, dm):
    mt = jnp.swapaxes(m, -1, -2)
    return (-jnp.matmul(jnp.matmul(mt, dm, precision=_HI), mt,
                        precision=_HI),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


@jax.custom_vjp
def chunk_decay(g):
    """(gamma, G) of the log decay g (.., C) of whole chunks: gamma_i =
    sum_{t<=i} g_t and G_ij = exp(gamma_i - gamma_j) for i >= j, else
    0.  A VJP of its own: G depends on g_t through the pairs j < t <= i
    alone, and the gradient is summed over those; differentiating the
    difference of two cumulative sums instead adds +x to gamma_i and -x
    to gamma_j for EVERY pair and leaves their float32 cancellation in
    the sum (a hundred times the error on a decay parameter's
    gradient)."""
    return _chunk_decay(g)


def _suffix_sum(x, axis):
    """sum_{i >= t} x_i along `axis`."""
    return jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)


def _lower(c, strict=False):
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return row > col if strict else row >= col


def _chunk_decay(g):
    gamma = jnp.cumsum(g, axis=-1)
    return gamma, jnp.exp(jnp.where(
        _lower(g.shape[-1]), gamma[..., :, None] - gamma[..., None, :],
        -jnp.inf))


def _decay_fwd(g):
    gamma, decay = _chunk_decay(g)
    return (gamma, decay), decay


def _decay_bwd(decay, cts):
    dgamma, ddecay = cts
    # sum_{i >= t} over rows, then over the columns j < t
    pairs = jnp.sum(jnp.where(_lower(decay.shape[-1], strict=True),
                              _suffix_sum(ddecay * decay, -2), 0.0), axis=-1)
    return (pairs + _suffix_sum(dgamma, -1),)


chunk_decay.defvjp(_decay_fwd, _decay_bwd)


def chunk_operands(q, k, v, g, beta):
    """What the sequential part reads, for every chunk at once.  q, k
    (N, T, Hk, Dk), v (N, T, Hv, Dv), g, beta (N, T, Hv) float32, T a
    whole number of chunks; value head h reads key head h // (Hv / Hk).
    Returns W, U, Qg, Kd (N*Hv, T, D) and P (N*Hv, T, C) in v's dtype
    and exp(gamma_C) (N*Hv, T / C) float32."""
    n, t, hk, dk = k.shape
    hv, dv = v.shape[2], v.shape[3]
    nc, c, r, dt, f32 = t // CHUNK, CHUNK, hv // hk, v.dtype, jnp.float32

    def chunks(x):          # (N, T, H, ..) -> (N, nc, C, H, ..)
        return x.reshape((n, nc, c) + x.shape[2:])

    def products(a, b):     # a key head's (C, C) products, float32
        return jnp.einsum("ncihd,ncjhd->nhcij", chunks(a), chunks(b),
                          preferred_element_type=f32)

    def heads(x):           # key heads -> the value heads that read them
        return jnp.repeat(x, r, axis=1)

    gc = jnp.moveaxis(chunks(g.astype(f32)), 3, 1)          # (N,Hv,nc,C)
    gamma, decay = chunk_decay(gc)
    last = gamma[..., -1:]
    # what is left of the chunk after each position, summed as it is
    # (not as last - gamma: a difference of two sums)
    rest = _suffix_sum(gc, -1) - gc
    b = jnp.moveaxis(chunks(beta.astype(f32)), 3, 1)        # (N,Hv,nc,C)
    a = jnp.where(_lower(c, strict=True),
                  b[..., :, None] * heads(products(k, k)) * decay, 0.0)
    solve = (unit_lower_inverse(a) * b[..., None, :]).astype(dt)
    p = (heads(products(q, k)) * decay).astype(dt)          # lower incl.

    def per_head(x):        # (N, T, H, D) -> (N, Hv, nc, C, D) float32
        x = jnp.moveaxis(chunks(x), 3, 1).astype(f32)
        return x if x.shape[1] == hv else heads(x)

    e = jnp.exp(gamma)[..., None]
    kh = per_head(k)
    w = jnp.einsum("nhcij,nhcjd->nhcid", solve, (kh * e).astype(dt),
                   preferred_element_type=f32).astype(dt)
    u = jnp.einsum("nhcij,nhcjd->nhcid", solve,
                   jnp.moveaxis(chunks(v), 3, 1),
                   preferred_element_type=f32).astype(dt)
    qg = (per_head(q) * e).astype(dt)
    kd = (kh * jnp.exp(rest)[..., None]).astype(dt)
    flat = lambda x, d: x.reshape(n * hv, t, d)  # noqa: E731
    return (flat(w, dk), flat(u, dv), flat(qg, dk), flat(kd, dk),
            flat(p, c), jnp.exp(last).reshape(n * hv, nc))


# -- the batch part, as the kernels run it -----------------------------
#
# Two value heads read a key head (`operand_kernels_take`), and their
# two (C, C) matrices lie side by side in one (C, 2C) float32 tile, 128
# lanes: head 0 in lanes 0-63, head 1 in lanes 64-127 ("side by side"
# below).  A product a head is one MXU product of such a tile with the
# (2C, 2C) block-diagonal form of the other operand (`_block_diagonal`).

def operand_kernels_take(hk, hv, dk, dv):
    """Whether the chunk-operand kernels run a call: from the shape
    alone (heads of 128, two value heads a key head)."""
    return kernel_takes(dk, dv) and hv == 2 * hk


# rows of the (8, 2C) float32 tile a chunk and key head carries into
# the kernels, each side by side: the cumulative decay, what is left of
# the chunk after a position, beta, and g itself (which no forward
# reads: the decay matrix's gradient is summed over the pairs that
# straddle a position and lands THERE, `chunk_decay`'s rule)
ROW_GAMMA, ROW_REST, ROW_BETA, ROW_G = range(4)
# rows a diagonal block of the substitution holds: the blocks' inverses
# row by row on the VPU, the rest by block products on the MXU
DIAGONAL_BLOCK = 16


def _dot_hi(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _tile_iotas():
    shape = (CHUNK, 2 * CHUNK)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return row, lane & (CHUNK - 1), lane < CHUNK


def _block_diagonal(x, left):
    """Side by side (C, 2C) -> [[x0, 0], [0, x1]] (2C, 2C)."""
    zero = jnp.zeros_like(x)
    return jnp.concatenate([jnp.where(left, x, zero),
                            jnp.where(left, zero, x)], axis=0)


def _side_by_side(x, left):
    """The diagonal blocks of a (2C, 2C) product, side by side."""
    return jnp.where(left, x[:CHUNK], x[CHUNK:])


def _heads_product(a, b, left):
    """a_h @ b_h a head, all three side by side."""
    return _dot_hi(a, _block_diagonal(b, left), ((1,), (0,)))


def _inverse_side_by_side(a, iotas, block=None):
    """(I + A_h)^-1 a head of side-by-side strictly lower A: forward
    substitution.  The diagonal blocks of `block` rows row by row, all
    at once: B starts as I, and step j takes column j of every block
    times the block's row j (final by then) off the rows below.  Then
    the blocks double: with M the inverse so far and L the lower-left
    quarter of each doubled block, the inverse is M - M L M.

    (`lax` and not `jnp` in the steps: a `jnp.where` or a `jnp` index
    is a jitted function of its own, and these sixty-odd steps are
    traced and lowered in every warm start, `setup_s`.)"""
    lax = jax.lax
    block = block or DIAGONAL_BLOCK
    row, col, left = iotas
    lanes = 2 * CHUNK
    one, zero = (jnp.full((CHUNK, lanes), x, jnp.float32) for x in (1., 0.))
    m = lax.select(row == col, one, zero)

    def wide(x, rows):          # a row or a column over (rows, lanes)
        return lax.broadcast_in_dim(x, (rows, lanes), (0, 1))

    for j in range(block - 1):
        # rows up to j are final: leave the sublane tiles that hold
        # nothing else alone
        skip = (j + 1) // 8 * 8
        rows = block - skip
        first_head = lax.broadcasted_iota(jnp.int32, (rows, lanes), 1) < CHUNK
        parts = []
        for first in range(0, CHUNK, block):
            at, top = first + j, first + skip
            column = lax.select(
                first_head,
                wide(lax.slice(a, (top, at), (top + rows, at + 1)), rows),
                wide(lax.slice(a, (top, CHUNK + at),
                               (top + rows, CHUNK + at + 1)), rows))
            pivot = wide(lax.slice(m, (at, 0), (at + 1, lanes)), rows)
            if skip:
                parts.append(lax.slice(m, (first, 0), (top, lanes)))
            parts.append(lax.slice(m, (top, 0), (top + rows, lanes))
                         - column * pivot)
        m = lax.concatenate(parts, 0)
    size = block
    while size < CHUNK:
        quarter = ((row // (2 * size)) == (col // (2 * size))) \
            & ((row // size) != (col // size))
        m = m - _heads_product(
            m, _heads_product(lax.select(quarter, a, zero), m, left), left)
        size *= 2
    return m


def _tile_columns(x8, iotas):
    """What every chunk-operand kernel builds of a chunk's row tile:
    its columns (gamma, what is left after a position, beta: a pair of
    (C, 1) a head each) and the decay matrix, side by side."""
    row, col, left = iotas
    xt = x8.T                                       # (2C, 8)
    columns = [(xt[:CHUNK, i:i + 1], xt[CHUNK:, i:i + 1])
               for i in (ROW_GAMMA, ROW_REST, ROW_BETA)]
    gamma_i = jnp.where(left, *columns[0])
    decay = jnp.where(row >= col,
                      jnp.exp(gamma_i - x8[ROW_GAMMA:ROW_GAMMA + 1]), 0.0)
    return columns, decay


def _twice(k):
    """[k; k]: a product against it is x K^T twice side by side (the
    MXU repeats a key head's product for its second value head)."""
    return jnp.concatenate([k, k], axis=0)


def _for_each_chunk(block_chunks, chunk):
    """`chunk(c)` for the chunks of a grid step: a loop, one chunk a
    body.  (A chunk is one chain of dependent steps, and two a body
    took 0.2 ms off a forward call and 1.3 off a backward one at 16384
    positions x 16 / 32 heads, of 9.8 and 5.5; but the bodies are
    traced and lowered in every warm start, and `setup_s` paid 2 s for
    them: my chip runs, PR 45.)"""
    def body(c, carry):
        chunk(c)
        return carry

    jax.lax.fori_loop(0, block_chunks, body, 0)


def _chunk_rows(c):
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)


def _l2norm(x, constant=1.0):
    """(x * rstd [* constant], rstd = 1 / sqrt(sum x^2 + 1e-6) (R, 1)) of
    float32 rows (R, 128), a head's lanes: an XLU lane reduction a row,
    `head_norm_fwd`'s float32 arithmetic."""
    rstd = _rstd(x, Form(0, HEAD_DIM))
    y = x * rstd
    return (y * constant if constant != 1 else y), rstd


def _head_rows(x_ref, scratch, constant=1.0):
    """r -> a chunk's float32 rows of a head (C, 128), for a kernel's
    chunk loop.  Without `scratch` `x_ref` (1, rows, 128) holds unit
    vectors, read as they are.  With it (VMEM, (rows, 128) float32: a
    call with `raw`) `x_ref` holds the projection's rows, and their
    l2norm [* constant] is taken HERE, before the loop, for all the rows
    of the grid step at once, into the scratch the chunks then read.
    Not a chunk at a time: the square, the lane reduction, the rsqrt and
    the product are one chain of dependent steps at the head of a
    chunk's own chain, which nothing in these kernels' chunk bodies
    hides (+0.31 ms a call of `gated_delta_inverse`, +0.41 of
    `_operands_fwd` at 16384 x 16 heads, ~70 cycles a chunk and as much
    as the `head_norm_fwd` passes they replace; a grid step's 512 rows
    pipeline: +0.18 and +0.21; my chip runs, PR 69, calls 1 and 2)."""
    if scratch is None:
        return lambda r: x_ref[0, r, :].astype(jnp.float32)
    scratch[...] = _l2norm(x_ref[0].astype(jnp.float32), constant)[0]
    return lambda r: scratch[r, :]


def _rstd_rows(x_ref, r):
    """A chunk's 1 / norm (C, 1) of the raw rows again, where a backward
    kernel needs it: at the chunk's END (the l2norm's rule), off the
    chain."""
    return _l2norm(x_ref[0, r, :].astype(jnp.float32))[1]


def _raw_gradient(dy, y, rstd, constant=1.0, through=0.0):
    """The l2norm's VJP: the gradient of the raw rows x given `dy`, that
    of y = x * rstd [* constant] (C, 128) float32; `through` (C, 1):
    what reaches rstd by other ways (kb = beta k rides k's)."""
    if constant != 1:
        dy, y = dy * constant, y * (1.0 / constant)
    along = jnp.sum(dy * y, axis=1, keepdims=True) + through
    return rstd * (dy - y * along)


_Q_SCALE = HEAD_DIM ** -0.5     # q's constant where a head is 128 lanes


def _unit_q_and_k_rows(q_ref, k_ref, scratch):
    """(`_head_rows` of q, of k); `scratch`: a kernel's trailing refs,
    q's and k's buffers or none."""
    q_scratch, k_scratch = scratch or (None, None)
    return (_head_rows(q_ref, q_scratch, _Q_SCALE),
            _head_rows(k_ref, k_scratch))


def _inverse_kernel(k_ref, x_ref, m_ref, *scratch, block_chunks):
    iotas = _tile_iotas()
    row, col, left = iotas
    rows_of_k = _head_rows(k_ref, scratch[0] if scratch else None)

    def chunk(c):
        r = _chunk_rows(c)
        k = rows_of_k(r).astype(k_ref.dtype)
        (_, _, beta), decay = _tile_columns(x_ref[0, c], iotas)
        kk = _dot(k, _twice(k), ((1,), (1,)))
        a = jnp.where(row > col, jnp.where(left, *beta) * kk * decay, 0.0)
        m_ref[0, r, :] = _inverse_side_by_side(a, iotas)

    _for_each_chunk(block_chunks, chunk)


def _operands_fwd_kernel(q_ref, k_ref, v_ref, x_ref, m_ref, w_ref, u_ref,
                         qg_ref, kd_ref, p_ref, *scratch, block_chunks):
    iotas = _tile_iotas()
    rows_of_q, rows_of_k = _unit_q_and_k_rows(q_ref, k_ref, scratch)

    def chunk(c):
        r = _chunk_rows(c)
        x8, dt = x_ref[0, c], k_ref.dtype
        qf, kf = rows_of_q(r), rows_of_k(r)
        q, k = qf.astype(dt), kf.astype(dt)
        (gamma, rest, _), decay = _tile_columns(x8, iotas)
        qk = _dot(q, _twice(k), ((1,), (1,)))
        solve = (m_ref[0, r, :] * x8[ROW_BETA:ROW_BETA + 1]).astype(dt)
        p = (qk * decay).astype(dt)
        for h in range(2):
            lanes = slice(h * CHUNK, (h + 1) * CHUNK)
            e = jnp.exp(gamma[h])
            p_ref[h, r, :] = p[:, lanes]
            w_ref[h, r, :] = _dot(solve[:, lanes], (kf * e).astype(dt),
                                  ((1,), (0,))).astype(dt)
            u_ref[h, r, :] = _dot(
                solve[:, lanes],
                v_ref[0, r, h * HEAD_DIM:(h + 1) * HEAD_DIM],
                ((1,), (0,))).astype(dt)
            qg_ref[h, r, :] = (qf * e).astype(dt)
            kd_ref[h, r, :] = (kf * jnp.exp(rest[h])).astype(dt)

    _for_each_chunk(block_chunks, chunk)


def _operands_bwd_kernel(q_ref, k_ref, v_ref, x_ref, m_ref, dw_ref, du_ref,
                         dqg_ref, dkd_ref, dp_ref, *rest, block_chunks,
                         raw=None, heads=None):
    """`rest`: the results, then the scratch.  The results are dq, dk
    and dv, a key head's block of each, and the row tiles' gradient;
    or, where `_joint(raw)` (`heads`: the key heads), ONE array dQKV,
    left in HBM (`pl.ANY`), and the row tiles' gradient: a grid step
    then writes its dq, dk and dv into VMEM buffers (two slots of each,
    the trailing scratch, with a DMA semaphore a copy) and sends the
    three to their lanes of dQKV itself, dq to `raw.q + 128 h`, dk to
    `raw.k + 128 h`, dv to `raw.v + 256 h`; it waits for the copies of
    two steps before, whose slot it takes, and a key head's last step
    waits for all that are in flight."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    slot, send = 0, None
    if _joint(raw):
        from jax.experimental.pallas import tpu as pltpu

        dqkv_ref, dx_ref, *scratch, dq_ref, dk_ref, dv_ref, sent = rest
        b, i, blocks = (pl.program_id(0), pl.program_id(1),
                        pl.num_programs(1))
        slot, rows = i % 2, block_chunks * CHUNK

        def send(slot, step):   # a step's three copies, to start or await
            r = pl.ds(pl.multiple_of(step * rows, rows), rows)
            return [pltpu.make_async_copy(
                ref.at[slot], dqkv_ref.at[b // heads, r, pl.ds(
                    pl.multiple_of(first + b % heads * lanes, HEAD_DIM),
                    lanes)], sent.at[slot, j])
                for j, (ref, first, lanes) in enumerate((
                    (dq_ref, raw.q, HEAD_DIM), (dk_ref, raw.k, HEAD_DIM),
                    (dv_ref, raw.v, 2 * HEAD_DIM)))]

        @pl.when(i >= 2)
        def _the_slot_is_free():
            for copy in send(slot, i - 2):
                copy.wait()
    else:
        dq_ref, dk_ref, dv_ref, dx_ref, *scratch = rest

    iotas = _tile_iotas()
    row, col, left = iotas
    shape = (2 * CHUNK, 2 * CHUNK)
    above = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    # [j, t] = j < t inside a head's block: X @ this sums a row's
    # entries left of each position
    left_of = jnp.where(
        ((above < CHUNK) == (lane < CHUNK))
        & ((above & (CHUNK - 1)) < (lane & (CHUNK - 1))), 1.0, 0.0
    ).astype(f32)
    sublane = jax.lax.broadcasted_iota(jnp.int32, (8, 2 * CHUNK), 0)

    rows_of_q, rows_of_k = _unit_q_and_k_rows(q_ref, k_ref, scratch)

    def to_row(columns):        # two (C, 1) columns -> a (1, 2C) row
        return jnp.sum(jnp.where(row == col, jnp.where(left, *columns), 0.0),
                       axis=0, keepdims=True)

    def by_head(x):             # a side-by-side tile's sums over a head
        return (jnp.sum(jnp.where(left, x, 0.0), axis=1, keepdims=True),
                jnp.sum(jnp.where(left, 0.0, x), axis=1, keepdims=True))

    def chunk(c):
        r = _chunk_rows(c)
        x8, m, dt = x_ref[0, c], m_ref[0, r, :], k_ref.dtype
        qf, kf = rows_of_q(r), rows_of_k(r)
        q, k = qf.astype(dt), kf.astype(dt)
        (gamma, rest, beta), decay = _tile_columns(x8, iotas)
        kk2 = _twice(k)
        kk, qk = _dot(k, kk2, ((1,), (1,))), _dot(q, kk2, ((1,), (1,)))
        beta_j = x8[ROW_BETA:ROW_BETA + 1]
        solve = (m * beta_j).astype(dt)
        dq = jnp.zeros((CHUNK, HEAD_DIM), f32)
        dk = jnp.zeros((CHUNK, HEAD_DIM), f32)
        dsolve, dgamma, drest = [], [], []
        for h in range(2):
            lanes = slice(h * CHUNK, (h + 1) * CHUNK)
            wide = slice(h * HEAD_DIM, (h + 1) * HEAD_DIM)
            e, left_after = jnp.exp(gamma[h]), jnp.exp(rest[h])
            dw, du = dw_ref[h, r, :], du_ref[h, r, :]
            dqg, dkd = dqg_ref[h, r, :].astype(f32), \
                dkd_ref[h, r, :].astype(f32)
            dsolve.append(
                _dot(dw, (kf * e).astype(dt), ((1,), (1,)))
                + _dot(du, v_ref[0, r, wide], ((1,), (1,))))
            dkg = _dot(solve[:, lanes], dw, ((0,), (0,)))
            dv_ref[slot, r, wide] = _dot(solve[:, lanes], du,
                                         ((0,), (0,))).astype(dt)
            dk = dk + dkg * e + dkd * left_after
            dq = dq + dqg * e
            dgamma.append(e * jnp.sum(dkg * kf + dqg * qf, axis=1,
                                      keepdims=True))
            drest.append(left_after * jnp.sum(dkd * kf, axis=1,
                                              keepdims=True))
        dsolve = jnp.concatenate(dsolve, axis=1)
        dbeta = jnp.sum(dsolve * m, axis=0, keepdims=True)
        # the inverse's gradient, dA = -M^T dM M^T a head
        da = _dot_hi(dsolve * beta_j, _block_diagonal(m, left), ((1,), (1,)))
        da = -_side_by_side(_dot_hi(m, da, ((0,), (0,))), left)
        da = jnp.where(row > col, da, 0.0) * decay
        beta_i = jnp.where(left, *beta)
        dbeta = dbeta + to_row(by_head(da * kk))
        dp = jnp.concatenate([dp_ref[0, r, :], dp_ref[1, r, :]],
                             axis=1).astype(f32)
        # the decay matrix's gradient, summed over the pairs j < t <= i
        pairs = da * beta_i * kk + dp * decay * qk
        pairs = jnp.sum(jnp.where(
            row >= col, _dot_hi(pairs, left_of, ((1,), (0,))), 0.0),
            axis=0, keepdims=True)
        dkk, dqk = (da * beta_i).astype(dt), (dp * decay).astype(dt)
        folded = _dot(dqk, q, ((0,), (0,))) + _dot(dkk, k, ((0,), (0,)))
        dq = dq + _dot(dqk, kk2, ((1,), (0,)))
        dk = (dk + _dot(dkk, kk2, ((1,), (0,)))
              + folded[:CHUNK] + folded[CHUNK:])
        if scratch:     # raw q and k: the l2norm's rule
            dq = _raw_gradient(dq, qf, _rstd_rows(q_ref, r), _Q_SCALE)
            dk = _raw_gradient(dk, kf, _rstd_rows(k_ref, r))
        dq_ref[slot, r, :] = dq.astype(dt)
        dk_ref[slot, r, :] = dk.astype(dt)
        rows = {ROW_GAMMA: to_row(dgamma), ROW_REST: to_row(drest),
                ROW_BETA: dbeta, ROW_G: pairs}
        tile = jnp.zeros((8, 2 * CHUNK), f32)
        for at, value in rows.items():
            tile = jnp.where(sublane == at, value, tile)
        dx_ref[0, c] = tile

    _for_each_chunk(block_chunks, chunk)
    if send:
        for copy in send(slot, i):
            copy.start()

        @pl.when(i == blocks - 1)
        def _nothing_stays_in_flight():
            for copy in send(slot, i):
                copy.wait()

            @pl.when(blocks > 1)
            def _nor_the_step_before():
                for copy in send(1 - slot, i - 1):
                    copy.wait()


def _joint(raw):
    """Whether a call's q, k AND v are one array, QKV (`RawQK.v`)."""
    return raw is not None and raw.v is not None


def _operand_specs(hk, bc, raw=None):
    """The chunk-operand kernels' blocks, by name: `query` / `key` (a
    key head's 128 lanes of q's and k's arrays: their own, or the ones
    `raw` describes), `narrow` (the same of an (N, T, Hk x 128) array),
    `pair` (its two value heads' lanes of v: v's own array, or QKV from
    `raw.v` on), `tile` (its row tiles), `inverse`, and `wide` /
    `square` (its two value heads of (N Hv, T, 128) / (.., C))."""
    import types

    from jax.experimental import pallas as pl

    rows = bc * CHUNK

    def key_head(width, start=0):   # a key head's lanes of (N, T, ..)
        first = start // width
        return pl.BlockSpec((1, rows, width),
                            lambda b, i: (b // hk, i, first + b % hk))

    def value_heads(width):     # its two value heads of (N Hv, T, width)
        return pl.BlockSpec((2, rows, width), lambda b, i: (b, i, 0))

    narrow = key_head(HEAD_DIM)
    return types.SimpleNamespace(
        query=key_head(HEAD_DIM, raw.q) if raw else narrow,
        key=key_head(HEAD_DIM, raw.k) if raw else narrow,
        narrow=narrow,
        pair=key_head(2 * HEAD_DIM, raw.v if _joint(raw) else 0),
        tile=pl.BlockSpec((1, bc, 8, 2 * CHUNK), lambda b, i: (b, i, 0, 0)),
        inverse=pl.BlockSpec((1, rows, 2 * CHUNK), lambda b, i: (b, i, 0)),
        wide=value_heads(HEAD_DIM), square=value_heads(CHUNK))


def _unit_scratch(raw, rows, count):
    """`count` float32 (rows, 128) buffers for the unit vectors a kernel
    makes of raw rows (`_head_rows`); none for unit operands."""
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM((rows, HEAD_DIM), jnp.float32)] * count if raw else []


def _operand_grid(k, x, raw):
    """(N, T, Hk, chunks a grid step, the grid) of a chunk-operand call:
    the heads by the row tiles, whatever array holds k."""
    n, t = k.shape[:2]
    hk, nc = x.shape[0] // n, t // CHUNK
    if raw:
        whole = (raw.heads, raw.dim, raw.q % HEAD_DIM, raw.k % HEAD_DIM) \
            == (hk, HEAD_DIM, 0, 0)
        if _joint(raw):     # a value-head pair's block, and every lane
            # of QKV some head's: dQKV is written and never added to
            parts = sorted([(raw.q, hk), (raw.k, hk), (raw.v, 2 * hk)])
            ends = [first + heads * HEAD_DIM for first, heads in parts]
            whole = whole and raw.v % (2 * HEAD_DIM) == 0 \
                and (raw.value_dim or raw.dim) == HEAD_DIM \
                and [first for first, _ in parts] + [k.shape[2]] == [0] + ends
    else:
        whole = k.shape[2] == hk * HEAD_DIM
    if not whole:
        raise ValueError(f"gated_delta kernels: k {k.shape} at {raw} is "
                         f"not {hk} heads of {HEAD_DIM} lanes")
    bc = _block_chunks(nc)
    return n, t, hk, bc, (n * hk, nc // bc)


_STATIC = ("raw", "interpreted")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _inverse_call(k, x, raw=None, interpreted=False):
    """(I + A)^-1 of every chunk (float32, two heads a tile: 134 MB a
    layer at the cell's shape), from k and the row tiles alone."""
    n, t, hk, bc, grid = _operand_grid(k, x, raw)
    at = _operand_specs(hk, bc, raw)
    return _pallas_call(
        functools.partial(_inverse_kernel, block_chunks=bc),
        name="gated_delta_inverse", grid=grid,
        in_specs=[at.key, at.tile], out_specs=at.inverse,
        out_shape=jax.ShapeDtypeStruct((n * hk, t, 2 * CHUNK), jnp.float32),
        scratch_shapes=_unit_scratch(raw, bc * CHUNK, 1),
        compiler_params=_params(),
    )(k, x)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _operands_fwd_call(q, k, v, x, m, raw=None, interpreted=False):
    """W, U, Qg, Kd, P of every chunk, given its (I + A)^-1."""
    n, t, hk, bc, grid = _operand_grid(k, x, raw)
    at = _operand_specs(hk, bc, raw)
    flat = lambda d: jax.ShapeDtypeStruct((2 * n * hk, t, d),  # noqa: E731
                                          v.dtype)
    return _pallas_call(
        functools.partial(_operands_fwd_kernel, block_chunks=bc),
        name="gated_delta_operands_fwd", grid=grid,
        in_specs=[at.query, at.key, at.pair, at.tile, at.inverse],
        out_specs=[at.wide] * 4 + [at.square],
        out_shape=[flat(HEAD_DIM)] * 4 + [flat(CHUNK)],
        scratch_shapes=_unit_scratch(raw, bc * CHUNK, 2),
        compiler_params=_params(),
    )(q, k, v, x, m)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _operands_bwd_call(q, k, v, x, m, dw, du, dqg, dkd, dp, raw=None,
                       interpreted=False):
    """(dq, dk, dv, the row tiles' gradient); dq and dk (N, T, Hk x 128)
    each: the unit vectors', or with `raw` the projection's own lanes'.
    Where q, k and v are the one QKV (`_joint`): (dQKV, the row tiles'
    gradient), dQKV as QKV lies, every lane written once by the
    kernel's own copies."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, t, hk, bc, grid = _operand_grid(k, x, raw)
    at = _operand_specs(hk, bc, raw)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    heads = jax.ShapeDtypeStruct((n, t, hk * HEAD_DIM), v.dtype)
    scratch = _unit_scratch(raw, bc * CHUNK, 2)
    if _joint(raw):
        out_specs = [pl.BlockSpec(memory_space=pl.ANY), at.tile]
        out_shape = [like(v), like(x)]
        # two slots of dq, dk and dv, and a semaphore a copy in flight
        scratch += [pltpu.VMEM((2, bc * CHUNK, lanes), v.dtype)
                    for lanes in (HEAD_DIM, HEAD_DIM, 2 * HEAD_DIM)]
        scratch.append(pltpu.SemaphoreType.DMA((2, 3)))
    else:
        out_specs = [at.narrow, at.narrow, at.pair, at.tile]
        out_shape = [heads, heads, like(v), like(x)]
    return _pallas_call(
        functools.partial(_operands_bwd_kernel, block_chunks=bc, raw=raw,
                          heads=hk),
        name="gated_delta_operands_bwd", grid=grid,
        in_specs=[at.query, at.key, at.pair, at.tile, at.inverse]
        + [at.wide] * 4 + [at.square],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=_params(),
    )(q, k, v, x, m, dw, du, dqg, dkd, dp)


def _record_operands(x, raw):
    """Count a call of a chunk-operand kernel where it is traced
    (outside the jitted call, which is traced once a shape), by its row
    tiles, and whether it took QKV whole; gives the interpret gate,
    which keys that call's cache: the same shapes are lowered through
    the interpreter and through Mosaic in one test process."""
    from ...observe.monitoring import runtime_stats
    from . import interpret

    runtime_stats.record_gated_delta_operands(2 * x.shape[0] * x.shape[1],
                                              flat=_joint(raw))
    return interpret()


def chunk_inverses(k, x, raw=None):
    """(I + A)^-1 of every chunk by `gated_delta_inverse`: k (N, T,
    Hk x 128), or with `raw` the array that holds it, and the row tiles
    -> (N Hk, T, 2C) float32, NAMED: a
    recompute segment keeps it (`ops/pallas keep_residuals`), so the
    segment's backward pass reads it and runs no substitution again.
    A constant of differentiation here: `operands_kernel`'s backward
    kernel holds the inverse's own rule (dA = -M^T dM M^T) and returns
    what flows through it with dk and the row tile's gradient."""
    from ...observe.monitoring import runtime_stats
    from . import INVERSE_RESIDUAL, interpret, keep_residuals

    runtime_stats.record_gated_delta_inverse()
    m, = keep_residuals(
        _inverse_call(jax.lax.stop_gradient(k), jax.lax.stop_gradient(x),
                      raw=raw, interpreted=interpret()),
        names=INVERSE_RESIDUAL)
    return m


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def operands_kernel(operands, x, m, raw=None):
    """`chunk_operands` less exp(gamma_C) by the Pallas kernels.
    `operands`: (q, k, v), q and k (N, T, Hk x 128) or with `raw` the
    arrays that hold them before the l2norm, v (N, T, 2 Hk x 128); or,
    where `raw.v` says that v lies there too, the ONE array QKV
    (N, T, W), whose gradient is then one array as well: every lane
    written once by the backward kernel, no pad and no sum of three.  x
    the row tiles (`_row_tiles`), m `chunk_inverses(k, x, raw)`."""
    return _operands_vjp_fwd(operands, x, m, raw)[0]


def _three(operands, raw):
    """q's, k's and v's arrays as a kernel call takes them: QKV thrice
    where it is the one operand."""
    return (operands,) * 3 if _joint(raw) else operands


def _operands_vjp_fwd(operands, x, m, raw):
    results = _operands_fwd_call(*_three(operands, raw), x, m, raw=raw,
                                 interpreted=_record_operands(x, raw))
    return tuple(results), (operands, x, m)


def _operands_vjp_bwd(raw, res, cts):
    operands, x, m = res
    q, k, v = _three(operands, raw)
    # m's own cotangent is none: its part is in dk and dx (above)
    *grads, dx = _operands_bwd_call(
        q, k, v, x, m, *(c.astype(v.dtype) for c in cts), raw=raw,
        interpreted=_record_operands(x, raw))
    if _joint(raw):
        grads, = grads          # dQKV, as QKV lies
    elif raw:
        dq, dk, dv = grads
        grads = (lane_range_gradient(dq, q.shape[-1], raw.q),
                 lane_range_gradient(dk, k.shape[-1], raw.k), dv)
    else:
        grads = tuple(grads)
    return grads, dx, jnp.zeros_like(m)


operands_kernel.defvjp(_operands_vjp_fwd, _operands_vjp_bwd)


def _row_tiles(g, beta, hk):
    """g, beta (N, T, Hv) float32 -> (N Hk, T / C, 8, 2C): a chunk's
    rows `ROW_GAMMA` .. `ROW_G`, a key head's two value heads side by
    side, and exp(gamma_C) (N Hv, T / C).  The sums are XLA's (and
    their gradients: sums again), as products with 0 / 1 triangles at
    "highest" (a cumulative sum along 64 of 2 MB is 1 ms of
    reduce-windows on the chip); `rest` is a suffix sum, not
    `last - gamma`."""
    n, t, hv = g.shape
    nc = t // CHUNK

    def heads_first(x):         # (N, T, Hv) -> (N, Hk, nc, 2, C)
        x = jnp.swapaxes(x, 1, 2).reshape(n, hk, 2, nc, CHUNK)
        return jnp.swapaxes(x, 2, 3)

    gc = heads_first(g)
    upto = jnp.where(_lower(CHUNK), 1.0, 0.0).astype(g.dtype)   # [i >= t]
    after = jnp.where(_lower(CHUNK), 0.0, 1.0).astype(g.dtype)  # [t > i]
    gamma = jnp.einsum("it,nhcvt->nhcvi", upto, gc, precision=_HI)
    rest = jnp.einsum("it,nhcvt->nhcvi", after, gc, precision=_HI)
    rows = jnp.stack([gamma, rest, heads_first(beta), gc], axis=3)
    rows = jnp.pad(rows.reshape(n * hk, nc, 4, 2 * CHUNK),
                   ((0, 0), (0, 0), (0, 4), (0, 0)))
    last = jnp.exp(jnp.swapaxes(gamma[..., -1], 2, 3))  # (N,Hk,2,nc)
    return rows, last.reshape(n * hv, nc)


def chunk_operands_kernel(q, k, v, g, beta, raw=None):
    """`chunk_operands` where `operand_kernels_take` the heads: the
    same six results, the chunk's matrices never in HBM.  With `raw`, q
    and k are the (N, T, W) arrays it describes and the kernels take
    their l2norm; where it says that v lies there too, q is QKV and k
    and v are None."""
    n, t, hv = g.shape
    hk = raw.heads if raw else k.shape[2]
    x, last = _row_tiles(g.astype(jnp.float32), beta.astype(jnp.float32),
                         hk)
    if _joint(raw):
        operands = k = q
    else:
        if not raw:
            q, k = (a.reshape(n, t, hk * HEAD_DIM) for a in (q, k))
        operands = (q, k, v.reshape(n, t, hv * HEAD_DIM))
    return operands_kernel(operands, x, chunk_inverses(k, x, raw),
                           raw) + (last,)


# -- the sequential part, as XLA runs it -------------------------------

def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                               preferred_element_type=jnp.float32)


def _chunk_step(s, w, u, qg, kd, p, dec):
    """One chunk of one head: (the state that leaves, O (C, Dv)
    float32).  `s` (Dk, Dv) float32; the dots read it in the operands'
    dtype."""
    dt = w.dtype
    sb = s.astype(dt)
    vp = (u.astype(jnp.float32) - _dot(w, sb, ((1,), (0,)))).astype(dt)
    o = _dot(qg, sb, ((1,), (0,))) + _dot(p, vp, ((1,), (0,)))
    return s * dec + _dot(kd, vp, ((0,), (0,))), o


def scan_xla(w, u, qg, kd, p, dec):
    """O (N*Hv, T, Dv) of the chunk operands: `_chunk_step` under a
    `lax.scan` over the chunks, every head at once."""
    bh, t, dk = w.shape
    dv, nc = u.shape[2], t // CHUNK

    def by_chunk(x):        # (BH, T, D) -> (nc, BH, C, D)
        return jnp.moveaxis(x.reshape(bh, nc, CHUNK, x.shape[2]), 1, 0)

    def step(s, xs):
        s, o = jax.vmap(_chunk_step)(s, *xs)
        return s, o

    xs = tuple(by_chunk(x) for x in (w, u, qg, kd, p)) \
        + (jnp.moveaxis(dec, 1, 0)[..., None, None],)
    _, o = jax.lax.scan(step, jnp.zeros((bh, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1).reshape(bh, t, dv).astype(u.dtype)


# -- the sequential part, as the kernels run it ------------------------

def _pallas_call(*args, **kw):
    from . import pallas_call  # shared interpret gate (package init)

    return pallas_call(*args, **kw)


def _rows(c):
    return slice(c * CHUNK, (c + 1) * CHUNK)


def _fwd_kernel(w_ref, u_ref, qg_ref, kd_ref, p_ref, dec_ref, o_ref,
                *rest, block_chunks):
    from jax.experimental import pallas as pl

    s_scr = rest[-1]
    states = rest[0] if len(rest) == 2 else None

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    for c in range(block_chunks):
        r = _rows(c)
        s = s_scr[...]
        if states is not None:      # the state that enters the chunk
            states[0, c * HEAD_DIM:(c + 1) * HEAD_DIM, :] = s.astype(
                states.dtype)
        s, o = _chunk_step(s, w_ref[0, r, :], u_ref[0, r, :],
                           qg_ref[0, r, :], kd_ref[0, r, :], p_ref[0, r, :],
                           dec_ref[0, c:c + 1, :])
        s_scr[...] = s
        o_ref[0, r, :] = o.astype(o_ref.dtype)


def _bwd_kernel(w_ref, u_ref, qg_ref, kd_ref, p_ref, dec_ref, s_ref, do_ref,
                dw_ref, du_ref, dqg_ref, dkd_ref, dp_ref, ddec_ref, ds_scr,
                *, block_chunks):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    f32 = jnp.float32
    for c in reversed(range(block_chunks)):
        r = _rows(c)
        w, qg, kd, p = (w_ref[0, r, :], qg_ref[0, r, :], kd_ref[0, r, :],
                        p_ref[0, r, :])
        do = do_ref[0, r, :]
        dt = w.dtype
        s = s_ref[0, c * HEAD_DIM:(c + 1) * HEAD_DIM, :]    # (Dk, Dv)
        ds = ds_scr[...]
        dsb = ds.astype(dt)
        vp = (u_ref[0, r, :].astype(f32)
              - _dot(w, s, ((1,), (0,)))).astype(dt)
        dvp = (_dot(p, do, ((0,), (0,)))
               + _dot(kd, dsb, ((1,), (0,)))).astype(dt)
        dp_ref[0, r, :] = _dot(do, vp, ((1,), (1,))).astype(dp_ref.dtype)
        dqg_ref[0, r, :] = _dot(do, s, ((1,), (1,))).astype(dqg_ref.dtype)
        dkd_ref[0, r, :] = _dot(vp, dsb, ((1,), (1,))).astype(dkd_ref.dtype)
        du_ref[0, r, :] = dvp.astype(du_ref.dtype)
        dw_ref[0, r, :] = (-_dot(dvp, s, ((1,), (1,)))).astype(dw_ref.dtype)
        ddec_ref[0, c:c + 1, :] = jnp.broadcast_to(
            jnp.sum(ds * s.astype(f32)), (1, HEAD_DIM))
        ds_scr[...] = (ds * dec_ref[0, c:c + 1, :]
                       + _dot(qg, do, ((0,), (0,)))
                       - _dot(w, dvp, ((0,), (0,))))


def _block_chunks(nc):
    """Chunks a grid step: the largest divisor of the chunk count within
    `DEFAULT_BLOCK_CHUNKS`."""
    return max(b for b in range(1, DEFAULT_BLOCK_CHUNKS + 1) if nc % b == 0)


def _specs(heads, bc, time):
    """Blocks of (N Hv, T, ..) operands a value head, and (the last) the
    head's lanes of the op's (N, T, Hv x 128) layout: o and its
    cotangent."""
    from jax.experimental import pallas as pl

    def tile(rows, lanes):
        return pl.BlockSpec((1, rows, lanes), lambda b, i: (b, time(i), 0))

    return (tile(bc * CHUNK, HEAD_DIM), tile(bc * CHUNK, CHUNK),
            tile(bc, HEAD_DIM), tile(bc * HEAD_DIM, HEAD_DIM),
            pl.BlockSpec((1, bc * CHUNK, HEAD_DIM),
                         lambda b, i: (b // heads, time(i), b % heads)))


def _lanes(dec):
    """exp(gamma_C) (BH, nc) as the kernels read it: a row of 128 equal
    lanes a chunk."""
    return jnp.broadcast_to(dec[..., None], dec.shape + (HEAD_DIM,))


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _record_scan(bh, nc):
    from ...observe.monitoring import runtime_stats

    runtime_stats.record_gated_delta(bh * nc)


def _scan_fwd_call(w, u, qg, kd, p, dec, heads, keep_states):
    from jax.experimental.pallas import tpu as pltpu

    bh, t, _ = w.shape
    nc = t // CHUNK
    bc = _block_chunks(nc)
    _record_scan(bh, nc)
    wide, narrow, scalar, state, lanes = _specs(heads, bc, lambda i: i)
    out_specs = [lanes]
    out_shape = [jax.ShapeDtypeStruct((bh // heads, t, heads * HEAD_DIM),
                                      u.dtype)]
    if keep_states:
        out_specs.append(state)
        out_shape.append(jax.ShapeDtypeStruct((bh, nc * HEAD_DIM, HEAD_DIM),
                                              w.dtype))
    return _pallas_call(
        functools.partial(_fwd_kernel, block_chunks=bc),
        name="gated_delta_fwd", grid=(bh, nc // bc),
        in_specs=[wide, wide, wide, wide, narrow, scalar],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((HEAD_DIM, HEAD_DIM), jnp.float32)],
        compiler_params=_params(),
    )(w, u, qg, kd, p, _lanes(dec))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def scan_kernel(w, u, qg, kd, p, dec, heads):
    """`scan_xla` by the Pallas kernels (Dk = Dv = 128), its result in
    the op's layout, (N, T, Hv x 128): a grid step writes its value
    head's 128 lanes, and the backward kernel reads dO the same way (no
    head-major o is made and none transposed)."""
    return _scan_fwd_call(w, u, qg, kd, p, dec, heads, False)[0]


def _scan_vjp_fwd(w, u, qg, kd, p, dec, heads):
    o, states = _scan_fwd_call(w, u, qg, kd, p, dec, heads, True)
    return o, (w, u, qg, kd, p, dec, states)


def _scan_vjp_bwd(heads, res, do):
    from jax.experimental.pallas import tpu as pltpu

    w, u, qg, kd, p, dec, states = res
    bh, t, _ = w.shape
    nc = t // CHUNK
    bc = _block_chunks(nc)
    nb = nc // bc
    _record_scan(bh, nc)
    wide, narrow, scalar, state, lanes = _specs(heads, bc,
                                                lambda i: nb - 1 - i)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    dw, du, dqg, dkd, dp, ddec = _pallas_call(
        functools.partial(_bwd_kernel, block_chunks=bc),
        name="gated_delta_bwd", grid=(bh, nb),
        in_specs=[wide, wide, wide, wide, narrow, scalar, state, lanes],
        out_specs=[wide, wide, wide, wide, narrow, scalar],
        out_shape=[like(w), like(u), like(qg), like(kd), like(p),
                   jax.ShapeDtypeStruct((bh, nc, HEAD_DIM), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((HEAD_DIM, HEAD_DIM), jnp.float32)],
        compiler_params=_params(),
    )(w, u, qg, kd, p, _lanes(dec), states, do.astype(u.dtype))
    return dw, du, dqg, dkd, dp, ddec[..., 0]


scan_kernel.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def gated_delta_rule(q, k, v, g, beta, use_kernel=False, raw=None):
    """O (N, T, Hv, Dv) of the recurrence at the top of this file.  q,
    k (N, T, Hk, Dk), v (N, T, Hv, Dv) in one dtype, g (the log decay,
    <= 0) and beta (N, T, Hv); value head h reads key head
    h // (Hv / Hk).  A T that is no whole number of chunks is padded
    with positions that write nothing (beta 0, no decay).
    `use_kernel`: the Pallas kernels (`kernel_takes` the head sizes),
    else `scan_xla`.  `raw` (a `RawQK`): q and k are not the unit
    vectors but the (N, T, W) arrays that hold the projection's, and
    the rule takes their l2norm: in the chunk-operand kernels where
    they run, through `unit_q_and_k` elsewhere.  Where `raw.v` says
    that v lies there too, q is that one array, QKV, and k and v are
    None: the kernels block q, k and v out of its lanes and hand back
    one dQKV; any other lowering cuts v out here."""
    n, t, hv = g.shape
    if raw:
        hk, dk = raw.heads, raw.dim
    else:
        hk, dk = k.shape[2:]
    dv = (raw.value_dim or dk) if _joint(raw) else v.shape[3]
    in_kernels = use_kernel and operand_kernels_take(hk, hv, dk, dv)
    if _joint(raw) and not in_kernels:
        k, v = q, q[..., raw.v:raw.v + hv * dv].reshape(n, t, hv, dv)
        raw = raw._replace(v=None)
    if raw and not in_kernels:
        q, k = (x.reshape(n, t, hk, dk) for x in unit_q_and_k(q, k, raw))
        raw = None
    apart = not _joint(raw)     # q, k and v are arrays of their own
    if hv % hk or beta.shape != g.shape or (apart and (
            q.shape != k.shape or v.shape[:3] != g.shape
            or not (raw or k.shape == (n, t, hk, dk)))):
        raise ValueError(
            f"gated_delta_rule: q {q.shape}, k {jnp.shape(k)}, v "
            f"{jnp.shape(v)}, g {g.shape}, beta {beta.shape} at {raw} are "
            f"not Hk key heads, a multiple Hv of value heads and a gate a "
            f"value head")
    if use_kernel and not kernel_takes(dk, dv):
        raise NotImplementedError(
            f"gated_delta_rule: the kernels take heads of {HEAD_DIM}, not "
            f"{dk} / {dv}")
    tail = -t % CHUNK
    if tail:
        q, k, v, g, beta = (
            x if x is None else
            jnp.pad(x, ((0, 0), (0, tail)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    if apart:
        q, k = q.astype(v.dtype), k.astype(v.dtype)
    if in_kernels:
        operands = chunk_operands_kernel(q, k, v, g, beta, raw)
    else:
        operands = chunk_operands(q, k, v, g, beta)
    if use_kernel:      # o as the op lays it, (N, T, Hv x Dv)
        o = scan_kernel(*operands, hv).reshape(n, t + tail, hv, dv)
    else:
        o = jnp.moveaxis(scan_xla(*operands).reshape(n, hv, t + tail, dv),
                         1, 2)
    return o[:, :t]
