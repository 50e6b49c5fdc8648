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

Two parts.  Everything that does not read S is a batch over all the
chunks and stays with XLA (`chunk_operands`): the cumulative decay, the
two (C, C) products a key head, the inverse, W, U and the two scaled
copies of Q and K.  (I + A)^-1 has no Mosaic primitive: it is XLA's
batched triangular solve against the identity (`unit_lower_inverse`,
with a VJP of its own, dA = -M^T dM M^T).  What
reads S is sequential over the chunks and is the kernel's: grid (batch x
value head, blocks of `DEFAULT_BLOCK_CHUNKS` chunks), the state in a
(Dk, Dv) float32 VMEM scratch across the grid as `recurrence.py` carries
(h, c), four MXU dots a chunk.  Operands of the dots are the operands'
dtype (bfloat16 under AMP), accumulation, S and gamma float32.

Backward: a custom VJP around the sequential part alone (XLA
differentiates the batch part).  The forward rule's kernel also writes
the state that ENTERS each chunk (in the operands' dtype, which is what
the dots read: 268 MB a layer at 16384 positions x 32 heads in
bfloat16); the backward kernel walks the blocks in reverse carrying dS
in scratch, rebuilds V' = U - W S from the saved state (one dot), and
emits dW, dU, d(Q exp gamma), d(K exp ..), dP and d exp(gamma_C).

The kernels take Dk = Dv = 128 (`kernel_takes`); any other head size
and the CPU run `scan_xla`, the same chunk steps as a `lax.scan` that
XLA differentiates.  `runtime_stats.gated_delta_calls` / `_chunks`
count the kernel calls traced and their chunks x heads: a step that
fell back reads 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 64
HEAD_DIM = 128          # the kernels' Dk and Dv
# chunks a grid step: 512 rows of every operand a DMA, 8 chunk steps
# unrolled in the body (tuned nowhere yet: retune here)
DEFAULT_BLOCK_CHUNKS = 8
_HI = jax.lax.Precision.HIGHEST


def kernel_takes(dk, dv):
    """Whether the Pallas kernels run a call: from the shape alone."""
    return (dk, dv) == (HEAD_DIM, HEAD_DIM)


# -- kernel cost registry (observe/cost.py) ----------------------------
#
# What the sequential part computes once, per chunk and head: forward
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


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("gated_delta_fwd", scan_fwd_cost)
    register_kernel_cost("gated_delta_bwd", scan_bwd_cost)


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


def _specs(bc, time):
    from jax.experimental import pallas as pl

    def tile(rows, lanes):
        return pl.BlockSpec((1, rows, lanes), lambda b, i: (b, time(i), 0))

    wide = tile(bc * CHUNK, HEAD_DIM)
    return wide, tile(bc * CHUNK, CHUNK), tile(bc, HEAD_DIM), \
        tile(bc * HEAD_DIM, HEAD_DIM)


def _lanes(dec):
    """exp(gamma_C) (BH, nc) as the kernels read it: a row of 128 equal
    lanes a chunk."""
    return jnp.broadcast_to(dec[..., None], dec.shape + (HEAD_DIM,))


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _scan_fwd_call(w, u, qg, kd, p, dec, keep_states):
    from jax.experimental.pallas import tpu as pltpu

    from ...observe.monitoring import runtime_stats

    bh, t, _ = w.shape
    nc = t // CHUNK
    bc = _block_chunks(nc)
    runtime_stats.record_gated_delta(bh * nc)
    wide, narrow, scalar, state = _specs(bc, lambda i: i)
    out_specs, out_shape = [wide], [jax.ShapeDtypeStruct(u.shape, u.dtype)]
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


@jax.custom_vjp
def scan_kernel(w, u, qg, kd, p, dec):
    """`scan_xla` by the Pallas kernels (Dk = Dv = 128)."""
    return _scan_fwd_call(w, u, qg, kd, p, dec, False)[0]


def _scan_vjp_fwd(w, u, qg, kd, p, dec):
    o, states = _scan_fwd_call(w, u, qg, kd, p, dec, True)
    return o, (w, u, qg, kd, p, dec, states)


def _scan_vjp_bwd(res, do):
    from jax.experimental.pallas import tpu as pltpu

    from ...observe.monitoring import runtime_stats

    w, u, qg, kd, p, dec, states = res
    bh, t, _ = w.shape
    nc = t // CHUNK
    bc = _block_chunks(nc)
    nb = nc // bc
    runtime_stats.record_gated_delta(bh * nc)
    wide, narrow, scalar, state = _specs(bc, lambda i: nb - 1 - i)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    dw, du, dqg, dkd, dp, ddec = _pallas_call(
        functools.partial(_bwd_kernel, block_chunks=bc),
        name="gated_delta_bwd", grid=(bh, nb),
        in_specs=[wide, wide, wide, wide, narrow, scalar, state, wide],
        out_specs=[wide, wide, wide, wide, narrow, scalar],
        out_shape=[like(w), like(u), like(qg), like(kd), like(p),
                   jax.ShapeDtypeStruct((bh, nc, HEAD_DIM), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((HEAD_DIM, HEAD_DIM), jnp.float32)],
        compiler_params=_params(),
    )(w, u, qg, kd, p, _lanes(dec), states, do.astype(u.dtype))
    return dw, du, dqg, dkd, dp, ddec[..., 0]


scan_kernel.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def gated_delta_rule(q, k, v, g, beta, use_kernel=False):
    """O (N, T, Hv, Dv) of the recurrence at the top of this file.  q,
    k (N, T, Hk, Dk), v (N, T, Hv, Dv) in one dtype, g (the log decay,
    <= 0) and beta (N, T, Hv); value head h reads key head
    h // (Hv / Hk).  A T that is no whole number of chunks is padded
    with positions that write nothing (beta 0, no decay).
    `use_kernel`: the Pallas kernels (`kernel_takes` the head sizes),
    else `scan_xla`."""
    n, t, hk, dk = k.shape
    hv, dv = v.shape[2], v.shape[3]
    if hv % hk or q.shape != k.shape or g.shape != (n, t, hv) \
            or beta.shape != g.shape:
        raise ValueError(
            f"gated_delta_rule: q {q.shape}, k {k.shape}, v {v.shape}, g "
            f"{g.shape}, beta {beta.shape} are not Hk key heads, a "
            f"multiple Hv of value heads and a gate a value head")
    if use_kernel and not kernel_takes(dk, dv):
        raise NotImplementedError(
            f"gated_delta_rule: the kernels take heads of {HEAD_DIM}, not "
            f"{dk} / {dv}")
    tail = -t % CHUNK
    if tail:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, tail)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    operands = chunk_operands(q.astype(v.dtype), k.astype(v.dtype), v, g,
                              beta)
    o = (scan_kernel if use_kernel else scan_xla)(*operands)
    return jnp.moveaxis(o.reshape(n, hv, t + tail, dv), 1, 2)[:, :t]
