"""Fused final-projection + label-smoothed softmax-CE Pallas kernel.

The big-vocab loss is the Transformer's HBM hot spot: composed, the
(N, V) logits tensor (N=B*T tokens, V≈32k vocab) materializes in f32 —
gigabytes of traffic per step between the projection matmul, the
softmax passes, and the backward.  This kernel never materializes
logits in HBM at all (the ops/jit/ tier of the reference,
kernel_base.h:25-44, is the precedent for owning hot kernels):

- forward: grid (token_blocks, vocab_blocks), vocab INNERMOST — the
  h-block and the online-softmax running stats (max, sumexp, target
  logit, logit sum) stay resident in VMEM while W streams through;
  per-token outputs are three f32 scalars (lse, z_label, z_sum).
  loss_i = lse_i - (1-eps) * z_label_i - (eps/V) * z_sum_i.
- backward: two accumulation kernels recomputing p = exp(z - lse)
  blockwise from the saved lse (flash-attention-style recompute):
  dh accumulates over vocab blocks (dh-block resident), dW over token
  blocks (dW-block resident).  dz = g * (p - (1-eps)*onehot - eps/V).

HBM traffic ≈ reads of h and W per pass instead of multiple (N, V)
round-trips; all matmuls are int-free MXU bf16 with f32 accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
# Block defaults — tuned ON the chip (r05, v5e, 32k vocab).  Two
# separate VMEM constraints bit here:
# 1. The (1, N) stat OUTPUTS (not the tiles) caused the original
#    compile failures at every block size — a degenerate sublane-1
#    layout that XLA stack-allocates in scoped VMEM ("exceeded scoped
#    vmem limit by 3.84M" regardless of blocks).  Fixed by the
#    8-sublane-replicated output layout in _fwd_kernel/_fwd.
# 2. The f32 logits tile + its mask/exp stack intermediates bound the
#    block product: (1024, 1024) and up fail Mosaic; (512, 1024)
#    compiles and measured fastest — bench sweep on chip:
#    (256,512) 0.3146 MFU < (512,512) 0.3204 ~ (1024,512) 0.3205
#    < (512,1024) 0.3250 (ties the unfused baseline at len256 and
#    beats it as part of the longctx stack: 0.3036 -> 0.3063; r05,
#    pre-ledger, not measured on the current code).
DEFAULT_BLOCK_T = 512
DEFAULT_BLOCK_V = 1024

# -- kernel cost registry (observe/cost.py injects these at the custom
# -- call instructions) ------------------------------------------------
#
# Dense-equivalent convention (see flash_attention.py): flops the
# composed projection+CE would compute once, backward recompute of z
# NOT credited.  For N tokens, D hidden, V vocab:
#   fwd: z = h @ W                 -> 2*N*D*V
#   bwd: dh = dz W^T, dW = h^T dz  -> 4*N*D*V
# Per-logit constants cover the softmax/CE elementwise work as XLA
# counts it in the dense composition (measured: ~4.0 flops/logit fwd,
# ~3.0 bwd; exp lands under transcendentals in both accountings).
_CE_FWD_PER_LOGIT = 4.0
_CE_BWD_PER_LOGIT = 3.0


def _ce_dims(operand_shapes):
    (n, d) = operand_shapes[0][0]
    v = operand_shapes[1][0][1]
    return n, d, v


def _io_bytes(operand_shapes, result_shapes):
    total = 0
    for dims, elem in list(operand_shapes) + list(result_shapes):
        count = 1
        for d in dims:
            count *= d
        total += count * elem
    return float(total)


def vocab_ce_fwd_cost(operand_shapes, result_shapes):
    n, d, v = _ce_dims(operand_shapes)
    flops = n * v * (2.0 * d + _CE_FWD_PER_LOGIT)
    return flops, _io_bytes(operand_shapes, result_shapes)


def vocab_ce_dh_cost(operand_shapes, result_shapes):
    n, d, v = _ce_dims(operand_shapes)
    flops = n * v * (2.0 * d + 2.0 / 3.0 * _CE_BWD_PER_LOGIT)
    return flops, _io_bytes(operand_shapes, result_shapes)


def vocab_ce_dw_cost(operand_shapes, result_shapes):
    n, d, v = _ce_dims(operand_shapes)
    flops = n * v * (2.0 * d + 1.0 / 3.0 * _CE_BWD_PER_LOGIT)
    return flops, _io_bytes(operand_shapes, result_shapes)


def vocab_ce_cost(n_tokens, d, v, dtype_bytes=4):
    """Dense-equivalent (flops, bytes) of one fwd+bwd fused vocab-CE —
    the sum of the three kernels' registry entries (test/parity
    helper)."""
    h = ((n_tokens, d), dtype_bytes)
    w = ((d, v), dtype_bytes)
    lbl = ((1, n_tokens), 4)
    row = ((1, n_tokens), 4)
    stat = ((8, n_tokens), 4)
    fwd = vocab_ce_fwd_cost([h, w, lbl], [stat, stat, stat])
    dh = vocab_ce_dh_cost([h, w, lbl, row, row], [h])
    dw = vocab_ce_dw_cost([h, w, lbl, row, row], [w])
    return (fwd[0] + dh[0] + dw[0], fwd[1] + dh[1] + dw[1])


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("vocab_ce_fwd", vocab_ce_fwd_cost)
    register_kernel_cost("vocab_ce_dh", vocab_ce_dh_cost)
    register_kernel_cost("vocab_ce_dw", vocab_ce_dw_cost)


_register_costs()


def _pallas_call(*args, **kw):
    from . import pallas_call  # shared interpret gate (package init)

    return pallas_call(*args, **kw)


def _z_block(h_ref, w_ref, vb, block_v, n_valid_v):
    """(block_t, block_v) logits for this tile, invalid vocab columns
    masked to NEG; returns (z, col_ids, valid_mask)."""
    z = jax.lax.dot_general(
        h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    col = vb * block_v + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    valid = col < n_valid_v
    return jnp.where(valid, z, NEG), col, valid


def _row_valid(tb, block_t, n_valid_t, shape):
    row = tb * block_t + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return row < n_valid_t


def _fwd_kernel(h_ref, w_ref, lbl_ref, lse_ref, zt_ref, zsum_ref,
                m_scr, s_scr, zt_scr, zsum_scr, *, block_v, n_valid_v):
    from jax.experimental import pallas as pl

    vb = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG)
        s_scr[:] = jnp.zeros_like(s_scr)
        zt_scr[:] = jnp.full_like(zt_scr, NEG)
        zsum_scr[:] = jnp.zeros_like(zsum_scr)

    z, col, valid = _z_block(h_ref, w_ref, vb, block_v, n_valid_v)
    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(z, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    s_scr[:] = s_scr[:] * alpha + jnp.sum(jnp.exp(z - m_new), axis=1,
                                          keepdims=True)
    m_scr[:] = m_new
    zsum_scr[:] = zsum_scr[:] + jnp.sum(jnp.where(valid, z, 0.0),
                                        axis=1, keepdims=True)
    hit = col == lbl_ref[...].reshape(-1, 1)
    zt_scr[:] = jnp.maximum(
        zt_scr[:], jnp.max(jnp.where(hit, z, NEG), axis=1,
                           keepdims=True))

    @pl.when(vb == nv - 1)
    def _fin():
        # stats replicated over 8 sublanes (same trick as
        # flash_attention's lse): a (1, n) output would carry a
        # degenerate T(1,128) sublane-1 layout that XLA:TPU stack-
        # allocates in scoped VMEM with 8x tile padding — the r05
        # on-chip compile failed with a scoped-vmem OOM on exactly
        # those three output buffers, at ANY block size
        lse = (m_scr[:] + jnp.log(s_scr[:]))[:, 0][None, :]
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)
        zt_ref[...] = jnp.broadcast_to(zt_scr[:][:, 0][None, :],
                                       zt_ref.shape)
        zsum_ref[...] = jnp.broadcast_to(zsum_scr[:][:, 0][None, :],
                                         zsum_ref.shape)


def _dz_block(h_ref, w_ref, lbl_ref, lse_ref, g_ref, tb, vb, *,
              block_t, block_v, n_valid_t, n_valid_v, eps):
    """Recomputed upstream-scaled logit gradient for this tile; padded
    token rows and vocab columns contribute exactly zero."""
    z, col, valid = _z_block(h_ref, w_ref, vb, block_v, n_valid_v)
    p = jnp.where(valid, jnp.exp(z - lse_ref[...].reshape(-1, 1)), 0.0)
    onehot = (col == lbl_ref[...].reshape(-1, 1)).astype(jnp.float32)
    dz = g_ref[...].reshape(-1, 1) * (
        p - (1.0 - eps) * onehot
        - jnp.where(valid, eps / n_valid_v, 0.0))
    rows_ok = _row_valid(tb, block_t, n_valid_t, dz.shape)
    return jnp.where(rows_ok, dz, 0.0)


def _bwd_dh_kernel(h_ref, w_ref, lbl_ref, lse_ref, g_ref, dh_ref,
                   dh_scr, *, block_t, block_v, n_valid_t, n_valid_v,
                   eps):
    from jax.experimental import pallas as pl

    tb = pl.program_id(0)
    vb = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vb == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)

    dz = _dz_block(h_ref, w_ref, lbl_ref, lse_ref, g_ref, tb, vb,
                   block_t=block_t, block_v=block_v,
                   n_valid_t=n_valid_t, n_valid_v=n_valid_v, eps=eps)
    # the vocab tail block's padded W columns are undefined memory; dz
    # is zero there but 0 * NaN poisons the contraction — zero them
    w = w_ref[...]
    col = vb * block_v + jax.lax.broadcasted_iota(jnp.int32,
                                                  (1, w.shape[1]), 1)
    w = jnp.where(col < n_valid_v, w, 0)
    dh_scr[:] += jax.lax.dot_general(
        dz.astype(w.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(vb == nv - 1)
    def _fin():
        dh_ref[...] = dh_scr[:].astype(dh_ref.dtype)


def _bwd_dw_kernel(h_ref, w_ref, lbl_ref, lse_ref, g_ref, dw_ref,
                   dw_scr, *, block_t, block_v, n_valid_t, n_valid_v,
                   eps):
    from jax.experimental import pallas as pl

    vb = pl.program_id(0)
    tb = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(tb == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)

    dz = _dz_block(h_ref, w_ref, lbl_ref, lse_ref, g_ref, tb, vb,
                   block_t=block_t, block_v=block_v,
                   n_valid_t=n_valid_t, n_valid_v=n_valid_v, eps=eps)
    # padded token rows of h are undefined memory; dz is zero there so
    # zero the h rows too before the contraction (0 * NaN poisons)
    h = h_ref[...]
    rows_ok = _row_valid(tb, block_t, n_valid_t, (h.shape[0], 1))
    h = jnp.where(rows_ok, h, 0)
    dw_scr[:] += jax.lax.dot_general(
        h, dz.astype(h.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(tb == nt - 1)
    def _fin():
        dw_ref[...] = dw_scr[:].astype(dw_ref.dtype)


def _fwd(h, w, labels, block_t, block_v):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = h.shape
    v = w.shape[1]
    block_t = min(block_t, n)
    block_v = min(block_v, v)
    grid = (pl.cdiv(n, block_t), pl.cdiv(v, block_v))
    lse, zt, zsum = _pallas_call(
        functools.partial(_fwd_kernel, block_v=block_v, n_valid_v=v),
        name="vocab_ce_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d), lambda t, vb: (t, 0)),
            pl.BlockSpec((d, block_v), lambda t, vb: (0, vb)),
            pl.BlockSpec((1, block_t), lambda t, vb: (0, t)),
        ],
        out_specs=[
            pl.BlockSpec((8, block_t), lambda t, vb: (0, t)),
            pl.BlockSpec((8, block_t), lambda t, vb: (0, t)),
            pl.BlockSpec((8, block_t), lambda t, vb: (0, t)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((8, n), jnp.float32),
            jax.ShapeDtypeStruct((8, n), jnp.float32),
            jax.ShapeDtypeStruct((8, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_t, 1), jnp.float32)] * 4,
    )(h, w, labels.astype(jnp.int32).reshape(1, -1))
    return lse[0], zt[0], zsum[0]


def _bwd(h, w, labels, lse, g, eps, block_t, block_v):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = h.shape
    v = w.shape[1]
    block_t = min(block_t, n)
    block_v = min(block_v, v)
    lbl = labels.astype(jnp.int32).reshape(1, -1)
    lse2 = lse.reshape(1, -1)
    g2 = g.astype(jnp.float32).reshape(1, -1)
    common = dict(block_t=block_t, block_v=block_v, n_valid_t=n,
                  n_valid_v=v, eps=eps)
    dh = _pallas_call(
        functools.partial(_bwd_dh_kernel, **common),
        name="vocab_ce_dh",
        grid=(pl.cdiv(n, block_t), pl.cdiv(v, block_v)),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda t, vb: (t, 0)),
            pl.BlockSpec((d, block_v), lambda t, vb: (0, vb)),
            pl.BlockSpec((1, block_t), lambda t, vb: (0, t)),
            pl.BlockSpec((1, block_t), lambda t, vb: (0, t)),
            pl.BlockSpec((1, block_t), lambda t, vb: (0, t)),
        ],
        out_specs=pl.BlockSpec((block_t, d), lambda t, vb: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), h.dtype),
        scratch_shapes=[pltpu.VMEM((block_t, d), jnp.float32)],
    )(h, w, lbl, lse2, g2)
    dw = _pallas_call(
        functools.partial(_bwd_dw_kernel, **common),
        name="vocab_ce_dw",
        grid=(pl.cdiv(v, block_v), pl.cdiv(n, block_t)),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda vb, t: (t, 0)),
            pl.BlockSpec((d, block_v), lambda vb, t: (0, vb)),
            pl.BlockSpec((1, block_t), lambda vb, t: (0, t)),
            pl.BlockSpec((1, block_t), lambda vb, t: (0, t)),
            pl.BlockSpec((1, block_t), lambda vb, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((d, block_v), lambda vb, t: (0, vb)),
        out_shape=jax.ShapeDtypeStruct((d, v), w.dtype),
        scratch_shapes=[pltpu.VMEM((d, block_v), jnp.float32)],
    )(h, w, lbl, lse2, g2)
    return dh, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_ce(h, w, labels, eps, block_t, block_v):
    lse, zt, zsum = _fwd(h, w, labels, block_t, block_v)
    v = w.shape[1]
    return lse - (1.0 - eps) * zt - (eps / v) * zsum


def _vjp_fwd(h, w, labels, eps, block_t, block_v):
    lse, zt, zsum = _fwd(h, w, labels, block_t, block_v)
    v = w.shape[1]
    loss = lse - (1.0 - eps) * zt - (eps / v) * zsum
    return loss, (h, w, labels, lse)


def _vjp_bwd(eps, block_t, block_v, res, g):
    h, w, labels, lse = res
    dh, dw = _bwd(h, w, labels, lse, g, eps, block_t, block_v)
    return dh, dw, None


_fused_ce.defvjp(_vjp_fwd, _vjp_bwd)


def fused_vocab_ce(hidden, weight, labels, epsilon=0.0,
                   block_t=DEFAULT_BLOCK_T, block_v=DEFAULT_BLOCK_V):
    """Per-token label-smoothed CE of `hidden @ weight` logits without
    materializing them.

    hidden: (..., D) activations (flattened internally); weight (D, V);
    labels (...) int token ids aligned with hidden's leading dims.
    Returns per-token loss with hidden's leading shape.  Differentiable
    w.r.t. hidden and weight (labels get no gradient)."""
    lead = hidden.shape[:-1]
    d = hidden.shape[-1]
    h2 = hidden.reshape(-1, d)
    lbl = labels.reshape(-1)
    if lbl.shape[0] != h2.shape[0]:
        raise ValueError(
            f"fused_vocab_ce: {h2.shape[0]} tokens but "
            f"{lbl.shape[0]} labels")
    # out-of-range labels clamp into [0, V) exactly like the non-fused
    # path's take_along_axis (mode='clip'); without this an invalid id
    # would leave the running target-logit at NEG and surface as a ~1e30
    # loss only on the fused path — a data bug must not look like a
    # backend bug
    lbl = jnp.clip(lbl, 0, weight.shape[1] - 1)
    loss = _fused_ce(h2, weight, lbl, float(epsilon), int(block_t),
                     int(block_v))
    return loss.reshape(lead)
