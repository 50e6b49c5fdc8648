"""Blocked fused LSTM recurrence kernel (Pallas, TPU).

The scan-bound story (a stacked LSTM through `lax.scan` runs nowhere
near any roofline): it lowers one XLA while-iteration per timestep, so
every step pays loop bookkeeping, an HBM round-trip for the (N, H)
carry, and a dynamic-slice/dynamic-update-slice pair on the stacked
(T, ...) tensors — the per-step recurrent GEMM (N×H @ H×4H) is far too
small to hide any of it.  The reference framework ships fused
recurrence as first-class capability (`fusion_lstm` / `fusion_gru` /
`cudnn_lstm`, paddle/fluid/operators/fused/fusion_lstm_op.cc); this
kernel is the TPU analog, with the same blocked-kernel discipline as
ops/pallas/flash_attention.py:

- ONE grid step covers a whole block of T_BLOCK timesteps: the carry
  (h, c) lives in f32 VMEM scratch across the entire sequence (grid
  steps run sequentially on a TPU core, so scratch persists), the
  x-slab for the block streams HBM→VMEM once, and the small recurrent
  GEMM fuses with the gate elementwise per step — no per-step HBM
  carry traffic, no while-loop bookkeeping.
- seq_len masking freezes the carry past each row's end (identical
  semantics to the scan path in ops/rnn.py); `is_reverse` is handled
  by flipping the time axis outside and adjusting the validity
  predicate for the zero-padded tail inside.
- custom VJP: the backward re-runs the gate math per block from the
  saved (h, c) sequences (flash-attention-style recompute — the
  (N, T, 4H) gate tensor is never materialized in HBM), accumulating
  dW in VMEM scratch and carrying (dh, dc) backward through the grid.

Gate layout matches ops/rnn.py `dynamic_lstm` exactly:
[candidate, input, forget, output] with sigmoid gates / tanh cell and
candidate.  Peepholes, nested (lod2) inputs, and non-default
activations are rejected LOUDLY (the backward derivatives are
hand-derived for sigmoid/tanh) — callers fall back to the scan path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Time-block default — lives ONLY here (CLAUDE.md VMEM lesson: a stale
# fallback at a call site silently overrides a retune).  Untuned on a
# chip: retune here, nowhere else.
DEFAULT_BLOCK_T = 4

# Mosaic gives a kernel 16 MiB of scoped VMEM unless told otherwise.
# The backward at the model width (N=128, H=512, f32) takes 27 MiB at
# block_t=4 by the compiler's count (x and dx slabs are N*4H*4B = 1 MiB
# per timestep each, W / dW / the dW accumulator 4 MiB each), so both
# kernels raise the limit — a limit reserves nothing — to half of a
# v5e core's 128 MiB.  What does not fit, Mosaic refuses at compile.
_VMEM_LIMIT = 64 << 20


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


# -- kernel cost registry (observe/cost.py injects these at the custom
# -- call instructions) ------------------------------------------------
#
# Dense-equivalent convention (flash_attention.py precedent): the flop
# count of the logical math the scan composition computes ONCE —
# backward gate recompute is NOT credited.  Per timestep, N rows, H
# hidden:
#   fwd: gates = h @ W            -> 2*N*H*4H
#   bwd: dh = dg W^T, dW += h^T dg -> 4*N*H*4H
# Per-cell constants cover the gate elementwise work as XLA counts it
# in the scan composition (adds/muls/selects; sigmoid/tanh land under
# transcendentals in both accountings).
_LSTM_FWD_PER_CELL = 10.0
_LSTM_BWD_PER_CELL = 22.0


def _lstm_dims(operand_shapes):
    (t, n, g4) = operand_shapes[0][0]
    return t, n, g4 // 4, g4


def lstm_fwd_cost(operand_shapes, result_shapes):
    t, n, h, g4 = _lstm_dims(operand_shapes)
    flops = t * n * (2.0 * h * g4 + _LSTM_FWD_PER_CELL * h)
    return flops, None  # bytes: default materialized-buffers model


def lstm_bwd_cost(operand_shapes, result_shapes):
    t, n, h, g4 = _lstm_dims(operand_shapes)
    flops = t * n * (4.0 * h * g4 + _LSTM_BWD_PER_CELL * h)
    return flops, None


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("lstm_fwd", lstm_fwd_cost)
    register_kernel_cost("lstm_bwd", lstm_bwd_cost)


_register_costs()


def _pallas_call(*args, **kw):
    from . import pallas_call  # shared interpret gate (package init)

    return pallas_call(*args, **kw)


def _valid(tidx, sl, t_true, rev):
    """(N, 1) mask: does original timestep `tidx` advance row state?
    Work domain is the (possibly flipped, zero-padded-to-block) time
    axis; `sl` is (N, 1) int32.  Padded tail steps (tidx >= t_true)
    must freeze the carry in BOTH directions or h_last drifts."""
    if rev:
        # work step tidx is original step (t_true - 1 - tidx)
        return jnp.logical_and(tidx < t_true, (t_true - 1 - tidx) < sl)
    return tidx < jnp.minimum(sl, t_true)


def _split_gates(gates):
    h = gates.shape[1] // 4
    # dynamic_lstm layout (lstm_op.cc): candidate, input, forget, output
    return (gates[:, :h], gates[:, h:2 * h], gates[:, 2 * h:3 * h],
            gates[:, 3 * h:])


def _fwd_kernel(x_ref, w_ref, h0_ref, c0_ref, sl_ref, hs_ref, cs_ref,
                h_scr, c_scr, *, block_t, t_true, rev):
    from jax.experimental import pallas as pl

    tb = pl.program_id(0)

    @pl.when(tb == 0)
    def _init():
        h_scr[:] = h0_ref[...].astype(jnp.float32)
        c_scr[:] = c0_ref[...].astype(jnp.float32)

    w = w_ref[...]
    sl = sl_ref[...]  # (N, 1) int32
    for k in range(block_t):  # static unroll: all indexing stays static
        tidx = tb * block_t + k
        h, c = h_scr[:], c_scr[:]
        gates = x_ref[k].astype(jnp.float32) + jax.lax.dot_general(
            h.astype(w.dtype), w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        cand, ig, fg, og = _split_gates(gates)
        i = jax.nn.sigmoid(ig)
        f = jax.nn.sigmoid(fg)
        c_new = f * c + i * jnp.tanh(cand)
        h_new = jax.nn.sigmoid(og) * jnp.tanh(c_new)
        ok = _valid(tidx, sl, t_true, rev)
        h_scr[:] = jnp.where(ok, h_new, h)
        c_scr[:] = jnp.where(ok, c_new, c)
        hs_ref[k] = h_scr[:].astype(hs_ref.dtype)
        cs_ref[k] = c_scr[:].astype(cs_ref.dtype)


def _bwd_kernel(x_ref, w_ref, hp_ref, cp_ref, sl_ref, dhs_ref, dcs_ref,
                dx_ref, dw_ref, dh0_ref, dc0_ref,
                dh_scr, dc_scr, dw_scr, *, block_t, t_true, rev):
    from jax.experimental import pallas as pl

    g = pl.program_id(0)
    ng = pl.num_programs(0)
    tb = ng - 1 - g  # grid runs time blocks in REVERSE

    @pl.when(g == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dc_scr[:] = jnp.zeros_like(dc_scr)
        dw_scr[:] = jnp.zeros_like(dw_scr)

    w = w_ref[...]
    sl = sl_ref[...]
    for k in range(block_t - 1, -1, -1):
        tidx = tb * block_t + k
        x_t = x_ref[k].astype(jnp.float32)
        h_prev = hp_ref[k].astype(jnp.float32)
        c_prev = cp_ref[k].astype(jnp.float32)
        # recompute the gates for this step (never stored in HBM)
        gates = x_t + jax.lax.dot_general(
            h_prev.astype(w.dtype), w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        cand, ig, fg, og = _split_gates(gates)
        i = jax.nn.sigmoid(ig)
        f = jax.nn.sigmoid(fg)
        o = jax.nn.sigmoid(og)
        ca = jnp.tanh(cand)
        c_new = f * c_prev + i * ca
        tc = jnp.tanh(c_new)

        dh_tot = dhs_ref[k].astype(jnp.float32) + dh_scr[:]
        # a frozen row's h_out is h_prev itself: its dh must NOT fold
        # into the cell cotangent through o*tanh'(c)
        dc_pass = dcs_ref[k].astype(jnp.float32) + dc_scr[:]
        dc_tot = dc_pass + dh_tot * o * (1.0 - tc * tc)
        dpre_o = (dh_tot * tc) * o * (1.0 - o)
        dpre_i = (dc_tot * ca) * i * (1.0 - i)
        dpre_f = (dc_tot * c_prev) * f * (1.0 - f)
        dpre_c = (dc_tot * i) * (1.0 - ca * ca)
        dg = jnp.concatenate([dpre_c, dpre_i, dpre_f, dpre_o], axis=1)
        ok = _valid(tidx, sl, t_true, rev)
        # frozen steps pass state (and its cotangent) straight through
        dg = jnp.where(ok, dg, 0.0)
        dx_ref[k] = dg.astype(dx_ref.dtype)
        dh_prev = jax.lax.dot_general(
            dg.astype(w.dtype), w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dh_scr[:] = jnp.where(ok, dh_prev, dh_tot)
        dc_scr[:] = jnp.where(ok, dc_tot * f, dc_pass)
        # dg rows are already zeroed for frozen/padded steps, so their
        # h_prev rows contribute nothing to dW
        dw_scr[:] += jax.lax.dot_general(
            h_prev, dg, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(g == ng - 1)
    def _fin():
        dh0_ref[...] = dh_scr[:].astype(dh0_ref.dtype)
        dc0_ref[...] = dc_scr[:].astype(dc0_ref.dtype)
        dw_ref[...] = dw_scr[:].astype(dw_ref.dtype)


def _fwd_call(xs, w, h0, c0, sl, t_true, rev, block_t):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_pad, n, g4 = xs.shape
    h_dim = g4 // 4
    grid = (t_pad // block_t,)
    return _pallas_call(
        functools.partial(_fwd_kernel, block_t=block_t, t_true=t_true,
                          rev=rev),
        name="lstm_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, n, g4), lambda tb: (tb, 0, 0)),
            pl.BlockSpec((h_dim, g4), lambda tb: (0, 0)),
            pl.BlockSpec((n, h_dim), lambda tb: (0, 0)),
            pl.BlockSpec((n, h_dim), lambda tb: (0, 0)),
            pl.BlockSpec((n, 1), lambda tb: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, n, h_dim), lambda tb: (tb, 0, 0)),
            pl.BlockSpec((block_t, n, h_dim), lambda tb: (tb, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_pad, n, h_dim), xs.dtype),
            jax.ShapeDtypeStruct((t_pad, n, h_dim), xs.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((n, h_dim), jnp.float32)] * 2,
        compiler_params=_compiler_params(),
    )(xs, w, h0, c0, sl)


def _bwd_call(xs, w, hp, cp, sl, dhs, dcs, t_true, rev, block_t):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_pad, n, g4 = xs.shape
    h_dim = g4 // 4
    nt = t_pad // block_t

    def tblock(g):
        return (nt - 1 - g, 0, 0)

    return _pallas_call(
        functools.partial(_bwd_kernel, block_t=block_t, t_true=t_true,
                          rev=rev),
        name="lstm_bwd",
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((block_t, n, g4), tblock),
            pl.BlockSpec((h_dim, g4), lambda g: (0, 0)),
            pl.BlockSpec((block_t, n, h_dim), tblock),
            pl.BlockSpec((block_t, n, h_dim), tblock),
            pl.BlockSpec((n, 1), lambda g: (0, 0)),
            pl.BlockSpec((block_t, n, h_dim), tblock),
            pl.BlockSpec((block_t, n, h_dim), tblock),
        ],
        out_specs=[
            pl.BlockSpec((block_t, n, g4), tblock),
            pl.BlockSpec((h_dim, g4), lambda g: (0, 0)),
            pl.BlockSpec((n, h_dim), lambda g: (0, 0)),
            pl.BlockSpec((n, h_dim), lambda g: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_pad, n, g4), xs.dtype),
            jax.ShapeDtypeStruct((h_dim, g4), w.dtype),
            jax.ShapeDtypeStruct((n, h_dim), hp.dtype),
            jax.ShapeDtypeStruct((n, h_dim), cp.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, h_dim), jnp.float32),
            pltpu.VMEM((n, h_dim), jnp.float32),
            pltpu.VMEM((h_dim, g4), jnp.float32),
        ],
        compiler_params=_compiler_params(),
    )(xs, w, hp, cp, sl, dhs, dcs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _lstm(xs, w, h0, c0, sl, t_true, rev, block_t):
    return _fwd_call(xs, w, h0, c0, sl, t_true, rev, block_t)


def _lstm_vjp_fwd(xs, w, h0, c0, sl, t_true, rev, block_t):
    hs, cs = _fwd_call(xs, w, h0, c0, sl, t_true, rev, block_t)
    return (hs, cs), (xs, w, h0, c0, sl, hs, cs)


def _lstm_vjp_bwd(t_true, rev, block_t, res, cts):
    xs, w, h0, c0, sl, hs, cs = res
    dhs, dcs = cts
    # per-step previous states from the saved sequences (padded tail
    # entries hold the frozen carry — finite, and their dg is masked)
    hp = jnp.concatenate([h0[None].astype(hs.dtype), hs[:-1]], axis=0)
    cp = jnp.concatenate([c0[None].astype(cs.dtype), cs[:-1]], axis=0)
    dxs, dw, dh0, dc0 = _bwd_call(xs, w, hp, cp, sl,
                                  dhs.astype(hs.dtype),
                                  dcs.astype(cs.dtype),
                                  t_true, rev, block_t)
    return dxs, dw, dh0.astype(h0.dtype), dc0.astype(c0.dtype), None


_lstm.defvjp(_lstm_vjp_fwd, _lstm_vjp_bwd)


def fused_lstm(x, w, h0=None, c0=None, seq_len=None, *,
               is_reverse=False, use_peepholes=False,
               gate_activation="sigmoid", cell_activation="tanh",
               candidate_activation="tanh", block_t=None):
    """Fused multi-timestep LSTM over a pre-projected, bias-added input.

    x: (N, T, 4H) — `x @ W_x + b` done by the caller (the dynamic_lstm
    contract); w: (H, 4H) recurrent weights; h0/c0: optional (N, H)
    initial states; seq_len: optional (N,) int lengths (state freezes
    past each row's end, matching the scan path bit-for-bit semantics).

    Returns (hidden (N, T, H), cell (N, T, H), last_h (N, H),
    last_c (N, H)).  Differentiable w.r.t. x, w, h0, c0 via a custom
    VJP that recomputes gates per time block.
    """
    if use_peepholes:
        raise ValueError(
            "fused_lstm (Pallas recurrence kernel) does not support "
            "peepholes — use the scan path (use_pallas=False)")
    acts = (gate_activation, cell_activation, candidate_activation)
    if acts != ("sigmoid", "tanh", "tanh"):
        raise ValueError(
            f"fused_lstm supports only (sigmoid, tanh, tanh) "
            f"activations, got {acts} — the fused backward derivatives "
            f"are hand-derived; use the scan path (use_pallas=False)")
    n, t, g4 = x.shape
    if g4 % 4:
        raise ValueError(f"fused_lstm: input width {g4} is not 4*H")
    h_dim = g4 // 4
    block_t = DEFAULT_BLOCK_T if block_t is None else int(block_t)
    block_t = max(1, min(block_t, t))
    if h0 is None:
        h0 = jnp.zeros((n, h_dim), x.dtype)
    if c0 is None:
        c0 = jnp.zeros((n, h_dim), x.dtype)
    sl = (seq_len if seq_len is not None
          else jnp.full((n,), t, jnp.int32))
    sl = sl.astype(jnp.int32).reshape(n, 1)

    xs = jnp.swapaxes(x, 0, 1)  # (T, N, 4H) time-major
    if is_reverse:
        xs = jnp.flip(xs, axis=0)
    t_pad = -(-t // block_t) * block_t
    if t_pad != t:
        xs = jnp.pad(xs, ((0, t_pad - t), (0, 0), (0, 0)))
    hs, cs = _lstm(xs, w, h0, c0, sl, t, bool(is_reverse),
                   int(block_t))
    hs, cs = hs[:t], cs[:t]
    # the carry freezes past seq ends, so the last work-domain step IS
    # the final state (identical to the scan path's final carry)
    h_last, c_last = hs[-1], cs[-1]
    if is_reverse:
        hs = jnp.flip(hs, axis=0)
        cs = jnp.flip(cs, axis=0)
    return (jnp.swapaxes(hs, 0, 1), jnp.swapaxes(cs, 0, 1),
            h_last, c_last)
