"""Tiled flash-attention forward AND backward kernels (Pallas, TPU).

Online-softmax attention: never materializes the (Tq, Tk) score matrix in
HBM — q-blocks stream k/v-blocks through VMEM keeping running max /
normalizer / accumulator (the standard flash algorithm).  This is the
modern TPU equivalent of the LoD no-padding efficiency story
(SURVEY.md §5.7): padding positions are masked via an additive key bias.

The backward is tiled too, recomputing p = exp(s - lse) from the saved
logsumexp: end-to-end O(T) memory, so long-context training never
materializes the score matrix.  Score blocks are kept in (k, q)
orientation in the backward so the per-q lse/delta vectors broadcast
along the TPU lane dimension (no transposes in-kernel).  delta =
rowsum(do*o) is recomputed in-kernel from the o/do tiles (cheap
elementwise per block) instead of a separate XLA reduction, so NOTHING
but the q/k/v/o/do/lse buffers crosses the kernel boundary.  Every dot
takes q, k, v, do in the dtype they arrive in (p and ds cast to it) and
accumulates in float32, as flash_gqa.py and flash_mla.py do; scores,
soft-max and delta are float32.

It is ONE kernel where the call allows it (`_bwd_kernel`, PR 37): p and
ds once a block pair, dq, dk and dv from them: five score-sized matmuls
where two kernels run seven, the soft-max's vector work once.  dk / dv
sum over the query blocks and dq over the key blocks, which no grid
order visits consecutively for both, so one side is held full-length in
float32 VMEM.  Layout (A) was taken: the dk / dv kernel's grid (N*H, k
blocks, q blocks), dk and dv block accumulators as before, and dq of
the head's whole sequence in scratch (nq, block_q, d), 4 * d bytes a
position (2 MiB at 4096 x 128).  Each dq block leaves for HBM on the
step that completes it (its last key block: the diagonal's when
causal, the last pass otherwise); the output's index map moves on only
then, so Pallas never writes a half-summed block back and no partial of
dq reaches HBM.  Layout (B), query blocks outside with dk and dv of the
head full-length (twice the scratch, whole-head output blocks written
in one burst at the head's last step), compiled too and was 6-11%
slower alone at every block size tried on the chip (PERF.md, PR 37).

What goes where is decided by the CALL ALONE (`fused_backward_fits`; no
option, attribute, environment variable or config key):

- the single kernel: self-attention (T_q == T_k) over whole blocks,
  causal or not, no bias, no position offsets, no returned logsumexp,
  the dq accumulator within `FUSED_ACCUMULATOR_BUDGET`, 48 MiB since
  PR 54 (98304 positions at d_head 128; a band call, which holds dq,
  dk and dv, 12 * d_head bytes a position: 32768 positions at d_head
  128, 16384 at d_head 256, the widest call a cell makes: a
  `full_attention` layer of 16 / 2 heads).  Both layouts; any d_head
  the forward takes.  What a decoder layer asks, and Ulysses
  attention's local call;
- the two kernels (`flash_dkv` + `flash_dq`, each recomputing s and
  dp): a key-padding bias (its db is a third result of the dk / dv
  kernel), ring attention's calls (dynamic offsets: the step that
  completes a dq block is not known when the grid is laid out; and a
  logsumexp cotangent), cross-attention, a ragged last block, a longer
  sequence.

Both paths take every term from the same `_bwd_p_ds` and add it in
the same order: the same bits out (tests/test_flash_attention.py).  The
single kernel is passed to `pallas_call` under the name `flash_dkv`: it
is that kernel grown by dq's dot, its registered cost grows by dq's
work when the call has three gradient results, and the benchmark's
closed list of kernels (`benchmarks/kernel_counts.py FLASH_KERNELS`)
knows that name; `flash_dq` exists on the two-kernel path only.
`observe.monitoring` counts the backward passes traced as
`flash_attention_backward_fused` / `_split`.

The backward's blocks are its own, 1024 x 1024 (`DEFAULT_BWD_BLOCK_*`;
the forward keeps 256 x 1024): alone on the chip the single kernel took
1.34 ms a call at 4096 x 16 heads against 1.40 at 512 x 1024, 1.53 at
256 x 1024 and 2.69 for the parent's two kernels; the band kernel at
16384 x 16 / 2 heads of 256, the budget's edge, 33.1 ms a call against
33.5 at 512 x 1024, 34.6 at 1024 x 512, 36.2 at 512 x 512 and 45.6 for
the two kernels (PERF.md, PR 54).  Their float32 score
blocks pass Mosaic's default 16 MiB of scoped VMEM (17.35 MiB), so such
a call names a limit (`_vmem_params`: only where blocks and accumulator
need it; a call that fits names none).

Under a WINDOW the forward is not online at all where the band is
short (PR 60, `_flash_fwd_whole_band`): a query tile's whole band is
the window's W - 1 earlier keys and the tile's own, 1023 keys at W =
b = 512, and its float32 scores fit VMEM (2 MiB).  One grid step is
then a query tile against its WHOLE band: scores, mask, max, exp, sum
and the value product once, no running statistics, no rescale, no
init / finalize predicates; a key/value head's whole group of query
heads a step.  The online grid paid the soft-max's row-wise work
(lane reductions, (b, 1) statistics, the accumulator's rescale) once
a VISIT, two visits a row, and ran at 32 % of the MXU passes it
executes; the whole-band step at 72-77 % (PERF.md, PR 60).  The
call's shapes alone choose (`whole_band_forward_fits`: a window of
1025 keys at the most); the residuals (`o`, `lse8`), the kernel's name
and its declared cost are the same, so the backward kernels see the
same inputs.

WITHOUT a window a band call (grouped key/value heads over the whole
causal prefix: `flash_fwd`, `flash_dkv`, past the budget `flash_dq`
too) walks a scalar-prefetched LIST OF VISITS (PR 63, the section "a
grid of visits" below; `flash_block_diffusion.py` walks the same
shells over its own geometry): a grid step a tile that holds a score,
136 a head at 16384 rows where the rectangle took 256, and the 120
tiles wholly under the diagonal (`FULL`) computed with no iota, compare
or select.  Same arithmetic in the same order; a window's grids (two
or three tiles a run, none empty) and the plain calls (`band is None`:
a bias, traced offsets, ragged blocks, a returned logsumexp, cross
lengths, no causal mask; their diagonal may move with a traced offset,
so no host-made table fits) keep their rectangles.

Two operand layouts, selected by `layout=`:

- "nhtd" (historical): q/k/v arrive (N, H, T, D) and are folded to
  (N*H, T, D) by a free reshape.
- "nthd" (head-major end-to-end, ISSUE 8): q/k/v arrive (N, T, H*D)
  head-grouped — EXACTLY what a (D_model -> H*D) projection emits — and
  the batch*head fold happens in the GRID instead of the data: block
  index maps pick head g%H of batch g//H out of the grouped minor dim.
  No transpose ever exists in the program; the per-head (T, D) slab is
  a strided DMA.  The kernel tile shapes are IDENTICAL to the folded
  layout ((block, d) tiles), so the Mosaic lowering is the proven one.

The additive key-padding bias stays (N, 1, 1, Tk) — one row per batch,
never repeated per head (the index map reuses row g//H); its gradient
is summed over heads outside the kernel.

Ring-attention support (parallel/ring_attention.py): the kernel takes
dynamic global position offsets (SMEM scalars) so causal masking works
across rotated k/v chunks, and can return the per-row logsumexp whose
cotangent folds into the backward as ds = p*(dp - (delta - dlse)).

Supported bias: additive key-padding bias broadcastable as (N, 1, 1, Tk),
plus in-kernel causal masking.  Richer biases fall back to the XLA
composition in ops/attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import interpret, keep_residuals

# Tuned on v5e (seq 2048, d 128): q=256/k=1024 beats the XLA-composed
# attention; both dims are clamped to the actual sequence length.
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 1024
# the backward pass's own blocks (the statistics are block-free, so it
# need not take the forward's)
DEFAULT_BWD_BLOCK_Q = 1024
DEFAULT_BWD_BLOCK_K = 1024
# The band kernels' own (a window, grouped key/value heads), tuned on
# v5e at 16384 positions, 32 / 4 heads of 128, alone (PERF.md, PR 38).
# Their forward takes 1024 x 1024: 18.9 ms a call over the whole prefix
# against 28.6 at the 256 x 1024 above and 21.9 at 512 x 1024; the
# online soft-max under a window of 1024 keys 4.78 ms against 6.50,
# 5.26 and 6.20 at 512 x 512, though half of what it then visits is
# masked (a window that narrow takes the whole-band step below since
# PR 60; one of 1026 keys or more keeps these tiles).  The window's backward
# takes 512 x 512 (7.19 ms; 8.52 at 1024 x 1024, 8.26 at 256 x 512),
# the backward over the whole prefix the 1024 x 1024 above (36.7).
DEFAULT_BAND_BLOCK_Q = 1024
DEFAULT_BAND_BLOCK_K = 1024
DEFAULT_WINDOW_BWD_BLOCK_Q = 512
DEFAULT_WINDOW_BWD_BLOCK_K = 512
# the smallest forward tile a narrow window is given
# (`_window_fwd_blocks`)
MIN_WINDOW_FWD_BLOCK = 256
# The window forward's whole-band step (`_flash_fwd_whole_band`, PR 60):
# wherever the float32 scores of a query tile's whole band fit the
# budget (3 MiB: 512 x 1536, a window of 1025 keys at the most), the
# tile is 512 and a grid step is a key/value head's whole group against
# the band.  Alone on v5e at 16384 positions, bf16 (PERF.md, PR 60), ms
# a call forward: under 512 keys at 64 / 8 heads of 128 3.63 (3.73 at
# 256 x 768 keys, fewer entries and twice the steps; a head a step
# 3.86; the scores in (k, q) orientation 4.41) against 8.69 for the
# online soft-max over 512 x 512 tiles (9.47 at 1024 x 1024, 14.69 at
# 256 x 256: PR 51); under 1024 keys at 32 / 4 heads 2.90 (3.61 at
# 1024 x 2048 keys, which also needs a VMEM limit) against 4.80 for
# 1024 x 1024 tiles and 6.24 for 512 x 512; at 8192 positions, 40 / 20
# heads, 512 keys 1.27 against 2.75.  A wider window keeps the online
# soft-max over 1024 x 1024 tiles
WHOLE_BAND_FWD_BLOCK = 512
WHOLE_BAND_SCORE_BUDGET = 3 << 20
# The block-diffusion mask's square tiles (`flash_block_diffusion.py`),
# forward and backward, tuned on v5e at 2 x 8192 rows, 32 / 4 heads of
# 128, blocks of 4, alone: on the list of visits 1024 x 1024 takes 10.1
# ms forward and 28.6 forward + backward, 512 x 512 18.1 and 39.4
# (PERF.md, PR 59; on the rectangles of PR 47 11.2 / 33.7 and 19.4 /
# 47.7, 256 x 256 45.7 / 109.8).  Of a head's 256 tiles of 1024 x 1024
# 80 hold an allowed pair, 16 of them half a mask and 8 a diagonal of
# 128 x 128 squares (of 1024 tiles of 512 x 512: 288, 32 and 16: fewer
# pairs computed, and three and a half times the grid steps)
DEFAULT_DIFFUSION_BLOCK = 1024
DEFAULT_DIFFUSION_BWD_BLOCK = 1024
NEG_INF = -1e30
# The single backward kernel holds one head's dq, a whole sequence of
# float32 (4 * d bytes a position); a band call's holds dk and dv of
# the key/value head beside it (12 * d).  They may take this much of
# v5e's 128 MiB of VMEM; a longer sequence goes to the two kernels,
# which hold blocks only.  48 MiB is what Mosaic was ASKED for the
# widest call a cell makes (PR 54; 32 MiB before): the band kernel at
# 16384 x 256 (16 / 2 heads) and at 32768 x 128 (32 / 4; with and
# without a window, and under the block-diffusion mask) and the plain
# kernel at 98304 x 128 compile for a described v5e under `_VMEM_LIMIT`
# at the backward's own blocks, in bfloat16 and (the first) in float32
# at "highest" precision
# (tests/test_chip_compile_kernels.py, tests/test_chip_compile_flash.py),
# and the chip ran the first in both (PERF.md, PR 54).  Not further
# without a compile that says so: the accumulators, four float32 score
# blocks (16 MiB) and the pipeline's two buffers of every tile have to
# stay under that limit
FUSED_ACCUMULATOR_BUDGET = 48 << 20
# A backward kernel whose float32 score blocks and accumulators pass
# Mosaic's default 16 MiB of scoped VMEM claims this much instead (the
# verdict is Mosaic's: tests/test_chip_compile_flash.py); one that
# fits asks for nothing, because XLA plans and schedules a step
# differently around a call that names a limit (PERF.md, PRs 35, 37).
# "Fits": accumulator + four score blocks within _VMEM_FITS
_VMEM_LIMIT = 100 << 20
_VMEM_FITS = 10 << 20

# -- kernel cost registry (observe/cost.py injects these at the custom
# -- call instructions; tests/test_observe_cost.py holds them to the
# -- dense twin) -------------------------------------------------------
#
# Dense-equivalent convention: full Tq*Tk scores regardless of causal
# (the twin computes the masked positions too), backward recompute of
# s/p NOT credited.  Per flattened head (NH = N*H):
#   fwd:  s = q k^T and o = p v            -> 2 dots = 4*Tq*Tk*D
#   bwd:  dq, dk, dv, dp = do v^T          -> 4 dots = 8*Tq*Tk*D
# The per-score constants cover the softmax's non-transcendental
# elementwise work as XLA counts it in the dense composition
# (measured: ~8.2 flops/score fwd, ~8.1 bwd; exp is tallied under
# "transcendentals", not flops, in both accountings).
_SOFTMAX_FWD_PER_SCORE = 8.0
_SOFTMAX_BWD_PER_SCORE = 8.0


def _attn_dims(operand_shapes, stat_dims):
    """(nh, t_q, t_k, d) from the q/k operands plus the lse statistic's
    dims — (nh, 8, t_q) sublane-replicated, or the pre-r07 (nh, t_q)
    form (tolerated so old recorded protos stay analyzable).  Works for
    BOTH layouts: folded (NH, T, D) operands have nh == q.shape[0]
    (heads-per-batch 1 below), while head-major grouped (N, T, H*D)
    operands recover H = nh // N from the statistic and split the
    grouped minor dim."""
    qd = operand_shapes[0][0]
    kd = operand_shapes[1][0]
    nh, t_q = stat_dims[0], stat_dims[-1]
    heads = max(nh // max(qd[0], 1), 1)
    return nh, t_q, kd[1], qd[2] // heads


def _tensors(operand_shapes):
    """The operands without a leading table of visits (scalar prefetch,
    (9, V) int32: the band call over the whole prefix, whose kernels
    keep the plain names): q, k, v, .. as every other call has them."""
    return operand_shapes[len(operand_shapes[0][0]) == 2:]


def _io_bytes(operand_shapes, result_shapes):
    total = 0
    for dims, elem in list(operand_shapes) + list(result_shapes):
        n = 1
        for d in dims:
            n *= d
        total += n * elem
    return float(total)


def flash_fwd_cost(operand_shapes, result_shapes):
    # result_shapes[-1] is the (nh, 8, t_q) lse output
    operand_shapes = _tensors(operand_shapes)
    nh, t_q, t_k, d = _attn_dims(operand_shapes, result_shapes[-1][0])
    flops = nh * t_q * t_k * (4.0 * d + _SOFTMAX_FWD_PER_SCORE)
    return flops, _io_bytes(operand_shapes, result_shapes)


def _dq_flops(operand_shapes):
    nh, t_q, t_k, d = _attn_dims(operand_shapes, operand_shapes[5][0])
    return nh * t_q * t_k * (2.0 * d + 0.375 * _SOFTMAX_BWD_PER_SCORE)


def flash_dkv_cost(operand_shapes, result_shapes):
    # carries dk + dv + the shared dp dot (dense-equivalent split with
    # flash_dq_cost: together they sum to the dense backward's 4 dots).
    # operand_shapes[5] is the (nh, 8, t_q) lse input.  The kernel of
    # this name that emits dq too (the single backward kernel: three
    # gradients out, where a bias's third result is a (nh, t_k, 1)
    # column) does dq's work as well.
    operand_shapes = _tensors(operand_shapes)
    nh, t_q, t_k, d = _attn_dims(operand_shapes, operand_shapes[5][0])
    flops = nh * t_q * t_k * (6.0 * d + 0.625 * _SOFTMAX_BWD_PER_SCORE)
    if len(result_shapes) == 3 and result_shapes[2][0] == result_shapes[1][0]:
        flops += _dq_flops(operand_shapes)
    return flops, _io_bytes(operand_shapes, result_shapes)


def flash_dq_cost(operand_shapes, result_shapes):
    operand_shapes = _tensors(operand_shapes)
    return _dq_flops(operand_shapes), _io_bytes(operand_shapes, result_shapes)


def attention_cost(nh, t_q, t_k, d, dtype_bytes=4):
    """Dense-equivalent (flops, bytes) of one fwd+bwd flash attention —
    the sum of the three kernels' registry entries (test/parity
    helper; q/k/v/do/o assumed dtype_bytes wide, lse f32)."""
    q = ((nh, t_q, d), dtype_bytes)
    k = ((nh, t_k, d), dtype_bytes)
    stat = ((nh, 8, t_q), 4)
    fwd = flash_fwd_cost([q, k, k], [q, stat])
    dkv = flash_dkv_cost([q, k, k, q, q, stat], [k, k])
    dq = flash_dq_cost([q, k, k, q, q, stat], [q])
    return (fwd[0] + dkv[0] + dq[0], fwd[1] + dkv[1] + dq[1])


def _register_costs():
    from . import DECLARED_AT_CALL, register_kernel_cost

    register_kernel_cost("flash_fwd", flash_fwd_cost)
    register_kernel_cost("flash_dkv", flash_dkv_cost)
    register_kernel_cost("flash_dq", flash_dq_cost)
    # a call with a window or under the block-diffusion mask
    # (`flash_block_diffusion.py`): the band's pairs (`_Band.cost_estimate`)
    for kernel in ("fwd", "dkv", "dq"):
        register_kernel_cost("flash_window_" + kernel, DECLARED_AT_CALL)
        register_kernel_cost("flash_block_diffusion_" + kernel,
                             DECLARED_AT_CALL)


_register_costs()


def _pallas_call(*args, **kw):
    from . import pallas_call  # shared interpret gate (package init)

    return pallas_call(*args, **kw)


def _vmem_params(accumulator_bytes, block_q, block_k):
    """`pallas_call` keywords of a backward kernel: a VMEM limit where
    its float32 score blocks (Mosaic holds some four of them: 17.35 MiB
    scoped at 1024 x 1024 with a 2 MiB accumulator) and its
    accumulators come near the default, nothing where they fit."""
    from jax.experimental.pallas import tpu as pltpu

    if accumulator_bytes + 4 * 4 * block_q * block_k <= _VMEM_FITS:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=_VMEM_LIMIT)}


def _fwd_vmem_params(block_q, block_k, d, itemsize):
    """`pallas_call` keywords of a forward kernel, by the same rule of
    thumb: its q, o, k, v tiles twice over (the pipeline's two
    buffers), its float32 accumulator and two score blocks.  Past
    Mosaic's default 16 MiB it claims the limit: float32 operands at
    d_head 256 in 1024 x 1024 blocks (17 MiB by this count, 27.5 by
    Mosaic's: the parity scripts' "highest" run of a grouped call; the
    chip refused it unasked, PR 44).  bfloat16 at d_head 256 (13 MiB)
    and every call at d_head 128 fit and ask for nothing, so their
    steps are scheduled as they were."""
    from jax.experimental.pallas import tpu as pltpu

    tiles = 2 * 2 * (block_q + block_k) * d * itemsize
    if tiles + 4 * block_q * d + 2 * 4 * block_q * block_k <= 16 << 20:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=_VMEM_LIMIT)}


def _offs(offs_ref):
    """(q_off, k_off) global position offsets from the SMEM scalar input
    (zero when no offsets were passed)."""
    if offs_ref is None:
        return 0, 0
    return offs_ref[0, 0], offs_ref[0, 1]


def _tile(ref):
    """The (block, d) tile of a q/k/v/o/do ref — both layouts block
    these operands as (1, block, d); the leading dim is squeezed."""
    return ref[0]


# -- block-spec factories ---------------------------------------------------
#
# One grid for both layouts: (N*H, time blocks, time blocks).  The
# difference is ONLY where a (1, block, d) tile lives in the array:
# folded (NH, T, D) indexes (g, t, 0); head-major grouped (N, T, H*D)
# indexes (g // H, t, g % H) — the block unit of the minor dim is d, so
# block index g % H lands on head g % H's d-slice.  lse/delta stay in
# the folded (NH, 8, T) form in both layouts (kernel-internal
# statistics, never touching the model's activation layout).

def _tile_spec(block, d, layout, h, tsel, group=1):
    """BlockSpec for a (1, block, d) q/k/v/o/do tile; `tsel` maps the
    non-head grid axes (a, b) to the time block index.  `group` > 1
    (head-major): the tile of the key/value head that query head g % h
    reads, (g % h) // group."""
    from jax.experimental import pallas as pl

    if group != 1:
        return pl.BlockSpec(
            (1, block, d),
            lambda g, a, b: (g // h, tsel(a, b), (g % h) // group))
    if layout == "nthd":
        return pl.BlockSpec((1, block, d),
                            lambda g, a, b: (g // h, tsel(a, b), g % h))
    return pl.BlockSpec((1, block, d),
                        lambda g, a, b: (g, tsel(a, b), 0))


def _stat_spec(block_q, tsel):
    from jax.experimental import pallas as pl

    return pl.BlockSpec((1, 8, block_q),
                        lambda g, a, b: (g, 0, tsel(a, b)))


# -- forward ----------------------------------------------------------------

def _softmax_step(s, v_rows, m_scr, l_scr, acc_scr, at=slice(None)):
    """One score block `s` (queries, keys) into the running max, sum and
    accumulator of query rows `at`; `v_rows()` loads the value rows."""
    m_prev = m_scr[at]                # (block_q, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)            # (block_q, block_k)
    alpha = jnp.exp(m_prev - m_new)   # (block_q, 1)
    l_new = alpha * l_scr[at] + jnp.sum(p, axis=1, keepdims=True)
    vv = v_rows()
    acc_scr[at] = acc_scr[at] * alpha + jax.lax.dot_general(
        p.astype(vv.dtype), vv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[at] = m_new
    l_scr[at] = l_new


def _init_softmax(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _write_o_lse(o_ref, lse_ref, m_scr, l_scr, acc_scr):
    l = jnp.maximum(l_scr[:], 1e-30)
    o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
    # lse replicated over 8 sublanes to satisfy TPU tiling of the
    # (nh, 8, t_q) output layout
    lse = (m_scr[:] + jnp.log(l))[:, 0]
    lse_ref[0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, offs_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                t_k, band=None):
    from jax.experimental import pallas as pl

    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    pl.when(kb == 0)(functools.partial(_init_softmax, m_scr, l_scr, acc_scr))

    qb = pl.program_id(1)
    q_off, k_off = _offs(offs_ref)
    step = kb
    if band is None:
        # causal: skip k-blocks strictly above the (offset) diagonal
        run = (q_off + (qb + 1) * block_q > k_off + kb * block_k) \
            if causal else True
    else:
        # the grid's last axis counts the key blocks of the band
        kb = band.key_at(qb, step)
        run = band.key_runs(qb, step, kb)

    @pl.when(run)
    def _compute():
        q = _tile(q_ref)                  # (block_q, d)
        k = _tile(k_ref)                  # (block_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)

        # Always mask k-positions past the true sequence length: when
        # t_k % block_k != 0 the last k-block is padded and its garbage
        # columns would otherwise corrupt the online softmax and lse.
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < t_k
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = valid & (q_off + q_pos >= k_off + k_pos)
            if band is not None and band.window:
                valid = valid & (q_pos - k_pos < band.window)
        s = jnp.where(valid, s, NEG_INF)

        def v_rows():
            # Zero padded v-rows: block padding is undefined memory and
            # 0 * NaN would poison the accumulator even though p==0 there.
            rows = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)
            return jnp.where(rows < t_k, _tile(v_ref), 0)

        _softmax_step(s, v_rows, m_scr, l_scr, acc_scr)

    pl.when(step == nk - 1)(functools.partial(
        _write_o_lse, o_ref, lse_ref, m_scr, l_scr, acc_scr))


def _fwd_dims(q, k, layout, n_head):
    if layout == "nthd":
        n, t_q, hd = q.shape
        return n * n_head, t_q, k.shape[1], hd // n_head
    nh, t_q, d = q.shape
    return nh, t_q, k.shape[1], d


def _flash_fwd(q, k, v, bias, offsets, scale, causal, block_q, block_k,
               layout, n_head, band=None, group=1):
    """`band` (a causal self-attention call over whole blocks with a
    window, or grouped key/value heads): the grid's last axis runs over
    the key blocks of a query block's band alone, and the k / v index
    maps stop at the diagonal's block, so a block outside the band
    costs no compute and no DMA."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nh, t_q, t_k, d = _fwd_dims(q, k, layout, n_head)
    h = n_head
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_k)
    if band is None:
        grid = (nh, pl.cdiv(t_q, block_q), pl.cdiv(t_k, block_k))
        k_time = lambda a, b: b     # noqa: E731
    else:
        grid = (nh, band.nq, band.k_steps)
        k_time = band.k_time

    in_specs = [
        _tile_spec(block_q, d, layout, h, lambda a, b: a),
        _tile_spec(block_k, d, layout, h, k_time, group),
        _tile_spec(block_k, d, layout, h, k_time, group),
    ]
    args = [q, k, v]
    has_bias = bias is not None
    has_offs = offsets is not None
    if has_bias:
        # bias is (N, 1, 1, Tk): one row per BATCH, the index map fans
        # it out over heads — no per-head repeat ever materializes
        in_specs.append(
            pl.BlockSpec((1, 1, 1, block_k),
                         lambda g, a, b: (g // h, 0, 0, b)))
        args.append(bias)
    if has_offs:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(offsets)

    def kern(*refs):
        n_in = 3 + has_bias + has_offs
        ins, outs = refs[:n_in], refs[n_in:]
        q_r, k_r, v_r = ins[:3]
        b_r = ins[3] if has_bias else None
        of_r = ins[3 + has_bias] if has_offs else None
        _fwd_kernel(q_r, k_r, v_r, b_r, of_r, *outs, scale=scale,
                    causal=causal, block_q=block_q, block_k=block_k,
                    t_k=t_k, band=band)

    if layout == "nthd":
        o_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    else:
        o_shape = jax.ShapeDtypeStruct((nh, t_q, d), q.dtype)
    declared = {}
    if band is not None:
        if band.window:
            # a head's: the batch of a build-time shape inference is a
            # placeholder
            band.record_blocks()
        declared = band.declared_cost("fwd", nh, d, q.dtype.itemsize, group)
    o, lse8 = _pallas_call(
        kern,
        name=(band.prefix if band is not None else "flash_") + "fwd",
        **declared,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            _tile_spec(block_q, d, layout, h, lambda a, b: a),
            _stat_spec(block_q, lambda a, b: a),
        ],
        out_shape=[
            o_shape,
            jax.ShapeDtypeStruct((nh, 8, t_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        **_fwd_vmem_params(block_q, block_k, d, q.dtype.itemsize),
    )(*args)
    return o, lse8


# -- backward kernels -------------------------------------------------------
#
# Standard flash backward math, recomputing p from the saved lse:
#   p  = exp(s - lse);      dv = p^T do;       dp = do v^T
#   ds = p * (dp - delta),  delta = rowsum(do * o) - dlse
#   dq = scale * ds k;      dk = scale * ds^T q;   db = sum_q ds
# Score blocks are held transposed, sT: (block_k, block_q), so the per-q
# vectors (lse, delta) broadcast along lanes.  delta is recomputed from
# the o/do tiles in-kernel (elementwise, cheap) so no (NH, T) statistic
# has to be produced by XLA between the kernels.  Every dot takes its
# operands in the dtype they arrive in (p and ds cast to it) and
# accumulates in float32, as flash_gqa.py and flash_mla.py do; scores,
# soft-max and delta are float32.

def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                               preferred_element_type=jnp.float32)


def _row_clean(ref, base, limit, block):
    """Load a (block, d) tile zeroing rows at absolute position >= limit
    (undefined padding of the final block)."""
    x = _tile(ref)
    if limit % block == 0:      # whole blocks: nothing to zero
        return x
    rows = base + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    return jnp.where(rows < limit, x, 0)


def _delta_row(do, o, dlse_ref):
    """(1, block_q) float32 delta = rowsum(do * o) [- dlse], recomputed
    from the already-cleaned tiles.  dlse arrives 8-sublane-stored with
    only row 0 populated (the public wrapper slices lse8[:, 0, :]), so
    the sublane SUM recovers it."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=1)[None, :]
    if dlse_ref is not None:
        delta = delta - jnp.sum(dlse_ref[0], axis=0)[None, :]
    return delta


def _runs(offs_ref, kb, qb, *, causal, block_q, block_k, **_):
    """Whether block pair (kb, qb) holds a score: a causal key block
    sees no query block wholly above the (offset) diagonal."""
    if not causal:
        return True
    q_off, k_off = _offs(offs_ref)
    return q_off + (qb + 1) * block_q > k_off + kb * block_k


def _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref,
              bias_ref, offs_ref, kb, qb, *, scale, causal, block_q,
              block_k, t_q, t_k, window=None, mask=None):
    """(q, k, do, p, ds) of block pair (kb, qb): the tiles with their
    padding zeroed, and the float32 (block_k, block_q) p and ds every
    gradient is a dot of.  All three backward kernels take their terms
    from here.

    Invalid (padded) score positions are masked via `valid`, never
    letting undefined block padding reach an accumulator (0 * NaN
    poisons).  ds is d(loss)/d(s_with_bias): unscaled — the q/k grads
    multiply by `scale` at their accumulation (chain rule through
    s = scale*qk^T), while the bias grad uses ds directly."""
    q = _row_clean(q_ref, qb * block_q, t_q, block_q)
    do = _row_clean(do_ref, qb * block_q, t_q, block_q)
    o = _row_clean(o_ref, qb * block_q, t_q, block_q)
    k = _row_clean(k_ref, kb * block_k, t_k, block_k)
    sT = _dot(k, q, ((1,), (1,))) * scale
    if bias_ref is not None:    # a (block_k, 1) column over the lanes
        sT = sT + bias_ref[0].astype(jnp.float32)
    if mask is not None:
        # the block-diffusion kernels' own, over whole blocks: what its
        # visit's kind leaves of the tile (nothing to mask: [])
        valid = mask
    else:
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0)
        q_pos = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1)
        q_off, k_off = _offs(offs_ref)
        # a sequence of whole blocks has no padding to mask (static)
        valid = [k_pos < t_k] * bool(t_k % block_k) \
            + [q_pos < t_q] * bool(t_q % block_q) \
            + [q_off + q_pos >= k_off + k_pos] * bool(causal)
        if window:
            valid.append(q_pos - k_pos < window)
    p = jnp.exp(sT - lse_ref[0, 0][None, :])
    ds = p * (_dot(_tile(v_ref), do, ((1,), (1,)))
              - _delta_row(do, o, dlse_ref))
    if valid:
        valid = functools.reduce(jnp.logical_and, valid)
        p, ds = jnp.where(valid, p, 0.0), jnp.where(valid, ds, 0.0)
    return q, k, do, p, ds


def _add_dk_dv(p, ds, q, do, dk_scr, dv_scr, scale, at=slice(None)):
    """The block pair's part of dk and dv into their float32 sums."""
    dv_scr[at] += _dot(p.astype(do.dtype), do, ((1,), (0,)))
    dk_scr[at] += scale * _dot(ds.astype(q.dtype), q, ((1,), (0,)))


def _add_dq(ds, k, dq_scr, scale, at=slice(None)):
    """The block pair's part of dq into its float32 sum `dq_scr[at]`:
    dq[q, d] = scale * sum_k ds[k, q] * k[k, d]."""
    dq_scr[at] += scale * _dot(ds.astype(k.dtype), k, ((0,), (0,)))


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                    dlse_ref, bias_ref, offs_ref, dk_ref, dv_ref, db_ref,
                    dk_scr, dv_scr, db_scr, **dims):
    from jax.experimental import pallas as pl

    kb = pl.program_id(1)
    qb = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        if db_scr is not None:
            db_scr[:] = jnp.zeros_like(db_scr)

    @pl.when(_runs(offs_ref, kb, qb, **dims))
    def _compute():
        q, _, do, p, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref,
            bias_ref, offs_ref, kb, qb, **dims)
        _add_dk_dv(p, ds, q, do, dk_scr, dv_scr, dims["scale"])
        if db_scr is not None:
            db_scr[:] += jnp.sum(ds, axis=1, keepdims=True)

    @pl.when(qb == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)
        if db_ref is not None:
            db_ref[0] = db_scr[:].astype(db_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                   dlse_ref, bias_ref, offs_ref, dq_ref, dq_scr, **dims):
    from jax.experimental import pallas as pl

    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_runs(offs_ref, kb, qb, **dims))
    def _compute():
        _, k, _, _, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref,
            bias_ref, offs_ref, kb, qb, **dims)
        _add_dq(ds, k, dq_scr, dims["scale"])

    @pl.when(kb == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
                dk_ref, dv_ref, dq_acc, dk_scr, dv_scr, *, last_k, **dims):
    """The whole backward pass, grid (NH, kb, qb): p and ds once a block
    pair, dq, dk and dv from them, each added as the kernel that holds
    blocks only adds it.  dk and dv sum over the query blocks, the
    inner axis, in block scratch; dq sums over the key blocks in
    `dq_acc` (nq, block_q, d), the head's whole sequence, each block
    leaving for HBM on the step that completes it (`last_k`)."""
    from jax.experimental import pallas as pl

    kb = pl.program_id(1)
    qb = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(kb == 0)       # every query block meets key block 0, first
    def _init_dq():
        dq_acc[qb] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

    @pl.when(_runs(None, kb, qb, **dims))
    def _compute():
        q, k, do, p, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, None, None, None,
            kb, qb, **dims)
        _add_dk_dv(p, ds, q, do, dk_scr, dv_scr, dims["scale"])
        _add_dq(ds, k, dq_acc, dims["scale"], at=qb)

    @pl.when(qb == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(kb == last_k(qb))
    def _finalize_dq():
        dq_ref[0] = dq_acc[qb].astype(dq_ref.dtype)


def fused_backward_fits(t_q, t_k, d, block_q, block_k, *, bias=False,
                        offsets=False, lse_cotangent=False):
    """Whether the backward pass of a call is the single kernel: from
    the call alone, never from an option.  It takes self-attention over
    whole blocks with nothing beside q, k, v (causal or not): the step
    that completes a dq block must be known when the grid is laid out
    (no dynamic offsets), and a bias's gradient, a logsumexp cotangent
    and the masks of a ragged last block stay with the two kernels.
    Its float32 dq of one head's whole sequence, 4 * d bytes a
    position, must fit the budget."""
    plain = not (bias or offsets or lse_cotangent)
    whole = (t_q == t_k and t_q % min(block_q, t_q) == 0
             and t_k % min(block_k, t_k) == 0)
    return plain and whole and t_q * d * 4 <= FUSED_ACCUMULATOR_BUDGET


def _flash_bwd(q, k, v, bias, offsets, o, lse8, do, dlse8, scale, causal,
               block_q, block_k, layout, n_head):
    from ...observe.monitoring import runtime_stats

    nh, t_q, t_k, d = _fwd_dims(q, k, layout, n_head)
    fused = fused_backward_fits(
        t_q, t_k, d, block_q, block_k, bias=bias is not None,
        offsets=offsets is not None, lse_cotangent=dlse8 is not None)
    runtime_stats.record_flash_backward("flash_attention", fused)
    if fused:
        return _flash_bwd_fused(q, k, v, o, lse8, do, scale, causal,
                                block_q, block_k, layout, n_head) + (None,)
    return _flash_bwd_split(q, k, v, bias, offsets, o, lse8, do, dlse8,
                            scale, causal, block_q, block_k, layout, n_head)


def _grad_shapes(q, k, layout, nh, t_q, t_k, d):
    """(dq, dk) shapes; dv is dk's."""
    if layout == "nthd":
        return (jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, q.dtype))
    return (jax.ShapeDtypeStruct((nh, t_q, d), q.dtype),
            jax.ShapeDtypeStruct((nh, t_k, d), q.dtype))


def _flash_bwd_fused(q, k, v, o, lse8, do, scale, causal, block_q, block_k,
                     layout, n_head):
    """dq, dk and dv by ONE kernel, named `flash_dkv`: it is that kernel
    grown by dq's dot, and the name is the one the benchmark's closed
    list of kernels knows.  Grid (NH, kb, qb), the dk / dv kernel's."""
    from jax.experimental.pallas import tpu as pltpu

    nh, t, _, d = _fwd_dims(q, k, layout, n_head)
    h = n_head
    block_q, block_k = min(block_q, t), min(block_k, t)
    nq, nk = t // block_q, t // block_k

    # The key block that completes query block qb, and the first query
    # block key block kb (or a later one) completes: the diagonal's
    # when causal, the last pass otherwise
    def last_k(qb):
        return ((qb + 1) * block_q - 1) // block_k if causal else nk - 1

    def first_q(kb):
        if causal:
            return (kb * block_k) // block_q
        return jnp.where(kb >= nk, nq, 0)

    def q_time(kb, qb):     # a skipped block pair fetches nothing new
        return jnp.maximum(qb, first_q(kb))

    def dq_time(kb, qb):
        # the query block last completed, or being completed: the
        # blocks before first_q(kb) met their last key block in an
        # earlier pass, those before first_q(kb + 1) meet it in this
        # one.  The index moves on only on the step that writes the
        # next block, so no half-summed block is ever what Pallas
        # writes back
        done = jnp.minimum(jnp.maximum(qb, first_q(kb) - 1),
                           first_q(kb + 1) - 1)
        return jnp.maximum(done, 0)

    q_spec = _tile_spec(block_q, d, layout, h, q_time)
    kv_spec = _tile_spec(block_k, d, layout, h, lambda kb, qb: kb)
    dq_shape, dk_shape = _grad_shapes(q, k, layout, nh, t, t, d)
    kern = functools.partial(
        _bwd_kernel, last_k=last_k, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, t_q=t, t_k=t)
    return tuple(_pallas_call(
        kern,
        name="flash_dkv",
        grid=(nh, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec,
                  _stat_spec(block_q, q_time)],
        out_specs=[_tile_spec(block_q, d, layout, h, dq_time), kv_spec,
                   kv_spec],
        out_shape=[dq_shape, dk_shape, dk_shape],
        scratch_shapes=[pltpu.VMEM((nq, block_q, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        **_vmem_params(nq * block_q * d * 4, block_q, block_k),
    )(q, k, v, do, o, lse8))


def _flash_bwd_split(q, k, v, bias, offsets, o, lse8, do, dlse8, scale,
                     causal, block_q, block_k, layout, n_head):
    """dk / dv (and db) and dq by a kernel each: every score twice."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nh, t_q, t_k, d = _fwd_dims(q, k, layout, n_head)
    h = n_head
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_k)
    nq = pl.cdiv(t_q, block_q)
    nk = pl.cdiv(t_k, block_k)
    dims = dict(scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, t_q=t_q, t_k=t_k)
    vmem = _vmem_params(0, block_q, block_k)

    # bias arrives (N, 1, 1, t_k); kernels want it as a (block_k, 1)
    # column so it broadcasts over the lane (q) dimension
    bias_t = None if bias is None else \
        bias.reshape(bias.shape[0], t_k, 1)
    has_bias = bias_t is not None
    has_dlse = dlse8 is not None
    has_offs = offsets is not None

    def specs(order):
        """order: 'kq' → grid (g, kb, qb); 'qk' → grid (g, qb, kb)."""
        if order == "kq":
            q_t = lambda a, b: b     # noqa: E731
            k_t = lambda a, b: a     # noqa: E731
        else:
            q_t = lambda a, b: a     # noqa: E731
            k_t = lambda a, b: b     # noqa: E731
        sp = [
            _tile_spec(block_q, d, layout, h, q_t),
            _tile_spec(block_k, d, layout, h, k_t),
            _tile_spec(block_k, d, layout, h, k_t),
            _tile_spec(block_q, d, layout, h, q_t),   # do
            _tile_spec(block_q, d, layout, h, q_t),   # o
            _stat_spec(block_q, q_t),                 # lse8
        ]
        if has_dlse:
            sp.append(_stat_spec(block_q, q_t))
        if has_bias:
            sp.append(pl.BlockSpec((1, block_k, 1),
                                   lambda g, a, b: (g // h, k_t(a, b), 0)))
        if has_offs:
            sp.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        return sp

    args = [q, k, v, do, o, lse8]
    if has_dlse:
        args.append(dlse8)
    if has_bias:
        args.append(bias_t)
    if has_offs:
        args.append(offsets)
    n_in = 6 + has_dlse + has_bias + has_offs

    def unpack(refs):
        ins = refs[:n_in]
        i = 6
        dl_r = b_r = of_r = None
        if has_dlse:
            dl_r = ins[i]
            i += 1
        if has_bias:
            b_r = ins[i]
            i += 1
        if has_offs:
            of_r = ins[i]
        return ins[:6], dl_r, b_r, of_r, refs[n_in:]

    def grad_spec(block, tsel):
        return _tile_spec(block, d, layout, h, tsel)

    dq_shape, dk_shape = _grad_shapes(q, k, layout, nh, t_q, t_k, d)

    # dk/dv (+db): grid (g, kb, qb), accumulate over q-blocks
    def dkv_kern(*refs):
        (q_r, k_r, v_r, do_r, o_r, lse_r), dl_r, b_r, of_r, rest = \
            unpack(refs)
        if has_bias:
            dk_r, dv_r, db_r, dk_s, dv_s, db_s = rest
        else:
            dk_r, dv_r, dk_s, dv_s = rest
            db_r = db_s = None
        _bwd_dkv_kernel(q_r, k_r, v_r, do_r, o_r, lse_r, dl_r, b_r, of_r,
                        dk_r, dv_r, db_r, dk_s, dv_s, db_s, **dims)

    kq_out_specs = [grad_spec(block_k, lambda a, b: a),
                    grad_spec(block_k, lambda a, b: a)]
    kq_out_shape = [dk_shape, dk_shape]
    kq_scratch = [
        pltpu.VMEM((block_k, d), jnp.float32),
        pltpu.VMEM((block_k, d), jnp.float32),
    ]
    if has_bias:
        # db stays PER-HEAD (NH, t_k, 1) — grid dim 0 revisits of a
        # shared (N, ...) block would not be consecutive, so the
        # head-sum happens outside (a tiny reduce, not a layout op)
        kq_out_specs.append(
            pl.BlockSpec((1, block_k, 1), lambda g, a, b: (g, a, 0)))
        kq_out_shape.append(
            jax.ShapeDtypeStruct((nh, t_k, 1), jnp.float32))
        kq_scratch.append(pltpu.VMEM((block_k, 1), jnp.float32))

    dkv_out = _pallas_call(
        dkv_kern,
        name="flash_dkv",
        grid=(nh, nk, nq),
        in_specs=specs("kq"),
        out_specs=kq_out_specs,
        out_shape=kq_out_shape,
        scratch_shapes=kq_scratch,
        **vmem,
    )(*args)
    if has_bias:
        dk, dv, db = dkv_out
        n_b = bias.shape[0]
        dbias = db.reshape(n_b, nh // n_b, t_k).sum(axis=1) \
            .reshape(n_b, 1, 1, t_k).astype(bias.dtype)
    else:
        dk, dv = dkv_out
        dbias = None

    # dq: grid (g, qb, kb), accumulate over k-blocks
    def dq_kern(*refs):
        (q_r, k_r, v_r, do_r, o_r, lse_r), dl_r, b_r, of_r, rest = \
            unpack(refs)
        dq_r, dq_s = rest
        _bwd_dq_kernel(q_r, k_r, v_r, do_r, o_r, lse_r, dl_r, b_r, of_r,
                       dq_r, dq_s, **dims)

    dq = _pallas_call(
        dq_kern,
        name="flash_dq",
        grid=(nh, nq, nk),
        in_specs=specs("qk"),
        out_specs=grad_spec(block_q, lambda a, b: a),
        out_shape=dq_shape,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        **vmem,
    )(*args)

    return dq, dk, dv, dbias


# -- the band: a window, grouped key/value heads -----------------------------
#
# A causal self-attention call, head-major, over whole blocks, in which
# query i reads keys i - window < j <= i (window None: every j <= i)
# and `group` query heads read one key/value head.  Under a window the
# grids below run over the block pairs of the band alone: an axis counts
# from the band's first block, and the index maps stop at its last, so
# a block pair outside the band costs no compute and no DMA.  Without
# one (and under the block-diffusion training mask, whose tiles lie in
# two runs: `flash_block_diffusion.py`) a rectangle would leave half its
# steps empty, and the grid walks a list of visits (further down).  k,
# v, dk and dv stay n_kv_head heads wide in HBM: query head a reads head
# a // group through the index maps, and dk / dv sum over the group in
# VMEM.

# Rows of a visit table (scalar prefetch, a column a grid step), and
# what a visit computes of its tile: all of it with no mask, or all of
# it under the mask (`flash_block_diffusion.py` has a third kind)
V_Q, V_K, V_HEAD, V_KIND, V_FIRST, V_LAST, V_DQ, V_DQ_FIRST, V_DQ_LAST = \
    range(9)
FULL, DIAGONAL = range(2)


def _hi(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.maximum(a, b)


def _lo(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.minimum(a, b)


class _Band:
    """The block geometry of such a call of `t` positions: which key
    blocks a query block meets (`first_k` .. `last_k`, the diagonal's)
    and which query blocks a key block (`first_q`, the diagonal's, ..
    `last_q`), on Python ints or on grid indices alike."""

    def __init__(self, t, block_q, block_k, window):
        self.t, self.window = t, window
        self.block_q, self.block_k = block_q, block_k
        self.nq, self.nk = t // block_q, t // block_k
        spans_k = [self.last_k(qb) - self.first_k(qb) + 1
                   for qb in range(self.nq)]
        # grid steps a query block takes over its key blocks, a key
        # block over its query blocks; and the block pairs that hold
        # an allowed score
        self.k_steps = max(spans_k)
        self.q_steps = max(self.last_q(kb) - self.first_q(kb) + 1
                           for kb in range(self.nk))
        self.blocks_allowed = sum(spans_k)

    def first_k(self, qb):
        if not self.window:
            return 0
        return _hi(qb * self.block_q - (self.window - 1), 0) // self.block_k

    def last_k(self, qb):
        return ((qb + 1) * self.block_q - 1) // self.block_k

    def first_q(self, kb):
        return (kb * self.block_k) // self.block_q

    def last_q(self, kb):
        if not self.window:
            return self.nq - 1
        return _lo(((kb + 1) * self.block_k + self.window - 2)
                   // self.block_q, self.nq - 1)

    @property
    def prefix(self):
        """What the kernels' names begin with.  A call with a window
        runs under names of its own and declares its cost at the call:
        no operand's shape says what a band allows."""
        return "flash_window_" if self.window else "flash_"

    def _shape(self):       # a static argument of the jitted passes
        return type(self), self.t, self.block_q, self.block_k, self.window

    def __eq__(self, other):
        return self._shape() == other._shape()

    def __hash__(self):
        return hash(self._shape())

    def interior(self, qb, kb):
        """Whether every pair of tile (qb, kb) is allowed: its last key
        no later than its first query, and inside the window."""
        first_q, last_q = qb * self.block_q, (qb + 1) * self.block_q - 1
        first_k, last_k = kb * self.block_k, (kb + 1) * self.block_k - 1
        return last_k <= first_q and not (
            self.window and last_q - first_k >= self.window)

    def tiles(self):
        """(query tile, key tile, kind) of every tile that holds an
        allowed pair, a query tile's in a row."""
        return [(qb, kb, FULL if self.interior(qb, kb) else DIAGONAL)
                for qb in range(self.nq)
                for kb in range(self.first_k(qb), self.last_k(qb) + 1)]

    def visits(self, key_major=False, group=1):
        """The int32 (9, V) table a grid's last axis walks, from the
        shape, on the host: query-major (the forward, `_dq`) or
        key-major (the backward; with `group` each key tile meets its
        query tiles once a head of the group, `V_HEAD`: the kernel that
        holds tiles only).  `V_FIRST` / `V_LAST` bracket the run of one
        (major tile, head), `V_DQ_FIRST` / `V_DQ_LAST` a query tile's
        visits; `V_DQ` is the dq tile the output's index map holds, the
        last one completed (before any is, the first to be): it moves
        on only on the step that writes the next tile, so no half-summed
        tile is ever what Pallas writes back."""
        rows = sorted(
            ((qb, kb, head, kind) for head in range(group)
             for qb, kb, kind in self.tiles()),
            key=lambda r: (r[1], r[2], r[0]) if key_major else r[:2])
        q, k, head, kind = np.array(rows, np.int32).T
        run = (k if key_major else q) * group + head
        first = np.r_[True, run[1:] != run[:-1]]
        at = np.arange(q.size)
        met = [np.flatnonzero(q == qb) for qb in range(self.nq)]
        dq_first = np.isin(at, [m[0] for m in met])
        dq_last = np.isin(at, [m[-1] for m in met])
        done = np.maximum.accumulate(np.where(dq_last, at, -1))
        dq = q[np.where(done < 0, np.flatnonzero(dq_last)[0], done)]
        return np.stack([q, k, head, kind, first, np.r_[first[1:], True],
                         dq, dq_first, dq_last]).astype(np.int32)

    def allowed(self, qb, kb, q_axis):
        """The mask of a tile the diagonal (or the window's edge)
        crosses, by position; queries along `q_axis` of the tile."""
        shape = (self.block_q, self.block_k) if q_axis == 0 else \
            (self.block_k, self.block_q)
        q_pos = qb * self.block_q + jax.lax.broadcasted_iota(
            jnp.int32, shape, q_axis)
        k_pos = kb * self.block_k + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1 - q_axis)
        if not self.window:
            return q_pos >= k_pos
        return (q_pos >= k_pos) & (q_pos - k_pos < self.window)

    def visit(self, visits, v, q_axis, compute):
        """Run `compute(mask, at)` as visit `v`'s kind says: rows `at`
        of the tile's two sides, under the masks in `mask` (`FULL`:
        none, so no iota, no compare and no select)."""
        from jax.experimental import pallas as pl

        kind = visits[V_KIND, v]
        pl.when(kind == FULL)(lambda: compute([], slice(None)))
        pl.when(kind == DIAGONAL)(lambda: compute(
            [self.allowed(visits[V_Q, v], visits[V_K, v], q_axis)],
            slice(None)))

    def record_visits(self):
        """One traced pass over the whole causal prefix, a head's: the
        grid steps it takes and its visits by kind."""
        from ...observe.monitoring import runtime_stats

        kinds = [kind for _, _, kind in self.tiles()]
        runtime_stats.record_flash_prefix_visits(
            len(kinds), kinds.count(FULL), kinds.count(DIAGONAL))

    def declared_cost(self, *call):
        """`cost_estimate` where the kernels' names are the band's own;
        under the plain names the registry reads the operands' shapes."""
        return {} if self.prefix == "flash_" else self.cost_estimate(*call)

    def record_blocks(self, whole_band=False):
        """The forward grid of a call with a window, a head's: the key
        tiles it visits (a whole-band step holds `k_steps` of them and
        computes every one; the tiled grid skips the compute of those
        past the diagonal), and which forward ran."""
        from ...observe.monitoring import runtime_stats

        visited = self.nq * self.k_steps
        runtime_stats.record_flash_window_blocks(visited,
                                                 self.blocks_allowed)
        runtime_stats.record_flash_window_call(
            self.pairs(),
            (visited if whole_band else self.blocks_allowed)
            * self.block_q * self.block_k)
        runtime_stats.record_flash_window_forward(whole_band)

    # A grid's last axis counts the blocks of the band: the key block
    # of a query block's `step` (`key_at`) and whether the pair holds a
    # score (`key_runs`); the same from a key block's side.
    def key_at(self, qb, step):
        return self.first_k(qb) + step

    def key_runs(self, qb, step, kb):
        return kb <= self.last_k(qb)

    def query_at(self, kb, step):
        return self.first_q(kb) + step

    def query_runs(self, kb, step, qb):
        return qb <= self.last_q(kb)

    def k_time(self, qb, step):
        """The key block of a query block's `step`: past the diagonal
        it stays there, and nothing new is fetched."""
        return _lo(self.first_k(qb) + step, self.last_k(qb))

    def q_time(self, kb, step):
        return _lo(self.first_q(kb) + step, self.last_q(kb))

    def dq_time(self, kb, step):
        """The dq block last completed, or being completed, when key
        block `kb` meets its `step`-th query block: a query block is
        complete after the diagonal's key block, so ((kb + 1) *
        block_k) // block_q of them are once pass kb ends.  The index
        moves on only on the step that writes the next block, so no
        half-summed block is ever what Pallas writes back."""
        done = ((kb + 1) * self.block_k) // self.block_q
        return _hi(_lo(self.first_q(kb) + step, done - 1), 0)

    def pairs(self):
        """Score pairs the mask allows, a head."""
        w = min(self.window or self.t, self.t)
        return w * self.t - w * (w - 1) // 2

    def cost_estimate(self, kernel, nh, d, itemsize, group):
        """`pallas_call`'s cost_estimate of a window kernel: the
        registry's convention (dense-equivalent, recomputation not
        credited) over the pairs the BAND allows, which no operand's
        shape says; `observe/cost.py` reads it off the custom call."""
        from jax.experimental import pallas as pl

        dots, soft = {"fwd": (4.0, _SOFTMAX_FWD_PER_SCORE),
                      "dq": (2.0, 0.375 * _SOFTMAX_BWD_PER_SCORE),
                      "dkv": (6.0, 0.625 * _SOFTMAX_BWD_PER_SCORE),
                      "bwd": (8.0, _SOFTMAX_BWD_PER_SCORE)}[kernel]
        tiles = {"fwd": (2, 2), "dq": (4, 2), "dkv": (3, 4),
                 "bwd": (4, 4)}[kernel]          # q-wide, k/v-wide
        pairs = nh * self.pairs()
        return {"cost_estimate": pl.CostEstimate(
            flops=int(pairs * (dots * d + soft)), transcendentals=int(pairs),
            bytes_accessed=int(nh * self.t * d * itemsize
                               * (tiles[0] + tiles[1] / group)))}


# -- the whole-band forward ---------------------------------------------------
#
# A window forward whose band is short enough for VMEM (the module
# docstring says why and what it returned; `whole_band_forward_fits`
# says when).

def _whole_band_fwd_kernel(q_ref, *refs, scale, block, tiles, window, heads,
                           d):
    """One query tile against its WHOLE band: `tiles` key / value tiles
    (the same arrays under one BlockSpec a tile: key tile qb - back of
    the step's query tile qb, clamped at 0), the scores of all of them,
    the soft-max in one pass.  `heads` query heads a step, all reading
    the step's key/value head: a `fori_loop` over the lane tiles of the
    (block, heads * d) q / o block."""
    from jax.experimental import pallas as pl

    k_refs, v_refs = refs[:tiles], refs[tiles:2 * tiles]
    o_ref, lse_ref = refs[2 * tiles:]
    qb = pl.program_id(1)
    # query position minus key position, in a tile `back` tiles before
    # the diagonal's, is back * block + rel
    shape = (block, block)
    rel = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
           - jax.lax.broadcasted_iota(jnp.int32, shape, 1))

    def scores(q, j):
        back = tiles - 1 - j
        s = _dot(q, k_refs[j][0], ((1,), (1,))) * scale
        keep = [rel >= 0] * (back == 0)         # the diagonal's: causal
        if (back + 1) * block > window:
            # the window's edge crosses the tile; a tile before the
            # sequence's first (its index clamped) keeps nothing
            edge = window - back * block
            if back:
                edge = jnp.where(qb >= back, edge, -block)
            keep.append(rel < edge)
        elif back:
            keep.append(jnp.broadcast_to(qb >= back, shape))
        return jnp.where(functools.reduce(jnp.logical_and, keep), s, NEG_INF)

    def head(i, carry):
        lanes = pl.ds(pl.multiple_of(i * d, d), d)
        q = q_ref[0, :, lanes]
        s = [scores(q, j) for j in range(tiles)]
        # every row holds its own key, so its max is a score's
        m = jnp.max(functools.reduce(jnp.maximum, s), axis=1, keepdims=True)
        p = [jnp.exp(x - m) for x in s]
        l = jnp.sum(functools.reduce(jnp.add, p), axis=1, keepdims=True)
        acc = functools.reduce(jnp.add, (
            _dot(p[j].astype(v_refs[j].dtype), v_refs[j][0], ((1,), (0,)))
            for j in range(tiles)))
        o_ref[0, :, lanes] = (acc / l).astype(o_ref.dtype)
        # lse replicated over 8 sublanes, as the tiled forward writes it
        lse = (m + jnp.log(l))[:, 0]
        lse_ref[i] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])
        return carry

    jax.lax.fori_loop(0, heads, head, 0)


def whole_band_forward_fits(window, block_q, block_k):
    """Whether the forward pass of a band call is the whole-band kernel:
    from the shape alone.  A window, square tiles, and the float32
    scores of a query tile's whole band (the window's W - 1 earlier
    keys and the tile's own, in whole key tiles) within
    `WHOLE_BAND_SCORE_BUDGET`."""
    if not window or block_q != block_k:
        return False
    tiles = -(-(window - 1) // block_k) + 1
    return 4 * block_q * tiles * block_k <= WHOLE_BAND_SCORE_BUDGET


def _flash_fwd_whole_band(q, k, v, scale, block, n_head, group, window):
    """The forward pass of a call with a window whose band fits VMEM
    (`whole_band_forward_fits`): grid (N x Hkv, query tiles), a step a
    key/value head's whole group of query heads against a query tile's
    whole band.  The q / o block is (1, block, group * d), contiguous
    lanes of the head-major layout; k and v are fetched once a group.
    Same residuals as the tiled forward (`o`, `lse8`), so the backward
    kernels see the same inputs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, t, hd = q.shape
    h, hkv, d = n_head, n_head // group, hd // n_head
    band = _Band(t, block, block, window)
    tiles = band.k_steps
    q_spec = pl.BlockSpec((1, block, group * d),
                          lambda g, qb: (g // hkv, qb, g % hkv))
    kv_specs = [
        pl.BlockSpec((1, block, d), lambda g, qb, back=tiles - 1 - j: (
            g // hkv, jnp.maximum(qb - back, 0), g % hkv))
        for j in range(tiles)]
    # the q and o blocks and the key / value tiles twice over (the
    # pipeline's two buffers) and two float32 copies of the band's
    # scores (Mosaic scopes 7.5 MiB for bfloat16 at 64 / 8 heads under
    # 512 keys, where this counts 9): near Mosaic's default 16 MiB only
    # in float32 at a group of 8 (the parity scripts' "highest" runs),
    # so no cell's step is planned around a call that names a limit
    item = q.dtype.itemsize
    vmem = {}
    if (4 * block * (group + tiles) * d * item
            + 8 * tiles * block * block) > 12 << 20:
        vmem = {"compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT)}
    band.record_blocks(whole_band=True)
    return _pallas_call(
        functools.partial(_whole_band_fwd_kernel, scale=scale, block=block,
                          tiles=tiles, window=window, heads=group, d=d),
        name=band.prefix + "fwd",
        **band.cost_estimate("fwd", n * h, d, item, group),
        grid=(n * hkv, band.nq),
        in_specs=[q_spec] + kv_specs + kv_specs,
        out_specs=[q_spec, pl.BlockSpec((group, 8, block),
                                        lambda g, qb: (g, 0, qb))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((n * h, 8, t), jnp.float32)],
        **vmem,
    )(q, *([k] * tiles), *([v] * tiles))


def band_backward_fits(t, d):
    """Whether the backward pass of a band call is the single kernel:
    from the shape alone.  It holds dq of one query head and dk, dv of
    the key/value head it reads, whole sequences of float32 (12 * d
    bytes a position: 24 MiB at 16384 x 128, 48 MiB, the budget's
    edge, at 16384 x 256)."""
    return t * d * 4 * 3 <= FUSED_ACCUMULATOR_BUDGET


def _bwd_band_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
                     dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, band, group,
                     **dims):
    """The whole backward pass of a band call, grid (N*Hkv, group, k
    blocks, query blocks of the key block's band): p and ds once a
    block pair, dq, dk and dv from them.  dq of the query head sums
    over the key blocks and dk, dv of the key/value head over the query
    blocks AND the group's query heads, which lie outside the key
    blocks (as `flash_gqa.py` sets out), so all three are held
    full-length: `dq_acc` (nq, block_q, d), each block leaving on the
    step that completes it, and `dk_acc`, `dv_acc` (nk, block_k, d),
    leaving during the group's last head."""
    from jax.experimental import pallas as pl

    gi, kb, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    qb = band.query_at(kb, step)
    run = band.query_runs(kb, step, qb)

    @pl.when((gi == 0) & (step == 0))
    def _init():
        dk_acc[kb] = jnp.zeros(dk_acc.shape[1:], dk_acc.dtype)
        dv_acc[kb] = jnp.zeros(dv_acc.shape[1:], dv_acc.dtype)

    @pl.when(run & (kb == band.first_k(qb)))
    def _init_dq():
        dq_acc[qb] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

    @pl.when(run)
    def _compute():
        q, k, do, p, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, None, None, None,
            kb, qb, **dims)
        _add_dk_dv(p, ds, q, do, dk_acc, dv_acc, dims["scale"], at=kb)
        _add_dq(ds, k, dq_acc, dims["scale"], at=qb)

    @pl.when((gi == group - 1) & (step == band.q_steps - 1))
    def _finalize():
        dk_ref[0] = dk_acc[kb].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[kb].astype(dv_ref.dtype)

    @pl.when(run & (kb == band.last_k(qb)))
    def _finalize_dq():
        dq_ref[0] = dq_acc[qb].astype(dq_ref.dtype)


def _bwd_band_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                         dk_ref, dv_ref, dk_scr, dv_scr, *, band, group,
                         **dims):
    """dk, dv of a band call past the single kernel's budget, grid
    (N*Hkv, k blocks, group, query blocks of the band): blocks only."""
    from jax.experimental import pallas as pl

    kb, gi, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    qb = band.query_at(kb, step)

    @pl.when((gi == 0) & (step == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(band.query_runs(kb, step, qb))
    def _compute():
        q, _, do, p, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, None, None, None,
            kb, qb, **dims)
        _add_dk_dv(p, ds, q, do, dk_scr, dv_scr, dims["scale"])

    @pl.when((gi == group - 1) & (step == band.q_steps - 1))
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_band_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
                        dq_scr, *, band, **dims):
    """dq of a band call past the budget, on the forward's grid."""
    from jax.experimental import pallas as pl

    qb, step = pl.program_id(1), pl.program_id(2)
    kb = band.key_at(qb, step)

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(band.key_runs(qb, step, kb))
    def _compute():
        _, k, _, _, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, None, None, None,
            kb, qb, **dims)
        _add_dq(ds, k, dq_scr, dims["scale"])

    @pl.when(step == band.k_steps - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_band(q, k, v, o, lse8, do, scale, band, n_head, group):
    """(dq, dk, dv) of a band call: one kernel where `band_backward_fits`,
    the two that hold blocks only beyond; both take every term from
    `_bwd_p_ds` and add it in the same order.  The kernels of a call
    with a window run under names of their own (`flash_window_*`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ...observe.monitoring import runtime_stats

    n, t, hd = q.shape
    h, hkv, d = n_head, n_head // group, hd // n_head
    bq, bk = band.block_q, band.block_k
    fused = band_backward_fits(t, d)
    runtime_stats.record_flash_backward("flash_attention", fused)
    dims = dict(scale=scale, causal=True, block_q=bq, block_k=bk, t_q=t,
                t_k=t, window=band.window)
    item = q.dtype.itemsize

    def name(kernel):
        return band.prefix + kernel

    def cost(kernel):
        return band.declared_cost(kernel, n * h, d, item, group)

    dq_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    dk_shape = jax.ShapeDtypeStruct(k.shape, q.dtype)

    # grid (g = batch x key/value head, .., ..): which of the last
    # three axes is the group's head, the key block and the step over
    # its query blocks differs by kernel
    def specs(order):
        def at(i):
            return lambda *ids: ids[1 + order.index(i)]

        gi, kb, step = at("g"), at("k"), at("s")

        def head(*ids):
            return (ids[0] % hkv) * group + gi(*ids)

        def q_tile(time):
            return pl.BlockSpec(
                (1, bq, d), lambda *ids: (ids[0] // hkv,
                                          time(kb(*ids), step(*ids)),
                                          head(*ids)))

        def kv_tile(time):
            return pl.BlockSpec(
                (1, bk, d), lambda *ids: (ids[0] // hkv, time(*ids),
                                          ids[0] % hkv))

        stat = pl.BlockSpec(
            (1, 8, bq), lambda *ids: (ids[0] * group + gi(*ids), 0,
                                      band.q_time(kb(*ids), step(*ids))))
        q_in, kv_in = q_tile(band.q_time), kv_tile(kb)
        return ([q_in, kv_in, kv_in, q_in, q_in, stat], q_tile, kv_tile,
                gi, kb)

    args = (q, k, v, do, o, lse8)
    if fused:
        in_specs, q_tile, kv_tile, gi, kb = specs("gks")
        kern = functools.partial(_bwd_band_kernel, band=band, group=group,
                                 **dims)
        # dk / dv leave during the group's last head, block by block
        kv_out = kv_tile(lambda *ids: jnp.where(gi(*ids) == group - 1,
                                                kb(*ids), 0))
        return tuple(_pallas_call(
            kern,
            name=name("dkv"),
            grid=(n * hkv, group, band.nk, band.q_steps),
            in_specs=in_specs,
            out_specs=[q_tile(band.dq_time), kv_out, kv_out],
            out_shape=[dq_shape, dk_shape, dk_shape],
            scratch_shapes=[pltpu.VMEM((band.nq, bq, d), jnp.float32),
                            pltpu.VMEM((band.nk, bk, d), jnp.float32),
                            pltpu.VMEM((band.nk, bk, d), jnp.float32)],
            **_vmem_params(3 * t * d * 4, bq, bk), **cost("bwd"),
        )(*args))

    vmem = _vmem_params(0, bq, bk)
    in_specs, _, kv_tile, _, kb = specs("kgs")
    dk, dv = _pallas_call(
        functools.partial(_bwd_band_dkv_kernel, band=band, group=group,
                          **dims),
        name=name("dkv"),
        grid=(n * hkv, band.nk, group, band.q_steps),
        in_specs=in_specs,
        out_specs=[kv_tile(kb), kv_tile(kb)],
        out_shape=[dk_shape, dk_shape],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        **vmem, **cost("dkv"),
    )(*args)
    # dq on the forward's grid (N*H, q blocks, key blocks of the band)
    q_spec = _tile_spec(bq, d, "nthd", h, lambda a, b: a)
    kv_spec = _tile_spec(bk, d, "nthd", h, band.k_time, group)
    dq = _pallas_call(
        functools.partial(_bwd_band_dq_kernel, band=band, **dims),
        name=name("dq"),
        grid=(n * h, band.nq, band.k_steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec,
                  _stat_spec(bq, lambda a, b: a)],
        out_specs=q_spec,
        out_shape=dq_shape,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        **vmem, **cost("dq"),
    )(*args)
    return dq, dk, dv


# -- a grid of visits ---------------------------------------------------------
#
# The band call WITHOUT a window (the whole causal prefix over grouped
# key/value heads) and the block-diffusion mask
# (`flash_block_diffusion.py`) walk a scalar-prefetched LIST OF VISITS
# on their grids' last axis (`_Band.visits`, made on the host from the
# shape when the call is traced): a column a visit, with its query
# tile, its key tile, FIRST / LAST of its run, the dq tile the output
# holds and a KIND.  A rectangle of (tiles) x (the longest run) takes
# 256 grid steps a head for the 136 tiles of a causal prefix of 16 x 16
# and masks every one as if the diagonal crossed it; the list takes
# 136, 120 of them `FULL`: no iota, no compare, no select (PERF.md, PR
# 63).  Same tile arithmetic in the same order as the rectangles
# (`_softmax_step`, `_bwd_p_ds`, `_add_dk_dv`, `_add_dq`): the same
# bits.  A pass is jitted on its shapes, its geometry and its lowering,
# so a program's layers share one trace and one lowering of it.

def _visits_fwd_kernel(visits, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_scr, l_scr, acc_scr, *, scale, band):
    """The forward pass on a list of visits, query-major: `_fwd_kernel`'s
    online soft-max, a visit a grid step."""
    from jax.experimental import pallas as pl

    v = pl.program_id(2)
    pl.when(visits[V_FIRST, v] == 1)(functools.partial(
        _init_softmax, m_scr, l_scr, acc_scr))

    def _compute(mask, at):
        s = _dot(q_ref[0, at], k_ref[0, at], ((1,), (1,))) * scale
        for allowed in mask:
            s = jnp.where(allowed, s, NEG_INF)
        _softmax_step(s, lambda: v_ref[0, at], m_scr, l_scr, acc_scr, at)

    band.visit(visits, v, 0, _compute)
    pl.when(visits[V_LAST, v] == 1)(functools.partial(
        _write_o_lse, o_ref, lse_ref, m_scr, l_scr, acc_scr))


def _visits_bwd_kernel(visits, q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                       *, band, group, fused, dq_ref=None, dk_ref=None,
                       dv_ref=None, dq_acc=None, dk_acc=None, dv_acc=None,
                       **dims):
    """The backward pass on a list of visits: p and ds once a visit
    and, of dq, dk and dv, the sums it was given.  `fused`
    (`_bwd_band_kernel`'s layout, key-major): all three, whole sequences
    in VMEM, a dq tile leaving on the visit that completes it, dk and dv
    during the group's last head.  Past that budget a kernel holds ONE
    tile of each: dk and dv key-major, the group's heads inside a key
    tile's run (`V_HEAD`), and dq query-major."""
    from jax.experimental import pallas as pl

    v = pl.program_id(2)
    gi = pl.program_id(1) + visits[V_HEAD, v]
    qb, kb = (visits[V_Q, v], visits[V_K, v]) if fused else (0, 0)

    def zero(acc, at):
        acc[at] = jnp.zeros(acc.shape[1:], acc.dtype)

    if dk_acc is not None:
        @pl.when((gi == 0) & (visits[V_FIRST, v] == 1))
        def _init():
            zero(dk_acc, kb)
            zero(dv_acc, kb)

    if dq_acc is not None:
        pl.when(visits[V_DQ_FIRST, v] == 1)(lambda: zero(dq_acc, qb))

    def _compute(mask, at):
        refs = [r.at[:, at] for r in (q_ref, k_ref, v_ref, do_ref, o_ref)]
        q, k, do, p, ds = _bwd_p_ds(
            *refs, lse_ref.at[:, :, at], None, None, None, kb, qb, mask=mask,
            **dims)
        if dk_acc is not None:
            _add_dk_dv(p, ds, q, do, dk_acc, dv_acc, dims["scale"],
                       at=(kb, at))
        if dq_acc is not None:
            _add_dq(ds, k, dq_acc, dims["scale"], at=(qb, at))

    band.visit(visits, v, 1, _compute)

    if dk_acc is not None:
        @pl.when((gi == group - 1) & (visits[V_LAST, v] == 1))
        def _finalize():
            dk_ref[0] = dk_acc[kb].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[kb].astype(dv_ref.dtype)

    if dq_acc is not None:
        @pl.when(visits[V_DQ_LAST, v] == 1)
        def _finalize_dq():
            dq_ref[0] = dq_acc[qb].astype(dq_ref.dtype)


def _visits_call(kernel, name, band, table, hkv, group, outs, scratch,
                 operands, **params):
    """One kernel over the grid (N*Hkv, the group's heads, the visits of
    `table`; a table with a `V_HEAD` holds the heads itself).  `outs`:
    (what, shape) pairs, `what` a tile at the table's `V_Q` ("q"), at
    `V_DQ` ("dq"), at `V_K` ("kv"; "kv_last": during the group's last
    head alone, when dk and dv of the single kernel leave, tile by tile)
    or the statistic ("stat")."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q = operands[0]
    bq, bk, d = band.block_q, band.block_k, q.shape[2] // (hkv * group)

    def head(g, a, v, visits):
        return (g % hkv) * group + a + visits[V_HEAD, v]

    def q_tile(row):
        return pl.BlockSpec((1, bq, d), lambda g, a, v, visits: (
            g // hkv, visits[row, v], head(g, a, v, visits)))

    def kv_tile(last):
        return pl.BlockSpec((1, bk, d), lambda g, a, v, visits: (
            g // hkv, jnp.where(a == group - 1, visits[V_K, v], 0) if last
            else visits[V_K, v], g % hkv))

    spec = {"q": q_tile(V_Q), "dq": q_tile(V_DQ), "kv": kv_tile(False),
            "kv_last": kv_tile(True),
            "stat": pl.BlockSpec((1, 8, bq), lambda g, a, v, visits: (
                g * group + a + visits[V_HEAD, v], 0, visits[V_Q, v]))}
    ins = ["q", "kv", "kv", "q", "q", "stat"][:len(operands)]
    return _pallas_call(
        kernel, name=band.prefix + name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(q.shape[0] * hkv, group // (int(table[V_HEAD].max()) + 1),
                  table.shape[1]),
            in_specs=[spec[what] for what in ins],
            out_specs=[spec[what] for what, _ in outs],
            scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                            for shape in scratch]),
        out_shape=[shape for _, shape in outs], **params,
    )(table, *operands)


# (jitted, as `grouped_matmul.py`'s kernels are: a program's layers
# share their shapes, so a pass is traced and lowered once a shape and
# called from every layer.  `interpret`, the package's gate as the
# caller reads it, keys the trace: a process that lowers the same shapes
# through the interpreter and for Mosaic keeps the two apart.  Counters
# are the callers': a jitted pass is traced once, not once a layer)
@functools.partial(jax.jit, static_argnames=("scale", "band", "n_head",
                                             "group", "interpret"))
def _flash_fwd_visits(q, k, v, *, scale, band, n_head, group, interpret):
    n, t, hd = q.shape
    bq, bk, d = band.block_q, band.block_k, hd // n_head
    item = q.dtype.itemsize
    return _visits_call(
        functools.partial(_visits_fwd_kernel, scale=scale, band=band),
        "fwd", band, band.visits(), n_head // group, group,
        [("q", jax.ShapeDtypeStruct(q.shape, q.dtype)),
         ("stat", jax.ShapeDtypeStruct((n * n_head, 8, t), jnp.float32))],
        [(bq, 1), (bq, 1), (bq, d)], (q, k, v), interpret=interpret,
        **band.declared_cost("fwd", n * n_head, d, item, group),
        **_fwd_vmem_params(bq, bk, d, item))


@functools.partial(jax.jit, static_argnames=("scale", "band", "n_head",
                                             "group", "fused", "interpret"))
def _flash_bwd_visits(q, k, v, o, lse8, do, *, scale, band, n_head, group,
                      fused, interpret):
    """(dq, dk, dv) on a list of visits: one kernel where `fused`
    (`band_backward_fits`), `_dkv` and `_dq` that hold tiles only
    beyond; every term from `_bwd_p_ds`, added in the same order."""
    n, t, hd = q.shape
    bq, bk, d = band.block_q, band.block_k, hd // n_head
    dk_shape = jax.ShapeDtypeStruct(k.shape, q.dtype)
    shape = {"dq": jax.ShapeDtypeStruct(q.shape, q.dtype), "dk": dk_shape,
             "dv": dk_shape}
    kv_held = (band.nk if fused else 1, bk, d)
    held = {"dq": (band.nq if fused else 1, bq, d), "dk": kv_held,
            "dv": kv_held}

    def call(name, cost, parts, table, accumulators):
        names = [part + kind for kind in ("_ref", "_acc") for part in parts]

        def kern(visits, *refs):
            _visits_bwd_kernel(
                visits, *refs[:6], band=band, group=group, fused=fused,
                scale=scale, causal=False, block_q=bq, block_k=bk, t_q=t,
                t_k=t, **dict(zip(names, refs[6:])))

        return _visits_call(
            kern, name, band, table, n_head // group, group,
            [("dq" if part == "dq" else "kv_last" if fused else "kv",
              shape[part]) for part in parts],
            [held[part] for part in parts],
            (q, k, v, do, o, lse8), interpret=interpret,
            **band.declared_cost(cost, n * n_head, d, q.dtype.itemsize,
                                 group),
            **_vmem_params(accumulators, bq, bk))

    if fused:
        return tuple(call("dkv", "bwd", ("dq", "dk", "dv"),
                          band.visits(key_major=True), 3 * t * d * 4))
    dk, dv = call("dkv", "dkv", ("dk", "dv"),
                  band.visits(key_major=True, group=group), 0)
    dq, = call("dq", "dq", ("dq",), band.visits(), 0)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_band(q, k, v, scale, blocks, bwd_blocks, n_head, group, window):
    return _flash_band_fwd(q, k, v, scale, blocks, bwd_blocks, n_head, group,
                           window)[0]


def _flash_band_fwd(q, k, v, scale, blocks, bwd_blocks, n_head, group,
                    window):
    from ...observe.monitoring import runtime_stats

    if whole_band_forward_fits(window, *blocks):
        o, lse8 = _flash_fwd_whole_band(q, k, v, scale, blocks[0], n_head,
                                        group, window)
    elif window:
        o, lse8 = _flash_fwd(q, k, v, None, None, scale, True, *blocks,
                             "nthd", n_head,
                             _Band(q.shape[1], *blocks, window), group)
    else:
        # the counters count the calls traced, a layer's each, whichever
        # of them the jitted pass is traced for
        if group > 1:
            runtime_stats.record_flash_grouped_call()
        band = _Band(q.shape[1], *blocks, None)
        band.record_visits()
        o, lse8 = _flash_fwd_visits(
            q, k, v, scale=scale, band=band, n_head=n_head, group=group,
            interpret=interpret())
    o, lse8 = keep_residuals(o, lse8)
    return o, (q, k, v, o, lse8)


def _flash_band_bwd(scale, blocks, bwd_blocks, n_head, group, window, res,
                    do):
    from ...observe.monitoring import runtime_stats

    q, k, v, o, lse8 = res
    band = _Band(q.shape[1], *bwd_blocks, window)
    if window:
        dq, dk, dv = _flash_bwd_band(q, k, v, o, lse8, do, scale, band,
                                     n_head, group)
    else:
        fused = band_backward_fits(q.shape[1], q.shape[2] // n_head)
        runtime_stats.record_flash_backward("flash_attention", fused)
        band.record_visits()
        dq, dk, dv = _flash_bwd_visits(
            q, k, v, o, lse8, do, scale=scale, band=band, n_head=n_head,
            group=group, fused=fused, interpret=interpret())
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_band.defvjp(_flash_band_fwd, _flash_band_bwd)


# -- custom VJP -------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, bias, offsets, scale, causal, blocks, bwd_blocks,
           layout, n_head, with_lse):
    return _flash_vjp_fwd(q, k, v, bias, offsets, scale, causal, blocks,
                          bwd_blocks, layout, n_head, with_lse)[0]


def _flash_vjp_fwd(q, k, v, bias, offsets, scale, causal, blocks,
                   bwd_blocks, layout, n_head, with_lse):
    o, lse8 = keep_residuals(*_flash_fwd(
        q, k, v, bias, offsets, scale, causal, *blocks, layout, n_head))
    out = (o, lse8) if with_lse else o
    return out, (q, k, v, bias, offsets, o, lse8)


def _flash_vjp_bwd(scale, causal, blocks, bwd_blocks, layout, n_head,
                   with_lse, res, cts):
    q, k, v, bias, offsets, o, lse8 = res
    if with_lse:
        do, dlse8 = cts
    else:
        do, dlse8 = cts, None
    dq, dk, dv, dbias = _flash_bwd(q, k, v, bias, offsets, o, lse8, do,
                                   dlse8, scale, causal, *bwd_blocks,
                                   layout, n_head)
    doffs = None if offsets is None else \
        np.zeros(offsets.shape, dtype=jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias, doffs)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _window_fwd_blocks(window):
    """The forward tile under a window of `window` keys: square, from
    the window alone.  Where the whole band of a `WHOLE_BAND_FWD_BLOCK`
    tile fits (`whole_band_forward_fits`: up to 1025 keys) that tile,
    512, or the largest power of two a narrower window holds, no
    smaller than `MIN_WINDOW_FWD_BLOCK`; the forward is then the
    whole-band step.  A wider window keeps the online soft-max over the
    band's own `DEFAULT_BAND_BLOCK_*`, 1024 x 1024."""
    side = max(MIN_WINDOW_FWD_BLOCK, 1 << (int(window).bit_length() - 1))
    tiled = (min(side, DEFAULT_BAND_BLOCK_Q), min(side, DEFAULT_BAND_BLOCK_K))
    whole = min(*tiled, WHOLE_BAND_FWD_BLOCK)
    if whole_band_forward_fits(window, whole, whole):
        return (whole, whole)
    return tiled


def _band_blocks(t, block_q, block_k, window):
    """(forward blocks, backward blocks) of a band call: a block size
    given holds for both passes; left out, the band kernels take their
    own (`DEFAULT_BAND_BLOCK_*`, `DEFAULT_WINDOW_BWD_BLOCK_*`), the
    forward tile of a call with a window following the window
    (`_window_fwd_blocks`)."""
    fwd = _window_fwd_blocks(window) if window else \
        (DEFAULT_BAND_BLOCK_Q, DEFAULT_BAND_BLOCK_K)
    own = (fwd,
           (DEFAULT_WINDOW_BWD_BLOCK_Q, DEFAULT_WINDOW_BWD_BLOCK_K)
           if window else (DEFAULT_BWD_BLOCK_Q, DEFAULT_BWD_BLOCK_K))
    return tuple(
        tuple(min(int(given or default), t)
              for given, default in zip((block_q, block_k), pair))
        for pair in own)


def pallas_flash_attention(q, k, v, bias=None, scale=None, causal=False,
                           block_q=None, block_k=None,
                           q_offset=None, k_offset=None,
                           return_lse=False, layout="nhtd",
                           n_head=None, n_kv_head=None, window=None,
                           block_diffusion=None):
    """layout="nhtd" (default): q/k/v (N, H, T, D), output (N, H, T, D).
    layout="nthd": q/k/v (N, T, H*D) head-grouped — the head-major
    end-to-end contract; `n_head` is required and the batch*head fold
    happens in the kernel grid, so NO transpose/copy exists at the
    kernel boundary.  bias: None or broadcastable (N, 1, 1, Tk) in
    either layout.  `n_kv_head` < `n_head` (head-major only): k, v are
    (N, T, n_kv_head*D) and query head j reads key/value head
    j // (n_head / n_kv_head); they are never repeated.  `window` W
    (head-major, causal): query i reads keys i - W < j <= i, its own
    included; a window that holds every key is no window.  Both are
    causal self-attention over whole blocks with nothing beside q, k,
    v (the band kernels above); anything else with them raises.
    `block_diffusion` B (head-major, not causal, no window): the rows
    are a clean half and a noised half of T / 2 positions each, cut
    into blocks of B, under the block-diffusion training mask
    (`flash_block_diffusion.py`: kernels of its own on a grid of
    visits), with or without grouped key/value heads, at d_head 128 or
    more on the chip.  The three geometries (the causal prefix over
    grouped heads, the causal prefix under a window, the block-diffusion
    mask) each refuse a bias, offsets, a returned logsumexp, cross
    lengths and a ragged block.  The band kernels'
    tiles (`_band_blocks`; a `block_q` / `block_k` given holds
    for both passes): forward 1024 x 1024 and backward 1024 x 1024 over
    the whole prefix; under a window the backward 512 x 512 and the
    forward's tile AND PATH follow the window, from the shape alone
    (`_window_fwd_blocks`, `whole_band_forward_fits`): up to 1025 keys
    a query tile of 512 (the largest power of two a narrower window
    holds, no smaller than 256) against its WHOLE band in one grid
    step, the soft-max in one pass, a key/value head's group of query
    heads a step; a wider window keeps the online soft-max over 1024 x
    1024 tiles, as does a tile given that is not square or whose band
    passes the budget.  Any group
    size runs (query head j reads key/value head j // group: 8 over 4,
    8 over 2, 6 and 8 over 8 are data to the grids).

    q_offset/k_offset: optional GLOBAL position offsets (python ints or
    traced scalars) applied in causal masking — ring attention passes the
    rotated chunk's origin so the causal structure survives sharding.
    With return_lse=True also returns the per-row logsumexp —
    (N, H, T) for nhtd, (N, T, H) for nthd — differentiable (the dlse
    cotangent folds into the backward).

    A block size given holds for both passes; left out, each pass takes
    its own (`DEFAULT_BLOCK_*`, `DEFAULT_BWD_BLOCK_*`), the backward
    pass the forward's where a sequence is not a whole number of its
    own."""
    if layout == "nthd":
        if n_head is None:
            raise ValueError("layout='nthd' needs n_head (operands are "
                             "(N, T, H*D) head-grouped)")
        n, t_q, hd = q.shape
        if hd % n_head != 0:
            raise ValueError(f"nthd minor dim {hd} not divisible by "
                             f"n_head {n_head}")
        h, d = n_head, hd // n_head
        n_kv_head = n_head if n_kv_head is None else n_kv_head
        # One head's (block, d) tile is a lane slice of the grouped
        # minor dim, which Mosaic takes only at whole 128-lane tiles:
        # at d_head 64 a causal self-attention call goes to the kernels
        # that block heads in pairs (flash_gqa.py), which also read
        # grouped key/value heads.  Anything else at 64 keeps the
        # per-head blocks below (interpret mode; Mosaic refuses them).
        plain = (bias is None and causal and not return_lse
                 and q_offset is None and k_offset is None
                 and k.shape[1] == t_q)
        if window is not None and plain and window >= t_q:
            window = None
        if block_diffusion is not None:
            from .flash_block_diffusion import flash_block_diffusion

            return flash_block_diffusion(
                q, k, v, scale, h, n_kv_head, int(block_diffusion), block_q,
                block_k, bare=bias is None and not (
                    causal or return_lse or window is not None
                    or q_offset is not None or k_offset is not None))
        if d == 64 and plain and window is None:
            from .flash_gqa import flash_gqa

            return flash_gqa(q, k, v, h, n_kv_head, scale)
        if n_kv_head != n_head or window is not None:
            if window is not None and window < 1:
                raise ValueError(f"window {window} holds no key")
            blocks, bwd_blocks = _band_blocks(t_q, block_q, block_k, window)
            if not plain or any(t_q % b for b in blocks + bwd_blocks):
                raise NotImplementedError(
                    f"flash attention with a window or grouped key/value "
                    f"heads is causal self-attention over whole blocks "
                    f"with no bias, offsets or returned logsumexp; got "
                    f"causal={causal}, T_q {t_q}, T_k {k.shape[1]}, "
                    f"blocks {blocks} / {bwd_blocks}")
            return _flash_band(
                q, k, v, float(d ** -0.5 if scale is None else scale),
                blocks, bwd_blocks, int(h), int(h // n_kv_head),
                None if window is None else int(window))
        t_k = k.shape[1]
        qf, kf, vf = q, k, v
    elif layout == "nhtd":
        if (n_kv_head is not None or window is not None
                or block_diffusion is not None):
            raise NotImplementedError(
                "flash attention with grouped key/value heads, a "
                "window or the block-diffusion mask is head-major "
                "(layout='nthd')")
        n, h, t_q, d = q.shape
        t_k = k.shape[2]
        qf = q.reshape(n * h, t_q, d)
        kf = k.reshape(n * h, t_k, d)
        vf = v.reshape(n * h, t_k, d)
    else:
        raise ValueError(f"unknown layout {layout!r}")
    if scale is None:
        scale = d ** -0.5
    if bias is not None:
        bias = jnp.broadcast_to(bias, (n, 1, 1, t_k))
    offsets = None
    if q_offset is not None or k_offset is not None:
        offsets = jnp.stack([
            jnp.asarray(q_offset if q_offset is not None else 0,
                        jnp.int32),
            jnp.asarray(k_offset if k_offset is not None else 0,
                        jnp.int32),
        ]).reshape(1, 2)

    blocks = (int(block_q or DEFAULT_BLOCK_Q), int(block_k or DEFAULT_BLOCK_K))
    bwd_blocks = tuple(
        int(given or (own if t % min(own, t) == 0 else fwd))
        for given, own, fwd, t in zip(
            (block_q, block_k), (DEFAULT_BWD_BLOCK_Q, DEFAULT_BWD_BLOCK_K),
            blocks, (t_q, t_k)))
    out = _flash(qf, kf, vf, bias, offsets, float(scale), bool(causal),
                 blocks, bwd_blocks, layout, int(h), bool(return_lse))
    if return_lse:
        o, lse8 = out
        lse = lse8[:, 0, :].reshape(n, h, t_q)
        if layout == "nthd":
            # per-chunk statistic for ring merging rides (N, T, H) so
            # it broadcasts against the head-grouped output
            return o, jnp.moveaxis(lse, 1, 2)
        return o.reshape(n, h, t_q, d), lse
    if layout == "nthd":
        return out
    return out.reshape(n, h, t_q, d)
