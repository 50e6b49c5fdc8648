"""Ragged paged-attention decode kernel (Pallas, TPU).

The serving-decode analog of flash_attention.py: one query token per
batch slot attends over that slot's K/V PAGES — fixed-size blocks of a
shared pool, addressed through a per-slot page table — masked to the
slot's true length (Ragged Paged Attention, PAPERS.md arxiv 2604.15464;
the input contract is exactly the repo's ragged padded-dense +
lengths convention applied to a block pool instead of a dense buffer).

Layout contract (head-major end-to-end, ISSUE 8/12): the query arrives
(S, H*D) head-grouped — exactly what the attn_qkv projection emits —
and the pools are (P, page, H*D) in the same grouping, so a page write
is a row scatter and NOTHING transposes at the kernel boundary.

Grid: (S, max_pages) with the page axis innermost; the page table and
lengths ride as SCALAR-PREFETCH operands so each k/v BlockSpec index
map dereferences the page table directly — pallas double-buffers the
page DMAs, no manual copy loop.  Each k/v block is one FULL page row
(1, page, H*D): the whole grouped minor dim travels in one contiguous
DMA and the head split happens in-kernel as static lane slices (the
decode q is a single token, so scores are VPU reductions — a 1-row MXU
matmul would waste the systolic array anyway).  Pages at or beyond a
slot's length are predicated off, and the online-softmax running
(m, l, acc) state lives in VMEM scratch across the page axis, one lane
per head.

Optional int8 pools: k/v arrive int8 with per-token-row f32 scale
sidecars (P, page, 1) — the blockwise scheme of
parallel/collectives.py applied per cache row — dequantized in-kernel.

The query block is (1, 1, H*D): the wrapper reshapes q to (S, 1, H*D)
(free minor-dim split, not a transpose) so the sublane-1 memref is an
explicit array dim — the same <1xN>-layout hint jax's reference
paged-attention kernel uses — and the kernel runs in f32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# Default logical page: 16 tokens.  Small pages waste less pool on
# ragged tails; the per-page DMA is (page, H*D) so even 16 rows is a
# full-lane contiguous transfer.  Lives HERE per the r05 rule: call
# sites must not carry stale fallbacks.
DEFAULT_PAGE_SIZE = 16


# -- kernel cost registry (observe/cost.py injects these at the custom
# -- call instructions; same dense-equivalent convention as flash) ------
#
# Dense-equivalent flops: every slot attends over its FULL page-table
# capacity T_cap = max_pages * page_size (that is what the XLA
# dense-gather twin computes once): qk^T + pv = 4 * S * T_cap * (H*d)
# per decode step.  The per-score softmax constant cannot be recovered
# from the operand shapes (H is folded into the grouped minor dim), so
# only the dot flops are credited — they dominate at any real d.
# Bytes: q/out once plus the S * max_pages pages the kernel actually
# gathers (NOT the whole pool — a mostly-empty pool is not traffic).

def _find_paged_dims(operand_shapes):
    """(s, hd, page, maxp, kv_elem_bytes) from the custom call's
    operands: page_table (S*maxp,) i32, lengths (S,) i32, q (S, 1, HD),
    then k/v pools (P, page, HD) [+ optional (P, page, 1) scales]."""
    q = next(dims for dims, _ in operand_shapes
             if len(dims) == 3 and dims[1] == 1)
    kv = next((dims, eb) for dims, eb in operand_shapes
              if len(dims) == 3 and dims[2] == q[2] and dims[1] != 1)
    one_d = sorted(dims[0] for dims, _ in operand_shapes
                   if len(dims) == 1)
    s = q[0]
    maxp = one_d[-1] // s if s else 0
    return s, q[2], kv[0][1], maxp, kv[1]


def paged_attn_cost(operand_shapes, result_shapes):
    s, hd, page, maxp, kv_eb = _find_paged_dims(operand_shapes)
    t_cap = maxp * page
    flops = 4.0 * s * t_cap * hd
    io = float(2 * s * hd * 4                  # q + out (f32)
               + 2 * s * t_cap * hd * kv_eb    # gathered k + v pages
               + s * 4 + s * maxp * 4)         # lengths + page table
    return flops, io


def _register_costs():
    from . import register_kernel_cost

    register_kernel_cost("paged_attn", paged_attn_cost)


_register_costs()


def _pallas_call(*args, **kw):
    from . import pallas_call  # shared interpret gate (package init)

    return pallas_call(*args, **kw)


def _paged_attn_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref,
                       vs_ref, o_ref, m_scr, l_scr, acc_scr, *, scale,
                       page, maxp, n_head, d):
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[s]

    @pl.when(p * page < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # (1, H*D)
        k = k_ref[0].astype(jnp.float32)                   # (page, H*D)
        v = v_ref[0].astype(jnp.float32)
        if ks_ref is not None:
            k = k * ks_ref[0].astype(jnp.float32)          # (page, 1)
        if vs_ref is not None:
            v = v * vs_ref[0].astype(jnp.float32)
        pos = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (page, 1), 0)
        valid = pos < length
        # zero invalid v rows: 0 * undefined-pool-memory would poison
        v = jnp.where(valid, v, 0.0)
        # static head loop over lane slices of the grouped minor dim —
        # one token's scores per head are a VPU reduction, kept
        # (page, 1) so the per-slot scalars broadcast along sublanes
        # the product is formed at FULL width and sliced per head
        # afterwards: Mosaic keeps the one-row q sublane-replicated and
        # refuses a lane-offset slice of such a value (q[:, hs]), while
        # the row broadcast and the slice of a (page, H*D) tile are fine
        kq = k * q                                         # (page, H*D)
        for h in range(n_head):
            hs = slice(h * d, (h + 1) * d)
            s_col = jnp.sum(kq[:, hs], axis=1,
                            keepdims=True) * scale         # (page, 1)
            s_col = jnp.where(valid, s_col, NEG_INF)
            m_prev = m_scr[:, h:h + 1]                     # (1, 1)
            m_cur = jnp.max(s_col, axis=0,
                            keepdims=True).reshape(1, 1)
            m_new = jnp.maximum(m_prev, m_cur)
            pw = jnp.exp(s_col - m_new)                    # (page, 1)
            alpha = jnp.exp(m_prev - m_new)                # (1, 1)
            acc_scr[:, hs] = acc_scr[:, hs] * alpha + jax.lax.dot_general(
                pw, v[:, hs], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # (1, d)
            l_scr[:, h:h + 1] = alpha * l_scr[:, h:h + 1] + jnp.sum(
                pw, axis=0, keepdims=True).reshape(1, 1)
            m_scr[:, h:h + 1] = m_new

    @pl.when(p == maxp - 1)
    def _finalize():
        o = jnp.concatenate(
            [acc_scr[:, h * d:(h + 1) * d]
             / jnp.maximum(l_scr[:, h:h + 1], 1e-30)
             for h in range(n_head)], axis=1)              # (1, H*D)
        o_ref[0] = o.astype(o_ref.dtype)


def ragged_paged_attention(q, k_pages, v_pages, page_table, lengths,
                           *, n_head, scale=None, k_scales=None,
                           v_scales=None):
    """Decode-step attention over paged KV.

    q: (S, H*D) head-grouped queries, one token per slot.
    k_pages/v_pages: (P, page, H*D) pools (f32/bf16, or int8 with the
        per-row scale sidecars).
    page_table: (S, max_pages) int32 — physical page of each logical
        page; entries past a slot's used range must still be valid
        indices (the host keeps them 0) — they are DMA'd and masked.
    lengths: (S,) int32 — valid tokens per slot (prompt + committed).
    k_scales/v_scales: optional (P, page, 1) f32 sidecars (int8 pools).

    Returns (S, H*D) in q's dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_slots, hd = q.shape
    n_pages, page, hd_kv = k_pages.shape
    if hd_kv != hd:
        raise ValueError(f"q minor dim {hd} != pool minor dim {hd_kv}")
    if hd % n_head:
        raise ValueError(f"minor dim {hd} not divisible by n_head "
                         f"{n_head}")
    d = hd // n_head
    maxp = page_table.shape[1]
    if scale is None:
        scale = d ** -0.5
    has_scales = k_scales is not None

    # (S, 1, H*D): free minor split making the 1-sublane q memref an
    # explicit dim (the jax paged-attention <1xN> layout hint); the
    # kernel launches in f32
    q3 = q.reshape(s_slots, 1, hd).astype(jnp.float32)

    # index maps receive the grid indices first, then the scalar
    # prefetch refs (page table, lengths) as trailing arguments
    def q_idx(s, p, pt, ln):
        return (s, 0, 0)

    def kv_idx(s, p, pt, ln):
        return (pt[s * maxp + p], 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, hd), q_idx),
        pl.BlockSpec((1, page, hd), kv_idx),
        pl.BlockSpec((1, page, hd), kv_idx),
    ]
    args = [q3, k_pages, v_pages]
    if has_scales:
        in_specs += [pl.BlockSpec((1, page, 1), kv_idx),
                     pl.BlockSpec((1, page, 1), kv_idx)]
        args += [k_scales, v_scales]

    def kern(*refs):
        pt_r, ln_r = refs[0], refs[1]
        n_in = 3 + 2 * has_scales
        ins, rest = refs[2:2 + n_in], refs[2 + n_in:]
        q_r, k_r, v_r = ins[:3]
        ks_r, vs_r = (ins[3], ins[4]) if has_scales else (None, None)
        _paged_attn_kernel(pt_r, ln_r, q_r, k_r, v_r, ks_r, vs_r,
                           *rest, scale=float(scale), page=page,
                           maxp=maxp, n_head=n_head, d=d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_slots, maxp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, hd), q_idx),
        scratch_shapes=[
            pltpu.VMEM((1, n_head), jnp.float32),   # running max/head
            pltpu.VMEM((1, n_head), jnp.float32),   # running norm/head
            pltpu.VMEM((1, hd), jnp.float32),       # output accumulator
        ],
    )
    out = _pallas_call(
        kern,
        name="paged_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, 1, hd), jnp.float32),
    )(page_table.reshape(-1).astype(jnp.int32),
      lengths.astype(jnp.int32), *args)
    return out.reshape(s_slots, hd).astype(q.dtype)
