"""Fused attention op.

The reference composes attention from matmul/softmax primitives
(nets.py scaled_dot_product_attention; the 2018 codebase has no fused
kernel — SURVEY.md §5.7 marks this a capability gap to fill natively).
`flash_attention` is the single-op attention: inputs Q/K/V laid out
(N, H, T, D) — or, with layout="nthd" + the n_head attr, head-grouped
(N, T, H*D), the head-major end-to-end contract that deletes every
boundary transpose (ISSUE 8) — plus an optional additive Bias; the
default implementation is a numerically-stable lax composition (XLA
fuses it well on TPU), and ops/pallas/flash_attention.py provides the
tiled Pallas kernel used when `use_pallas` is set and we're on TPU
(forward via custom_vjp).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import first, opt_in, out


def _causal_mask(t_q, t_k, window=None):
    """Query i reads keys j <= i, and with a `window` W only the W
    newest of them, i - W < j <= i."""
    mask = jnp.tril(jnp.ones((t_q, t_k), jnp.bool_))
    if window is not None:
        mask = mask & ~jnp.tril(jnp.ones((t_q, t_k), jnp.bool_), -window)
    return mask


def _block_diffusion_mask(t, length):
    """The block-diffusion training mask (Arriola et al.,
    arXiv:2503.09573) over `t` = 2 L rows, the clean half first and the
    noised half after it, row L + p standing at position p, blocks of
    `length` positions: a clean row reads the clean rows of its own and
    earlier blocks; a noised row the clean rows of strictly earlier
    blocks and the noised rows of its own block, later ones too."""
    half = t // 2
    row = jnp.arange(t)
    noised = row >= half
    blk = (row - half * noised) // length
    r_blk, s_blk = blk[:, None], blk[None, :]
    r_noised, s_noised = noised[:, None], noised[None, :]
    return jnp.where(
        s_noised, r_noised & (s_blk == r_blk),
        jnp.where(r_noised, s_blk < r_blk, s_blk <= r_blk))


def _xla_attention(q, k, v, bias, scale, causal, window=None):
    logits = jnp.einsum("nhqd,nhkd->nhqk", q, k) * scale
    if bias is not None:
        logits = logits + bias
    if causal:
        t_q, t_k = logits.shape[-2], logits.shape[-1]
        mask = _causal_mask(t_q, t_k, window)
        logits = jnp.where(mask, logits, -1e9)
    weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    o = jnp.einsum("nhqk,nhkd->nhqd", weights.astype(q.dtype), v)
    return o


def _xla_attention_nthd(q, k, v, bias, scale, causal, n_head,
                        n_kv_head=None, window=None, block_diffusion=None):
    """XLA composition over head-grouped (N, T, H*D) operands.  The
    4D views are free reshapes (minor-dim split/merge) and the einsums
    carry the head dim as a dot batch dim — XLA folds the operand
    orderings into the dot dimension numbers, no boundary transpose.
    With fewer key/value heads than query heads this composition (the
    CPU's and the tests', not the chip's) repeats them."""
    n, t_q, hd = q.shape
    d = hd // n_head
    n_kv_head = n_kv_head or n_head
    q4 = q.reshape(n, t_q, n_head, d)
    k4 = k.reshape(n, k.shape[1], n_kv_head, d)
    v4 = v.reshape(n, v.shape[1], n_kv_head, d)
    if n_kv_head != n_head:
        k4 = jnp.repeat(k4, n_head // n_kv_head, axis=2)
        v4 = jnp.repeat(v4, n_head // n_kv_head, axis=2)
    logits = jnp.einsum("nqhd,nkhd->nhqk", q4, k4) * scale
    if bias is not None:
        logits = logits + bias
    if causal:
        t_kk = logits.shape[-1]
        mask = _causal_mask(t_q, t_kk, window)
        logits = jnp.where(mask, logits, -1e9)
    if block_diffusion is not None:
        logits = jnp.where(_block_diffusion_mask(t_q, block_diffusion),
                           logits, -1e9)
    weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", weights.astype(q.dtype), v4)
    return o.reshape(n, t_q, hd)


@register_op("flash_attention")
def flash_attention(ctx, ins, attrs):
    q, k, v = first(ins, "Q"), first(ins, "K"), first(ins, "V")
    bias = opt_in(ins, "Bias")
    layout = attrs.get("layout", "nhtd")
    n_head = attrs.get("n_head", None)
    if layout == "nthd":
        # head-major end-to-end contract (ISSUE 8): operands are
        # (N, T, H*D) head-grouped — exactly what the attn_qkv
        # projection emits — and nothing transposes at this boundary
        if not n_head:
            raise ValueError("flash_attention layout='nthd' needs the "
                             "n_head attr (operands are (N, T, H*D))")
        if q.shape[-1] % int(n_head):
            raise ValueError(
                f"flash_attention nthd: minor dim {q.shape[-1]} not "
                f"divisible by n_head {n_head}")
        head_dim = q.shape[-1] // int(n_head)
        t_axis, h_count = 1, int(n_head)
        n_kv_head = int(attrs.get("n_kv_head") or n_head)
        if (h_count % n_kv_head
                or k.shape[-1] != n_kv_head * head_dim):
            raise ValueError(
                f"flash_attention nthd: K minor dim {k.shape[-1]} is not "
                f"n_kv_head {n_kv_head} heads of {head_dim}, or n_head "
                f"{h_count} is not a multiple of it")
    elif layout == "nhtd":
        head_dim = q.shape[-1]
        t_axis, h_count = 2, q.shape[1]
        n_kv_head = h_count
        if attrs.get("n_kv_head"):
            raise ValueError("flash_attention: n_kv_head (grouped-query "
                             "attention) needs layout='nthd'")
    else:
        raise ValueError(f"flash_attention: unknown layout {layout!r}")
    scale = attrs.get("scale", None)
    if scale is None:
        scale = head_dim ** -0.5
    causal = attrs.get("causal", False)
    window = attrs.get("window", None)
    if window is not None:
        # the newest `window` keys of the causal prefix, the query's
        # own included: self-attention with nothing beside q, k, v
        if (not causal or bias is not None or layout != "nthd"
                or attrs.get("sequence_parallel", False)
                or k.shape[t_axis] != q.shape[t_axis]):
            raise NotImplementedError(
                "flash_attention: a window is causal head-major "
                "self-attention with no Bias and no sequence_parallel")
        window = int(window)
    block_diffusion = attrs.get("block_diffusion", None)
    if block_diffusion is not None:
        # the mask is the call's own: a clean and a noised half of
        # whole blocks, nothing beside q, k, v
        t = q.shape[t_axis]
        block_diffusion = int(block_diffusion)
        if (causal or window is not None or bias is not None
                or layout != "nthd"
                or attrs.get("sequence_parallel", False)
                or k.shape[t_axis] != t):
            raise NotImplementedError(
                "flash_attention: the block-diffusion mask is head-major "
                "self-attention with no causal mask or window beside it, "
                "no Bias and no sequence_parallel")
        if block_diffusion < 1 or t % (2 * block_diffusion):
            raise ValueError(
                f"flash_attention: {t} rows are not a clean and a noised "
                f"half of whole blocks of {block_diffusion}")
    if attrs.get("sequence_parallel", False):
        # long-context path: shard the sequence axis over the mesh's
        # sp axis and run ring attention (KV rotation via ppermute) or
        # Ulysses (head/sequence all-to-all), parallel/ring_attention.
        # Only ACTIVE inside a CompiledProgram traced under a mesh WITH
        # an sp axis — but the strategy value validates everywhere so a
        # typo'd flag can never silently no-op.
        strategy0 = attrs.get("sequence_parallel")
        if strategy0 not in (True, "ring", "ulysses"):
            raise ValueError(
                f"sequence_parallel must be True/'ring'/'ulysses', "
                f"got {strategy0!r}")
        from ..parallel.mesh import get_exec_context

        ectx = get_exec_context()
        mesh = None if ectx is None else ectx.mesh
        # the compiled program's actual batch axis (not a hardcoded
        # "dp"): a non-default batch axis name must still keep batch
        # sharding inside the sp shard_map
        batch_axis = "dp" if ectx is None else ectx.batch_axis
        if mesh is not None and mesh.shape.get("sp", 1) > 1:
            if n_kv_head != h_count:
                raise NotImplementedError(
                    "sequence_parallel flash_attention does not take "
                    "grouped key/value heads (n_kv_head < n_head)")
            if bias is not None:
                raise ValueError(
                    "sequence_parallel flash_attention does not take "
                    "an additive Bias: ring attention supports causal "
                    "masking only — drop padding bias (full-length "
                    "sequences / packed batches) or disable "
                    "sequence_parallel")
            sp = mesh.shape["sp"]
            if q.shape[t_axis] % sp != 0:
                raise ValueError(
                    f"sequence_parallel flash_attention: sequence "
                    f"length {q.shape[t_axis]} must be divisible by "
                    f"the sp axis size ({sp}) — pad T to a multiple")
            strategy = "ring" if strategy0 is True else strategy0
            if strategy == "ulysses":
                if h_count % sp != 0:
                    raise ValueError(
                        f"ulysses sequence_parallel: the sp axis "
                        f"({sp}) must divide n_head ({h_count}) — "
                        f"use 'ring' for head counts below the sp "
                        f"degree")
                from ..parallel.ring_attention import ulysses_attention

                o = ulysses_attention(
                    q, k, v, mesh, axis="sp", scale=scale,
                    causal=causal, use_pallas=attrs.get("use_pallas"),
                    batch_axis=batch_axis, layout=layout,
                    n_head=h_count)
                return out(Out=o)
            from ..parallel.ring_attention import ring_attention

            # use_pallas None = ring's auto (Pallas on TPU); the batch
            # axis keeps dp-sharded activations dp-sharded inside the
            # shard_map instead of all-gathering per dp group
            o = ring_attention(q, k, v, mesh, axis="sp", scale=scale,
                               causal=causal,
                               use_pallas=attrs.get("use_pallas"),
                               batch_axis=batch_axis, layout=layout,
                               n_head=h_count)
            return out(Out=o)
        # no sp axis in this compile: fall through to the local kernel
    use_pallas = attrs.get("use_pallas", False)
    if use_pallas and block_diffusion is not None:
        from .pallas.flash_block_diffusion import block_diffusion_takes

        # a half the kernels' tiles do not divide runs the explicit mask
        use_pallas = block_diffusion_takes(t, block_diffusion)
    if use_pallas:
        def _kernel_bias_ok(b):
            # the tiled kernel takes a KEY-padding bias broadcastable
            # TO (N, 1, 1, Tk): every (right-aligned) dim must be 1 or
            # match the target
            target = (q.shape[0], 1, 1, k.shape[t_axis])
            if b.ndim > 4:
                return False
            for bd, td in zip(reversed(b.shape), reversed(target)):
                if bd != 1 and bd != td:
                    return False
            return True

        if bias is not None and not _kernel_bias_ok(bias):
            # a program that asks for the kernel gets the kernel or an
            # error, never a silent XLA composition
            raise ValueError(
                f"flash_attention(use_pallas=True): the Pallas kernel "
                f"takes only a key-padding bias broadcastable to "
                f"(N, 1, 1, Tk); got {tuple(bias.shape)}.  Express "
                f"causal+padding as causal=True plus a key bias, or "
                f"leave use_pallas unset for the XLA composition")
        from .pallas.flash_attention import pallas_flash_attention

        extra = {} if window is None else {"window": window}
        if block_diffusion is not None:
            extra["block_diffusion"] = block_diffusion
        o = pallas_flash_attention(
            q, k, v, bias, scale, causal, layout=layout, n_head=h_count,
            n_kv_head=None if n_kv_head == h_count else n_kv_head, **extra)
    elif layout == "nthd":
        o = _xla_attention_nthd(q, k, v, bias, scale, causal, h_count,
                                n_kv_head, window, block_diffusion)
    else:
        o = _xla_attention(q, k, v, bias, scale, causal)
    return out(Out=o)


@register_op("fused_vocab_softmax_ce")
def fused_vocab_softmax_ce(ctx, ins, attrs):
    """Final vocab projection + label-smoothed softmax-CE in one fused
    op (ops/pallas/vocab_ce.py): Hidden (..., D) @ W (D, V) logits are
    never materialized in HBM.  With use_pallas unset (or on CPU) runs
    an XLA chunked-equivalent composition for numerics parity."""
    hidden = first(ins, "Hidden")
    w = first(ins, "W")
    labels = first(ins, "Label")
    eps = float(attrs.get("epsilon", 0.0))
    if attrs.get("use_pallas", False):
        from .pallas.vocab_ce import (DEFAULT_BLOCK_T, DEFAULT_BLOCK_V,
                                      fused_vocab_ce)

        # fall back to the kernel module's defaults — they encode the
        # measured on-chip VMEM budget (r05: a stale 1024/2048 fallback
        # here kept overriding the retuned defaults and every compile
        # failed identically)
        loss = fused_vocab_ce(
            hidden, w, labels, eps,
            int(attrs.get("block_t", DEFAULT_BLOCK_T)),
            int(attrs.get("block_v", DEFAULT_BLOCK_V)))
    else:
        v = w.shape[1]
        z = (hidden @ w).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        zt = jnp.take_along_axis(
            z, labels.reshape(labels.shape + (1,)).astype(jnp.int32),
            axis=-1)[..., 0]
        loss = lse - (1.0 - eps) * zt - (eps / v) * jnp.sum(z, axis=-1)
    return out(Loss=loss)
