"""Functional collectives over the mesh.

Replaces the reference's NCCL op handles and raw nccl ops
(details/all_reduce_op_handle.cc, operators/nccl/nccl_op.cu.cc,
collective_server).  These are thin shard_map wrappers around XLA
collectives (psum / all_gather / ppermute / all_to_all) for code that
wants explicit communication (ring attention, expert dispatch); ordinary
data/tensor parallelism never calls these — GSPMD inserts collectives
from sharding annotations alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def compat_shard_map(fn, mesh, in_specs, out_specs, check=False,
                     auto=frozenset()):
    """`jax.shard_map` (jax 0.9) with this repo's defaults: the
    replication check off unless asked for, and partial-manual maps
    spelled by the axes left to GSPMD.

    `auto`: mesh axes left to GSPMD — the composed grad-sync path maps
    manually over the data axes while mp stays auto-partitioned; jax
    names the MANUAL axes instead (`axis_names`), so the complement is
    passed.  CAUTION: only psum-family collectives (psum/pmean/pmax)
    survive partial-manual on this XLA; all_gather / all_to_all
    hard-abort the SPMD partitioner (the reason
    quantized_all_reduce_psum exists)."""
    manual = (frozenset(mesh.axis_names) - frozenset(auto)
              if auto else frozenset())
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=check)


def psum(x, axis_name):
    return jax.lax.psum(x, axis_name)


def all_reduce(x, mesh, axis: str, shard_dim: int = 0, op: str = "sum"):
    """Reduce per-device values stacked along `shard_dim` to one
    replicated result with that dim removed (the PE all-reduce,
    details/all_reduce_op_handle.cc: N per-device grads → one summed
    grad everywhere)."""
    spec = [None] * x.ndim
    spec[shard_dim] = axis

    def f(xs):
        if op == "sum":
            r = jax.lax.psum(xs, axis)
        elif op == "max":
            r = jax.lax.pmax(xs, axis)
        elif op == "mean":
            r = jax.lax.pmean(xs, axis)
        else:
            raise ValueError(op)
        return jax.numpy.squeeze(r, shard_dim)

    out_spec = [None] * (x.ndim - 1)
    return compat_shard_map(f, mesh, (P(*spec),), P(*out_spec))(x)


def all_gather(x, mesh, axis: str, shard_dim: int = 0):
    spec = [None] * x.ndim
    spec[shard_dim] = axis

    def f(xs):
        return jax.lax.all_gather(xs, axis, axis=shard_dim, tiled=True)

    return compat_shard_map(f, mesh, (P(*spec),), P(*[None] * x.ndim))(x)


def reduce_scatter(x, mesh, axis: str, shard_dim: int = 0):
    """Replicated-in, sharded-out sum (the kReduce build-strategy mode,
    build_strategy.h:55)."""
    def f(xs):
        return jax.lax.psum_scatter(xs, axis, scatter_dimension=shard_dim,
                                    tiled=True)

    out_spec = [None] * x.ndim
    out_spec[shard_dim] = axis
    return compat_shard_map(f, mesh, (P(*[None] * x.ndim),), P(*out_spec))(x)


def ppermute(x, mesh, axis: str, perm, shard_dim: int = 0):
    """Neighbor exchange over the ring (ICI) — building block for ring
    attention."""
    spec = [None] * x.ndim
    spec[shard_dim] = axis

    def f(xs):
        return jax.lax.ppermute(xs, axis, perm)

    return compat_shard_map(f, mesh, (P(*spec),), P(*spec))(x)


def all_to_all(x, mesh, axis: str, split_dim: int, concat_dim: int):
    """Ulysses-style head/sequence exchange."""
    n = mesh.shape[axis]
    in_spec = [None] * x.ndim
    in_spec[concat_dim] = axis

    def f(xs):
        return jax.lax.all_to_all(xs, axis, split_axis=split_dim,
                                  concat_axis=concat_dim, tiled=True)

    out_spec = [None] * x.ndim
    out_spec[split_dim] = axis
    return compat_shard_map(f, mesh, (P(*in_spec),), P(*out_spec))(x)


def barrier(mesh, axis: str):
    """Synchronization barrier (the reference's send_barrier /
    fetch_barrier ops) — a trivial psum forces a cross-replica sync."""
    def f():
        return jax.lax.psum(jnp.ones(()), axis)

    return compat_shard_map(f, mesh, (), P())()


# ---------------------------------------------------------------------------
# Quantized gradient all-reduce (EQuARX, arxiv 2506.17615)
# ---------------------------------------------------------------------------

# Tensors below this element count ride the exact psum instead of the
# quantized exchange: at small sizes the per-block scale sidecar and the
# two-phase latency cost more than the byte saving, and biases /
# layernorm scales are exactly the tensors where quantization error
# hurts most per byte moved (docs/DIST.md, error model).
DEFAULT_QUANT_BLOCK = 256
DEFAULT_QUANT_FLOOR = 4096


def _numel(shape) -> int:
    out = 1
    for d in shape:
        out *= int(d)
    return out


def quantize_blockwise(x, block_size: int = DEFAULT_QUANT_BLOCK):
    """Symmetric per-block int8 quantization of a flat (..., block)
    array: scale = max|block| / 127 (0-blocks get scale 1 so they
    round-trip to exact zeros).  Deterministic: jnp.rint is
    round-half-even, and the scale depends only on the block's values —
    every rank quantizing the same bytes produces the same bytes.

    Returns (q int8 of x.shape, scales f32 of x.shape[:-1])."""
    assert x.shape[-1] == block_size, (x.shape, block_size)
    amax = jnp.max(jnp.abs(x), axis=-1)
    scales = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.rint(x / scales[..., None]), -127, 127)
    return q.astype(jnp.int8), scales


def dequantize_blockwise(q, scales, dtype=jnp.float32):
    return q.astype(dtype) * scales[..., None].astype(dtype)


def quantized_all_reduce_local(x, axis: str, n_ranks: int,
                               block_size: int = DEFAULT_QUANT_BLOCK,
                               min_quant_numel: int = DEFAULT_QUANT_FLOOR,
                               op: str = "mean"):
    """Blockwise-int8 all-reduce of a per-rank partial value — for use
    INSIDE a shard_map over `axis` where every rank holds a full-shaped
    partial sum (the dp gradient-sync situation).  EQuARX-style
    two-phase exchange:

      phase 1 (reduce-scatter): split into one chunk per rank,
        quantize each chunk per `block_size` block (int8 payload + f32
        scale sidecar, ~1/[2·block] overhead), all_to_all so rank i
        receives everyone's chunk i, dequantize into f32 and
        accumulate locally;
      phase 2 (all-gather): re-quantize the reduced chunk and
        all_gather payload + scales, dequantize.

    vs the bf16 ring all-reduce this moves ~half the bytes per phase
    (int8 vs bf16) at the cost of two quantization roundings; the
    elementwise error bound is documented in docs/DIST.md and pinned by
    tests/test_quantized_allreduce.py.

    Determinism: quantization is value-deterministic, the accumulation
    is a fixed-order sum over the rank dim, and phase 2's gathered
    bytes are identical on every rank — all ranks agree BITWISE on the
    result (the property dp grad sync needs so replicated params never
    drift apart).

    Falls back to the exact jax.lax.psum for tensors smaller than
    `min_quant_numel` (or than one block per rank) and for non-float
    inputs.  op: "sum" or "mean" (mean divides by n_ranks — the dp
    gradient convention where each rank differentiates its local-batch
    mean loss)."""
    if op not in ("sum", "mean"):
        raise ValueError(f"unknown reduce op {op!r}")
    inv = 1.0 / n_ranks if op == "mean" else 1.0

    def exact(v):
        r = jax.lax.psum(v, axis)
        return r * jnp.asarray(inv, r.dtype) if op == "mean" else r

    size = _numel(x.shape)
    if (not jnp.issubdtype(x.dtype, jnp.floating)
            or size < max(min_quant_numel, n_ranks * block_size)):
        return exact(x)

    orig_dtype = x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-size) % (n_ranks * block_size)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    # (n_ranks, blocks_per_chunk, block)
    chunks = flat.reshape(n_ranks, -1, block_size)

    # phase 1: quantize every outgoing chunk, exchange, accumulate
    q, scales = quantize_blockwise(chunks, block_size)
    q = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                           tiled=False)
    scales = jax.lax.all_to_all(scales, axis, split_axis=0,
                                concat_axis=0, tiled=False)
    reduced = jnp.sum(dequantize_blockwise(q, scales), axis=0)

    # phase 2: re-quantize the reduced chunk, gather all chunks back
    q2, s2 = quantize_blockwise(reduced, block_size)
    q2 = jax.lax.all_gather(q2, axis, axis=0, tiled=True)
    s2 = jax.lax.all_gather(s2, axis, axis=0, tiled=True)
    out = dequantize_blockwise(q2, s2).reshape(-1)
    if pad:
        out = out[:size]
    return (out * inv).reshape(x.shape).astype(orig_dtype)


def quantized_all_reduce_psum(x, axes, n_ranks: int, rank_index,
                              block_size: int = DEFAULT_QUANT_BLOCK,
                              min_quant_numel: int = DEFAULT_QUANT_FLOOR,
                              op: str = "mean"):
    """The EQuARX two-phase exchange in its psum-only form — for
    shard_map regions where all_to_all/all_gather cannot lower (a
    partial-auto region with GSPMD-owned axes, or a multi-axis data
    group): SAME quantization steps, SAME error model, but the data
    movement is a single psum.

      phase 1: quantize every chunk per block (identical bytes to the
        wire path), dequantize locally, psum over `axes` — every rank
        now holds every reduced chunk (the wire path's rank i holds
        only chunk i);
      phase 2: re-quantize ALL reduced chunks (rank i's chunk i
        quantizes identically on every rank — same input bytes, same
        rint), dequantize.  No gather needed: the phase-2 result is
        already replicated, bitwise-identically, everywhere.

    Determinism: quantization is value-deterministic and psum produces
    bitwise-identical results on every participating rank, so all
    ranks agree bitwise — the dp grad-sync invariant.  `rank_index` is
    accepted for signature symmetry with a future chunk-local variant
    and unused (every rank computes all chunks).

    Byte honesty: this form moves f32 psum bytes, not int8 payloads —
    the numerics/error-model guarantees hold, the wire-byte saving
    does NOT (docs/DIST.md §hybrid).  Pure single-axis dp keeps the
    real all_to_all/all_gather exchange."""
    del rank_index
    if op not in ("sum", "mean"):
        raise ValueError(f"unknown reduce op {op!r}")
    inv = 1.0 / n_ranks if op == "mean" else 1.0

    def exact(v):
        r = jax.lax.psum(v, axes)
        return r * jnp.asarray(inv, r.dtype) if op == "mean" else r

    size = _numel(x.shape)
    if (not jnp.issubdtype(x.dtype, jnp.floating)
            or size < max(min_quant_numel, n_ranks * block_size)):
        return exact(x)

    orig_dtype = x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-size) % (n_ranks * block_size)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(n_ranks, -1, block_size)

    # phase 1: quantize outgoing chunks, reduce via psum of the
    # dequantized payloads (numerically the wire path's fixed-order
    # rank sum up to all-reduce ordering; bitwise-identical everywhere)
    q, scales = quantize_blockwise(chunks, block_size)
    reduced = jax.lax.psum(dequantize_blockwise(q, scales), axes)

    # phase 2: re-quantize the reduced chunks — replicated input bytes
    # make the rounding identical on every rank, so no gather is needed
    q2, s2 = quantize_blockwise(reduced, block_size)
    out = dequantize_blockwise(q2, s2).reshape(-1)
    if pad:
        out = out[:size]
    return (out * inv).reshape(x.shape).astype(orig_dtype)


def quantized_all_reduce(x, mesh, axis: str, shard_dim: int = 0,
                         op: str = "mean",
                         block_size: int = DEFAULT_QUANT_BLOCK,
                         min_quant_numel: int = DEFAULT_QUANT_FLOOR):
    """Host-level wrapper mirroring `all_reduce`: per-rank partial
    values stacked along `shard_dim` reduce to one replicated result
    with that dim removed, through the blockwise-int8 two-phase
    exchange above.  The executor's dp grad-sync hook calls the _local
    form directly inside its own shard_map; this wrapper is the
    standalone/test surface."""
    n = mesh.shape[axis]
    spec = [None] * x.ndim
    spec[shard_dim] = axis

    def f(xs):
        v = jnp.squeeze(xs, shard_dim)
        return quantized_all_reduce_local(
            v, axis, n, block_size=block_size,
            min_quant_numel=min_quant_numel, op=op)

    out_spec = [None] * (x.ndim - 1)
    return compat_shard_map(f, mesh, (P(*spec),), P(*out_spec))(x)
