"""Time the parts of the chunked gated delta rule alone, on the chip.

    chiprun -- python tools/time_gated_delta.py [--dtype bfloat16]
        [--positions 16384] [--key-heads 16] [--diagonals 8,16,32,64]

At `qwen3next-16k`'s shape by default (1 x 16384 positions, 16 key and
32 value heads of 128): the chunk-local part by each lowering (XLA's
`chunk_operands`, the Pallas kernels at each candidate size of the
substitution's diagonal blocks), the part's three kernels each alone
(`gated_delta_inverse`, `gated_delta_operands_fwd` given the inverse,
`gated_delta_operands_bwd`), the inverse alone by each candidate (XLA's
batched triangular solve, the in-kernel substitution on a given A), the
scan kernels, the whole op; each forward and forward + backward (a VJP
against fixed cotangents, every gradient a result).  Milliseconds a
call, the median of `--repeats` timed calls after a warm-up.  The last
stdout line is one JSON object; the same line goes to
`chiprun_out/time_gated_delta.log`.  It exits non-zero off a TPU: a CPU
time is no device time.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops.pallas import gated_delta as gd  # noqa: E402


def timed(fn, args, repeats):
    """Median ms of `fn(*args)`, jitted, after one warm-up call."""
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(repeats):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(1e3 * (time.perf_counter() - start))
    return round(statistics.median(took), 3)


def forward_and_backward(fn, args, repeats, seed=1):
    """(forward ms, forward + backward ms) of `fn`."""
    outs = jax.eval_shape(fn, *args)
    keys = jax.random.split(jax.random.PRNGKey(seed),
                            len(jax.tree.leaves(outs)))
    cts = jax.tree.unflatten(jax.tree.structure(outs), [
        jax.random.normal(key, o.shape, jnp.float32).astype(o.dtype)
        for key, o in zip(keys, jax.tree.leaves(outs))])

    def both(cts, *args):
        return jax.vjp(fn, *args)[1](cts)

    return timed(fn, args, repeats), timed(both, (cts,) + tuple(args),
                                           repeats)


def inverse_kernel(a, diagonal):
    """The substitution alone: side-by-side A (B, T, 2C) -> M."""
    from jax.experimental import pallas as pl

    from paddle_tpu.ops.pallas import pallas_call

    rows = gd.DEFAULT_BLOCK_CHUNKS * gd.CHUNK

    def kernel(a_ref, m_ref):
        iotas = gd._tile_iotas()

        def chunk(c):
            r = gd._chunk_rows(c)
            m_ref[0, r, :] = gd._inverse_side_by_side(a_ref[0, r, :], iotas,
                                                      diagonal)

        gd._for_each_chunk(gd.DEFAULT_BLOCK_CHUNKS, chunk)

    spec = pl.BlockSpec((1, rows, 2 * gd.CHUNK), lambda b, i: (b, i, 0))
    return pallas_call(kernel, name=f"inverse_{diagonal}",
                       grid=(a.shape[0], a.shape[1] // rows),
                       in_specs=[spec], out_specs=spec,
                       out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype))(a)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--positions", type=int, default=16384)
    parser.add_argument("--key-heads", type=int, default=16)
    parser.add_argument("--diagonals", default="8,16,32,64")
    parser.add_argument("--repeats", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": f"{device.platform} is no TPU"}))
        return 1
    dt, t, hk = jnp.dtype(args.dtype), args.positions, args.key_heads
    hv, d, c = 2 * hk, gd.HEAD_DIM, gd.CHUNK
    diagonals = [int(x) for x in args.diagonals.split(",")]
    r = np.random.default_rng(args.seed)
    q, k = r.normal(size=(2, 1, t, hk, d))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q, k, v = (jnp.asarray(x, dt) for x in (q, k, r.normal(size=(1, t, hv, d))))
    g = jnp.asarray(-np.abs(r.normal(size=(1, t, hv))) * 0.05, jnp.float32)
    beta = jnp.asarray(1 / (1 + np.exp(-r.normal(size=(1, t, hv)))),
                       jnp.float32)
    operands = (q, k, v, g, beta)
    out = {"device": device.device_kind, "dtype": dt.name, "positions": t,
           "key_heads": hk, "value_heads": hv, "repeats": args.repeats,
           "unit": "ms", "default_diagonal": gd.DIAGONAL_BLOCK}
    both = functools.partial(forward_and_backward, repeats=args.repeats)

    out["chunk_operands_xla"] = both(gd.chunk_operands, operands)
    want = jax.jit(gd.chunk_operands)(*operands)
    default = gd.DIAGONAL_BLOCK
    for diagonal in diagonals:
        gd.DIAGONAL_BLOCK = diagonal
        gd._inverse_call.clear_cache()
        tag = f"chunk_operands_kernel_{diagonal}"
        try:
            out[tag] = both(gd.chunk_operands_kernel, operands)
        except Exception as e:  # a candidate Mosaic refuses
            out[tag] = str(e)[:200]
            continue
        got = jax.jit(gd.chunk_operands_kernel)(*operands)
        out[tag + "_max_error"] = max(
            float(jnp.abs(a.astype(jnp.float32)
                          - b.astype(jnp.float32)).max()
                  / jnp.abs(b.astype(jnp.float32)).max())
            for a, b in zip(got, want))
    gd.DIAGONAL_BLOCK = default
    gd._inverse_call.clear_cache()

    # the part's kernels alone, as the op hands them their operands: a
    # recompute segment's backward pass runs the second and third, the
    # inverse is kept from the forward pass
    flat = [x.reshape(1, t, -1) for x in (q, k, v)]
    tiles, _ = jax.jit(functools.partial(gd._row_tiles, hk=hk))(g, beta)
    inverse = jax.jit(gd._inverse_call)(flat[1], tiles)
    out["inverse_kernel"] = timed(gd._inverse_call, (flat[1], tiles),
                                  args.repeats)
    out["operands_fwd_kernel_given_inverse"] = timed(
        gd._operands_fwd_call, (*flat, tiles, inverse), args.repeats)
    cts = jax.jit(gd._operands_fwd_call)(*flat, tiles, inverse)
    out["operands_bwd_kernel"] = timed(
        gd._operands_bwd_call, (*flat, tiles, inverse, *cts), args.repeats)

    # what XLA does around the kernels: the row tiles and exp(gamma_C)
    out["row_tiles_xla"] = both(
        functools.partial(gd._row_tiles, hk=hk), (g, beta))

    # the inverse alone: a layer's 8192 matrices, strictly lower
    a = np.tril(r.normal(size=(hv * t // c, c, c)) * 0.2, -1)
    a = jnp.asarray(a, jnp.float32)
    out["inverse_xla_solve"] = timed(gd.unit_lower_inverse, (a,),
                                     args.repeats)
    side = jnp.moveaxis(a.reshape(hk, 2, t // c, c, c), 1, 3) \
        .reshape(hk, t, 2 * c)
    for diagonal in diagonals:
        try:
            out[f"inverse_kernel_{diagonal}"] = timed(
                functools.partial(inverse_kernel, diagonal=diagonal),
                (side,), args.repeats)
        except Exception as e:
            out[f"inverse_kernel_{diagonal}"] = str(e)[:200]

    scan_operands = jax.jit(gd.chunk_operands)(*operands)
    out["scan_kernels"] = both(gd.scan_kernel, scan_operands)
    out["scan_xla"] = both(gd.scan_xla, scan_operands)
    out["op_kernels"] = both(
        functools.partial(gd.gated_delta_rule, use_kernel=True), operands)
    # the op as it ran before the chunk-operand kernels
    out["op_xla_operands"] = both(
        lambda *x: gd.scan_kernel(*gd.chunk_operands(*x)), operands)
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_gated_delta.log", "a") as log:
        log.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
