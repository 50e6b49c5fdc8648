"""Time the scalar-a-head state-space scan's kernels
(`ops/pallas/ssd_scan.py`) alone, on the chip, and hold them against
the XLA lowering there.

    chiprun -- python tools/time_ssd_scan.py [--rows 8192] [--heads 64]
        [--head-blocks 8,16]

One call of 1 x `--rows` positions x `--heads` heads of 64 x 128
states, x, B and C bfloat16, the step float32: the forward kernel and
forward + backward (a VJP against a fixed cotangent) at each number of
heads a grid step of `--head-blocks`; milliseconds a call (`--repeats`
calls dispatched back to back and waited for once, the median of five
such rounds after a warm-up).  `against_xla`: the kernels' y and six
gradients against `scan_xla`'s on the same operands, as the norm of the
difference over the norm.  The last stdout line is one JSON object; the
same line goes to `chiprun_out/time_ssd_scan.log`.  It exits non-zero
off a TPU: a CPU time is no device time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops.pallas import ssd_scan as ssd  # noqa: E402


def ms_a_call(fn, args, repeats):
    jax.block_until_ready(fn(*args))
    rounds = []
    for _ in range(5):
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(repeats)]
        jax.block_until_ready(outs)
        rounds.append(1e3 * (time.perf_counter() - t0) / repeats)
    return float(np.median(rounds))


def operands(t, heads, seed):
    r = np.random.default_rng(seed)
    bf16, f32 = jnp.bfloat16, jnp.float32

    def draw(shape, dtype):
        return jnp.asarray(r.normal(size=shape), dtype)

    step = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), size=(1, t, heads)))
    width = heads * ssd.HEAD_DIM
    return (draw((1, t, width), bf16), jnp.asarray(step, f32),
            jnp.asarray(-np.arange(1, heads + 1), f32),
            draw((1, t, ssd.STATE), bf16), draw((1, t, ssd.STATE), bf16),
            jnp.ones((heads,), f32)), draw((1, t, width), bf16)


def vjp_of(fn):
    return jax.jit(lambda ct, *xs: jax.vjp(fn, *xs)[1](ct))


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--rows", type=int, default=8192)
    parser.add_argument("--heads", type=int, default=64)
    parser.add_argument("--head-blocks", default="8,16")
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": f"{device.platform} is no TPU"}))
        return 1
    xs, ct = operands(args.rows, args.heads, args.seed)
    out = {"device": device.device_kind, "rows": args.rows,
           "heads": args.heads, "chunk": ssd.CHUNK, "forward_ms": {},
           "forward_backward_ms": {}}
    chosen = ssd.HEAD_BLOCK
    for block in [int(x) for x in args.head_blocks.split(",")]:
        ssd.HEAD_BLOCK = block
        jax.clear_caches()
        out["forward_ms"][str(block)] = ms_a_call(
            jax.jit(ssd.scan_kernel), xs, args.repeats)
        out["forward_backward_ms"][str(block)] = ms_a_call(
            vjp_of(ssd.scan_kernel), (ct,) + xs, args.repeats)
    ssd.HEAD_BLOCK = chosen
    jax.clear_caches()

    def err(got, want):
        got, want = (np.asarray(x, np.float64) for x in (got, want))
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    names = ("x", "dt", "a", "b", "c", "d")
    out["against_xla"] = dict(
        y=err(jax.jit(ssd.scan_kernel)(*xs), jax.jit(ssd.scan_xla)(*xs)),
        **{f"d{name}": err(g, w) for name, g, w in zip(
            names, vjp_of(ssd.scan_kernel)(ct, *xs),
            vjp_of(ssd.scan_xla)(ct, *xs))})
    out["xla_forward_ms"] = ms_a_call(jax.jit(ssd.scan_xla), xs, 2)
    out["xla_forward_backward_ms"] = ms_a_call(
        vjp_of(ssd.scan_xla), (ct,) + xs, 2)
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_ssd_scan.log", "a") as f:
        f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
