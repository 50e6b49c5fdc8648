"""Time the selective scan's kernels (`ops/pallas/selective_scan.py`)
alone, on the chip, and hold them against the XLA lowering there.

    chiprun -- python tools/time_selective_scan.py [--rows 8192]
        [--channels 5120] [--tiles 512,256] [--bwd-tiles 256,128]

One call of 1 x `--rows` positions x `--channels` channels x 16 states,
u, Delta, B and C bfloat16: the forward kernel at each channel tile of
`--tiles`, forward + backward (a VJP against a fixed cotangent) at each
of `--bwd-tiles`; milliseconds a call (`--repeats` calls dispatched
back to back and waited for once, the median of five such rounds after
a warm-up).  `against_xla`: the kernels' y and seven gradients against
`scan_xla`'s on the same operands, as the norm of the difference over
the norm.  The last stdout line is one JSON object; the same line goes
to `chiprun_out/time_selective_scan.log`.  It exits non-zero off a TPU:
a CPU time is no device time.
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

from paddle_tpu.ops.pallas import selective_scan as ss  # noqa: E402


def ms_a_call(fn, args, repeats):
    jax.block_until_ready(fn(*args))
    rounds = []
    for _ in range(5):
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(repeats)]
        jax.block_until_ready(outs)
        rounds.append(1e3 * (time.perf_counter() - t0) / repeats)
    return float(np.median(rounds))


def operands(t, d, seed):
    r = np.random.default_rng(seed)
    bf16, f32 = jnp.bfloat16, jnp.float32

    def draw(shape, scale, dtype):
        return jnp.asarray(r.normal(size=shape) * scale, dtype)

    rates = -np.tile(np.arange(1, ss.STATE + 1, dtype=np.float32), (d, 1))
    step = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), size=d))
    return (draw((1, t, d), 1.0, bf16), draw((1, t, d), 0.5, bf16),
            jnp.asarray(rates), draw((1, t, ss.STATE), 1.0, bf16),
            draw((1, t, ss.STATE), 1.0, bf16), jnp.ones((d,), f32),
            jnp.asarray(np.log(np.expm1(step)), f32)), \
        draw((1, t, d), 1.0, bf16)


def vjp_of(fn):
    return jax.jit(lambda ct, *xs: jax.vjp(fn, *xs)[1](ct))


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--rows", type=int, default=8192)
    parser.add_argument("--channels", type=int, default=5120)
    parser.add_argument("--tiles", default="512,256")
    parser.add_argument("--bwd-tiles", default="256,128")
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": f"{device.platform} is no TPU"}))
        return 1
    xs, ct = operands(args.rows, args.channels, args.seed)
    out = {"device": device.device_kind, "rows": args.rows,
           "channels": args.channels, "chunk": ss.CHUNK, "forward_ms": {},
           "forward_backward_ms": {}}
    chosen = (ss.CHANNEL_TILE, ss.BWD_CHANNEL_TILE)
    for tile in [int(x) for x in args.tiles.split(",")]:
        ss.CHANNEL_TILE = tile
        jax.clear_caches()
        out["forward_ms"][str(tile)] = ms_a_call(
            jax.jit(ss.scan_kernel), xs, args.repeats)
    ss.CHANNEL_TILE = chosen[0]
    for tile in [int(x) for x in args.bwd_tiles.split(",")]:
        ss.BWD_CHANNEL_TILE = tile
        jax.clear_caches()
        out["forward_backward_ms"][str(tile)] = ms_a_call(
            vjp_of(ss.scan_kernel), (ct,) + xs, args.repeats)
    ss.BWD_CHANNEL_TILE = chosen[1]
    jax.clear_caches()

    def err(got, want):
        got, want = (np.asarray(x, np.float64) for x in (got, want))
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    names = ("u", "delta", "a", "b", "c", "d", "delta_bias")
    out["against_xla"] = dict(
        y=err(jax.jit(ss.scan_kernel)(*xs), jax.jit(ss.scan_xla)(*xs)),
        **{f"d{name}": err(g, w) for name, g, w in zip(
            names, vjp_of(ss.scan_kernel)(ct, *xs),
            vjp_of(ss.scan_xla)(ct, *xs))})
    out["xla_forward_ms"] = ms_a_call(jax.jit(ss.scan_xla), xs, 2)
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_selective_scan.log", "a") as f:
        f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
