"""Time the short causal convolution alone, on the chip.

    chiprun -- python tools/time_short_conv.py [--dtype bfloat16]
        [--row-tiles 256,512,1024] [--channel-tiles 256,512,1024]
        [--row-chunks 64] [--lane-groups 256] [--forms silu,gated]

At the two cells' shapes: `qwen3next-16k`'s (1, 16384, 8192) x 4 taps
under a SiLU and `lfm2-8k`'s gated (1, 8192, 3 x 2048) x 3 taps.  Each
form by both lowerings of the `short_conv` op: XLA's composition under
`jax.checkpoint` (`ops/decoder.py`) and the Pallas kernels
(`ops/pallas/short_conv.py`) at every candidate tiling; forward and
forward + backward (a VJP against a fixed cotangent, dX and dFilter
results).  Milliseconds a call: `--repeats` calls dispatched back to
back and waited for once, so that the host's ~0.5 ms a dispatch and
wait is not in a 1 ms kernel's time; the median of five such rounds
after a warm-up.  Beside each kernel timing its largest difference from
the composition (Out, dX, dFilter; relative to the composition's
largest value).  The last stdout line is one JSON object; the same line
goes to `chiprun_out/time_short_conv.log`.  It exits non-zero off a
TPU: a CPU time is no device time.
"""

from __future__ import annotations

import argparse
import functools
import itertools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops import decoder  # noqa: E402
from paddle_tpu.ops.pallas import short_conv as sc  # noqa: E402

# form -> (X's shape, channels, taps, the composition)
SHAPES = {
    "silu": ((1, 16384, 8192), 8192, 4, decoder._silu_conv),
    "gated": ((1, 8192, 3 * 2048), 2048, 3, decoder._short_conv),
}


def timed(fn, args, repeats):
    """ms a call of `fn(*args)`, jitted: the device's time, the calls
    queued behind each other."""
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(5):
        start = time.perf_counter()
        for _ in range(repeats):
            last = fn(*args)
        jax.block_until_ready(last)
        took.append(1e3 * (time.perf_counter() - start) / repeats)
    return round(statistics.median(took), 3)


def forward_and_backward(fn, args, ct, repeats):
    """(forward ms, forward + backward ms) of `fn`: the second a VJP
    whose forward result nobody reads, so a lowering that recomputes
    runs its backward pass alone."""
    def both(ct, *args):
        return jax.vjp(fn, *args)[1](ct)

    return timed(fn, args, repeats), timed(both, (ct,) + tuple(args),
                                           repeats)


def ints(text):
    return [int(x) for x in text.split(",")]


def worst(got, want):
    return max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
                     .max() / jnp.abs(b.astype(jnp.float32)).max())
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--forms", default="silu,gated")
    parser.add_argument("--row-tiles", default="256,512,1024")
    parser.add_argument("--channel-tiles", default="256,512,1024")
    parser.add_argument("--row-chunks", default=str(sc.ROW_CHUNK))
    parser.add_argument("--lane-groups", default=str(sc.LANE_GROUP))
    parser.add_argument("--repeats", type=int, default=20)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": f"{device.platform} is no TPU"}))
        return 1
    dt = jnp.dtype(args.dtype)
    r = np.random.default_rng(args.seed)
    out = {"device": device.device_kind, "dtype": dt.name,
           "repeats": args.repeats, "unit": "ms",
           "columns": ["forward", "forward+backward", "max_error"]}
    both = functools.partial(forward_and_backward, repeats=args.repeats)

    def results(fn, x, w, ct):
        o, vjp = jax.vjp(fn, x, w)
        return o, vjp(ct)

    for form in args.forms.split(","):
        shape, d, taps, composition = SHAPES[form]
        x = jnp.asarray(r.normal(size=shape), dt)
        w = jnp.asarray(r.normal(size=(d, taps)) * 0.5, jnp.float32)
        ct = jnp.asarray(r.normal(size=shape[:2] + (d,)), dt)
        xla = jax.checkpoint(composition)
        out[f"{form}_xla"] = both(xla, (x, w), ct)
        want = jax.jit(functools.partial(results, xla))(x, w, ct)
        gated = form == "gated"
        channel_tiles = [d] if gated else ints(args.channel_tiles)
        for tr, td, rc, lg in itertools.product(
                ints(args.row_tiles), channel_tiles, ints(args.row_chunks),
                ints(args.lane_groups)):
            sc.ROW_CHUNK, sc.LANE_GROUP = rc, lg
            sc._fwd_call.clear_cache()
            sc._bwd_call.clear_cache()

            def fn(x, w, tr=tr, td=td):
                return sc.short_conv_kernel(x, w, gated, tr, td)

            tag = f"{form}_kernel_r{tr}_c{td}_k{rc}_g{lg}"
            try:
                out[tag] = list(both(fn, (x, w), ct)) + [worst(
                    jax.jit(functools.partial(results, fn))(x, w, ct), want)]
            except Exception as e:  # a tiling Mosaic refuses
                out[tag] = str(e)[:200]
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_short_conv.log", "a") as log:
        log.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
