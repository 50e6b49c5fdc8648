"""Time the q / k preparation of an attention layer alone, on the chip.

    chiprun -- python tools/time_rope.py [--dtype bfloat16]
        [--row-tiles 128,256,512,1024]
        [--shapes q_128,k_128,q_256_quarter,k_256_quarter]

At the cells' shapes, 1 x `--rows` (16384) rows: `mellum2-16k`'s and `sdar-8k`'s q
and k (32 and 4 heads of 128, QK-norm a head), `qwen3next-16k`'s (16
and 2 heads of 256 of which 64 lanes turn, zero-centred).  Each three
ways: `xla_two_ops`, what
ran before the norm rode in the `rope` op (the `rms_norm` op with
`group_size`, its result rounded to X's dtype, then `rope`, each under
`jax.checkpoint` as a recompute segment has them); `xla`, the op's
composition (`ops/decoder.py _rope`, float32 from X to Out); and the
Pallas kernels (`ops/pallas/rope.py`) at every candidate row tile.
Forward, and backward (a VJP against a fixed cotangent whose forward
result nobody reads, dX and dScale results: every lowering recomputes
from X there, the kernel inside its one pass).  Milliseconds a call: `--repeats` calls dispatched
back to back and waited for once, so that the host's ~0.5 ms a
dispatch and wait is not in a 0.5 ms kernel's time; the median of five
such rounds after a warm-up.  Beside each timing the share of the
chip's 819 GB/s the ALGORITHM's bytes reach in it (X and Out forward;
X, dOut and dX backward; no table), and beside each kernel its
largest difference from the composition (Out, dX, dScale; relative to
the composition's largest value).  The last stdout line is one JSON
object; the same line goes to `chiprun_out/time_rope.log`.  It exits
non-zero off a TPU: a CPU time is no device time.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.core.registry import OpContext, get_op_impl  # noqa: E402
from paddle_tpu.ops import decoder  # noqa: E402
from paddle_tpu.ops.pallas import rope as rk  # noqa: E402
from time_short_conv import forward_and_backward, ints, worst  # noqa: E402

HBM_BYTES_PER_S = 819e9
# shape -> (heads, d_head, lanes that turn, zero-centred)
SHAPES = {
    "q_128": (32, 128, 128, False),
    "k_128": (4, 128, 128, False),
    "q_256_quarter": (16, 256, 64, True),
    "k_256_quarter": (2, 256, 64, True),
}
EPS = 1e-6


def op(name, ins, attrs):
    return list(get_op_impl(name)(OpContext(jax.random.PRNGKey(0), 0), ins,
                                  attrs).values())[0][0]


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--rows", type=int, default=16384)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--row-tiles", default="128,256,512,1024")
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
           "repeats": args.repeats, "unit": "ms", "rows": args.rows,
           "columns": ["forward", "backward", "forward_bytes_share",
                       "backward_bytes_share", "max_error"]}

    def results(fn, x, w, ct):
        o, vjp = jax.vjp(fn, x, w)
        return o, vjp(ct)

    for shape in args.shapes.split(","):
        heads, d, rotary, centred = SHAPES[shape]
        x = jnp.asarray(r.normal(size=(1, args.rows, heads * d)), dt)
        ct = jnp.asarray(r.normal(size=x.shape), dt)
        w = jnp.asarray(r.normal(size=(d,)) * 0.1 + (not centred),
                        jnp.float32)
        turn = {"n_head": heads, "theta": 1e6, "rotary_dim": rotary}
        normed = {"epsilon": EPS, "zero_centered": centred}
        cos, sin = decoder._cos_sin(args.rows, rotary, turn, None)
        tile = x.size * dt.itemsize

        def scale(w, centred=centred):
            return 1.0 + w if centred else w

        def row(ms, error=None, tile=tile):
            shares = [round(100 * k * tile / HBM_BYTES_PER_S / (1e-3 * one), 1)
                      for k, one in zip((2, 3), ms)]
            return list(ms) + shares + ([] if error is None else [error])

        @jax.checkpoint
        def norm_op(x, w):
            return op("rms_norm", {"X": [x], "Scale": [w]},
                      dict(normed, group_size=d))

        @jax.checkpoint
        def rope_op(x):
            return op("rope", {"X": [x]}, turn)

        def two_ops(x, w):
            return rope_op(norm_op(x, w))

        @jax.checkpoint
        def composition(x, w):
            return decoder._rope(x, scale(w), cos, sin, heads, EPS)

        both = functools.partial(forward_and_backward, repeats=args.repeats)
        out[f"{shape}_xla_two_ops"] = row(both(two_ops, (x, w), ct))
        out[f"{shape}_xla"] = row(both(composition, (x, w), ct))
        want = jax.jit(functools.partial(results, composition))(x, w, ct)
        for tr in ints(args.row_tiles):
            def fn(x, w, tr=tr):
                return rk.rope_kernel(x, scale(w), cos[0, :, 0], sin[0, :, 0],
                                      heads, EPS, tr)

            tag = f"{shape}_kernel_r{tr}"
            try:
                out[tag] = row(both(fn, (x, w), ct), worst(
                    jax.jit(functools.partial(results, fn))(x, w, ct), want))
            except Exception as e:  # a tiling Mosaic refuses
                out[tag] = str(e)[:200]
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_rope.log", "a") as log:
        log.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
