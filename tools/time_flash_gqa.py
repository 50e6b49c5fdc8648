"""Time the head-pair kernels of `ops/pallas/flash_gqa.py` alone, on the
chip: the forward over a sweep of tiles, forward + backward at the tiles
the shape chooses.

    chiprun -- python tools/time_flash_gqa.py [--rows 8192] [--batch 1]
        [--heads 32] [--kv-heads 8] [--tiles 512,1024x512,512x1024,1024]
        [--dtype bfloat16]

One call of `--batch` x `--rows` positions at `--heads` query heads of
64 over `--kv-heads` key/value heads, causal.  The forward alone
(`flash_gqa_fwd`) at each tile of `--tiles` (a side, or query x key
sides; the tile `default_blocks` chooses is always among them, marked
`chosen`); forward + backward (a VJP against a fixed cotangent) at the
chosen tile, with the backward it ran: `kernels` 1 = the single kernel,
2 = `dkv` + `dq` (`fused_backward_fits`, read from the counters around
the trace).  Beside each time, the MXU passes the call EXECUTES: every
score-sized matmul at d_head 64 is a whole 128-lane pass over every
score of every tile the grid computes (2 a head forward, 5 backward on
the single kernel, 7 on the two), at the chip's bf16 peak
(`benchmarks/peaks.json`), and their share of the time.  Milliseconds a
call: `--repeats` calls dispatched back to back and waited for once; the
median of five such rounds after a warm-up.  The last stdout line is one
JSON object; the same line goes to `chiprun_out/time_flash_gqa.log`.
It exits non-zero off a TPU: a CPU time is no device time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.observe.monitoring import runtime_stats  # noqa: E402
from paddle_tpu.ops.pallas import flash_gqa as fg  # noqa: E402


def ms_a_call(fn, args, repeats):
    jax.block_until_ready(fn(*args))
    rounds = []
    for _ in range(5):
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(repeats)]
        jax.block_until_ready(outs)
        rounds.append(1e3 * (time.perf_counter() - t0) / repeats)
    return float(np.median(rounds))


def tiles_computed(t, bq, bk):
    """Tiles of a causal (t, t) triangle that hold a score."""
    return sum(1 for a in range(t // bq) for b in range(t // bk)
               if (a + 1) * bq > b * bk)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--rows", type=int, default=8192)
    parser.add_argument("--batch", type=int, default=1)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--kv-heads", type=int, default=8)
    parser.add_argument("--tiles", default="512,1024x512,512x1024,1024")
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--repeats", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": f"{device.platform} is no TPU"}))
        return 1
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        peak = json.load(f)[device.device_kind]["bf16_flops"]
    n, t, h, hkv = args.batch, args.rows, args.heads, args.kv_heads
    dtype = jnp.dtype(args.dtype)
    r = np.random.default_rng(args.seed)

    def draw(heads):
        return jnp.asarray(r.normal(size=(n, t, heads * fg.HEAD_DIM)), dtype)

    q, k, v, ct = draw(h), draw(hkv), draw(hkv), draw(h)
    scale = fg.HEAD_DIM ** -0.5
    chosen = tuple(min(side, t) for side in fg.default_blocks(t))

    def passes_ms(matmuls, bq, bk):
        scores = n * h * tiles_computed(t, bq, bk) * bq * bk
        return 1e3 * matmuls * scores * 2 * fg.LANES / peak

    def timed(ms, matmuls, bq, bk):
        executed = passes_ms(matmuls, bq, bk)
        return {"ms": ms, "mxu_passes_ms": executed,
                "mxu_passes_share": executed / ms}

    out = {"device": device.device_kind, "batch": n, "rows": t, "heads": h,
           "kv_heads": hkv, "dtype": dtype.name, "chosen": chosen,
           "forward": {}, "forward_backward": {}}
    sides = [tuple(min(int(side), t) for side in (x.split("x") * 2)[:2])
             for x in args.tiles.split(",") if x]
    for bq, bk in dict.fromkeys(sides + [chosen]):
        fwd = jax.jit(lambda q, k, v, bq=bq, bk=bk: fg._flash_fwd(
            q, k, v, scale, fg._Geometry(q, k, h, hkv, bq, bk)))
        out["forward"][f"{bq}x{bk}"] = timed(
            ms_a_call(fwd, (q, k, v), args.repeats), 2, bq, bk)
    vjp = jax.jit(lambda q, k, v, ct: jax.vjp(
        lambda *a: fg.flash_gqa(*a, h, hkv), q, k, v)[1](ct))
    before = runtime_stats.snapshot()
    ms = ms_a_call(vjp, (q, k, v, ct), args.repeats)
    split = runtime_stats.delta(before)["flash_gqa_backward_split"]
    out["forward_backward"]["{}x{}".format(*chosen)] = dict(
        timed(ms, 9 if split else 7, *chosen), kernels=2 if split else 1)
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_flash_gqa.log", "a") as f:
        f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
