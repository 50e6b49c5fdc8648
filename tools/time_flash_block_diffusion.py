"""Time the block-diffusion flash kernels of
`ops/pallas/flash_attention.py` alone, on the chip.

    chiprun -- python tools/time_flash_block_diffusion.py [--rows 16384]
        [--heads 32] [--kv-heads 4] [--head-dim 128] [--block-length 4]
        [--tiles 1024,512] [--own-blocks 1] [--skip-kinds diagonal]
        [--repo _parent]

One call of 1 x `--rows` rows (a clean and a noised half) at `--heads`
query heads of `--head-dim` over `--kv-heads` key/value heads, bfloat16,
blocks of `--block-length`: the forward alone (`flash_block_diffusion_fwd`)
and forward + backward (a VJP against a fixed cotangent; `kernels` 1 =
the single backward kernel, 2 = `_dkv` + `_dq`) at each square tile of
`--tiles`, with the call's own counters: grid steps and tiles computed a
head's pass, the visits by kind, and the tiles' fill (pairs the mask
allows over the score entries computed).

What a KIND of visit costs is read by taking it out, in THIS process
only: `--own-blocks 0` runs a noised tile against itself as a `DIAGONAL`
visit, the whole tile under the mask (the grid of visits alone, PR 59's
step 2); `--skip-kinds full,diagonal,own_blocks` leaves the visits of
those kinds in the table (their init, finalize and DMA stay) and computes
nothing in them, so the time lost is their arithmetic (the results are
then wrong: a timing, not a call).  `--repo DIR` times another checkout's
kernels (the parent's, unpacked under a git-ignored directory) with the
same operands; one without the visit table reads no counters of it.
Milliseconds a call: `--repeats` calls dispatched back to back and waited
for once; the median of five such rounds after a warm-up.  The last
stdout line is one JSON object; the same line goes to
`chiprun_out/time_flash_block_diffusion.log`.  It exits non-zero off a
TPU: a CPU time is no device time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

KINDS = ("full", "diagonal", "own_blocks")


def ms_a_call(fn, args, repeats):
    import jax
    import numpy as np

    jax.block_until_ready(fn(*args))
    rounds = []
    for _ in range(5):
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(repeats)]
        jax.block_until_ready(outs)
        rounds.append(1e3 * (time.perf_counter() - t0) / repeats)
    return float(np.median(rounds))


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--rows", type=int, default=16384)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--kv-heads", type=int, default=4)
    parser.add_argument("--head-dim", type=int, default=128)
    parser.add_argument("--block-length", type=int, default=4)
    parser.add_argument("--tiles", default="1024")
    parser.add_argument("--own-blocks", type=int, default=1)
    parser.add_argument("--skip-kinds", default="")
    parser.add_argument("--repo", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    parser.add_argument("--repeats", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.observe.monitoring import runtime_stats
    from paddle_tpu.ops.pallas import flash_attention as fa

    try:        # the kernels' own module, with the list of visits (PR 59)
        from paddle_tpu.ops.pallas import flash_block_diffusion as fbd
    except ImportError:
        fbd = None      # a checkout from before it: the band kernels
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": f"{device.platform} is no TPU"}))
        return 1
    t, h, hkv, d = args.rows, args.heads, args.kv_heads, args.head_dim
    length = args.block_length
    skipped = [KINDS.index(kind) for kind in args.skip_kinds.split(",")
               if kind]
    if fbd is None and (skipped or not args.own_blocks):
        print(json.dumps({"error": f"{args.repo} has no list of visits"}))
        return 1
    if not args.own_blocks:
        fbd.LANES = t           # no side under the tile: DIAGONAL
    if skipped:
        table = fbd._DiffusionBand.visits

        def visits(band, *a, **kw):
            rows = table(band, *a, **kw)
            # a kind no branch of a kernel takes
            rows[fbd.V_KIND, np.isin(rows[fbd.V_KIND], skipped)] = len(KINDS)
            return rows

        fbd._DiffusionBand.visits = visits
    r = np.random.default_rng(args.seed)

    def draw(heads):
        return jnp.asarray(r.normal(size=(1, t, heads * d)), jnp.bfloat16)

    q, k, v, ct = draw(h), draw(hkv), draw(hkv), draw(h)
    out = {"device": device.device_kind, "repo": args.repo, "rows": t,
           "heads": h, "kv_heads": hkv, "head_dim": d,
           "block_length": length, "own_blocks": bool(args.own_blocks),
           "skipped": args.skip_kinds, "tiles": {}}

    def call(tile):
        if fbd is None:
            return jax.jit(lambda q, k, v: fa._flash_band(
                q, k, v, d ** -0.5, (tile,) * 2, (tile,) * 2, h, h // hkv,
                None, length))
        return jax.jit(lambda q, k, v: fbd._flash(
            q, k, v, d ** -0.5, (tile, tile), h, h // hkv, length))

    for tile in [int(x) for x in args.tiles.split(",")]:
        fn = call(tile)
        vjp = jax.jit(lambda q, k, v, ct, fn=fn: jax.vjp(fn, q, k, v)[1](ct))
        before = runtime_stats.snapshot()
        row = {"forward_ms": ms_a_call(fn, (q, k, v), args.repeats)}
        took = runtime_stats.delta(before)
        row["forward_backward_ms"] = ms_a_call(vjp, (q, k, v, ct),
                                               args.repeats)
        row["kernels"] = 2 if runtime_stats.delta(before)[
            "flash_attention_backward_split"] else 1
        steps = took.get("flash_block_diffusion_grid_steps")
        if steps:
            kinds = [kind for *_, kind in
                     fbd._DiffusionBand(t, tile, length).tiles()]
            row.update(
                grid_steps=steps,
                tiles_computed=took["flash_block_diffusion_blocks_visited"],
                visits={name: kinds.count(i)
                        for i, name in enumerate(KINDS)},
                fill=took["flash_block_diffusion_pairs_allowed"]
                / took["flash_block_diffusion_entries_computed"])
        out["tiles"][str(tile)] = row
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_flash_block_diffusion.log", "a") as f:
        f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
