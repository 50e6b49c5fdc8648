"""Time the band kernels of `ops/pallas/flash_attention.py` alone, on the
chip, over a sweep of tiles.

    chiprun -- python tools/time_flash_window.py [--rows 16384]
        [--heads 64] [--kv-heads 8] [--head-dim 128] [--window 512]
        [--tiles 1024,512,256] [--bwd-tiles 512,256,512x1024]
        [--budgets-mib 32,48] [--repo _parent]

One call of 1 x `--rows` positions at `--heads` query heads of
`--head-dim` over `--kv-heads` key/value heads, bfloat16: under
`--window` keys (`flash_window_fwd` / `_dkv`; 0 = the whole causal
prefix, `flash_fwd` / `flash_dkv`).  The forward alone at each square
tile of `--tiles`, the online soft-max over the band's key tiles
(`tiled:<b>`) beside the whole-band step (`whole_band:<b>`: a query
tile against its whole band, the soft-max in one pass; under a window,
where the band's float32 scores stay under 8 MiB), each with its grid
steps, the tiles' fill (pairs the band allows over the score entries
the computed tiles hold), the two products of every computed tile at
the bf16 peak (`products_ms_at_peak`) and that over the measured time
(`products_share`: 0.32 for the tiled forward under 512 keys, which is
where PR 60 began), and how far the two forwards' o and logsumexp lie
apart on the same operands; the call as `_band_blocks` chooses it
(`chosen`); forward + backward (a VJP against a fixed
cotangent) at the chosen forward tile and each backward tile of
`--bwd-tiles` (a side, or query x key sides; the chosen backward tile
is always among them), each with the backward it ran: `kernels` 1 =
the single kernel, 2 = `dkv` + `dq` (`band_backward_fits`, read from
the counters around the trace).  `--budgets-mib` times the backward
under each of these values of `FUSED_ACCUMULATOR_BUDGET`, set in THIS
process only (how PR 54 held the single kernel at d_head 256 against
the two before the constant moved); left out, the module's own.
Without a window (`--window 0`) the forward at a tile is the band
call's own (`_flash_band`: since PR 63 a list of visits, before it the
rectangle of query tiles x the longest run), and every row, forward and
forward + backward, says the grid steps a head's pass takes, the visits
by kind where the checkout has a table (`full`: computed with no mask;
`diagonal`), `products_share` (forward two products a tile; a single
backward kernel five, two kernels seven) and a digest of the results'
bytes (`o`, and `dq`, `dk`, `dv`): two checkouts on the same operands
give the same digests where their kernels give the same bits.  `--repo
DIR` times another checkout's kernels (the parent's, unpacked under a
git-ignored directory) with the same operands.
Milliseconds a call: `--repeats` calls dispatched back to back and
waited for once; the median of five such rounds after a warm-up.  The
last stdout line is one JSON object; the same line goes to
`chiprun_out/time_flash_window.log`.  It exits non-zero off a TPU: a CPU
time is no device time.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

PEAK = 197e12      # v5e, bf16 (Google Cloud documentation, "TPU v5e")
KINDS = ("full", "diagonal")


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
    parser.add_argument("--heads", type=int, default=64)
    parser.add_argument("--kv-heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=128)
    parser.add_argument("--window", type=int, default=512)
    parser.add_argument("--tiles", default="1024,512,256")
    parser.add_argument("--bwd-tiles", default="512,256")
    parser.add_argument("--budgets-mib", default="")
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

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": f"{device.platform} is no TPU"}))
        return 1
    t, h, hkv, d = args.rows, args.heads, args.kv_heads, args.head_dim
    window = args.window or None
    r = np.random.default_rng(args.seed)

    def draw(heads):
        return jnp.asarray(r.normal(size=(1, t, heads * d)), jnp.bfloat16)

    q, k, v, ct = draw(h), draw(hkv), draw(hkv), draw(h)
    scale = d ** -0.5
    chosen = fa._band_blocks(t, None, None, window)
    out = {"device": device.device_kind, "repo": args.repo, "rows": t,
           "heads": h, "kv_heads": hkv, "head_dim": d, "window": window,
           "chosen": chosen,
           "pairs_a_head": fa._Band(t, *chosen[0], window).pairs(),
           "forward": {}, "forward_backward": {}}

    def vjp(fwd, bwd):
        return jax.jit(lambda q, k, v, ct: jax.vjp(
            lambda q, k, v: fa._flash_band(q, k, v, scale, fwd, bwd, h,
                                           h // hkv, window),
            q, k, v)[1](ct))

    def forward(ms, band, tiles_computed, steps, products=2):
        # the products of every tile a head's grid computes (two
        # forward), at the bf16 peak, over the measured time
        entries = tiles_computed * band.block_q * band.block_k
        products_ms = 1e3 * h * entries * products * 2 * d / PEAK
        return {"ms": ms, "steps": h * steps,
                "us_a_step": 1e3 * ms / (h * steps),
                "fill": band.pairs() / entries,
                "products_ms_at_peak": products_ms,
                "products_share": products_ms / ms}

    def walk(band):
        """(grid steps a head's pass takes, {"visits": by kind}) of a
        band call without a window: the list where the checkout has
        one, else the rectangle."""
        if not hasattr(band, "tiles"):
            return band.nq * band.k_steps, {}
        kinds = [kind for *_, kind in band.tiles()]
        return len(kinds), {"visits": {
            name: kinds.count(i) for i, name in enumerate(KINDS)}}

    def digest(*arrays):
        return hashlib.sha256(b"".join(
            np.asarray(x).tobytes() for x in arrays)).hexdigest()[:16]

    for tile in [int(x) for x in args.tiles.split(",") if x]:
        band = fa._Band(t, tile, tile, window)
        if window:
            tiled = jax.jit(lambda q, k, v, tile=tile, band=band:
                            fa._flash_fwd(q, k, v, None, None, scale, True,
                                          tile, tile, "nthd", h, band,
                                          h // hkv))
            steps, kinds = band.nq * band.k_steps, {}
        else:
            tiled = jax.jit(lambda q, k, v, tile=tile: fa._flash_band(
                q, k, v, scale, (tile, tile), (tile, tile), h, h // hkv,
                None))
            steps, kinds = walk(band)
            kinds["digest"] = digest(tiled(q, k, v))
        out["forward"][f"tiled:{tile}"] = dict(forward(
            ms_a_call(tiled, (q, k, v), args.repeats), band,
            band.blocks_allowed, steps), **kinds)
        if not window or band.k_steps * tile * tile * 4 > 8 << 20:
            continue        # no band, or one whose scores VMEM does not hold
        whole = jax.jit(lambda q, k, v, tile=tile: fa._flash_fwd_whole_band(
            q, k, v, scale, tile, h, h // hkv, window))
        row = forward(ms_a_call(whole, (q, k, v), args.repeats), band,
                      band.nq * band.k_steps, band.nq / (h // hkv))
        # the two forwards on the same operands: o (bfloat16) and the
        # logsumexp (float32)
        (o, lse), (o2, lse2) = tiled(q, k, v), whole(q, k, v)
        row["o_max_abs_diff"] = float(jnp.max(jnp.abs(
            o.astype(jnp.float32) - o2.astype(jnp.float32))))
        row["lse_max_abs_diff"] = float(jnp.max(jnp.abs(lse - lse2)))
        out["forward"][f"whole_band:{tile}"] = row
    bwd_tiles = [tuple(min(int(side), t) for side in (x.split("x") * 2)[:2])
                 for x in args.bwd_tiles.split(",") if x]
    budgets = [int(x) << 20 for x in args.budgets_mib.split(",") if x]
    for budget in budgets or [fa.FUSED_ACCUMULATOR_BUDGET]:
        fa.FUSED_ACCUMULATOR_BUDGET = budget
        for bq, bk in dict.fromkeys(bwd_tiles + [chosen[1]]):
            before = runtime_stats.snapshot()
            ms = ms_a_call(vjp(chosen[0], (bq, bk)), (q, k, v, ct),
                           args.repeats)
            split = runtime_stats.delta(before)[
                "flash_attention_backward_split"]
            row = {"ms": ms, "kernels": 2 if split else 1}
            if not window:
                # forward and backward, each on its own tiles: two
                # products a forward tile, five a tile of the single
                # backward kernel, seven of the two
                rows = [forward(ms, band, band.blocks_allowed, walk(band)[0],
                                products)
                        for band, products in (
                            (fa._Band(t, *chosen[0], None), 2),
                            (fa._Band(t, bq, bk, None), 7 if split else 5))]
                products_ms = sum(r["products_ms_at_peak"] for r in rows)
                row.update(
                    steps=sum(r["steps"] for r in rows),
                    products_ms_at_peak=products_ms,
                    products_share=products_ms / ms,
                    digest=digest(*vjp(chosen[0], (bq, bk))(q, k, v, ct)),
                    **walk(fa._Band(t, bq, bk, None))[1])
            out["forward_backward"][
                f"{chosen[0][0]}/{bq}x{bk}@{budget >> 20}MiB"] = row
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_flash_window.log", "a") as f:
        f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
