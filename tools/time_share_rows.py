"""Time the way back to token order of a share-holding expert layer
alone, on the chip.

    chiprun -- python tools/time_share_rows.py [--dtype bfloat16]
        [--cells mellum2-16k,sdar-8k,qwen3next-16k,lfm2-8k,joyai-8k]
        [--tiles 128x128,256x128,256x256,512x256]

At the five share cells' shapes (R rows of the first row buffer, T
tokens, k experts a token, width D, G of E experts held; a router that
picks k distinct experts a token uniformly), rows -> tokens

    out[t] = sum over the held rows r of token t of c[r] * vals[r]

four ways: `composition`, what `ops/moe_dropless.py` ran before PR 50
(`_pairs_rows`: a (T, k, D) array gathered out of the R rows for EVERY
pair, zeroed where a pair is not held, summed over k); `kernel_<tile>x
<chunk>`, `ops/pallas/rows_to_tokens.py` at each candidate (token tile,
row chunk), with the gather of the R rows into token order and without
it;
`segment_sum`, `jax.ops.segment_sum` with sorted ids over the same
token-ordered rows; each `weighted` (float32 routing weights: the
combine) and `plain` (c = 1: the gradient of the gather).  Beside them
`token_order` (the sort of R keys and the visit table, once a row
buffer for both sums), `gather`, the R-row gather into token order
alone, and `gather_f32`, tokens -> rows of the output's float32
gradient.  Then the section's two `custom_vjp`s whole, forward + backward:
`combine_vjp_*` (rows -> tokens forward; backward the rows' gradient and
the weights') and `take_vjp_*` (the gather and its gradient), as
`_composition` and as `_kernel`.

Milliseconds a call: `--repeats` calls dispatched back to back and
waited for once; the median of five such rounds after a warm-up.
Beside each kernel its largest difference from the composition,
relative to the composition's largest value.  The last stdout line is
one JSON object; the same line goes to
`chiprun_out/time_share_rows.log`.  It exits non-zero off a TPU: a CPU
time is no device time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops import moe_dropless as md  # noqa: E402
from paddle_tpu.ops.pallas import rows_to_tokens as rt  # noqa: E402
from time_short_conv import timed, worst  # noqa: E402

# cell -> tokens, experts a token, experts, experts held, width
CELLS = {
    "mellum2-16k": (16384, 8, 64, 8, 2304),
    "sdar-8k": (16384, 8, 128, 16, 2048),
    "qwen3next-16k": (16384, 10, 512, 16, 2048),
    "lfm2-8k": (8192, 4, 64, 8, 2048),
    "joyai-8k": (8192, 8, 256, 8, 2048),
}


def routed(r, t, k, e, held):
    """(order (T*k,), back (T, k), n) of a router that picks k distinct
    experts a token uniformly, experts 0..held-1 held: as the op sorts
    them."""
    experts = np.argsort(r.random((t, e)), axis=1)[:, :k].astype(np.int32)
    flat = experts.reshape(-1)
    order = np.argsort(flat, kind="stable").astype(np.int32)
    back = np.argsort(order).astype(np.int32).reshape(t, k)
    return jnp.asarray(order), jnp.asarray(back), int((flat < held).sum())


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--cells", default=",".join(CELLS))
    parser.add_argument("--tiles", default="128x128,256x128,256x256,512x256")
    parser.add_argument("--repeats", type=int, default=20)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": f"{device.platform} is no TPU"}))
        return 1
    dt = jnp.dtype(args.dtype)
    r = np.random.default_rng(args.seed)
    tilings = [tuple(int(x) for x in t.split("x"))
               for t in args.tiles.split(",")]
    out = {"device": device.device_kind, "dtype": dt.name,
           "repeats": args.repeats, "unit": "ms",
           "columns": ["ms", "max_error"],
           "kernel_columns": ["ms", "ms_without_the_gather", "max_error"]}

    def ms(fn, *xs):
        return timed(fn, xs, args.repeats)

    for cell in args.cells.split(","):
        t, k, e, held, d = CELLS[cell]
        rows = md.row_buffer_sizes(t, k, e, held)[0]
        order, back, n = routed(r, t, k, e, held)
        head = order[:rows]
        tokens = head // k
        vals = jnp.asarray(r.normal(size=(rows, d)), dt).at[n:].set(0)
        x = jnp.asarray(r.normal(size=(t, d)), dt)
        w = jnp.asarray(r.uniform(0.01, 1, size=(t, k)), jnp.float32)
        ct = jnp.asarray(r.normal(size=(t, d)), jnp.float32)
        c = w.reshape(-1)[head]
        out[f"{cell}_shape"] = {"R": rows, "T": t, "k": k, "D": d,
                                "G": held, "E": e, "held_rows": n}

        def composition(vals, w):
            yk = md._pairs_rows(vals, back, n).astype(jnp.float32)
            return jnp.sum(yk if w is None else yk * w[..., None], axis=1)

        want = {"weighted": jax.jit(composition)(vals, w),
                "plain": jax.jit(lambda v: composition(v, None))(vals)}
        out[f"{cell}_composition_weighted"] = [ms(composition, vals, w)]
        out[f"{cell}_composition_plain"] = [
            ms(lambda v: composition(v, None), vals)]

        plan = rt.token_order(tokens, n, t, c)
        out[f"{cell}_token_order"] = [
            ms(lambda tok, c: rt.token_order(tok, n, t, c), tokens, c)]
        out[f"{cell}_gather"] = [ms(lambda v, p: v[p], vals, plan[0])]

        # tokens -> rows: the R-row gather of the output's float32
        # gradient (the section's largest item beside the kernels)
        out[f"{cell}_gather_f32"] = [ms(lambda g, tok: g[tok], ct, tokens)]

        def segments(vals, c, plan):
            perm, keys = plan[0], plan[1].reshape(-1)
            rows_t = vals[perm].astype(jnp.float32)
            if c is not None:
                rows_t = rows_t * plan[4].reshape(-1)[:, None]
            return jax.ops.segment_sum(rows_t, keys, num_segments=t + 1,
                                       indices_are_sorted=True)[:t]

        for kind, cc in (("weighted", c), ("plain", None)):
            fn = (lambda v, cc=cc: segments(v, cc, plan))
            out[f"{cell}_segment_sum_{kind}"] = [
                ms(fn, vals), worst(jax.jit(fn)(vals), want[kind])]
            for tile, chunk in tilings:
                if not rt.rows_to_tokens_takes(rows, t, d, tile, chunk):
                    out[f"{cell}_kernel_{tile}x{chunk}_{kind}"] = \
                        "shape not taken"
                    continue
                tiled = rt.token_order(tokens, n, t, cc, tile, chunk)
                perm, keys, visits, count, cc_t = tiled
                tag = f"{cell}_kernel_{tile}x{chunk}_{kind}"
                fn = (lambda v, cc=cc, tiled=tiled, tile=tile:
                      rt.rows_to_tokens(v, tiled, t, cc is not None,
                                        token_tile=tile))
                alone = (lambda v, cc_t=cc_t, tile=tile, keys=keys,
                         visits=visits, count=count:
                         rt._call(v, cc_t, keys, visits, count, t, tile))
                try:
                    out[tag] = [ms(fn, vals), ms(alone, vals[perm]),
                                worst(jax.jit(fn)(vals), want[kind])]
                except Exception as err:  # a tiling Mosaic refuses
                    out[tag] = str(err)[:200]

        # the section's custom_vjps whole: forward + backward
        def vjp_of(fn, *xs):
            def both(ct, *xs):
                y, pull = jax.vjp(fn, *xs)
                return y, pull(ct)
            return both

        pairs = {
            "combine_vjp_composition": (
                lambda ys, w: md._combine(ys, w, back, head, n), (vals, w),
                ct),
            "combine_vjp_kernel": (
                lambda ys, w: md._combine_rows(ys, w, c, back, tokens, n, plan),
                (vals, w), ct),
            "take_vjp_composition": (
                lambda x: md._take_head(x, tokens, back, n), (x,), vals),
            "take_vjp_kernel": (
                lambda x: md._take_rows(x, tokens, plan), (x,), vals),
        }
        got = {}
        for tag, (fn, xs, cot) in pairs.items():
            both = vjp_of(fn)
            got[tag] = jax.jit(both)(cot, *xs)
            out[f"{cell}_{tag}"] = [ms(both, cot, *xs)]
        for what in ("combine_vjp", "take_vjp"):
            out[f"{cell}_{what}_kernel"].append(worst(
                got[f"{what}_kernel"], got[f"{what}_composition"]))
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_share_rows.log", "a") as log:
        log.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
