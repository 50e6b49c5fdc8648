"""Time a head's lane statistic (`ops/pallas/head_norm.py`) alone, on the
chip: the two kernels against the compositions XLA ran before them.

    chiprun -- python tools/time_head_norm.py [--rows 8192] [--heads 32]
                                              [--parent CHECKOUT]

One call of `--rows` rows x `--heads` heads of 128 lanes, bfloat16, at
`kimilinear-8k`'s shape by default (`--rows 16384 --heads 16` is
`qwen3next-16k`'s), in the three forms the ops use: `l2norm` (q, lanes
`--heads` x 128 .. of a QKV array three times as wide, times Dk^-1/2),
`silu_gated` and `sigmoid_gated` (`rms_norm(group_size=128)` under a
gate, one scale (128,)).  Each forward and forward + backward (a VJP
against a fixed cotangent; every gradient the form has) by

    kernel     `head_norm_fwd` / `head_norm_bwd`
    view       `head_norm_xla`: the (.., H, 128) view, which the chip
               re-lays in float32 (`qwen3next-16k` before PR 68)
    products   the heads' sums and spreads as products with a 0 / 1
               matrix at "highest" (`kimilinear-8k` before PR 68), by
               the PARENT's `channel_delta.head_sums` / `head_spread`:
               only with `--parent`, a checkout of a commit that has
               them (`git archive ce73951 | tar -x -C _parent`)

milliseconds a call (`--repeats` calls dispatched back to back and
waited for once, the median of five such rounds after a warm-up), and
`against_view`: the kernel's results against the view's in float32, as
the norm of the difference over the norm.  The last stdout line is one
JSON object; the same line goes to `chiprun_out/time_head_norm.log`.
It exits non-zero off a TPU: a CPU time is no device time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tools")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops.pallas import head_norm as hn  # noqa: E402
from time_channel_delta import load_parent, ms_a_call, vjp_of  # noqa: E402

# form -> (gated, `head_norm`'s keywords beside `lanes`)
FORMS = {
    "l2norm": (False, dict(denom=1.0, eps=1e-6, constant=hn.GROUP ** -0.5)),
    "silu_gated": (True, dict(denom=float(hn.GROUP), eps=1e-6)),
    "sigmoid_gated": (True, dict(denom=float(hn.GROUP), eps=1e-6,
                                 gate_activation="sigmoid")),
}


def by_products(cd):
    """The parent's composition of `kimilinear-8k`: a head's sum and its
    spread each a product with a 0 / 1 matrix (module `cd`)."""
    def fn(x, scale, gate, form):
        f32 = jnp.float32
        xf = x.astype(f32)
        heads = x.shape[-1] // hn.GROUP
        inv = jax.lax.rsqrt(cd.head_sums(xf * xf, heads) / form.denom
                            + form.eps)
        y = xf * cd.head_spread(inv, hn.GROUP)
        if scale is not None:
            y = y * jnp.tile(scale.astype(f32), heads)
        if gate is not None:
            y = y * hn.SQUASH[form.gate_activation](gate.astype(f32))
        return (y * form.constant).astype(x.dtype)

    return fn


def ways(parent):
    """name -> fn(array, scale, gate, lanes, **form keywords)."""
    def composed(composition):
        def fn(x, scale, gate, lanes, **kw):
            start, width = lanes
            return composition(x[..., start:start + width], scale, gate,
                               hn.Form(0, width, **kw))
        return fn

    out = {"kernel": lambda x, scale, gate, lanes, **kw: hn.head_norm(
               x, scale, gate, lanes=lanes, **kw),
           "view": composed(hn.head_norm_xla)}
    if parent:
        out["products"] = composed(by_products(load_parent(parent)))
    return out


def operands(rows, heads, seed, dtype, gated):
    r = np.random.default_rng(seed)
    width = heads * hn.GROUP
    draw = lambda *shape: jnp.asarray(r.normal(size=shape), dtype)  # noqa: E731
    x = draw(1, rows, width if gated else 3 * width)
    scale = jnp.asarray(1 + 0.3 * r.normal(size=hn.GROUP), jnp.float32)
    return ((x, scale, draw(1, rows, width)) if gated else (x,)), \
        draw(1, rows, width), ((0, width) if gated else (width, width))


def closed(way, gated, lanes, kw):
    """`way` over its differentiable operands alone."""
    if gated:
        return lambda x, scale, gate: way(x, scale, gate, lanes, **kw)
    return lambda x: way(x, None, None, lanes, **kw)


def measure(args):
    out = {"ms": {}, "against_view": {}}
    table = ways(args.parent)
    for form, (gated, kw) in FORMS.items():
        xs, ct, lanes = operands(args.rows, args.heads, args.seed,
                                 jnp.bfloat16, gated)
        for name, way in table.items():
            fn = closed(way, gated, lanes, kw)
            out["ms"][f"{form}.{name}.fwd"] = ms_a_call(
                jax.jit(fn), xs, args.repeats)
            out["ms"][f"{form}.{name}.fwd_bwd"] = ms_a_call(
                vjp_of(fn), (ct,) + xs, args.repeats)
        xs, ct, lanes = operands(args.rows, args.heads, args.seed + 1,
                                 jnp.float32, gated)

        def results(way):
            fn = closed(way, gated, lanes, kw)
            return (jax.jit(fn)(*xs),) + vjp_of(fn)(ct, *xs)

        def err(got, want):
            got, want = (np.asarray(x, np.float64) for x in (got, want))
            return float(np.linalg.norm(got - want) / np.linalg.norm(want))

        names = ("y", "dx", "dscale", "dgate")
        out["against_view"][form] = {
            n: err(a, b) for n, a, b in zip(names, results(table["kernel"]),
                                            results(table["view"]))}
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--rows", type=int, default=8192)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--parent", help="a checkout of a commit whose "
                        "channel_delta.py has head_sums / head_spread: the "
                        "0 / 1 products are measured in the same call")
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": f"{device.platform} is no TPU"}))
        return 1
    out = {"device": device.device_kind, "rows": args.rows,
           "heads": args.heads, **measure(args)}
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_head_norm.log", "a") as f:
        f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
