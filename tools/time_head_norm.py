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
the norm of the difference over the norm.

`--inside channel` (or `gated`: `--heads` key heads, twice as many
value heads) times INSTEAD what the l2norm costs where it is taken
since PR 69: a delta rule's three chunk-local kernels (`*_inverse`,
`*_operands_fwd`, `*_operands_bwd` of `channel_delta.py` /
`gated_delta.py`) on unit q and k (`unit`) and on QKV as it lies, the
statistic inside (`raw`), beside the four `head_norm_*` calls the first
needs (`l2norm.*`), and their sums for one layer of a step
(`layer.unit` = the inverse + two forwards + the backward + four
`head_norm_fwd` + two `_bwd`: forward, recomputed forward, backward;
`layer.raw` the four kernel calls alone); the whole op forward and
forward + backward each way (`op.*`: with XLA's part, the padded
gradient of QKV); and `raw_against_unit`, float32 "highest" on the
first heads: o and the gradients of QKV, g (the decay's operand) and
beta, as the norm of the difference over the norm.

The last stdout line is one JSON object; the same line goes to
`chiprun_out/time_head_norm.log`.  It exits non-zero off a TPU: a CPU
time is no device time.
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


def err(got, want):
    """The norm of the difference over the norm."""
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


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

        names = ("y", "dx", "dscale", "dgate")
        out["against_view"][form] = {
            n: err(a, b) for n, a, b in zip(names, results(table["kernel"]),
                                            results(table["view"]))}
    return out


def delta_family(name, heads):
    """(module, value heads, where raw q and k lie in QKV, fn(qkv, q, k,
    g, beta) -> (the inverse kernel's operands, the other two's before
    the inverse)), by family."""
    from paddle_tpu.ops.pallas import channel_delta as cd, gated_delta as gd

    width = heads * hn.GROUP
    raw = gd.RawQK(q=0, k=width, heads=heads, dim=hn.GROUP)
    if name == "channel":
        def operands(qkv, q, k, g, beta):
            # (kb: beta's product is XLA's either way; any array times)
            kb, v = qkv[..., width:2 * width], qkv[..., 2 * width:]
            return (q, k, kb, g), (q, k, kb, v, g)
        return cd, heads, raw, operands

    def operands(qkv, q, k, g, beta):
        x, _ = gd._row_tiles(g, beta, heads)
        return (k, x), (q, k, qkv[..., 2 * width:], x)
    return gd, 2 * heads, raw, operands


def measure_inside(args):
    from paddle_tpu.ops.pallas import gated_delta as gd

    mod, hv, raw, kernel_operands = delta_family(args.inside, args.heads)
    channel = args.inside == "channel"
    width, d = args.heads * hn.GROUP, hn.GROUP

    def draw(rows, heads, value_heads, seed, dtype):
        r = np.random.default_rng(seed)
        qkv = jnp.asarray(r.normal(size=(1, rows, (2 * heads + value_heads)
                                         * d)), dtype)
        shape = (1, rows, heads * d if channel else value_heads)
        g = jnp.asarray(-np.exp(r.uniform(np.log(1e-3), np.log(0.3),
                                          size=shape)), jnp.float32)
        beta = jnp.asarray(1 / (1 + np.exp(-r.normal(
            size=(1, rows, value_heads)))), jnp.float32)
        ct = jnp.asarray(r.normal(size=(1, rows, value_heads * d)), dtype)
        return qkv, g, beta, ct

    def op(raw_, heads, value_heads):
        at = raw._replace(k=heads * d, heads=heads)

        def fn(qkv, g, beta):
            q, k = (qkv, qkv) if raw_ else gd.unit_q_and_k(qkv, qkv, at)
            v = qkv[..., 2 * heads * d:]
            if channel:
                return mod.channel_delta_rule(
                    q, k, v, g, beta, use_kernel=True,
                    raw=at if raw_ else None)
            n, t = qkv.shape[:2]
            if not raw_:
                q, k = (x.reshape(n, t, heads, d) for x in (q, k))
            return mod.gated_delta_rule(
                q, k, v.reshape(n, t, value_heads, d), g, beta,
                use_kernel=True, raw=at if raw_ else None,
            ).reshape(n, t, value_heads * d)
        return fn

    qkv, g, beta, ct = draw(args.rows, args.heads, hv, args.seed,
                            jnp.bfloat16)
    ms = {}
    # the four head-statistic calls the unit way needs
    forms = {"q": hn.Form(0, width, constant=d ** -0.5),
             "k": hn.Form(width, width)}
    x2 = qkv.reshape(args.rows, -1)
    dy = ct.reshape(args.rows, -1)[:, :width]
    unit = {}
    for name, form in forms.items():
        fwd = jax.jit(lambda x, form=form: hn._fwd_call(x, None, None, form))
        bwd = jax.jit(lambda x, dy, form=form: hn._bwd_call(
            x, None, None, dy, form)[0])
        ms[f"l2norm.{name}.fwd"] = ms_a_call(fwd, (x2,), args.repeats)
        ms[f"l2norm.{name}.bwd"] = ms_a_call(bwd, (x2, dy), args.repeats)
        unit[name] = fwd(x2).reshape(1, args.rows, width)
    for way, (q, k, at) in {"unit": (unit["q"], unit["k"], None),
                            "raw": (qkv, qkv, raw)}.items():
        first, rest = jax.jit(kernel_operands)(qkv, q, k, g, beta)
        inverse = jax.jit(lambda *xs, at=at: mod._inverse_call(*xs, raw=at))
        forward = jax.jit(lambda *xs, at=at: mod._operands_fwd_call(
            *xs, raw=at))
        backward = jax.jit(lambda *xs, at=at: mod._operands_bwd_call(
            *xs, raw=at))
        kept = inverse(*first)
        kept = tuple(kept) if channel else (kept,)
        outs = tuple(forward(*rest, kept[0]))
        ms[f"inverse.{way}"] = ms_a_call(inverse, first, args.repeats)
        ms[f"operands_fwd.{way}"] = ms_a_call(forward, rest + kept[:1],
                                              args.repeats)
        ms[f"operands_bwd.{way}"] = ms_a_call(
            backward, rest + kept[:1] + outs + kept[1:], args.repeats)
        ms[f"layer.{way}"] = (ms[f"inverse.{way}"]
                              + 2 * ms[f"operands_fwd.{way}"]
                              + ms[f"operands_bwd.{way}"])
        fn = op(way == "raw", args.heads, hv)
        ms[f"op.{way}.fwd"] = ms_a_call(jax.jit(fn), (qkv, g, beta),
                                        args.repeats)
        ms[f"op.{way}.fwd_bwd"] = ms_a_call(vjp_of(fn), (ct, qkv, g, beta),
                                            args.repeats)
    ms["layer.unit"] += sum(2 * ms[f"l2norm.{n}.fwd"] + ms[f"l2norm.{n}.bwd"]
                            for n in forms)
    ms["layer.saved"] = ms["layer.unit"] - ms["layer.raw"]

    few = (2, 2) if channel else (1, 2)     # the first (key, value) heads
    xs = draw(args.rows, *few, args.seed + 1, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, want = (
            (jax.jit(fn)(*xs[:3]),) + vjp_of(fn)(xs[3], *xs[:3])
            for fn in (op(True, *few), op(False, *few)))
    return {"inside": args.inside, "ms": ms, "raw_against_unit": {
        n: err(a, b) for n, a, b in zip(("o", "dqkv", "dg", "dbeta"),
                                        got, want)}}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--inside", choices=("channel", "gated"),
                        help="time the l2norm inside that delta rule's "
                        "chunk-local kernels against the head_norm calls "
                        "before them, instead of the forms")
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
           "heads": args.heads,
           **(measure_inside(args) if args.inside else measure(args))}
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_head_norm.log", "a") as f:
        f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
