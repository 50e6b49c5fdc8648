"""Time the scalar-a-head state-space scan's kernels
(`ops/pallas/ssd_scan.py`) alone, on the chip, hold them against the
XLA lowering there, and time a mixer's convolution + scan with the
scan's operand the convolution's result WHOLE against the parent's
form, which split it for the scan.

    chiprun -- python tools/time_ssd_scan.py [--rows 8192] [--heads 64]
        [--head-blocks 8,16] [--parent _parent]

One call of 1 x `--rows` positions x `--heads` heads of 64 x 128
states, xBC bfloat16, the step float32: the forward kernel and forward
+ backward (a VJP against a fixed cotangent) at each number of heads a
grid step of `--head-blocks`; milliseconds a call (`--repeats` calls
dispatched back to back and waited for once, the median of five such
rounds after a warm-up).  `against_xla`: the kernels' y and six
gradients (through the entry whose operands lie apart) against
`scan_xla`'s on the same operands, as the norm of the difference over
the norm.  `layer`: the biased SiLU convolution over xBC (4 taps) and
the scan behind it, forward, forward + backward, and forward + backward
of a recompute segment around the two (`segment_policy`: what a layer
of the cell runs), as `joint` (this tree: the kernels read x, B and C
out of xBC's lanes, write d xBC as one array, the segment keeps xBC and
convolves once) and, with `--parent` (a checkout of the parent commit,
`git archive <commit> | tar -x -C _parent`), as `split`: that
checkout's kernels on x, B and C cut out of xBC, their three gradients
glued together, the segment's backward pass convolving a second time.
The last stdout line is one JSON object; the same line goes to
`chiprun_out/time_ssd_scan.log`.  It exits non-zero off a TPU: a CPU
time is no device time.
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


def joined(x, dt, a, b, c, d):
    """`operands` as the kernels take them: (xBC, dt, a, d)."""
    return jnp.concatenate([x, b, c], axis=2), dt, a, d


def split_scan(checkout):
    """The parent's scan on operands that lie apart: `checkout`'s
    ssd_scan.py beside this tree's (its relative imports are this
    tree's package), its two kernel calls tied as it ties them, y and
    the entry states named for a segment."""
    import importlib.util

    from paddle_tpu.ops.pallas import SSD_RESIDUALS, keep_residuals

    spec = importlib.util.spec_from_file_location(
        ssd.__name__ + "_parent",
        os.path.join(checkout, "paddle_tpu/ops/pallas/ssd_scan.py"))
    parent = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = parent
    spec.loader.exec_module(parent)

    def forward(*xs):
        y, entry = keep_residuals(*parent._fwd_call(*xs),
                                  names=SSD_RESIDUALS[:2])
        return y, xs + (entry,)

    scan = jax.custom_vjp(lambda *xs: forward(*xs)[0])
    scan.defvjp(forward, lambda res, dy: parent._bwd_call(*res, dy))

    def of_xbc(xbc, dt, a, d):
        width = xbc.shape[2] - 2 * ssd.STATE
        return scan(xbc[..., :width], dt, a,
                    xbc[..., width:width + ssd.STATE],
                    xbc[..., width + ssd.STATE:], d)

    return of_xbc


def time_layer(scan, xs, ct, repeats, taps=4):
    """A mixer's convolution + `scan`(xbc, dt, a, d): ms a call."""
    from paddle_tpu.ops.pallas import segment_policy
    from paddle_tpu.ops.pallas.short_conv import biased_conv_kernel

    u, dt, a, d = xs
    r = np.random.default_rng(1)
    w = jnp.asarray(r.normal(size=(u.shape[2], taps)) / taps, jnp.float32)
    bias = jnp.asarray(r.normal(size=(u.shape[2],)), jnp.float32)

    def layer(u, w, bias, dt, a, d):
        return scan(biased_conv_kernel(u, w, bias), dt, a, d)

    args = (u, w, bias, dt, a, d)
    return {
        "forward_ms": ms_a_call(jax.jit(layer), args, repeats),
        "forward_backward_ms": ms_a_call(
            vjp_of(layer), (ct,) + args, repeats),
        "segment_forward_backward_ms": ms_a_call(
            vjp_of(jax.checkpoint(layer, policy=segment_policy())),
            (ct,) + args, repeats)}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--rows", type=int, default=8192)
    parser.add_argument("--heads", type=int, default=64)
    parser.add_argument("--head-blocks", default="8,16")
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--parent", help="a checkout of the parent commit: "
                        "its kernels on x, B and C cut out of xBC, beside "
                        "this tree's on xBC whole")
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": f"{device.platform} is no TPU"}))
        return 1
    xs, ct = operands(args.rows, args.heads, args.seed)
    joint = joined(*xs)
    out = {"device": device.device_kind, "rows": args.rows,
           "heads": args.heads, "chunk": ssd.CHUNK, "forward_ms": {},
           "forward_backward_ms": {}}
    chosen = ssd.HEAD_BLOCK
    for block in [int(x) for x in args.head_blocks.split(",")]:
        ssd.HEAD_BLOCK = block
        jax.clear_caches()
        out["forward_ms"][str(block)] = ms_a_call(
            jax.jit(ssd.scan_kernel), joint, args.repeats)
        out["forward_backward_ms"][str(block)] = ms_a_call(
            vjp_of(ssd.scan_kernel), (ct,) + joint, args.repeats)
    ssd.HEAD_BLOCK = chosen
    jax.clear_caches()
    out["layer"] = {"joint": time_layer(ssd.scan_kernel, joint, ct,
                                        args.repeats)}
    if args.parent:
        out["layer"]["split"] = time_layer(
            split_scan(args.parent), joint, ct, args.repeats)

    def err(got, want):
        got, want = (np.asarray(x, np.float64) for x in (got, want))
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    names = ("x", "dt", "a", "b", "c", "d")
    out["against_xla"] = dict(
        y=err(jax.jit(ssd.ssd_scan)(*xs), jax.jit(ssd.scan_xla)(*xs)),
        **{f"d{name}": err(g, w) for name, g, w in zip(
            names, vjp_of(ssd.ssd_scan)(ct, *xs),
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
