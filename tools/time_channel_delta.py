"""Time the lane-decayed delta rule's kernels
(`ops/pallas/channel_delta.py`) alone, on the chip, part by part, and
hold them against the position-by-position recurrence there.

    chiprun -- python tools/time_channel_delta.py [--rows 8192] [--heads 32]
                                                  [--parent CHECKOUT]

One call of 1 x `--rows` positions x `--heads` heads of 128 x 128, q, k,
v bfloat16, g and beta float32, at `kimilinear-8k`'s shape by default:
each kernel alone (`channel_delta_inverse`, `_operands_fwd`,
`_operands_bwd`, the scan forward, the scan forward + backward), the
whole op forward and forward + backward (a VJP against a fixed
cotangent); milliseconds a call (`--repeats` calls dispatched back to
back and waited for once, the median of five such rounds after a
warm-up).  `inverse_less_substitution` is `channel_delta_inverse` with
its substitution taken out (A written where (I + A)^-1 goes) and
`products_share_of_inverse` its share of the whole kernel: the decayed
products' part, as far as the two add up (the kernel less its
substitution by the other road, `inverse` - `inverse_less_substitution`,
is the substitution's).  `against_scan`: the kernels in float32 on the
first two heads against a `lax.scan` over positions, o and the five
gradients, as the norm of the difference over the norm;
`bf16_against_scan` the same with bfloat16 operands.  `--parent`: a
checkout of the parent commit (`git archive <commit> | tar -x -C
_parent`), whose `channel_delta.py` is measured the same way in the same
call, under `"parent"`.  The last stdout line is one JSON object; the
same line goes to `chiprun_out/time_channel_delta.log`.  It exits
non-zero off a TPU: a CPU time is no device time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmarks")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import reference_kimi_linear as reference  # noqa: E402
from paddle_tpu.ops.pallas import channel_delta as cd  # noqa: E402


def ms_a_call(fn, args, repeats):
    jax.block_until_ready(fn(*args))
    rounds = []
    for _ in range(5):
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(repeats)]
        jax.block_until_ready(outs)
        rounds.append(1e3 * (time.perf_counter() - t0) / repeats)
    return float(np.median(rounds))


def operands(t, heads, seed, dtype):
    r = np.random.default_rng(seed)
    d = cd.HEAD_DIM

    def unit(x):
        x = x.reshape(1, t, heads, d)
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            1, t, heads * d)

    draw = lambda *shape: r.normal(size=shape)  # noqa: E731
    q = unit(draw(1, t, heads * d)) * d ** -0.5
    k, v = unit(draw(1, t, heads * d)), draw(1, t, heads * d)
    # a decay a lane between ~1e-3 and ~3 a position
    g = -np.exp(r.uniform(np.log(1e-3), np.log(3.0), size=(1, t, heads * d)))
    beta = 1 / (1 + np.exp(-draw(1, t, heads)))
    as_ = lambda x, kind: jnp.asarray(x, kind)  # noqa: E731
    return (as_(q, dtype), as_(k, dtype), as_(v, dtype),
            as_(g, jnp.float32), as_(beta, jnp.float32)), \
        as_(draw(1, t, heads * d), dtype)


def sequential(q, k, v, g, beta):
    """The recurrence, position by position, float32: the benchmark
    reference's own scan."""
    n, t, h = beta.shape
    heads = lambda x: x.astype(jnp.float32).reshape(  # noqa: E731
        n, t, h, cd.HEAD_DIM)
    o = reference.delta_rule(heads(q), heads(k), heads(v), heads(g),
                             beta.astype(jnp.float32))
    return o.reshape(n, t, h * cd.HEAD_DIM)


def vjp_of(fn):
    return jax.jit(lambda ct, *xs: jax.vjp(fn, *xs)[1](ct))


def load_parent(checkout):
    """`checkout`'s channel_delta.py as a module beside this tree's: its
    relative imports are this tree's (`gated_delta.py` and the package)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        cd.__name__ + "_parent",
        os.path.join(checkout, "paddle_tpu/ops/pallas/channel_delta.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def inverse_less_substitution(cd):
    """`channel_delta_inverse` with A written where (I + A)^-1 goes: its
    decayed products (A and P) and its reads and writes alone."""
    def call(*xs):
        kept = cd._inverse_side_by_side
        cd._inverse_side_by_side = lambda a, iotas: a
        try:
            return cd._inverse_call.__wrapped__(*xs)
        finally:
            cd._inverse_side_by_side = kept

    return jax.jit(call)


def measure(cd, args):
    """The kernels' ms a call and the op's errors against the
    recurrence, by module `cd`."""
    bf16 = jnp.bfloat16
    (q, k, v, g, beta), ct = operands(args.rows, args.heads, args.seed, bf16)
    h = args.heads
    out = {"ms": {}}
    m, p = jax.jit(cd._inverse_call)(q, k, k, g)
    ops = jax.jit(cd._operands_fwd_call)(q, k, k, v, g, m)
    dec = jnp.full((h, args.rows // cd.CHUNK, cd.HEAD_DIM), 0.9, jnp.float32)
    scan = lambda *xs: cd.scan_kernel(*xs, h)  # noqa: E731
    op = lambda *xs: cd.channel_delta_rule(*xs, use_kernel=True)  # noqa: E731
    for name, fn, xs in [
            ("inverse", jax.jit(cd._inverse_call), (q, k, k, g)),
            ("inverse_less_substitution", inverse_less_substitution(cd),
             (q, k, k, g)),
            ("operands_fwd", jax.jit(cd._operands_fwd_call),
             (q, k, k, v, g, m)),
            ("operands_bwd", jax.jit(cd._operands_bwd_call),
             (q, k, k, v, g, m) + tuple(ops) + (p,)),
            ("scan_fwd", jax.jit(scan), tuple(ops) + (p, dec)),
            ("scan_fwd_bwd", vjp_of(scan), (ct,) + tuple(ops) + (p, dec)),
            ("op_fwd", jax.jit(op), (q, k, v, g, beta)),
            ("op_fwd_bwd", vjp_of(op), (ct, q, k, v, g, beta))]:
        out["ms"][name] = ms_a_call(fn, xs, args.repeats)
    # the decayed products' share of `channel_delta_inverse`
    out["products_share_of_inverse"] = (
        out["ms"]["inverse_less_substitution"] / out["ms"]["inverse"])

    def err(got, want):
        got, want = (np.asarray(x, np.float64) for x in (got, want))
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    xs32, ct32 = operands(args.rows, 2, args.seed + 1, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = (jax.jit(sequential)(*xs32),) + vjp_of(sequential)(ct32, *xs32)
        got = (jax.jit(op)(*xs32),) + vjp_of(op)(ct32, *xs32)
    out["against_scan"] = {n: err(a, b) for n, a, b in zip(names, got, want)}
    low = lambda x: x.astype(bf16) if x.shape[-1] != 2 else x  # noqa: E731
    xs16 = tuple(low(x) for x in xs32[:3]) + xs32[3:]
    got = (jax.jit(op)(*xs16),) + vjp_of(op)(ct32.astype(bf16), *xs16)
    out["bf16_against_scan"] = {n: err(a, b)
                                for n, a, b in zip(names, got, want)}
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--rows", type=int, default=8192)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--parent", help="a checkout of the parent commit: "
                        "its channel_delta.py is measured in the same call")
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": f"{device.platform} is no TPU"}))
        return 1
    out = {"device": device.device_kind, "rows": args.rows,
           "heads": args.heads, "chunk": cd.CHUNK, **measure(cd, args)}
    if args.parent:
        out["parent"] = measure(load_parent(args.parent), args)
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_channel_delta.log", "a") as f:
        f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
