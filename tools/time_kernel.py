"""Time one kernel family alone, on the chip: the one way this repo
times a kernel before a whole-cell run.

    chiprun -- python tools/time_kernel.py <family> [--cell CELL]
        [--sweep NAME=v1,v2,...] [--parent CHECKOUT] [--repeats N]
        [--seed S]

`<family>` names an entry of `tools/kernel_cases.py FAMILIES` (one a
kernel file of `paddle_tpu/ops/pallas/`), `--cell` a workload of
`BENCHMARK.json` that runs it: the operands are drawn at that cell's
shape (the family's first cell where left out).  Every WAY of the entry
(`kernel`; the composition the module keeps as its fall-back, `xla` or
`view`; a layer of the cell around the kernel, where the entry has one)
is timed forward and forward + backward, a VJP against a fixed
cotangent that returns every gradient the operands have: a warm-up
call, then five rounds of `--repeats` calls dispatched back to back and
waited for once (so the host's ~0.5 ms a wait is in no kernel's time),
the median, in ms a call; a way too slow for that (XLA's attention at
16384 rows) gets the calls half a second holds, two at the least.
`against_<fall-back>`: the result and every gradient of `kernel` (and
`parent`) against the fall-back's on the same operands, as the norm of
the difference over the norm, in float64 (an operand that is several
side by side, `gated_delta`'s QKV, a part at a time: dq, dk, dv).

`--sweep NAME=v1,v2,...` (may be given again) times `kernel` alone once a
value: NAME a module constant of the kernel file (`CHANNEL_TILE`,
`FUSED_ACCUMULATOR_BUDGET`, ...) or a keyword of the entry's `kernel`
builder (`block`, `row_tile`, ...) that the entry lists as sweepable,
a value an integer or, for a tile, `AxB` (query x key side); jax's caches are cleared around each, a
tiling Mosaic refuses is reported as its message.  `--parent CHECKOUT`
(`git archive <commit> | tar -x -C _parent`): that checkout's kernel
file beside this tree's (its relative imports are this tree's package),
timed through the same builder as the way `parent`; a parent whose
entry does not take the entry's operands is said so, exit 1.

The last stdout line is one JSON object; the same line is appended to
`chiprun_out/time_kernel.log`.  It exits non-zero off a TPU: a CPU time
is no device time.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from kernel_cases import FAMILIES  # noqa: E402

ROUNDS = 5
ROUND_MS = 500.0        # a round of a slow way is cut to this, two calls at least
PALLAS = "paddle_tpu.ops.pallas."


def ms_a_call(fn, args, repeats):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    one = 1e3 * (time.perf_counter() - t0)
    repeats = max(2, min(repeats, int(ROUND_MS / one)))
    rounds = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(repeats)]
        jax.block_until_ready(outs)
        rounds.append(1e3 * (time.perf_counter() - t0) / repeats)
    return float(np.median(rounds))


def vjp_of(fn):
    return jax.jit(lambda ct, *xs: jax.vjp(fn, *xs)[1](ct))


def err(got, want):
    """The norm of the difference over the norm (the difference's own
    where there is nothing to be off from)."""
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) or 1.0))


def by_part(names, parts, values):
    """{label: value} of a way's result and gradients: `y`, then `d<name>`
    an operand, one that `parts` cuts (`{name: {part: (first lane,
    end)}}`: several operands side by side) a `d<part>` a range."""
    labelled = {"y": values[0]}
    for name, grad in zip(names, values[1:]):
        for part, (first, end) in parts.get(name, {name: (0, None)}).items():
            labelled["d" + part] = grad[..., first:end]
    return labelled


def load_parent(checkout, module):
    """`checkout`'s `ops/pallas/<module>.py` as a module beside this
    tree's: its relative imports are this tree's package."""
    spec = importlib.util.spec_from_file_location(
        PALLAS + module + "_parent",
        os.path.join(checkout, "paddle_tpu/ops/pallas", module + ".py"))
    parent = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = parent
    spec.loader.exec_module(parent)
    return parent


def cotangent(fn, xs, seed):
    """A fixed cotangent of `fn(*xs)`, drawn from the result's shape (a
    result of several arrays: one each)."""
    key = jax.random.PRNGKey(seed + 1)
    return jax.tree.map(
        lambda out: jax.random.normal(key, out.shape, jnp.float32)
        .astype(out.dtype), jax.eval_shape(fn, *xs))


def timings(fn, xs, ct, repeats):
    return {"fwd": ms_a_call(jax.jit(fn), xs, repeats),
            "fwd_bwd": ms_a_call(vjp_of(fn), (ct,) + xs, repeats)}


def parse_sweep(family, text):
    """`NAME=v1,v2` -> (NAME, [values]); a name the family does not list
    is refused."""
    name, _, values = text.partition("=")
    if name not in FAMILIES[family].sweepable or not values:
        raise ValueError(
            f"--sweep {text!r}: {family} sweeps "
            f"{', '.join(FAMILIES[family].sweepable) or 'nothing'}, as "
            f"NAME=v1,v2,...")
    return name, [tuple(int(x) for x in v.split("x")) if "x" in v else int(v)
                  for v in values.split(",")]


def swept(entry, mod, shape, aux, name, value, xs, ct, repeats):
    """`kernel`'s timings with `name` set to `value`: a constant of `mod`
    for the time of the call, or a keyword of the builder."""
    constant = name.isupper()
    kept = getattr(mod, name) if constant else None
    if constant:
        setattr(mod, name, value)
    jax.clear_caches()
    try:
        return timings(
            entry.ways["kernel"](mod, shape, aux,
                                 **({} if constant else {name: value})),
            xs, ct, repeats)
    except Exception as e:      # a tiling Mosaic refuses
        return str(e)[:200]
    finally:
        if constant:
            setattr(mod, name, kept)
        jax.clear_caches()


def run(family, cell=None, sweeps=(), parent=None, repeats=10, seed=0):
    """The JSON object of one call (`main` holds it to a TPU); `sweeps`
    as `parse_sweep` gives them."""
    entry = FAMILIES[family]
    cell = cell or next(iter(entry.cells))
    shape = entry.cells[cell]
    mod = importlib.import_module(PALLAS + entry.module)
    xs, aux = entry.operands(shape, seed)
    fns = {name: way(mod, shape, aux) for name, way in entry.ways.items()}
    if parent:
        try:
            fns["parent"] = entry.ways["kernel"](
                load_parent(parent, entry.module), shape, aux)
            jax.eval_shape(fns["parent"], *xs)
        except (AttributeError, TypeError, ValueError) as e:
            return {"family": family, "cell": cell, "error":
                    f"{parent}'s {entry.module}.py does not take this "
                    f"entry's operands: {e}"[:400]}
    ct = cotangent(fns["kernel"], xs, seed)
    out = {"family": family, "cell": cell, "shape": shape,
           "device": jax.devices()[0].device_kind, "repeats": repeats,
           "seed": seed, "unit": "ms a call", "ms": {}}
    if sweeps:
        out["ms"]["kernel"] = timings(fns["kernel"], xs, ct, repeats)
        out["sweep"] = {
            f"{name}={v if isinstance(v, int) else 'x'.join(map(str, v))}":
            swept(entry, mod, shape, aux, name, v, xs, ct, repeats)
            for name, values in sweeps for v in values}
        return out
    fns.update({name: way(mod, shape, aux)
                for name, way in entry.composites.items()})
    for name, fn in fns.items():
        out["ms"][name] = timings(fn, xs, ct, repeats)
    fallback = list(entry.ways)[1]

    def results(fn):
        return (jax.jit(fn)(*xs),) + tuple(vjp_of(fn)(ct, *xs))

    parts = entry.parts(shape)
    want = by_part(entry.names, parts, results(fns[fallback]))
    out["against_" + fallback] = {
        name: {label: err(got, want[label]) for label, got in by_part(
            entry.names, parts, results(fn)).items()}
        for name, fn in fns.items()
        if name != fallback and name not in entry.composites}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("family", choices=sorted(FAMILIES))
    parser.add_argument("--cell", help="a BENCHMARK.json workload that runs "
                        "the family (default: the family's first)")
    parser.add_argument("--sweep", action="append", default=[],
                        metavar="NAME=v1,v2,...")
    parser.add_argument("--parent", metavar="CHECKOUT")
    parser.add_argument("--repeats", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    if args.cell and args.cell not in FAMILIES[args.family].cells:
        parser.error(f"{args.family} runs in "
                     f"{', '.join(FAMILIES[args.family].cells)}")
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"error": f"{device.platform} is no TPU"}))
        return 1
    try:
        sweeps = [parse_sweep(args.family, text) for text in args.sweep]
    except ValueError as e:
        parser.error(str(e))
    out = run(args.family, args.cell, sweeps, args.parent, args.repeats,
              args.seed)
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_kernel.log", "a") as log:
        log.write(line + "\n")
    print(line)
    return 1 if "error" in out else 0


if __name__ == "__main__":
    sys.exit(main())
