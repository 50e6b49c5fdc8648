#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, in one process, on the chips.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics with `--trace 0`,
its per-layer metrics with `--trace 1`), `device` and, traced,
`breakdown`.  Earlier lines carry what else is worth reading.

This file is the training loop and nothing else.  It knows no model,
cell or per-layer metric by name: it finds `workloads/<cell>.json`,
`configs/<config>.json`, `models/<family>.py` and
`layer_metrics/<metric>.py` by name under its roots (README.md).

Timing is lagged sync, depth LAG: after dispatching step i the loop
blocks on the loss of step i-LAG, turns it into a host float and stamps
the host clock.  The stamps are completion times; the device never
drains and every loss reaches the host.  The window runs from the stamp
that ends warm-up to the first stamp at or after `--seconds`.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import collections  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402

LAG = 2
# Warm-up is two passes through the pool: the first step compiles (or
# reads the cache), the next LAG fill the pipeline, and by the end of
# the second pass every batch of the pool has been fed once from a
# steady pipeline.  The first pass is also the "before" of the
# loss-fell check.
WARMUP_PASSES = 2
TRACE_SECONDS = 3.0     # at most; a third of a shorter window
TRACE_ROOT = os.path.join(REPO, "chiprun_out", "trace")


# --------------------------------------------------------------------------
# finding things by name
# --------------------------------------------------------------------------

def find(roots, *parts):
    for root in roots:
        path = os.path.join(root, *parts)
        if os.path.exists(path):
            return path
    sys.exit(f"benchmarks/run.py: no {os.path.join(*parts)} under "
             f"{list(roots)}")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        "bench_" + "".join(c if c.isalnum() else "_" for c in stem), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload, roots):
    cell = load_json(find(roots, "workloads", workload + ".json"))
    cell["name"] = workload
    config = load_json(find(roots, "configs", cell["config"] + ".json"))
    family = load_module(find(roots, "models", config["family"] + ".py"))
    return cell, config, family


def layer_readers(cell_name, roots):
    """{metric name: module} for every `layer_metrics/<metric>.py`
    whose META lists this cell (or no cells: every cell)."""
    readers = {}
    for root in roots:
        for path in sorted(glob.glob(
                os.path.join(root, "layer_metrics", "*.py"))):
            name = os.path.splitext(os.path.basename(path))[0]
            module = load_module(path)
            cells = module.META.get("cells")
            if name not in readers and (cells is None
                                        or cell_name in cells):
                readers[name] = module
    return readers


def require_tpu(chips, roots):
    """The device as jax reports it, with its row of peaks.json.
    Anything that is not a TPU with at least `chips` chips and a row
    of peaks ends the run: nothing is measured on a stand-in."""
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        sys.exit(f"benchmarks/run.py: no TPU was found: jax.devices()[0] "
                 f"is {devs[0].platform!r} ({kind!r}); the benchmark "
                 f"measures nothing on a stand-in")
    if len(devs) < chips:
        sys.exit(f"benchmarks/run.py: the cell needs {chips} chips, jax "
                 f"reports {len(devs)}")
    peaks = load_json(find(roots, "peaks.json"))
    if not isinstance(peaks.get(kind), dict):
        sys.exit(f"benchmarks/run.py: peaks.json has no row for device "
                 f"kind {kind!r}")
    return {"platform": devs[0].platform, "kind": kind,
            "count": len(devs), "bf16_flops": peaks[kind]["bf16_flops"]}


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------

def build(config, cell, family, seed):
    """Program pair, start-up run (weights on the device from the
    seed), and the mesh wrapper where the cell names one."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        loss = family.build(config)
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
        if cell.get("mesh"):
            from paddle_tpu.parallel import make_mesh

            fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name,
                build_strategy=fluid.BuildStrategy(),
                mesh=make_mesh(cell["mesh"]))
    return exe, main, scope, loss


def residency(main, scope, loss, feed):
    """Where the state lives and what the compiled step exchanges
    (chip_smoke._residency): bytes of persistable state by device, and
    the all-reduces in the SPMD step's text.  One AOT compile of the
    step the window ran, so it reads the compile cache."""
    per_device = {}
    for var in main.global_block().vars.values():
        if not var.persistable or not scope.has_var(var.name):
            continue
        arr = scope.find_var(var.name)
        for sh in getattr(arr, "addressable_shards", None) or ():
            per_device[sh.device.id] = (per_device.get(sh.device.id, 0)
                                        + sh.data.nbytes)
    text = main._compiled_wrapper.compiled_step(
        feed, [loss.name], scope).as_text()
    return {"state_bytes_per_device": per_device,
            "all_reduces": text.count(" all-reduce(")
            + text.count(" all-reduce-start(")}


# --------------------------------------------------------------------------
# the loop
# --------------------------------------------------------------------------

def train_window(exe, main, scope, loss, pool, seconds, trace_dir):
    """Warm up, then step for `seconds`.  Returns the stamps, losses
    and dispatch times of every step and the window's bounds."""
    import jax
    from jax.profiler import TraceAnnotation

    from paddle_tpu.observe.monitoring import runtime_stats

    warmup = WARMUP_PASSES * len(pool)
    trace_len = min(TRACE_SECONDS, seconds / 3.0)
    trace_at = (seconds - trace_len) / 2.0
    pending = collections.deque()
    stamps, losses, dispatches = [], [], []
    t_first = time.perf_counter()
    window_t0 = window_t1 = snap = traced_from = None
    tracing = False
    i = 0

    def sync():
        with TraceAnnotation("bench.lagged_sync"):
            losses.append(float(np.asarray(pending.popleft())
                                .reshape(-1)[0]))

    while window_t1 is None:
        with TraceAnnotation("bench.make_batch"):
            feed = pool[i % len(pool)]
        t = time.perf_counter()
        with TraceAnnotation("bench.executor_run"):
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope, return_numpy=False)
        dispatches.append((t, time.perf_counter() - t, tracing))
        pending.append(lv)
        i += 1
        if len(pending) <= LAG:
            continue
        sync()
        now = time.perf_counter()
        stamps.append(now)
        if len(stamps) == 1:
            first_step_s = now - t_first
        if len(stamps) == warmup:
            window_t0 = now
            snap = runtime_stats.snapshot()
        elif window_t0 is None:
            continue
        elif now - window_t0 >= seconds:
            window_t1 = now
        elif trace_dir and traced_from is None \
                and now - window_t0 >= trace_at:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # spans, not every call
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            tracing, traced_from = True, time.perf_counter()
        elif tracing and time.perf_counter() - traced_from >= trace_len:
            jax.profiler.stop_trace()
            tracing = False
    if tracing:
        jax.profiler.stop_trace()
    compiles = runtime_stats.delta(snap)["compiles"]
    while pending:          # past the window: drained, not stamped
        sync()
    return {"stamps": stamps, "losses": losses, "dispatches": dispatches,
            "warmup": warmup, "window_t0": window_t0,
            "window_t1": window_t1, "compiles_in_window": compiles,
            "first_step_s": first_step_s}


def reduce_trace(trace_dir, chips):
    """The traced slice, cut to whole steps on each chip.  None where
    the trace holds no device plane (a CPU rehearsal)."""
    path = trace_reduce.newest_xplane(trace_dir)
    if path is None:
        return None
    tr = trace_reduce.load(path)
    per_chip = {}
    for chip in sorted(tr["ops"])[:chips]:
        window = trace_reduce.step_window(tr["modules"].get(chip, []))
        if window is None:
            continue
        lo, hi, steps = window
        busy = trace_reduce.busy_union(tr["ops"][chip])
        per_chip[chip] = {
            "lo": lo, "hi": hi, "steps": steps, "busy": busy,
            "ops": tr["ops"][chip],
            "busy_s": trace_reduce.busy_seconds(busy, lo, hi),
            "collective_s": trace_reduce.collective_seconds(
                tr["ops"][chip], lo, hi)}
    if not per_chip:
        return None
    return {"path": path, "chips": per_chip,
            "chip0": per_chip[min(per_chip)], "spans": tr["spans"]}


def window_of(w, pool_size):
    """The window's bookkeeping: one gap per step completed between the
    stamp that ends warm-up and the first stamp at or after --seconds."""
    k = w["warmup"]
    stamps = [s for s in w["stamps"][k - 1:] if s <= w["window_t1"]]
    gaps_ms = np.diff(stamps) * 1e3
    losses = w["losses"][k:k + len(gaps_ms)]
    return {"window_s": w["window_t1"] - w["window_t0"],
            "gaps_ms": gaps_ms, "attempted": len(gaps_ms),
            "failed": int(sum(not math.isfinite(x) for x in losses)),
            "loss_first_pass": float(np.mean(w["losses"][:pool_size])),
            "loss_last_pass": float(np.mean(losses[-pool_size:]))}


def end_to_end(win, family, config, cell, peak, setup_s):
    rate = win["attempted"] / win["window_s"]
    metrics = {"mfu": {
        "value": 100.0 * family.train_flops(config, cell) * rate
        / (cell["chips"] * peak), "unit": "%"}}
    for name, u in family.units(config, cell).items():
        metrics[name] = {"value": u["per_step"] * rate, "unit": u["unit"]}
    metrics["step_ms_p95"] = {
        "value": float(np.percentile(win["gaps_ms"], 95)), "unit": "ms"}
    metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    return metrics


def per_layer(run, readers):
    """The traced run's part of the line: per-layer metrics, the
    device's busy seconds and the breakdown."""
    metrics, device, breakdown = {}, {}, None
    for name, reader in readers.items():
        value = reader.compute(run)
        if value is not None:
            metrics[name] = {"value": float(value),
                             "unit": reader.META["unit"]}
    reduced = run["trace"]
    if reduced:
        per = list(reduced["chips"].values())
        device = {"busy_s": sum(c["busy_s"] for c in per) / len(per),
                  "window_s": sum(c["hi"] - c["lo"] for c in per)
                  / len(per)}
        c0 = reduced["chip0"]
        table = trace_reduce.op_table(c0["ops"], c0["lo"], c0["hi"])
        breakdown = {
            "device_ops": [[n, s] for n, s, _ in table],
            "idle_gaps": trace_reduce.longest_gaps(
                c0["busy"], c0["lo"], c0["hi"], reduced["spans"])}
        print(json.dumps({"traced_steps": c0["steps"],
                          "op_table_share": table,
                          "trace": reduced["path"]}), flush=True)
    return metrics, device, breakdown


def run_cell(workload, seed, seconds, trace, roots=(HERE,), device=None,
             t0=None):
    """Run one cell and return the result object of the last line.
    `device` is a test's stand-in for the TPU check ({platform, kind,
    count, bf16_flops}); the command never passes it."""
    t0 = PROCESS_T0 if t0 is None else t0
    marks = {}                      # seconds since t0, by set-up phase

    def mark(name):
        marks[name] = time.perf_counter() - t0

    cell, config, family = load_cell(workload, roots)
    chips = cell["chips"]
    import jax

    if device is None:
        from paddle_tpu.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        # small programs (the start-up ops) are cached too, so a warm
        # run compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        device = require_tpu(chips, roots)
        print(json.dumps({"cache_dir": cache_dir}), flush=True)
    peak = device.pop("bf16_flops")
    mark("device")

    from paddle_tpu.observe.monitoring import runtime_stats

    exe, main, scope, loss = build(config, cell, family, seed)
    mark("built_and_started")
    rng = np.random.default_rng(seed)
    pool = [family.make_batch(config, cell, rng)
            for _ in range(cell["pool"])]
    if cell["feed"] == "device":
        import jax.numpy as jnp

        pool = [{k: jnp.asarray(v) for k, v in b.items()} for b in pool]
        jax.block_until_ready(pool)
    elif cell["feed"] != "host":
        sys.exit(f"benchmarks/run.py: feed {cell['feed']!r} is neither "
                 f"'host' nor 'device'")
    mark("pool")
    trace_dir = None
    if trace:
        trace_dir = os.path.join(TRACE_ROOT, workload)
        shutil.rmtree(trace_dir, ignore_errors=True)

    w = train_window(exe, main, scope, loss, pool, seconds, trace_dir)
    totals = runtime_stats.snapshot()
    win = window_of(w, len(pool))
    checks = {
        "finite": win["failed"] == 0,
        "no_compile_in_window": w["compiles_in_window"] == 0,
        "loss_fell": win["attempted"] >= len(pool)
        and win["loss_last_pass"] < win["loss_first_pass"]}
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    if cell.get("mesh"):
        res = residency(main, scope, loss, pool[0])
        sizes = list(res["state_bytes_per_device"].values())
        checks["state_on_every_chip"] = (len(sizes) == chips
                                         and len(set(sizes)) == 1)
        checks["all_reduce_in_step"] = res["all_reduces"] > 0
        print(json.dumps({"residency": res}), flush=True)
    print(json.dumps({
        "workload": workload, "seed": seed, "window_s": win["window_s"],
        "step_ms_samples": win["attempted"],
        "step_ms_median": float(np.median(win["gaps_ms"])),
        "step_ms_max": float(win["gaps_ms"].max()),
        "setup_marks_s": dict(marks, first_step=marks["pool"]
                              + w["first_step_s"]),
        "compiles_total": totals["compiles"],
        "compile_s_total": totals["compile_time_s"],
        "loss_first_pass": win["loss_first_pass"],
        "loss_last_pass": win["loss_last_pass"],
        "checks": checks}), flush=True)

    result = {
        "correct": all(checks.values()), "attempted": win["attempted"],
        "failed": win["failed"], "metrics": None,
        "device": dict(device, memory_peak_bytes=max(
            int(s.get("peak_bytes_reserved", 0)) for s in stats))}
    if not trace:
        result["metrics"] = end_to_end(win, family, config, cell, peak,
                                       w["window_t0"] - t0)
        return result
    run = {"cell": cell, "config": config, "chips": chips,
           "steps": win["attempted"], "window_s": win["window_s"],
           # profiling slows the host: dispatch is read outside it
           "dispatch_s": [d for t, d, traced in w["dispatches"]
                          if not traced
                          and w["window_t0"] <= t < w["window_t1"]],
           "compiles_in_window": w["compiles_in_window"],
           "memory_stats": stats,
           "trace": reduce_trace(trace_dir, chips)}
    result["metrics"], busy, breakdown = per_layer(
        run, layer_readers(workload, roots))
    result["device"].update(busy)
    if breakdown:
        result["breakdown"] = breakdown
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
