"""What the program itself says about one traced step, for the readers
in `layer_metrics/` that share it.

The program (PR 24 on) joins a trace's device op events to its own
names (`paddle_tpu/observe/trace.py op_rows`: one row per program and
HLO instruction with self time, `cost._bucket`, FLOPs, fluid op type
and phase) and times the host phases of `Executor.run`
(`runtime_stats.recent`).  This file calls the join once per trace and
memoises it, picks the step program, and holds the arithmetic the
readers share.  A program that has neither (any commit before PR 24)
gives `None` everywhere, and the readers leave their metric out.

It sits beside `run.py`, not in `layer_metrics/`, where `run.py` takes
every `*.py` for a reader.
"""

from __future__ import annotations

import functools
import json
import os
import statistics
import time

HERE = os.path.dirname(os.path.abspath(__file__))
MXU_BUCKETS = ("matmul", "conv")


@functools.lru_cache(maxsize=4)
def _chip0_rows(path, lo, hi):
    """The program's rows for chip 0 in [lo, hi), or None where the
    program has no such join."""
    from paddle_tpu.observe import trace

    op_rows = getattr(trace, "op_rows", None)
    if op_rows is None:
        return None
    t0 = time.perf_counter()
    rows = op_rows(path, windows={0: (lo, hi)}, chips=(0,))
    print(json.dumps({"step_anatomy_join_s": time.perf_counter() - t0,
                      "rows": len(rows)}), flush=True)
    return rows


def anatomy(run):
    """`{"steps", "rows", "step_rows", "step_module"}` of the traced
    window on chip 0: every row, and those of the step program, the
    one with the most time (as `trace_reduce.step_window` picks it).
    None without a trace or without the program's join."""
    reduced = run["trace"]
    if not reduced:
        return None
    c0 = reduced["chip0"]
    rows = _chip0_rows(reduced["path"], c0["lo"], c0["hi"])
    if not rows:
        return None
    by_module = {}
    for r in rows:
        if r["module"] is not None:
            by_module[r["module"]] = (by_module.get(r["module"], 0.0)
                                      + r["self_s"])
    if not by_module:
        return None
    step = max(by_module, key=by_module.get)
    return {"steps": c0["steps"], "rows": rows, "step_module": step,
            "step_rows": [r for r in rows if r["module"] == step]}


def buckets_ms_per_step(a):
    """`{bucket: ms per step}` of the step program's self time on chip
    0 in the traced window, by `cost._bucket`, and under
    `[other programs]` everything else that ran there: together the
    chip's busy time."""
    out = {}
    for r in a["rows"]:
        key = (r["bucket"] if r["module"] == a["step_module"]
               else "[other programs]")
        out[key] = out.get(key, 0.0) + 1e3 * r["self_s"] / a["steps"]
    return out


def bucket_ms_per_step(run, bucket):
    """One bucket of `buckets_ms_per_step` (0 if the step has no such
    instruction), or None without the program's join."""
    a = anatomy(run)
    return None if a is None else buckets_ms_per_step(a).get(bucket, 0.0)


def fluid_op_table(a, top=20):
    """`[op type, phase, ms per step, share of the step program's op
    time]` for its `top` longest (fluid op type, phase) pairs."""
    by_op, total = {}, 0.0
    for r in a["step_rows"]:
        key = (r["op_type"] or "[no scope]", r["phase"])
        by_op[key] = by_op.get(key, 0.0) + r["self_s"]
        total += r["self_s"]
    rows = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return [[op, phase, 1e3 * s / a["steps"], s / total]
            for (op, phase), s in rows]


def executor_ms(run, phase):
    """Median host milliseconds of one phase of `Executor.run` over
    the process's runs, from the program's own ring of durations (not
    from the xplane: the profiler slows the host).  None on an
    untraced run, as for the device readers, and where the program
    has no such ring."""
    if not run["trace"]:
        return None
    from paddle_tpu.observe.monitoring import runtime_stats

    recent = getattr(runtime_stats, "recent", None)
    durations = recent(phase) if recent else None
    return 1e3 * statistics.median(durations) if durations else None


def peak_flops():
    """`bf16_flops` of this device's row in `peaks.json`, or None."""
    import jax

    with open(os.path.join(HERE, "peaks.json")) as f:
        row = json.load(f).get(jax.devices()[0].device_kind)
    return row["bf16_flops"] if isinstance(row, dict) else None
