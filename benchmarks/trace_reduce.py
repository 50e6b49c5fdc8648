"""From a profiler trace to busy/idle, an op table and gap attribution.

`load` reads the `.xplane.pb` that `jax.profiler` wrote and returns
plain lists of `(name, start_s, duration_s)` tuples; everything after
that is a pure function of such lists, so the arithmetic is tested on
hand-made events (tests/benchmark) and no later PR can move it.

What the planes and lines of a TPU v5e trace look like, read by hand
(PERF.md section 3, under `collective_ms_per_step`): one plane per chip
named `/device:TPU:<n>`; on it the line `XLA Ops` holds one event per
executed HLO instruction (fusions, copies, collectives) and the line
`XLA Modules` one event per run of a compiled program; the host's
threads are lines of the plane `/host:CPU`, and the harness's
`TraceAnnotation` spans sit on the line of the thread that wrote them.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
# an op is a collective by the HLO opcode its name starts with; the
# async forms end in -start/-done and both halves carry the prefix
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")


def newest_xplane(trace_dir):
    """The newest `.xplane.pb` under `trace_dir`, or None."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path):
    """Read one trace.  Returns
    `{"ops": {chip: [event]}, "modules": {chip: [event]}, "spans": [event]}`
    with `event = (name, start_s, duration_s)` on the trace's one
    clock; `chip` is the number in the device plane's name; `spans` are
    the host events whose name starts with `bench.`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"ops": {}, "modules": {}, "spans": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            chip = int(plane.name[len(DEVICE_PLANE_PREFIX):].split()[0])
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    out[key][chip] = sorted(
                        ((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                         for e in line.events), key=lambda e: e[1])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["spans"] += [
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX)]
    out["spans"].sort(key=lambda e: e[1])
    return out


_ARRAY_TYPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def short_name(name):
    """`%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...)`, the whole
    HLO instruction the trace prints as an op's name, cut to
    `fusion.3 bf16[8,128]`: its name and its first result type."""
    head, _, rest = name.partition(" = ")
    shape = _ARRAY_TYPE.search(rest)
    return head.lstrip("%") + (" " + shape.group(0) if shape else "")


def busy_union(events):
    """Merge `(name, start, duration)` events into disjoint, sorted
    `(start, end)` intervals: nested and overlapping events count
    once."""
    merged = []
    for start, end in sorted((s, s + d) for _, s, d in events):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def clip(intervals, lo, hi):
    """The parts of disjoint sorted intervals that lie in [lo, hi]."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_seconds(intervals, lo, hi):
    return sum(e - s for s, e in clip(intervals, lo, hi))


def idle_gaps(intervals, lo, hi):
    """The `(start, end)` gaps of [lo, hi] that no interval covers."""
    gaps, at = [], lo
    for s, e in clip(intervals, lo, hi):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def covering_span(t, spans):
    """The name of the innermost (latest-started) span that covers
    time `t`, or "none"."""
    name = "none"
    for span, start, dur in spans:
        if start <= t < start + dur:
            name = span
    return name


def longest_gaps(intervals, lo, hi, spans, top=5):
    """The `top` longest idle gaps as `[span name, seconds]`, each
    named after the host span that covers the gap's start."""
    gaps = sorted(idle_gaps(intervals, lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    return [[covering_span(s, spans), e - s] for s, e in gaps]


def op_table(events, lo, hi, top=10):
    """`[short name, seconds, share of summed op time]` for the `top`
    ops by total duration among events that start in [lo, hi).  Shares are of
    the summed durations of all those events, so they sum to 1 over
    the whole table."""
    total, by_name = 0.0, {}
    for name, start, dur in events:
        if lo <= start < hi:
            name = short_name(name)
            by_name[name] = by_name.get(name, 0.0) + dur
            total += dur
    rows = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [[name, secs, secs / total] for name, secs in rows]


def is_collective(name):
    return name.lstrip("%").startswith(COLLECTIVE_PREFIXES)


def collective_seconds(events, lo, hi):
    """Summed device duration of the collective events that start in
    [lo, hi)."""
    return sum(d for name, s, d in events
               if lo <= s < hi and is_collective(name))


def step_window(modules, skip=2):
    """The traced window on one chip, cut to whole steps: from the
    start of the step program's run number `skip` to the start of its
    last run in the trace.  The step program is the module name with
    the most total time.  Returns `(lo, hi, steps)` or None when the
    trace holds fewer than two runs after the skipped ones.  The first
    runs are skipped because starting the profiler stalls the host and
    drains the device: that gap is the profiler's, not the program's."""
    total = {}
    for name, _, dur in modules:
        total[name] = total.get(name, 0.0) + dur
    if not total:
        return None
    step = max(total, key=total.get)
    starts = [s for name, s, _ in modules if name == step][skip:]
    if len(starts) < 2:
        return None
    return starts[0], starts[-1], len(starts) - 1
