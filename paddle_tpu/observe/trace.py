"""Post-trace attribution: join a captured jax.profiler trace to the
program's own names, into per-instruction rows and a per-fluid-op time
table.

The reference Fluid profiler printed a per-op summary table after the
profiled region (python/paddle/fluid/profiler.py `sorted_key`; the data
came from RecordEvent ranges + the CUPTI DeviceTracer).  On TPU the raw
material is the XPlane protobuf jax.profiler writes: a device plane
carries one timed event per executed HLO instruction, and the trace's
serialized HLO modules carry each instruction's `metadata.op_name`,
which contains the `<op_type>:<op_index>` named scopes the executor
emits around every op lowering (core/executor.py _run_one_op).  Joining
the two recovers fluid-op attribution from a device timeline without
any host-side hooks.

Two readers, one each for what the other cannot reach:

- `jax.profiler.ProfileData` reads planes, lines, events and the
  clock (`read_events`): the one path from an xplane to events.
- the minimal protobuf wire-format scanner below reads the
  `/host:metadata` plane's `Hlo Proto` stats (`hlo_protos`), which
  `ProfileData` does not expose (the plane has no lines), and skips
  every other plane's bytes unread.  `observe/cost.py` and
  `observe/memory.py` read HLO and buffer-assignment protos with the
  same scanner, so no tensorflow / tensorboard import is needed.

What a trace looks like (PERF.md section 3).  A TPU writes one plane
per chip, `/device:TPU:<n>`, with the lines `XLA Ops` (one event per
executed instruction, named by the WHOLE instruction text,
`%fusion.157 = (f32[2048]{0:T(1024)}, ...) fusion(...)`; a `while`
event contains its body's events), `XLA Modules` (one event per
program run, `jit_step(<fingerprint>)`), `Steps` and `Async XLA Ops`
(neither is op time).  XLA:CPU writes no device plane: its instruction
events sit on the host's thread lines, named by the bare instruction
name, each with `hlo_module` and `program_id` stats.  `join_events` is
a pure function of plain tuples shaped either way.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Tuple

# --------------------------------------------------------------------------
# minimal protobuf wire-format scanner
# --------------------------------------------------------------------------


def _uvarint(buf: bytes, i: int) -> Tuple[int, int]:
    x = 0
    s = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << s
        if not b & 0x80:
            return x, i
        s += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, Any]]:
    """Yield (field_number, wire_type, value) over one message's bytes.
    Length-delimited values are returned as raw bytes (caller decides
    whether they are strings or sub-messages)."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _uvarint(buf, i)
        fno, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _uvarint(buf, i)
        elif wt == 2:
            ln, i = _uvarint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        else:  # groups (3/4) never appear in these schemas
            raise ValueError(f"unsupported wire type {wt}")
        yield fno, wt, v


def _first(buf: bytes, fno: int, default=None):
    for f, _wt, v in _fields(buf):
        if f == fno:
            return v
    return default


def _utf8(v, default: str = "") -> str:
    if isinstance(v, bytes):
        return v.decode("utf-8", "replace")
    return default if v is None else str(v)


# --------------------------------------------------------------------------
# the /host:metadata plane: program name -> serialized HloProto
# --------------------------------------------------------------------------

# XSpace:           planes=1
# XPlane:           name=2 lines=3 event_metadata=4(map) stat_metadata=5(map)
# XEventMetadata:   id=1 name=2 display_name=3 stats=5
# XStatMetadata:    id=1 name=2
# XStat:            metadata_id=1 ... bytes=6

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def _map_value(entry) -> bytes:
    return _first(entry, 2, b"")


def hlo_protos(path: str) -> Dict[str, bytes]:
    """{program name: serialized HloProto} of one .xplane.pb file: the
    `Hlo Proto` stat of each event-metadata entry of the
    `/host:metadata` plane (`jit_step(1025)` on XLA:CPU,
    `jit_step(<fingerprint>)` on a TPU).  Every other plane is skipped
    by its length, unread."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, bytes] = {}
    for f, _wt, plane in _fields(space):
        if f != 1 or _utf8(bytes(_first(plane, 2, b""))) != METADATA_PLANE:
            continue
        plane = bytes(plane)
        stat_ids = {_first(_map_value(v), 1, 0)
                    for pf, _pwt, v in _fields(plane) if pf == 5
                    and _utf8(_first(_map_value(v), 2, b""))
                    == HLO_PROTO_STAT}
        for pf, _pwt, v in _fields(plane):
            if pf != 4:
                continue
            meta = _map_value(v)
            for mf, _mwt, stat in _fields(meta):
                if mf == 5 and _first(stat, 1, 0) in stat_ids:
                    proto = _first(stat, 6, b"")
                    if proto:
                        out[_utf8(_first(meta, 2, b""))] = proto
    return out


# --------------------------------------------------------------------------
# the program's names inside an HLO op_name
# --------------------------------------------------------------------------

# the executor's scope convention: "<op_type>:<op_index>".  jax
# transforms WRAP scope segments — under value_and_grad the forward
# lowers as "jvp(mul:3)" and the backward as "transpose(jvp(mul:3))" —
# so a scope may be delimited by parens, not just "/".
_FLUID_SCOPE_RE = re.compile(
    r"(?:^|[/(])([A-Za-z0-9_.\-]+):(\d+)(?=[/)]|$)")


# the same, the LAST one of an op_name (the innermost scope)
_LAST_FLUID_SCOPE_RE = re.compile(r"(?s:.*)" + _FLUID_SCOPE_RE.pattern)
_SCOPE_PATH_RE = re.compile(r"((?:[A-Za-z0-9_.\-]+/)*)$")


def fluid_op_of(op_name: str) -> Optional[str]:
    """Innermost `<op_type>:<index>` scope segment of an HLO op_name
    (including transform-wrapped `jvp(...)` / `transpose(jvp(...))`
    forms), or None when the instruction carries no fluid
    attribution."""
    hits = _FLUID_SCOPE_RE.findall(op_name)
    return hits[-1][0] if hits else None


def name_scope_of(op_name: str) -> str:
    """The `fluid.name_scope()` path an instruction's op was built
    under ("" for none): the executor lowers such an op as
    "<path>/<op_type>:<op_index>", so the path is the run of plain
    segments that ends at the innermost fluid scope (a `jit(...)` or a
    transform's `jvp(` ends it; a `while/body` of jax's own between
    the two would read as part of it, so ask for a segment, not for
    equality)."""
    return fluid_scope_of(op_name)[1]


def fluid_scope_of(op_name: str) -> Tuple[Optional[str], str]:
    """(`fluid_op_of`, `name_scope_of`) in one pass over the op_name."""
    hit = _LAST_FLUID_SCOPE_RE.match(op_name)
    if hit is None:
        return None, ""
    return hit.group(1), _SCOPE_PATH_RE.search(
        op_name[:hit.start(1)]).group(1).rstrip("/")


def phase_of(op_name: str) -> str:
    """`backward` for an op_name under `transpose(jvp(`, `forward`
    under `jvp(`, else `other` (optimizer and feed ops, which the
    executor lowers outside value_and_grad)."""
    if "transpose(jvp(" in op_name:
        return "backward"
    return "forward" if "jvp(" in op_name else "other"


def instruction_name(event_name: str) -> str:
    """A TPU op event is named by the whole instruction text: cut
    `%fusion.157 = (f32[2048]{0:T(1024)}, ...) fusion(...)` to
    `fusion.157`.  XLA:CPU's bare names pass through."""
    return event_name.partition(" = ")[0].lstrip("%")


# what `cost.instruction_costs` says of an instruction beyond its
# bucket, FLOPs, bytes and kernel: its result, the op it works for and,
# in the `layout` bucket, where what it moves comes from
OWNER_KEYS = ("shape", "shape_bytes", "owner", "owner_op_type",
              "owner_name_scope", "owner_phase", "owner_via",
              "owner_consumers", "source", "source_parameter",
              "source_shape")


def _own_scope(op_name: str) -> Dict[str, Any]:
    """The owner keys of an instruction without a cost row: itself
    where it carries a fluid scope, else nobody."""
    op_type, name_scope = fluid_scope_of(op_name)
    out = dict.fromkeys(OWNER_KEYS)
    out.update(owner_op_type=op_type, owner_consumers=0,
               owner_name_scope=name_scope,
               owner_phase=phase_of(op_name),
               owner_via="scope" if op_type else "none")
    return out


def program_map(proto: bytes) -> Dict[str, Dict[str, Any]]:
    """{instruction name: {op_name, bucket, flops, bytes, kernel,
    *OWNER_KEYS}} of one serialized HloProto: every computation's
    instructions with their `metadata.op_name`, and for the entry
    computation's, those of every branch of its `conditional`s (any of
    which may be the one that ran) and those of the body of every
    counted `while` the bucket, FLOPs and bytes (per call), Mosaic
    kernel name and owner keys of `cost.instruction_costs` (elsewhere
    None and no owner keys: the body of a `while` whose trip count is
    not known, whose instructions own themselves by their scope or
    not at all)."""
    from . import cost

    module = cost.HloModule(proto)
    out: Dict[str, Dict[str, Any]] = {}
    keys = ("op_name", "bucket", "flops", "bytes", "kernel") + OWNER_KEYS
    for row in cost.instruction_costs(module, every_branch=True):
        out[row["name"]] = {k: row[k] for k in keys}
    for comp in module.computations.values():
        for instr in comp.instructions:
            if instr.name not in out:
                out[instr.name] = {"op_name": instr.op_name,
                                   "bucket": None, "flops": None,
                                   "bytes": None, "kernel": None}
    return out


# --------------------------------------------------------------------------
# the join, pure on plain tuples
# --------------------------------------------------------------------------

UNJOINED_BUCKET = "unknown"     # the instruction is in no map
# in the map, no cost row: the body of a `while` whose trip count XLA
# did not recover (a counted loop's body has rows of its own)
BODY_BUCKET = "loop"


def _enclosing(modules, starts, t) -> Optional[str]:
    """Name of the module event that contains time `t` (the latest
    started one: a chip runs one program at a time)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i][1] + modules[i][2]:
        return modules[i][0]
    return None


def join_events(ops, modules, programs, window=None, chip=0
                ) -> List[Dict[str, Any]]:
    """Join one line of instruction events to the programs' own names.

    `ops`: `(name, start_s, duration_s)` events of ONE line (a chip's
    `XLA Ops`, or one XLA:CPU thread), or 4-tuples whose last item is
    the program they ran in.  `modules`: the same chip's `XLA Modules`
    events; a 3-tuple op belongs to the module event that contains its
    start.  `programs`: `{module name: program_map(...)}`; an op is
    looked up in ITS program's map, never in a merged one.  `window`:
    `(lo, hi)`; ops that start in `[lo, hi)` count.

    An event nested inside another on the line (a `while`'s body)
    keeps its time and takes it from its parent: `self_s` is time no
    child covers, so the rows of a window sum to its busy union.  The
    body of a counted loop joins like the entry computation: its
    instructions have buckets, FLOPs and bytes of their own (per
    call; `calls` counts the trips), and the `while` row keeps the
    time no body event covers.

    One row per (module, instruction): chip, module, instruction,
    op_name, op_type (fluid), name_scope (the `fluid.name_scope()` path
    the op was built under, "" for none), phase, bucket, flops and bytes (per
    call), kernel (a Mosaic kernel's name, else None), joined (found
    in its program's map), calls, self_s, total_s, max_s, min_s (of
    one call's self time), and `OWNER_KEYS` as
    `cost.instruction_costs` gives them: `owner_op_type`,
    `owner_name_scope`, `owner_phase` of the fluid op the instruction
    works for and `owner_via`, how it was found ("scope": its own;
    "consumer" / "producer": a scopeless copy handed to the op it
    feeds / comes from; "none": nobody, as for every instruction that
    is in no map), `source` of a `layout` row ("state": it re-lays a
    step input, `source_parameter` of shape `source_shape`; "carry";
    "activation").
    """
    modules = sorted(modules, key=lambda e: e[1])
    starts = [m[1] for m in modules]
    events = sorted(ops, key=lambda e: (e[1], -e[2]))
    self_s = [e[2] for e in events]
    stack: List[int] = []               # indices of open events
    for i, ev in enumerate(events):
        while stack and events[stack[-1]][1] + events[stack[-1]][2] \
                <= ev[1]:
            stack.pop()
        if stack:       # the part of it that its parent covers
            parent = events[stack[-1]]
            self_s[stack[-1]] -= min(ev[2], parent[1] + parent[2] - ev[1])
        stack.append(i)
    rows: Dict[Tuple[Optional[str], str], Dict[str, Any]] = {}
    for ev, own in zip(events, self_s):
        if window is not None and not window[0] <= ev[1] < window[1]:
            continue
        module = ev[3] if len(ev) > 3 else _enclosing(modules, starts,
                                                      ev[1])
        name = instruction_name(ev[0])
        r = rows.get((module, name))
        if r is None:
            info = programs.get(module, {}).get(name)
            op_name = info["op_name"] if info else None
            op_type, name_scope = fluid_scope_of(op_name or "")
            owned = (info if info and "owner_via" in info
                     else _own_scope(op_name or ""))
            r = rows[(module, name)] = {
                "chip": chip, "module": module, "instruction": name,
                "op_name": op_name,
                "op_type": op_type,
                "name_scope": name_scope,
                "phase": phase_of(op_name or ""),
                "bucket": (UNJOINED_BUCKET if info is None
                           else info["bucket"] or BODY_BUCKET),
                "flops": info["flops"] if info else None,
                "bytes": info["bytes"] if info else None,
                "kernel": info.get("kernel") if info else None,
                "joined": info is not None,
                "calls": 0, "self_s": 0.0, "total_s": 0.0,
                "max_s": 0.0, "min_s": float("inf"),
                **{k: owned[k] for k in OWNER_KEYS}}
        own = max(own, 0.0)
        r["calls"] += 1
        r["self_s"] += own
        r["total_s"] += ev[2]
        r["max_s"] = max(r["max_s"], own)
        r["min_s"] = min(r["min_s"], own)
    return list(rows.values())


# --------------------------------------------------------------------------
# from a profiler log dir to rows
# --------------------------------------------------------------------------

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
_DEVICE_PLANE_RE = re.compile(r"/device:[A-Za-z]+:(\d+)\s*$")


def _trace_files(profile_dir: str) -> List[str]:
    """Newest run's .xplane.pb files under a jax.profiler log dir (the
    dir itself, or profile_dir/plugins/profile/<timestamp>/), or the
    one file `profile_dir` names."""
    if os.path.isfile(profile_dir):
        return [profile_dir]
    direct = sorted(glob.glob(os.path.join(profile_dir, "*.xplane.pb")))
    if direct:
        return direct
    runs = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*")))
    if not runs:
        raise FileNotFoundError(
            f"no profiler runs under {profile_dir!r}")
    files = sorted(glob.glob(os.path.join(runs[-1], "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(
            f"no .xplane.pb in newest run {runs[-1]!r}")
    return files


def _line_events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]


def read_events(path: str, chips=None) -> Dict[str, Any]:
    """Instruction and module events of one .xplane.pb file, read with
    `jax.profiler.ProfileData`:
    `{"chips": {n: {"ops": [...], "modules": [...]}}, "host": [[...]]}`
    with `(name, start_s, duration_s)` events on the trace's one
    clock.  A device plane gives its `XLA Ops` and `XLA Modules` lines
    and nothing else (`Steps`, `Async XLA Ops` are not op time);
    `chips` keeps only those chips.  Where the file holds no device
    plane (XLA:CPU), `host` holds one list per host thread of the
    events that carry an `hlo_op` stat, as 4-tuples ending in their
    program, `<hlo_module>(<program_id>)`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, Any] = {"chips": {}, "host": []}
    host_plane = None
    for plane in data.planes:
        m = _DEVICE_PLANE_RE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chips is not None and chip not in chips:
                continue
            found = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    found[key] = _line_events(line)
            out["chips"][chip] = found
        elif plane.name == HOST_PLANE:
            host_plane = plane
    if not out["chips"] and host_plane is not None:
        for line in host_plane.lines:
            events = []
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_op" in stats:
                    events.append((
                        e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                        f"{stats.get('hlo_module')}"
                        f"({stats.get('program_id')})"))
            if events:
                out["host"].append(events)
    return out


def op_rows(profile_dir: str, windows=None, chips=None
            ) -> List[Dict[str, Any]]:
    """The rows of `join_events` for every chip (or XLA:CPU thread) of
    the newest trace under `profile_dir` (a log dir or one
    `.xplane.pb`).  `windows`: `{chip: (lo, hi)}` in seconds on the
    trace's clock, for the chips it names; `chips`: read only those.
    Only the programs that ran in the trace are parsed."""
    rows: List[Dict[str, Any]] = []
    for path in _trace_files(profile_dir):
        events = read_events(path, chips=chips)
        ran = {m[0] for c in events["chips"].values()
               for m in c["modules"]}
        ran.update(e[3] for line in events["host"] for e in line)
        programs = {name: program_map(proto)
                    for name, proto in hlo_protos(path).items()
                    if name in ran}
        for chip, c in sorted(events["chips"].items()):
            rows += join_events(c["ops"], c["modules"], programs,
                                window=(windows or {}).get(chip),
                                chip=chip)
        for line in events["host"]:
            rows += join_events(line, (), programs,
                                window=(windows or {}).get(0))
    return rows


def instr_time_table(profile_dir: str, windows=None
                     ) -> Dict[Tuple[str, str], Dict[str, Any]]:
    """Measured self time per (program, HLO instruction) from a
    captured trace, over all chips and threads:
    {(module, instruction): {total_ms, calls, op_name}}: the join key
    for observe.cost's analytic per-instruction flop/byte rows."""
    out: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for r in op_rows(profile_dir, windows):
        t = out.setdefault((r["module"], r["instruction"]),
                           {"total_ms": 0.0, "calls": 0,
                            "op_name": r["op_name"]})
        t["total_ms"] += r["self_s"] * 1e3
        t["calls"] += r["calls"]
    return out


def op_time_table(profile_dir: str, windows=None) -> List[Dict[str, Any]]:
    """Aggregate a captured trace into per-fluid-op-type rows.

    Returns [{op_type, calls, total_ms, avg_ms, max_ms, min_ms, ratio}]
    sorted by total time; times are self times, so the rows sum to the
    device's busy time.  `op_type` is the OWNER's (`owner_op_type`): an
    instruction under an `<op>:<idx>` scope counts for that op, and a
    scopeless one (the compiler's copies, slices and prefetches) for
    the op it feeds or, failing that, comes from.  "[unattributed]" is
    what truly has no owner: un-annotated programs, an instruction its
    program's map does not hold, a scopeless one with no scoped
    instruction either way; host python events, `Steps`, `XLA Modules`
    and `Async XLA Ops` are not op time.
    `windows`: `{chip: (lo, hi)}` seconds on the trace's clock.
    """
    rows: Dict[str, Dict[str, Any]] = {}
    for r in op_rows(profile_dir, windows):
        op = r["owner_op_type"] or "[unattributed]"
        t = rows.setdefault(op, {"op_type": op, "calls": 0,
                                 "total_ms": 0.0, "max_ms": 0.0,
                                 "min_ms": float("inf")})
        t["calls"] += r["calls"]
        t["total_ms"] += r["self_s"] * 1e3
        t["max_ms"] = max(t["max_ms"], r["max_s"] * 1e3)
        t["min_ms"] = min(t["min_ms"], r["min_s"] * 1e3)
    out = sorted(rows.values(), key=lambda r: -r["total_ms"])
    total = sum(r["total_ms"] for r in out) or 1.0
    for r in out:
        r["avg_ms"] = r["total_ms"] / r["calls"]
        r["ratio"] = r["total_ms"] / total
    return out


_SORT_KEYS = {"total": "total_ms", "calls": "calls", "max": "max_ms",
              "min": "min_ms", "ave": "avg_ms", "avg": "avg_ms"}


def format_op_table(profile_dir: str,
                    sorted_key: Optional[str] = "total") -> str:
    """The fluid profiler report: one row per fluid op type (the
    owner's, `op_time_table`), sorted by `sorted_key`
    (total/calls/max/min/ave — fluid's vocabulary)."""
    rows = op_time_table(profile_dir)
    key = _SORT_KEYS.get(str(sorted_key).lower(), "total_ms")
    rows = sorted(rows, key=lambda r: -r[key])
    lines = ["------->     Profiling Report     <-------", ""]
    hdr = (f"{'Event':<28}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>10}"
           f"{'Max(ms)':>10}{'Min(ms)':>10}{'Ratio':>8}")
    lines += [f"sorted by: {sorted_key}", "", hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['op_type']:<28}{r['calls']:>8}{r['total_ms']:>12.3f}"
            f"{r['avg_ms']:>10.4f}{r['max_ms']:>10.4f}"
            f"{r['min_ms']:>10.4f}{r['ratio']:>8.1%}")
    if not rows:
        lines.append("(no attributable device events in trace)")
    return "\n".join(lines)
