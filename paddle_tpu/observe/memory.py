"""HBM memory observability: buffer-level attribution, the peak-memory
timeline, and the pre-compile fit planner (observe pillar 5).

Time (trace.py) and flops/bytes-moved (cost.py) already attribute to
fluid ops; memory was one opaque host-side number
(`observe.peak_memory_bytes()`), even though the three most
consequential recorded decisions of the r05 cycle were MEMORY
decisions: remat on/off at longctx (0.306 vs 0.243 MFU — and the XLA
composition needs remat just to fit), dense-at-8k "cannot compile at
all", and the serving bucket ladder sized by guesswork.  This module
makes HBM a first-class observed quantity:

- **buffer attribution** (`memory_report` / `memory_table` /
  `format_memory_table`): parse the optimized module's
  BufferAssignmentProto — `compiled.memory_analysis()
  .serialized_buffer_assignment_proto`, read with the same
  dependency-free wire scanner as trace/cost — and attribute every
  logical buffer to its fluid op through the `metadata.op_name` scope
  join cost.py already uses.  Peak = the sum of allocation sizes:
  XLA's heap simulation has ALREADY packed temp buffers into arenas
  with liveness-based reuse, so the allocation total IS what the
  device must hold (cross-checked against CompiledMemoryStats
  args+outputs+temps-aliased within 0.1% on CPU).  Where the backend
  hands back an EMPTY assignment (the TPU's compiler does) the report
  falls back to a live-range sweep over the instruction sequence from
  our own proto walk, tagged `source: "module-shapes"`; a missing or
  renamed jax attribute raises instead.

- **buckets**: every buffer lands in params / optimizer_state /
  gradients / activations / workspace, with donated bytes tallied
  across buckets.  Entry parameters classify by NAME — the executor's
  step is `fn(state, feeds)` and the flattened pytree leaf order is
  the HLO entry parameter order, so parameter_number → state var name
  (`Executor.compiled_step(with_names=True)` plumbs the names).
  Instruction-defined buffers classify by scope: `transpose(jvp(...))`
  wrappers are the AD backward (gradients), optimizer op types are
  update math (optimizer_state), other attributed scopes are forward
  activations, unattributed temps are workspace.

- **timeline** (`memory_timeline` / `export_chrome_trace`): cumulative
  live bytes over the entry instruction schedule, built from the
  assignment's (allocation, offset) slots so XLA's buffer reuse is
  respected — "what is alive at the peak" is a one-call answer, and
  the curve exports as chrome-trace counter events next to the
  RunEventLog.

- **fit planner** (`plan_fit`): predict peak HBM for a candidate
  (batch, seq, dtype, remat) configuration WITHOUT compiling it.
  Peak memory of these step programs is affine in batch (params and
  optimizer state are constant; activations, gradients, and feeds
  scale per-example), so the planner compiles the SAME program at two
  small probe batches — cheap, CPU-safe, never touching the candidate
  shape — and extrapolates the affine fit to the candidate.  Dev
  validation on CPU: within 1% at 16x extrapolation on both headline
  models; `PLAN_FIT_REL_TOL` records the asserted bound (10%).  A
  static fusion-model estimator over the unoptimized module was
  validated first and REJECTED: its error spanned 0.8x-1.4x across
  models because XLA's fusion/layout decisions (inlined calls,
  materialized concats, layout copies) are not predictable pre-compile
  — and the measured arena itself moves ~15% with parameter name
  ordering, so only a same-program probe can stay inside 10%.

CPU-vs-TPU caveat (docs/OBSERVE.md): CPU `memory_analysis` numbers
bound the program's buffer structure but do not equal v5e HBM —
layout/padding and fusion differ per backend.  Chip-free planning is
for RELATIVE decisions (ladder sizing, remat A/Bs, batch scaling); an
absolute fit verdict against `DEVICE_HBM_BYTES` is a prediction whose
accuracy band is only recorded for same-backend probes.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from .cost import HloModule, _varints
from .trace import _fields, fluid_op_of

# --------------------------------------------------------------------------
# device HBM budgets (planning denominators; memory_stats()["bytes_limit"]
# is the live source on a real chip — device_memory_budget())
# --------------------------------------------------------------------------

DEVICE_HBM_BYTES = {
    "TPU v4": 32_000_000_000,
    "TPU v5 lite": 16_000_000_000,
    "TPU v5e": 16_000_000_000,
    "TPU v5p": 95_000_000_000,
    "TPU v5": 95_000_000_000,
    "TPU v6 lite": 32_000_000_000,
    "TPU v6e": 32_000_000_000,
}

# optimizer op types (ops/optim.py registrations): instructions scoped
# to these are update math, and their non-Param/Grad operands name the
# resident optimizer-state vars
OPTIMIZER_OP_TYPES = {
    "sgd", "momentum", "lars_momentum", "adam", "adamax", "adagrad",
    "decayed_adagrad", "adadelta", "rmsprop", "ftrl", "proximal_gd",
    "proximal_adagrad", "average_accumulates", "ema_accumulate",
}

BUCKETS = ("params", "optimizer_state", "gradients", "activations",
           "workspace")

# plan_fit's recorded accuracy bound vs the proto-derived measurement
# on the SAME backend (asserted by tests/test_observe_memory.py and the
# run_ci.sh memory smoke; dev validation measured <1% at 16x batch
# extrapolation on the resnet/transformer test configs)
PLAN_FIT_REL_TOL = 0.10


def device_memory_budget(device=None) -> Optional[int]:
    """The device allocator's byte limit (`memory_stats()["bytes_limit"]`),
    falling back to the DEVICE_HBM_BYTES table by device kind; None when
    neither reports (the CPU test backend) — callers must treat None as
    "no budget known", never assume a default chip."""
    from .monitoring import device_memory_stats

    stats = device_memory_stats(device)
    if "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    import jax

    kind = (device if device is not None
            else jax.local_devices()[0]).device_kind
    for prefix, cap in DEVICE_HBM_BYTES.items():
        if kind.startswith(prefix):
            return cap
    return None


# --------------------------------------------------------------------------
# BufferAssignmentProto parsing (xla/service/hlo.proto, stable numbers)
# --------------------------------------------------------------------------

# HloProto:              hlo_module=1 buffer_assignment=3
# BufferAssignmentProto: logical_buffers=1 buffer_aliases=2
#                        buffer_allocations=3 heap_simulator_traces=4
# LogicalBufferProto:    id=1 size=2 defined_at=3
#   .Location:           shape_index=3 instruction_id=4
# BufferAllocationProto: index=1 size=2 is_thread_local=3
#                        is_entry_computation_parameter=5
#                        parameter_number=6 maybe_live_out=7 color=8
#                        assigned=9 is_tuple=11 is_constant=12
#   .Assigned:           logical_buffer_id=1 offset=2 size=3


class LogicalBuffer:
    __slots__ = ("id", "size", "instr_id", "shape_index")

    def __init__(self, buf: bytes):
        self.id = 0
        self.size = 0
        self.instr_id: Optional[int] = None
        self.shape_index: List[int] = []
        for f, _wt, v in _fields(buf):
            if f == 1:
                self.id = v
            elif f == 2:
                self.size = v
            elif f == 3:
                for lf, _lwt, lv in _fields(v):
                    if lf == 4:
                        self.instr_id = lv
                    elif lf == 3:
                        self.shape_index = _varints(lv)


class Allocation:
    __slots__ = ("index", "size", "is_param", "param_number", "live_out",
                 "is_constant", "is_tuple", "is_thread_local", "assigned")

    def __init__(self, buf: bytes):
        self.index = 0
        self.size = 0
        self.is_param = False
        self.param_number: Optional[int] = None
        self.live_out = False
        self.is_constant = False
        self.is_tuple = False
        self.is_thread_local = False
        self.assigned: List[Tuple[int, int, int]] = []  # (buf_id, off, sz)
        for f, _wt, v in _fields(buf):
            if f == 1:
                self.index = v
            elif f == 2:
                self.size = v
            elif f == 3:
                self.is_thread_local = bool(v)
            elif f == 5:
                self.is_param = bool(v)
            elif f == 6:
                self.param_number = v
            elif f == 7:
                self.live_out = bool(v)
            elif f == 11:
                self.is_tuple = bool(v)
            elif f == 12:
                self.is_constant = bool(v)
            elif f == 9:
                bid = off = sz = 0
                for af, _awt, av in _fields(v):
                    if af == 1:
                        bid = av
                    elif af == 2:
                        off = av
                    elif af == 3:
                        sz = av
                self.assigned.append((bid, off, sz))
        if self.is_param and self.param_number is None:
            self.param_number = 0  # proto3 omits the zero default


class BufferAssignment:
    def __init__(self, buf: bytes):
        self.buffers: Dict[int, LogicalBuffer] = {}
        self.allocations: List[Allocation] = []
        for f, _wt, v in _fields(buf):
            if f == 1:
                lb = LogicalBuffer(v)
                self.buffers[lb.id] = lb
            elif f == 3:
                self.allocations.append(Allocation(v))

    @property
    def total_bytes(self) -> int:
        """Peak device memory: the sum of allocation sizes.  XLA's heap
        simulation already packed temp buffers into arenas with
        liveness-based reuse, and a donated (param AND live-out)
        allocation appears ONCE — this total is what the device must
        actually hold."""
        return int(sum(a.size for a in self.allocations))


def compiled_memory(compiled) -> Tuple[bytes, Optional[BufferAssignment],
                                       Any]:
    """(optimized HloModuleProto, BufferAssignment|None,
    CompiledMemoryStats) of a jax Compiled object.  The assignment is
    `memory_analysis().serialized_buffer_assignment_proto` (jax 0.9);
    it is None only where the backend hands back an empty one (libtpu
    0.0.34 does) — callers then tag their result
    `source: "module-shapes"`.  A renamed attribute raises here."""
    from .cost import compiled_hlo_proto

    stats = compiled.memory_analysis()
    if isinstance(stats, (list, tuple)):
        stats = stats[0]
    ba = BufferAssignment(stats.serialized_buffer_assignment_proto)
    return (compiled_hlo_proto(compiled),
            ba if ba.allocations else None, stats)


def compiled_peak_bytes(compiled) -> int:
    """Predicted-peak device bytes of one compiled executable: the
    buffer-assignment allocation total, or the CompiledMemoryStats
    arithmetic where the backend exposes no assignment."""
    _proto, ba, stats = compiled_memory(compiled)
    if ba is not None:
        return ba.total_bytes
    return int(stats.argument_size_in_bytes
               + stats.output_size_in_bytes
               + stats.temp_size_in_bytes
               - stats.alias_size_in_bytes)


# --------------------------------------------------------------------------
# classification
# --------------------------------------------------------------------------

def _program_var_buckets(program) -> Tuple[set, set]:
    """(param_names, optimizer_state_names) from the program desc.
    Optimizer state = the non-Param/Grad operands and outputs of
    optimizer ops (accumulators, pow counters, the lr var) — robust to
    the `<param>.<acc>` naming without parsing names."""
    params, opt = set(), set()
    block = program.global_block()
    for name, var in block.vars.items():
        if getattr(var.desc, "is_parameter", False):
            params.add(name)
    for op in block.ops:
        if op.type not in OPTIMIZER_OP_TYPES:
            continue
        for slot, names in op.desc.inputs.items():
            if slot not in ("Param", "Grad"):
                opt.update(names)
        for slot, names in op.desc.outputs.items():
            if slot != "ParamOut":
                opt.update(names)
    return params, opt - params


def _state_bucket(name: str, params: set, opt: set) -> str:
    from ..core.executor import RNG_STATE_VAR
    from .metrics import TELEMETRY_VAR

    if name in params:
        return "params"
    if name in opt:
        return "optimizer_state"
    if name in (RNG_STATE_VAR, TELEMETRY_VAR):
        return "workspace"
    # other persistable state (BN running stats, custom counters) is
    # model state: it must be resident exactly like params
    return "params"


def _instr_bucket(op_name: str) -> str:
    op_type = fluid_op_of(op_name or "")
    if op_type is None:
        return "workspace"
    if op_type in OPTIMIZER_OP_TYPES:
        return "optimizer_state"
    if "transpose(" in op_name:
        # the executor's AD boundary: backward instructions carry
        # transpose(jvp(<op>:<idx>)) scopes (see trace.py)
        return "gradients"
    return "activations"


def _arg_labels(state, feed_arrays, compiled=None
                ) -> List[Tuple[str, str]]:
    """Flattened (kind, name) per HLO entry parameter, in jax's pytree
    leaf order for fn(state, feeds).  With `compiled`, labels of
    arguments jax PRUNED from the executable (keep_unused=False drops
    unused leaves) are filtered out via the executable's kept-var set —
    otherwise a pruned leaf shifts every later label and memory_report
    falls back to nameless params."""
    import jax.tree_util as jtu

    labels: List[Tuple[str, str]] = []
    for path, _leaf in jtu.tree_flatten_with_path((state, feed_arrays))[0]:
        kind = "state" if path[0].idx == 0 else "feed"
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path[1:])
        labels.append((kind, name))
    if compiled is not None:
        # jax exposes the kept-argument set only privately (0.9.0); a
        # rename raises here rather than shifting every label
        kept = compiled._executable._kept_var_idx
        labels = [lb for i, lb in enumerate(labels) if i in kept]
    return labels


# --------------------------------------------------------------------------
# the buffer table
# --------------------------------------------------------------------------

def _module_positions(module: HloModule):
    """(entry, entry position by instruction id, entry position of every
    non-entry computation via its call site — sub-computation buffers
    account at the calling while/fusion/call's schedule position)."""
    entry = module.entry
    pos = {i.id: k for k, i in enumerate(entry.instructions)}
    comp_pos: Dict[int, int] = {}
    pending = [(cid, pos[i.id]) for i in entry.instructions
               for cid in i.called_ids]
    while pending:
        cid, p = pending.pop()
        if cid in comp_pos or cid not in module.computations:
            continue
        comp_pos[cid] = p
        for i in module.computations[cid].instructions:
            for sub in i.called_ids:
                pending.append((sub, p))
    instr_comp: Dict[int, int] = {}
    for cid, comp in module.computations.items():
        for i in comp.instructions:
            instr_comp[i.id] = cid
    return entry, pos, comp_pos, instr_comp


def memory_report(program=None, feed=None, fetch_list=None, scope=None,
                  exe=None, compiled=None, arg_names=None
                  ) -> Dict[str, Any]:
    """Buffer-level memory accounting of a program's optimized step.

    Returns {rows, peak_bytes, breakdown, source, stats}:
    - rows: one per parameter/constant ALLOCATION and one per sized
      temp logical buffer — {bytes, bucket, op_type, opcode,
      instruction, param, donated, live_out, allocation}.  A donated
      parameter is ONE row (the updated value shares its slot).
    - peak_bytes: the allocation total (what the device must hold).
    - breakdown: per-bucket byte sums + "donated" (cross-bucket) +
      "peak_bytes".  params/optimizer_state sums are exact resident
      sizes; temp-bucket sums (activations/gradients/workspace) are
      FOOTPRINT attribution — XLA reuses arena slots over time, so
      their sum may exceed peak_bytes.  Use the timeline for
      concurrently-live truth.
    - source: "buffer_assignment" | "module-shapes" (no assignment
      exposed: rows synthesized from instruction output shapes, peak
      from a live-range sweep — an estimate, tagged as such).
    """
    if compiled is None:
        if program is None:
            raise ValueError("memory_report needs a program or a "
                             "compiled step")
        from ..core.executor import Executor

        exe = exe or Executor()
        compiled, arg_names = exe.compiled_step(
            program, feed=feed, fetch_list=fetch_list, scope=scope,
            with_names=True)
    params, opt = (set(), set())
    if program is not None:
        params, opt = _program_var_buckets(program)

    proto, ba, stats = compiled_memory(compiled)
    module = HloModule(proto)
    entry, pos, comp_pos, instr_comp = _module_positions(module)
    by_id = {i.id: i for comp in module.computations.values()
             for i in comp.instructions}
    n_entry_params = sum(1 for i in entry.instructions
                         if i.opcode == "parameter")
    # parameter_number -> (kind, name); only trustworthy when jax kept
    # every flattened leaf as an entry parameter (keep_unused pruning
    # breaks the numbering — then params stay nameless, never mislabeled)
    names_ok = arg_names is not None and len(arg_names) == n_entry_params

    rows: List[Dict[str, Any]] = []

    def classify(alloc: Optional[Allocation], instr) -> Tuple[str, Any]:
        if alloc is not None and alloc.is_param:
            if names_ok and alloc.param_number is not None \
                    and alloc.param_number < len(arg_names):
                kind, name = arg_names[alloc.param_number]
                if kind == "feed":
                    return "activations", name
                return _state_bucket(name, params, opt), name
            return "params", None
        if instr is not None and instr.opcode == "parameter" \
                and instr_comp.get(instr.id) != entry.id:
            # sub-computation parameter (loop carry): workspace
            return "workspace", None
        return _instr_bucket(instr.op_name if instr is not None
                             else ""), None

    if ba is not None:
        for a in ba.allocations:
            members = [ba.buffers[bid] for bid, _off, _sz in a.assigned
                       if bid in ba.buffers]
            if a.is_param:
                # one row per parameter allocation: the in-place
                # updated value (donation) shares the slot — two rows
                # would double-count the resident bytes
                lb = next((b for b in members
                           if (i := by_id.get(b.instr_id)) is not None
                           and i.opcode == "parameter"), None)
                instr = by_id.get(lb.instr_id) if lb is not None else None
                bucket, pname = classify(a, instr)
                rows.append({
                    "bytes": int(a.size), "bucket": bucket,
                    "op_type": None, "opcode": "parameter",
                    "instruction": (instr.name if instr is not None
                                    else None),
                    "param": pname,
                    "donated": bool(a.live_out),
                    "live_out": bool(a.live_out),
                    "allocation": a.index,
                })
                continue
            if a.is_constant:
                rows.append({
                    "bytes": int(a.size), "bucket": "workspace",
                    "op_type": None, "opcode": "constant",
                    "instruction": None, "param": None,
                    "donated": False, "live_out": bool(a.live_out),
                    "allocation": a.index,
                })
                continue
            for lb in members:
                if lb.size <= 0:
                    continue
                instr = by_id.get(lb.instr_id)
                bucket, pname = classify(None, instr)
                rows.append({
                    "bytes": int(lb.size),
                    "bucket": bucket,
                    "op_type": (fluid_op_of(instr.op_name)
                                if instr is not None else None),
                    "opcode": (instr.opcode if instr is not None
                               else None),
                    "instruction": (instr.name if instr is not None
                                    else None),
                    "param": pname,
                    "donated": False,
                    "live_out": bool(a.live_out),
                    "allocation": a.index,
                })
        peak = ba.total_bytes
        source = "buffer_assignment"
    else:
        # no assignment exposed: synthesize buffers from entry
        # instruction output shapes; peak = live-range sweep estimate
        for k, instr in enumerate(entry.instructions):
            nbytes = instr.shape.bytes
            if nbytes <= 0:
                continue
            if instr.opcode == "parameter":
                bucket, pname = "params", None
                if names_ok:
                    # entry parameters appear in order in the entry
                    pidx = sum(1 for i in entry.instructions[:k]
                               if i.opcode == "parameter")
                    if pidx < len(arg_names):
                        kind, name = arg_names[pidx]
                        pname = name
                        bucket = ("activations" if kind == "feed"
                                  else _state_bucket(name, params, opt))
                rows.append({"bytes": int(nbytes), "bucket": bucket,
                             "op_type": None, "opcode": "parameter",
                             "instruction": instr.name, "param": pname,
                             "donated": False, "live_out": False,
                             "allocation": None})
                continue
            if instr.opcode in ("constant", "tuple",
                                "get-tuple-element", "bitcast"):
                continue
            rows.append({
                "bytes": int(nbytes),
                "bucket": _instr_bucket(instr.op_name),
                "op_type": fluid_op_of(instr.op_name),
                "opcode": instr.opcode,
                "instruction": instr.name,
                "param": None,
                "donated": False,
                "live_out": instr.id == entry.root_id,
                "allocation": None,
            })
        peak = _sweep_module_shapes(entry)
        source = "module-shapes"

    rows.sort(key=lambda r: -r["bytes"])
    breakdown = {b: 0 for b in BUCKETS}
    donated = 0
    for r in rows:
        breakdown[r["bucket"]] = breakdown.get(r["bucket"], 0) + r["bytes"]
        if r["donated"]:
            donated += r["bytes"]
    breakdown["donated"] = donated
    breakdown["peak_bytes"] = int(peak)
    out = {"rows": rows, "peak_bytes": int(peak),
           "breakdown": breakdown, "source": source}
    out["stats"] = {
        "argument_bytes": int(stats.argument_size_in_bytes),
        "output_bytes": int(stats.output_size_in_bytes),
        "temp_bytes": int(stats.temp_size_in_bytes),
        "alias_bytes": int(stats.alias_size_in_bytes),
    }
    return out


def _sweep_module_shapes(entry) -> int:
    """Live-range peak estimate over a bare module's entry sequence:
    every non-bookkeeping instruction output materializes from its
    definition to its last use (the cost.py materialized-buffers
    model), parameters and the root are resident."""
    n = len(entry.instructions)
    last_use: Dict[int, int] = {}
    for k, i in enumerate(entry.instructions):
        for oid in i.operand_ids:
            last_use[oid] = k
    deltas = [0] * (n + 1)
    always = 0
    for k, i in enumerate(entry.instructions):
        nbytes = i.shape.bytes
        if nbytes <= 0:
            continue
        if i.opcode == "parameter" or i.id == entry.root_id:
            always += nbytes
            continue
        if i.opcode in ("constant", "tuple", "get-tuple-element",
                        "bitcast"):
            continue
        deltas[k] += nbytes
        deltas[last_use.get(i.id, k) + 1] -= nbytes
    live, peak = always, always
    for k in range(n):
        live += deltas[k]
        peak = max(peak, live)
    return peak


def memory_table(program=None, feed=None, fetch_list=None, scope=None,
                 exe=None, compiled=None, top: Optional[int] = None
                 ) -> List[Dict[str, Any]]:
    """The buffer rows of `memory_report`, largest first (top=N
    truncates)."""
    rows = memory_report(program, feed=feed, fetch_list=fetch_list,
                         scope=scope, exe=exe, compiled=compiled)["rows"]
    return rows[:top] if top else rows


def format_memory_table(rows: Sequence[Dict[str, Any]],
                        top: int = 30) -> str:
    """Human-readable top-N buffer report — the memory analog of
    format_cost_table."""
    hdr = (f"{'MB':>10}  {'Bucket':<16}{'Op':<22}{'Opcode':<16}"
           f"{'Param/Instruction':<32}{'Flags'}")
    lines = ["-------> Buffer-level memory attribution <-------", hdr,
             "-" * len(hdr)]
    for r in rows[:top]:
        flags = []
        if r.get("donated"):
            flags.append("donated")
        if r.get("live_out"):
            flags.append("live-out")
        who = r.get("param") or r.get("instruction") or "?"
        lines.append(
            f"{r['bytes'] / 1e6:>10.3f}  {r['bucket']:<16}"
            f"{(r.get('op_type') or '-'):<22}"
            f"{(r.get('opcode') or '-'):<16}{who:<32}"
            f"{','.join(flags)}")
    if len(rows) > top:
        rest = sum(r["bytes"] for r in rows[top:])
        lines.append(f"... ({len(rows) - top} more buffers, "
                     f"{rest / 1e6:.3f} MB)")
    return "\n".join(lines)


def sharded_memory_report(program, feed=None, fetch_list=None,
                          scope=None) -> Dict[str, Any]:
    """memory_report of the SHARDED (post-SPMD) step: buffer
    accounting of one device's partition of the CompiledProgram
    executable — `breakdown["optimizer_state"]` here is the PER-DEVICE
    resident opt-state bytes, the number the fsdp/ZeRO A/B claims
    drops ~1/N (ISSUE 13).  Requires the program to carry a
    CompiledProgram wrapper (with_data_parallel)."""
    wrapper = getattr(program, "_compiled_wrapper", None)
    if wrapper is None:
        raise ValueError("sharded_memory_report needs a program "
                         "compiled with CompiledProgram"
                         ".with_data_parallel")
    names = [f.name if hasattr(f, "name") else str(f)
             for f in (fetch_list or [])]
    compiled, arg_names = wrapper.compiled_step(
        dict(feed or {}), names, scope, with_names=True)
    return memory_report(program=program, compiled=compiled,
                         arg_names=arg_names)


def resident_state_bytes(report: Dict[str, Any],
                         bucket: str = "optimizer_state") -> int:
    """Resident bytes of a bucket's ENTRY-PARAMETER allocations in a
    memory_report — the arrays that must live in HBM for the whole
    step (accumulators, params), EXCLUDING scope-attributed temps
    (e.g. the pre-all-gather updated-param shard the ZeRO update
    materializes inside the adam scope).  This is the
    `opt_state_bytes_per_device` number the fsdp A/B tracks: on a
    sharded compile it is exactly the per-device accumulator
    footprint, 1/N under ZeRO."""
    return sum(r["bytes"] for r in report["rows"]
               if r["bucket"] == bucket and r["opcode"] == "parameter")


def step_mem_breakdown(program=None, feed=None, fetch_list=None,
                       scope=None, exe=None) -> Dict[str, Any]:
    """`memory_report` as one flat dict: per-bucket byte
    sums + peak_bytes + source.  A program compiled over a REAL
    (multi-device) mesh reports its SHARDED step's per-device buffer
    assignment — the number that must fit each chip — instead of the
    unsharded single-device twin's."""
    wrapper = getattr(program, "_compiled_wrapper", None)
    if wrapper is not None and wrapper._mesh is not None \
            and wrapper._mesh.devices.size > 1:
        rep = sharded_memory_report(program, feed=feed,
                                    fetch_list=fetch_list, scope=scope)
    else:
        rep = memory_report(program, feed=feed, fetch_list=fetch_list,
                            scope=scope, exe=exe)
    out = dict(rep["breakdown"])
    out["source"] = rep["source"]
    return out


# --------------------------------------------------------------------------
# the peak-memory timeline
# --------------------------------------------------------------------------

def memory_timeline(program=None, feed=None, fetch_list=None, scope=None,
                    exe=None, compiled=None) -> Dict[str, Any]:
    """Cumulative live bytes over the entry instruction schedule.

    Built from the buffer assignment's (allocation, offset) slots:
    logical buffers XLA assigned to overlapping offsets of one
    allocation share one physical slot (in-place reuse), so the curve
    reflects the memory the schedule actually occupies — its peak can
    only be ≤ `peak_bytes` (arena packing holds the gap).

    Returns {points, peak_live_bytes, peak_index, peak_instruction,
    live_at_peak, resident_bytes, n_instructions}; `points` is
    [(instruction_index, live_bytes)] at every change, `live_at_peak`
    the slot rows occupying the peak, largest first.
    """
    if compiled is None:
        if program is None:
            raise ValueError("memory_timeline needs a program or a "
                             "compiled step")
        from ..core.executor import Executor

        exe = exe or Executor()
        compiled = exe.compiled_step(program, feed=feed,
                                     fetch_list=fetch_list, scope=scope)
    proto, ba, _stats = compiled_memory(compiled)
    module = HloModule(proto)
    entry, pos, comp_pos, instr_comp = _module_positions(module)
    by_id = {i.id: i for comp in module.computations.values()
             for i in comp.instructions}
    n = len(entry.instructions)
    last_use: Dict[int, int] = {}
    for k, i in enumerate(entry.instructions):
        for oid in i.operand_ids:
            last_use[oid] = k

    def instr_pos(instr_id: Optional[int]) -> Optional[int]:
        if instr_id is None:
            return None
        if instr_id in pos:
            return pos[instr_id]
        cid = instr_comp.get(instr_id)
        return comp_pos.get(cid) if cid is not None else None

    slots: List[Dict[str, Any]] = []
    resident = 0
    if ba is not None:
        for a in ba.allocations:
            if a.is_param or a.is_constant or a.live_out:
                resident += a.size
                continue
            # group assigned buffers into offset-overlap slots
            spans = []
            for bid, off, sz in sorted(a.assigned, key=lambda t: t[1]):
                lb = ba.buffers.get(bid)
                if lb is None or sz <= 0:
                    continue
                p = instr_pos(lb.instr_id)
                if p is None:
                    p = 0
                lo = p
                hi = max(last_use.get(lb.instr_id, p), p) \
                    if lb.instr_id in pos else n - 1
                instr = by_id.get(lb.instr_id)
                if spans and off < spans[-1]["end"]:
                    s = spans[-1]
                    s["end"] = max(s["end"], off + sz)
                    s["lo"] = min(s["lo"], lo)
                    s["hi"] = max(s["hi"], hi)
                    s["buffers"].append(lb.id)
                else:
                    spans.append({"start": off, "end": off + sz,
                                  "lo": lo, "hi": hi,
                                  "buffers": [lb.id],
                                  "op_type": (fluid_op_of(instr.op_name)
                                              if instr is not None
                                              else None),
                                  "instruction": (instr.name
                                                  if instr is not None
                                                  else None)})
            for s in spans:
                slots.append({"bytes": s["end"] - s["start"],
                              "lo": s["lo"], "hi": s["hi"],
                              "op_type": s["op_type"],
                              "instruction": s["instruction"],
                              "buffers": s["buffers"]})
    else:
        # fallback: the module-shapes sweep's buffers are the slots
        for k, i in enumerate(entry.instructions):
            nbytes = i.shape.bytes
            if nbytes <= 0 or i.opcode in (
                    "parameter", "constant", "tuple",
                    "get-tuple-element", "bitcast"):
                if i.opcode == "parameter" or i.id == entry.root_id:
                    resident += max(nbytes, 0)
                continue
            if i.id == entry.root_id:
                resident += nbytes
                continue
            slots.append({"bytes": nbytes, "lo": k,
                          "hi": max(last_use.get(i.id, k), k),
                          "op_type": fluid_op_of(i.op_name),
                          "instruction": i.name, "buffers": [i.id]})

    deltas = [0] * (n + 1)
    for s in slots:
        deltas[s["lo"]] += s["bytes"]
        deltas[min(s["hi"], n - 1) + 1] -= s["bytes"]
    points: List[Tuple[int, int]] = []
    live, peak, peak_idx = resident, resident, 0
    for k in range(n):
        if deltas[k]:
            live += deltas[k]
            points.append((k, live))
            if live > peak:
                peak, peak_idx = live, k
    if not points:
        points = [(0, resident)]
    live_at_peak = sorted(
        (s for s in slots if s["lo"] <= peak_idx <= s["hi"]),
        key=lambda s: -s["bytes"])
    peak_instr = entry.instructions[peak_idx].name \
        if peak_idx < n else None
    return {
        "points": points,
        "peak_live_bytes": int(peak),
        "peak_index": peak_idx,
        "peak_instruction": peak_instr,
        "live_at_peak": live_at_peak,
        "resident_bytes": int(resident),
        "n_instructions": n,
        "source": "buffer_assignment" if ba is not None
                  else "module-shapes",
    }


def export_chrome_trace(timeline: Dict[str, Any], path: str) -> str:
    """Write the timeline as chrome-trace JSON (counter events over the
    instruction schedule + an instant event at the peak) — load in
    chrome://tracing or Perfetto next to a jax.profiler trace."""
    import json

    events = [{"name": "live_hbm_bytes", "ph": "C", "pid": 0, "tid": 0,
               "ts": idx, "args": {"bytes": live}}
              for idx, live in timeline["points"]]
    events.append({
        "name": "peak", "ph": "i", "pid": 0, "tid": 0, "s": "g",
        "ts": timeline["peak_index"],
        "args": {"peak_live_bytes": timeline["peak_live_bytes"],
                 "instruction": timeline["peak_instruction"],
                 "top_buffers": [
                     {"bytes": s["bytes"], "op_type": s["op_type"],
                      "instruction": s["instruction"]}
                     for s in timeline["live_at_peak"][:10]]},
    })
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f)
    return path


# --------------------------------------------------------------------------
# the fit planner
# --------------------------------------------------------------------------

def _feed_spec(feed) -> Dict[str, Any]:
    import jax
    import numpy as np

    out = {}
    for n, v in (feed or {}).items():
        if isinstance(v, jax.ShapeDtypeStruct):
            out[n] = v
        else:
            arr = np.asarray(v)
            out[n] = jax.ShapeDtypeStruct(arr.shape, arr.dtype)
    return out


def _infer_batch(spec: Dict[str, Any]) -> Optional[int]:
    from collections import Counter

    dims = Counter(int(s.shape[0]) for s in spec.values() if s.shape)
    if not dims:
        return None
    return dims.most_common(1)[0][0]


def plan_fit(program, feed, fetch_list=None, scope=None, exe=None,
             batch: Optional[int] = None,
             probe_batches: Tuple[int, int] = (2, 4),
             budget_bytes: Optional[int] = None) -> Dict[str, Any]:
    """Predict the step's peak device memory for a CANDIDATE feed
    without compiling the candidate.

    `feed` maps input name → array or jax.ShapeDtypeStruct at the
    candidate shape (no data needed).  The planner compiles the SAME
    program at two small probe batches — every feed whose leading dim
    equals the candidate batch is shrunk, everything else (seq length,
    dtype, the program's remat structure) stays at the candidate value
    — and extrapolates the affine peak(b) fit.  Probe compiles are
    memoized in the executor's AOT cache, so planning a whole ladder of
    batches pays the two compiles once.

    Returns {predicted_peak_bytes, batch, probe_batches, probe_peaks,
    per_example_bytes, resident_bytes, breakdown, rel_tol, budget_bytes,
    fits, headroom_bytes}; `fits`/`headroom_bytes` are None when no
    budget is known (budget_bytes argument, else the live device
    budget).  `rel_tol` is the recorded accuracy bound
    (PLAN_FIT_REL_TOL) of the prediction vs a real same-backend
    measurement.  Raises ValueError when the batch axis cannot be
    inferred (pass batch=).
    """
    import jax

    from ..core.executor import Executor

    exe = exe or Executor()
    spec = _feed_spec(feed)
    if not spec:
        raise ValueError("plan_fit needs a feed (the candidate shapes; "
                         "programs with no feeds have nothing to scale)")
    batch = batch if batch is not None else _infer_batch(spec)
    if batch is None or batch < 1:
        raise ValueError(f"cannot infer the batch axis from {spec}; "
                         f"pass batch=")

    def at_batch(b: int) -> Dict[str, Any]:
        out = {}
        for n, s in spec.items():
            if s.shape and int(s.shape[0]) == batch:
                out[n] = jax.ShapeDtypeStruct((b,) + tuple(s.shape[1:]),
                                              s.dtype)
            else:
                out[n] = s
        return out

    def peak_at(b: int) -> Tuple[int, Any]:
        compiled = exe.compiled_step(program, feed=at_batch(b),
                                     fetch_list=fetch_list, scope=scope)
        return compiled_peak_bytes(compiled), compiled

    b0, b1 = sorted(int(b) for b in probe_batches)
    if not (0 < b0 < b1):
        raise ValueError(f"probe_batches must be two distinct positive "
                         f"sizes, got {probe_batches}")
    if batch <= b1:
        # candidate is probe-sized: measure it directly (exact)
        peak, _ = peak_at(batch)
        p0 = p1 = peak
        slope, intercept = 0.0, float(peak)
        predicted = peak
        exact = True
    else:
        p0, _ = peak_at(b0)
        p1, _ = peak_at(b1)
        slope = (p1 - p0) / float(b1 - b0)
        intercept = p0 - slope * b0
        predicted = int(round(intercept + slope * batch))
        exact = False

    # exact resident components from the program/state (chip-free)
    params, opt = _program_var_buckets(program)
    from ..core.executor import global_scope

    sc = scope if scope is not None else global_scope()
    import numpy as np

    def _nbytes(name):
        v = sc.find_var(name)
        if v is None:
            return 0
        try:
            return int(np.asarray(v).nbytes)
        except Exception:  # noqa: BLE001
            return 0

    params_bytes = sum(_nbytes(n) for n in params)
    opt_bytes = sum(_nbytes(n) for n in opt)
    feed_bytes = int(sum(
        int(np.prod(s.shape, dtype=np.int64) or 1)
        * np.dtype(s.dtype).itemsize for s in spec.values()))

    if budget_bytes is None:
        budget_bytes = device_memory_budget()
    fits = headroom = None
    if budget_bytes:
        fits = bool(predicted <= budget_bytes)
        headroom = int(budget_bytes - predicted)
    return {
        "predicted_peak_bytes": int(predicted),
        "exact": exact,
        "batch": int(batch),
        "probe_batches": [b0, b1] if not exact else [batch],
        "probe_peaks": [int(p0), int(p1)] if not exact else [int(p0)],
        "per_example_bytes": int(round(slope)),
        "resident_bytes": int(round(intercept)),
        "breakdown": {
            "params": params_bytes,
            "optimizer_state": opt_bytes,
            "feeds": feed_bytes,
            "temp": int(max(predicted - params_bytes - opt_bytes
                            - feed_bytes, 0)),
        },
        "rel_tol": PLAN_FIT_REL_TOL,
        "budget_bytes": budget_bytes,
        "fits": fits,
        "headroom_bytes": headroom,
    }
