"""Per-op cost attribution: analytic flop/byte accounting over the
*optimized* HLO module, joined to fluid ops and measured device time.

Why XLA's own aggregates are not enough:

- `cost_analysis()["bytes accessed"]` OVERCOUNTS real HBM traffic —
  per-instruction estimates inside fusions are summed with utilization
  heuristics, so a roofline "ceiling" built on it can come out BELOW
  a measured rate.
- Pallas custom calls report ZERO flops, so a Pallas-active step's
  count would otherwise need a dense twin program compiled beside it.
- The aggregate has no attribution: copy/transpose time several times
  the flash kernels' own is found in a device profile only by reading
  the trace by hand.

This module recomputes both sides analytically from the optimized
HloModuleProto (read with trace.py's dependency-free wire scanner):

- FLOPS: contraction math for dot (exact vs XLA's count) and
  convolution (exact for VALID padding; a small overcount at padded
  edges), 1 flop/element for elementwise arithmetic, reduction sizes
  for reduce/reduce-window, recursive descent into fusions and called
  computations.  Transcendentals (exp/log/tanh/...) are tallied
  separately, matching XLA's flops-vs-transcendentals split.
  `while` bodies are multiplied by the loop's TRIP COUNT when it is
  recoverable from the scan-emitted counted-loop pattern
  (`while_trip_count`) — XLA's own cost analysis counts loop bodies
  ONCE, which undercounted scan-bound models (the r05 LSTM) by ~T and
  made their rooflines fiction.  An unrecoverable loop falls back to
  ×1 and is tagged with the loud `[loop?]` bucket instead of silently
  reading as a straight-line body.
- BYTES: the *materialized-buffers* model — after optimization each
  entry-computation instruction is one kernel that reads its operands
  from HBM once and writes its output once; fusion internals move no
  HBM bytes.  This is a minimum-traffic model: reuse inside a kernel
  is free, multiple uses of one buffer by one kernel count once.  A
  roofline built on it can only be MORE permissive than reality, so a
  ceiling can never fall below an honest measurement again.
- ATTRIBUTION: each instruction's `metadata.op_name` carries the
  executor's `<op_type>:<op_index>` named scopes (observe pillar 1),
  so every cost lands on a fluid op; each instruction is also binned
  into a BUCKET — matmul / conv / elementwise / layout (copy +
  transpose + bitcast-convert, the r05 longctx finding as a standard
  diagnostic) / comm / custom_call.
- PALLAS: custom calls whose scope names a registered kernel
  (`ops/pallas` KERNEL_COSTS, populated next to each kernel's
  DEFAULT_BLOCK_*) get that kernel's declared dense-equivalent
  (flops, bytes) injected at the instruction, so Pallas-active
  programs compute MFU numerators natively
  (tests/test_observe_cost.py pins the formulas against the dense twin).
"""

from __future__ import annotations

import functools
import math
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

from .trace import _fields, _first, _utf8, fluid_scope_of, phase_of

# --------------------------------------------------------------------------
# device peaks (op_cost_table)
# --------------------------------------------------------------------------

# bf16 MXU peak FLOP/s and HBM bandwidth by device kind prefix
DEVICE_PEAKS = {
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}


def device_peaks(kind: Optional[str] = None):
    """(peak_flops, hbm_bw) for a device kind, or (None, None) when the
    kind is unknown (CPU test backend) — callers must treat None as
    "no roofline denominator", never assume a default chip."""
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    for prefix, peaks in DEVICE_PEAKS.items():
        if kind.startswith(prefix):
            return peaks
    return None, None


# --------------------------------------------------------------------------
# HloModuleProto parsing (field numbers are stable in xla/service/hlo.proto)
# --------------------------------------------------------------------------

# HloModuleProto:      name=1 entry_computation_name=2 computations=3
#                      id=5 entry_computation_id=6
# HloComputationProto: name=1 instructions=2 id=5 root_id=6
# HloInstructionProto: name=1 opcode=2 shape=3 metadata=7 window=15
#                      convolution_dimension_numbers=16
#                      custom_call_target=28 dot_dimension_numbers=30
#                      id=35 operand_ids=36 called_computation_ids=38
#                      feature_group_count=50 literal=8 tuple_index=13
#                      comparison_direction=63 parameter_number=9
# HloModuleProto.schedule=7: sequences=1 (map: computation id ->
#                      InstructionSequence{instruction_ids=1})
# LiteralProto:        s32s=4 s64s=5 u32s=6 u64s=7
# ShapeProto:          element_type=2 dimensions=3 tuple_shapes=4
# OpMetadata:          op_type=1 op_name=2
# DotDimensionNumbers: lhs_contracting=1 rhs_contracting=2 lhs_batch=3
#                      rhs_batch=4
# Window/WindowDimension: dimensions=1 / size=1 stride=2

_ELEM_BYTES = {1: 1, 2: 1, 3: 2, 4: 4, 5: 8, 6: 1, 7: 2, 8: 4, 9: 8,
               10: 2, 11: 4, 12: 8, 15: 8, 16: 2, 18: 16, 19: 1, 20: 1,
               21: 1, 22: 1, 23: 1, 24: 1, 25: 1}


def _varints(v) -> List[int]:
    """Decode a repeated int64 field: packed (bytes of varints) or a
    single already-decoded varint."""
    if isinstance(v, int):
        return [v]
    out, i, n = [], 0, len(v)
    while i < n:
        x = s = 0
        while True:
            b = v[i]
            i += 1
            x |= (b & 0x7F) << s
            if not b & 0x80:
                break
            s += 7
        out.append(x)
    return out


def _repeated_ints(buf: bytes, fno: int) -> List[int]:
    out: List[int] = []
    for f, _wt, v in _fields(buf):
        if f == fno:
            out.extend(_varints(v))
    return out


_ELEM_NAMES = {1: "pred", 2: "s8", 3: "s16", 4: "s32", 5: "s64", 6: "u8",
               7: "u16", 8: "u32", 9: "u64", 10: "f16", 11: "f32",
               12: "f64", 15: "c64", 16: "bf16", 17: "token", 18: "c128",
               19: "f8e5m2", 20: "f8e4m3fn", 21: "s4", 22: "u4"}


class Shape:
    __slots__ = ("element_type", "dims", "tuple_shapes")

    def __init__(self, buf: Optional[bytes]):
        self.element_type = 0
        self.dims: List[int] = []
        self.tuple_shapes: List["Shape"] = []
        if not buf:
            return
        for f, _wt, v in _fields(buf):
            if f == 2:
                self.element_type = v
            elif f == 3:
                self.dims.extend(_varints(v))
            elif f == 4:
                self.tuple_shapes.append(Shape(v))

    @property
    def elements(self) -> int:
        if self.tuple_shapes:
            return sum(s.elements for s in self.tuple_shapes)
        n = 1
        for d in self.dims:
            n *= d
        return n

    @property
    def bytes(self) -> int:
        if self.tuple_shapes:
            return sum(s.bytes for s in self.tuple_shapes)
        return self.elements * _ELEM_BYTES.get(self.element_type, 0)

    @property
    def elem_bytes(self) -> int:
        return _ELEM_BYTES.get(self.element_type, 0)

    @property
    def text(self) -> str:
        """`bf16[8,2048,1536]`, as the compiled text writes it without
        its layout."""
        if self.tuple_shapes:
            return "(" + ", ".join(s.text for s in self.tuple_shapes) + ")"
        return (f"{_ELEM_NAMES.get(self.element_type, '?')}"
                f"[{','.join(map(str, self.dims))}]")


class Instr:
    __slots__ = ("name", "opcode", "shape", "op_name", "id",
                 "operand_ids", "called_ids", "dot_dnums_buf",
                 "window_buf", "conv_dnums_buf", "feature_group_count",
                 "custom_call_target", "backend_config", "literal_buf",
                 "tuple_index", "comparison_direction",
                 "parameter_number")

    def __init__(self, buf: bytes):
        self.name = ""
        self.opcode = ""
        self.shape = Shape(None)
        self.op_name = ""
        self.id = 0
        self.operand_ids: List[int] = []
        self.called_ids: List[int] = []
        self.dot_dnums_buf = b""
        self.window_buf = b""
        self.conv_dnums_buf = b""
        self.feature_group_count = 1
        self.custom_call_target = ""
        self.backend_config = b""
        self.literal_buf = b""
        self.tuple_index = 0
        self.comparison_direction = ""
        self.parameter_number = 0
        for f, _wt, v in _fields(buf):
            if f == 1:
                self.name = _utf8(v)
            elif f == 8:
                self.literal_buf = v
            elif f == 9:
                self.parameter_number = int(v)
            elif f == 13:
                self.tuple_index = int(v)
            elif f == 63:
                self.comparison_direction = _utf8(v)
            elif f == 2:
                self.opcode = _utf8(v)
            elif f == 3:
                self.shape = Shape(v)
            elif f == 7:
                self.op_name = _utf8(_first(v, 2, b""))
            elif f == 15:
                self.window_buf = v
            elif f == 16:
                self.conv_dnums_buf = v
            elif f == 28:
                self.custom_call_target = _utf8(v)
            elif f == 30:
                self.dot_dnums_buf = v
            elif f == 35:
                self.id = v
            elif f == 36:
                self.operand_ids.extend(_varints(v))
            elif f == 38:
                self.called_ids.extend(_varints(v))
            elif f == 43:
                self.backend_config = v
            elif f == 50:
                self.feature_group_count = max(int(v), 1)


class Computation:
    __slots__ = ("name", "id", "root_id", "instructions", "by_id")

    def __init__(self, buf: bytes):
        self.name = ""
        self.id = 0
        self.root_id = 0
        self.instructions: List[Instr] = []
        for f, _wt, v in _fields(buf):
            if f == 1:
                self.name = _utf8(v)
            elif f == 2:
                self.instructions.append(Instr(v))
            elif f == 5:
                self.id = v
            elif f == 6:
                self.root_id = v
        self.by_id = {i.id: i for i in self.instructions}

    @property
    def root(self) -> Optional[Instr]:
        return self.by_id.get(self.root_id) or (
            self.instructions[-1] if self.instructions else None)


class HloModule:
    def __init__(self, proto: bytes):
        # accept either a bare HloModuleProto or an HloProto wrapper
        # (hlo_module=1) — traces embed the wrapper, runtime
        # executables hand out the bare module
        if _first(proto, 2) is None and _first(proto, 1) is not None:
            inner = _first(proto, 1)
            if isinstance(inner, bytes) and _first(inner, 3) is not None:
                proto = inner
        self.name = _utf8(_first(proto, 1, b""))
        self.entry_id = _first(proto, 6, 0)
        self.computations: Dict[int, Computation] = {}
        # {computation id: instruction ids in the order they run}: a
        # compiled module is scheduled, and a computation's own list
        # is a post-order, not that order
        self.schedule: Dict[int, List[int]] = {}
        for f, _wt, v in _fields(proto):
            if f == 3:
                comp = Computation(v)
                self.computations[comp.id] = comp
            elif f == 7:
                for sf, _swt, entry in _fields(v):
                    if sf == 1:
                        self.schedule[_first(entry, 1, 0)] = _repeated_ints(
                            _first(entry, 2, b""), 1)

    @property
    def entry(self) -> Computation:
        if self.entry_id in self.computations:
            return self.computations[self.entry_id]
        # fall back: the computation with the largest id is the entry
        # in XLA's numbering
        return self.computations[max(self.computations)]


# --------------------------------------------------------------------------
# while-loop trip counts (the scan undercount fix)
# --------------------------------------------------------------------------

def while_trip_count(module: HloModule, comp: Computation,
                     instr: Instr) -> Optional[int]:
    """Known trip count of a counted `while` (the lax.scan / fori_loop
    pattern), or None when none can be recovered.

    XLA:CPU's loop analysis annotates every counted while with
    `backend_config={"known_trip_count":{"n":"T"}, ...}` after
    optimization; that annotation is read where it is there.  The TPU
    compiler leaves none, and keeps the loop in its plain form, which
    is read instead (`_induction_trip_count`): the condition compares
    one element of the carry with a constant, the body adds a constant
    to that element, and it starts from a constant.  A genuine
    data-dependent `while` (a decode loop) matches neither and returns
    None — callers fall back to x1 with the loud `[loop?]` bucket,
    never a silent guess.
    """
    if instr.opcode != "while":
        return None
    if instr.backend_config:
        import json

        try:
            known = json.loads(instr.backend_config).get("known_trip_count")
        except ValueError:
            known = None
        if known:
            return int(known["n"])
    return _induction_trip_count(module, comp, instr)


def _scalar_int(instr: Optional[Instr]) -> Optional[int]:
    """The value of an integer scalar `constant`, else None."""
    if instr is None or instr.opcode != "constant" or instr.shape.dims:
        return None
    for f, _wt, v in _fields(instr.literal_buf):
        if f in (4, 5, 6, 7):
            values = _varints(v)
            if len(values) == 1:
                value = values[0]
                # int32 / int64 are plain varints: a negative one
                # arrives as its 64-bit two's complement
                return value - (1 << 64) if value >> 63 else value
    return 0 if instr.literal_buf else None     # an all-default literal


def _behind(comp: Computation, instr: Optional[Instr]) -> Optional[Instr]:
    """`instr` behind the copies, bitcasts and converts that wrap it."""
    while (instr is not None and len(instr.operand_ids) == 1
           and instr.opcode in ("copy", "bitcast", "convert")):
        instr = comp.by_id.get(instr.operand_ids[0])
    return instr


def _carried_index(comp: Computation, instr: Optional[Instr]
                   ) -> Optional[int]:
    """i where `instr` is `get-tuple-element(parameter), index=i`."""
    instr = _behind(comp, instr)
    if instr is None or instr.opcode != "get-tuple-element" \
            or len(instr.operand_ids) != 1:
        return None
    source = comp.by_id.get(instr.operand_ids[0])
    if source is None or source.opcode != "parameter":
        return None
    return instr.tuple_index


def _induction_trip_count(module: HloModule, comp: Computation,
                          instr: Instr) -> Optional[int]:
    """Trip count of `while (carry[i] < N) carry[i] += step`, carry[i]
    starting from a constant, every piece read off the instructions
    themselves; None for anything else."""
    if len(instr.called_ids) != 2 or len(instr.operand_ids) != 1:
        return None
    body, cond = (module.computations.get(c) for c in instr.called_ids)
    if body is None or cond is None or body.root is None \
            or cond.root is None:
        return None
    if cond.root.opcode != "compare":
        body, cond = cond, body
    test = cond.root
    if test.opcode != "compare" or test.comparison_direction != "LT" \
            or len(test.operand_ids) != 2:
        return None
    index = _carried_index(cond, cond.by_id.get(test.operand_ids[0]))
    limit = _scalar_int(_behind(cond, cond.by_id.get(test.operand_ids[1])))
    if index is None or limit is None:
        return None
    init = _behind(comp, comp.by_id.get(instr.operand_ids[0]))
    if init is None or init.opcode != "tuple" \
            or index >= len(init.operand_ids):
        return None
    start = _scalar_int(_behind(comp,
                                comp.by_id.get(init.operand_ids[index])))
    root = body.root
    if start is None or root.opcode != "tuple" \
            or index >= len(root.operand_ids):
        return None
    nxt = _behind(body, body.by_id.get(root.operand_ids[index]))
    if nxt is None or nxt.opcode != "add" or len(nxt.operand_ids) != 2:
        return None
    a, b = (body.by_id.get(i) for i in nxt.operand_ids)
    if _carried_index(body, a) != index:
        a, b = b, a
    step = _scalar_int(_behind(body, b))
    if _carried_index(body, a) != index or not step or step < 0:
        return None
    return max(0, -(-(limit - start) // step))


# --------------------------------------------------------------------------
# analytic flop model (mirrors xla HloCostAnalysis conventions)
# --------------------------------------------------------------------------

_TRANSCENDENTAL = {
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "logistic", "tanh", "sine", "cosine", "tan", "sqrt", "rsqrt",
    "cbrt", "atan2", "power", "erf",
}

# elementwise arithmetic XLA counts at 1 flop/element
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "compare", "select", "clamp", "and", "or", "xor", "not", "negate",
    "abs", "sign", "floor", "ceil", "round-nearest-afz",
    "round-nearest-even", "remainder", "shift-left",
    "shift-right-arithmetic", "shift-right-logical", "is-finite",
    "count-leading-zeros", "popcnt", "convert", "real", "imag",
    "complex", "stochastic-convert",
}

# pure data movement / bookkeeping: zero flops AND (except where they
# appear at the entry level) no modeled HBM traffic of their own
_NO_BYTES = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "after-all", "partition-id", "replica-id",
             "domain", "opt-barrier", "optimization-barrier"}

_COMM = {"all-reduce", "all-gather", "all-to-all", "collective-permute",
         "collective-broadcast", "reduce-scatter", "send", "recv",
         "send-done", "recv-done", "all-reduce-start", "all-reduce-done",
         "all-gather-start", "all-gather-done",
         "collective-permute-start", "collective-permute-done"}

_LAYOUT = {"copy", "transpose", "bitcast-convert", "copy-start",
           "copy-done", "reshape"}


def _dot_flops(instr: Instr, operands: List[Instr]) -> float:
    # fma(2) * output elements * contracted width — identical to
    # HloCostAnalysis::HandleDot
    k = 1
    if instr.dot_dnums_buf and operands:
        lhs_contract = _repeated_ints(instr.dot_dnums_buf, 1)
        lhs_dims = operands[0].shape.dims
        for dim in lhs_contract:
            if dim < len(lhs_dims):
                k *= lhs_dims[dim]
    return 2.0 * instr.shape.elements * k


@functools.lru_cache(maxsize=None)
def _valid_positions(size: int, kernel: int, out: int,
                     dim: bytes) -> int:
    """(output position, kernel position) pairs of one spatial
    dimension that read a real input element: not padding, not a hole
    of the base dilation.  `dim` is the serialized WindowDimension
    (stride=2 padding_low=3 window_dilation=5 base_dilation=6)."""
    w = {f: v for f, _wt, v in _fields(dim)}
    stride, pad_low = w.get(2) or 1, w.get(3, 0)
    if pad_low >= 1 << 63:           # a negative int64 on the wire
        pad_low -= 1 << 64
    rhs_dilate, lhs_dilate = w.get(5) or 1, w.get(6) or 1
    dilated = (size - 1) * lhs_dilate + 1
    n = 0
    for o in range(out):
        for k in range(kernel):
            p = o * stride + k * rhs_dilate - pad_low
            n += 0 <= p < dilated and p % lhs_dilate == 0
    return n


def _conv_flops(instr: Instr, operands: List[Instr]) -> float:
    # fma(2) * output batch * output features * input features per
    # group * the valid window positions of each spatial dimension:
    # HloCostAnalysis::HandleConvolution.  Counting positions, not
    # window sizes, matters on a TPU, whose compiler writes a batched
    # dot as a convolution over the batch dimensions with a window as
    # large as the batch and a dilation that leaves one position of it
    # valid.
    if len(operands) < 2:
        return 0.0
    dn = instr.conv_dnums_buf
    lhs, kernel, out = (operands[0].shape.dims, operands[1].shape.dims,
                        instr.shape.dims)

    def dim(dims, fno):              # a dimension number; 0 is omitted
        i = _first(dn, fno, 0)
        return dims[i] if i < len(dims) else 1

    flops = 2.0 * dim(out, 9) * dim(out, 10) * dim(kernel, 3)
    windows = [v for f, _wt, v in _fields(instr.window_buf) if f == 1]
    for li, ki, oi, window in zip(_repeated_ints(dn, 11),
                                  _repeated_ints(dn, 6),
                                  _repeated_ints(dn, 12), windows):
        flops *= _valid_positions(lhs[li], kernel[ki], out[oi], window)
    return flops


def _is_dot(instr: Instr) -> bool:
    """A convolution that jax traced as a `dot_general`: the TPU
    compiler writes every dot as a convolution, and only the
    instruction's `op_name` still says which it was."""
    return instr.op_name.rpartition("/")[2].startswith("dot_general")


def _reduce_ops(module: HloModule, instr: Instr) -> int:
    """Flop-bearing instruction count of a reduce/scatter computation
    (1 for add/max — the common case)."""
    n = 0
    for cid in instr.called_ids:
        comp = module.computations.get(cid)
        if not comp:
            continue
        n += sum(1 for i in comp.instructions
                 if i.opcode in _ELEMENTWISE or i.opcode in _TRANSCENDENTAL)
    return max(n, 1)


def _computation_flops(module: HloModule, comp: Computation,
                       seen: Optional[set] = None) -> Tuple[float, float]:
    """(flops, transcendentals) of every instruction in `comp`,
    descending into fusions/calls (cycle-safe)."""
    seen = set() if seen is None else seen
    if comp.id in seen:
        return 0.0, 0.0
    seen.add(comp.id)
    flops = transc = 0.0
    for instr in comp.instructions:
        f, t = _instr_flops(module, comp, instr, seen)
        flops += f
        transc += t
    return flops, transc


def _instr_flops(module: HloModule, comp: Computation, instr: Instr,
                 seen: Optional[set] = None) -> Tuple[float, float]:
    op = instr.opcode
    elems = instr.shape.elements
    operands = [comp.by_id[i] for i in instr.operand_ids
                if i in comp.by_id]
    if op == "dot":
        return _dot_flops(instr, operands), 0.0
    if op == "convolution":
        return _conv_flops(instr, operands), 0.0
    if op in _TRANSCENDENTAL:
        return 0.0, float(elems)
    if op in _ELEMENTWISE:
        return float(elems), 0.0
    if op == "reduce":
        in_elems = operands[0].shape.elements if operands else 0
        return (max(in_elems - elems, 0) * _reduce_ops(module, instr),
                0.0)
    if op in ("reduce-window", "select-and-scatter"):
        window = 1
        for wd, _wt, v in _fields(instr.window_buf):
            if wd == 1:
                window *= _first(v, 1, 1)
        return float(elems) * window * _reduce_ops(module, instr), 0.0
    if op == "scatter":
        upd = operands[-1].shape.elements if operands else 0
        return float(upd) * _reduce_ops(module, instr), 0.0
    if op == "conditional":
        # one branch runs, not all: the heaviest, which no call exceeds
        return max((_computation_flops(module, sub, seen)
                    for sub in map(module.computations.get, instr.called_ids)
                    if sub is not None), default=(0.0, 0.0))
    if op in ("fusion", "call", "while", "async-start"):
        flops = transc = 0.0
        for cid in instr.called_ids:
            sub = module.computations.get(cid)
            if sub is not None:
                f, t = _computation_flops(module, sub, seen)
                flops += f
                transc += t
        if op == "while":
            # a while body runs trip-count times, not once (the r05
            # scan undercount); unrecoverable loops stay at ×1 and are
            # surfaced via the [loop?] bucket in instruction_costs
            trip = while_trip_count(module, comp, instr)
            if trip is not None:
                flops *= trip
                transc *= trip
        return flops, transc
    # custom-call: zero here; the Pallas registry injects at a higher
    # level so callers can see xla-vs-registry flops separately
    return 0.0, 0.0


# --------------------------------------------------------------------------
# Pallas kernel cost registry injection
# --------------------------------------------------------------------------

_PALLAS_SCOPE_RE = re.compile(r"pallas_([A-Za-z0-9_]+)")
# the `cost_estimate` a `pallas_call` declared, as Mosaic's custom call
# carries it in its backend_config
_COST_ESTIMATE_RE = re.compile(
    rb'"cost_estimate":\{"flops":"?(\d+)"?,"transcendentals":"?\d+"?,'
    rb'"bytes_accessed":"?(\d+)"?')


def _pallas_kernel_of(op_name: str) -> Optional[str]:
    """Registered kernel name from an instruction's op_name scope, or
    None when the custom call is not a scoped Pallas kernel."""
    m = _PALLAS_SCOPE_RE.search(op_name or "")
    return m.group(1) if m else None


def _registry_cost(kernel: str, instr: Instr, operands: List[Instr]):
    """(flops, bytes|None) declared by the kernel module, or None when
    the kernel has no registered cost."""
    from ..ops import pallas as pallas_pkg

    fn = pallas_pkg.KERNEL_COSTS.get(kernel)
    if fn is None:
        return None
    if fn == pallas_pkg.DECLARED_AT_CALL:
        m = _COST_ESTIMATE_RE.search(instr.backend_config or b"")
        return (float(m.group(1)), float(m.group(2))) if m else None
    op_shapes = [(tuple(o.shape.dims), o.shape.elem_bytes)
                 for o in operands]
    res = instr.shape
    res_shapes = ([(tuple(s.dims), s.elem_bytes)
                   for s in res.tuple_shapes]
                  if res.tuple_shapes else [(tuple(res.dims),
                                             res.elem_bytes)])
    return fn(op_shapes, res_shapes)


# XLA's own grouped matmul.  The TPU compiler lowers `ragged-dot`
# (jax.lax.ragged_dot and its two transposes) to Mosaic kernels of its
# own and REPLACES the instruction's op_name with "ragged-dot-<mode>":
# the fluid scope is gone, the custom call reports zero flops.
_XLA_RAGGED_DOT = "ragged-dot"
RAGGED_DOT_KERNEL = "ragged_dot"


def _xla_kernel_of(op_name: str) -> Optional[str]:
    """Name of a Mosaic kernel the TPU compiler wrote itself, from the
    op_name it stamped on the custom call: `ragged_dot`, or
    `ragged_dot_metadata` for its helper (group offsets, no matmul)."""
    if not (op_name or "").startswith(_XLA_RAGGED_DOT):
        return None
    return (RAGGED_DOT_KERNEL + "_metadata" if "metadata" in op_name
            else RAGGED_DOT_KERNEL)


def ragged_dot_cost(operand_shapes, result_shapes):
    """(flops, bytes) of one grouped matmul over M sorted rows in G
    groups: 2*M*K*N whatever the routing, because a row meets one
    group's (K, N) weight, never all G (counting G x dense would put
    a routed-expert layer past the chip's peak).  The matrices are the
    last two operands (tiling metadata comes first): `(M,K) x (G,K,N)
    -> (M,N)` forward and for dX, `(M,K) x (M,N) -> (G,K,N)` for dW."""
    (lhs, lhs_b), (rhs, rhs_b) = operand_shapes[-2:]
    out, out_b = result_shapes[0]
    if len(out) == 3:                   # dW: the rows are contracted
        flops = 2.0 * lhs[0] * lhs[1] * rhs[1]
    else:                               # rows kept: out is (M, N)
        flops = 2.0 * out[0] * out[1] * lhs[1]
    return flops, float(math.prod(lhs) * lhs_b + math.prod(rhs) * rhs_b
                        + math.prod(out) * out_b)


# --------------------------------------------------------------------------
# per-instruction cost rows + bucketing
# --------------------------------------------------------------------------

BRANCH_BUCKET = "branch"      # a `conditional`: its branches' rows follow


def _bucket(module: HloModule, comp: Computation, instr: Instr) -> str:
    op = instr.opcode
    if op == "custom-call":
        return "custom_call"
    if op == "while":
        # "loop" when the trip count is recovered (flops already carry
        # the multiplication); the LOUD "[loop?]" tag marks a body
        # counted ONCE because the induction pattern was unrecoverable
        # — a roofline reader must never mistake that for real coverage
        trip = while_trip_count(module, comp, instr)
        return "loop" if trip is not None else "[loop?]"
    if op == "conditional":
        return BRANCH_BUCKET
    if op == "dot":
        return "matmul"
    if op == "convolution":
        return "matmul" if _is_dot(instr) else "conv"
    if op in _COMM:
        return "comm"
    if op in ("async-start", "async-update", "async-done"):
        # the TPU compiler's own asynchronous slices and copies
        # (`slice-start` / `slice-done`): data movement, unless what
        # the async computation wraps is a collective
        start = instr
        while start is not None and start.opcode != "async-start":
            start = (comp.by_id.get(start.operand_ids[0])
                     if start.operand_ids else None)
        for cid in start.called_ids if start is not None else ():
            sub = module.computations.get(cid)
            if sub is not None and sub.root is not None \
                    and sub.root.opcode in _COMM:
                return "comm"
        return "layout"
    if op == "fusion":
        ops_inside = set()
        convs = []
        root_op = None
        for cid in instr.called_ids:
            sub = module.computations.get(cid)
            if sub is None:
                continue
            ops_inside.update(i.opcode for i in sub.instructions)
            convs += [i for i in sub.instructions
                      if i.opcode == "convolution"]
            if root_op is None and sub.root is not None:
                root_op = sub.root.opcode
        if "dot" in ops_inside or (convs and all(map(_is_dot, convs))):
            return "matmul"
        if convs:
            return "conv"
        if root_op in _LAYOUT:
            return "layout"
        return "elementwise"
    if op in _LAYOUT:
        return "layout"
    if op in _NO_BYTES:
        return "noop"
    return "elementwise"


# --------------------------------------------------------------------------
# one def-use map a computation: whose work a scopeless instruction is
# --------------------------------------------------------------------------

# no work of their own: an owner walk passes through them whatever
# scope they carry (an asynchronous pair from its start to its done)
_TRANSPARENT = {"bitcast", "get-tuple-element", "tuple", "opt-barrier",
                "optimization-barrier", "copy-start", "copy-done",
                "async-start", "async-update", "async-done"}

OWNER_VIAS = ("scope", "consumer", "producer")     # else "none"


class DefUse:
    """The users of every instruction of ONE computation, and the
    owner of each: the fluid op an instruction works for.

    The compiler's own copies, slices and prefetches carry no
    `metadata.op_name`.  An instruction under a fluid
    `<op_type>:<index>` scope owns itself (`via` "scope").  One without
    is handed to (a) "consumer": the first instruction in the order
    the module runs (its schedule: the first user is what the copy had
    to be ready for) that carries a fluid scope and is reached forward
    through scopeless and `_TRANSPARENT` instructions; `consumers`
    counts all that the walk met, so a copy shared by several ops shows
    as shared and is not split; (b) "producer": where no consumer is
    met (the walk ends at the root, a state array written back, or at
    a loop's carry, a stacked residual), the nearest scoped
    instruction backward, the latest to run among equally near ones;
    (c) nobody (`via` "none").  A walk stays inside its computation.
    """

    def __init__(self, module: HloModule, comp: Computation):
        self.comp = comp
        self.users: Dict[int, List[Instr]] = {}
        for instr in comp.instructions:
            for oid in dict.fromkeys(instr.operand_ids):
                self.users.setdefault(oid, []).append(instr)
        order = module.schedule.get(comp.id) or ()
        self.position = {iid: n for n, iid in enumerate(order)}
        for instr in comp.instructions:
            self.position.setdefault(instr.id, len(self.position))
        # (fluid op type or None, name scope) of every instruction
        self.scopes = {i.id: fluid_scope_of(i.op_name)
                       for i in comp.instructions}
        # (owning instruction or None, via, scoped consumers met)
        self.owners: Dict[int, Tuple[Optional[Instr], str, int]] = \
            self._walk()

    def _walk(self):
        comp, position = self.comp, self.position
        scoped = {iid: scope[0] is not None
                  for iid, scope in self.scopes.items()}
        passed = {i.id: not scoped[i.id] or i.opcode in _TRANSPARENT
                  for i in comp.instructions}
        ordered = sorted(comp.instructions, key=lambda i: position[i.id])
        # operands run before their users: one pass each way
        forward: Dict[int, frozenset] = {}
        for instr in reversed(ordered):
            if not passed[instr.id]:
                continue
            met = set()
            for user in self.users.get(instr.id, ()):
                if passed[user.id]:
                    met |= forward.get(user.id, frozenset())
                else:
                    met.add(user.id)
            forward[instr.id] = frozenset(met)
        backward: Dict[int, Tuple[int, int, int]] = {}
        for instr in ordered:
            if not passed[instr.id]:
                continue
            near = None                 # (distance, -position, id)
            for oid in instr.operand_ids:
                if oid not in comp.by_id:
                    continue
                if not passed[oid]:
                    found = (1, -position[oid], oid)
                elif oid in backward:
                    d, p, producer = backward[oid]
                    found = (d + 1, p, producer)
                else:
                    continue
                near = found if near is None else min(near, found)
            if near is not None:
                backward[instr.id] = near
        owners = {}
        for instr in comp.instructions:
            if scoped[instr.id]:
                owners[instr.id] = (instr, "scope", 0)
            elif forward[instr.id]:
                first = min(forward[instr.id], key=position.get)
                owners[instr.id] = (comp.by_id[first], "consumer",
                                    len(forward[instr.id]))
            elif instr.id in backward:
                owners[instr.id] = (comp.by_id[backward[instr.id][2]],
                                    "producer", 0)
            else:
                owners[instr.id] = (None, "none", 0)
        return owners

    def source(self, instr: Instr, buckets: Dict[int, str],
               parameters: str
               ) -> Tuple[str, Optional[Instr], Optional[Shape]]:
        """Where the array a `layout` instruction moves comes from:
        `parameters` (what a parameter of this computation is: "state"
        in the entry computation, a weight, a moment or a feed that is
        re-laid every step; "carry" in a counted loop's body) with the
        parameter and its shape, where the chain of operands backward
        through `_TRANSPARENT` and `layout` instructions reaches one
        (of an instruction with several operands the largest; of a
        `tuple` the element a `get-tuple-element` on the way asked
        for, and no other); else "activation"."""
        comp = self.comp
        indices: List[int] = []
        while True:
            if instr.opcode == "parameter":
                shape = instr.shape
                if shape.tuple_shapes and indices \
                        and indices[-1] < len(shape.tuple_shapes):
                    shape = shape.tuple_shapes[indices[-1]]
                return parameters, instr, shape
            operands = [comp.by_id[i] for i in instr.operand_ids
                        if i in comp.by_id]
            if not operands or (instr.opcode not in _TRANSPARENT
                                and buckets.get(instr.id) != "layout"):
                return "activation", None, None
            if instr.opcode == "get-tuple-element":
                indices.append(instr.tuple_index)
            if instr.opcode == "tuple":
                if not indices or indices[-1] >= len(operands):
                    return "activation", None, None
                instr = operands[indices.pop()]
            elif len(operands) == 1:
                instr = operands[0]
            else:
                instr = max(operands, key=lambda o: o.shape.bytes)


def instruction_costs(proto, every_branch: bool = False
                      ) -> List[Dict[str, Any]]:
    """Analytic per-instruction cost rows for the entry computation of
    a serialized module, or of an `HloModule` already parsed (one row
    per post-fusion kernel).  A `conditional` is a row without cost
    (bucket "branch") followed by the rows of its HEAVIEST branch, the
    one with the most FLOPs, which no call exceeds: one branch runs,
    so the rows still sum to a step.  `every_branch`: the rows of all
    its branches instead (what a trace is joined to: any may run).

    Row keys: name, opcode, op_type (fluid attribution or None),
    bucket, flops, transcendentals, bytes, pallas_kernel (set when a
    registered Pallas kernel's cost was injected at a custom call),
    kernel (every `tpu_custom_call` row: the Pallas kernel's `name`
    from its `pallas_<name>` scope, registered or not, or
    `ragged_dot` for the compiler's own grouped matmul; else None),
    branch_of (the `conditional` whose branch holds the instruction,
    None in the entry computation),
    trip_count (while rows: the recovered loop trip count; None =
    unrecoverable, body counted once IN the row and bucketed
    "[loop?]"),
    loop_of / trips (every row: the innermost counted `while` whose
    body or condition holds the instruction, None outside one, and how
    often a step runs it: the product of the enclosing trip counts, 1
    outside).
    A counted `while` is a row WITHOUT cost (bucket "loop") followed by
    the rows of its body and condition, each PER CALL: a step's cost is
    `flops * trips`, which `total_costs` and `op_cost_table` sum, and a
    trace's events join the body's instructions under their own names
    (`calls` there counts the trips).  The row keeps `body_flops`, its
    body's FLOPs over all trips, for reading alone.
    `flops` already includes the injected registry flops; `xla_flops`
    carries the pre-injection analytic count.
    Every row also says whose work it is, from ONE def-use map a
    computation (`DefUse`: the entry, every branch, every counted
    loop's body and condition): op_name, shape / shape_bytes (the
    result as text, `bf16[8,2048,1536]`, and its bytes: what `_moved`
    gives of a tuple), operands / users (instruction names), copyish
    (a copy or transpose, or a fusion rooted at one),
    owner (the owning instruction's name, None for nobody),
    owner_op_type / owner_name_scope / owner_phase (that instruction's
    fluid op, `fluid.name_scope()` path and forward / backward /
    other), owner_via ("scope": the row's own `<op_type>:<index>`
    scope, for which nothing changes; "consumer" / "producer": a
    scopeless row handed to the first scoped instruction it feeds, or
    failing that the nearest behind it; "none"), owner_consumers (the
    scoped consumers the walk met: a copy shared by several ops says
    so and is not split).  Rows of the `layout` bucket carry source:
    "state" where what they move is a parameter of the ENTRY
    computation (a weight, an optimizer moment, a feed: re-laid or
    fetched EVERY step), with source_parameter (its number) and
    source_shape; "carry" where it is a counted loop's body parameter
    (source_shape: the carried element's); "activation" otherwise
    (None off the bucket).
    """
    # force kernel-cost registration before walking custom calls
    from ..ops.pallas import dropout_mask as _dm  # noqa: F401
    from ..ops.pallas import flash_attention as _fa  # noqa: F401
    from ..ops.pallas import flash_mla as _fm  # noqa: F401 (and flash_gqa)
    from ..ops.pallas import grouped_matmul as _gm  # noqa: F401
    from ..ops.pallas import paged_attention as _pa  # noqa: F401
    from ..ops.pallas import recurrence as _rc  # noqa: F401
    from ..ops.pallas import vocab_ce as _vc  # noqa: F401

    module = proto if isinstance(proto, HloModule) else HloModule(proto)
    return _computation_costs(module, module.entry, every_branch, None)


def _moved(instr: Instr) -> Shape:
    """The array an instruction gives: its result, of an asynchronous
    start `((operands), result, context)` the result, of any other
    tuple (a `copy-start`'s `(destination, source, context)`) the
    largest array."""
    shape = instr.shape
    if instr.opcode in ("async-start", "async-update") \
            and len(shape.tuple_shapes) > 1:
        shape = shape.tuple_shapes[1]
    while shape.tuple_shapes:
        shape = max(shape.tuple_shapes, key=lambda s: s.bytes)
    return shape


def _computation_costs(module: HloModule, comp: Computation,
                       every_branch: bool, branch_of: Optional[str],
                       loop_of: Optional[str] = None, trips: int = 1,
                       parameters: str = "state"
                       ) -> List[Dict[str, Any]]:
    rows: List[Dict[str, Any]] = []
    def_use = DefUse(module, comp)
    buckets: Dict[int, str] = {}
    for instr in comp.instructions:
        operands = [comp.by_id[i] for i in instr.operand_ids
                    if i in comp.by_id]
        # a conditional's cost is its branches', a counted loop's its
        # body's: their rows follow it
        branching = instr.opcode == "conditional"
        trip = while_trip_count(module, comp, instr)
        flops, transc = ((0.0, 0.0) if branching
                         else _instr_flops(module, comp, instr))
        body_flops = flops if trip is not None else None
        if trip is not None:
            flops = transc = 0.0
        bucket = buckets[instr.id] = _bucket(module, comp, instr)
        if branching or trip is not None or instr.opcode in _NO_BYTES:
            nbytes = 0
        else:
            # materialized-buffers model: unique operands read once,
            # output written once; operands that are themselves
            # bookkeeping (tuple/gte wrapping a buffer) still stand in
            # for one read of their underlying buffer size
            seen_ids = set()
            nbytes = instr.shape.bytes
            for o in operands:
                if o.id in seen_ids:
                    continue
                seen_ids.add(o.id)
                nbytes += o.shape.bytes
        owner, via, consumers = def_use.owners[instr.id]
        owner_type, owner_scope = (def_use.scopes[owner.id]
                                   if owner is not None else (None, ""))
        moved = _moved(instr)
        row = {
            "name": instr.name,
            "opcode": instr.opcode,
            "op_name": instr.op_name,
            "op_type": def_use.scopes[instr.id][0],
            "bucket": bucket,
            "flops": flops,
            "xla_flops": flops,
            "transcendentals": transc,
            "bytes": float(nbytes),
            "shape": moved.text,
            "shape_bytes": float(moved.bytes),
            "pallas_kernel": None,
            "kernel": None,
            "branch_of": branch_of,
            "loop_of": loop_of,
            "trips": trips,
            "operands": tuple(o.name for o in operands),
            "users": tuple(u.name for u in def_use.users.get(instr.id, ())),
            "copyish": _is_copyish(module, instr),
            "owner": owner.name if owner is not None else None,
            "owner_op_type": owner_type,
            "owner_name_scope": owner_scope,
            "owner_phase": phase_of(owner.op_name if owner is not None
                                    else ""),
            "owner_via": via,
            "owner_consumers": consumers,
            "source": None,
            "source_parameter": None,
            "source_shape": None,
        }
        if bucket == "layout":
            source, parameter, shape = def_use.source(instr, buckets,
                                                      parameters)
            row["source"] = source
            if source != "activation":
                row["source_shape"] = shape.text
            if source == "state":
                row["source_parameter"] = parameter.parameter_number
        if instr.opcode == "while":
            row["trip_count"] = trip
            row["body_flops"] = body_flops
        if instr.opcode == "custom-call":
            row["custom_call_target"] = instr.custom_call_target
            kernel = _pallas_kernel_of(instr.op_name)
            if kernel is not None:
                cost = _registry_cost(kernel, instr, operands)
                if cost is not None:
                    kflops, kbytes = cost
                    row["pallas_kernel"] = kernel
                    row["flops"] = float(kflops)
                    if kbytes is not None:
                        row["bytes"] = float(kbytes)
            else:
                kernel = _xla_kernel_of(instr.op_name)
            if kernel == RAGGED_DOT_KERNEL:
                row["flops"], row["bytes"] = ragged_dot_cost(
                    [(tuple(o.shape.dims), o.shape.elem_bytes)
                     for o in operands],
                    [(tuple(instr.shape.dims), instr.shape.elem_bytes)])
            if instr.custom_call_target == "tpu_custom_call":
                row["kernel"] = kernel
        rows.append(row)
        if branching:
            branches = [
                _computation_costs(module, sub, every_branch, instr.name,
                                   loop_of, trips, "activation")
                for sub in map(module.computations.get, instr.called_ids)
                if sub is not None]
            if not every_branch and branches:
                branches = [max(branches, key=lambda b: sum(
                    r["flops"] * r["trips"] for r in b))]
            for branch in branches:
                rows += branch
        elif trip is not None:
            for sub in map(module.computations.get, instr.called_ids):
                if sub is not None:
                    rows += _computation_costs(
                        module, sub, every_branch, branch_of, instr.name,
                        trips * trip, "carry")
    return rows


def per_step(row: Dict[str, Any], key: str) -> float:
    """A cost row's `key` (flops, transcendentals, bytes) over one
    step: per call x the trips of the loops that hold it."""
    return row[key] * row.get("trips", 1)


def total_costs(proto: bytes) -> Dict[str, Any]:
    """Whole-program totals over `instruction_costs`.

    flops = analytic flops INCLUDING injected Pallas registry costs;
    `pallas_flops` is the injected share, `custom_calls` /
    `pallas_matched` make an unmatched (uncounted) KERNEL visible
    instead of silently reading as zero flops (the compiler's own
    grouped matmul and its metadata helper count as matched: their
    cost is `ragged_dot_cost`).  Only Mosaic kernels
    (`tpu_custom_call`) count: the TPU compiler also emits zero-flop
    bookkeeping custom calls of its own (ConcatBitcast,
    AssumeGatherIndicesInBound, ...), which no registry could cover."""
    rows = instruction_costs(proto)
    custom = [r for r in rows
              if r.get("custom_call_target") == "tpu_custom_call"]
    matched = [r for r in custom if r["pallas_kernel"]
               or (r["kernel"] or "").startswith(RAGGED_DOT_KERNEL)]
    return {
        "flops": sum(per_step(r, "flops") for r in rows),
        "transcendentals": sum(per_step(r, "transcendentals")
                               for r in rows),
        "bytes": sum(per_step(r, "bytes") for r in rows),
        "pallas_flops": sum(per_step(r, "flops") for r in matched),
        "custom_calls": len(custom),
        "pallas_matched": len(matched),
        "bucket_bytes": _sum_by(rows, "bytes"),
        "bucket_flops": _sum_by(rows, "flops"),
    }


def _sum_by(rows: Iterable[Dict[str, Any]], key: str) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for r in rows:
        out[r["bucket"]] = out.get(r["bucket"], 0.0) + per_step(r, key)
    return out


# --------------------------------------------------------------------------
# compiled-program access + the per-op table
# --------------------------------------------------------------------------

def compiled_hlo_proto(compiled) -> bytes:
    """Serialized optimized HloModuleProto of a jax Compiled object."""
    modules = compiled.runtime_executable().hlo_modules()
    return modules[0].as_serialized_hlo_module_proto()


def compiled_xla_flops(compiled) -> float:
    analyses = compiled.cost_analysis()
    if isinstance(analyses, (list, tuple)):
        analyses = analyses[0]
    return float(analyses.get("flops", 0.0))


def program_costs(program, feed=None, fetch_list=None, scope=None,
                  exe=None) -> Dict[str, Any]:
    """Compile a fluid program's one-iteration step (AOT, shared with
    Executor.cost_analysis) and return `total_costs` of the optimized
    module plus XLA's own aggregate flops for cross-checking and the
    step's peak device memory (`peak_hbm_bytes`, the buffer-assignment
    allocation total from the same compile)."""
    from ..core.executor import Executor

    exe = exe or Executor()
    compiled = exe.compiled_step(program, feed=feed,
                                 fetch_list=fetch_list, scope=scope)
    proto = compiled_hlo_proto(compiled)
    out = total_costs(proto)
    out["xla_aggregate_flops"] = compiled_xla_flops(compiled)
    from .memory import compiled_peak_bytes

    out["peak_hbm_bytes"] = compiled_peak_bytes(compiled)
    return out


def op_cost_table(program=None, feed=None, fetch_list=None, scope=None,
                  exe=None, profile_dir: Optional[str] = None,
                  peak_flops: Optional[float] = None,
                  hbm_bw: Optional[float] = None,
                  proto: Optional[bytes] = None,
                  windows=None) -> List[Dict[str, Any]]:
    """Per-framework-op cost rows for a program's optimized step.

    Each row aggregates the entry instructions attributed to one
    (fluid op type, bucket) pair:

        {op_type, bucket, instructions, flops, transcendentals, bytes,
         time_ms, arith_intensity, achieved_flops_frac,
         roofline_time_ms}

    - `time_ms` joins measured per-instruction device self time from
      a jax.profiler trace under `profile_dir`, summed over its chips
      (None when no trace is given or no event matched — cost
      attribution works chip-free).  Of the programs that ran in the
      trace the one with this module's name and the most time is
      taken; `windows` is `{chip: (lo, hi)}` seconds on the trace's
      clock (observe/trace.py).
    - `achieved_flops_frac` = (flops / time) / peak_flops when both a
      time and a peak are known, else None.
    - `roofline_time_ms` = max(flops/peak, bytes/bw): the row's own
      roofline lower bound (None off-chip).

    Pass `proto` to analyze an already-serialized optimized module
    instead of compiling `program`.
    """
    if proto is None:
        if program is None:
            raise ValueError("op_cost_table needs a program or a proto")
        from ..core.executor import Executor

        exe = exe or Executor()
        compiled = exe.compiled_step(program, feed=feed,
                                     fetch_list=fetch_list, scope=scope)
        proto = compiled_hlo_proto(compiled)
    module = HloModule(proto)
    rows = instruction_costs(module)

    times: Dict[str, float] = {}
    if profile_dir is not None:
        from .trace import instr_time_table

        by_program: Dict[str, Dict[str, float]] = {}
        for (prog, name), t in instr_time_table(profile_dir,
                                                windows).items():
            # "jit_step(1025)" is a run of the module "jit_step"
            if prog and prog.rpartition("(")[0] == module.name:
                by_program.setdefault(prog, {})[name] = t["total_ms"]
        if by_program:
            times = max(by_program.values(),
                        key=lambda m: sum(m.values()))

    if peak_flops is None and hbm_bw is None:
        peak_flops, hbm_bw = device_peaks()

    grouped: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for r in rows:
        if r["bucket"] == "noop":
            continue
        key = (r["op_type"] or "[unattributed]", r["bucket"])
        g = grouped.setdefault(key, {
            "op_type": key[0], "bucket": key[1], "instructions": 0,
            "flops": 0.0, "transcendentals": 0.0, "bytes": 0.0,
            "time_ms": None,
        })
        g["instructions"] += 1
        g["flops"] += per_step(r, "flops")
        g["transcendentals"] += per_step(r, "transcendentals")
        g["bytes"] += per_step(r, "bytes")
        t = times.get(r["name"])
        if t is not None:
            g["time_ms"] = (g["time_ms"] or 0.0) + t

    out = []
    for g in grouped.values():
        g["arith_intensity"] = (round(g["flops"] / g["bytes"], 3)
                                if g["bytes"] else None)
        g["achieved_flops_frac"] = None
        g["roofline_time_ms"] = None
        if peak_flops:
            if g["time_ms"]:
                g["achieved_flops_frac"] = round(
                    (g["flops"] / (g["time_ms"] / 1e3)) / peak_flops, 4)
            if hbm_bw:
                g["roofline_time_ms"] = round(
                    max(g["flops"] / peak_flops,
                        g["bytes"] / hbm_bw) * 1e3, 4)
        out.append(g)
    out.sort(key=lambda g: (-(g["time_ms"] or 0.0), -g["flops"],
                            -g["bytes"]))
    return out


def layout_byte_share(proto: bytes) -> float:
    """Fraction of the step's modeled HBM traffic spent in the LAYOUT
    bucket (copy/transpose/bitcast-convert + layout-rooted fusions) —
    transpose traffic at kernel boundaries as one number, from a
    compile alone.  The benchmark's reading of the same bucket is
    MEASURED: `device_ms_per_step.layout`, the self time of those
    instructions in a traced run (PERF.md section 3)."""
    rows = instruction_costs(proto)
    total = sum(r["bytes"] for r in rows if r["bucket"] != "noop")
    if not total:
        return 0.0
    layout = sum(r["bytes"] for r in rows if r["bucket"] == "layout")
    return layout / total


# copy/transpose opcodes — the subset of the layout bucket that is pure
# relayout traffic (reshape/bitcast-convert can be free bitcasts; these
# never are)
_COPYISH = {"copy", "transpose", "copy-start", "copy-done"}


def _is_copyish(module: HloModule, instr: Instr) -> bool:
    if instr.opcode in _COPYISH:
        return True
    if instr.opcode == "fusion":
        for cid in instr.called_ids:
            sub = module.computations.get(cid)
            if sub is not None and sub.root is not None \
                    and sub.root.opcode in _COPYISH:
                return True
    return False


def _entry_rows(proto) -> List[Dict[str, Any]]:
    """`instruction_costs` of the entry computation alone."""
    return [r for r in instruction_costs(proto)
            if r["branch_of"] is None and r["loop_of"] is None]


def flash_boundary_layout(proto: bytes,
                          kernel_prefix: str = "flash") -> List[Dict[str, str]]:
    """Copy/transpose instructions ADJACENT (operand or user, by the
    rows' `operands` and `users`) to Pallas flash custom calls in the
    entry computation — the ISSUE 8 "zero
    transpose traffic at the kernel boundary" proof, asserted empty by
    tests/test_head_major.py and the run_ci.sh layout smoke.  On a
    backend where Pallas runs in interpret mode (CPU) there are no
    custom calls and the list is trivially empty — pair this with
    `copyish_instructions` / the program-level zero-`transpose`-ops
    check for a chip-free proof."""
    rows = {r["name"]: r for r in _entry_rows(proto)}
    offenders = []
    for row in rows.values():
        if row["opcode"] != "custom-call":
            continue
        kern = _pallas_kernel_of(row["op_name"])
        if not kern or not kern.startswith(kernel_prefix):
            continue
        for name in row["operands"] + row["users"]:
            if rows[name]["copyish"]:
                offenders.append({"custom_call": row["name"],
                                  "kernel": kern,
                                  "neighbor": name,
                                  "opcode": rows[name]["opcode"]})
    return offenders


def copyish_instructions(proto: bytes,
                         op_types: Optional[set] = None) -> List[Dict[str, Any]]:
    """Entry-computation copy/transpose instructions (incl. fusions
    rooted at one: the rows' `copyish`), optionally restricted to rows
    attributed to the
    given fluid op types.  The chip-free half of the boundary proof:
    with Pallas in interpret mode the flash custom calls don't exist,
    but a head-major program still must not contain transpose kernels
    attributed to its attention ops."""
    return [{"name": r["name"], "opcode": r["opcode"],
             "op_type": r["op_type"], "bytes": r["shape_bytes"]}
            for r in _entry_rows(proto) if r["copyish"]
            and (op_types is None or r["op_type"] in op_types)]


def bucket_summary(rows: List[Dict[str, Any]]) -> Dict[str, Dict[str, float]]:
    """Collapse op_cost_table rows to per-bucket totals — the
    layout/copy/transpose share IS the r05 longctx diagnostic."""
    out: Dict[str, Dict[str, float]] = {}
    for r in rows:
        b = out.setdefault(r["bucket"], {"flops": 0.0, "bytes": 0.0,
                                         "time_ms": 0.0,
                                         "instructions": 0})
        b["flops"] += r["flops"]
        b["bytes"] += r["bytes"]
        b["time_ms"] += r["time_ms"] or 0.0
        b["instructions"] += r["instructions"]
    return out


def format_cost_table(rows: List[Dict[str, Any]],
                      top: int = 30) -> str:
    """Human-readable per-op cost report (the r05 manual device-profile
    reading, automated)."""
    hdr = (f"{'Op':<24}{'Bucket':<12}{'Instrs':>7}{'GFLOP':>10}"
           f"{'MB':>10}{'Time(ms)':>10}{'AI':>8}{'Ach.MFU':>9}")
    lines = ["-------> Per-op cost attribution <-------", hdr,
             "-" * len(hdr)]
    for r in rows[:top]:
        lines.append(
            f"{r['op_type']:<24}{r['bucket']:<12}{r['instructions']:>7}"
            f"{r['flops'] / 1e9:>10.3f}{r['bytes'] / 1e6:>10.2f}"
            f"{(r['time_ms'] if r['time_ms'] is not None else -1):>10.3f}"
            f"{(r['arith_intensity'] or 0):>8.1f}"
            f"{(r['achieved_flops_frac'] if r['achieved_flops_frac'] is not None else -1):>9.4f}")
    if len(rows) > top:
        lines.append(f"... ({len(rows) - top} more rows)")
    return "\n".join(lines)
