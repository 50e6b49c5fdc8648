"""`rng_evals_per_step` (benchmarks/layer_metrics/rng_evals_per_step.py)
on the CPU: the walk over a hand-made HLO module, serialized as the
trace holds it, and the reader's arithmetic over hand-made rows of the
program's join.  No number from here is a speed."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmarks")
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

from paddle_tpu.observe import cost, trace  # noqa: E402

STEP = "jit_step(9)"
U32, F32 = 8, 11        # xla_data.proto PrimitiveType


@pytest.fixture(scope="module")
def reader():
    return bench_run.load_module(os.path.join(
        BENCH, "layer_metrics", "rng_evals_per_step.py"))


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _ld(fno, payload):
    return _varint((fno << 3) | 2) + _varint(len(payload)) + payload


def _vi(fno, n):
    return _varint(fno << 3) + _varint(n)


def _instr(name, opcode, iid, called=(), dtype=F32, op_name=""):
    """A serialized HloInstructionProto (name=1 opcode=2 shape=3
    metadata=7 id=35 called_computation_ids=38)."""
    return (_ld(1, name.encode()) + _ld(2, opcode.encode())
            + _ld(3, _vi(2, dtype) + _vi(3, 8))
            + _ld(7, _ld(2, op_name.encode())) + _vi(35, iid)
            + b"".join(_vi(38, c) for c in called))


def _comp(name, cid, instrs):
    return (_ld(1, name.encode()) + b"".join(_ld(2, i) for i in instrs)
            + _vi(5, cid) + _vi(6, 1))


def _threefry(blocks, op_name="jit(step)/jvp(dropout:7)/jit(_bernoulli)"
                              "/jit(_uniform)/xor"):
    """The generator as the TPU compiler leaves it: 20 rounds and the
    fold of the two words, a u32 xor each, and adds between them."""
    return [_instr(f"{op}.{i}", op, 10 + 2 * i + (op == "xor"), dtype=U32,
                   op_name=op_name)
            for i in range(21 * blocks) for op in ("add", "xor")]


def _module():
    """ENTRY: a dot fusion with a cloned generator, a dot fusion
    without, a loop fusion whose generator sits in a nested fusion, a
    multi-output fusion of two sibling masks, a stand-alone
    rng-bit-generator, a `pred` xor fusion, and the step key's scalar
    `fold_in` unfused."""
    dot = _instr("convolution.1", "convolution", 1)
    comps = [
        _comp("fused_dot_with_rng", 1, [dot] + _threefry(1)),
        _comp("fused_dot", 2, [dot]),
        _comp("inner_mask", 3, _threefry(1, op_name="renamed")),
        _comp("fused_outer", 4, [
            _instr("multiply.1", "multiply", 1),
            _instr("fusion.9", "fusion", 2, called=[3])]),
        _comp("fused_two_masks", 5, _threefry(2)),
        # 19 rounds are not a generator, nor are xors of another type
        _comp("fused_not_rng", 6, _threefry(1)[:38] + [
            _instr(f"xor.p{i}", "xor", 900 + i, dtype=1)
            for i in range(30)]),
        _comp("main", 7, [
            _instr("fusion.1", "fusion", 1, called=[1]),
            _instr("fusion.2", "fusion", 2, called=[2]),
            _instr("fusion.3", "fusion", 3, called=[4]),
            _instr("fusion.4", "fusion", 4, called=[5]),
            _instr("rng-bit-generator.5", "rng-bit-generator", 5,
                   dtype=U32),
            _instr("fusion.6", "fusion", 6, called=[6]),
        ] + _threefry(1, op_name="jit(_threefry_fold_in)/xor")),
    ]
    return (_ld(1, b"jit_step") + b"".join(_ld(3, c) for c in comps)
            + _vi(6, 7))


def test_walk_counts_generators_in_bodies_at_any_depth(reader):
    module = cost.HloModule(_module())
    by_name = {c.name: reader.generators(c)
               for c in module.computations.values()}
    assert by_name == {"fused_dot_with_rng": 1, "fused_dot": 0,
                       "inner_mask": 1, "fused_outer": 0,
                       "fused_two_masks": 2, "fused_not_rng": 0,
                       "main": 2}
    # the entry's own scalar rounds are in no instruction's body
    assert reader.rng_instructions(module) == {
        "fusion.1": 1, "fusion.3": 1, "fusion.4": 2,
        "rng-bit-generator.5": 1}


def _row(name, calls, module=STEP):
    return {"chip": 0, "module": module, "instruction": name,
            "op_name": "x", "op_type": "dropout", "phase": "forward",
            "bucket": "elementwise", "flops": 0.0, "bytes": 1.0,
            "joined": True, "calls": calls, "self_s": 0.01 * calls,
            "total_s": 0.01 * calls, "max_s": 0.01, "min_s": 0.01}


RUN = {"trace": {"path": "/nowhere/x.xplane.pb",
                 "chip0": {"lo": 10.0, "hi": 14.0, "steps": 4}}}


@pytest.mark.parametrize("rows,want", [
    # every instruction once a step: 1 + 0 + 1 + 2 + 1 + 0
    ([_row(f, 4) for f in ("fusion.1", "fusion.2", "fusion.3", "fusion.4",
                           "rng-bit-generator.5", "fusion.6")], 5.0),
    # one executed twice a step, as a recomputed segment's would be
    ([_row("fusion.1", 8), _row("fusion.2", 4)], 2.0),
    # a step program with no generator in it reads 0, not nothing
    ([_row("fusion.2", 4), _row("fusion.6", 4)], 0.0),
    # the same name in another program is that program's instruction
    ([_row("fusion.2", 40), _row("fusion.1", 4, module="jit_init(3)")],
     0.0),
])
def test_reader_counts_executed_generators_per_step(reader, monkeypatch,
                                                    rows, want):
    monkeypatch.setattr(step_anatomy, "_chip0_rows", lambda *a: rows)
    monkeypatch.setattr(trace, "hlo_protos",
                        lambda path: {STEP: _module(),
                                      "jit_init(3)": _module()})
    assert reader.compute(RUN) == pytest.approx(want)


@pytest.mark.parametrize("rows,protos", [
    (None, {STEP: _module()}),      # a program without the join
    ([_row("fusion.1", 4)], {}),    # a trace without the step's HLO
])
def test_reader_leaves_the_metric_out_where_nothing_is_to_read(
        reader, monkeypatch, rows, protos):
    monkeypatch.setattr(step_anatomy, "_chip0_rows", lambda *a: rows)
    monkeypatch.setattr(trace, "hlo_protos", lambda path: protos)
    assert reader.compute(RUN) is None
    assert reader.compute({"trace": None}) is None
