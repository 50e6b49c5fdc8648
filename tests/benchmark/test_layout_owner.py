"""The two readers of PR 49 (benchmarks/layout_owner.py,
`op_time_owned_share` and `device_ms_per_step.layout_state` in
benchmarks/layer_metrics/) on the CPU: their arithmetic on hand-made
rows of the program's join, the two printed tables, `None` without a
trace and on a program whose rows carry no owner, and each file
against its `BENCHMARK.json` entry, found BY NAME.  No number from
here is a speed."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

import layout_owner  # noqa: E402
import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

STEP, SLICE = "jit_step(9)", "jit__multi_slice(7)"
NEW = {"op_time_owned_share": ("%", "higher"),
       "device_ms_per_step.layout_state": ("ms", "lower")}


def row(module, name, bucket, self_s, op_type=None, phase="other",
        owner=None, via="scope", owner_phase=None, source=None,
        shape="f32[8]", nbytes=32.0, calls=4, joined=True):
    """A row of `observe/trace.py join_events` with its owner keys."""
    return {"chip": 0, "module": module, "instruction": name,
            "op_name": "x", "op_type": op_type, "phase": phase,
            "bucket": bucket, "flops": 0.0, "bytes": 1.0,
            "joined": joined, "calls": calls, "self_s": self_s,
            "owner_op_type": owner or op_type,
            "owner_phase": owner_phase or phase, "owner_via": via,
            "owner_consumers": int(via == "consumer"), "source": source,
            "shape": shape, "shape_bytes": nbytes}


# four steps in the window; seconds over the window
ROWS = [
    row(STEP, "fusion.1", "matmul", 0.200, "mul", "forward"),
    row(STEP, "fusion.2", "elementwise", 0.060, "adam"),
    # the compiler's own: a weight's prefetch in two slices, the copy
    # of an activation, a copy nobody owns, a scoped transpose
    row(STEP, "slice-start.3", "layout", 0.004, owner="mul",
        via="consumer", owner_phase="forward", source="state",
        shape="bf16[4,512]", nbytes=4096.0, calls=8),
    row(STEP, "slice-done.3", "layout", 0.036, owner="mul",
        via="consumer", owner_phase="forward", source="state",
        shape="bf16[4,512]", nbytes=4096.0, calls=8),
    row(STEP, "slice-done.4", "layout", 0.020, owner="mul",
        via="consumer", owner_phase="forward", source="state",
        shape="bf16[4,512]", nbytes=4096.0, calls=8),
    row(STEP, "copy.5", "layout", 0.024, owner="adam", via="producer",
        source="activation"),
    row(STEP, "copy.6", "layout", 0.008, via="none", source="carry"),
    row(STEP, "fusion.7", "layout", 0.040, "transpose", "backward",
        source="activation"),
    row(STEP, "fusion.999", "unknown", 0.008, via="none", joined=False),
    # another program's copy is not the step's
    row(SLICE, "copy.1", "layout", 0.002, via="none", source="state"),
]
RUN = {"trace": {"path": "/nowhere/x.xplane.pb",
                 "chip0": {"lo": 10.0, "hi": 14.0, "steps": 4}}}


@pytest.fixture
def readers():
    return bench_run.layer_readers("tbase-256", (BENCH,))


@pytest.fixture
def joined(monkeypatch):
    rows = list(ROWS)
    monkeypatch.setattr(step_anatomy, "_chip0_rows",
                        lambda path, lo, hi: rows)
    return rows


@pytest.mark.parametrize("name,want", [
    # 0.400 s in the step program, 0.016 s of it nobody's
    ("op_time_owned_share", 96.0),
    # (0.004 + 0.036 + 0.020) s over 4 steps: the step's own `state`
    # rows, not `_multi_slice`'s
    ("device_ms_per_step.layout_state", 15.0),
])
def test_reader_on_hand_made_rows(readers, joined, name, want):
    assert readers[name].compute(RUN) == pytest.approx(want)
    layout = readers["device_ms_per_step.layout"].compute(RUN)
    joined_share = readers["op_time_joined_share"].compute(RUN)
    if name == "device_ms_per_step.layout_state":
        assert 0.0 <= want <= layout == pytest.approx(33.0)
    else:       # a hand-off can only add to what the scopes gave
        assert want >= joined_share


def test_the_printed_tables(readers, joined, capfd):
    readers["op_time_owned_share"].compute(RUN)
    lines = [json.loads(ln) for ln in capfd.readouterr().out.splitlines()]
    owned = lines[0]["fluid_op_table_owned"]
    # the prefetches and slices went to `mul`, the copy behind `adam`
    # to `adam`: 0.260 and 0.084 s over 4 steps of 0.400 s
    assert owned[0] == ["mul", "forward", pytest.approx(65.0),
                        pytest.approx(0.65)]
    assert ["adam", "other", pytest.approx(21.0),
            pytest.approx(0.21)] in owned
    assert ["[no scope]", "other", pytest.approx(4.0),
            pytest.approx(0.04)] in owned
    assert sum(r[3] for r in owned) == pytest.approx(1.0)
    table = lines[1]["layout_table"]
    # grouped by owner, phase, via, source, opcode and shape: the two
    # `slice-done`s are one group
    assert table[0] == ["mul", "forward", "consumer", "state",
                        "slice-done", "bf16[4,512]", pytest.approx(14.0),
                        pytest.approx(4.0), 4096.0]
    assert table[1][:6] == ["transpose", "backward", "scope",
                            "activation", "fusion", "f32[8]"]
    assert ["[no scope]", "other", "none", "carry", "copy", "f32[8]",
            pytest.approx(2.0), pytest.approx(1.0), 32.0] in table
    # the rows sum to `device_ms_per_step.layout`
    assert sum(r[6] for r in table) == pytest.approx(
        readers["device_ms_per_step.layout"].compute(RUN))


def test_a_long_layout_table_ends_in_the_rest(joined):
    joined += [row(STEP, f"copy.{100 + i}", "layout", 0.001 * (i + 1),
                   owner="mul", via="consumer", source="activation",
                   shape=f"f32[{i}]") for i in range(30)]
    a = step_anatomy.anatomy(RUN)
    table = layout_owner.layout_table(a)
    assert len(table) == 26 and table[-1][0] == "[rest]"
    assert sum(r[6] for r in table) == pytest.approx(
        step_anatomy.buckets_ms_per_step(a)["layout"])
    assert [r[6] for r in table[:-1]] == sorted(
        (r[6] for r in table[:-1]), reverse=True)


def test_none_without_a_trace_and_on_a_program_without_owners(
        readers, monkeypatch):
    """The parent commit's join gives rows without an owner: the
    readers leave their metrics out and do not raise."""
    for name in NEW:
        assert readers[name].compute({"trace": None}) is None, name
    old = [{k: v for k, v in r.items()
            if not k.startswith(("owner_", "source", "shape"))}
           for r in ROWS]
    monkeypatch.setattr(step_anatomy, "_chip0_rows",
                        lambda path, lo, hi: old)
    for name in NEW:
        assert readers[name].compute(RUN) is None, name
    assert readers["device_ms_per_step.layout"].compute(RUN) \
        == pytest.approx(33.0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_matches_its_benchmark_json_entry_by_name(readers, name):
    bj = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entries = [m for m in bj["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    entry, meta = entries[0], readers[name].META
    unit, better = NEW[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "device_trace", "layer": "ops",
                     "moves": "mfu"}
    assert (meta["layer"], meta["unit"], meta["moves"], meta["source"],
            meta["cells"]) == ("ops", unit, "mfu", "device_trace", None)
    # every cell reports `mfu`, so every cell has the reader
    for w in bj["workloads"]:
        assert name in bench_run.layer_readers(w["name"], (BENCH,))
