"""The set-up readers of PR 34 (benchmarks/setup_anatomy.py and the
seven `setup_*` files of benchmarks/layer_metrics/) on the CPU: each
against its BENCHMARK.json entry, its arithmetic on hand-made records,
`None` where the program keeps no record, and the toy cells end to end
(one device and the mesh).  No number from here is a speed."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
FIXTURES = os.path.join(HERE, "fixtures")
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
import setup_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 8, "bf16_flops": 1e12}
NEW = {"setup_ms.build_program": ("ms", "program_span"),
       "setup_ms.startup_run": ("ms", "program_span"),
       "setup_ms.step_first_run": ("ms", "program_span"),
       "setup_ms.step_trace": ("ms", "program_span"),
       "setup_ms.step_compile": ("ms", "program_span"),
       "setup_cache_misses": ("count", "program_counter"),
       "setup_cold_runs": ("count", "program_counter")}
# the readers look at no trace, only at whether the run reduced one
RUN = {"trace": {"path": "/nowhere/x.xplane.pb"}}


def record(program, feed_arrays=0, fetches=0, call_s=0.0, **over):
    r = {"program": program, "ops": 5, "state_arrays": 3,
         "feed_arrays": feed_arrays, "fetches": fetches,
         "placement": False, "new_signature": True, "t_entry": 0.0,
         "prepare_s": 0.001, "place_s": 0.002, "call_s": call_s,
         "writeback_s": 0.003, "trace_s": 0.0, "lower_s": 0.0,
         "backend_compile_s": 0.0, "compiles": 0, "cache_hits": 0,
         "cache_misses": 0, "cache_read_s": 0.0}
    r.update(over)
    return r


# an earlier cell of the process (programs 1 and 2), then this one's:
# two start-up runs, the step's first run, the mesh's second lowering
# of the same step, and a retrace on a last short batch
RECORDS = [
    record(1, call_s=9.0, cache_misses=4),
    record(2, feed_arrays=2, fetches=1, call_s=9.0, cache_misses=1),
    record(3, call_s=0.5, cache_misses=2),
    record(3, call_s=0.25, new_signature=False),
    record(4, feed_arrays=2, fetches=1, call_s=2.0, trace_s=0.75,
           lower_s=0.25, backend_compile_s=0.5, cache_misses=1),
    record(4, feed_arrays=2, fetches=1, call_s=0.125,
           new_signature=False, trace_s=0.0625),
    record(4, feed_arrays=2, fetches=1, call_s=1.0, trace_s=0.5),
]


class Stats:
    """`runtime_stats` as the readers see it."""

    def __init__(self, records, **counters):
        self._records, self._counters = records, counters

    def cold_runs(self):
        return list(self._records)

    def snapshot(self):
        return dict(self._counters)


@pytest.fixture
def readers():
    return bench_run.layer_readers("tbase-256", (BENCH,))


def test_readers_match_their_benchmark_json_entries(readers):
    bj = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {m["name"]: m for m in bj["per_layer"]}
    # by name, in the issue's order, wherever later PRs' entries lie
    assert [m["name"] for m in bj["per_layer"] if m["name"] in NEW] == list(
        NEW)
    for name, (unit, source) in NEW.items():
        assert listed[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": source, "layer": "program -> one jitted step",
            "moves": "setup_s"}
        assert readers[name].META == {
            "layer": "program -> one jitted step", "unit": unit,
            "moves": "setup_s", "source": source, "cells": None}
    # every cell reports them: each reports setup_s
    for w in bj["workloads"]:
        assert set(NEW) <= set(bench_run.layer_readers(w["name"],
                                                       (BENCH,)))


def test_readers_on_hand_made_records(readers, monkeypatch, capfd):
    from paddle_tpu.observe import monitoring

    monkeypatch.setattr(monitoring, "runtime_stats", Stats(
        RECORDS, build_program_time_s=0.125, cold_runs=7))
    got = {name: readers[name].compute(RUN) for name in NEW}
    assert got == pytest.approx({
        "setup_ms.build_program": 125.0,
        # programs 3's two runs; 6 ms of other phases each
        "setup_ms.startup_run": 762.0,
        # the first of program 4's three records
        "setup_ms.step_first_run": 2006.0,
        "setup_ms.step_trace": 1000.0,
        "setup_ms.step_compile": 500.0,
        # over every record of the process
        "setup_cache_misses": 8.0,
        "setup_cold_runs": 7.0})
    printed = [json.loads(l) for l in capfd.readouterr().out.splitlines()]
    assert printed == [{"cold_runs": RECORDS}]


def test_pick_passes_later_runs_of_the_step_and_other_cells():
    startup, step = setup_anatomy.pick(RECORDS)
    assert step is RECORDS[4]
    assert startup == RECORDS[2:4]
    # a fetch without a feed (a metric read from the scope) is neither
    odd = [record(3), record(3, fetches=1), record(4, feed_arrays=1)]
    assert setup_anatomy.pick(odd) == ([odd[0]], odd[2])
    assert setup_anatomy.pick([record(3)]) == ([], None)
    assert setup_anatomy.pick([]) == ([], None)
    assert setup_anatomy.pick(None) == ([], None)


def test_readers_are_none_without_a_record(readers, monkeypatch):
    """The parent commit keeps no records and has no build counter: the
    readers leave their metrics out and do not raise.  A process that
    has not run a step yet reads the counters it has."""
    from paddle_tpu.observe import monitoring

    class Old:
        def snapshot(self):
            return {"compiles": 3}

    monkeypatch.setattr(monitoring, "runtime_stats", Old())
    for name in NEW:
        assert readers[name].compute(RUN) is None, name
    monkeypatch.setattr(monitoring, "runtime_stats", Stats(
        [], build_program_time_s=0.0, cold_runs=0))
    got = {name: readers[name].compute(RUN) for name in NEW}
    assert {n for n, v in got.items() if v is not None} == {
        "setup_ms.build_program", "setup_cold_runs"}
    # a run without a reduced trace (a CPU rehearsal): left out, as the
    # program's other timings are (`step_anatomy.executor_ms`)
    monkeypatch.setattr(monitoring, "runtime_stats", Stats(
        RECORDS, build_program_time_s=0.125, cold_runs=7))
    for name in NEW:
        assert readers[name].compute({"trace": None}) is None, name


@pytest.mark.parametrize("workload,mesh", [("tiny-host", False),
                                           ("tiny-dp4", True)])
def test_toy_cell_reads_all_seven_past_a_later_aot_compile(
        workload, mesh, readers, capfd):
    """The mesh cell's `residency()` AOT-compiles the step after the
    window and before the readers run: it leaves no record, and the
    step's record is still its first run.  (A CPU trace holds no
    device plane, so the line leaves the readers out: they are called
    here as a chip's run calls them.)"""
    from paddle_tpu.observe.monitoring import runtime_stats

    before = runtime_stats.snapshot()
    result = bench_run.run_cell(workload, 11, 0.5, True,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    assert result["correct"], result
    assert not set(NEW) & set(result["metrics"])
    capfd.readouterr()
    m = {name: readers[name].compute(RUN) for name in NEW}
    m["compiles_in_window"] = result["metrics"]["compiles_in_window"][
        "value"]
    assert all(isinstance(v, (int, float)) for v in m.values()), m
    (line,) = capfd.readouterr().out.splitlines()
    records = json.loads(line)["cold_runs"]
    d = runtime_stats.delta(before)
    ours = records[-d["cold_runs"]:]
    startup, step = setup_anatomy.pick(records)
    assert step in ours and all(r in ours for r in startup)
    assert (step["feed_arrays"] > 0, step["fetches"]) == (True, 1)
    assert step["new_signature"] and step["placement"] == mesh
    assert step is next(r for r in ours if r["feed_arrays"])
    assert [r["feed_arrays"] + r["fetches"] for r in startup] == [0]
    # the tiny Transformer is built by `models/transformer.build_model`
    assert d["build_program_count"] == 1
    assert m["setup_ms.build_program"] == pytest.approx(
        1e3 * runtime_stats.snapshot()["build_program_time_s"])
    assert m["setup_ms.step_first_run"] == pytest.approx(
        1e3 * setup_anatomy.phases_s(step))
    assert m["setup_ms.step_trace"] + m["setup_ms.step_compile"] \
        <= 1.1 * m["setup_ms.step_first_run"]
    assert m["setup_ms.startup_run"] > 0.0
    assert m["setup_cold_runs"] == runtime_stats.snapshot()["cold_runs"]
    # nothing compiled in the window, and every cold run is one of
    # set-up: the start-up's, the step's first, over a mesh at most a
    # second lowering of the step
    assert m["compiles_in_window"] == 0
    assert 2 <= d["cold_runs"] <= (3 if mesh else 2)
    if mesh:
        # residency() prepared the step once more, outside any run
        assert d["prepare_count"] == d["call_count"] + 1
