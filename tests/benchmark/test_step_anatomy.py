"""The per-layer readers of PR 24 (benchmarks/step_anatomy.py and the
ten new files of benchmarks/layer_metrics/) on the CPU: each reader's
arithmetic on a hand-made `run` and hand-made rows of the program's
join, `None` without a trace or without the program's part, and the
host phases against the harness's own span around `Executor.run`.
No number from here is a speed."""

import json
import os
import statistics
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
FIXTURES = os.path.join(HERE, "fixtures")
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}
STEP, SLICE = "jit_step(9)", "jit__multi_slice(7)"
NEW = ["executor_ms.prepare", "executor_ms.place", "executor_ms.call",
       "executor_ms.writeback", "device_ms_per_step.matmul",
       "device_ms_per_step.elementwise", "device_ms_per_step.layout",
       "device_ms_per_step.conv", "mxu_peak_share",
       "op_time_joined_share"]


def row(module, name, bucket, self_s, calls=4, flops=0.0, op_type="mul",
        phase="forward", joined=True):
    return {"chip": 0, "module": module, "instruction": name,
            "op_name": "x", "op_type": op_type, "phase": phase,
            "bucket": bucket, "flops": flops, "bytes": 1.0,
            "joined": joined, "calls": calls, "self_s": self_s,
            "total_s": self_s, "max_s": self_s, "min_s": self_s}


# four steps in the window; seconds over the window
ROWS = [
    row(STEP, "fusion.1", "matmul", 0.200, flops=1e9),
    row(STEP, "fusion.2", "matmul", 0.040, flops=5e8, phase="backward"),
    row(STEP, "convolution.3", "conv", 0.080, flops=2e9, op_type="conv2d"),
    row(STEP, "fusion.4", "elementwise", 0.060, op_type="adam",
        phase="other"),
    row(STEP, "copy.5", "layout", 0.012, op_type=None, phase="other"),
    row(STEP, "all-reduce.6", "comm", 0.004, op_type=None, phase="other"),
    row(STEP, "fusion.999", "unknown", 0.002, op_type=None, joined=False),
    row(SLICE, "fusion.1", "layout", 0.002, op_type=None, phase="other"),
]
RUN = {"trace": {"path": "/nowhere/x.xplane.pb",
                 "chip0": {"lo": 10.0, "hi": 14.0, "steps": 4}}}


@pytest.fixture
def readers():
    return bench_run.layer_readers("resnet50-b128", (BENCH,))


@pytest.fixture
def joined(monkeypatch):
    """The program's join, replaced by the hand-made rows."""
    calls = []

    def rows(path, lo, hi):
        calls.append((path, lo, hi))
        return ROWS

    monkeypatch.setattr(step_anatomy, "_chip0_rows", rows)
    monkeypatch.setattr(step_anatomy, "peak_flops", lambda: 1e11)
    return calls


@pytest.mark.parametrize("name,want", [
    # (0.200 + 0.040) s over 4 steps
    ("device_ms_per_step.matmul", 60.0),
    ("device_ms_per_step.conv", 20.0),
    ("device_ms_per_step.elementwise", 15.0),
    # the step program's copy only: `_multi_slice`'s is left out
    ("device_ms_per_step.layout", 3.0),
    # FLOPs 4 x (1e9 + 5e8 + 2e9) = 1.4e10 over 0.32 s = 4.375e10
    # FLOP/s of a 1e11 peak
    ("mxu_peak_share", 43.75),
    # 0.38 s of 0.40 s found and scoped: not the copies, the
    # all-reduce, the unknown fusion or `_multi_slice`
    ("op_time_joined_share", 95.0),
])
def test_device_reader_on_hand_made_rows(readers, joined, name, want,
                                         capfd):
    assert readers[name].compute(RUN) == pytest.approx(want)
    assert joined[0] == ("/nowhere/x.xplane.pb", 10.0, 14.0)
    if name == "op_time_joined_share":
        lines = [json.loads(l) for l in capfd.readouterr().out.splitlines()]
        buckets = lines[0]["device_ms_per_step_by_bucket"]
        assert lines[0]["step_program"] == STEP
        assert buckets == pytest.approx({
            "matmul": 60.0, "conv": 20.0, "elementwise": 15.0,
            "layout": 3.0, "comm": 1.0, "unknown": 0.5,
            "[other programs]": 0.5})
        # every bucket and the other programs: the chip's busy time
        assert sum(buckets.values()) == pytest.approx(100.0)
        table = lines[1]["fluid_op_table"]
        assert table[0] == ["mul", "forward", pytest.approx(50.0),
                            pytest.approx(0.2 / 0.398)]
        assert ["conv2d", "forward", pytest.approx(20.0),
                pytest.approx(0.08 / 0.398)] in table
        assert sum(r[3] for r in table) == pytest.approx(1.0)


def test_every_new_reader_is_none_without_a_trace(readers):
    assert set(NEW) <= set(readers)
    for name in NEW:
        assert readers[name].compute({"trace": None}) is None, name


def test_host_readers_are_the_median_of_the_programs_ring(readers,
                                                          monkeypatch):
    from paddle_tpu.observe import monitoring

    stats = monitoring.RuntimeStats()
    for ms in (1.0, 9.0, 2.0):
        stats._record_phase("place", ms / 1e3)
    monkeypatch.setattr(monitoring, "runtime_stats", stats)
    assert readers["executor_ms.place"].compute(RUN) == pytest.approx(2.0)
    # a phase the process never entered is left out
    assert readers["executor_ms.call"].compute(RUN) is None


def test_a_program_without_the_join_or_the_ring_gives_none(
        readers, monkeypatch):
    """The parent commit has neither `op_rows` nor `recent`: the
    readers leave their metrics out and do not raise."""
    from paddle_tpu.observe import monitoring, trace

    class Old:
        pass

    step_anatomy._chip0_rows.cache_clear()
    monkeypatch.delattr(trace, "op_rows")
    monkeypatch.setattr(monitoring, "runtime_stats", Old())
    for name in NEW:
        assert readers[name].compute(RUN) is None, name
    step_anatomy._chip0_rows.cache_clear()


def test_phases_sum_to_the_harness_span_around_executor_run():
    """The four phases are `dispatch_ms.train` seen from inside.  Held
    on the traced slice, where the harness's `bench.executor_run` span
    and the program's four spans cover the SAME steps (a mean over
    other steps would move with every hiccup of a loaded test host)."""
    from jax.profiler import ProfileData

    import trace_reduce
    from paddle_tpu.observe.monitoring import STEP_PHASES, runtime_stats

    tails = {p: len(runtime_stats.recent(p)) for p in STEP_PHASES}
    result = bench_run.run_cell("tiny-host", 7, 1.0, True,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    # a CPU trace holds no device plane: `run["trace"]` is None and
    # none of the new readers is in the line
    assert not set(NEW) & set(result["metrics"])
    for p in STEP_PHASES:       # every step of the run is in the ring
        assert len(runtime_stats.recent(p)) - tails[p] >= \
            result["attempted"]
    spans = {}
    path = trace_reduce.newest_xplane(
        os.path.join(bench_run.TRACE_ROOT, "tiny-host"))
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("bench.executor_run",
                                          "paddle_tpu.step.")):
                        spans.setdefault(e.name, []).append(e.duration_ns)
    outside = spans.pop("bench.executor_run")
    assert set(spans) == {"paddle_tpu.step." + p for p in STEP_PHASES}
    assert {len(v) for v in spans.values()} == {len(outside)}
    inside = sum(map(sum, spans.values()))
    assert 0.7 <= inside / sum(outside) <= 1.0, (inside, sum(outside))
    medians = sum(statistics.median(v) for v in spans.values())
    assert 0.7 <= medians / statistics.median(outside) <= 1.05


def test_peak_flops_reads_this_devices_row(monkeypatch):
    import jax

    class Dev:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    assert step_anatomy.peak_flops() == 197e12
    Dev.device_kind = "cpu"
    assert step_anatomy.peak_flops() is None
