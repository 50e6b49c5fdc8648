"""Observability subsystem (paddle_tpu.observe): trace attribution,
device-side StepTelemetry, compile/retrace accounting, run events.

Locks in the architecture rules of docs/OBSERVE.md:
- op scopes reach XLA HLO metadata (the trace-attribution pillar),
- the telemetry accumulator lives INSIDE the one jitted step (no
  callbacks in the lowering, survives chain_iterations with zero extra
  dispatches),
- a feed shape change on a cached step counts exactly one retrace,
- the JSONL event log round-trips.
"""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe


def _linreg_program(batch_feed_names=("x", "y")):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return main, startup, scope, loss


def _feed(rng, n=8):
    return {"x": rng.rand(n, 4).astype(np.float32),
            "y": rng.rand(n, 1).astype(np.float32)}


def test_named_scopes_reach_compiled_hlo_and_no_callbacks():
    main, startup, scope, loss = _linreg_program()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        fn, state, feeds = exe._prepare(
            main, _feed(rng), [loss.name], scope, 1, True)
        lowered = fn.lower(state, feeds)
        stablehlo = lowered.as_text()
        # the ONE-computation invariant: telemetry/observability must
        # not introduce host round-trips
        assert "callback" not in stablehlo
        compiled_hlo = lowered.compile().as_text()
    # every op lowering is scoped "<op_type>:<op_index>" and the scope
    # survives into XLA's op metadata (what device traces attribute by)
    for op_type in ("mul", "mean", "sgd"):
        assert f"{op_type}:" in compiled_hlo, \
            f"scope for {op_type!r} missing from compiled HLO metadata"


def test_telemetry_accumulates_across_chained_iterations():
    main, startup, scope, loss = _linreg_program()
    observe.enable_telemetry(main)
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        feed = _feed(rng)
        exe.run(main, feed=feed, fetch_list=[loss])
        # 4 more steps in ONE dispatch: the accumulator must ride the
        # fori_loop carry, not a per-step host fetch
        exe.run(main, feed=feed, fetch_list=[loss], iterations=4)
    tel = observe.fetch_telemetry(scope)
    assert tel.steps == 5
    assert tel.loss_mean > 0.0
    assert tel.grad_norm_mean > 0.0
    assert tel.update_norm_mean > 0.0
    assert tel.healthy
    # the lowered telemetry-enabled step is still callback-free
    with fluid.scope_guard(scope):
        fn, state, feeds = exe._prepare(
            main, _feed(rng), [loss.name], scope, 4, True)
        assert "callback" not in fn.lower(state, feeds).as_text()
    # fetch(reset=True) starts a fresh window
    with fluid.scope_guard(scope):
        exe.run(main, feed=_feed(rng), fetch_list=[loss])
    tel2 = observe.fetch_telemetry(scope)
    assert tel2.steps == 1


def test_telemetry_counts_nonfinite_loss_and_grads():
    main, startup, scope, loss = _linreg_program()
    observe.enable_telemetry(main)
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        bad = _feed(rng)
        bad["x"][0, 0] = np.nan
        exe.run(main, feed=bad, fetch_list=[loss])
    tel = observe.fetch_telemetry(scope)
    assert tel.steps == 1
    assert tel.nonfinite_loss_steps == 1
    assert tel.nonfinite_grad_steps == 1
    assert not tel.healthy


def test_telemetry_off_is_zero_footprint():
    main, startup, scope, loss = _linreg_program()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=_feed(rng), fetch_list=[loss])
    assert scope.find_var(observe.TELEMETRY_VAR) is None
    assert observe.fetch_telemetry(scope) is None


def _wrap(main, loss, mesh):
    if mesh:
        from paddle_tpu.parallel import make_mesh

        fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, mesh=make_mesh(mesh))


@pytest.mark.parametrize("mesh", [None, {"dp": 2}])
def test_retrace_counter_increments_exactly_once_on_shape_change(mesh):
    main, startup, scope, loss = _linreg_program()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        _wrap(main, loss, mesh)
        exe.run(main, feed=_feed(rng, 8), fetch_list=[loss])
        snap = observe.runtime_stats.snapshot()
        # same signature: cached, no retrace
        exe.run(main, feed=_feed(rng, 8), fetch_list=[loss])
        assert observe.runtime_stats.delta(snap)["retraces"] == 0
        # new batch size = new jit signature = exactly one retrace
        exe.run(main, feed=_feed(rng, 6), fetch_list=[loss])
        d = observe.runtime_stats.delta(snap)
        assert d["retraces"] == 1
        # seen signature again: still one
        exe.run(main, feed=_feed(rng, 6), fetch_list=[loss])
        assert observe.runtime_stats.delta(snap)["retraces"] == 1
        assert observe.runtime_stats.delta(snap)["builds"] == 0


@pytest.mark.parametrize("mesh", [None, {"dp": 2}])
def test_compile_accounting_sees_backend_compiles(mesh):
    main, startup, scope, loss = _linreg_program()
    rng = np.random.RandomState(1)
    snap = observe.runtime_stats.snapshot()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        _wrap(main, loss, mesh)
        started = observe.runtime_stats.snapshot()
        exe.run(main, feed=_feed(rng), fetch_list=[loss])
        exe.run(main, feed=_feed(rng), fetch_list=[loss])
    d = observe.runtime_stats.delta(snap)
    assert d["compiles"] >= 1
    assert d["compile_time_s"] > 0.0
    assert d["builds"] == 2       # the start-up program, the step
    assert observe.runtime_stats.delta(started)["builds"] == 1
    assert d["dispatches"] >= 1


def test_event_log_roundtrip(tmp_path):
    path = os.path.join(str(tmp_path), "events.jsonl")
    with observe.RunEventLog(path, mesh_shape={"dp": 8}) as log:
        rid = log.run_id
        log.event("checkpoint", serial=3, epoch=1)
        log.telemetry_window({"steps": 10, "loss_mean": 0.5},
                             retraces=0)
    events = observe.read_events(path)
    kinds = [e["event"] for e in events]
    assert kinds == ["run_begin", "checkpoint", "telemetry", "run_end"]
    assert all(e["run_id"] == rid for e in events)
    begin = events[0]
    assert "git_sha" in begin and "argv" in begin
    assert begin["mesh_shape"] == {"dp": 8}
    assert events[2]["steps"] == 10 and events[2]["retraces"] == 0
    # a torn final line (killed writer) is tolerated; corruption in the
    # middle is not
    with open(path, "a") as f:
        f.write('{"ts": 1, "run_id"')
    assert len(observe.read_events(path)) == 4
    with open(path, "a") as f:
        f.write('\n{"ok": true}\n')
    with pytest.raises(json.JSONDecodeError):
        observe.read_events(path)


def test_fluid_op_of_scope_parsing():
    assert observe.fluid_op_of("jit(step)/mul:3/dot_general") == "mul"
    assert observe.fluid_op_of(
        "jit(step)/while/body/conv2d:12/convolution") == "conv2d"
    # innermost scope wins (nested macro op -> sub-block op)
    assert observe.fluid_op_of("jit(f)/while_op:2/mul:7/mul") == "mul"
    assert observe.fluid_op_of("jit(f)/transpose/no_scope_here") is None


def test_trace_summary_attributes_fluid_ops(tmp_path, capsys):
    """End-to-end pillar 1: run a step under profiler.profiler(), then
    the parsed per-op table must attribute device time to fluid op
    types (XLA:CPU emits per-instruction events, so this works on the
    test backend)."""
    from paddle_tpu import profiler

    main, startup, scope, loss = _linreg_program()
    rng = np.random.RandomState(0)
    trace_dir = os.path.join(str(tmp_path), "trace")
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        feed = _feed(rng)
        exe.run(main, feed=feed, fetch_list=[loss])  # compile outside
        with profiler.profiler(sorted_key="total",
                               profile_path=trace_dir):
            exe.run(main, feed=feed, fetch_list=[loss])
    printed = capsys.readouterr().out
    assert "Profiling Report" in printed
    rows = profiler.profile_table(trace_dir)
    assert rows, "no attributable device events parsed from trace"
    ops = {r["op_type"] for r in rows}
    fluid_ops = ops - {"[unattributed]"}
    assert fluid_ops, f"no fluid-op attribution in {ops}"
    for r in rows:
        assert r["calls"] >= 1
        assert r["total_ms"] >= 0.0
        assert 0.0 <= r["ratio"] <= 1.0


def test_trainer_telemetry_hook(tmp_path):
    from paddle_tpu.contrib import Trainer

    log_path = os.path.join(str(tmp_path), "run.jsonl")

    def train_func():
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        return layers.mean(layers.square_error_cost(pred, y))

    trainer = Trainer(
        train_func=train_func,
        optimizer_func=lambda: fluid.optimizer.SGDOptimizer(
            learning_rate=0.05),
        telemetry=observe.TelemetryConfig(interval=2, log_path=log_path))

    rng = np.random.RandomState(0)

    def reader():
        for _ in range(5):
            yield _feed(rng)

    trainer.train(num_epochs=1, reader=reader)
    trainer.stop()
    assert trainer.last_telemetry is not None
    events = observe.read_events(log_path)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_begin"
    assert "train_begin" in kinds and "train_end" in kinds
    windows = [e for e in events if e["event"] == "telemetry"]
    # 5 steps at interval 2 -> two full windows + the final flush of 1
    assert [w["steps"] for w in windows] == [2, 2, 1]
    for w in windows:
        assert w["loss_mean"] > 0.0
        assert "retraces" in w and "compile_time_s" in w
    assert windows[0]["epoch"] == 0


# -- host phases of one step (ISSUE 24) ------------------------------------

def test_phase_feeds_totals_ring_and_dispatches():
    from paddle_tpu.observe.monitoring import RuntimeStats

    stats = RuntimeStats()
    assert stats.recent("call") == []
    before = stats.snapshot()
    for name in ("prepare", "call", "call"):
        with stats.phase(name):
            pass
    d = stats.delta(before)
    assert (d["prepare_count"], d["call_count"]) == (1, 2)
    # `call` is the dispatch; no other phase counts as one
    assert d["dispatches"] == 2
    assert d["dispatch_time_s"] == d["call_time_s"] > 0.0
    assert sum(stats.recent("call")) == pytest.approx(d["call_time_s"])
    assert len(stats.recent("prepare")) == 1
    # a phase that raises is still recorded, and the error passes
    with pytest.raises(KeyError):
        with stats.phase("writeback"):
            raise KeyError("x")
    assert stats.snapshot()["writeback_count"] == 1
    # the ring is bounded, the totals are not
    for _ in range(5000):
        stats._record_phase("place", 1e-6)
    assert len(stats.recent("place")) == 4096
    assert stats.snapshot()["place_count"] == 5000
    assert not hasattr(stats, "last_dispatch_s")


@pytest.mark.parametrize("mesh", [None, {"dp": 1}, {"dp": 4}])
def test_every_run_records_four_phases_and_one_dispatch(mesh):
    from paddle_tpu.observe.monitoring import STEP_PHASES

    main, startup, scope, loss = _linreg_program()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        _wrap(main, loss, mesh)
        exe.run(main, feed=_feed(rng), fetch_list=[loss])
        snap = observe.runtime_stats.snapshot()
        tails = {p: len(observe.runtime_stats.recent(p))
                 for p in STEP_PHASES}
        for _ in range(3):
            exe.run(main, feed=_feed(rng), fetch_list=[loss])
    d = observe.runtime_stats.delta(snap)
    assert d["dispatches"] == 3
    for p in STEP_PHASES:
        assert d[p + "_count"] == 3 and d[p + "_time_s"] > 0.0
        grown = len(observe.runtime_stats.recent(p)) - tails[p]
        assert grown == 3 or len(observe.runtime_stats.recent(p)) == 4096


@pytest.mark.parametrize("mesh", [None, {"dp": 2}])
def test_phase_spans_lie_in_a_trace_nested_in_order(tmp_path, mesh):
    import jax

    from jax.profiler import ProfileData, TraceAnnotation

    main, startup, scope, loss = _linreg_program()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        _wrap(main, loss, mesh)
        feed = _feed(rng)
        exe.run(main, feed=feed, fetch_list=[loss])
        with jax.profiler.trace(str(tmp_path)):
            with TraceAnnotation("test.executor_run"):
                exe.run(main, feed=feed, fetch_list=[loss])
    from paddle_tpu.observe.trace import _trace_files

    (path,) = _trace_files(str(tmp_path))
    spans = sorted(
        ((e.start_ns, e.start_ns + e.duration_ns, e.name)
         for plane in ProfileData.from_file(path).planes
         if plane.name == "/host:CPU"
         for line in plane.lines for e in line.events
         if e.name.startswith(("paddle_tpu.step.", "test."))),
        key=lambda s: (s[0], -s[1]))        # a parent before its child
    names = [s[2] for s in spans]
    phases = ["paddle_tpu.step." + p
              for p in ("prepare", "place", "call", "writeback")]
    children = ["paddle_tpu.step.place_state",
                "paddle_tpu.step.place_feed"] if mesh else []
    assert names == (["test.executor_run"] + phases[:2] + children
                     + phases[2:])
    by_name = {s[2]: s for s in spans}
    outer = by_name["test.executor_run"]
    for a, b in zip(phases, phases[1:]):
        assert outer[0] <= by_name[a][0] and by_name[b][1] <= outer[1]
        assert by_name[a][1] <= by_name[b][0]       # one after the other
    for c in children:
        place = by_name["paddle_tpu.step.place"]
        assert place[0] <= by_name[c][0] and by_name[c][1] <= place[1]


# -- the join of device events to the program's names (ISSUE 24) -----------

TPU_FUSION = ("%fusion.157 = (f32[2048]{0:T(1024)}, bf16[64,256,2048]"
              "{2,1,0:T(8,128)(2,1)}) fusion(f32[2048]{0:T(1024)} "
              "%copy.1345), kind=kOutput, calls=%fused_computation.229")
TPU_COPY = "%copy.1345 = f32[2048]{0:T(1024)} copy(f32[2048]{0} %p.1)"
TPU_WHILE = ("%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) "
             "%tuple.1), condition=%cond.1, body=%body.1")


def _info(op_name, bucket="elementwise", flops=10.0, nbytes=4.0):
    return {"op_name": op_name, "bucket": bucket, "flops": flops,
            "bytes": nbytes}


STEP = "jit_step(3523654495268983989)"
SLICE = "jit__multi_slice(77)"
PROGRAMS = {
    STEP: {"fusion.157": _info("jit(step)/jvp(mul:3)/dot_general",
                               "matmul", 4e9, 2e6),
           "copy.1345": _info("", "layout", 0.0, 16384.0),
           "fusion.1": _info("jit(step)/transpose(jvp(softmax:25))/mul"),
           "while.3": _info("jit(step)/while_op:9/while", "loop"),
           # body instructions: in the map, no cost row
           "fusion.88": {"op_name": "jit(step)/while_op:9/mul:2/mul",
                         "bucket": None, "flops": None, "bytes": None},
           "add.5": {"op_name": "jit(step)/while_op:9/adam:4/add",
                     "bucket": None, "flops": None, "bytes": None}},
    SLICE: {"fusion.1": _info("jit(_multi_slice)/slice", "layout")},
}


def _rows(ops, modules, window=None, programs=PROGRAMS):
    from paddle_tpu.observe.trace import join_events

    rows = join_events(ops, modules, programs, window=window, chip=2)
    return {(r["module"], r["instruction"]): r for r in rows}


JOIN_CASES = {
    # whole-instruction TPU names are cut to the instruction name and
    # hit their program's map; XLA:CPU's bare names pass through
    "tpu_names_are_cut": dict(
        ops=[(TPU_FUSION, 1.0, 0.5), (TPU_COPY, 1.5, 0.25),
             ("fusion.157", 2.0, 0.5)],
        modules=[(STEP, 0.9, 2.0)],
        expect={(STEP, "fusion.157"): dict(
            self_s=1.0, calls=2, bucket="matmul", op_type="mul",
            phase="forward", flops=4e9, joined=True),
            (STEP, "copy.1345"): dict(
            self_s=0.25, bucket="layout", op_type=None, phase="other",
            joined=True)}),
    # two programs share `fusion.1`: the enclosing module event decides
    "same_name_two_programs": dict(
        ops=[("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p)", 1.0, 0.1),
             ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 2.0, 0.3)],
        modules=[(SLICE, 0.95, 0.2), (STEP, 1.9, 1.0)],
        expect={(SLICE, "fusion.1"): dict(
            self_s=0.1, bucket="layout", op_type=None),
            (STEP, "fusion.1"): dict(
            self_s=0.3, bucket="elementwise", op_type="softmax",
            phase="backward")}),
    # a while with two body events: the three rows sum to the while's
    # own duration, the body rows take the bucket `loop`
    "while_gives_its_time_to_its_body": dict(
        ops=[(TPU_WHILE, 1.0, 1.0),
             ("%fusion.88 = f32[8]{0} fusion(f32[8]{0} %x)", 1.1, 0.3),
             ("%add.5 = f32[8]{0} add(f32[8]{0} %x, f32[8]{0} %y)",
              1.5, 0.4), (TPU_COPY, 2.0, 0.5)],
        modules=[(STEP, 0.5, 3.0)],
        expect={(STEP, "while.3"): dict(self_s=0.3, total_s=1.0,
                                        bucket="loop"),
                (STEP, "fusion.88"): dict(self_s=0.3, bucket="loop",
                                          op_type="mul", flops=None),
                (STEP, "add.5"): dict(self_s=0.4, bucket="loop",
                                      op_type="adam"),
                (STEP, "copy.1345"): dict(self_s=0.5)},
        total=1.5),
    # ops that start in [lo, hi) count, others do not
    "a_window_cuts_rows": dict(
        ops=[(TPU_COPY, 0.5, 0.2), (TPU_FUSION, 1.0, 0.5),
             (TPU_COPY, 1.7, 0.25), (TPU_FUSION, 2.0, 0.5)],
        modules=[(STEP, 0.4, 3.0)], window=(1.0, 2.0),
        expect={(STEP, "fusion.157"): dict(self_s=0.5, calls=1),
                (STEP, "copy.1345"): dict(self_s=0.25, calls=1)},
        total=0.75),
    # an op outside every module event, or missing from its program's
    # map, is unjoined: never looked up in another program's map
    "unknown_stays_unknown": dict(
        ops=[("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p)", 5.0, 0.1),
             ("%fusion.999 = f32[4]{0} fusion(f32[4]{0} %p)", 1.0, 0.2)],
        modules=[(STEP, 0.9, 2.0)],
        expect={(None, "fusion.1"): dict(self_s=0.1, joined=False,
                                         bucket="unknown", op_type=None),
                (STEP, "fusion.999"): dict(self_s=0.2, joined=False,
                                           bucket="unknown")}),
    # XLA:CPU: a 4-tuple names its program itself
    "cpu_events_name_their_program": dict(
        ops=[("fusion.1", 1.0, 0.1, SLICE), ("fusion.1", 1.2, 0.1, STEP)],
        modules=[],
        expect={(SLICE, "fusion.1"): dict(bucket="layout"),
                (STEP, "fusion.1"): dict(op_type="softmax")}),
}


@pytest.mark.parametrize("case", sorted(JOIN_CASES))
def test_join_events_on_hand_made_events(case):
    c = JOIN_CASES[case]
    rows = _rows(c["ops"], c["modules"], c.get("window"))
    assert set(rows) == set(c["expect"])
    for key, want in c["expect"].items():
        for field, value in want.items():
            assert rows[key][field] == pytest.approx(value), (key, field)
        assert rows[key]["chip"] == 2
    if "total" in c:
        assert sum(r["self_s"] for r in rows.values()) == pytest.approx(
            c["total"])


def test_read_events_takes_op_time_from_the_ops_line_only(monkeypatch):
    """`Steps`, `XLA Modules` and `Async XLA Ops` events of a device
    plane are not op time; host lines are read only where there is no
    device plane."""
    import jax.profiler

    from paddle_tpu.observe import trace

    class Ev:
        def __init__(self, name, start, dur, stats=()):
            self.name, self.start_ns, self.duration_ns = name, start, dur
            self.stats = list(stats)

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    step = Ev("3", 1e9, 1e9)
    module = Ev(STEP, 1e9, 9e8)
    op = Ev(TPU_FUSION, 1.1e9, 2e8)
    dma = Ev("%copy-start.4 = (f32[8]{0}) copy-start(...)", 1.1e9, 7e8)
    device = [Line("Steps", [step]), Line("XLA Modules", [module]),
              Line("XLA Ops", [op]), Line("Async XLA Ops", [dma])]
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.executor_run", 1e9, 1e6),
        Ev("fusion.1", 2e9, 1e6, [("hlo_op", "fusion.1"),
                                  ("hlo_module", "jit_step"),
                                  ("program_id", 5)])])])

    class Data:
        planes = [Plane("/host:metadata", []), host,
                  Plane("/device:TPU:0", device),
                  Plane("/device:TPU:1", device)]

    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: Data))
    got = trace.read_events("x.xplane.pb")
    assert got["host"] == [] and set(got["chips"]) == {0, 1}
    assert got["chips"][0] == {
        "ops": [(TPU_FUSION, pytest.approx(1.1), pytest.approx(0.2))],
        "modules": [(STEP, pytest.approx(1.0), pytest.approx(0.9))]}
    assert set(trace.read_events("x", chips=(1,))["chips"]) == {1}
    Data.planes = Data.planes[:2]          # XLA:CPU: no device plane
    got = trace.read_events("x.xplane.pb")
    assert got["chips"] == {} and got["host"] == [[
        ("fusion.1", pytest.approx(2.0), pytest.approx(1e-3),
         "jit_step(5)")]]


def test_hlo_protos_reads_only_the_metadata_plane(tmp_path):
    """The wire scanner finds each program's `Hlo Proto` in a real
    trace, and the trace's own module parses into a map whose entry
    instructions carry buckets and whose names carry fluid scopes."""
    import jax

    from paddle_tpu.observe import trace

    main, startup, scope, loss = _linreg_program()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        feed = _feed(rng)
        exe.run(main, feed=feed, fetch_list=[loss])
        with jax.profiler.trace(str(tmp_path)):
            exe.run(main, feed=feed, fetch_list=[loss])
    (path,) = trace._trace_files(str(tmp_path))
    protos = trace.hlo_protos(path)
    # every program the process compiled is there (the start-up
    # program is a `jit_step` too); the rows name the one that ran
    rows = trace.op_rows(path)
    steps = sorted({r["module"] for r in rows})
    assert len(steps) == 1 and steps[0] in protos
    assert sum(n.startswith("jit_step(") for n in protos) >= 2
    pmap = trace.program_map(protos[steps[0]])
    assert {"mul", "sgd"} <= {trace.fluid_op_of(i["op_name"])
                              for i in pmap.values() if i["op_name"]}
    assert any(i["bucket"] == "matmul" for i in pmap.values())
    assert {"forward", "backward", "other"} >= {r["phase"] for r in rows}
    assert sum(r["self_s"] for r in rows if r["joined"]) > 0
    # a window that holds nothing cuts every row
    assert trace.op_rows(path, windows={0: (-2.0, -1.0)}) == []
