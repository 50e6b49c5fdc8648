"""Observe pillar 8: the wall-clock goodput ledger.

Locks in the ISSUE 16 acceptance criteria:
- Σ categories == elapsed wall, by construction ("idle" is the
  residual) — fake-clock exact and real-Trainer within rounding,
- the guard discipline: threading a ledger adds zero dispatches, zero
  retraces, and the step lowering is byte-identical with or without
  it (the ledger is PURE HOST — monotonic reads at phase boundaries),
- XLA compile wall is re-attributed out of whichever phase it struck
  (a first step contributes dispatch time to "step", compile to
  "compile"),
- restart-replay badput: a crash between the last checkpoint and the
  progress cursor makes the relaunch re-execute steps, counted as
  "replay" with the resume→crash window recorded,
- data stalls: a slow reader's next() time lands in "data_stall",
- checkpoint blocking lands in "checkpoint" and ckpt_stats keeps the
  old blocking_ms/write_ms keys as ledger reads,
- prometheus exposition via goodput_collector in the Trainer's
  MetricsRegistry,
- the step-anatomy chrome trace: one row per category under pid 1000.
"""

import contextlib
import json
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.observe.goodput import (CATEGORIES, GOODPUT_TRACE_PID,
                                        PHASE_CATEGORIES, GoodputLedger,
                                        format_goodput_table,
                                        goodput_report)


class FakeClock:
    """Deterministic monotonic clock for exact-arithmetic tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


# ---------------------------------------------------------------------------
# Ledger unit tests (fake clock: exact arithmetic)
# ---------------------------------------------------------------------------

def test_sum_of_categories_equals_wall_exactly():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    led.open_window()
    with led.phase("step", steps=1):
        clk.advance(1.0)
    with led.phase("data_stall"):
        clk.advance(0.5)
    with led.phase("checkpoint", label="save:0"):
        clk.advance(0.2)
    clk.advance(0.3)  # unclaimed host time -> idle residual
    led.close_window()
    rep = led.report()
    assert rep["wall_s"] == 2.0
    cats = rep["categories_s"]
    assert set(cats) == set(CATEGORIES)
    assert cats["step"] == 1.0
    assert cats["data_stall"] == 0.5
    assert cats["checkpoint"] == 0.2
    assert cats["idle"] == 0.3
    assert sum(cats.values()) == rep["wall_s"]
    assert abs(sum(rep["fractions"].values()) - 1.0) < 1e-9
    assert rep["goodput"] == 0.5
    assert rep["steps"] == 1
    assert rep["mean_step_s"] == 1.0
    # module-level alias returns the same decomposition
    assert goodput_report(led) == rep


def test_unknown_category_raises():
    led = GoodputLedger(clock=FakeClock())
    with pytest.raises(ValueError, match="unknown goodput category"):
        with led.phase("espresso"):
            pass
    # "idle" is the residual, never claimable explicitly
    with pytest.raises(ValueError):
        with led.phase("idle"):
            pass


def test_nested_phase_own_time_excludes_child():
    """Exclusivity under nesting: a checkpoint inside a step claims
    its slice ONCE — the parent's own time excludes the child's."""
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    with led.window():
        with led.phase("step", steps=1):
            clk.advance(0.4)
            with led.phase("checkpoint"):
                clk.advance(0.3)
            clk.advance(0.3)
    rep = led.report()
    assert rep["categories_s"]["step"] == pytest.approx(0.7)
    assert rep["categories_s"]["checkpoint"] == pytest.approx(0.3)
    assert sum(rep["categories_s"].values()) == \
        pytest.approx(rep["wall_s"])


def test_outside_window_phase_joins_wall():
    """An instrumented wait AFTER close_window (the gang
    done-rendezvous) still keeps Σ categories == wall."""
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    led.open_window()
    with led.phase("step", steps=1):
        clk.advance(1.0)
    led.close_window()
    with led.phase("barrier_wait"):
        clk.advance(0.7)
    rep = led.report()
    assert rep["wall_s"] == pytest.approx(1.7)
    assert rep["categories_s"]["barrier_wait"] == pytest.approx(0.7)
    assert sum(rep["categories_s"].values()) == \
        pytest.approx(rep["wall_s"])


def test_background_channel_is_not_a_wall_category():
    """Overlapped work (the async checkpoint writer thread) rides the
    side channel — never double-counted into the wall."""
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    with led.window():
        with led.phase("step", steps=1):
            clk.advance(1.0)
        led.note_background("ckpt_write", 1.5)
    rep = led.report()
    assert rep["wall_s"] == 1.0
    assert sum(rep["categories_s"].values()) == rep["wall_s"]
    assert rep["background_ms"] == {"ckpt_write": 1500.0}
    assert led.background_ms("ckpt_write") == 1500.0


def test_open_window_idempotent_and_live_wall():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    led.open_window()
    clk.advance(1.0)
    led.open_window()  # idempotent: must NOT reset the wall origin
    clk.advance(1.0)
    assert led.wall_s() == pytest.approx(2.0)  # live read, still open
    led.close_window()
    led.close_window()  # idempotent too
    assert led.wall_s() == pytest.approx(2.0)


def test_replay_counting_and_info():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    led.note_replay((0, 6), (0, 9))
    with led.window():
        with led.phase("replay", steps=3):
            clk.advance(0.9)
        with led.phase("step", steps=2):
            clk.advance(0.8)
    rep = led.report()
    assert rep["replay_steps"] == 3
    assert rep["steps"] == 2
    assert rep["replay"] == {"from": [0, 6], "to": [0, 9]}
    assert rep["categories_s"]["replay"] == pytest.approx(0.9)
    # replay is badput: goodput counts only the fresh steps
    assert rep["goodput"] == pytest.approx(0.8 / 1.7)


def test_effective_mfu_and_straggler_estimate():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    with led.window():
        with led.phase("step", steps=4):
            clk.advance(2.0)
        clk.advance(2.0)
    rep = led.report(mfu=0.32, skew={"max_lag_steps": 4})
    assert rep["goodput"] == 0.5
    assert rep["mfu"] == 0.32
    assert rep["effective_mfu"] == round(0.32 * 0.5, 6)
    assert rep["straggler_est_s"] == pytest.approx(4 * 0.5)
    table = format_goodput_table(rep)
    assert "effective_mfu" in table and "straggler_est_s" in table
    for c in CATEGORIES:
        assert c in table


def test_span_ring_bounded_with_drop_counter():
    clk = FakeClock()
    led = GoodputLedger(clock=clk, max_spans=1)  # clamps to 16
    with led.window():
        for _ in range(20):
            with led.phase("step", steps=1):
                clk.advance(0.01)
    assert led.spans_dropped == 4
    rep = led.report()
    assert rep["spans_dropped"] == 4
    assert rep["steps"] == 20  # counters are NOT ring-bounded


def test_category_s_idle_residual_read():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    with led.window():
        with led.phase("step", steps=1):
            clk.advance(1.0)
        clk.advance(0.25)
    assert led.category_s("idle") == pytest.approx(0.25)
    assert led.category_ms("step") == pytest.approx(1000.0)


# ---------------------------------------------------------------------------
# Chrome trace (the step-anatomy timeline)
# ---------------------------------------------------------------------------

def test_chrome_trace_rows_and_pid(tmp_path):
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    with led.window():
        with led.phase("step", label="s0", steps=1):
            clk.advance(0.5)
        with led.phase("data_stall"):
            clk.advance(0.25)
    path = str(tmp_path / "goodput_trace.json")
    out = led.export_chrome_trace(path)
    with open(path) as f:
        assert json.load(f) == out
    ev = out["traceEvents"]
    procs = [e for e in ev if e["ph"] == "M"
             and e["name"] == "process_name"]
    assert procs[0]["pid"] == GOODPUT_TRACE_PID
    assert procs[0]["args"]["name"] == "training goodput"
    tids = {e["args"]["name"]: e["tid"] for e in ev
            if e["ph"] == "M" and e["name"] == "thread_name"}
    # one thread row per category present, tid = category index
    assert tids == {"step": PHASE_CATEGORIES.index("step"),
                    "data_stall": PHASE_CATEGORIES.index("data_stall")}
    xs = [e for e in ev if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["s0", "data_stall"]
    assert xs[0]["ts"] == 0.0 and xs[0]["dur"] == pytest.approx(5e5)
    assert xs[1]["ts"] == pytest.approx(5e5)
    assert all(e["pid"] == GOODPUT_TRACE_PID for e in xs)
    assert xs[0]["args"]["category"] == "step"
    # an explicit base shifts timestamps — the reqtrace-alignment knob
    shifted = led.export_chrome_trace(base=-1.0)
    xs2 = [e for e in shifted["traceEvents"] if e["ph"] == "X"]
    assert xs2[0]["ts"] == pytest.approx(1e6)


# ---------------------------------------------------------------------------
# Compile re-attribution (real executor, real clock)
# ---------------------------------------------------------------------------

def _named_program(lr=0.1):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[8], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        h = layers.fc(x, size=16, act="relu")
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(learning_rate=lr).minimize(loss)
    return main, startup, scope, loss


def _feed(rng, n=8):
    return {"x": rng.rand(n, 8).astype(np.float32),
            "y": rng.rand(n, 1).astype(np.float32)}


def test_compile_reattributed_out_of_step_phase():
    """A first step that triggers XLA compile must NOT inflate "step":
    the compile wall moves to "compile" wherever it struck."""
    main, startup, scope, loss = _named_program()
    feed = _feed(np.random.RandomState(0))
    led = GoodputLedger()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        with led.window():
            with led.phase("step", steps=1):  # first run: compiles
                exe.run(main, feed=feed, fetch_list=[loss])
            with led.phase("step", steps=1):  # warm: dispatch only
                exe.run(main, feed=feed, fetch_list=[loss])
    rep = led.report()
    assert rep["categories_s"]["compile"] > 0.0
    assert rep["steps"] == 2
    # the warm step bounds what a dispatch costs; the cold step's
    # "step" share must be dispatch-sized, not compile-sized
    assert rep["categories_s"]["step"] < rep["wall_s"]
    assert sum(rep["categories_s"].values()) == \
        pytest.approx(rep["wall_s"], abs=1e-3)


def test_window_level_compile_outside_phases():
    """Compile striking inside the window but outside any phase (an
    unwrapped eager warmup) is attributed at close_window."""
    main, startup, scope, loss = _named_program()
    feed = _feed(np.random.RandomState(1))
    led = GoodputLedger()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        led.open_window()
        exe.run(main, feed=feed, fetch_list=[loss])  # no phase
        led.close_window()
    rep = led.report()
    assert rep["categories_s"]["compile"] > 0.0
    assert sum(rep["categories_s"].values()) == \
        pytest.approx(rep["wall_s"], abs=1e-3)


# ---------------------------------------------------------------------------
# Guard discipline: zero overhead, byte-identical lowering
# ---------------------------------------------------------------------------

def test_ledger_is_zero_overhead_and_lowering_identical():
    """The ISSUE 4 guard discipline applied to pillar 8: running under
    a ledger adds zero dispatches and zero retraces, and the step
    lowering is BYTE-IDENTICAL with or without one — the ledger never
    touches the program, the trace, or the device."""
    rng_feed = _feed(np.random.RandomState(0))

    def run_and_count(with_ledger):
        main, startup, scope, loss = _named_program()
        led = GoodputLedger() if with_ledger else None
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            if led is not None:
                led.open_window()
            snap = observe.runtime_stats.snapshot()
            for _ in range(3):
                cm = (led.phase("step", steps=1) if led is not None
                      else contextlib.nullcontext())
                with cm:
                    exe.run(main, feed=rng_feed, fetch_list=[loss])
            delta = observe.runtime_stats.delta(snap)
            if led is not None:
                led.close_window()
            fn, state, feeds = exe._prepare(
                main, rng_feed, [loss.name], scope, 1, True)
            text = fn.lower(state, feeds).as_text()
        return delta, text

    off, text_off = run_and_count(False)
    on, text_on = run_and_count(True)
    assert on["dispatches"] == off["dispatches"]
    assert on["retraces"] == off["retraces"] == 0
    assert "callback" not in text_on  # pure host: no round-trips
    assert text_on == text_off  # byte-identical step lowering


# ---------------------------------------------------------------------------
# Trainer integration (slow reader, checkpoint, replay, metrics)
# ---------------------------------------------------------------------------

def _train_func():
    x = layers.data(name="x", shape=[6], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h = layers.fc(x, size=8, act="relu")
    pred = layers.fc(h, size=1)
    return layers.mean(layers.square_error_cost(pred, y))


def _opt_func():
    return fluid.optimizer.SGDOptimizer(learning_rate=0.01)


def _reader(n=6, delay=0.0):
    def read():
        r = np.random.RandomState(7)
        for _ in range(n):
            if delay:
                time.sleep(delay)
            yield {"x": r.rand(8, 6).astype(np.float32),
                   "y": r.rand(8, 1).astype(np.float32)}

    return read


def _trainer(ckpt_dir, log=None, step_interval=3):
    from paddle_tpu.contrib import CheckpointConfig, Trainer

    tel = (observe.TelemetryConfig(interval=100, log_path=log)
           if log else None)
    return Trainer(_train_func, _opt_func,
                   checkpoint_config=CheckpointConfig(
                       ckpt_dir, step_interval=step_interval,
                       epoch_interval=10 ** 6),
                   telemetry=tel)


def test_trainer_ledger_sums_to_wall_with_data_stall(tmp_path):
    """A short Trainer run, pinned: a slow reader's sleeps land
    in data_stall, checkpoint blocking in checkpoint, Σ == wall, and
    ckpt_stats keeps the old keys as ledger reads."""
    log = str(tmp_path / "ev.jsonl")
    t = _trainer(str(tmp_path / "ck"), log=log)
    t.train(num_epochs=1, reader=_reader(6, delay=0.02))
    t.stop()
    rep = t.goodput(mfu=0.3254)
    cats = rep["categories_s"]
    assert set(cats) == set(CATEGORIES)
    assert abs(sum(cats.values()) - rep["wall_s"]) < 1e-3
    assert abs(sum(rep["fractions"].values()) - 1.0) < 1e-4
    assert rep["steps"] == 6
    assert rep["replay_steps"] == 0
    assert cats["data_stall"] >= 6 * 0.02 * 0.8  # the sleeps, found
    assert cats["checkpoint"] > 0.0  # 2 saves @ interval 3
    # effective_mfu is derived from the UNROUNDED step fraction inside
    # report(); recomputing from the rounded goodput can differ by 1e-6
    assert rep["effective_mfu"] == \
        pytest.approx(0.3254 * rep["goodput"], abs=2e-6)
    # satellite: the pre-pillar-8 checkpoint-cost keys are now READS
    # of the ledger — old consumers see identical semantics
    assert t.ckpt_stats["blocking_ms"] == pytest.approx(
        t.goodput_ledger.category_ms("checkpoint"), abs=1e-3)
    assert t.ckpt_stats["write_ms"] == pytest.approx(
        t.goodput_ledger.background_ms("ckpt_write"), abs=1e-3)
    # the event log carries the report + the train_end summary fields
    events = observe.read_events(log)
    kinds = [e["event"] for e in events]
    assert "goodput_report" in kinds
    end = [e for e in events if e["event"] == "train_end"][-1]
    for k in ("goodput", "replay_steps", "wall_s",
              "ckpt_blocking_ms", "ckpt_write_ms"):
        assert k in end, k
    gp = [e for e in events if e["event"] == "goodput_report"][-1]
    assert gp["goodput"] == end["goodput"]


def test_trainer_restart_replay_badput(tmp_path):
    """ISSUE 16 acceptance (in-process form): crash after step 6's
    progress write but before step 7's, resume from the step-6
    checkpoint -> exactly the steps between checkpoint and crash
    cursor are accounted as replay, and replay seconds track
    replay_steps x mean step time."""
    from paddle_tpu.contrib.trainer import EndStepEvent

    ck = str(tmp_path / "ck")
    t = _trainer(ck)

    class Boom(RuntimeError):
        pass

    def handler(e):
        # EndStepEvent fires BEFORE the progress write for its step:
        # raising at step 7 leaves the crash cursor at (0, 7)
        if isinstance(e, EndStepEvent) and e.step == 7:
            raise Boom("chaos")

    with pytest.raises(Boom):
        t.train(num_epochs=1, reader=_reader(12),
                event_handler=handler)
    t.stop()

    t2 = _trainer(ck)
    # saves at steps 3 and 6 (interval 3): resume cursor is (0, 6)
    assert (t2._resume_epoch, t2._resume_step_in_epoch) == (0, 6)
    t2.train(num_epochs=1, reader=_reader(12))
    t2.stop()
    rep = t2.goodput()
    assert rep["replay_steps"] == 1  # step 6 ran twice
    assert rep["steps"] == 5  # steps 7..11 are fresh work
    assert rep["replay"] == {"from": [0, 6], "to": [0, 7]}
    assert rep["categories_s"]["replay"] > 0.0
    # replay badput ~ replayed-step count x mean step time; the first
    # resumed dispatch pays a residual cold cost beyond the
    # re-attributed trace/compile wall — allowed as absolute slack
    est = rep["replay_steps"] * rep["mean_step_s"]
    assert 0.1 * est < rep["categories_s"]["replay"] < 10 * est + 0.1
    assert abs(sum(rep["categories_s"].values()) - rep["wall_s"]) \
        < 1e-3
    # a clean run records no replay
    t3 = _trainer(str(tmp_path / "ck2"))
    t3.train(num_epochs=1, reader=_reader(3))
    t3.stop()
    clean = t3.goodput()
    assert clean["replay_steps"] == 0 and "replay" not in clean


def test_trainer_prometheus_exposition(tmp_path):
    """goodput_collector rides the Trainer's MetricsRegistry: the
    pillar-8 families appear in text exposition format 0.0.4."""
    t = _trainer(str(tmp_path / "ck"))
    t.train(num_epochs=1, reader=_reader(3))
    t.stop()
    text = t.metrics_registry().prometheus_text()
    assert "goodput_available 1" in text
    assert "goodput_fraction_good " in text
    assert "goodput_wall_seconds_total " in text
    assert "goodput_steps_total 3" in text
    assert "goodput_replay_steps_total 0" in text
    assert 'goodput_fraction{category="step"}' in text
    assert 'goodput_badput_seconds_total{category="checkpoint"}' \
        in text
    # "step" is goodput, never badput
    assert 'goodput_badput_seconds_total{category="step"}' not in text
    assert "goodput_mean_step_seconds " in text
    assert "goodput_effective_mfu" in text  # family present (no mfu)


def test_goodput_collector_before_any_ledger():
    """fetch -> None (no run yet) degrades to goodput_available 0 —
    the one-sick-subsystem isolation contract."""
    from paddle_tpu.observe.registry import (MetricsRegistry,
                                             goodput_collector)

    reg = MetricsRegistry().register(
        "goodput", goodput_collector(lambda: None))
    text = reg.prometheus_text()
    assert "goodput_available 0" in text
    assert "goodput_wall_seconds_total" not in text
    assert 'observe_collector_up{collector="goodput"} 1' in text
