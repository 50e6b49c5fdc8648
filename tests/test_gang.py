"""Gang fault tolerance (ISSUE 9): distributed health plane,
coordinated abort, and the self-healing multi-process supervisor.

Fast (in-process) coverage — FakeKv + injected clocks, no real
process death:
- heartbeat publish/beat, peer-loss detection within the configured
  miss budget (poison written + peer_lost event emitted), startup
  grace, stall detection, KV-unreachable == coordinator loss,
- poison write/read/consume: `plane.check()` raises each poison
  exactly ONCE (idempotent across an in-process re-`train()` — the
  PR 7 drain-flag mirror),
- orderly leave: a rank that published its done marker is departed,
  not dead,
- straggler telemetry: per-rank step-rate skew + rank_slow events,
- Deadline's timer-thread fallback (off-main-thread watchdog),
- DispatchWatchdog: compile-grace vs hung-step distinguished under
  `chaos.hang`, step_hang event emitted before the abort,
- the barrier poison fast-path (`io._wait_barrier_peers`),
- Supervisor: exit-code registry, crash→restart with the
  deterministic backoff schedule, budget exhaustion →
  GangFailedError with per-attempt exit codes, preempt-drain
  relaunch without backoff,
- `shutdown_distributed()` idempotence,
- Trainer integration: ZERO extra dispatches/retraces with the
  health plane enabled (the acceptance counter assert), poison abort
  + idempotent re-train, per-step watchdog budgets.

Slow (real-subprocess) chaos — the acceptance proof:
- SIGKILL a RANDOM rank mid-train (coordinator included — the
  supervisor hosts the coordination service so rank 0 is killable
  too): the survivor detects within the miss budget (structured
  PeerLostError naming the dead rank), the supervisor kills the
  remainder and relaunches, and the restarted gang finishes with
  params BIT-IDENTICAL to an uninterrupted control run, no orphans,
- a checkpoint barrier with a poisoned peer aborts in seconds (vs
  its 120 s timeout) with the poison reason attached.
"""

import glob
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.contrib import Trainer
from paddle_tpu.io import _wait_barrier_peers
from paddle_tpu.resilience import (PEER_LOST_EXIT_CODE, PREEMPT_EXIT_CODE,
                                   CheckpointBarrierPoisonedError, Deadline,
                                   DispatchWatchdog, FakeKv, GangFailedError,
                                   GangPoisonedError, HealthConfig,
                                   HealthPlane, PeerLostError,
                                   PeerStalledError, StepHangError,
                                   WatchdogTimeout, backoff_schedule, chaos,
                                   health)
from paddle_tpu.resilience.supervisor import Supervisor, classify_exit

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "gang_worker.py")
STEPS_PER_EPOCH = 12  # gang_worker.BATCHES_PER_EPOCH
EPOCHS = 2


@pytest.fixture(autouse=True)
def _gang_teardown():
    yield
    chaos.clear()
    health.stop_health_plane()


def _beat(kv, rank, step, t):
    kv.key_value_set(health.HB_DIR + str(rank), json.dumps(
        {"rank": rank, "step": step, "wall_time": t, "pid": 1,
         "seq": t}), allow_overwrite=True)


# ---------------------------------------------------------------------------
# Health plane units (FakeKv + injected clock)
# ---------------------------------------------------------------------------

def test_heartbeat_publishes_and_beat_updates_step():
    kv = FakeKv()
    hb = health.Heartbeat(kv, rank=3, config=HealthConfig(
        interval_s=1.0, miss_budget=5), clock=lambda: 42.0)
    assert hb.publish_once()
    hb.beat(17)
    assert hb.publish_once()
    entries = dict(kv.key_value_dir_get(health.HB_DIR.rstrip("/")))
    payload = json.loads(entries[health.HB_DIR + "3"])
    assert payload["rank"] == 3 and payload["step"] == 17
    assert payload["wall_time"] == 42.0 and payload["seq"] == 2


def test_monitor_detects_lost_peer_within_budget(tmp_path):
    """A peer silent past interval*budget raises PeerLostError naming
    it, writes the poison key, and emits a peer_lost event."""
    log = observe.RunEventLog(str(tmp_path / "ev.jsonl"))
    kv = FakeKv()
    clk = [0.0]
    cfg = HealthConfig(interval_s=1.0, miss_budget=3,
                       startup_grace_s=100.0)
    m = health.HealthMonitor(kv, 0, 2, cfg, clock=lambda: clk[0],
                             event_log=log)
    _beat(kv, 1, 0, 0.0)
    assert m.poll_once() is None
    clk[0] = 2.9  # within window
    assert m.poll_once() is None
    clk[0] = 3.1  # over: 3.1 > 3.0 = 1.0 * 3
    alarm = m.poll_once()
    assert isinstance(alarm, PeerLostError)
    d = alarm.as_dict()
    assert d["missing_ranks"] == [1]
    assert d["budget_s"] == 3.0
    assert d["age_s"][1] >= 3.0
    poison = health.read_poison(kv)
    assert poison["kind"] == "peer_lost"
    assert poison["missing_ranks"] == [1]
    log.close()
    kinds = [e["event"] for e in observe.read_events(log.path)]
    assert "peer_lost" in kinds


def test_monitor_startup_grace_for_never_published_peer():
    kv = FakeKv()
    clk = [0.0]
    cfg = HealthConfig(interval_s=1.0, miss_budget=2,
                       startup_grace_s=5.0)
    m = health.HealthMonitor(kv, 0, 2, cfg, clock=lambda: clk[0])
    assert m.poll_once() is None  # peer 1 never published: grace
    clk[0] = 4.9
    assert m.poll_once() is None
    clk[0] = 5.1
    alarm = m.poll_once()
    assert isinstance(alarm, PeerLostError)
    assert alarm.details["missing_ranks"] == [1]


def test_monitor_detects_stalled_peer():
    """Heartbeats flowing but the step counter frozen past
    gang_stall_timeout_s -> PeerStalledError (the hung-collective
    signature when the watchdog is not armed)."""
    kv = FakeKv()
    clk = [0.0]
    cfg = HealthConfig(interval_s=1.0, miss_budget=100,
                       stall_timeout_s=3.0, startup_grace_s=100.0)
    m = health.HealthMonitor(kv, 0, 2, cfg, clock=lambda: clk[0])
    for t in (0.0, 1.0, 2.0):
        clk[0] = t
        _beat(kv, 1, 5, t)  # alive, step frozen at 5
        assert m.poll_once() is None
    clk[0] = 3.5
    _beat(kv, 1, 5, 3.5)
    alarm = m.poll_once()
    assert isinstance(alarm, PeerStalledError)
    d = alarm.as_dict()
    assert d["stalled_ranks"] == [1] and d["steps"] == {1: 5}


def test_monitor_kv_unreachable_is_coordinator_loss():
    """Sustained KV failure == the coordinator process died: a
    PeerLostError naming rank 0."""
    kv = FakeKv()
    clk = [0.0]
    m = health.HealthMonitor(
        kv, 1, 2, HealthConfig(interval_s=0.5, miss_budget=4),
        clock=lambda: clk[0])
    m.poll_once()
    kv.fail_with = RuntimeError("UNAVAILABLE: socket closed")
    for t in (0.5, 1.0, 2.6):  # window = 2.0s from first failure
        clk[0] = t
        m.poll_once()
    alarm = m.alarm()
    assert isinstance(alarm, PeerLostError)
    assert alarm.details["missing_ranks"] == [health.COORDINATOR_RANK]
    assert "kv_error" in alarm.details


def test_done_rank_is_departed_not_dead():
    """Orderly leave: a rank that published its done marker may go
    silent without being declared lost (the first-finisher-is-not-
    dead rule resumed gangs need — ranks resume at different cursors
    and finish at different times)."""
    kv = FakeKv()
    clk = [0.0]
    cfg = HealthConfig(interval_s=1.0, miss_budget=2,
                       startup_grace_s=100.0)
    m = health.HealthMonitor(kv, 0, 2, cfg, clock=lambda: clk[0])
    _beat(kv, 1, 9, 0.0)
    m.poll_once()
    kv.key_value_set(health.DONE_DIR + "1", json.dumps({"rank": 1}))
    clk[0] = 50.0  # way past the miss window
    assert m.poll_once() is None
    assert m.done_ranks == {1}


def test_poison_roundtrip_and_plane_consumption_idempotent():
    """write/read/clear poison; plane.check() raises each poison id
    exactly once and the plane's own poison is born consumed."""
    kv = FakeKv()
    assert health.read_poison(kv) is None
    cfg = HealthConfig(interval_s=1000.0, miss_budget=5,
                       startup_grace_s=10 ** 9)
    plane = HealthPlane(kv, 0, 2, config=cfg)
    # self-poison: marked consumed at write (the writer already knows)
    p = plane.poison("own abort", kind="step_hang")
    plane.monitor.poll_once()
    plane.check()  # no raise
    # a PEER's poison raises once, then is consumed
    p2 = health.write_poison(kv, rank=1, reason="peer abort")
    assert p2["id"] != p["id"]
    plane.monitor.poll_once()
    with pytest.raises(GangPoisonedError) as ei:
        plane.check()
    assert ei.value.details["poison"]["reason"] == "peer abort"
    plane.monitor.poll_once()
    plane.check()  # consumed: idempotent
    health.clear_poison(kv)
    assert health.read_poison(kv) is None


def test_skew_snapshot_and_rank_slow_event(tmp_path):
    """Straggler telemetry: rates derived from heartbeat step deltas;
    the slow rank is flagged and gang_skew/rank_slow events land."""
    log = observe.RunEventLog(str(tmp_path / "ev.jsonl"))
    kv = FakeKv()
    clk = [0.0]
    cfg = HealthConfig(interval_s=1.0, miss_budget=100,
                       startup_grace_s=100.0, skew_report_every=4,
                       slow_factor=2.0)
    m = health.HealthMonitor(kv, 0, 2, cfg, clock=lambda: clk[0],
                             event_log=log)
    for t, (s0, s1) in enumerate([(0, 0), (10, 2), (20, 4), (30, 6)]):
        clk[0] = float(t)
        _beat(kv, 0, s0, float(t))
        _beat(kv, 1, s1, float(t))
        m.poll_once()
    sk = m.skew()
    assert sk["rates"] == {0: 10.0, 1: 2.0}
    assert sk["max_lag_steps"] == 24
    assert sk["slow_ranks"] == [1]
    log.close()
    events = observe.read_events(log.path)
    kinds = [e["event"] for e in events]
    assert "gang_skew" in kinds and "rank_slow" in kinds
    slow = [e for e in events if e["event"] == "rank_slow"][-1]
    assert slow["rank"] == 1 and slow["median_rate"] == 10.0


# ---------------------------------------------------------------------------
# Watchdog: timer-thread Deadline + DispatchWatchdog
# ---------------------------------------------------------------------------

def test_deadline_timer_thread_fallback():
    """Off the main thread, Deadline must now FIRE (timer thread +
    async-exc) instead of silently degrading to a no-op."""
    result = {}

    def worker():
        try:
            with Deadline(0.4, what="thread region") as d:
                assert d.mode == "timer"
                chaos.hang(10)
            result["r"] = "no-fire"
        except WatchdogTimeout as e:
            result["r"] = e.details

    t = threading.Thread(target=worker)
    t.start()
    t.join(15)
    assert not t.is_alive()
    assert result["r"]["mode"] == "timer"
    assert result["r"]["what"] == "thread region"


def test_deadline_sigalrm_on_main_thread_unchanged():
    with pytest.raises(WatchdogTimeout) as ei:
        with Deadline(1, what="main hang") as d:
            assert d.mode == "sigalrm"
            chaos.hang(10)
    assert ei.value.details["mode"] == "sigalrm"


def test_dispatch_watchdog_compile_grace_vs_hung_step(tmp_path):
    """The satellite: single-process collective-hang detection via
    chaos.hang — the FIRST region (no dispatch ever completed) rides
    the compile-grace budget; once a real dispatch completed, a
    hanging step gets the tight budget and a `step_hang` event with
    kind=hung_step BEFORE the StepHangError."""
    log = observe.RunEventLog(str(tmp_path / "ev.jsonl"))
    hangs = []
    # budgets sized so a loaded CI box can't flake the real dispatch
    # below, while the hangs still overrun decisively
    wd = DispatchWatchdog(step_deadline_s=2.0, compile_grace_s=5.0,
                          event_log=log, on_hang=hangs.append)
    # region 0: would blow the step budget, but compile grace covers it
    with wd.guard("step 0"):
        chaos.hang(2.3)
    assert wd.regions[0]["kind"] == "first_compile"
    assert wd.regions[0]["budget_s"] == 5.0
    assert wd.regions[0]["hang"] is None

    # complete one REAL dispatch so the watchdog sees steady state
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = layers.data("x", shape=[2, 2], append_batch_size=False)
        y = layers.mean(x)
        exe = fluid.Executor()
        exe.run(startup)
        with wd.guard("step 1"):
            exe.run(main, feed={"x": np.zeros((2, 2), "f4")},
                    fetch_list=[y])
    assert wd.regions[1]["kind"] == "step"

    with pytest.raises(StepHangError) as ei:
        with wd.guard("step 2"):
            chaos.hang(10)
    d = ei.value.as_dict()
    assert d["kind"] == "hung_step"
    assert d["budget_s"] == 2.0
    assert hangs and hangs[0]["kind"] == "hung_step"
    log.close()
    ev = [e for e in observe.read_events(log.path)
          if e["event"] == "step_hang"]
    assert ev and ev[0]["hang_kind"] == "hung_step"
    assert "dispatches_delta" in ev[0]


def test_dispatch_watchdog_first_compile_timeout_kind():
    """A hang that outlives even the compile grace is reported as a
    first_compile hang (backend init / compile wedged)."""
    wd = DispatchWatchdog(step_deadline_s=0.5, compile_grace_s=1.0)
    with pytest.raises(StepHangError) as ei:
        with wd.guard("step 0"):
            chaos.hang(10)
    assert ei.value.details["kind"] == "first_compile"
    assert ei.value.details["budget_s"] == 1.0


# ---------------------------------------------------------------------------
# Barrier poison fast-path (unit; the real thing runs in the slow test)
# ---------------------------------------------------------------------------

def test_wait_barrier_peers_aborts_on_poison_fast():
    kv = FakeKv()
    t0 = time.monotonic()

    def poison_later():
        time.sleep(0.25)
        health.write_poison(kv, rank=1, reason="peer declared dead",
                            kind="peer_lost", missing_ranks=[1])

    threading.Thread(target=poison_later).start()
    with pytest.raises(CheckpointBarrierPoisonedError) as ei:
        _wait_barrier_peers(kv, "bar/t/0/", [1], "t", timeout_s=30.0,
                            poison_poll_s=0.05)
    elapsed = time.monotonic() - t0
    assert elapsed < 5.0, elapsed  # nowhere near the 30s timeout
    d = ei.value.as_dict()
    assert d["error"] == "checkpoint_barrier_poisoned"
    assert d["poison"]["reason"] == "peer declared dead"
    assert d["missing_ranks"] == [1]


def test_wait_barrier_peers_timeout_names_missing():
    kv = FakeKv()
    kv.key_value_set("bar/t/0/2", "ok")  # rank 2 arrived, 1 never
    missing = _wait_barrier_peers(kv, "bar/t/0/", [1, 2], "t",
                                  timeout_s=0.3, poison_poll_s=0.05)
    assert missing == [1]


# ---------------------------------------------------------------------------
# Supervisor (jax-free process management)
# ---------------------------------------------------------------------------

def test_classify_exit_registry():
    assert classify_exit(0) == "ok"
    assert classify_exit(PREEMPT_EXIT_CODE) == "preempt_drain"
    assert classify_exit(PEER_LOST_EXIT_CODE) == "peer_lost"
    assert classify_exit(-9) == "signal:SIGKILL"
    assert classify_exit(137) == "signal:SIGKILL"
    assert classify_exit(-15) == "signal:SIGTERM"
    assert classify_exit(3) == "crash:3"
    assert classify_exit(None) == "running"


def test_backoff_schedule_deterministic():
    assert backoff_schedule(4, 1.0, 30.0) == [1.0, 2.0, 4.0, 8.0]
    assert backoff_schedule(6, 1.0, 4.0) == [1.0, 2.0, 4.0, 4.0, 4.0,
                                             4.0]


def test_supervisor_restarts_crashed_gang_with_backoff(tmp_path):
    """Rank 1 crashes on attempt 0 and is clean after; the supervisor
    terminates the survivor, backs off the deterministic schedule,
    and the relaunch succeeds."""
    script = (
        "import os,sys,time\n"
        "d = sys.argv[1]\n"
        "rank = os.environ['PADDLE_TRAINER_ID']\n"
        "assert os.environ['PADDLE_TRAINERS'] == '2'\n"
        "assert ':' in os.environ['PADDLE_COORDINATOR']\n"
        "f = os.path.join(d, 'n_r' + rank)\n"
        "n = int(open(f).read()) if os.path.exists(f) else 0\n"
        "open(f, 'w').write(str(n + 1))\n"
        "if rank == '1' and n == 0:\n"
        "    sys.exit(9)\n"
        "time.sleep(0.2)\n")
    slept = []
    sup = Supervisor([sys.executable, "-c", script, str(tmp_path)], 2,
                     max_restarts=3, grace_s=1.0, backoff_base_s=1.5,
                     backoff_max_s=30.0, sleep=slept.append)
    r = sup.run()
    assert r.ok and r.restarts == 1
    assert r.attempts[0]["reason"] == "crash"
    assert r.attempts[0]["exit_codes"][1] == 9
    assert slept == [1.5]  # base * 2**0, asserted via injected sleep


def test_supervisor_budget_exhaustion_is_structured(tmp_path):
    """The satellite: restart-budget exhaustion returns a structured
    GangFailedError with per-attempt exit codes."""
    slept = []
    sup = Supervisor([sys.executable, "-c", "import sys; sys.exit(5)"],
                     2, max_restarts=2, grace_s=1.0, backoff_base_s=1.0,
                     sleep=slept.append)
    with pytest.raises(GangFailedError) as ei:
        sup.run()
    d = ei.value.as_dict()
    assert d["error"] == "gang_failed"
    assert len(d["attempts"]) == 3  # 1 + 2 restarts
    for a in d["attempts"]:
        assert a["reason"] == "crash"
        assert set(a["exit_codes"].values()) <= {5, -15, -9}
    assert slept == [1.0, 2.0]  # deterministic retry_call schedule


def test_supervisor_preempt_drain_relaunches_without_backoff(tmp_path):
    script = (
        "import os,sys\n"
        "f = os.path.join(sys.argv[1],"
        " 'p_r' + os.environ['PADDLE_TRAINER_ID'])\n"
        "n = int(open(f).read()) if os.path.exists(f) else 0\n"
        "open(f, 'w').write(str(n + 1))\n"
        f"sys.exit({PREEMPT_EXIT_CODE} if n == 0 else 0)\n")
    slept = []
    # a generous grace: both ranks exit on their own, and a rank that a
    # loaded host has not even started within 1 s must not be killed
    # before it counts its first attempt (seen under 6 xdist workers)
    sup = Supervisor([sys.executable, "-c", script, str(tmp_path)], 2,
                     max_restarts=2, grace_s=10.0, sleep=slept.append)
    r = sup.run()
    assert r.ok and r.restarts == 1
    assert r.attempts[0]["reason"] == "preempt_drain"
    assert sup.backoffs_slept == [0.0] and slept == []


# ---------------------------------------------------------------------------
# dist.py hygiene
# ---------------------------------------------------------------------------

def test_shutdown_distributed_idempotent():
    """Safe when never initialized, and safe to double-call — teardown
    paths must not crash on a not-running runtime."""
    from paddle_tpu.parallel import shutdown_distributed

    shutdown_distributed()
    shutdown_distributed()


# ---------------------------------------------------------------------------
# Trainer integration (in-process plane over FakeKv)
# ---------------------------------------------------------------------------

def _tiny_trainer():
    def train_func():
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        return layers.mean(layers.square_error_cost(pred, y))

    return Trainer(train_func,
                   lambda: fluid.optimizer.SGD(learning_rate=0.1))


def _tiny_reader(n=6):
    def read():
        r = np.random.RandomState(0)
        for _ in range(n):
            yield {"x": r.rand(8, 4).astype(np.float32),
                   "y": r.rand(8, 1).astype(np.float32)}

    return read


def _quiet_plane_config():
    # budgets so generous nothing can alarm during an in-process test
    return HealthConfig(interval_s=1000.0, miss_budget=5,
                        startup_grace_s=10 ** 9)


def test_health_plane_adds_zero_dispatches_or_retraces():
    """Acceptance: the jitted train step is untouched by the health
    plane — dispatch count identical to a plane-less control run,
    zero retraces, and the heartbeat step counter advanced purely
    host-side."""
    from paddle_tpu.observe import runtime_stats

    t0 = _tiny_trainer()
    snap = runtime_stats.snapshot()
    t0.train(num_epochs=1, reader=_tiny_reader())
    control = runtime_stats.delta(snap)

    plane = health.start_health_plane(rank=0, num_ranks=2, kv=FakeKv(),
                                      config=_quiet_plane_config())
    t1 = _tiny_trainer()
    snap = runtime_stats.snapshot()
    t1.train(num_epochs=1, reader=_tiny_reader())
    with_plane = runtime_stats.delta(snap)

    assert with_plane["dispatches"] == control["dispatches"], \
        (control, with_plane)
    assert with_plane["retraces"] == 0, with_plane
    assert plane.heartbeat._step == 6  # beat() advanced host-side


def test_trainer_poison_aborts_and_retrain_is_idempotent():
    """The satellite regression (drain-flag mirror): a poisoned gang
    aborts train() with GangPoisonedError; the consumption is
    idempotent, so an in-process re-train() against the SAME stale
    poison key runs to completion."""
    plane = health.start_health_plane(rank=0, num_ranks=2, kv=FakeKv(),
                                      config=_quiet_plane_config())
    health.write_poison(plane.kv, rank=1, reason="peer watchdog fired",
                        kind="step_hang")
    plane.monitor.poll_once()
    t = _tiny_trainer()
    with pytest.raises(GangPoisonedError) as ei:
        t.train(num_epochs=1, reader=_tiny_reader())
    assert ei.value.details["poison"]["rank"] == 1
    # the key is still in the store, but consumed: re-train completes
    plane.monitor.poll_once()
    t.train(num_epochs=1, reader=_tiny_reader())


def test_trainer_step_watchdog_budgets():
    """Trainer(step_deadline_s=...) now rides DispatchWatchdog: the
    first step (compile) gets the grace budget, steady-state steps the
    tight one."""
    def train_func():
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        return layers.mean(layers.square_error_cost(pred, y))

    t = Trainer(train_func,
                lambda: fluid.optimizer.SGD(learning_rate=0.1),
                step_deadline_s=30.0)
    t.train(num_epochs=1, reader=_tiny_reader(3))
    regions = t._step_watchdog.regions
    assert len(regions) == 3
    assert regions[0]["kind"] == "first_compile"
    assert regions[0]["budget_s"] == 300.0  # 10x grace default
    assert all(r["kind"] == "step" and r["budget_s"] == 30.0
               for r in regions[1:])
    assert all(r["hang"] is None for r in regions)


# ---------------------------------------------------------------------------
# Cross-process crash chaos (the acceptance proof; slow)
# ---------------------------------------------------------------------------

def _worker_cmd(d):
    return [sys.executable, WORKER,
            "--ckpt-root", os.path.join(d, "ck"),
            "--out-root", os.path.join(d, "out"),
            "--log-root", os.path.join(d, "log"),
            "--epochs", str(EPOCHS), "--pace-s", "0.12"]


def _gang_env():
    env = {"FLAGS_heartbeat_interval_s": "0.25",
           "FLAGS_heartbeat_miss_budget": "6"}
    os.environ.pop("JAX_PLATFORMS", None)  # workers pin cpu themselves
    return env


def _assert_no_orphans(tag):
    for proc in glob.glob("/proc/[0-9]*/cmdline"):
        try:
            with open(proc, "rb") as f:
                cmd = f.read().decode(errors="replace").replace("\0", " ")
        except OSError:
            continue
        assert tag not in cmd, f"orphan process survived: {cmd}"


def run_gang_sigkill_chaos(tmp_path):
    """SIGKILL a random rank at a random mid-train step; assert
    bounded structured detection, one supervisor restart, bit-exact
    final params vs control, restart-replay badput in the relaunched
    ranks' goodput ledgers (observe pillar 8), and no orphans."""
    import random

    rng = random.Random(os.urandom(8))
    victim = rng.randrange(2)  # the COORDINATOR rank is fair game too
    kill_at = rng.randrange(3, (EPOCHS * STEPS_PER_EPOCH * 3) // 4)
    # keep the kill off the save boundary (crash cursor == resume
    # cursor -> zero replay): the goodput assertions below want the
    # victim's relaunch to re-execute at least one step
    if kill_at % 3 == 0:
        kill_at += 1

    dc = os.path.join(tmp_path, "ctl")
    sup_c = Supervisor(_worker_cmd(dc), 2, max_restarts=0, grace_s=8.0,
                       env=_gang_env(), host_coordinator=True,
                       log_dir=os.path.join(dc, "sup"))
    assert sup_c.run().ok

    dv = os.path.join(tmp_path, "chaos")
    env = _gang_env()
    chaos.arm_kill_rank_env(env, rank=victim, at_step=kill_at,
                            once_file=os.path.join(tmp_path,
                                                   "killed.flag"))
    t0 = time.monotonic()
    sup = Supervisor(_worker_cmd(dv), 2, max_restarts=2, grace_s=8.0,
                     backoff_base_s=0.2, env=env, host_coordinator=True,
                     log_dir=os.path.join(dv, "sup"))
    result = sup.run()
    elapsed = time.monotonic() - t0
    survivor = 1 - victim

    assert result.ok and result.restarts == 1, result.as_dict()
    a0 = result.attempts[0]
    assert a0["reason"] == "peer_lost", a0
    assert a0["classified"][victim] == "signal:SIGKILL", a0
    # the survivor exited DELIBERATELY with the peer-lost code
    assert a0["exit_codes"][survivor] == PEER_LOST_EXIT_CODE, a0

    # structured detection naming the dead rank, within the budget:
    # window = 0.25 * 6 = 1.5s; generous slack for a loaded CI box
    out = open(os.path.join(dv, "sup",
                            f"attempt0_rank{survivor}.out")).read()
    lines = [ln for ln in out.splitlines()
             if ln.startswith("PEER_LOST ")]
    assert lines, f"survivor never printed structured detection:\n{out}"
    payload = json.loads(lines[0][len("PEER_LOST "):])
    assert payload["missing_ranks"] == [victim], payload
    window = 0.25 * 6
    age = payload.get("age_s")
    if isinstance(age, dict):
        age = age[str(victim)] if str(victim) in age else age[victim]
    assert age is not None and age <= window + 10.0, payload

    # bit-exact: BOTH ranks' final params match the uninterrupted run
    for rank in (0, 1):
        a = np.load(os.path.join(dc, "out", f"rank{rank}.npz"))
        b = np.load(os.path.join(dv, "out", f"rank{rank}.npz"))
        assert sorted(a.files) == sorted(b.files)
        for k in a.files:
            assert a[k].dtype == b[k].dtype
            assert np.array_equal(a[k], b[k]), \
                f"rank{rank} {k} NOT bit-identical after gang restart"

    # pillar-8 acceptance: every rank that completed dumped its
    # goodput ledger, and the relaunched ranks' reports carry the
    # restart-replay badput matching the crash cursors the attempt-0
    # STEP lines recorded
    def _goodput(d, rank):
        p = os.path.join(d, "out", f"rank{rank}.goodput.json")
        with open(p) as f:
            return json.load(f)

    def _last_step(out_path):
        steps = [ln.split() for ln in open(out_path).read().splitlines()
                 if ln.startswith("STEP ")]
        return int(steps[-1][1]), int(steps[-1][2])

    def _g(cursor):  # (epoch, step) cursor -> global step count
        return cursor[0] * STEPS_PER_EPOCH + cursor[1]

    replayed = {}
    for rank in (0, 1):
        ctl = _goodput(dc, rank)
        assert ctl["replay_steps"] == 0 and "replay" not in ctl, ctl
        rep = _goodput(dv, rank)
        cats = rep["categories_s"]
        assert abs(sum(cats.values()) - rep["wall_s"]) < 1e-3, rep
        # per-step health beats + the done-rendezvous are accounted
        assert cats["barrier_wait"] > 0.0, rep
        le, ls = _last_step(os.path.join(
            dv, "sup", f"attempt0_rank{rank}.out"))
        # the victim died INSIDE its last STEP's handler — that step's
        # progress write never landed; the survivor reached the next
        # step boundary before detection raised
        crash_cursor = (le, ls) if rank == victim else (le, ls + 1)
        if rank == victim:
            assert rep["replay_steps"] >= 1, rep  # kill_at % 3 != 0
        if rep["replay_steps"]:
            assert _g(rep["replay"]["to"]) == _g(crash_cursor), \
                (rank, rep["replay"], crash_cursor)
            # every step between resume and crash cursor ran twice
            assert rep["replay_steps"] == \
                _g(rep["replay"]["to"]) - _g(rep["replay"]["from"]), rep
            # replay badput ~ replayed-step count x mean step time;
            # the first resumed dispatch pays a residual cold cost
            # beyond the re-attributed trace/compile wall (buffer
            # setup, executable caching) — allowed as absolute slack
            est = rep["replay_steps"] * rep["mean_step_s"]
            assert 0.1 * est < cats["replay"] < 10 * est + 0.1, \
                (rank, rep)
        else:
            assert "replay" not in rep, rep
        replayed[rank] = rep["replay_steps"]

    _assert_no_orphans(tmp_path)
    assert elapsed < 180, f"chaos run took {elapsed:.0f}s"
    return {"victim": victim, "kill_at": kill_at,
            "detect_age_s": age, "replay_steps": replayed,
            "wall_s": round(elapsed, 1)}


ELASTIC_WORKER = os.path.join(HERE, "elastic_worker.py")


def _elastic_cmd(d):
    return [sys.executable, ELASTIC_WORKER,
            "--ckpt-root", os.path.join(d, "ck"),
            "--out-root", os.path.join(d, "out"),
            "--log-root", os.path.join(d, "log"),
            "--epochs", str(EPOCHS), "--pace-s", "0.12"]


def run_elastic_reshard_chaos(tmp_path):
    """Gang elasticity (ISSUE 13): SIGKILL rank 1 mid-train; the
    elastic supervisor relaunches at the SURVIVING world size (1), the
    worker sizes its mesh from PADDLE_TRAINERS (fsdp=4 -> fsdp=2) and
    io.load_sharded reshards the fsdp=4-saved checkpoint — ZeRO-sharded
    Momentum state included — onto the smaller mesh.  The resumed run
    must converge to the uninterrupted control's loss/params (float
    reduction tolerance: steps after the resume point run on a
    different mesh size)."""
    import random

    rng = random.Random(os.urandom(8))
    kill_at = rng.randrange(4, (EPOCHS * STEPS_PER_EPOCH * 3) // 4)

    dc = os.path.join(tmp_path, "ectl")
    sup_c = Supervisor(_elastic_cmd(dc), 2, max_restarts=0, grace_s=8.0,
                       env=_gang_env(), host_coordinator=True,
                       log_dir=os.path.join(dc, "sup"))
    assert sup_c.run().ok

    dv = os.path.join(tmp_path, "echaos")
    env = _gang_env()
    chaos.arm_kill_rank_env(env, rank=1, at_step=kill_at,
                            once_file=os.path.join(tmp_path,
                                                   "ekilled.flag"))
    sup = Supervisor(_elastic_cmd(dv), 2, max_restarts=2, grace_s=8.0,
                     backoff_base_s=0.2, env=env, host_coordinator=True,
                     elastic=True, log_dir=os.path.join(dv, "sup"))
    result = sup.run()

    assert result.ok and result.restarts == 1, result.as_dict()
    a0 = result.attempts[0]
    assert a0["classified"][1] == "signal:SIGKILL", a0
    assert a0["exit_codes"][0] == PEER_LOST_EXIT_CODE, a0
    # the elastic shrink is recorded and the relaunch ran ONE rank
    assert a0["shrunk_to"] == 1, a0
    assert sorted(result.attempts[1]["exit_codes"]) == [0], result.attempts

    # the relaunched rank 0 really resumed mid-run on the SMALLER mesh
    out1 = open(os.path.join(dv, "sup", "attempt1_rank0.out")).read()
    mesh_line = [ln for ln in out1.splitlines()
                 if ln.startswith("MESH ")][0]
    assert "fsdp=2 world=1" in mesh_line, mesh_line
    assert "resume_epoch=0 resume_step=0" not in mesh_line, \
        f"relaunch started FRESH instead of resuming: {mesh_line}"

    # convergence: final loss + params match the uninterrupted control
    # within float-reduction tolerance (mesh size changed mid-run)
    ctl = np.load(os.path.join(dc, "out", "rank0.npz"))
    got = np.load(os.path.join(dv, "out", "rank0.npz"))
    ctl_loss = float(ctl["__final_loss__"])
    got_loss = float(got["__final_loss__"])
    assert abs(got_loss - ctl_loss) <= 1e-4 * max(abs(ctl_loss), 1e-6), \
        (got_loss, ctl_loss)
    for k in ctl.files:
        if k == "__final_loss__":
            continue
        np.testing.assert_allclose(
            got[k], ctl[k], rtol=1e-4, atol=1e-6,
            err_msg=f"{k} diverged after the elastic reshard resume")
    _assert_no_orphans(tmp_path)
    return {"kill_at": kill_at, "ctl_loss": round(ctl_loss, 6),
            "resumed_loss": round(got_loss, 6),
            "shrunk_to": a0["shrunk_to"]}


def run_barrier_poison_chaos(tmp_path):
    """A rank already WAITING in a checkpoint barrier when a peer
    poisons the gang and dies must abort in seconds (vs the 120 s
    barrier timeout), with the poison reason attached."""
    d = os.path.join(tmp_path, "bp")
    cmd = _worker_cmd(d) + ["--mode", "barrier_poison"]
    sup = Supervisor(cmd, 2, max_restarts=0, grace_s=8.0,
                     env=_gang_env(), host_coordinator=True,
                     log_dir=os.path.join(d, "sup"))
    try:
        sup.run()
        raise AssertionError("rank 1's deliberate exit(7) not seen")
    except GangFailedError as e:
        codes = e.details["attempts"][0]["exit_codes"]
        assert codes[1] == 7, codes
        assert codes[0] == 0, codes  # rank 0 handled the abort cleanly
    out = open(os.path.join(d, "sup", "attempt0_rank0.out")).read()
    lines = [ln for ln in out.splitlines()
             if ln.startswith("BARRIER_POISONED ")]
    assert lines, out
    payload = json.loads(lines[0][len("BARRIER_POISONED "):])
    assert payload["error"] == "checkpoint_barrier_poisoned"
    assert payload["timeout_s"] == 120.0
    assert payload["elapsed_wall_s"] < 30.0, payload  # bounded, not 120
    assert payload["poison"]["reason"].startswith("chaos:"), payload
    _assert_no_orphans(tmp_path)
    return {"barrier_abort_s": payload["elapsed_wall_s"]}


@pytest.mark.slow
def test_gang_sigkill_random_rank_bit_exact_restart(tmp_path):
    info = run_gang_sigkill_chaos(str(tmp_path))
    print("gang sigkill chaos:", info)


@pytest.mark.slow
def test_barrier_with_poisoned_peer_fails_bounded(tmp_path):
    info = run_barrier_poison_chaos(str(tmp_path))
    print("barrier poison chaos:", info)


@pytest.mark.slow
def test_elastic_gang_shrinks_and_reshards(tmp_path):
    info = run_elastic_reshard_chaos(str(tmp_path))
    print("elastic reshard chaos:", info)


def test_supervisor_elastic_shrinks_to_survivors(tmp_path):
    """Unit (fast, jax-free): an elastic supervisor relaunches a gang
    whose rank died BY SIGNAL at the surviving world size, and the
    shrink is recorded on the attempt.  Deliberate exits do not
    shrink."""
    marker = os.path.join(str(tmp_path), "attempt2.flag")
    script = (
        "import os, signal, sys\n"
        "rank = int(os.environ['PADDLE_TRAINER_ID'])\n"
        "world = int(os.environ['PADDLE_TRAINERS'])\n"
        f"marker = {marker!r}\n"
        "if world == 2:\n"
        "    if rank == 1:\n"
        "        os.kill(os.getpid(), signal.SIGKILL)\n"
        "    sys.exit(43)\n"          # survivor: deliberate peer-lost
        "open(marker, 'w').write(str(world))\n"
        "sys.exit(0)\n")
    sup = Supervisor([sys.executable, "-c", script], 2, max_restarts=2,
                     grace_s=2.0, backoff_base_s=0.0, elastic=True,
                     poll_s=0.05)
    result = sup.run()
    assert result.ok and result.restarts == 1, result.as_dict()
    assert result.attempts[0]["shrunk_to"] == 1, result.attempts
    assert list(result.attempts[1]["exit_codes"]) == [0]
    assert open(marker).read() == "1"  # relaunched at world size 1
