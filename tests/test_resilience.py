"""Resilience subsystem (ISSUE 4): every recovery behavior is proven by
injecting its fault (resilience/chaos.py) —

- the in-step update guard skips EXACTLY the poisoned step (parameters
  thereafter match a run that never saw that batch) with zero extra
  dispatches/retraces vs an unguarded step (the one-jitted-step
  invariant),
- dynamic loss scaling halves on overflow and recovers after N good
  steps, surviving telemetry-window resets,
- torn checkpoints (death between shard write and manifest write, via
  the `ckpt:before_manifest` failpoint) are NEVER loadable — the
  CLAUDE.md manifest-last claim, finally tested — and resume picks the
  prior serial,
- a corrupt shard fails CRC with a structured CheckpointError and the
  Trainer falls back to the newest VALID serial (logged, not
  swallowed),
- the serving circuit breaker opens/half-opens/closes
  deterministically; all rejections are structured dicts,
- the watchdog fires on an injected hang; retry backoff is
  deterministic.
"""

import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe, resilience
from paddle_tpu.contrib import CheckpointConfig, Trainer
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import (AdmissionController, CircuitBreaker,
                                CircuitOpenError)


@pytest.fixture(autouse=True)
def _clear_failpoints():
    yield
    chaos.clear()


def _linreg_program():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.1, momentum=0.9).minimize(loss)
    return main, startup, scope, loss


def _batches(n, seed=7, bs=8):
    rng = np.random.RandomState(seed)
    return [{"x": rng.rand(bs, 4).astype(np.float32),
             "y": rng.rand(bs, 1).astype(np.float32)}
            for _ in range(n)]


def _persistables(main):
    return {v.name: np.asarray(fluid.global_scope().find_var(v.name))
            for v in main.list_vars() if v.persistable}


# ---------------------------------------------------------------------------
# In-step update guard
# ---------------------------------------------------------------------------

def test_guard_skips_exactly_the_poisoned_step():
    batches = _batches(4)
    poisoned = chaos.poison_feed(batches[2], names=["x"])

    # reference: a run that never saw the poisoned batch
    main, startup, scope, loss = _linreg_program()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        for b in (batches[0], batches[1], batches[3]):
            exe.run(main, feed=b, fetch_list=[loss])
        ref = _persistables(main)

    # guarded run: same stream WITH the poison in the middle
    main2, startup2, scope2, loss2 = _linreg_program()
    resilience.enable_update_guard(main2)
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor()
        exe2.run(startup2)
        for b in (batches[0], batches[1], poisoned, batches[3]):
            exe2.run(main2, feed=b, fetch_list=[loss2])
        got = _persistables(main2)
    tel = observe.fetch_telemetry(scope2)
    assert tel.steps == 4
    assert tel.skipped_update_steps == 1
    assert tel.nonfinite_grad_steps == 1
    for name, want in ref.items():
        assert np.isfinite(got[name]).all(), name
        np.testing.assert_allclose(got[name], want, rtol=1e-6,
                                   atol=1e-7, err_msg=name)


def test_unguarded_program_is_corrupted_by_the_same_poison():
    """The guard is the difference: without it, one NaN batch destroys
    every parameter (the failure mode the ISSUE names)."""
    batches = _batches(2)
    main, startup, scope, loss = _linreg_program()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=chaos.poison_feed(batches[0], names=["x"]),
                fetch_list=[loss])
        got = _persistables(main)
    assert any(not np.isfinite(v).all() for v in got.values())


def test_guard_adds_no_dispatches_retraces_or_callbacks():
    """Acceptance criterion: runtime_stats counters for a guarded step
    match an unguarded step — the guard lives INSIDE the one jitted
    computation."""
    batches = _batches(2)

    def run_and_count(guard):
        main, startup, scope, loss = _linreg_program()
        if guard:
            resilience.enable_update_guard(main)
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            snap = observe.runtime_stats.snapshot()
            for b in batches:
                exe.run(main, feed=b, fetch_list=[loss])
            delta = observe.runtime_stats.delta(snap)
            fn, state, feeds = exe._prepare(
                main, batches[0], [loss.name], scope, 1, True)
            text = fn.lower(state, feeds).as_text()
        return delta, text

    unguarded, _ = run_and_count(False)
    guarded, lowered = run_and_count(True)
    assert guarded["dispatches"] == unguarded["dispatches"]
    assert guarded["retraces"] == unguarded["retraces"] == 0
    assert "callback" not in lowered  # no host round-trips


def test_guard_composes_with_chained_iterations():
    """K chained steps with a guard still accumulate correctly (the
    guard state rides the fori_loop carry)."""
    batches = _batches(1)
    main, startup, scope, loss = _linreg_program()
    resilience.enable_update_guard(main)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=batches[0], fetch_list=[loss], iterations=4)
    tel = observe.fetch_telemetry(scope)
    assert tel.steps == 4
    assert tel.skipped_update_steps == 0


# ---------------------------------------------------------------------------
# Dynamic loss scaling
# ---------------------------------------------------------------------------

def _scaled_program(init_scale=8.0, incr_every=2):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt = fluid.amp.decorate(
            fluid.optimizer.SGDOptimizer(learning_rate=0.1),
            use_dynamic_loss_scaling=True,
            init_loss_scaling=init_scale,
            incr_every_n_steps=incr_every)
        opt.minimize(loss)
    return main, startup, scope, loss


def test_loss_scale_halves_on_overflow_and_recovers():
    batches = _batches(3)
    main, startup, scope, loss = _scaled_program(init_scale=8.0,
                                                 incr_every=2)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=chaos.poison_feed(batches[0], names=["x"]),
                fetch_list=[loss])
        tel = observe.fetch_telemetry(scope, reset=False)
        assert tel.loss_scale == 4.0          # halved on overflow
        assert tel.skipped_update_steps == 1
        exe.run(main, feed=batches[1], fetch_list=[loss])
        exe.run(main, feed=batches[2], fetch_list=[loss])
    tel = observe.fetch_telemetry(scope)
    assert tel.loss_scale == 8.0              # doubled after 2 good
    assert tel.skipped_update_steps == 1


def test_loss_scaled_updates_match_unscaled_amp_run():
    """Scaling is numerically transparent: the scale is a power of two
    (exact exponent shift) and grads are unscaled before the optimizer,
    so an amp run WITH dynamic scaling matches the same amp run WITHOUT
    it on clean data (the only delta is the scale machinery)."""
    batches = _batches(3, seed=11)

    def amp_run(use_scaling):
        main, startup = fluid.Program(), fluid.Program()
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), \
                fluid.scope_guard(scope), fluid.unique_name.guard():
            x = layers.data(name="x", shape=[4], dtype="float32")
            y = layers.data(name="y", shape=[1], dtype="float32")
            pred = layers.fc(x, size=1)
            loss = layers.mean(layers.square_error_cost(pred, y))
            opt = fluid.amp.decorate(
                fluid.optimizer.MomentumOptimizer(learning_rate=0.1,
                                                  momentum=0.9),
                use_dynamic_loss_scaling=use_scaling,
                init_loss_scaling=1024.0)
            opt.minimize(loss)
            exe = fluid.Executor()
            exe.run(startup)
            for b in batches:
                exe.run(main, feed=b, fetch_list=[loss])
            return _persistables(main)

    ref = amp_run(False)
    got = amp_run(True)
    for name, want in ref.items():
        np.testing.assert_allclose(got[name], want, rtol=1e-5,
                                   atol=1e-6, err_msg=name)


def test_loss_scale_survives_telemetry_window_reset():
    batches = _batches(1)
    main, startup, scope, loss = _scaled_program(init_scale=8.0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=chaos.poison_feed(batches[0], names=["x"]),
                fetch_list=[loss])
        assert observe.fetch_telemetry(scope).loss_scale == 4.0
        # the reset above zeroed window counters but kept the schedule
        tel = observe.fetch_telemetry(scope, reset=False)
        assert tel.loss_scale == 4.0
        assert tel.steps == 0


# ---------------------------------------------------------------------------
# Checkpoint integrity
# ---------------------------------------------------------------------------

def _build_ckpt(tmp_path, train_steps=2):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    ckpt = str(tmp_path / "ck")
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        for b in _batches(train_steps):
            exe.run(main, feed=b, fetch_list=[loss])
        fluid.io.save_sharded(exe, ckpt, main_program=main)
    return main, scope, exe, ckpt


def test_missing_manifest_is_structured_not_raw(tmp_path):
    main, scope, exe, _ = _build_ckpt(tmp_path)
    with pytest.raises(resilience.CheckpointNotFoundError) as ei:
        with fluid.scope_guard(scope):
            fluid.io.load_sharded(exe, str(tmp_path / "nowhere"),
                                  main_program=main)
    d = ei.value.as_dict()
    assert d["error"] == "checkpoint_not_found"
    assert "nowhere" in d["dirname"]


def test_corrupt_shard_fails_verification(tmp_path):
    main, scope, exe, ckpt = _build_ckpt(tmp_path)
    chaos.corrupt_shard(ckpt, mode="flip")
    with pytest.raises(resilience.CheckpointCorruptError) as ei:
        with fluid.scope_guard(scope):
            fluid.io.load_sharded(exe, ckpt, main_program=main)
    assert ei.value.as_dict()["error"] == "checkpoint_corrupt"


def test_truncated_shard_fails_verification(tmp_path):
    main, scope, exe, ckpt = _build_ckpt(tmp_path)
    chaos.corrupt_shard(ckpt, mode="truncate")
    with pytest.raises(resilience.CheckpointCorruptError):
        with fluid.scope_guard(scope):
            fluid.io.load_sharded(exe, ckpt, main_program=main)


def test_garbage_manifest_is_corrupt_not_json_error(tmp_path):
    main, scope, exe, ckpt = _build_ckpt(tmp_path)
    with open(os.path.join(ckpt, fluid.io.SHARD_MANIFEST), "w") as f:
        f.write("{ not json")
    with pytest.raises(resilience.CheckpointCorruptError):
        with fluid.scope_guard(scope):
            fluid.io.load_sharded(exe, ckpt, main_program=main)


def test_newer_format_version_is_structured(tmp_path):
    main, scope, exe, ckpt = _build_ckpt(tmp_path)
    mpath = os.path.join(ckpt, fluid.io.SHARD_MANIFEST)
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["version"] = 10 ** 6
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(resilience.CheckpointFormatError):
        with fluid.scope_guard(scope):
            fluid.io.load_sharded(exe, ckpt, main_program=main)


def test_combined_format_missing_manifest_structured(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        layers.fc(x, size=1)
        exe = fluid.Executor()
        exe.run(startup)
        with pytest.raises(resilience.CheckpointNotFoundError):
            fluid.io.load_persistables(exe, str(tmp_path / "empty"),
                                       main_program=main)


def test_combined_format_crc_roundtrip_and_corruption(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    d = str(tmp_path / "plain")
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        layers.fc(x, size=1)
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_persistables(exe, d, main_program=main)
        fluid.io.load_persistables(exe, d, main_program=main)  # clean
        chaos.corrupt_file(os.path.join(d, "params.npz"))
        with pytest.raises(resilience.CheckpointCorruptError):
            fluid.io.load_persistables(exe, d, main_program=main)


# ---------------------------------------------------------------------------
# Trainer fallback (torn + corrupt) — the CLAUDE.md manifest-last claim
# ---------------------------------------------------------------------------

def _train_func():
    x = layers.data(name="x", shape=[4], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    pred = layers.fc(x, size=1)
    return layers.mean(layers.square_error_cost(pred, y))


def _opt_func():
    return fluid.optimizer.SGDOptimizer(learning_rate=0.1)


def _reader(n=6):
    def read():
        r = np.random.RandomState(3)
        for _ in range(n):
            yield {"x": r.rand(8, 4).astype(np.float32),
                   "y": r.rand(8, 1).astype(np.float32)}
    return read


def test_torn_checkpoint_never_loadable_resume_picks_prior(tmp_path):
    """Simulated death BETWEEN shard write and manifest write (the
    chaos failpoint io.save_sharded calls at exactly that spot): the
    partial directory must never be considered loadable, and a
    restarted Trainer resumes from the prior serial."""
    ckpt_dir = str(tmp_path / "ck")
    log = str(tmp_path / "ev.jsonl")
    t = Trainer(_train_func, _opt_func,
                checkpoint_config=CheckpointConfig(ckpt_dir,
                                                   step_interval=2),
                telemetry=observe.TelemetryConfig(interval=100,
                                                  log_path=log))
    t.train(num_epochs=1, reader=_reader())
    ids = t._list_checkpoints()
    assert ids, "no checkpoints saved"
    last_good = ids[-1]

    chaos.arm("ckpt:before_manifest")
    with pytest.raises(chaos.ChaosKilled):
        t._save_checkpoint(last_good + 1, 0, 99)
    torn = os.path.join(ckpt_dir, f"ckpt_{last_good + 1}")
    assert os.path.isdir(torn)  # shards were written...
    assert not os.path.exists(  # ...but the manifest never was
        os.path.join(torn, fluid.io.SHARD_MANIFEST))

    # the torn dir is invisible to checkpoint listing AND unloadable
    t2 = Trainer(_train_func, _opt_func,
                 checkpoint_config=CheckpointConfig(ckpt_dir,
                                                    step_interval=2),
                 telemetry=observe.TelemetryConfig(interval=100,
                                                   log_path=log))
    assert t2._list_checkpoints()[-1] == last_good
    with pytest.raises(resilience.CheckpointError):
        t2._load_checkpoint(torn)
    # resume landed on the last COMPLETE serial's cursor
    with open(os.path.join(ckpt_dir, f"ckpt_{last_good}",
                           "__trainer_state__.json")) as f:
        st = json.load(f)
    assert (t2._resume_epoch, t2._resume_step_in_epoch) \
        == (st["epoch"], st["step"])


def test_trainer_falls_back_over_corrupt_newest(tmp_path):
    ckpt_dir = str(tmp_path / "ck")
    log = str(tmp_path / "ev.jsonl")
    t = Trainer(_train_func, _opt_func,
                checkpoint_config=CheckpointConfig(ckpt_dir,
                                                   step_interval=2),
                telemetry=observe.TelemetryConfig(interval=100,
                                                  log_path=log))
    t.train(num_epochs=1, reader=_reader())
    ids = t._list_checkpoints()
    assert len(ids) >= 2, ids
    chaos.corrupt_shard(os.path.join(ckpt_dir, f"ckpt_{ids[-1]}"))

    t2 = Trainer(_train_func, _opt_func,
                 checkpoint_config=CheckpointConfig(ckpt_dir,
                                                    step_interval=2),
                 telemetry=observe.TelemetryConfig(interval=100,
                                                   log_path=log))
    events = observe.read_events(log)
    falls = [e for e in events if e["event"] == "ckpt_fallback"]
    assert falls and falls[-1]["serial"] == ids[-1]
    assert falls[-1]["error"]["error"] == "checkpoint_corrupt"
    resumes = [e for e in events if e["event"] == "ckpt_resume"]
    assert resumes and resumes[-1]["serial"] == ids[-2]
    assert resumes[-1]["fallback"] is True
    # the cursor is the fallback serial's, not the corrupt one's
    with open(os.path.join(ckpt_dir, f"ckpt_{ids[-2]}",
                           "__trainer_state__.json")) as f:
        st = json.load(f)
    assert t2._resume_step_in_epoch == st["step"]


def test_keep_last_k_retention(tmp_path):
    ckpt_dir = str(tmp_path / "ck")
    t = Trainer(_train_func, _opt_func,
                checkpoint_config=CheckpointConfig(
                    ckpt_dir, max_num_checkpoints=2, step_interval=1))
    t.train(num_epochs=1, reader=_reader(5))
    ids = t._list_checkpoints()
    assert len(ids) <= 2
    # newest serials survive the rotation
    assert ids == sorted(ids) and ids[-1] >= 4


# ---------------------------------------------------------------------------
# Circuit breaker (deterministic: injected clock)
# ---------------------------------------------------------------------------

def test_circuit_breaker_open_half_open_close():
    now = [0.0]
    br = CircuitBreaker(failure_threshold=3, cooldown_s=10.0,
                        clock=lambda: now[0])
    assert br.state == br.CLOSED
    assert not br.record_failure()
    assert not br.record_failure()
    assert br.record_failure()          # threshold → OPEN
    assert br.state == br.OPEN
    assert not br.allow()               # cooldown not elapsed
    now[0] = 9.9
    assert not br.allow()
    now[0] = 10.0
    assert br.allow()                   # THE half-open probe
    assert br.state == br.HALF_OPEN
    assert not br.allow()               # concurrent submits still shed
    assert br.record_success()          # probe ok → CLOSED
    assert br.state == br.CLOSED
    assert br.opens == 1 and br.closes == 1


def test_circuit_breaker_failed_probe_reopens():
    now = [0.0]
    br = CircuitBreaker(failure_threshold=1, cooldown_s=5.0,
                        clock=lambda: now[0])
    assert br.record_failure()
    now[0] = 5.0
    assert br.allow()
    assert br.record_failure()          # probe failed → OPEN again
    assert br.state == br.OPEN
    assert not br.allow()               # fresh cooldown from reopen
    now[0] = 9.9
    assert not br.allow()
    now[0] = 10.0
    assert br.allow()


def test_admission_degraded_rejections_are_structured():
    now = [0.0]
    adm = AdmissionController(
        queue_capacity=4,
        breaker=CircuitBreaker(failure_threshold=2, cooldown_s=5.0,
                               clock=lambda: now[0]))
    adm.start()
    assert adm.record_dispatch_result(False) is None
    assert adm.record_dispatch_result(False) == "opened"
    assert adm.state == "degraded"
    with pytest.raises(CircuitOpenError) as ei:
        adm.check(inflight=0)
    d = ei.value.as_dict()
    assert d["error"] == "circuit_open"
    assert d["breaker"]["state"] == "open"
    assert d["retry_after_s"] == 5.0
    assert adm.health()["breaker"]["consecutive_failures"] == 2
    now[0] = 5.0
    adm.check(inflight=0)               # the half-open probe admits
    assert adm.record_dispatch_result(True) == "closed"
    assert adm.state == "running"
    # drain must work from DEGRADED too (rolling restart of a sick box)
    adm.record_dispatch_result(False)
    adm.record_dispatch_result(False)
    assert adm.state == "degraded"
    adm.begin_drain()
    assert adm.state == "draining"


def test_engine_breaker_degrades_and_recovers_end_to_end(tmp_path):
    """The breaker behind a LIVE ServingEngine, its executor failing by
    injection (chaos FlakyPredictor): two failed dispatches reach their
    callers as structured executor failures and open the circuit, the
    next request is rejected without a dispatch, and after the
    cool-down one half-open probe that succeeds puts the engine back to
    RUNNING.  (The tests above drive the breaker with an injected
    clock and no engine.)"""
    from paddle_tpu.resilience import FlakyPredictor
    from paddle_tpu.serving import (BucketConfig, ExecutorFailureError,
                                    ServingEngine)

    model_dir = str(tmp_path / "model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), \
            fluid.scope_guard(fluid.Scope()):
        x = layers.data("x", shape=[8], append_batch_size=True)
        pred = layers.fc(x, size=4)
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_program=main)
    flaky = FlakyPredictor(fluid.Predictor(model_dir), fail_first=2)
    engine = ServingEngine(
        flaky, {"x": np.zeros(8, np.float32)}, buckets=BucketConfig((1, 2)),
        max_wait_ms=0, queue_capacity=8,
        breaker=CircuitBreaker(failure_threshold=2, cooldown_s=0.2)).start()
    x0 = np.ones(8, np.float32)
    try:
        for _ in range(2):
            with pytest.raises(ExecutorFailureError) as ei:
                engine.infer({"x": x0}, timeout_s=60)
            assert ei.value.as_dict()["error"] == "executor_failure"
        assert engine.health()["state"] == "degraded", engine.health()
        calls = flaky.calls
        with pytest.raises(CircuitOpenError) as ei:
            engine.infer({"x": x0}, timeout_s=60)
        assert ei.value.as_dict()["error"] == "circuit_open"
        assert flaky.calls == calls     # rejected before any dispatch
        time.sleep(0.25)
        out = engine.infer({"x": x0}, timeout_s=60)  # the half-open probe
        assert np.isfinite(out[0]).all()
        assert engine.health()["state"] == "running", engine.health()
        assert engine.health()["breaker"]["state"] == "closed"
    finally:
        engine.close()
    assert flaky.failures_injected == 2


# ---------------------------------------------------------------------------
# Watchdog + retry
# ---------------------------------------------------------------------------

def test_deadline_fires_on_injected_hang():
    with pytest.raises(resilience.WatchdogTimeout) as ei:
        with resilience.Deadline(1, what="chaos hang"):
            chaos.hang(10.0)
    d = ei.value.as_dict()
    assert d["error"] == "watchdog_timeout"
    assert d["what"] == "chaos hang"


def test_deadline_disabled_and_clean_exit():
    with resilience.Deadline(0, what="disabled"):
        pass
    with resilience.Deadline(60, what="fast"):
        x = 1 + 1
    assert x == 2


def test_retry_backoff_is_deterministic():
    sleeps = []
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("transient")
        return "ok"

    out = resilience.retry_call(flaky, retries=3, base_delay_s=0.1,
                                retry_on=(ConnectionError,),
                                sleep=sleeps.append)
    assert out == "ok"
    assert sleeps == [0.1, 0.2]


def test_retry_exhaustion_is_structured():
    sleeps = []
    with pytest.raises(resilience.RetriesExhaustedError) as ei:
        resilience.retry_call(
            lambda: (_ for _ in ()).throw(ConnectionError("down")),
            retries=2, base_delay_s=0.1, retry_on=(ConnectionError,),
            sleep=sleeps.append)
    d = ei.value.as_dict()
    assert d["attempts"] == 3
    assert "ConnectionError" in d["last_error"]
    assert sleeps == [0.1, 0.2]


def test_retry_does_not_catch_unlisted_exceptions():
    with pytest.raises(ValueError):
        resilience.retry_call(
            lambda: (_ for _ in ()).throw(ValueError("bug")),
            retries=5, retry_on=(ConnectionError,),
            sleep=lambda _s: None)
