"""Preemption-tolerant training (ISSUE 7): async checkpointing,
bit-exact resume, and crash chaos.

Fast (in-process) coverage:
- async saves stall the step loop only for the snapshot: an injected
  slow write (chaos delaypoint) does not block `_save_checkpoint`, and
  `ckpt_save` events record snapshot_ms vs write_ms separately,
- a second save submitted while one is writing waits — never
  interleaves/corrupts,
- a writer-thread failure (failpoint mid-write) surfaces as a
  structured CheckpointWriteError on the NEXT save, and the torn
  directory stays unloadable (manifest-last invariant, async edition),
- bit-exact resume: dropout RNG + Adam moments + dynamic loss-scale
  value/counters + guard skip counter all survive save→"kill"→resume,
  and the resumed trajectory is BIT-IDENTICAL to an uninterrupted one,
- resuming against a drifted unique_name build fails loudly
  (CheckpointStateMismatchError), newer train_state versions are
  rejected, drain via request_drain() writes the emergency checkpoint
  and raises TrainingPreempted with the distinct exit code.

Slow (real-subprocess) chaos — the acceptance proof:
- SIGKILL at a random step + relaunch → final params bit-identical to
  an uninterrupted control, zero loadable torn checkpoints,
- SIGTERM → drain → exit code PREEMPT_EXIT_CODE + ckpt_emergency event
  → relaunch → bit-identical.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.contrib import CheckpointConfig, Trainer
from paddle_tpu.contrib.trainer import TRAIN_STATE_VERSION
from paddle_tpu.resilience import PREEMPT_EXIT_CODE, chaos, preempt
from paddle_tpu.resilience import errors as resilience_errors

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "preempt_worker.py")
STEPS_PER_EPOCH = 12  # preempt_worker.BATCHES_PER_EPOCH
EPOCHS = 2


@pytest.fixture(autouse=True)
def _clear_chaos_and_drain():
    yield
    chaos.clear()
    preempt.clear_drain()
    preempt.uninstall_preempt_handler()


# ---------------------------------------------------------------------------
# In-process: the training job (mirrors preempt_worker, smaller)
# ---------------------------------------------------------------------------

def _train_func():
    x = layers.data(name="x", shape=[6], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h = layers.fc(x, size=8, act="relu")
    h = layers.dropout(h, dropout_prob=0.3)
    pred = layers.fc(h, size=1)
    return layers.mean(layers.square_error_cost(pred, y))


def _opt_func():
    return fluid.amp.decorate(
        fluid.optimizer.Adam(learning_rate=0.01),
        use_dynamic_loss_scaling=True, init_loss_scaling=16.0,
        incr_every_n_steps=3)


def _reader(n=12, nan_at=4):
    from paddle_tpu.data import decorator

    def base():
        r = np.random.RandomState(5)
        for _ in range(n):
            yield {"x": r.rand(8, 6).astype(np.float32),
                   "y": r.rand(8, 1).astype(np.float32)}

    shuffled = decorator.shuffle(base, 4, seed=13)

    def read():
        for i, b in enumerate(shuffled()):
            yield (chaos.poison_feed(b, ["x"]) if i == nan_at else b)

    return read


def _persistables(t):
    return {v.name: np.asarray(t.scope.find_var(v.name))
            for v in t.train_program.list_vars() if v.persistable}


def _trainer(ckpt_dir, log=None, async_save=True, step_interval=3):
    tel = (observe.TelemetryConfig(interval=100, log_path=log)
           if log else None)
    return Trainer(_train_func, _opt_func,
                   checkpoint_config=CheckpointConfig(
                       ckpt_dir, step_interval=step_interval,
                       epoch_interval=10 ** 6, async_save=async_save),
                   telemetry=tel)


# ---------------------------------------------------------------------------
# Async checkpointing
# ---------------------------------------------------------------------------

def test_async_save_blocks_only_for_snapshot(tmp_path):
    """Acceptance: steps proceed while the background write is
    artificially slowed; the blocking (snapshot) portion is measured
    and reported separately from the write portion."""
    log = str(tmp_path / "ev.jsonl")
    t = _trainer(str(tmp_path / "ck"), log=log)
    chaos.arm_delay("ckpt:write", 0.5, times=10 ** 6)
    t0 = time.perf_counter()
    t.train(num_epochs=1, reader=_reader(6))  # 2 saves @ interval 3
    elapsed = time.perf_counter() - t0
    t.stop()
    saves = [e for e in observe.read_events(log)
             if e["event"] == "ckpt_save"]
    assert len(saves) == 2
    for e in saves:
        assert e["asynchronous"] is True
        assert e["write_ms"] >= 500, e  # the injected stall landed...
        assert e["snapshot_ms"] < 500, e  # ...in the write phase only
        assert e["bytes"] > 0
    # the step loop paid the snapshot (+ wait-for-previous), not the
    # two 0.5s writes back to back; generous bound for a loaded box
    assert t.ckpt_stats["saves"] == 2
    assert t.ckpt_stats["blocking_ms"] < 1000.0, t.ckpt_stats
    assert elapsed < 30, elapsed
    # and the final checkpoint is complete + loadable
    t2 = _trainer(str(tmp_path / "ck"), log=log)
    assert (t2._resume_epoch, t2._resume_step_in_epoch) == (0, 6)


def test_async_second_save_waits_never_corrupts(tmp_path):
    """Two saves in quick succession with a slowed writer: the second
    submit WAITS for the first write; both land complete and the
    newest is loadable with intact CRCs."""
    t = _trainer(str(tmp_path / "ck"))
    chaos.arm_delay("ckpt:write", 0.3, times=10 ** 6)
    t.train(num_epochs=1, reader=_reader(12))  # 4 saves, back to back
    t.stop()  # waits out the writer; surfaces any failure
    ids = t._list_checkpoints()
    assert len(ids) >= 2
    # every listed serial has manifest + trainer state and loads clean
    t2 = _trainer(str(tmp_path / "ck"))
    for serial in ids:
        path = os.path.join(str(tmp_path / "ck"), f"ckpt_{serial}")
        assert os.path.exists(os.path.join(path,
                                           fluid.io.SHARD_MANIFEST))
        st = t2._load_checkpoint(path)  # CRC-verified member reads
        assert st["serial"] == serial


def test_async_writer_failure_surfaces_on_next_save(tmp_path):
    """A writer-thread death mid-flush (failpoint between shard and
    manifest writes) must surface as a structured CheckpointWriteError
    on the NEXT save — and the torn dir must stay unloadable."""
    t = _trainer(str(tmp_path / "ck"))
    t.train(num_epochs=1, reader=_reader(3))  # serial 0 lands clean
    chaos.arm("ckpt:before_manifest")
    t._save_checkpoint(1, 0, 99)              # background write dies
    time.sleep(0.1)  # let the writer thread hit the failpoint
    with pytest.raises(resilience_errors.CheckpointWriteError) as ei:
        t._save_checkpoint(2, 0, 100)
    d = ei.value.as_dict()
    assert d["error"] == "checkpoint_write_failed"
    assert "ckpt:before_manifest" in str(d)
    torn = os.path.join(str(tmp_path / "ck"), "ckpt_1")
    assert os.path.isdir(torn)
    assert not os.path.exists(os.path.join(torn,
                                           fluid.io.SHARD_MANIFEST))
    # a restarted trainer never sees the torn serial
    t3 = _trainer(str(tmp_path / "ck"))
    assert 1 not in t3._list_checkpoints()


def test_trainer_train_end_surfaces_writer_failure(tmp_path):
    """The same failure at the END of training surfaces from train()
    itself (await-pending before returning green)."""
    t = _trainer(str(tmp_path / "ck"))
    chaos.arm("ckpt:before_manifest")
    with pytest.raises(resilience_errors.CheckpointWriteError):
        t.train(num_epochs=1, reader=_reader(3))


# ---------------------------------------------------------------------------
# Bit-exact resume (the PR-4 state that used to be silently dropped)
# ---------------------------------------------------------------------------

def _control_params(tmp_path):
    tc = _trainer(str(tmp_path / "ctl"), step_interval=100,
                  async_save=False)
    tc.train(num_epochs=1, reader=_reader(12))
    return _persistables(tc), tc


def test_bit_exact_resume_with_rng_adam_loss_scale(tmp_path):
    """Kill at step 6 (simulated: a 6-batch reader ends the run right
    after the step-6 save), resume with the full reader: final params
    must be BIT-identical to the uninterrupted control — proving RNG
    stream, Adam moments, and the loss-scale schedule all resumed."""
    ref, _tc = _control_params(tmp_path)

    tk = _trainer(str(tmp_path / "ck"))
    tk.train(num_epochs=1, reader=_reader(6))
    killed_tel = observe.fetch_telemetry(tk.scope, reset=False)
    killed_moments = {k: v for k, v in _persistables(tk).items()
                      if "moment" in k or "pow_acc" in k}
    tk.stop()

    tr = _trainer(str(tmp_path / "ck"))
    assert (tr._resume_epoch, tr._resume_step_in_epoch) == (0, 6)
    # PR-4 state restored at resume time, before any new step:
    resumed_tel = observe.fetch_telemetry(tr.scope, reset=False)
    # the schedule MOVED by kill time (16 → 32 after 3 calm steps →
    # 16 on the NaN), so equality here is not a vacuous init-vs-init
    assert resumed_tel.loss_scale == killed_tel.loss_scale
    assert resumed_tel.skipped_update_steps \
        == killed_tel.skipped_update_steps == 1
    for name, want in killed_moments.items():
        np.testing.assert_array_equal(
            np.asarray(tr.scope.find_var(name)), want, err_msg=name)

    tr.train(num_epochs=1, reader=_reader(12))
    got = _persistables(tr)
    assert set(got) == set(ref)
    for name, want in ref.items():
        assert got[name].dtype == want.dtype
        assert np.array_equal(got[name], want), \
            f"{name} diverged after resume"


def test_resume_restores_ls_counters_exactly(tmp_path):
    """The loss-scale good/bad counters (not just the scale value)
    survive: a resume mid-way through an incr_every_n_steps window must
    not restart the window (that would double the calm-step wait)."""
    tk = _trainer(str(tmp_path / "ck"))
    tk.train(num_epochs=1, reader=_reader(6))
    from paddle_tpu.observe.metrics import TELEMETRY_VAR

    raw = {k: int(np.asarray(v)) if np.asarray(v).dtype.kind == "i"
           else float(np.asarray(v))
           for k, v in tk.scope.find_var(TELEMETRY_VAR).items()}
    tk.stop()
    tr = _trainer(str(tmp_path / "ck"))
    raw2 = {k: int(np.asarray(v)) if np.asarray(v).dtype.kind == "i"
            else float(np.asarray(v))
            for k, v in tr.scope.find_var(TELEMETRY_VAR).items()}
    for k in ("loss_scale", "ls_good_steps", "ls_bad_steps",
              "skipped_update_steps"):
        assert raw2[k] == raw[k], (k, raw, raw2)
    # the schedule moved off init in the killed run, so this is not a
    # vacuous all-zeros comparison
    assert raw["ls_good_steps"] > 0 or raw["ls_bad_steps"] > 0


def test_resume_without_unique_name_guard_fails_loudly(tmp_path):
    """Regression (satellite): a resuming build whose unique_name
    counters drifted must raise CheckpointStateMismatchError — never
    silently bind saved arrays to wrong variables.  Drift is simulated
    by tampering the recorded counters (equivalently: the build ran
    outside unique_name.guard() after other programs polluted the
    global generator)."""
    t = _trainer(str(tmp_path / "ck"))
    t.train(num_epochs=1, reader=_reader(3))
    t.stop()
    sp = os.path.join(str(tmp_path / "ck"), "ckpt_0",
                      "__trainer_state__.json")
    with open(sp) as f:
        st = json.load(f)
    ids = st["train_state"]["unique_name_ids"]
    ids["fc"] = ids.get("fc", 0) + 7  # drifted counter
    with open(sp, "w") as f:
        json.dump(st, f)
    with pytest.raises(
            resilience_errors.CheckpointStateMismatchError) as ei:
        _trainer(str(tmp_path / "ck"))
    d = ei.value.as_dict()
    assert d["error"] == "checkpoint_state_mismatch"
    assert "fc" in d["drifted_keys"]

    # and at the io layer: a program REALLY built without the guard
    # (second build in-process -> drifted generated names) fails the
    # load with a structured missing-variable error, not a mis-bind
    def build(guarded):
        import contextlib

        main, startup = fluid.Program(), fluid.Program()
        scope = fluid.Scope()
        guard = (fluid.unique_name.guard() if guarded
                 else contextlib.nullcontext())
        with guard, fluid.program_guard(main, startup), \
                fluid.scope_guard(scope):
            x = layers.data(name="x", shape=[4], dtype="float32")
            pred = layers.fc(x, size=1)
            loss = layers.mean(pred)
            fluid.optimizer.Adam(learning_rate=0.1).minimize(loss)
            exe = fluid.Executor()
            exe.run(startup)
        return main, scope, exe

    main1, scope1, exe1 = build(guarded=True)
    d1 = str(tmp_path / "io_ck")
    with fluid.scope_guard(scope1):
        fluid.io.save_sharded(exe1, d1, main_program=main1)
    # the unguarded rebuild inherits a polluted GLOBAL generator (any
    # earlier in-process program build leaves counters behind — here
    # made explicit), so every generated name drifts
    for _ in range(3):
        fluid.unique_name.generate("fc")
    main2, scope2, exe2 = build(guarded=False)  # names drift here
    with pytest.raises(resilience_errors.CheckpointIncompleteError):
        with fluid.scope_guard(scope2):
            fluid.io.load_sharded(exe2, d1, main_program=main2)


def test_newer_train_state_version_rejected(tmp_path):
    t = _trainer(str(tmp_path / "ck"))
    t.train(num_epochs=1, reader=_reader(3))
    t.stop()
    sp = os.path.join(str(tmp_path / "ck"), "ckpt_0",
                      "__trainer_state__.json")
    with open(sp) as f:
        st = json.load(f)
    st["train_state"]["version"] = TRAIN_STATE_VERSION + 1
    with open(sp, "w") as f:
        json.dump(st, f)
    t2 = _trainer(str(tmp_path / "ck"))
    with pytest.raises(resilience_errors.CheckpointFormatError):
        t2._load_checkpoint(os.path.join(str(tmp_path / "ck"),
                                         "ckpt_0"))


# ---------------------------------------------------------------------------
# Drain (in-process)
# ---------------------------------------------------------------------------

def test_request_drain_writes_emergency_ckpt_and_raises(tmp_path):
    log = str(tmp_path / "ev.jsonl")
    t = _trainer(str(tmp_path / "ck"), log=log)

    def handler(e):
        from paddle_tpu.contrib.trainer import EndStepEvent

        if isinstance(e, EndStepEvent) and e.step == 3:
            preempt.request_drain("test-preemption")

    with pytest.raises(resilience_errors.TrainingPreempted) as ei:
        t.train(num_epochs=1, reader=_reader(12),
                event_handler=handler)
    assert ei.value.exit_code == PREEMPT_EXIT_CODE
    d = ei.value.as_dict()
    assert d["reason"] == "test-preemption"
    # the in-flight step FINISHED before the drain: cursor is step 4
    assert (d["epoch"], d["step"]) == (0, 4)
    events = observe.read_events(log)
    kinds = [e["event"] for e in events]
    assert "preempt_drain" in kinds
    assert "ckpt_emergency" in kinds
    em = [e for e in events if e["event"] == "ckpt_emergency"][-1]
    assert em["serial"] == d["serial"]
    # the drain request was CONSUMED by the drain (the flag is
    # process-global): an in-process resumed train() must run to
    # completion, not instantly re-drain on the stale flag
    assert not preempt.drain_requested()
    # auto-resume picks the emergency checkpoint up
    t2 = _trainer(str(tmp_path / "ck"), log=log)
    assert (t2._resume_epoch, t2._resume_step_in_epoch) == (0, 4)
    t2.train(num_epochs=1, reader=_reader(12))  # completes, no drain
    t2.stop()


def test_sigterm_handler_sets_drain_flag():
    installed = preempt.install_preempt_handler()
    assert installed  # pytest runs tests on the main thread
    assert not preempt.drain_requested()
    os.kill(os.getpid(), signal.SIGTERM)
    # CPython delivers the signal at the next bytecode boundary
    deadline = time.monotonic() + 5
    while not preempt.drain_requested():
        assert time.monotonic() < deadline
        time.sleep(0.01)
    assert preempt.drain_reason() == "signal:SIGTERM"


# ---------------------------------------------------------------------------
# Cross-process crash chaos (the acceptance proof; slow)
# ---------------------------------------------------------------------------

def _worker_cmd(ckpt, out, log, slow_write_ms=120.0):
    return [sys.executable, WORKER, "--ckpt", ckpt, "--out", out,
            "--log", log, "--epochs", str(EPOCHS),
            "--slow-write-ms", str(slow_write_ms)]


def _worker_env():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # worker pins cpu via jax.config
    return env


def _run_to_done(ckpt, out, log, timeout=300, **kw):
    err_path = out + ".stderr"
    with open(err_path, "w") as ef:
        r = subprocess.run(_worker_cmd(ckpt, out, log, **kw),
                          stdout=subprocess.PIPE, stderr=ef,
                          text=True, env=_worker_env(),
                          timeout=timeout)
    assert r.returncode == 0 and "DONE" in r.stdout, \
        f"worker rc={r.returncode}\n{r.stdout}\n" \
        + open(err_path).read()[-3000:]
    return r.stdout


def _run_until_step(ckpt, out, log, target_global_step, sig,
                    timeout=300, **kw):
    """Launch the worker, watch STEP lines, send `sig` the moment the
    target step completes.  Returns (returncode, stdout_so_far+rest)."""
    err_path = out + f".stderr.{int(sig)}"
    ef = open(err_path, "w")
    p = subprocess.Popen(_worker_cmd(ckpt, out, log, **kw),
                         stdout=subprocess.PIPE, stderr=ef,
                         text=True, env=_worker_env())
    lines = []
    try:
        deadline = time.monotonic() + timeout
        for line in p.stdout:
            lines.append(line)
            if line.startswith("STEP "):
                _, e, s = line.split()
                if int(e) * STEPS_PER_EPOCH + int(s) \
                        >= target_global_step:
                    p.send_signal(sig)
                    break
            if time.monotonic() > deadline:
                p.kill()
                raise AssertionError(
                    "worker never reached step "
                    f"{target_global_step}: {''.join(lines)}")
        rest = p.stdout.read()
        rc = p.wait(timeout=60)
    finally:
        ef.close()
    return rc, "".join(lines) + (rest or "")


def _assert_zero_loadable_torn(ckpt_dir):
    """Every torn directory (killed mid-save) must be invisible to the
    resume walk: a dir missing the trainer-state file is by definition
    not listed, and a dir missing the shard manifest must not carry a
    trainer-state file at all (state is written strictly last)."""
    if not os.path.isdir(ckpt_dir):
        return 0  # killed before the first save — fresh-start resume
    torn = 0
    for name in sorted(os.listdir(ckpt_dir)):
        path = os.path.join(ckpt_dir, name)
        if not (name.startswith("ckpt_") and os.path.isdir(path)):
            continue
        has_manifest = os.path.exists(
            os.path.join(path, fluid.io.SHARD_MANIFEST))
        has_state = os.path.exists(
            os.path.join(path, "__trainer_state__.json"))
        if has_state:
            assert has_manifest, \
                f"{name}: trainer state without manifest — the " \
                f"write-order invariant broke (state must be LAST)"
        else:
            torn += 1
    return torn


def _compare_final_params(out_a, out_b):
    a, b = np.load(out_a), np.load(out_b)
    assert sorted(a.files) == sorted(b.files)
    for k in a.files:
        assert a[k].dtype == b[k].dtype
        assert np.array_equal(a[k], b[k]), \
            f"{k} NOT bit-identical after crash-resume"


def _random_kill_step():
    # an ARBITRARY step (acceptance wording) — anywhere in the first
    # 3/4 of the run so the relaunch has work left; logged on failure
    import random

    return random.Random(os.urandom(8)).randrange(
        2, (EPOCHS * STEPS_PER_EPOCH * 3) // 4)


def run_sigkill_chaos(tmp_path):
    ctl_out = os.path.join(tmp_path, "ctl.npz")
    _run_to_done(os.path.join(tmp_path, "ctl_ck"), ctl_out,
                 os.path.join(tmp_path, "ctl.jsonl"))

    ck = os.path.join(tmp_path, "victim_ck")
    vic_out = os.path.join(tmp_path, "victim.npz")
    log = os.path.join(tmp_path, "victim.jsonl")
    kill_at = _random_kill_step()
    rc, out = _run_until_step(ck, vic_out, log, kill_at,
                              signal.SIGKILL)
    assert rc == -signal.SIGKILL, (kill_at, rc, out)
    assert not os.path.exists(vic_out)  # it really died mid-run
    torn = _assert_zero_loadable_torn(ck)
    # relaunch: auto-resume must complete and match the control
    out2 = _run_to_done(ck, vic_out, log)
    assert "DONE" in out2
    _compare_final_params(ctl_out, vic_out)
    return {"kill_at_global_step": kill_at, "torn_dirs": torn}


def run_sigterm_drain_chaos(tmp_path):
    ctl_out = os.path.join(tmp_path, "ctl2.npz")
    _run_to_done(os.path.join(tmp_path, "ctl2_ck"), ctl_out,
                 os.path.join(tmp_path, "ctl2.jsonl"))

    ck = os.path.join(tmp_path, "drain_ck")
    vic_out = os.path.join(tmp_path, "drain.npz")
    log = os.path.join(tmp_path, "drain.jsonl")
    term_at = _random_kill_step()
    rc, out = _run_until_step(ck, vic_out, log, term_at,
                              signal.SIGTERM)
    # the DISTINCT drained-exit code — not 143 (raw SIGTERM death)
    assert rc == PREEMPT_EXIT_CODE, (term_at, rc, out)
    assert "PREEMPTED" in out
    events = observe.read_events(log)
    kinds = [e["event"] for e in events]
    assert "preempt_drain" in kinds, kinds
    assert "ckpt_emergency" in kinds, kinds
    drain = [e for e in events if e["event"] == "preempt_drain"][-1]
    assert drain["reason"] == "signal:SIGTERM"
    out2 = _run_to_done(ck, vic_out, log)
    assert "DONE" in out2
    _compare_final_params(ctl_out, vic_out)
    return {"term_at_global_step": term_at}


@pytest.mark.slow
def test_sigkill_chaos_bit_exact_resume(tmp_path):
    info = run_sigkill_chaos(str(tmp_path))
    print("sigkill chaos:", info)


@pytest.mark.slow
def test_sigterm_drain_distinct_exit_and_bit_exact(tmp_path):
    info = run_sigterm_drain_chaos(str(tmp_path))
    print("sigterm drain chaos:", info)
