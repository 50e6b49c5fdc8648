"""Multi-trainer tests: 2 real localhost processes vs single-process
reference (reference: python/paddle/fluid/tests/unittests/
test_dist_base.py:21-80 — subprocess trainers, RUN_STEP steps, loss
parity within delta)."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "dist_worker.py")

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _single_process_reference(accum=1):
    sys.path.insert(0, os.path.dirname(HERE))
    from tests.dist_worker import LOCAL_B, RUN_STEP, build

    main, startup, loss = build()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(7)
        losses = []
        for _ in range(RUN_STEP):
            gx = rng.rand(2 * LOCAL_B, 4).astype("float32")
            gy = rng.rand(2 * LOCAL_B, 1).astype("float32")
            (lv,) = exe.run(main, feed={"x": gx, "y": gy},
                            fetch_list=[loss],
                            accumulation_steps=accum)
            losses.append(float(lv))
    return losses


def _run_trainers(accum=1, timeout=240, ckpt_dir=None, mode=None,
                  extra_env=None):
    port = _free_port()
    coordinator = f"127.0.0.1:{port}"
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # worker sets cpu itself
    if extra_env:
        env.update(extra_env)
    extra = [str(ckpt_dir)] if ckpt_dir else []
    if mode:
        extra.append(mode)
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(tid), coordinator, str(accum)]
            + extra,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            text=True)
        for tid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    return outs


def _extract_losses(outs):
    losses = []
    for rc, out, err in outs:
        if rc != 0:
            pytest.fail(f"trainer failed rc={rc}\nstdout:{out}\nstderr:{err}")
        for line in out.splitlines():
            if line.startswith("DIST_LOSSES "):
                losses.append(json.loads(line[len("DIST_LOSSES "):]))
    assert len(losses) == 2, f"missing loss lines: {outs}"
    return losses


@pytest.mark.slow
def test_two_trainer_loss_parity():
    """2-process dp training must match the single-process trajectory on
    the same global batch (allreduce-equivalence, the nccl2-mode
    contract)."""
    outs = _run_trainers(accum=1)
    l0, l1 = _extract_losses(outs)
    ref = _single_process_reference(accum=1)
    np.testing.assert_allclose(l0, l1, rtol=1e-6)  # replicas agree
    np.testing.assert_allclose(l0, ref, rtol=1e-4, atol=1e-6)


@pytest.mark.slow
def test_two_trainer_sharded_ckpt_roundtrip(tmp_path):
    """True MULTI-PROCESS sharded checkpointing: each of the 2 trainer
    processes writes only its own shard file mid-run, the manifest is
    written once, load re-materializes into the NamedShardings, and the
    post-restore trajectory still matches the uninterrupted
    single-process reference."""
    ck = tmp_path / "dist_ckpt"
    outs = _run_trainers(accum=1, ckpt_dir=ck)
    l0, l1 = _extract_losses(outs)
    ref = _single_process_reference(accum=1)
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    np.testing.assert_allclose(l0, ref, rtol=1e-4, atol=1e-6)
    # both processes wrote their own NON-EMPTY shard file (fsdp
    # placement puts real slices on each process); one manifest
    files = sorted(p.name for p in ck.iterdir())
    assert "__shards__.json" in files
    for shard in ("shards_p0.npz", "shards_p1.npz"):
        assert shard in files
        assert len(np.load(ck / shard).files) > 0, f"{shard} is empty"


@pytest.mark.slow
def test_dead_peer_in_sharded_save_is_barrier_timeout_not_hang(tmp_path):
    """Crash chaos for the multi-process save barrier (ISSUE 7): worker
    1 dies abruptly INSIDE the sharded-save window; worker 0 must get a
    structured CheckpointBarrierTimeoutError naming the missing rank
    within the configured timeout — never hang — and must clean up its
    partial shard files so the directory holds neither a manifest
    (manifest-last invariant) nor orphaned shards."""
    import time

    ck = tmp_path / "chaos_ckpt"
    t0 = time.monotonic()
    outs = _run_trainers(
        accum=1, ckpt_dir=ck, mode="die_before_save", timeout=180,
        extra_env={"PADDLE_TPU_CKPT_BARRIER_TIMEOUT_S": "8"})
    elapsed = time.monotonic() - t0
    rc0, out0, err0 = outs[0]
    rc1, _out1, _err1 = outs[1]
    assert rc1 == 17, f"worker 1 should have died abruptly: {_err1}"
    assert rc0 == 0, f"worker 0 crashed:\n{out0}\n{err0}"
    lines = [ln for ln in out0.splitlines()
             if ln.startswith("BARRIER_TIMEOUT ")]
    assert lines, ("worker 0 never reported the barrier timeout "
                   f"(hang or wrong error):\n{out0}\n{err0}")
    payload = json.loads(lines[0][len("BARRIER_TIMEOUT "):])
    assert payload["error"] == "checkpoint_barrier_timeout"
    assert payload["missing_ranks"] == [1]
    assert payload["tag"] == "save_sharded:shards"
    assert payload["timeout_s"] == 8.0
    # bounded: the whole 2-worker run (incl. jax startup) finished in
    # startup + ~8s of barrier wait, nowhere near a hang
    assert elapsed < 150, f"took {elapsed:.0f}s — barrier hung?"
    # no manifest (the save never completed) and worker 0's partial
    # shard files were cleaned up on the timeout path
    if ck.exists():
        files = sorted(p.name for p in ck.iterdir())
        assert "__shards__.json" not in files, files
        assert "shards_p0.npz" not in files, files
        assert "shards_p0.crc.json" not in files, files


@pytest.mark.slow
def test_two_trainer_with_gradient_accumulation():
    """dp × gradient accumulation (batch-merge) still matches the
    single-process accumulated run."""
    outs = _run_trainers(accum=2)
    l0, _l1 = _extract_losses(outs)
    ref = _single_process_reference(accum=2)
    np.testing.assert_allclose(l0, ref, rtol=1e-4, atol=1e-6)


def test_accumulation_matches_full_batch():
    """K-step accumulation over one big batch == single full-batch step
    (mean loss ⇒ averaged grads are identical)."""
    from tests.dist_worker import LOCAL_B, build

    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(2 * LOCAL_B, 4).astype("float32"),
            "y": rng.rand(2 * LOCAL_B, 1).astype("float32")}
    traj = []
    for accum in (1, 4):
        main, startup, loss = build()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            traj.append([float(exe.run(main, feed=feed, fetch_list=[loss],
                                       accumulation_steps=accum)[0])
                         for _ in range(4)])
    np.testing.assert_allclose(traj[0], traj[1], rtol=1e-5)


def test_accumulation_fetch_contract():
    """Fetched per-example forward vars keep full-batch shape; the loss
    keeps its declared (1,) shape; explicit accumulation_steps passed to
    run() is honored through a CompiledProgram wrapper too."""
    from paddle_tpu.parallel import make_mesh

    B = 8
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[B, 4], append_batch_size=False)
        y = layers.data("y", shape=[B, 1], append_batch_size=False)
        p = layers.fc(x, size=1, param_attr=fluid.ParamAttr(
            name="w", initializer=fluid.initializer.Constant(0.2)))
        loss = layers.reduce_mean(layers.square_error_cost(p, y))
        fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)

    rng = np.random.RandomState(2)
    feed = {"x": rng.rand(B, 4).astype(np.float32),
            "y": rng.rand(B, 1).astype(np.float32)}

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        p1, l1 = exe.run(main, feed=feed, fetch_list=[p, loss])
        p2, l2 = exe.run(main, feed=feed, fetch_list=[p, loss],
                         accumulation_steps=2)
    assert p2.shape == p1.shape == (B, 1)
    np.testing.assert_allclose(p2, p1, rtol=1e-5)  # lr=0: same params
    assert l2.shape == l1.shape  # (1,) contract survives accumulation
    assert float(l1.reshape(())) == pytest.approx(float(l2.reshape(())),
                                                  rel=1e-5)

    # per-run override reaches a CompiledProgram dispatch
    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, startup2):
        x = layers.data("x", shape=[B, 4], append_batch_size=False)
        y = layers.data("y", shape=[B, 1], append_batch_size=False)
        p = layers.fc(x, size=1, param_attr=fluid.ParamAttr(
            name="w", initializer=fluid.initializer.Constant(0.2)))
        loss = layers.reduce_mean(layers.square_error_cost(p, y))
        fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe = fluid.Executor()
        exe.run(startup2)
        compiled = fluid.CompiledProgram(main2).with_data_parallel(
            loss_name=loss.name, mesh=make_mesh({"dp": 2}))
        with pytest.raises(ValueError):
            # B=8 not divisible by 3 → the validation must fire, proving
            # the explicit accumulation_steps was not silently dropped
            exe.run(compiled, feed=feed, fetch_list=[loss],
                    accumulation_steps=3)


def test_accumulation_rejects_indivisible_batch():
    from tests.dist_worker import build

    main, startup, loss = build()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        with pytest.raises(ValueError):
            exe.run(main,
                    feed={"x": np.zeros((8, 4), np.float32),
                          "y": np.zeros((8, 1), np.float32)},
                    fetch_list=[loss], accumulation_steps=3)


def test_multihost_mesh_axes():
    """DCN axes are outermost; training over a hybrid dcn×ici mesh runs."""
    from paddle_tpu.parallel import make_multihost_mesh
    from tests.dist_worker import LOCAL_B, build

    mesh = make_multihost_mesh({"mp": 4}, {"dp": 2})
    assert mesh.axis_names == ("dp", "mp")
    assert dict(mesh.shape) == {"dp": 2, "mp": 4}

    rng = np.random.RandomState(5)
    feed = {"x": rng.rand(2 * LOCAL_B, 4).astype("float32"),
            "y": rng.rand(2 * LOCAL_B, 1).astype("float32")}
    main, startup, loss = build()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        compiled = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, mesh=mesh)
        losses = [float(exe.run(compiled, feed=feed, fetch_list=[loss])[0])
                  for _ in range(3)]
    assert losses[-1] < losses[0]


def test_init_distributed_single_trainer_noop():
    from paddle_tpu.parallel import init_distributed

    tid, n = init_distributed(trainer_id=0, num_trainers=1)
    assert (tid, n) == (0, 1)


def test_compiled_program_accumulation_on_mesh():
    """CompiledProgram + BuildStrategy.gradient_accumulation_steps on a
    multi-device mesh matches the plain-executor accumulated run."""
    from paddle_tpu.parallel import make_mesh
    from tests.dist_worker import LOCAL_B, build

    rng = np.random.RandomState(3)
    feed = {"x": rng.rand(2 * LOCAL_B, 4).astype("float32"),
            "y": rng.rand(2 * LOCAL_B, 1).astype("float32")}

    main, startup, loss = build()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ref = [float(exe.run(main, feed=feed, fetch_list=[loss],
                             accumulation_steps=2)[0]) for _ in range(3)]

    main2, startup2, loss2 = build()
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe = fluid.Executor()
        exe.run(startup2)
        bs = fluid.BuildStrategy()
        bs.gradient_accumulation_steps = 2
        compiled = fluid.CompiledProgram(main2).with_data_parallel(
            loss_name=loss2.name, build_strategy=bs,
            mesh=make_mesh({"dp": 2}))
        got = [float(exe.run(compiled, feed=feed, fetch_list=[loss2])[0])
               for _ in range(3)]
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
