"""Multi-trainer worker used by test_dist.py (spawned as a subprocess).

reference pattern: python/paddle/fluid/tests/unittests/test_dist_base.py:21
— real localhost processes, RUN_STEP steps, losses pickled back to the
parent for comparison against the single-process reference.
"""

import json
import os
import sys

# Script-mode only (the test module also imports this file for build();
# clobbering XLA_FLAGS there would shrink conftest's 8-device mesh):
# one CPU device per trainer process (both read at backend init).
if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=1")

import jax  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.parallel import (global_batch, init_distributed,  # noqa: E402
                                 make_mesh)

RUN_STEP = 5
LOCAL_B = 4


def build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[2 * LOCAL_B, 4], append_batch_size=False)
        y = layers.data("y", shape=[2 * LOCAL_B, 1], append_batch_size=False)
        h = layers.fc(x, size=8, act="tanh",
                      param_attr=fluid.ParamAttr(
                          name="w1",
                          initializer=fluid.initializer.Constant(0.3)),
                      bias_attr=fluid.ParamAttr(
                          name="b1",
                          initializer=fluid.initializer.Constant(0.0)))
        p = layers.fc(h, size=1,
                      param_attr=fluid.ParamAttr(
                          name="w2",
                          initializer=fluid.initializer.Constant(0.1)),
                      bias_attr=fluid.ParamAttr(
                          name="b2",
                          initializer=fluid.initializer.Constant(0.0)))
        loss = layers.reduce_mean(layers.square_error_cost(p, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def main():
    trainer_id = int(sys.argv[1])
    coordinator = sys.argv[2]
    accum = int(sys.argv[3]) if len(sys.argv) > 3 else 1
    # optional: sharded-ckpt round-trip mid-run (save + load back into
    # the NamedShardings after step 2) — the parent checks loss parity
    # with the uninterrupted single-process reference, proving the
    # MULTI-PROCESS per-shard save/load path is lossless
    ckpt_dir = sys.argv[4] if len(sys.argv) > 4 else None
    # optional chaos mode (test_dist barrier-timeout test):
    # "die_before_save" — worker 1 dies abruptly right before the
    # sharded save, worker 0 must get a structured
    # CheckpointBarrierTimeoutError naming rank 1, not hang
    mode = sys.argv[5] if len(sys.argv) > 5 else None

    # die_before_save pins the PLAIN barrier-timeout semantics (ISSUE
    # 7): opt out of the ISSUE-9 health plane there, whose peer-loss
    # poison would (correctly) abort the barrier EARLIER as a
    # CheckpointBarrierPoisonedError — that faster path has its own
    # proof in tests/test_gang.py.
    init_distributed(trainer_id=trainer_id, num_trainers=2,
                     coordinator=coordinator,
                     health=(mode != "die_before_save"))
    assert jax.process_count() == 2, jax.process_count()

    if mode == "die_before_save":
        # Barrier chaos (ISSUE 7): exercises only the distributed KV
        # runtime the checkpoint barrier rides — deliberately NO
        # cross-process XLA computation, so the test stays valid on
        # CPU backends without multiprocess collectives.  Worker 1
        # dies abruptly inside the save window; worker 0 must get a
        # structured CheckpointBarrierTimeoutError naming rank 1 and
        # clean up its partial shard files.
        main_prog, startup, loss = build()
        exe = fluid.Executor()
        exe.run(startup)
        if trainer_id == 1:
            # simulated preemption: no shard file, no barrier arrival
            # — worker 0 is on its own.  os._exit runs no cleanup,
            # like a real SIGKILL.
            sys.stdout.flush()
            os._exit(17)
        # make the save GENUINELY gang-wide: replace one persistable
        # with a dp-sharded GLOBAL array whose other half lives on the
        # (dead) peer's device — built locally from this process's
        # shard only, no cross-process compute.  Since ISSUE 9 a save
        # whose manifest references only the local process's shard
        # file is process-local and skips the barrier entirely, so a
        # barrier-timeout test must present a manifest that names the
        # peer's shard file.
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()).reshape(2), ("dp",))
        w1 = np.asarray(fluid.global_scope().find_var("w1"))
        local = jax.device_put(w1[:w1.shape[0] // 2],
                               jax.local_devices()[0])
        garr = jax.make_array_from_single_device_arrays(
            w1.shape, NamedSharding(mesh, P("dp")), [local])
        fluid.global_scope().set_var("w1", garr)
        from paddle_tpu.resilience import CheckpointBarrierTimeoutError
        try:
            fluid.io.save_sharded(exe, ckpt_dir,
                                  main_program=main_prog)
            print("BARRIER_UNEXPECTED_OK", flush=True)
        except CheckpointBarrierTimeoutError as e:
            print("BARRIER_TIMEOUT " + json.dumps(e.as_dict()),
                  flush=True)
        # _exit skips distributed-shutdown teardown that would wait on
        # the dead peer
        sys.stdout.flush()
        os._exit(0)

    mesh = make_mesh({"dp": jax.device_count()})

    main_prog, startup, loss = build()
    exe = fluid.Executor()
    exe.run(startup)

    bs = fluid.BuildStrategy()
    bs.num_trainers = 2
    bs.trainer_id = trainer_id
    bs.gradient_accumulation_steps = accum
    if ckpt_dir:
        # FSDP param placement so BOTH processes own real shard data —
        # a replicated layout would park every shard on process 0 and
        # make the multi-process ckpt test vacuous
        from paddle_tpu.parallel.strategies import ShardingRules

        bs.sharding_rules = ShardingRules(default="fsdp",
                                          fsdp_axis="dp")
    compiled = fluid.CompiledProgram(main_prog).with_data_parallel(
        loss_name=loss.name, build_strategy=bs, mesh=mesh)

    # deterministic global data; each trainer feeds its own half
    rng = np.random.RandomState(7)
    losses = []
    for _step in range(RUN_STEP):
        gx = rng.rand(2 * LOCAL_B, 4).astype("float32")
        gy = rng.rand(2 * LOCAL_B, 1).astype("float32")
        lo = trainer_id * LOCAL_B
        feed = {"x": global_batch(mesh, gx[lo:lo + LOCAL_B]),
                "y": global_batch(mesh, gy[lo:lo + LOCAL_B])}
        (lv,) = exe.run(compiled, feed=feed, fetch_list=[loss])
        losses.append(float(np.asarray(lv)))
        if ckpt_dir and _step == 1:
            fluid.io.save_sharded(exe, ckpt_dir, main_program=main_prog)
            # PERTURB the state with an off-stream batch, then load:
            # the remaining trajectory only matches the reference if
            # load actually rewinds the parameters (a silently no-op
            # load would leave the perturbed state and diverge)
            rng2 = np.random.RandomState(99)
            px = rng2.rand(2 * LOCAL_B, 4).astype("float32")
            py = rng2.rand(2 * LOCAL_B, 1).astype("float32")
            exe.run(compiled,
                    feed={"x": global_batch(mesh, px[lo:lo + LOCAL_B]),
                          "y": global_batch(mesh, py[lo:lo + LOCAL_B])},
                    fetch_list=[loss])
            fluid.io.load_sharded(exe, ckpt_dir, main_program=main_prog,
                                  mesh=mesh,
                                  sharding_rules=bs.sharding_rules)
    print("DIST_LOSSES " + json.dumps(losses), flush=True)


if __name__ == "__main__":
    main()
