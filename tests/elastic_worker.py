"""Elastic gang worker for the reshard-resume chaos harness
(tests/test_gang.py::test_elastic_gang_shrinks_and_reshards; ISSUE 13
gang elasticity).

One rank of a supervised gang whose WORLD SIZE can shrink between
attempts (Supervisor(elastic=True)): the worker sizes its VIRTUAL
training mesh from PADDLE_TRAINERS — `fsdp = 2 * world` — so a gang
relaunched at the surviving world size must RESHARD its checkpoint
(saved fsdp=4-sharded at world 2) onto the smaller mesh (fsdp=2 at
world 1) via io.load_sharded's mesh-shape-agnostic assembly.  The
fsdp axis ZeRO-shards the Momentum optimizer state, so the reshard
covers exactly the state ISSUE 13 sharded.

Like tests/gang_worker.py, the gang is KV-store-only (no cross-process
XLA — the container jax has no CPU collectives): every rank trains the
SAME deterministic replica on its own local virtual mesh, rank r
checkpoints to `<ckpt-root>/rank<r>`, and the health plane provides
the structured peer-loss detection the supervisor's elastic relaunch
rides on.  Training math is mesh-size-invariant at a fixed global
batch (tests/test_grad_sync.py dp parity), so the shrunken resumed
run must CONVERGE TO THE UNINTERRUPTED RUN'S LOSS — the final loss
and params are written to `<out-root>/rank<r>.npz` for the harness to
compare within float-reduction tolerance.
"""

import argparse
import json
import os
import sys
import time

# one virtual mesh of 4 CPU devices per rank: big enough for the
# world-2 fsdp=4 mesh, and the shrunken world-1 fsdp=2 mesh uses a
# prefix of it.  Must be set before jax import (conftest-less script).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import jax  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.contrib import CheckpointConfig, Trainer  # noqa: E402
from paddle_tpu.contrib.trainer import EndStepEvent  # noqa: E402
from paddle_tpu.parallel import init_distributed, make_mesh  # noqa: E402
from paddle_tpu.resilience import (PEER_LOST_EXIT_CODE,  # noqa: E402
                                   CheckpointBarrierPoisonedError,
                                   GangError, TrainingPreempted, chaos,
                                   health)

BATCHES_PER_EPOCH = 12
BATCH = 8


def train_func():
    x = layers.data(name="x", shape=[16], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h = layers.fc(x, size=32, act="relu", name="ffn_in")
    pred = layers.fc(h, size=1, name="ffn_out")
    return layers.mean(layers.square_error_cost(pred, y))


def opt_func():
    # Momentum: a same-shape accumulator per param — the ZeRO-sharded
    # state the reshard must reassemble bit-faithfully
    return fluid.optimizer.MomentumOptimizer(learning_rate=0.05,
                                             momentum=0.9)


def make_reader():
    def reader():
        # IDENTICAL stream on every rank and every attempt: the gang is
        # a replicated-training stand-in, so any rank's trajectory IS
        # the reference trajectory
        r = np.random.RandomState(1234)
        for _ in range(BATCHES_PER_EPOCH):
            yield {"x": r.rand(BATCH, 16).astype(np.float32),
                   "y": r.rand(BATCH, 1).astype(np.float32)}

    return reader


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-root", required=True)
    ap.add_argument("--out-root", required=True)
    ap.add_argument("--log-root", required=True)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--step-interval", type=int, default=3)
    ap.add_argument("--pace-s", type=float, default=0.12)
    args = ap.parse_args()

    rank, nranks = init_distributed()
    # the elastic contract: mesh size FOLLOWS the world size the
    # supervisor relaunched us at — a shrink forces a reshard-on-load
    mesh = make_mesh({"fsdp": 2 * nranks}, devices=jax.local_devices())
    plane = health.get_health_plane()  # None at world size 1

    trainer = Trainer(
        train_func, opt_func,
        checkpoint_config=CheckpointConfig(
            os.path.join(args.ckpt_root, f"rank{rank}"),
            step_interval=args.step_interval,
            epoch_interval=10 ** 6, max_num_checkpoints=4),
        mesh=mesh)
    print(f"MESH fsdp={2 * nranks} world={nranks} "
          f"resume_epoch={trainer._resume_epoch} "
          f"resume_step={trainer._resume_step_in_epoch}", flush=True)

    last_loss = [None]

    def handler(event):
        if isinstance(event, EndStepEvent):
            gpos = event.epoch * BATCHES_PER_EPOCH + event.step
            last_loss[0] = float(np.asarray(
                event.metrics[0]).reshape(-1)[0])
            print(f"STEP {event.epoch} {event.step} {last_loss[0]:.6f}",
                  flush=True)
            chaos.kill_rank(rank, gpos)
            if args.pace_s > 0:
                time.sleep(args.pace_s)

    t0 = time.monotonic()
    try:
        trainer.train(num_epochs=args.epochs, reader=make_reader(),
                      event_handler=handler)
    except TrainingPreempted as e:
        print("PREEMPTED " + json.dumps(e.as_dict()), flush=True)
        os._exit(e.exit_code)
    except (GangError, CheckpointBarrierPoisonedError) as e:
        payload = e.as_dict()
        payload["detected_at_train_s"] = round(time.monotonic() - t0, 3)
        payload["rank"] = rank
        print("PEER_LOST " + json.dumps(payload), flush=True)
        os._exit(PEER_LOST_EXIT_CODE)
    params = {v.name: np.asarray(trainer.scope.find_var(v.name))
              for v in trainer.train_program.list_vars()
              if v.persistable}
    os.makedirs(args.out_root, exist_ok=True)
    np.savez(os.path.join(args.out_root, f"rank{rank}.npz"),
             __final_loss__=np.float64(last_loss[0]), **params)
    print(f"DONE {last_loss[0]:.6f}", flush=True)
    if plane is not None:
        plane.leave()
        plane.wait_gang_done(timeout_s=60.0)
    os._exit(0)


if __name__ == "__main__":
    main()
