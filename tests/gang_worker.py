"""Gang worker for the multi-process fault-tolerance chaos harness
(tests/test_gang.py): one rank of a REAL supervised training gang.

Launched by `resilience.Supervisor` (or tools/launch_gang.py), so it
reads its identity from the PADDLE_TRAINER_ID / PADDLE_TRAINERS /
PADDLE_COORDINATOR env contract via `parallel.init_distributed()` —
which also auto-registers the distributed HEALTH PLANE (heartbeats +
peer-loss monitor + poison key) on the KV store.  Each rank trains its
own single-device model (KV-store-only gang, NO cross-process XLA —
the container jax has no CPU collectives; same constraint as
tests/test_dist.py's dead-peer test), but the health plane, the
checkpoint-save barriers, and the supervisor protocol are the real
multi-process articles.

Protocol:
- "STEP <epoch> <step>" after every completed step,
- chaos is env-armed (`chaos.kill_rank` / `chaos.hang_rank` with a
  once-file so a relaunched gang does not re-fire),
- on a GangError (peer lost / stalled / poisoned) or a poisoned
  checkpoint barrier: print "PEER_LOST <json>" (detection latency
  attached) and exit `PEER_LOST_EXIT_CODE`,
- on SIGTERM: the Trainer drain path exits `PREEMPT_EXIT_CODE`,
- on clean completion: final persistables land in
  `<out-root>/rank<k>.npz`, the goodput ledger report (observe pillar
  8) in `<out-root>/rank<k>.goodput.json`, and the worker prints
  "DONE" — unless the done-rendezvous finds the gang broken (a peer
  died AFTER this rank finished), in which case the same structured
  "PEER_LOST <json>" + `PEER_LOST_EXIT_CODE` exit as the mid-train
  path, so the supervisor classifies the attempt correctly.

mode=barrier_poison: rank 1 writes the poison key and dies; rank 0
enters a sharded-save barrier and must get a
CheckpointBarrierPoisonedError in bounded time (seconds, not the
600 s barrier timeout) — printed as "BARRIER_POISONED <json>".
"""

import argparse
import json
import os
import sys
import time

# Script-mode env pins: one CPU device per rank.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=1")

import jax  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers, observe  # noqa: E402
from paddle_tpu.contrib import CheckpointConfig, Trainer  # noqa: E402
from paddle_tpu.contrib.trainer import EndStepEvent  # noqa: E402
from paddle_tpu.data import decorator  # noqa: E402
from paddle_tpu.parallel import init_distributed  # noqa: E402
from paddle_tpu.resilience import (PEER_LOST_EXIT_CODE,  # noqa: E402
                                   CheckpointBarrierPoisonedError,
                                   GangError, TrainingPreempted, chaos,
                                   health)

BATCHES_PER_EPOCH = 12
BATCH = 8


def train_func():
    x = layers.data(name="x", shape=[6], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h = layers.fc(x, size=16, act="relu")
    h = layers.dropout(h, dropout_prob=0.3)
    pred = layers.fc(h, size=1)
    return layers.mean(layers.square_error_cost(pred, y))


def opt_func():
    return fluid.optimizer.Adam(learning_rate=0.01)


def make_reader(rank):
    def base():
        # per-rank deterministic stream (seed differs by rank so the
        # two models' trajectories are distinct artifacts)
        r = np.random.RandomState(11 + rank)
        for _ in range(BATCHES_PER_EPOCH):
            yield {"x": r.rand(BATCH, 6).astype(np.float32),
                   "y": r.rand(BATCH, 1).astype(np.float32)}

    return decorator.shuffle(base, 4, seed=29 + rank)


def run_barrier_poison(rank, ckpt_root):
    """Deterministic bounded-barrier proof: rank 0 is already WAITING
    inside a checkpoint barrier when rank 1 writes the poison key and
    dies abruptly — the barrier must abort with a structured
    CheckpointBarrierPoisonedError within the ~1 s poison-poll cadence,
    never after the full (here 120 s) timeout.  (A per-rank LOCAL save
    skips barriers by design, so the barrier is driven directly — it is
    exactly what a gang-wide sharded save calls.)"""
    del ckpt_root
    kv = health.kv_client()
    assert kv is not None
    if rank == 1:
        time.sleep(1.5)  # rank 0 is inside the barrier by now
        health.write_poison(kv, rank=1,
                            reason="chaos: deliberate gang abort",
                            kind="manual", missing_ranks=[1])
        sys.stdout.flush()
        os._exit(7)  # abrupt: no barrier arrival, no cleanup
    t0 = time.monotonic()
    try:
        fluid.io._barrier("gang_test:poisoned", timeout_s=120.0)
        print("BARRIER_UNEXPECTED_OK", flush=True)
        os._exit(1)
    except CheckpointBarrierPoisonedError as e:
        payload = e.as_dict()
        payload["elapsed_wall_s"] = round(time.monotonic() - t0, 3)
        print("BARRIER_POISONED " + json.dumps(payload), flush=True)
    os._exit(0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-root", required=True)
    ap.add_argument("--out-root", required=True)
    ap.add_argument("--log-root", required=True)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--step-interval", type=int, default=3)
    ap.add_argument("--pace-s", type=float, default=0.12,
                    help="sleep per step so detection can land mid-train")
    ap.add_argument("--mode", default="train",
                    choices=["train", "barrier_poison"])
    args = ap.parse_args()

    rank, nranks = init_distributed()  # env contract + health plane
    assert jax.process_count() == nranks, jax.process_count()
    # multiprocess runtime: jax.devices()[0] is rank 0's device — pin
    # computation to THIS process's device (the gang is KV-only)
    jax.config.update("jax_default_device", jax.local_devices()[0])
    plane = health.get_health_plane()
    assert plane is not None, "init_distributed did not register health"

    if args.mode == "barrier_poison":
        run_barrier_poison(rank, args.ckpt_root)
        return

    trainer = Trainer(
        train_func, opt_func,
        checkpoint_config=CheckpointConfig(
            os.path.join(args.ckpt_root, f"rank{rank}"),
            step_interval=args.step_interval,
            epoch_interval=10 ** 6, max_num_checkpoints=4),
        telemetry=observe.TelemetryConfig(
            interval=100,
            log_path=os.path.join(args.log_root, f"rank{rank}.jsonl")),
        preempt_drain=True)

    def handler(event):
        if isinstance(event, EndStepEvent):
            gpos = event.epoch * BATCHES_PER_EPOCH + event.step
            print(f"STEP {event.epoch} {event.step}", flush=True)
            chaos.kill_rank(rank, gpos)
            chaos.hang_rank(rank, gpos)
            if args.pace_s > 0:
                time.sleep(args.pace_s)

    t0 = time.monotonic()
    try:
        trainer.train(num_epochs=args.epochs,
                      reader=make_reader(rank), event_handler=handler)
    except TrainingPreempted as e:
        print("PREEMPTED " + json.dumps(e.as_dict()), flush=True)
        os._exit(e.exit_code)
    except (GangError, CheckpointBarrierPoisonedError) as e:
        payload = e.as_dict()
        payload["detected_at_train_s"] = round(time.monotonic() - t0, 3)
        payload["rank"] = rank
        print("PEER_LOST " + json.dumps(payload), flush=True)
        # os._exit: jax.distributed teardown would hang on dead peers
        os._exit(PEER_LOST_EXIT_CODE)
    params = {v.name: np.asarray(trainer.scope.find_var(v.name))
              for v in trainer.train_program.list_vars()
              if v.persistable}
    os.makedirs(args.out_root, exist_ok=True)
    np.savez(os.path.join(args.out_root, f"rank{rank}.npz"), **params)
    def dump_goodput():
        # pillar-8 artifact: this process's wall-clock decomposition
        # (instrumented waits land via the attached ledger); a
        # relaunched rank's report carries the restart-replay badput
        # the chaos test asserts on
        with open(os.path.join(args.out_root,
                               f"rank{rank}.goodput.json"), "w") as f:
            json.dump(trainer.goodput(), f)

    # orderly leave: announce done and wait for the laggards so a
    # finished rank's silence is never mistaken for death (resumed
    # ranks run different numbers of remaining steps)
    plane.leave()
    if not plane.wait_gang_done(timeout_s=60.0):
        # the gang broke while we waited for the laggards (a peer died
        # after we finished — ranks drift apart, so a mid-train kill
        # for the victim can be post-train for us): surface the SAME
        # structured detection the mid-train path prints, so the
        # supervisor classifies the attempt as peer_lost, not a bare
        # crash.  A plain done-wait timeout still falls through to
        # DONE — our own work is complete either way.
        try:
            plane.check()
        except (GangError, CheckpointBarrierPoisonedError) as e:
            payload = e.as_dict()
            payload["detected_at_train_s"] = round(
                time.monotonic() - t0, 3)
            payload["rank"] = rank
            payload["at"] = "done_wait"
            dump_goodput()
            print("PEER_LOST " + json.dumps(payload), flush=True)
            os._exit(PEER_LOST_EXIT_CODE)
    dump_goodput()
    print("DONE", flush=True)
    sys.stdout.flush()
    os._exit(0)  # skip distributed teardown (peer may already be gone)


if __name__ == "__main__":
    main()
