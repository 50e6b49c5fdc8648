"""Training worker for the crash-chaos harness (tests/test_preempt.py):
a REAL training subprocess the parent SIGKILLs/SIGTERMs at an arbitrary
step and relaunches.

The job is deliberately loaded with every piece of state bit-exact
resume must carry (docs/RESILIENCE.md):

- dropout (the per-step RNG stream `__rng_key__`),
- Adam (optimizer moment/beta-power accumulators),
- dynamic loss scaling + the in-step update guard, with a NaN batch
  injected at a fixed step so the scale value and the good/bad/skip
  counters are all NON-trivial at kill time,
- a seeded shuffled reader (deterministic feed order across restarts).

Protocol (parent side in test_preempt.py):
- "STEP <epoch> <step>" on stdout after every completed step,
- on SIGTERM: Trainer's drain path writes an emergency checkpoint and
  the worker exits with resilience.PREEMPT_EXIT_CODE,
- on clean completion: final persistables land in --out (npz) and the
  worker prints "DONE".  Two runs are compared with np.array_equal.
"""

import argparse
import json
import os
import sys

# Script-mode only: one CPU device.
if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=1")

import jax  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers, observe  # noqa: E402
from paddle_tpu.contrib import CheckpointConfig, Trainer  # noqa: E402
from paddle_tpu.contrib.trainer import EndStepEvent  # noqa: E402
from paddle_tpu.data import decorator  # noqa: E402
from paddle_tpu.resilience import TrainingPreempted, chaos  # noqa: E402

BATCHES_PER_EPOCH = 12
BATCH = 8
NAN_AT_STEP = 4  # poisons epoch-0 step 4: loss-scale/guard state moves


def train_func():
    x = layers.data(name="x", shape=[6], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h = layers.fc(x, size=16, act="relu")
    h = layers.dropout(h, dropout_prob=0.3)
    pred = layers.fc(h, size=1)
    return layers.mean(layers.square_error_cost(pred, y))


def opt_func():
    return fluid.amp.decorate(
        fluid.optimizer.Adam(learning_rate=0.01),
        use_dynamic_loss_scaling=True, init_loss_scaling=16.0,
        incr_every_n_steps=3)


def make_reader():
    def base():
        r = np.random.RandomState(5)
        for _ in range(BATCHES_PER_EPOCH):
            yield {"x": r.rand(BATCH, 6).astype(np.float32),
                   "y": r.rand(BATCH, 1).astype(np.float32)}

    shuffled = decorator.shuffle(base, 4, seed=13)

    def poisoned():
        for i, b in enumerate(shuffled()):
            yield chaos.poison_feed(b, ["x"]) if i == NAN_AT_STEP else b

    return poisoned


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--log", required=True)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--step-interval", type=int, default=3)
    ap.add_argument("--slow-write-ms", type=float, default=0.0,
                    help="chaos: stretch every background checkpoint "
                         "write so a SIGKILL lands mid-flush (torn-"
                         "checkpoint production)")
    ap.add_argument("--sync-save", action="store_true")
    args = ap.parse_args()

    if args.slow_write_ms > 0:
        chaos.arm_delay("ckpt:write", args.slow_write_ms / 1000.0,
                        times=10 ** 6)

    trainer = Trainer(
        train_func, opt_func,
        checkpoint_config=CheckpointConfig(
            args.ckpt, step_interval=args.step_interval,
            epoch_interval=10 ** 6,  # step-cadence saves only
            max_num_checkpoints=4,
            async_save=not args.sync_save),
        telemetry=observe.TelemetryConfig(interval=100,
                                          log_path=args.log),
        preempt_drain=True)

    def handler(event):
        if isinstance(event, EndStepEvent):
            print(f"STEP {event.epoch} {event.step}", flush=True)

    try:
        trainer.train(num_epochs=args.epochs, reader=make_reader(),
                      event_handler=handler)
    except TrainingPreempted as e:
        print("PREEMPTED " + json.dumps(e.as_dict()), flush=True)
        sys.exit(e.exit_code)
    params = {v.name: np.asarray(trainer.scope.find_var(v.name))
              for v in trainer.train_program.list_vars()
              if v.persistable}
    trainer.stop()
    np.savez(args.out, **params)
    print("DONE", flush=True)


if __name__ == "__main__":
    main()
