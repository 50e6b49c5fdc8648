#!/usr/bin/env python
"""CLI for the self-healing gang supervisor (docs/RESILIENCE.md).

    python tools/launch_gang.py --nproc 2 --max-restarts 3 \
        -- python my_train.py --ckpt ckpts/

Spawns the worker command once per rank with the PADDLE_TRAINER_ID /
PADDLE_TRAINERS / PADDLE_COORDINATOR env contract
`parallel.init_distributed` reads (fresh coordinator port per
attempt), translates the exit-code registry (0 ok, 77 preempt-drain,
43 peer-lost, signals), kills the remainder of a broken gang within
`--grace-s`, and relaunches on the deterministic backoff schedule
until the restart budget runs out.  Workers are expected to resume
from their newest valid checkpoint themselves (contrib.Trainer does).

On ONE host this is for CPU ranks only (JAX_PLATFORMS=cpu in the
workers): a chip belongs to one process at a time, so several ranks
cannot each take the host's chips — one process drives all four.  A
gang of chip-holding ranks needs one host per rank.

Prints one `GANG_ATTEMPT {json}` line per attempt and a final
`GANG_RESULT {json}` (or `GANG_FAILED {json}`); exits 0 on clean gang
completion, 1 on budget exhaustion.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from paddle_tpu.resilience import GangFailedError  # noqa: E402
from paddle_tpu.resilience.supervisor import Supervisor  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--nproc", type=int, default=2,
                    help="gang size (ranks)")
    ap.add_argument("--max-restarts", type=int, default=None,
                    help="relaunch budget (default FLAGS."
                         "supervisor_max_restarts)")
    ap.add_argument("--grace-s", type=float, default=None,
                    help="SIGTERM->SIGKILL grace for a broken gang's "
                         "survivors (default FLAGS.supervisor_grace_s)")
    ap.add_argument("--backoff-base-s", type=float, default=None)
    ap.add_argument("--backoff-max-s", type=float, default=None)
    ap.add_argument("--log-dir", default=None,
                    help="per-rank stdout/stderr capture directory "
                         "(default: inherit)")
    ap.add_argument("--host-coordinator", action="store_true",
                    help="host the jax coordination service in the "
                         "supervisor (fresh service per attempt) so "
                         "even rank 0 is killable with structured "
                         "detection by the survivors")
    ap.add_argument("--elastic", action="store_true",
                    help="relaunch a broken gang at the SURVIVING "
                         "world size: ranks killed by signal are "
                         "treated as lost capacity; workers read the "
                         "shrunken PADDLE_TRAINERS and reshard their "
                         "sharded checkpoints onto the smaller mesh "
                         "(io.load_sharded is mesh-shape-agnostic; "
                         "docs/DIST.md §hybrid)")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="worker command (prefix with --)")
    args = ap.parse_args(argv)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("no worker command given (append: -- python worker.py)")

    sup = Supervisor(cmd, args.nproc, max_restarts=args.max_restarts,
                     grace_s=args.grace_s,
                     backoff_base_s=args.backoff_base_s,
                     backoff_max_s=args.backoff_max_s,
                     log_dir=args.log_dir,
                     host_coordinator=args.host_coordinator,
                     elastic=args.elastic)
    try:
        result = sup.run()
    except GangFailedError as e:
        for a in e.details["attempts"]:
            print("GANG_ATTEMPT " + json.dumps(a), flush=True)
        print("GANG_FAILED " + json.dumps(e.as_dict()), flush=True)
        return 1
    for a in result.attempts:
        print("GANG_ATTEMPT " + json.dumps(a), flush=True)
    print("GANG_RESULT " + json.dumps(result.as_dict()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
