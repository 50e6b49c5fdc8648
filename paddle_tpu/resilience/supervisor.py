"""Self-healing multi-process gang supervisor (docs/RESILIENCE.md,
distributed failure model).

The reference framework assumed a supervising runtime that detects
trainer death and recovers from checkpoints; synchronous TPU gangs
need the same thing one level up from the health plane: something
that OWNS the worker processes.  `Supervisor` spawns the N ranks of a
gang (fresh coordinator endpoint per attempt), watches their exit
codes, and on a broken gang kills the remainder within a grace period
and relaunches — resuming from the newest valid checkpoint via the
Trainer machinery the workers already carry.

Exit-code registry (the supervisor's whole protocol):

| code                     | meaning                                   |
|--------------------------|-------------------------------------------|
| 0                        | clean completion                          |
| 77  `PREEMPT_EXIT_CODE`  | drained after SIGTERM; emergency ckpt landed — relaunch resumes |
| 43  `PEER_LOST_EXIT_CODE`| deliberate exit after detecting peer loss / poison (GangError) |
| 128+N / negative         | killed by signal N (SIGKILL'd rank, OOM)  |
| anything else            | crash                                     |

Restart policy: every relaunch consumes the `max_restarts` budget;
preempt-drain restarts relaunch immediately (the checkpoint already
landed — waiting helps nobody), failure restarts back off on the
deterministic `retry_call` schedule (base * 2**failures, capped),
with an injectable `sleep` so tests assert the schedule.  Budget
exhaustion raises `GangFailedError` carrying every attempt's per-rank
exit codes.  A `finally` sweep guarantees no orphan processes
outlive `run()` regardless of how it exits.

The supervisor itself is jax-free — it manages processes and sets the
PADDLE_TRAINER_* env contract `parallel.init_distributed` reads
(trainer id, world size, coordinator endpoint); `tools/launch_gang.py`
is the CLI wrapper.
"""

from __future__ import annotations

import os
import signal as _signal
import socket
import subprocess
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

from .errors import GangFailedError
from .health import PEER_LOST_EXIT_CODE
from .preempt import PREEMPT_EXIT_CODE


def classify_exit(rc: Optional[int]) -> str:
    """One word per exit code, per the registry above."""
    if rc is None:
        return "running"
    if rc == 0:
        return "ok"
    if rc == PREEMPT_EXIT_CODE:
        return "preempt_drain"
    if rc == PEER_LOST_EXIT_CODE:
        return "peer_lost"
    if rc < 0:
        try:
            return f"signal:{_signal.Signals(-rc).name}"
        except ValueError:
            return f"signal:{-rc}"
    if rc > 128:
        try:
            return f"signal:{_signal.Signals(rc - 128).name}"
        except ValueError:
            return f"signal:{rc - 128}"
    return f"crash:{rc}"


def _free_port(host: str) -> int:
    s = socket.socket()
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port


class GangResult:
    """Outcome of a supervised run: per-attempt exit codes and how
    many relaunches it took."""

    def __init__(self, attempts: List[Dict[str, Any]]):
        self.attempts = attempts
        self.restarts = len(attempts) - 1

    @property
    def ok(self) -> bool:
        return bool(self.attempts) and self.attempts[-1]["reason"] == "ok"

    def as_dict(self) -> Dict[str, Any]:
        return {"ok": self.ok, "restarts": self.restarts,
                "attempts": self.attempts}


class Supervisor:
    """Spawn-and-heal a gang of `num_workers` processes.

    worker_cmd: the argv to run for every rank, or a callable
        `(rank, num_workers, coordinator) -> argv` for per-rank
        commands.  Each rank's env carries PADDLE_TRAINER_ID /
        PADDLE_TRAINERS / PADDLE_COORDINATOR (fresh port per attempt,
        so a relaunch never races a dying coordinator socket) plus
        `env` overrides.
    log_dir: when set, rank stdout/stderr go to
        `<log_dir>/attempt<k>_rank<r>.out/.err` (default: inherited).
    elastic: relaunch a broken gang at the SURVIVING world size
        (ISSUE 13, gang elasticity): ranks that died BY SIGNAL
        (SIGKILL, OOM — the machine-lost signature) are treated as
        lost capacity and the next attempt spawns
        `num_workers - lost` ranks (floor 1); deliberate exits
        (peer_lost 43, preempt 77, crashes) relaunch at full size —
        the process died, not the machine.  Workers read the new
        world size from PADDLE_TRAINERS and are expected to reshard
        their state from checkpoints (io.load_sharded is
        mesh-shape-agnostic).  Each shrink is recorded in the attempt
        dict (`shrunk_to`).
    host_coordinator: host the jax coordination SERVICE in the
        supervisor process (one fresh service per attempt) instead of
        inside worker rank 0.  This makes EVERY rank killable with
        structured detection by the survivors: with the default
        rank-0-hosted service, killing rank 0 takes the KV store down
        and jaxlib hard-aborts every surviving client the moment the
        service socket closes — before any health-plane verdict can
        land.  Workers need no changes (PADDLE_COORDINATOR points at
        the supervisor's service; a rank-0 worker's own vestigial
        service is pushed to an ephemeral port via
        JAX_COORDINATOR_BIND_ADDRESS).
    sleep: injectable for deterministic backoff tests.
    """

    def __init__(self, worker_cmd: Union[Sequence[str], Callable],
                 num_workers: int, *,
                 max_restarts: Optional[int] = None,
                 grace_s: Optional[float] = None,
                 backoff_base_s: Optional[float] = None,
                 backoff_max_s: Optional[float] = None,
                 env: Optional[Dict[str, str]] = None,
                 log_dir: Optional[str] = None,
                 coordinator_host: str = "127.0.0.1",
                 host_coordinator: bool = False,
                 elastic: bool = False,
                 poll_s: float = 0.2,
                 sleep: Callable[[float], None] = time.sleep,
                 event_log=None):
        from ..flags import FLAGS

        self.worker_cmd = worker_cmd
        self.num_workers = int(num_workers)
        if self.num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        self.max_restarts = int(FLAGS.supervisor_max_restarts
                                if max_restarts is None else max_restarts)
        self.grace_s = float(FLAGS.supervisor_grace_s
                             if grace_s is None else grace_s)
        self.backoff_base_s = float(
            FLAGS.supervisor_backoff_base_s if backoff_base_s is None
            else backoff_base_s)
        self.backoff_max_s = float(
            FLAGS.supervisor_backoff_max_s if backoff_max_s is None
            else backoff_max_s)
        self.env = dict(env or {})
        self.log_dir = log_dir
        self.coordinator_host = coordinator_host
        self.host_coordinator = bool(host_coordinator)
        self.elastic = bool(elastic)
        self.poll_s = float(poll_s)
        self.sleep = sleep
        self.event_log = event_log
        self.backoffs_slept: List[float] = []  # test-observable schedule
        self._log_files: List[Any] = []
        self._service = None  # per-attempt hosted coordination service

    def _start_service(self, coordinator: str) -> None:
        """Host the coordination service here (host_coordinator=True):
        generous service-side heartbeat windows so the SERVICE never
        declares a task dead before our health plane does (its verdict
        would hard-abort the surviving clients)."""
        # jax 0.9 keeps the service constructor private (it is what
        # jax.distributed.initialize itself calls on process 0)
        from jax._src.lib import _jax

        self._service = _jax.get_distributed_runtime_service(
            coordinator, self.num_workers, heartbeat_timeout=100)

    def _stop_service(self) -> None:
        if self._service is not None:
            try:
                self._service.shutdown()
            except Exception:  # noqa: BLE001 — dead clients may linger
                pass
            self._service = None

    # -- spawning ---------------------------------------------------------
    def _cmd_for(self, rank: int, coordinator: str) -> List[str]:
        if callable(self.worker_cmd):
            return list(self.worker_cmd(rank, self.num_workers,
                                        coordinator))
        return list(self.worker_cmd)

    def _spawn_gang(self, attempt: int) -> Dict[int, subprocess.Popen]:
        port = _free_port(self.coordinator_host)
        coordinator = f"{self.coordinator_host}:{port}"
        if self.host_coordinator:
            self._start_service(coordinator)
        procs: Dict[int, subprocess.Popen] = {}
        for rank in range(self.num_workers):
            env = dict(os.environ)
            env.update(self.env)
            env["PADDLE_TRAINER_ID"] = str(rank)
            env["PADDLE_TRAINERS"] = str(self.num_workers)
            env["PADDLE_COORDINATOR"] = coordinator
            if self.host_coordinator:
                # rank 0 still instantiates its own (unused) service;
                # park it on an ephemeral port so it can't collide
                env["JAX_COORDINATOR_BIND_ADDRESS"] = \
                    f"{self.coordinator_host}:0"
            stdout = stderr = None
            if self.log_dir:
                os.makedirs(self.log_dir, exist_ok=True)
                base = os.path.join(self.log_dir,
                                    f"attempt{attempt}_rank{rank}")
                stdout = open(base + ".out", "w")
                stderr = open(base + ".err", "w")
                self._log_files += [stdout, stderr]
            procs[rank] = subprocess.Popen(
                self._cmd_for(rank, coordinator), env=env,
                stdout=stdout, stderr=stderr)
        if self.event_log is not None:
            self.event_log.event("gang_start", attempt=attempt,
                                 num_workers=self.num_workers,
                                 coordinator=coordinator)
        return procs

    # -- one attempt ------------------------------------------------------
    def _wait_gang(self, procs: Dict[int, subprocess.Popen]
                   ) -> Dict[int, int]:
        """Wait the gang out.  The moment any rank exits non-zero the
        gang is broken and a three-phase teardown starts:

        1. `grace_s` of HANDS OFF — the preferred exit is survivors
           detecting the break themselves (health plane →
           PEER_LOST_EXIT_CODE; the observable, structured path),
        2. SIGTERM stragglers (a preempt_drain worker writes its
           emergency checkpoint and exits 77) + another `grace_s`,
        3. SIGKILL whatever is left.

        Returns {rank: returncode}."""
        codes: Dict[int, int] = {}
        breaking_t: Optional[float] = None
        phase = 0  # 0 = hands off, 1 = terminated, 2 = killed
        while len(codes) < len(procs):
            for rank, p in procs.items():
                if rank in codes:
                    continue
                rc = p.poll()
                if rc is None:
                    continue
                codes[rank] = rc
                if rc != 0 and breaking_t is None:
                    breaking_t = time.monotonic()
            if len(codes) == len(procs):
                break
            if breaking_t is not None and phase < 2:
                overdue = time.monotonic() - breaking_t
                want = 1 if overdue > self.grace_s else 0
                if overdue > 2 * self.grace_s:
                    want = 2
                if want > phase:
                    phase = want
                    for r2, p2 in procs.items():
                        if r2 not in codes and p2.poll() is None:
                            try:
                                if phase == 1:
                                    p2.terminate()
                                else:
                                    p2.kill()
                            except OSError:
                                pass
            time.sleep(self.poll_s)
        for p in procs.values():
            p.wait()  # reap
        return codes

    @staticmethod
    def _attempt_reason(codes: Dict[int, int]) -> str:
        kinds = {r: classify_exit(rc) for r, rc in codes.items()}
        if all(k == "ok" for k in kinds.values()):
            return "ok"
        if any(k == "peer_lost" for k in kinds.values()):
            return "peer_lost"
        if any(k.startswith(("crash", "signal")) for k in kinds.values()):
            return "crash"
        return "preempt_drain"

    # -- the loop ---------------------------------------------------------
    def run(self) -> GangResult:
        """Run the gang to clean completion, relaunching through
        failures until the restart budget runs out (GangFailedError,
        per-attempt exit codes attached).  No orphans survive this
        call."""
        attempts: List[Dict[str, Any]] = []
        failures = 0
        procs: Dict[int, subprocess.Popen] = {}
        try:
            for attempt in range(self.max_restarts + 1):
                attempt_t0 = time.monotonic()
                procs = self._spawn_gang(attempt)
                try:
                    codes = self._wait_gang(procs)
                finally:
                    self._stop_service()
                reason = self._attempt_reason(codes)
                rec = {"attempt": attempt,
                       "exit_codes": dict(sorted(codes.items())),
                       "classified": {r: classify_exit(rc)
                                      for r, rc in sorted(codes.items())},
                       "reason": reason,
                       # attempt wall clock: a broken attempt's whole
                       # duration is restart badput from the job's
                       # point of view (the goodput ledger inside each
                       # worker decomposes the useful part)
                       "duration_s": round(
                           time.monotonic() - attempt_t0, 3)}
                if self.elastic and reason != "ok":
                    # signal deaths = lost capacity (preempted machine);
                    # the next attempt runs with the survivors only and
                    # workers reshard their checkpoints to the new size
                    lost = [r for r, rc in codes.items()
                            if classify_exit(rc).startswith("signal")]
                    new_n = max(1, self.num_workers - len(lost))
                    if new_n != self.num_workers:
                        rec["shrunk_to"] = new_n
                        self.num_workers = new_n
                attempts.append(rec)
                if self.event_log is not None:
                    self.event_log.event(
                        "gang_restart" if reason != "ok" else "gang_end",
                        **rec)
                if reason == "ok":
                    return GangResult(attempts)
                if attempt == self.max_restarts:
                    break
                if reason == "preempt_drain":
                    delay = 0.0  # ckpt landed; resume immediately
                else:
                    delay = min(self.backoff_base_s * (2.0 ** failures),
                                self.backoff_max_s)
                    failures += 1
                self.backoffs_slept.append(delay)
                if delay > 0:
                    self.sleep(delay)
        finally:
            # no-orphans guarantee, however run() exits
            for p in procs.values():
                if p.poll() is None:
                    try:
                        p.kill()
                        p.wait(timeout=10)
                    except (OSError, subprocess.TimeoutExpired):
                        pass
            self._stop_service()
            for f in self._log_files:
                try:
                    f.close()
                except OSError:
                    pass
            self._log_files = []
        err = GangFailedError(
            f"gang failed after {len(attempts)} attempt(s) "
            f"({self.max_restarts} restart budget): last attempt "
            f"exit codes {attempts[-1]['exit_codes']}",
            attempts=attempts, num_workers=self.num_workers,
            max_restarts=self.max_restarts)
        if self.event_log is not None:
            self.event_log.event("gang_failed", **err.as_dict())
        raise err


def launch_gang(worker_cmd, num_workers: int, **kw) -> GangResult:
    """One-call form: Supervisor(...).run()."""
    return Supervisor(worker_cmd, num_workers, **kw).run()
