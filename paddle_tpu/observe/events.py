"""Structured run events: an append-only JSONL log with provenance.

Every training/bench run gets a run-id + git-sha + backend/mesh stamp
and a stream of typed event records (telemetry windows, checkpoints,
compile storms) — the artifact a dashboards/alerting layer tails.
One JSON object per line; the file is valid to
tail mid-run (each line is flushed whole).
"""

from __future__ import annotations

import json
import os
import sys
import time
import uuid
import warnings
from typing import Any, Dict, List, Optional


# well-known serving event kinds (paddle_tpu.serving emits these; a
# dashboard tailing an event log can filter on them)
SERVING_EVENTS = (
    "serving_start",                # engine config at start()
    "serving_memory_plan",          # pre-warmup bucket-ladder fit plan
    #                                 (observe.memory probe prediction)
    "serving_warmup",               # bucket-ladder precompile summary
    "serving_window",               # periodic stats snapshot
    "serving_compile_post_warmup",  # LOUD: a shape leaked past buckets
    "serving_drain",                # final snapshot at drain
    "serving_breaker_open",         # LOUD: executor failure burst —
    #                                 admission flipped to DEGRADED
    "serving_breaker_close",        # half-open probe succeeded; RUNNING
    "serving_reload",               # hot weight swap applied (version,
    #                                 pause_ms) — ISSUE 15 straggler:
    #                                 emitted since PR 14, unregistered
)

# continuous-batching decode event kinds (docs/SERVING.md §decode) —
# the ISSUE 15 registry-enforcement sweep flushed these out: every one
# had been emitted since PR 12 without a registry entry, exactly the
# silent-typo rot the hang_kind collision (PR 9) showed
DECODE_EVENTS = (
    "serving_decode_start",        # engine geometry at start()
    "serving_decode_memory_plan",  # plan_fit gate verdict pre-warmup
    "serving_decode_warmup",       # executable precompile summary
    "serving_decode_window",       # periodic DecodeStats snapshot
    "serving_decode_drain",        # final snapshot at drain
    "serving_decode_preempt",      # a slot was evicted (pool dry)
    "serving_decode_evacuate",     # requests pulled off the replica
    #                                (weight roll / scheduler death)
    "serving_decode_reload",       # hot weight swap applied
)

# speculative-decoding event kinds (docs/SERVING.md §speculate): the
# multi-token verified-step path DecodeEngine(speculate_k=k) runs
SPECULATE_EVENTS = (
    "serving_decode_speculate",   # drafter armed at start(): k +
    #                               drafter class (the one-line record
    #                               that says THIS replica speculates)
    "serving_speculate_window",   # periodic speculation snapshot:
    #                               accept_rate, accept_hist,
    #                               speculation_efficiency
)

# serving-fleet event kinds (docs/SERVING.md §fleet): the router layer
# fronting N engine replicas.  Every record carries replica_id where
# one replica is the subject (engines stamp their own events with it
# too, via RunEventLog.bind — N replicas sharing one log stay
# disambiguated).
FLEET_EVENTS = (
    "serving_fleet_start",     # fleet config at start(): kind, replicas
    "serving_fleet_failover",  # LOUD: an in-flight request was pulled
    #                            off a replica and requeued on a
    #                            survivor (committed-token count rides)
    "serving_fleet_eject",     # LOUD: a replica was removed from
    #                            routing (scheduler death / manual)
    "serving_fleet_hedge",     # a slow attempt got a duplicate on
    #                            another replica (idempotent only)
    "serving_fleet_saturated", # LOUD: every replica fast-rejected —
    #                            the structured whole-fleet shed
    "serving_fleet_reload",    # one roll: begin/done phases + version
    "serving_fleet_reload_replica",  # per-replica swap: pause_ms,
    #                                  evacuated count
    "serving_fleet_window",    # periodic fleet-merged stats snapshot
    "serving_fleet_close",     # final merged snapshot at close
)

# disaggregated prefill/decode serving event kinds (docs/SERVING.md
# §disagg): the phase router, the KV-page handoff, and the
# SLO-driven autoscaler's decisions
DISAGG_EVENTS = (
    "serving_disagg_start",       # fleet topology at start()
    "serving_disagg_handoff",     # one KV-page hop: from_replica ->
    #                               to_replica, pages, bytes, handoff_ms
    "serving_disagg_failover",    # LOUD: a worker died mid-request —
    #                               the raw prompt re-prefills on a
    #                               survivor (phase + committed tokens)
    "serving_disagg_eject",       # LOUD: a worker removed from routing
    "serving_disagg_saturated",   # LOUD: one phase's workers all shed
    "serving_disagg_worker_join",  # zero-reject scale-up landed
    "serving_disagg_worker_leave", # zero-reject scale-down retired one
    "serving_disagg_window",      # periodic merged stats snapshot
    "serving_disagg_close",       # final snapshot at close
    "kv_transfer",                # also the router-row reqtrace span
    #                               name (registered for grep parity)
    "autoscale_up",               # Autoscaler added a worker: phase,
    #                               rule, observed value
    "autoscale_down",             # Autoscaler removed one after quiet_s
)

# resilience event kinds (docs/RESILIENCE.md): checkpoint fallback,
# save telemetry, and preemption-drain lifecycle, emitted by
# contrib.Trainer / the chaos CI smoke
RESILIENCE_EVENTS = (
    "ckpt_fallback",        # a serial was skipped (torn/corrupt), with
    #                         the structured CheckpointError as_dict()
    "ckpt_resume",          # resumed; fallback=True when not newest
    "ckpt_resume_failed",   # NO valid serial existed — fresh start
    "ckpt_save",            # one save: snapshot_ms (blocking) vs
    #                         write_ms (background) + bytes + async flag
    "ckpt_async_error",     # LOUD: a background write failed (the
    #                         structured CheckpointWriteError as_dict())
    "preempt_drain",        # SIGTERM/SIGINT received: finishing the
    #                         in-flight step, then emergency-saving
    "ckpt_emergency",       # the drain path's final checkpoint landed
)

# divergence-autopilot event kinds (docs/RESILIENCE.md §autopilot):
# the anomaly-triggered rollback-and-replay loop contrib.Trainer runs
# when built with autopilot= (resilience/autopilot.py)
RECOVERY_EVENTS = (
    "recovery_rollback",  # LOUD: in-process rollback to the newest
    #                       verified-good serial (trigger signal,
    #                       from/to cursor, budget state attached)
    "data_quarantine",    # the poisoned batch window the replay will
    #                       fast-forward past (never re-trained)
    "recovery_halt",      # LOUD: rollback budget exhausted (or no
    #                       verified-good serial) — train() raises
    #                       TrainingDivergedError after this record
)

# input-pipeline resilience event kinds (data/pipeline.py DeviceFeeder
# hardening + Trainer(validate_feed=True) admission checks)
FEED_EVENTS = (
    "feeder_retry",       # transient producer error: bounded
    #                       backoff retry (attempt, produced count)
    "feeder_stall",       # LOUD: the producer starved the queue past
    #                       stall_timeout_s — queue depth attached,
    #                       instead of the loop blocking silently
    "feed_quarantined",   # admission rejected a poisoned batch
    #                       (non-finite / signature drift) before any
    #                       device_put was spent on it
)

# gang fault-tolerance event kinds (docs/RESILIENCE.md, distributed
# failure model): health-plane detections, the dispatch watchdog's
# pre-abort record, straggler telemetry, and the supervisor lifecycle
GANG_EVENTS = (
    "peer_lost",       # LOUD: a peer stopped heartbeating (or the KV
    #                    store died with the coordinator); missing
    #                    ranks + staleness age attached
    "peer_stalled",    # a peer heartbeats but its step counter froze
    "step_hang",       # dispatch watchdog: a step blew its budget —
    #                    emitted BEFORE the abort, with the
    #                    first-compile vs hung-step verdict and the
    #                    runtime_stats deltas observed in the region
    "gang_skew",       # periodic per-rank step/step-rate snapshot
    #                    from heartbeat timestamps (straggler
    #                    telemetry before real multi-chip exists)
    "rank_slow",       # LOUD: one rank's step rate lags the gang
    #                    median by more than the slow factor
    "gang_start",      # supervisor: one gang attempt spawned
    "gang_restart",    # supervisor: attempt ended broken; relaunching
    "gang_end",        # supervisor: attempt ended clean
    "gang_failed",     # LOUD: restart budget exhausted — per-attempt
    #                    exit codes attached
)


# goodput observability event kinds (docs/OBSERVE.md pillar 8):
# the wall-clock decomposition contrib.Trainer emits at train_end
GOODPUT_EVENTS = (
    "goodput_report",  # the full GoodputLedger.report() dict: wall_s,
    #                    per-category seconds/fractions (Σ == wall),
    #                    goodput fraction, replay badput, effective_mfu
)


# alerting event kinds (docs/OBSERVE.md pillar 9): the AlertEngine's
# rule state-machine transitions — the records a pager/dashboard keys
# off, so the kinds are registered AND prefix-validated (an unknown
# alert_* kind is exactly the typo class this registry exists for)
ALERT_EVENTS = (
    "alert_pending",   # a rule breached; for_duration gating running
    "alert_firing",    # LOUD: the breach persisted — the rule fired
    #                    (value/target/severity attached; the
    #                    FlightRecorder bundles on this transition)
    "alert_resolved",  # the firing rule cleared (hysteresis +
    #                    resolve_duration satisfied)
)

# flight-recorder event kinds (docs/OBSERVE.md pillar 9)
FLIGHT_EVENTS = (
    "flight_record",   # one diagnostic bundle written: reason, path,
    #                    truncation flag, per-section errors
)


# numerics observability event kinds (docs/OBSERVE.md pillar 6):
# emitted by contrib.Trainer next to its telemetry windows
NUMERICS_EVENTS = (
    "nonfinite_provenance",  # LOUD: a telemetry window latched a
    #                          poisoned step — carries the joined
    #                          first_nonfinite_op (fluid op type/index/
    #                          group), the guard's skip counter and the
    #                          loss scale, so a skipped update is
    #                          attributable without re-running anything
)


# ---------------------------------------------------------------------------
# Event-kind validation (ISSUE 15 satellite): a dashboard's filter is a
# string match, so a typo'd kind silently drops off every chart — the
# PR 9 hang_kind-vs-kind collision class.  Kinds under the dashboard
# prefixes are validated against the registries above: warn by default,
# raise under tests (strict).
# ---------------------------------------------------------------------------

_VALIDATED_PREFIXES = ("serving_", "fleet_", "gang_", "alert_",
                       "flight_", "autoscale_", "recovery_",
                       "feeder_", "feed_")
_KNOWN_KINDS = set(SERVING_EVENTS) | set(DECODE_EVENTS) \
    | set(FLEET_EVENTS) | set(GANG_EVENTS) | set(RESILIENCE_EVENTS) \
    | set(NUMERICS_EVENTS) | set(GOODPUT_EVENTS) | set(ALERT_EVENTS) \
    | set(FLIGHT_EVENTS) | set(DISAGG_EVENTS) | set(RECOVERY_EVENTS) \
    | set(FEED_EVENTS) | set(SPECULATE_EVENTS)
_strict_kinds = [False]
_warned_kinds: set = set()


def set_strict_kinds(flag: bool) -> bool:
    """Unknown validated-prefix kinds raise instead of warning.
    Returns the previous setting (tests flip and restore); the
    PADDLE_TPU_STRICT_EVENTS env var also enables it."""
    prev = _strict_kinds[0]
    _strict_kinds[0] = bool(flag)
    return prev


def register_event_kinds(*kinds: str) -> None:
    """Extend the known-kind registry (a subsystem adding a new
    dashboard event registers it here — or in the tuples above when it
    ships in-tree)."""
    _KNOWN_KINDS.update(kinds)


def _validate_kind(kind: str) -> None:
    if not kind.startswith(_VALIDATED_PREFIXES) \
            or kind in _KNOWN_KINDS:
        return
    msg = (f"event kind {kind!r} matches a dashboard prefix "
           f"{_VALIDATED_PREFIXES} but is not registered in "
           f"observe.events (SERVING/DECODE/FLEET/GANG registries) — "
           f"a typo here silently drops the event off every dashboard "
           f"filter; register it with register_event_kinds() or fix "
           f"the name")
    if _strict_kinds[0] or os.environ.get("PADDLE_TPU_STRICT_EVENTS"):
        raise ValueError(msg)
    if kind not in _warned_kinds:
        _warned_kinds.add(kind)
        warnings.warn(msg, stacklevel=3)


def new_run_id() -> str:
    """Short unique id for one run/invocation (12 hex chars)."""
    return uuid.uuid4().hex[:12]


def git_sha(cwd: Optional[str] = None) -> Optional[str]:
    """Current git HEAD (short), or None outside a repo / without git."""
    import subprocess

    try:
        r = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                           capture_output=True, text=True, timeout=5,
                           cwd=cwd)
    except (OSError, subprocess.SubprocessError):
        return None
    sha = r.stdout.strip()
    return sha if r.returncode == 0 and sha else None


def _backend_info() -> Dict[str, Any]:
    """Backend/device provenance WITHOUT forcing backend init: only
    reports when jax is already imported and initialized (events logs
    must stay usable from pure-host tools)."""
    if "jax" not in sys.modules:
        return {}
    try:
        import jax

        devs = jax.devices()
        return {"backend": jax.default_backend(),
                "n_devices": len(devs),
                "device_kind": devs[0].device_kind if devs else None}
    except Exception:  # noqa: BLE001 — a dead backend must not kill logging
        return {}


class RunEventLog:
    """Append-only JSONL event log for one run.

        with RunEventLog("events.jsonl", mesh_shape={"dp": 8}) as log:
            log.event("checkpoint", serial=3)
            log.telemetry_window(tel, window=10)

    Records carry {ts (unix seconds), run_id, event, ...fields}.  The
    first record is `run_begin` with run provenance (git sha, backend,
    mesh); `close()` appends `run_end`.

    `max_bytes`: size-bound the log for long gang/serving runs (they
    append JSONL unbounded otherwise).  When the file would exceed the
    bound it rolls to `<path>.1` (one generation kept, the classic
    rotate) and the fresh file starts with a `run_rotate` record so a
    tailer knows records continue from a rolled file.  Rotation happens
    under the same write lock as every record (the PR 7 thread-locked
    path), so concurrent background-writer events never interleave or
    land in a half-rotated file.
    """

    def __init__(self, path: str, run_id: Optional[str] = None,
                 mesh_shape: Optional[Dict[str, int]] = None,
                 meta: Optional[Dict[str, Any]] = None,
                 max_bytes: Optional[int] = None):
        if max_bytes is not None and int(max_bytes) < 1024:
            raise ValueError("max_bytes < 1024 would rotate on nearly "
                             "every record")
        self.path = path
        self.run_id = run_id or new_run_id()
        self.max_bytes = None if max_bytes is None else int(max_bytes)
        self.rotations = 0
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")
        self._bytes = os.path.getsize(path)
        # async checkpoint writers emit ckpt_save from their background
        # thread; serialize record writes so lines never interleave
        import threading

        self._wlock = threading.Lock()
        begin: Dict[str, Any] = {"git_sha": git_sha(),
                                 "argv": list(sys.argv)}
        begin.update(_backend_info())
        if mesh_shape:
            begin["mesh_shape"] = dict(mesh_shape)
        if meta:
            begin.update(meta)
        self.event("run_begin", **begin)

    def _write_locked(self, rec: Dict[str, Any]) -> None:
        """Write one record; caller holds the lock."""
        line = json.dumps(rec, default=_jsonable) + "\n"
        if (self.max_bytes is not None
                and self._bytes + len(line) > self.max_bytes
                and self._bytes > 0):
            self._f.close()
            os.replace(self.path, self.path + ".1")
            self._f = open(self.path, "a", encoding="utf-8")
            self._bytes = 0
            self.rotations += 1
            marker = json.dumps(
                {"ts": round(time.time(), 3), "run_id": self.run_id,
                 "event": "run_rotate", "rotations": self.rotations,
                 "rolled_to": self.path + ".1"},
                default=_jsonable) + "\n"
            self._f.write(marker)
            self._bytes += len(marker)
        self._f.write(line)
        self._f.flush()
        self._bytes += len(line)

    def event(self, kind: str, **fields: Any) -> Dict[str, Any]:
        """Append one event record (flushed immediately).  Kinds under
        the dashboard prefixes (serving_/fleet_/gang_) are validated
        against the registries at the top of this module — warn by
        default, raise under strict mode (tests)."""
        _validate_kind(kind)
        rec = {"ts": round(time.time(), 3), "run_id": self.run_id,
               "event": kind}
        rec.update(fields)
        with self._wlock:
            self._write_locked(rec)
        return rec

    def telemetry_window(self, telemetry, **extra: Any) -> Dict[str, Any]:
        """Emit one periodic-fetch window (a StepTelemetry or plain
        dict) plus any runtime-stats fields the caller attaches."""
        fields = (telemetry.as_dict() if hasattr(telemetry, "as_dict")
                  else dict(telemetry))
        fields.update(extra)
        return self.event("telemetry", **fields)

    def serving_window(self, stats, **extra: Any) -> Dict[str, Any]:
        """Emit one serving stats snapshot (a serving.ServingStats or a
        plain dict) — the serving analog of telemetry_window."""
        fields = (stats.snapshot() if hasattr(stats, "snapshot")
                  else dict(stats))
        fields.update(extra)
        return self.event("serving_window", **fields)

    def bind(self, **fields: Any) -> "BoundEventLog":
        """A view over this log that stamps `fields` (e.g. replica_id)
        into every record it emits — the way N serving-engine replicas
        share ONE process log without their events becoming
        indistinguishable.  The view shares the file, write lock, and
        run_id; closing the view is a no-op (the owner closes the
        base)."""
        return BoundEventLog(self, fields)

    def close(self):
        if not self._f.closed:
            self.event("run_end")
            self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class BoundEventLog:
    """RunEventLog view with fixed fields merged into every record
    (see RunEventLog.bind).  Explicit per-event fields win on key
    collision.  Safe to re-bind (views nest by merging)."""

    def __init__(self, base: RunEventLog, fields: Dict[str, Any]):
        while isinstance(base, BoundEventLog):
            fields = {**base._fields, **fields}
            base = base._base
        self._base = base
        self._fields = dict(fields)

    @property
    def run_id(self) -> str:
        return self._base.run_id

    @property
    def path(self) -> str:
        return self._base.path

    def bind(self, **fields: Any) -> "BoundEventLog":
        return BoundEventLog(self, fields)

    def event(self, kind: str, **fields: Any) -> Dict[str, Any]:
        return self._base.event(kind, **{**self._fields, **fields})

    def telemetry_window(self, telemetry, **extra: Any) -> Dict[str, Any]:
        fields = (telemetry.as_dict() if hasattr(telemetry, "as_dict")
                  else dict(telemetry))
        fields.update(extra)
        return self.event("telemetry", **fields)

    def serving_window(self, stats, **extra: Any) -> Dict[str, Any]:
        fields = (stats.snapshot() if hasattr(stats, "snapshot")
                  else dict(stats))
        fields.update(extra)
        return self.event("serving_window", **fields)

    def close(self):
        """No-op: the view does not own the underlying file."""


def _jsonable(v):
    import numpy as np

    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, np.ndarray):
        return v.tolist()
    return str(v)


def read_events(path: str) -> List[Dict[str, Any]]:
    """Parse a JSONL event log back into records.  Raises on corrupt
    lines — an event log that silently drops records is worse than one
    that fails loudly (a torn final line from a killed process is the
    one tolerated exception)."""
    out: List[Dict[str, Any]] = []
    with open(path, encoding="utf-8") as f:
        lines = f.read().splitlines()
    for i, ln in enumerate(lines):
        if not ln.strip():
            continue
        try:
            out.append(json.loads(ln))
        except json.JSONDecodeError:
            if i == len(lines) - 1:
                break  # torn tail from a killed writer
            raise
    return out
