"""paddle_tpu.observe — device-side telemetry for the TPU runtime.

Three pillars (docs/OBSERVE.md):

1. TRACE ATTRIBUTION — the executor wraps every op lowering in
   `jax.named_scope("<op_type>:<op_index>")` so jax.profiler traces and
   XLA HLO metadata carry fluid op names end-to-end; `trace.py` parses
   a captured trace back into the fluid profiler's per-op time table
   (`profiler.profiler(sorted_key=...)` prints it).

2. DEVICE-SIDE METRICS — `StepTelemetry` accumulates loss/grad-norm/
   update-norm/non-finite counters INSIDE the jitted step (extra carry
   state, no host round-trips, no callbacks) and is fetched every N
   steps in one sync; host-side
   `runtime_stats` counts XLA compiles (+wall time, via
   jax.monitoring), persistent-cache hits and misses, executor
   retraces, and the four host phases of a step (prepare / place /
   call / writeback; `call` is the dispatch); every COLD run (jax
   traced, lowered, compiled or read its cache) leaves a record in
   `runtime_stats.cold_runs()`, and building a Program is
   `runtime_stats.stage("build_program")`.

3. STRUCTURED RUN EVENTS — `RunEventLog` writes JSONL records with
   run-id/git-sha/backend/mesh provenance, consumed by
   contrib.Trainer(telemetry=...).

4. COST ATTRIBUTION — `cost.py` walks the *optimized* HLO module with
   the same wire scanner, computing analytic per-instruction flops and
   materialized-buffer bytes, injecting the Pallas kernel cost
   registry at custom calls, and joining to fluid ops + measured
   device time (`op_cost_table`); a Pallas-active step's FLOP count
   is `total_costs` of it.

5. MEMORY — `memory.py` parses the optimized module's buffer
   assignment (compiled.memory_analysis()), attributing every HBM
   buffer to its fluid op and classifying it (params / optimizer_state
   / gradients / activations / workspace, donated tallied):
   `memory_report`/`memory_table` + `format_memory_table`, the
   `memory_timeline` live-bytes curve (chrome-trace exportable), and
   `plan_fit` — peak-HBM prediction for a candidate (batch, seq,
   dtype, remat) config from two small probe compiles, without ever
   compiling the candidate.  serving.ServingEngine validates its
   bucket ladder with it; `step_mem_breakdown` is the one-dict form.

7. PER-REQUEST TRACING + METRICS EXPORT — `reqtrace.py` threads a
   host-side `RequestTrace` (monotonic spans at queue boundaries
   only: zero device round-trips) through the serving stack —
   admission, batch formation, dispatch, decode joins, preemption,
   failover/hedge hops — with head sampling plus tail-based keep
   (slow/error/failover always survive), a bounded ring, and
   `export_chrome_trace` (rows = replica, so one trace draws across
   replica rows under chaos); `registry.py` is the pull-model
   `MetricsRegistry` joining every subsystem's existing snapshot
   surface (StepTelemetry, RuntimeStats, Serving/Decode/Fleet stats,
   gang heartbeat skew, memory peaks) into `metrics_snapshot()`,
   Prometheus text exposition (LatencyHistogram log bins mapped
   exactly onto cumulative `le` buckets), and an opt-in localhost
   `MetricsServer` (/metrics + /healthz) on Fleet/Trainer.

6. NUMERICS — `numerics.py` (the production replacement for the
   reference's host-side per-op NaN scan, operator.cc:943): per-layer
   training dynamics (grad/param norms + update ratio per NAMED
   parameter group, the sharding-layer names) as vector fields riding
   the `__telemetry__` accumulator, and first-nonfinite op provenance
   — a per-op finite bitmap computed in-step and latched on the first
   poisoned step, joined host-side to the fluid op desc
   (`numerics_report`/`format_numerics_table`;
   `StepTelemetry.groups`/`.first_nonfinite_op`).  All device-side,
   zero extra dispatches, byte-identical step when disabled.

8. GOODPUT — `goodput.py` accounts every second of a training run's
   WALL clock into exclusive categories (step / replay / compile /
   data_stall / checkpoint / recovery / barrier_wait / idle,
   Σ == wall):
   host-monotonic timestamps at phase boundaries only, zero device
   dispatches, byte-identical step lowering.  `GoodputLedger.report`
   yields the goodput fraction and `effective_mfu` = headline MFU x
   goodput; `export_chrome_trace` draws the step-anatomy timeline on
   rows aligned with reqtrace's exporter; `goodput_collector` feeds
   /metrics.  contrib.Trainer threads it (`Trainer.goodput()`).

9. ALERTING + FLIGHT RECORDING — `alerts.py` is the layer that
   *watches* pillars 1-8: declarative rules (threshold, multi-window
   burn-rate, z-score anomaly) evaluated on a background thread over
   `MetricsRegistry` snapshots, each walking a pending→firing→resolved
   state machine with `for_duration`/hysteresis, emitting registered
   `alert_*` events, exporting an `alerts` metric family + `/alerts`
   route, and exposing `signals()` for the future autoscaler;
   `flightrec.py` writes rate-limited, size-bounded diagnostic
   bundles (event tail, metrics snapshot, reqtrace export, goodput
   table, numerics provenance, thread stacks) on firing alerts,
   watchdog hangs, and unhandled crashes.  Pure host, zero device
   dispatches, byte-identical step lowering on vs off.
"""

from . import cost  # noqa: F401
from .alerts import (AlertEngine, AlertRule, AnomalyRule,  # noqa: F401
                     BurnRateRule, MetricSelector, ThresholdRule,
                     disagg_rule_pack, fleet_rule_pack,
                     serving_rule_pack, snapshot_value,
                     speculate_rule_pack, trainer_rule_pack)
from .cost import (bucket_summary, copyish_instructions,  # noqa: F401
                   device_peaks, flash_boundary_layout,
                   format_cost_table, layout_byte_share, op_cost_table,
                   program_costs)
from .events import (ALERT_EVENTS, DECODE_EVENTS,  # noqa: F401
                     DISAGG_EVENTS, FEED_EVENTS, FLEET_EVENTS,
                     FLIGHT_EVENTS, GANG_EVENTS, GOODPUT_EVENTS,
                     NUMERICS_EVENTS, RECOVERY_EVENTS,
                     RESILIENCE_EVENTS, SERVING_EVENTS,
                     SPECULATE_EVENTS, BoundEventLog,
                     RunEventLog, git_sha, new_run_id, read_events,
                     register_event_kinds, set_strict_kinds)
from .flightrec import FlightRecorder  # noqa: F401
from .goodput import (CATEGORIES as GOODPUT_CATEGORIES,  # noqa: F401
                      GoodputLedger, format_goodput_table,
                      goodput_report)
from .memory import (DEVICE_HBM_BYTES, PLAN_FIT_REL_TOL,  # noqa: F401
                     device_memory_budget, export_chrome_trace,
                     format_memory_table, memory_report, memory_table,
                     memory_timeline, plan_fit, resident_state_bytes,
                     sharded_memory_report, step_mem_breakdown)
from .metrics import (TELEMETRY_VAR, StepTelemetry,  # noqa: F401
                      enable_telemetry, fetch_telemetry, init_telemetry,
                      telemetry_enabled)
from .monitoring import (LatencyHistogram, RuntimeStats,  # noqa: F401
                         device_memory_stats, peak_memory_bytes,
                         runtime_stats)
from .numerics import (GROUP_NAMES, enable_numerics,  # noqa: F401
                       format_numerics_table, group_of,
                       join_first_nonfinite, numerics_enabled,
                       numerics_report, param_groups,
                       worst_update_ratio)
from .registry import (MetricFamily, MetricsRegistry,  # noqa: F401
                       MetricsServer, default_registry,
                       disagg_collector, fleet_collector,
                       gang_collector, goodput_collector,
                       memory_collector, metrics_snapshot,
                       process_collector, recovery_collector,
                       runtime_collector,
                       serving_stats_collector, standard_collectors,
                       telemetry_collector, tracer_collector)
from .reqtrace import (TAIL_KEEP_MARKS, ReqTracer,  # noqa: F401
                       RequestTrace, Span, new_trace_id)
from .trace import fluid_op_of, format_op_table, op_time_table  # noqa: F401


class TelemetryConfig:
    """How contrib.Trainer publishes telemetry.

    interval: fetch the device accumulator every N steps (the
        "device-accumulate, periodic-fetch" cadence — never per-step).
    log_path: write telemetry windows to this JSONL file (a
        RunEventLog is created per training run).
    event_log: alternatively, an existing RunEventLog to emit into.
    numerics: also enable observe pillar 6 on the train program —
        per-layer (named parameter group) training dynamics riding the
        same accumulator, and first-nonfinite op provenance; a window
        that latched a poisoned step additionally emits a
        `nonfinite_provenance` event through the RunEventLog.
    max_log_bytes: size-bound the JSONL log created from `log_path`
        (RunEventLog max_bytes rotation); None = unbounded.
    """

    def __init__(self, interval: int = 10, log_path=None, event_log=None,
                 numerics: bool = False, max_log_bytes=None):
        if interval < 1:
            raise ValueError("telemetry interval must be >= 1")
        self.interval = int(interval)
        self.log_path = log_path
        self.event_log = event_log
        self.numerics = bool(numerics)
        self.max_log_bytes = max_log_bytes
