"""Observe pillar 7 (ISSUE 15): per-request tracing + unified metrics.

The load-bearing properties:

- **guard discipline** (the ISSUE 4 / PR 11 pattern): tracing enabled
  at sample_rate=0 adds ZERO device dispatches, zero retraces, and the
  decode executable lowers byte-identically with or without a tracer —
  spans are host timestamps at queue boundaries only.
- **tail-based keep**: sampling can never hide a pathology — slow,
  errored, preempted, failed-over, hedged traces survive sample_rate=0.
- **exposition exactness**: LatencyHistogram log bins map onto
  cumulative Prometheus `le` buckets bin-for-bin (prefix sums, +Inf ==
  count, sum == sum_ms) — a scraped histogram IS the serving histogram.
- **one metrics plane**: a Fleet/engine/trainer registry scrape
  exposes families from every subsystem over localhost HTTP, and a
  sick collector degrades to `observe_collector_up 0`, never a dead
  scrape.
"""

from __future__ import annotations

import json
import re
import time
import urllib.request

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.observe import (MetricsRegistry, MetricsServer,
                                ReqTracer, RequestTrace)
from paddle_tpu.observe.monitoring import LatencyHistogram
from paddle_tpu.observe.registry import (MetricFamily, counter, gauge,
                                         histogram,
                                         serving_stats_collector,
                                         standard_collectors,
                                         telemetry_collector,
                                         tracer_collector)


# ---------------------------------------------------------------------------
# RequestTrace / ReqTracer mechanics
# ---------------------------------------------------------------------------

def test_trace_spans_and_phase_breakdown():
    tr = ReqTracer(sample_rate=1.0)
    t = tr.new_trace("decode")
    now = time.monotonic()
    t.add("join_wait", now - 0.020, now - 0.010, replica_id=0, slot=1)
    t.add("dispatch", now - 0.010, now - 0.004, kind="prefill",
          replica_id=0, slot=1)
    t.add("dispatch", now - 0.004, now, kind="decode", replica_id=0,
          slot=1, iterations=2)
    assert tr.finish(t) is True
    assert t.keep_reason == "head_sampled"
    ph = t.phase_ms()
    assert ph["join_wait"] == pytest.approx(10.0, rel=0.2)
    assert ph["dispatch"] == pytest.approx(10.0, rel=0.2)
    assert t.replica_ids() == [0]
    # per-phase aggregates are exact over finished traces
    summ = tr.phase_summary()
    assert summ["dispatch"]["count"] == 2
    assert summ["join_wait"]["count"] == 1
    wire = t.as_dict()
    assert wire["trace_id"] == t.trace_id
    assert len(wire["spans"]) == 3
    # double-finish is idempotent (failover paths can race a late
    # engine resolution)
    assert tr.finish(t) is True
    assert tr.snapshot()["finished"] == 1


def test_head_sampling_deterministic_and_ring_bound():
    tr = ReqTracer(sample_rate=0.25, capacity=8)
    kept = 0
    for _ in range(100):
        t = tr.new_trace()
        if tr.finish(t):
            kept += 1
    assert kept == 25  # deterministic 1-in-4, not probabilistic
    assert tr.snapshot()["ring_size"] == 8  # bounded: oldest evicted
    assert len(tr.traces()) == 8
    with pytest.raises(ValueError):
        ReqTracer(sample_rate=1.5)
    with pytest.raises(ValueError):
        ReqTracer(capacity=0)


def test_tail_keep_slow_error_and_marks():
    tr = ReqTracer(sample_rate=0.0, slow_keep_ms=5.0)
    # a fast clean trace at sample_rate=0 is dropped
    assert tr.finish(tr.new_trace()) is False
    # an error trace survives
    terr = tr.new_trace()
    assert tr.finish(terr, error=RuntimeError("boom")) is True
    assert terr.keep_reason == "error"
    assert terr.error == "RuntimeError: boom"
    # each pathology marker survives
    for mark in ("failover", "hedge", "abandoned", "preempt",
                 "evacuated"):
        t = tr.new_trace()
        t.point(mark, replica_id=0)
        assert tr.finish(t) is True, mark
        assert t.keep_reason == mark
    # a slow trace survives
    slow = tr.new_trace()
    slow.t_create -= 0.050  # 50 ms old
    assert tr.finish(slow) is True
    assert slow.keep_reason == "slow"
    snap = tr.snapshot()
    assert snap["kept"] == snap["tail_kept"] == 7
    assert snap["errors"] == 1


def test_max_spans_bound():
    tr = ReqTracer(max_spans=4)
    t = tr.new_trace()
    now = time.monotonic()
    for i in range(10):
        t.add("dispatch", now, now, slot=i)
    assert len(t.spans) == 4
    assert t.dropped_spans == 6
    tr.finish(t)
    assert t.as_dict()["dropped_spans"] == 6


def test_chrome_export_rows_and_metadata(tmp_path):
    tr = ReqTracer()
    t = tr.new_trace("fleet_decode")
    now = time.monotonic()
    t.add("route", now, now + 0.001)                       # router row
    t.add("dispatch", now + 0.001, now + 0.005, replica_id=0)
    t.add("failover", now + 0.005, now + 0.006,
          from_replica=0, to_replica=1)                    # router row
    t.add("dispatch", now + 0.006, now + 0.010, replica_id=1)
    tr.finish(t)
    path = str(tmp_path / "trace.json")
    out = tr.export_chrome_trace(path)
    with open(path) as f:
        assert json.load(f) == out
    xs = [e for e in out["traceEvents"] if e["ph"] == "X"]
    # rows: pid 0 = router, pid replica_id+1 = replica
    assert {e["pid"] for e in xs} == {0, 1, 2}
    names = {e["pid"]: set() for e in xs}
    for e in xs:
        names[e["pid"]].add(e["name"])
        assert e["args"]["trace_id"] == t.trace_id
        assert e["dur"] >= 1.0  # chrome drops 0-width spans
    assert names[0] == {"route", "failover"}
    meta = {e["args"]["name"] for e in out["traceEvents"]
            if e["ph"] == "M"}
    assert meta == {"router", "replica 0", "replica 1"}
    # empty window exports a valid empty trace
    assert tr.export_chrome_trace(window_s=0.0)["traceEvents"] == []


def test_chrome_export_kv_transfer_flow_events():
    """The disagg handoff pin (ISSUE 18): one trace_id draws the whole
    journey — prefill-worker row, a kv_transfer arrow, decode-worker
    row.  The exporter emits a chrome flow-event pair (ph "s" on the
    SOURCE replica's row at t0, ph "f" bp "e" on the DESTINATION
    replica's row at t1) for every kv_transfer span that names both
    endpoints, so the page hop renders as an arrow between rows."""
    tr = ReqTracer()
    t = tr.new_trace("disagg")
    now = time.monotonic()
    t.add("dispatch", now, now + 0.004, replica_id=0)      # prefill row
    t.add("kv_transfer", now + 0.004, now + 0.006,
          from_replica=0, to_replica=1, pages=3, bytes=4096)
    t.add("dispatch", now + 0.006, now + 0.012, replica_id=1)  # decode
    tr.finish(t)
    out = tr.export_chrome_trace()
    evs = out["traceEvents"]
    # the span itself stays a router-row slice (no replica_id attr)
    kv_x = [e for e in evs if e["ph"] == "X"
            and e["name"] == "kv_transfer"]
    assert len(kv_x) == 1 and kv_x[0]["pid"] == 0
    starts = [e for e in evs if e["ph"] == "s"]
    finishes = [e for e in evs if e["ph"] == "f"]
    assert len(starts) == len(finishes) == 1
    s, f = starts[0], finishes[0]
    assert s["name"] == f["name"] == "kv_transfer"
    assert s["id"] == f["id"]                  # one arrow, paired
    assert s["tid"] == f["tid"]
    assert f["bp"] == "e"                      # bind to enclosing slice
    assert s["pid"] == 1                       # replica 0's row
    assert f["pid"] == 2                       # replica 1's row
    assert s["ts"] < f["ts"]
    assert s["args"]["trace_id"] == t.trace_id
    # both endpoint rows exist: one trace spans prefill AND decode rows
    assert {e["pid"] for e in evs if e["ph"] == "X"} == {0, 1, 2}
    # a kv_transfer span missing an endpoint draws no arrow (and does
    # not crash the exporter)
    t2 = tr.new_trace("disagg")
    t2.add("kv_transfer", now, now + 0.001, from_replica=0,
           to_replica=None)
    tr.finish(t2)
    evs2 = tr.export_chrome_trace()["traceEvents"]
    assert len([e for e in evs2 if e["ph"] == "s"]) == 1  # unchanged


# ---------------------------------------------------------------------------
# Engine integration (single-shot serving + decode)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mlp_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("reqtrace_mlp"))
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = fluid.layers.data("x", shape=[8], append_batch_size=True)
        pred = fluid.layers.fc(x, size=4)
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [pred], exe,
                                      main_program=main)
    return d


def test_serving_engine_trace_phases(mlp_dir):
    from paddle_tpu.serving import BucketConfig, ServingEngine

    tracer = ReqTracer(sample_rate=1.0)
    engine = ServingEngine(mlp_dir, {"x": np.zeros(8, np.float32)},
                           buckets=BucketConfig((1, 2)),
                           max_wait_ms=1.0, tracer=tracer)
    engine.start()
    for i in range(4):
        engine.infer({"x": np.full(8, i, np.float32)}, timeout_s=60)
    engine.close()
    traces = tracer.traces()
    assert len(traces) == 4
    for t in traces:
        names = t.span_names()
        assert names == ["queue_wait", "batch_form", "dispatch"], names
        qw, bf, dp = t.spans
        # spans tile the request's lifetime: queue_wait ends exactly
        # where batch_form begins, batch_form where dispatch begins
        assert qw.t1 == bf.t0 and bf.t1 == dp.t0
        assert dp.attrs["batch"] >= 1 and bf.attrs["bucket"] in (1, 2)
        assert t.finished and t.error is None
    summ = tracer.phase_summary()
    assert summ["dispatch"]["count"] >= 1  # batched: <= 4 dispatches
    assert summ["queue_wait"]["count"] == 4


def _tiny_lm():
    from paddle_tpu.models.decoder_lm import DecoderLM

    return DecoderLM(vocab_size=32, n_layer=1, n_head=2, d_model=16,
                     d_inner=32, kv_dtype="float32", seed=3)


def _tiny_engine(tracer=None, num_pages=None):
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    cfg = DecodeConfig(num_slots=2, page_size=4, max_len=32,
                       num_pages=num_pages or 16, prefill_buckets=(8,),
                       decode_chunk=2, kv_dtype="float32")
    return DecodeEngine(_tiny_lm(), cfg, memory_budget_bytes=False,
                        tracer=tracer)


def test_decode_trace_tail_keeps_preemption():
    """sample_rate=0 on a pool sized to force preemption: the ONLY
    kept traces are the preempted ones (tail keep), and they carry the
    join_wait/dispatch span kinds plus the preempt marker."""
    from paddle_tpu.models.decoder_lm import make_prompts

    tracer = ReqTracer(sample_rate=0.0)
    # 2 slots x 8 pages/slot worst case = 16; 9 pages forces eviction
    eng = _tiny_engine(tracer=tracer, num_pages=9).start()
    prompts = make_prompts(4, 32, min_len=3, max_len=6, seed=1)
    futs = [eng.submit(p, max_new_tokens=18, priority=i)
            for i, p in enumerate(prompts)]
    for f in futs:
        f.result(300)
    eng.close()
    assert eng.stats.preemptions >= 1
    kept = tracer.traces()
    assert kept, "preempted traces must survive sample_rate=0"
    for t in kept:
        assert t.keep_reason == "preempt"
        names = t.span_names()
        assert "preempt" in names and "join_wait" in names \
            and "dispatch" in names, names
        # a preempted request re-joins: two join_wait spans
        assert len(t.find("join_wait")) >= 2, names
    # the phase aggregates saw EVERY request, not just the kept ones
    assert tracer.phase_summary()["join_wait"]["count"] >= \
        len(prompts) + len(kept)
    assert tracer.snapshot()["finished"] == len(prompts)


def test_tracing_zero_device_overhead_guard_discipline():
    """The acceptance pin: tracing enabled at sample_rate=0 performs
    the same device work as no tracer at all — equal dispatch counts,
    zero retraces, and the decode executable's lowering is
    byte-identical (spans are host timestamps; nothing reaches the
    traced computation)."""
    from paddle_tpu.models.decoder_lm import make_prompts

    prompts = make_prompts(3, 32, min_len=3, max_len=6, seed=2)

    def run(tracer):
        eng = _tiny_engine(tracer=tracer).start()
        snap = observe.runtime_stats.snapshot()
        futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        outs = [f.result(300).tolist() for f in futs]
        delta = observe.runtime_stats.delta(snap)
        compiles = eng.stats.post_warmup_compiles()
        params_spec, vec, pt, pool_specs = eng._specs()
        text = jax.jit(eng._build_decode_fn()).lower(
            params_spec, vec, vec, vec, vec, pt, pool_specs).as_text()
        eng.close()
        return outs, delta, compiles, text

    outs_off, delta_off, compiles_off, text_off = run(None)
    outs_on, delta_on, compiles_on, text_on = run(
        ReqTracer(sample_rate=0.0))
    assert outs_on == outs_off  # tokens untouched
    assert compiles_on == compiles_off == 0  # zero-compile contract
    assert delta_on["dispatches"] == delta_off["dispatches"]
    assert delta_on["retraces"] == delta_off["retraces"] == 0
    assert text_on == text_off, \
        "tracing changed the lowered step (must be host-side only)"


# ---------------------------------------------------------------------------
# Metrics registry + Prometheus exposition
# ---------------------------------------------------------------------------

def test_prometheus_histogram_bucket_exactness():
    """The exposition contract: cumulative `le` buckets equal the
    LatencyHistogram's bin prefix sums EXACTLY, +Inf equals count,
    sum equals sum_ms — a scrape loses nothing the histogram knew."""
    h = LatencyHistogram()
    samples = [0.004, 0.5, 3.7, 3.75, 50.0, 51.0, 52.0, 9000.0,
               120000.0]
    for v in samples:
        h.record(v)
    buckets = h.cumulative_buckets()
    # independent ground truth from the raw bins
    edges = [h._edge(i) for i in range(h._nbins)]
    for le, cum in buckets:
        expect = sum(1 for v in samples if h._edge(h._bin(v)) <= le)
        assert cum == expect, (le, cum, expect)
    assert buckets[-1][1] == h.count == len(samples)
    assert all(le in edges or le == edges[-1] for le, _ in buckets)
    # the text form carries the same numbers
    fam = histogram("e2e_ms", "test", h, scope="unit")
    reg = MetricsRegistry().register("t", lambda: [fam])
    text = reg.prometheus_text()
    got = re.findall(r'e2e_ms_bucket\{le="([^"]+)",scope="unit"\} (\d+)',
                     text)
    parsed = [(float(le) if le != "+Inf" else float("inf"), int(c))
              for le, c in got]
    assert parsed[:-1] == [(pytest.approx(le), c)
                           for le, c in buckets]
    assert parsed[-1] == (float("inf"), len(samples))
    assert f"e2e_ms_count{{scope=\"unit\"}} {len(samples)}" in text
    m = re.search(r'e2e_ms_sum\{scope="unit"\} ([0-9.e+-]+)', text)
    assert float(m.group(1)) == pytest.approx(h.sum_ms)
    # cumulative counts are monotone non-decreasing (le ascending)
    assert all(parsed[i][1] <= parsed[i + 1][1]
               for i in range(len(parsed) - 1))


def test_registry_families_labels_and_error_isolation():
    reg = MetricsRegistry()
    reg.register("good", lambda: [
        counter("reqs_total", "requests", 7, model="bert",
                bucket='b"8'),
        gauge("depth", "queue depth", 3.5, replica_id=0)])

    def bad():
        raise RuntimeError("collector died")

    reg.register("bad", bad)
    text = reg.prometheus_text()
    # label values escape quotes; samples carry their labels
    assert 'reqs_total{bucket="b\\"8",model="bert"} 7' in text
    assert 'depth{replica_id="0"} 3.5' in text
    # the sick collector is isolated and visible, not fatal
    assert 'observe_collector_up{collector="bad"} 0' in text
    assert 'observe_collector_up{collector="good"} 1' in text
    snap = reg.snapshot()
    assert snap["reqs_total"]["kind"] == "counter"
    assert snap["depth"]["samples"][0]["value"] == 3.5
    # replacement, not accumulation
    reg.register("good", lambda: [gauge("depth", "", 1.0)])
    assert reg.collector_names() == ["bad", "good"]
    with pytest.raises(ValueError):
        MetricFamily("bad name!", "gauge")
    with pytest.raises(ValueError):
        MetricFamily("x", "summary")


def test_serving_stats_and_telemetry_collectors():
    from paddle_tpu.observe.metrics import StepTelemetry
    from paddle_tpu.serving import DecodeStats

    stats = DecodeStats()
    stats.record_submit()
    stats.record_prefill(1, [2.0])
    stats.record_decode(4, 1, 2, 6, 5, 10, 12.0)
    stats.record_done()
    fams = {f.name: f for f in
            serving_stats_collector(stats, scope="fleet")()}
    assert fams["serving_submitted_total"].samples == \
        [({"scope": "fleet"}, 1.0)]
    assert fams["serving_tokens_generated_total"].samples[0][1] == 7.0
    assert fams["serving_post_warmup_compiles"].kind == "gauge"
    assert fams["serving_slot_occupancy"].samples[0][1] == \
        pytest.approx(0.5)
    hist_fam = fams["serving_ttft_ms"]
    assert hist_fam.kind == "histogram"
    assert hist_fam.samples[0][1]["count"] == 1

    tel = StepTelemetry(
        steps=10, loss_last=0.5, loss_mean=0.6, grad_norm_last=1.25,
        grad_norm_mean=1.5, update_norm_last=0.01,
        update_norm_mean=0.02, nonfinite_grad_steps=0,
        nonfinite_loss_steps=0, skipped_update_steps=1,
        loss_scale=1024.0,
        groups={"attn_qkv": {"grad_norm": 0.7, "update_ratio": 1e-3}})
    fams = {f.name: f for f in
            telemetry_collector(lambda: tel, job="t1")()}
    assert fams["training_loss_last"].samples == \
        [({"job": "t1"}, 0.5)]
    assert fams["training_loss_scale"].samples[0][1] == 1024.0
    grp = fams["training_group_grad_norm"].samples
    assert grp == [({"group": "attn_qkv", "job": "t1"}, 0.7)]
    # before the first window: degraded, not broken
    fams0 = {f.name: f for f in telemetry_collector(lambda: None)()}
    assert fams0["training_telemetry_windows"].samples[0][1] == 0

    # gang heartbeat skew adapter (the HealthMonitor.skew() wire form)
    from paddle_tpu.observe.registry import gang_collector

    skew = {"steps": {0: 10, 1: 8}, "rates": {0: 1.0, 1: 0.5},
            "max_lag_steps": 2, "median_rate": 0.75, "slow_ranks": [1]}
    fams = {f.name: f for f in gang_collector(lambda: skew)()}
    assert fams["gang_rank_steps"].samples == \
        [({"rank": 0}, 10.0), ({"rank": 1}, 8.0)]
    assert fams["gang_rank_step_rate"].samples[1] == ({"rank": 1}, 0.5)
    assert fams["gang_max_lag_steps"].samples[0][1] == 2
    assert fams["gang_slow_ranks"].samples[0][1] == 1


def test_metrics_server_endpoint_and_default_snapshot():
    tr = ReqTracer()
    t = tr.new_trace()
    t.add("dispatch", time.monotonic() - 0.001, time.monotonic(),
          replica_id=0)
    tr.finish(t)
    reg = standard_collectors(MetricsRegistry())
    reg.register("reqtrace", tracer_collector(tr))
    srv = MetricsServer(reg, health_fn=lambda: {"state": "ok",
                                                "n": 2}).start()
    try:
        assert srv.host == "127.0.0.1"  # localhost by default
        body = urllib.request.urlopen(
            srv.url + "/metrics", timeout=10).read().decode()
        hz = json.loads(urllib.request.urlopen(
            srv.url + "/healthz", timeout=10).read())
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(srv.url + "/nope", timeout=10)
    finally:
        srv.close()
    assert hz == {"state": "ok", "n": 2}
    subsystems = {ln.split("_")[0] for ln in body.splitlines()
                  if ln and not ln.startswith("#")}
    assert {"runtime", "process", "reqtrace", "memory"} <= subsystems
    assert re.search(r"^reqtrace_kept_total 1$", body, re.M)
    assert re.search(r'^reqtrace_phase_ms_bucket\{le="[^"]+",'
                     r'phase="dispatch"\} 1$', body, re.M)
    # the module-level snapshot over the process-default registry
    snap = observe.metrics_snapshot()
    assert "runtime_dispatches_total" in snap
    assert "process_uptime_seconds" in snap
    # tools/metrics_dump.py parses the same exposition
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "metrics_dump", os.path.join(os.path.dirname(__file__),
                                     "..", "tools", "metrics_dump.py"))
    md = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(md)
    fams = md.parse_exposition(body)
    assert fams["reqtrace_kept_total"]["kind"] == "counter"
    assert fams["reqtrace_phase_ms"]["kind"] == "histogram"


def test_event_kind_registry_enforcement(tmp_path):
    """Unregistered serving_/fleet_/gang_ kinds warn by default and
    raise under strict mode (conftest turns strict on for the suite);
    registering legitimizes a new kind; non-dashboard prefixes are
    never validated."""
    from paddle_tpu.observe import events

    log = observe.RunEventLog(str(tmp_path / "e.jsonl"))
    # conftest set strict: a typo raises before it can rot a dashboard
    with pytest.raises(ValueError, match="not registered"):
        log.event("serving_windw", completed=1)  # the classic typo
    with pytest.raises(ValueError):
        log.event("gang_skeww")
    prev = events.set_strict_kinds(False)
    try:
        with pytest.warns(UserWarning, match="not registered"):
            log.event("fleet_bogus", x=1)
    finally:
        events.set_strict_kinds(prev)
    # registered kinds (incl. the decode stragglers this PR flushed
    # out) pass silently
    for kind in ("serving_window", "serving_decode_preempt",
                 "serving_fleet_failover", "gang_skew",
                 "serving_reload"):
        log.event(kind, ok=True)
    events.register_event_kinds("serving_custom_extension")
    log.event("serving_custom_extension", x=2)
    # non-dashboard prefixes are unvalidated (telemetry, checkpoint..)
    log.event("my_custom_thing", x=3)
    log.close()
    recs = observe.read_events(str(tmp_path / "e.jsonl"))
    kinds = [r["event"] for r in recs]
    assert "serving_windw" not in kinds  # the typo never landed
    assert "serving_custom_extension" in kinds
    assert "serving_decode_preempt" in kinds
