"""Serving-fleet resilience suite (ISSUE 14) — the pinned chaos proofs.

The load-bearing properties, each proven by injecting its fault:

- **decode failover is invisible**: fault-inject one replica
  mid-generation under offered load → every affected request completes
  on a survivor with output TOKEN-IDENTICAL (greedy) to an
  uninterrupted control engine, zero client-visible failures, zero
  post-warmup compiles fleet-wide (the PR 12 preemption proof lifted
  across replica boundaries).
- **hot reload drops nothing**: `fleet.reload()` under sustained load
  rejects zero requests, performs zero recompiles (same-shape assert),
  and responses carry the new model version after the roll.
- **every boundary crossing is structured**: evacuation descriptors,
  retryable replica-failure errors, fleet saturation fast-rejects —
  all ServingError subclasses with `as_dict()`, all evented with
  replica_id stamps.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.core.executor import Executor, scope_guard
from paddle_tpu.models.decoder_lm import DecoderLM, make_prompts
from paddle_tpu.observe import read_events
from paddle_tpu.observe.monitoring import LatencyHistogram
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import (BucketConfig, DecodeConfig, DecodeEngine,
                                DecodeReplicaFailedError, DecodeStats,
                                Fleet, FleetConfig, FleetSaturatedError,
                                ServingEngine, ServingStats,
                                WeightReloadError)

VOCAB = 48
PROMPTS = make_prompts(6, VOCAB, min_len=3, max_len=8, seed=21)
BUDGETS = [14, 12, 16, 11, 14, 12]


def _lm():
    return DecoderLM(vocab_size=VOCAB, n_layer=2, n_head=2, d_model=32,
                     d_inner=64, kv_dtype="float32", seed=7)


def _engine(**kw):
    # one prefill bucket: each engine start is exactly two compiles
    # (decode chunk + prefill), keeping the tier-1 wall cost low
    cfg = DecodeConfig(num_slots=2, page_size=4, max_len=48,
                       num_pages=24, prefill_buckets=(8,),
                       decode_chunk=2, kv_dtype="float32")
    return DecodeEngine(_lm(), cfg, memory_budget_bytes=False, **kw)


@pytest.fixture(scope="module")
def control_tokens():
    """The uninterrupted control: the same requests through one
    unkilled engine — greedy, so any fleet schedule must reproduce
    these tokens exactly."""
    eng = _engine().start()
    outs = [eng.generate(p, max_new_tokens=b, timeout_s=300).tolist()
            for p, b in zip(PROMPTS, BUDGETS)]
    eng.close()
    return outs


@pytest.fixture(autouse=True)
def _clear_chaos():
    yield
    chaos.clear()


# -- the pinned chaos proof -------------------------------------------------

def test_replica_kill_failover_token_parity(control_tokens, tmp_path):
    """Kill one replica mid-generation under offered load: zero
    client-visible failures, every output token-identical to the
    control, committed prefixes verified, zero post-warmup compiles
    fleet-wide, the dead replica ejected.  With tracing on (ISSUE 15),
    the killed request keeps ONE trace_id across both replicas with a
    `failover` span naming the dead replica.  With alerts enabled
    (ISSUE 17), the kill flips the fleet_failover_rate rule to firing,
    the firing transition writes exactly ONE rate-limited flight
    bundle, and the rule resolves once the rate window slides past."""
    from paddle_tpu.observe import ReqTracer

    log_path = str(tmp_path / "fleet_events.jsonl")
    tracer = ReqTracer(sample_rate=1.0)
    engines = [_engine(), _engine()]
    fleet = Fleet(engines, FleetConfig(), log_path=log_path,
                  tracer=tracer).start()
    # pillar 9 rides the chaos proof: default SLO pack, no background
    # thread — the test drives evaluate() with an injected clock so
    # the rate windows are deterministic
    alerts = fleet.enable_alerts(start=False,
                                 flight_dir=str(tmp_path / "flight"),
                                 failover_window_s=30.0)
    assert alerts is fleet.alert_engine and not alerts.running
    alerts.evaluate(now=0.0)
    alerts.evaluate(now=1.0)
    assert alerts.firing() == []  # healthy fleet: nothing fires
    futs = [fleet.submit(p, max_new_tokens=b)
            for p, b in zip(PROMPTS, BUDGETS)]
    # mid-generation: wait until replica 0 has COMMITTED tokens, so at
    # least one failover carries a non-empty prefix to verify
    deadline = time.monotonic() + 60
    while (engines[0].stats.tokens_generated < 2
           and time.monotonic() < deadline):
        time.sleep(0.002)
    chaos.kill_replica(engines[0])
    resps = [f.result(300) for f in futs]
    outs = [r.tokens.tolist() for r in resps]
    snap = fleet.snapshot()
    assert outs == control_tokens, \
        "failover changed generated tokens (greedy identity broke)"
    assert snap["failed"] == 0
    assert snap["failovers"] >= 1, snap
    assert snap["parity_checked"] >= 1 and snap["parity_failed"] == 0
    assert snap["ejects"] == 1
    assert snap["post_warmup_compiles"] == 0, snap
    assert fleet.replicas[0].dead and not fleet.replicas[1].dead
    # requests that failed over say so in their provenance
    assert any(r.failovers >= 1 for r in resps)
    assert all(r.replica_id == 1 for r in resps if r.failovers)

    # ISSUE 15 trace continuity: the killed request's SINGLE trace_id
    # spans both replicas — its spans carry replica_id 0 AND 1, the
    # failover span names the dead replica and the survivor, and the
    # hop chain lands in the response
    killed = next(r for r in resps if r.failovers >= 1)
    assert killed.trace_id is not None
    assert 0 in killed.hops and killed.hops[-1] == 1, killed.hops
    traces = [t for t in tracer.traces()
              if t.trace_id == killed.trace_id]
    assert len(traces) == 1, "one trace_id per logical request"
    t = traces[0]
    assert set(t.replica_ids()) == {0, 1}, t.replica_ids()
    fo = t.find("failover")
    assert fo, t.span_names()
    assert fo[0].attrs["from_replica"] == 0
    assert fo[0].attrs["to_replica"] == 1
    names = t.span_names()
    for phase in ("join_wait", "dispatch", "evacuated", "complete"):
        assert phase in names, (phase, names)
    # chrome export renders the hop across replica rows (router + 2)
    ct = tracer.export_chrome_trace()
    rows = {e["pid"] for e in ct["traceEvents"] if e.get("ph") == "X"
            and e["args"].get("trace_id") == killed.trace_id}
    assert len(rows) >= 3, rows

    # ISSUE 17: the kill must flip the failover-rate rule to firing
    # and write exactly one rate-limited diagnostic bundle
    alerts.evaluate(now=2.0)
    assert "fleet_failover_rate" in alerts.firing(), alerts.state()
    sig = alerts.signals()["fleet_failover_rate"]
    assert sig["firing"] is True and sig["value"] > 0.0
    # the dead replica also trips fleet_replicas_down in the SAME
    # pass — its bundle is rate-limited: exactly one hits disk
    assert "fleet_replicas_down" in alerts.firing()
    rec = fleet.flight_recorder
    assert len(rec.bundles) == 1 and rec.suppressed == 1, \
        rec.snapshot()
    bundle = rec.bundles[0]
    assert os.path.basename(bundle) == \
        "bundle_001_alert_fleet_failover_rate"
    import json as _json

    man = _json.load(open(os.path.join(bundle, "MANIFEST.json")))
    assert man["context"]["rule"] == "fleet_failover_rate"
    assert man["errors"] == {}
    for f_ in ("metrics.json", "alerts.json", "reqtrace.json",
               "events_tail.jsonl", "stacks.txt"):
        assert f_ in man["files"], man["files"]
    cap = _json.load(open(os.path.join(bundle, "metrics.json")))
    assert sum(s["value"] for s in
               cap["fleet_failovers_total"]["samples"]) >= 1
    # the alerts family is on the fleet's /metrics surface
    text = fleet.metrics_registry().prometheus_text()
    assert 'alerts_firing{rule="fleet_failover_rate"' in text
    # still breaching inside the window: no flapping, no new bundle
    alerts.evaluate(now=3.0)
    assert "fleet_failover_rate" in alerts.firing()
    assert len(rec.bundles) == 1
    # recovery: the 30 s rate window slides past the kill → resolved
    alerts.evaluate(now=40.0)
    assert "fleet_failover_rate" not in alerts.firing(), \
        alerts.state()
    assert alerts.signals()["fleet_failover_rate"]["state"] == \
        "inactive"
    fleet.close()

    # satellite: replica_id stamps every engine event in the shared
    # log; the fleet lifecycle + failover events are present
    events = read_events(log_path)
    kinds = [e["event"] for e in events]
    assert "serving_fleet_start" in kinds
    assert "serving_fleet_failover" in kinds
    assert "serving_fleet_eject" in kinds
    replica_events = [e for e in events
                      if e["event"].startswith("serving_decode")]
    assert replica_events, kinds
    assert all("replica_id" in e for e in replica_events)
    assert {e["replica_id"] for e in replica_events} == {0, 1}
    # ISSUE 17: the alert lifecycle and the bundle write are evented
    # into the SAME shared log (registered kinds, strict-mode clean)
    fired = [e for e in events if e["event"] == "alert_firing"]
    assert {e["rule"] for e in fired} >= {"fleet_failover_rate",
                                          "fleet_replicas_down"}
    resolved = [e for e in events if e["event"] == "alert_resolved"]
    assert "fleet_failover_rate" in {e["rule"] for e in resolved}
    flights = [e for e in events if e["event"] == "flight_record"]
    assert len(flights) == 1
    assert flights[0]["reason"] == "alert_fleet_failover_rate"
    assert flights[0]["path"] == bundle


def test_hot_reload_under_load(control_tokens):
    """fleet.reload() during sustained load: zero dropped requests,
    zero recompiles, token parity before/after (same weights), and a
    post-roll response tagged with the new model version."""
    engines = [_engine(), _engine()]
    fleet = Fleet(engines, FleetConfig()).start()
    with tempfile.TemporaryDirectory() as d:
        with scope_guard(engines[0].scope):
            fluid.io.save_sharded(
                Executor(), d,
                main_program=engines[0].model.step["main"])
        futs = [fleet.submit(p, max_new_tokens=b)
                for p, b in zip(PROMPTS, BUDGETS)]
        info = fleet.reload(d)
        outs = [f.result(300).tokens.tolist() for f in futs]
    assert outs == control_tokens, "reload perturbed in-flight tokens"
    assert info["version"] == 1 and info["compiles"] == 0
    assert info["pause_ms_max"] > 0
    snap = fleet.snapshot()
    assert snap["failed"] == 0
    assert snap["reloads"] == 2 and snap["reload_pause_ms"] > 0
    assert snap["post_warmup_compiles"] == 0, snap
    post = fleet.generate(PROMPTS[0], max_new_tokens=4, timeout_s=300)
    assert post.model_version == 1
    assert post.tokens.tolist() == control_tokens[0][:4]
    assert fleet.model_version == 1
    assert all(e.model_version == 1 for e in engines)
    fleet.close()


def test_live_fleet_exporter_scrape_and_dump_cli():
    """The fleet's own /metrics endpoint over HTTP while it serves:
    families of at least four subsystems, the zero-recompile contract
    readable as a gauge, /healthz beside it, and `tools/metrics_dump.py`
    scraping the same URL.  (The registry's text form and the parser
    are tests/test_observe_reqtrace.py's; this is the server the fleet
    starts itself.)"""
    import json
    import re
    import subprocess
    import sys
    import urllib.request

    from paddle_tpu.observe import ReqTracer

    fleet = Fleet([_engine(), _engine()], FleetConfig(),
                  tracer=ReqTracer(sample_rate=1.0)).start()
    try:
        for p in PROMPTS[:2]:
            fleet.generate(p, max_new_tokens=4, timeout_s=300)
        srv = fleet.start_metrics_server()      # 127.0.0.1, any free port
        assert fleet.start_metrics_server() is srv
        body = urllib.request.urlopen(srv.url + "/metrics",
                                      timeout=10).read().decode()
        health = json.loads(urllib.request.urlopen(
            srv.url + "/healthz", timeout=10).read())
        assert health["healthy_replicas"] == 2 and not health["closed"]
        m = re.search(r"^serving_post_warmup_compiles\{[^}]*\} (\d+)$",
                      body, re.M)
        assert m and m.group(1) == "0", body[:400]
        subsystems = {ln.split("_")[0] for ln in body.splitlines()
                      if ln and not ln.startswith("#")}
        present = subsystems & {"serving", "fleet", "runtime", "reqtrace",
                                "process", "memory"}
        assert len(present) >= 4, subsystems
        tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                            "metrics_dump.py")
        dump = subprocess.run(
            [sys.executable, tool, "--url", srv.url + "/metrics",
             "--grep", "fleet_"],
            capture_output=True, text=True, timeout=60)
        assert dump.returncode == 0, dump.stderr
        assert "fleet_failovers_total" in dump.stdout, dump.stdout[:500]
        assert "serving_post_warmup_compiles" not in dump.stdout
    finally:
        fleet.close()


# -- structured evacuation / failure surface --------------------------------

@pytest.mark.slow
def test_evacuate_returns_requeueable_descriptors(control_tokens):
    eng = _engine().start()
    futs = [eng.submit(p, max_new_tokens=b)
            for p, b in zip(PROMPTS[:3], BUDGETS[:3])]
    deadline = time.monotonic() + 60
    while (eng.stats.tokens_generated < 2
           and time.monotonic() < deadline):
        time.sleep(0.002)
    descs = eng.evacuate()
    assert len(descs) == 3
    for f, d, p, b in zip(futs, descs, PROMPTS[:3], BUDGETS[:3]):
        exc = f.exception(timeout=10)
        assert isinstance(exc, DecodeReplicaFailedError)
        wire = exc.as_dict()
        assert wire["error"] == "decode_replica_failed"
        assert wire["retryable"] is True
        assert wire["reason"] == "evacuated"
        assert wire["descriptor"]["prompt"] == [int(t) for t in p]
        assert wire["descriptor"]["max_new_tokens"] == b
        assert (wire["descriptor"]["committed_tokens"]
                == len(wire["descriptor"]["generated"]))
    assert eng.stats.snapshot()["evacuations"] == 3
    # the engine keeps serving, and a requeued descriptor regenerates
    # token-identically, reproducing the committed prefix
    d0 = descs[0]
    regen = eng.generate(np.asarray(d0["prompt"]),
                         max_new_tokens=d0["max_new_tokens"],
                         timeout_s=300).tolist()
    assert regen == control_tokens[0]
    assert regen[:d0["committed_tokens"]] == d0["generated"]
    eng.close()


@pytest.mark.slow
def test_scheduler_death_resolves_futures_structured():
    eng = _engine()
    eng.set_replica_id(7)
    eng.start()
    futs = [eng.submit(p, max_new_tokens=b)
            for p, b in zip(PROMPTS[:2], BUDGETS[:2])]
    chaos.kill_replica(eng)
    for f in futs:
        exc = f.exception(timeout=60)
        assert isinstance(exc, DecodeReplicaFailedError)
        wire = exc.as_dict()
        assert wire["retryable"] is True
        assert wire["reason"] == "scheduler_failed"
        assert "ChaosKilled" in wire["cause"]
        assert wire["replica_id"] == 7
        assert wire["descriptor"]["prompt"]
    # a dead scheduler stops accepting with the structured closed error
    from paddle_tpu.serving import ServingClosedError

    with pytest.raises(ServingClosedError):
        eng.submit(PROMPTS[0], max_new_tokens=2)
    eng.close()


@pytest.mark.slow
def test_reload_shape_mismatch_rejected():
    eng = _engine().start()
    before = eng.generate(PROMPTS[0], max_new_tokens=3,
                          timeout_s=300).tolist()
    bad = {n: np.zeros((3, 3), np.float32) for n in eng._params}
    with pytest.raises(WeightReloadError) as e:
        eng.reload(bad)
    wire = e.value.as_dict()
    assert wire["error"] == "weight_reload" and wire["mismatched"]
    assert eng.model_version == 0  # old weights keep serving
    assert eng.generate(PROMPTS[0], max_new_tokens=3,
                        timeout_s=300).tolist() == before
    # refusing to swap under a live generation is also structured
    fut = eng.submit(PROMPTS[2], max_new_tokens=30)
    good = {n: np.asarray(v) for n, v in eng._params.items()}
    with pytest.raises(WeightReloadError) as e2:
        eng.reload(good)
    assert "evacuate" in str(e2.value)
    fut.result(300)
    eng.close()


# -- routing: saturation + hedging ------------------------------------------

@pytest.mark.slow
def test_fleet_saturated_fast_reject_structured():
    def tiny():
        cfg = DecodeConfig(num_slots=1, page_size=4, max_len=48,
                           num_pages=12, prefill_buckets=(8, 16),
                           decode_chunk=2, kv_dtype="float32")
        return DecodeEngine(_lm(), cfg, memory_budget_bytes=False,
                            queue_capacity=1)

    engines = [tiny(), tiny()]
    fleet = Fleet(engines, FleetConfig()).start()
    futs = [fleet.submit(p, max_new_tokens=20) for p in PROMPTS[:2]]
    with pytest.raises(FleetSaturatedError) as e:
        fleet.submit(PROMPTS[2], max_new_tokens=20)
    wire = e.value.as_dict()
    assert wire["error"] == "fleet_saturated"
    assert {r["reject"] for r in wire["rejects"]} == {"queue_full"}
    assert len(wire["replicas"]) == 2
    assert fleet.stats.snapshot()["saturated"] == 1
    for f in futs:  # accepted work still completes
        assert len(f.result(300).tokens) == 20
    fleet.close()


@pytest.mark.slow
def test_hedging_beats_straggler_replica(control_tokens):
    from paddle_tpu.observe import ReqTracer

    tracer = ReqTracer(sample_rate=1.0)
    engines = [_engine(), _engine()]
    fleet = Fleet(engines, FleetConfig(hedge_after_ms=100),
                  tracer=tracer).start()
    # replica 0 (first pick: least-loaded tie breaks on id) stalls for
    # 2 s; the hedge duplicate on replica 1 must win long before that
    chaos.delay_replica(engines[0], 2.0)
    t0 = time.monotonic()
    resp = fleet.generate(PROMPTS[0], max_new_tokens=4, timeout_s=300)
    elapsed = time.monotonic() - t0
    assert resp.tokens.tolist() == control_tokens[0][:4]
    assert resp.replica_id == 1
    assert elapsed < 1.9, f"hedge did not beat the straggler: {elapsed}"
    snap = fleet.stats.snapshot()
    assert snap["hedges"] >= 1 and snap["hedge_wins"] >= 1
    fleet.close()  # drains: the straggler attempt resolves before this
    #                returns, landing the loser's `abandoned` marker
    # ISSUE 15: the hedged request is ONE trace — the hedge fires, the
    # winner completes on replica 1, and the loser (delayed replica 0)
    # is marked abandoned when its late work surfaces
    t = tracer.trace(resp.trace_id)
    assert t is not None and resp.hedged
    assert t.has("hedge"), t.span_names()
    complete = t.find("complete")
    assert complete and complete[0].attrs["replica_id"] == 1
    abandoned = t.find("abandoned")
    assert abandoned, t.span_names()
    assert abandoned[0].attrs["replica_id"] == 0


# -- cross-replica stats aggregation ----------------------------------------

def test_decode_stats_merge_sums_and_rejects_mismatch():
    a, b = DecodeStats(), DecodeStats()
    a.record_submit()
    b.record_submit()
    b.record_submit()
    a.record_prefill(2, [1.0, 2.0])
    b.record_prefill(1, [3.0])
    a.record_decode(4, 2, 2, 6, 5, 10, 12.0)
    b.record_decode(2, 1, 2, 2, 8, 10, 4.0)
    a.record_preemption()
    b.record_reload(7.5)
    a.merge(b)
    s = a.snapshot()
    assert s["submitted"] == 3
    assert s["prefill_joins"] == 3
    assert s["tokens_generated"] == (2 + 6) + (1 + 2)
    assert s["ttft_ms"]["count"] == 3
    assert s["tpot_ms"]["count"] == 2
    assert s["peak_pages_in_use"] == 8
    assert s["reloads"] == 1 and s["reload_pause_ms"] == 7.5
    # exact weighted occupancy: (2*4 + 1*2) / (2*4 + 2*2)
    assert s["slot_occupancy"] == round(10 / 12, 4)
    # config mismatches are rejected, not silently mis-merged
    with pytest.raises(TypeError):
        ServingStats().merge(DecodeStats())
    odd = DecodeStats()
    odd.ttft_ms = LatencyHistogram(bins_per_decade=10)
    with pytest.raises(ValueError):
        DecodeStats().merge(odd)


def test_serving_stats_merge():
    a, b = ServingStats(), ServingStats()
    for s_ in (a, b):
        s_.record_submit(3)
        s_.record_batch(2, 4, 8.0, 16.0, 5.0)
        s_.record_done(11.0)
    b.record_shed()
    b.record_reload(3.25)
    a.merge(b)
    s = a.snapshot()
    assert s["submitted"] == 2 and s["completed"] == 2
    assert s["shed"] == 1 and s["batches"] == 2
    assert s["reloads"] == 1 and s["reload_pause_ms"] == 3.25
    assert s["e2e_ms"]["count"] == 2 and s["exec_ms"]["count"] == 2
    assert s["batch_occupancy"] == round(4 / 8, 4)


# -- the serving (single-shot) fleet kind -----------------------------------

@pytest.fixture(scope="module")
def mlp_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("fleet_mlp"))
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = fluid.layers.data("x", shape=[16], append_batch_size=True)
        h = fluid.layers.fc(x, size=32, act="relu")
        pred = fluid.layers.fc(h, size=4)
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [pred], exe,
                                      main_program=main)
    return d


@pytest.mark.slow
def test_serving_fleet_failover_and_reload(mlp_dir):
    """The single-shot kind: a killed dispatch fails over to the other
    replica (same answer), and a rolling reload swaps the live
    predictor params with zero recompiles and a version tag."""
    rng = np.random.RandomState(3)
    xs = rng.rand(8, 16).astype(np.float32)
    ref = fluid.Predictor(mlp_dir)
    refs = [ref.run({"x": xs[i:i + 1]})[0][0] for i in range(8)]

    def mk():
        return ServingEngine(mlp_dir, {"x": np.zeros(16, np.float32)},
                             buckets=BucketConfig((1, 2, 4)),
                             max_wait_ms=2.0)

    engines = [mk(), mk()]
    fleet = Fleet(engines, FleetConfig()).start()
    chaos.kill_replica(engines[0])  # next dispatch on 0 fails once
    resps = [fleet.infer({"x": xs[i]}, timeout_s=120) for i in range(8)]
    for i, r in enumerate(resps):
        np.testing.assert_allclose(r.outputs[0], refs[i], rtol=1e-5,
                                   atol=1e-6)
    snap = fleet.snapshot()
    assert snap["failed"] == 0 and snap["failovers"] >= 1
    assert snap["post_warmup_compiles"] == 0, snap
    # neither replica died (a failed dispatch is transient): both route
    assert all(not h.dead for h in fleet.replicas)

    info = fleet.reload(
        {n: np.asarray(v)
         for n, v in engines[0].predictor._params.items()})
    assert info["version"] == 1 and info["compiles"] == 0
    r = fleet.infer({"x": xs[0]}, timeout_s=120)
    assert r.model_version == 1
    np.testing.assert_allclose(r.outputs[0], refs[0], rtol=1e-5,
                               atol=1e-6)
    assert fleet.snapshot()["post_warmup_compiles"] == 0
    fleet.close()
