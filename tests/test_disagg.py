"""Disaggregated prefill/decode serving suite (ISSUE 18) — the pinned
phase-specialization proofs (docs/SERVING.md §disagg).

The load-bearing properties, each proven directly:

- **the handoff is invisible**: a prompt prefilled on a prefill worker
  and continued on a decode worker (KV-page export → fixed-shape
  import scatter) produces output TOKEN-IDENTICAL (greedy) to one
  unified engine, with zero post-warmup compiles fleet-wide — the
  import path never recompiles the decode executable.
- **chaos kill of EITHER worker kind is invisible**: a decode-worker
  death mid-generation re-prefills on a survivor token-identically
  (the PR 14 parity contract lifted across the phase hop); a
  prefill-worker death requeues the raw prompt.  Zero client-visible
  failures either way.
- **scaling never rejects and never recompiles**: add_worker warms the
  newcomer while traffic flows and re-opens the fleet-wide
  zero-compile window; the Autoscaler's policy is deterministic under
  an injectable clock + scripted signals.
- **the import op is exact**: a pool→rows→pool round-trip through a
  DIFFERENT page table reproduces the committed rows bitwise.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.models.decoder_lm import DecoderLM, make_prompts
from paddle_tpu.observe import ReqTracer, RunEventLog, read_events
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import (Autoscaler, DecodeConfig, DecodeEngine,
                                DisaggFleet)
from paddle_tpu.serving.disagg import DECODE, PREFILL

VOCAB = 48
PROMPTS = make_prompts(6, VOCAB, min_len=3, max_len=8, seed=21)
BUDGETS = [10, 8, 12, 7, 10, 9]


def _lm():
    return DecoderLM(vocab_size=VOCAB, n_layer=2, n_head=2, d_model=32,
                     d_inner=64, kv_dtype="float32", seed=7)


def _engine(role="unified", **kw):
    # one prefill bucket: each engine start stays a handful of
    # compiles (decode chunk + prefill [+ export/import per role]),
    # keeping the tier-1 wall cost low
    cfg = DecodeConfig(num_slots=2, page_size=4, max_len=48,
                       num_pages=24, prefill_buckets=(8,),
                       decode_chunk=2, kv_dtype="float32")
    return DecodeEngine(_lm(), cfg, role=role,
                        memory_budget_bytes=False, **kw)


@pytest.fixture(scope="module")
def control_tokens():
    """The uninterrupted control: the same requests through one
    unified engine — greedy, so any disagg schedule (including across
    chaos kills and the KV handoff) must reproduce these exactly."""
    eng = _engine().start()
    outs = [eng.generate(p, max_new_tokens=b, timeout_s=300).tolist()
            for p, b in zip(PROMPTS, BUDGETS)]
    eng.close()
    return outs


@pytest.fixture(autouse=True)
def _clear_chaos():
    yield
    chaos.clear()


def _kill_mid_generation(engine):
    """Kill a decode worker once it has COMMITTED tokens, so that its
    sessions carry a prefix to verify."""
    t0 = time.monotonic()
    while engine.stats.tokens_generated < 2 \
            and time.monotonic() - t0 < 60:
        time.sleep(0.002)
    chaos.kill_replica(engine)


def _assert_parity(outs, control):
    for i, (r, c) in enumerate(zip(outs, control)):
        assert list(r.tokens) == list(c), \
            (i, list(r.tokens), list(c), r.hops)


def test_handoff_token_parity_zero_recompiles(control_tokens):
    """The tentpole contract: 1 prefill + 1 decode worker reproduce
    the unified engine bit-for-bit, every request crosses exactly one
    KV-page handoff, and the fleet performs zero post-warmup
    compiles."""
    tracer = ReqTracer(sample_rate=1.0)
    fleet = DisaggFleet([_engine("prefill")], [_engine("decode")],
                        tracer=tracer).start()
    futs = [fleet.submit(p, max_new_tokens=b)
            for p, b in zip(PROMPTS, BUDGETS)]
    outs = [f.result(300) for f in futs]
    snap = fleet.snapshot()
    _assert_parity(outs, control_tokens)
    assert snap["failed"] == 0, snap
    assert snap["handoffs"] == len(PROMPTS), snap
    assert snap["pages_transferred"] > 0
    assert snap["bytes_transferred"] > 0
    assert snap["post_warmup_compiles"] == 0, snap
    # joint TTFT clocked once per request at the router
    assert snap["ttft_ms"]["count"] == len(PROMPTS)
    # provenance: prefill hop then decode hop, phases distinct
    for r in outs:
        assert len(r.hops) == 2, r.hops
        assert r.hops[0] in {h.replica_id for h in fleet.prefill}
        assert r.hops[1] in {h.replica_id for h in fleet.decode}
    # one trace draws the whole journey: prefill-side spans, the
    # kv_transfer hop, then decode-side spans
    tr = tracer.trace(outs[0].trace_id)
    names = tr.span_names()
    assert "kv_transfer" in names, names
    assert names.index("kv_transfer") > names.index("export")
    fleet.close()


def test_decode_worker_kill_token_parity(control_tokens):
    """Decode-worker death mid-generation: its sessions re-prefill on
    the surviving decode worker (via a fresh prefill hop) and finish
    token-identically — zero client-visible failures, zero
    recompiles."""
    fleet = DisaggFleet([_engine("prefill")],
                        [_engine("decode"), _engine("decode")]).start()
    victim = fleet.decode[0].engine
    futs = [fleet.submit(p, max_new_tokens=b)
            for p, b in zip(PROMPTS, BUDGETS)]
    _kill_mid_generation(victim)
    outs = [f.result(300) for f in futs]
    snap = fleet.snapshot()
    _assert_parity(outs, control_tokens)
    assert snap["failed"] == 0, snap
    assert snap["decode_failovers"] >= 1, snap
    assert snap["parity_failed"] == 0, snap
    assert snap["post_warmup_compiles"] == 0, snap
    # the failover is visible in provenance, not in the tokens
    assert any(r.failovers > 0 for r in outs)
    fleet.close()


def test_prefill_worker_kill_zero_client_failures(control_tokens):
    """Prefill-worker death: queued prompts requeue RAW on the
    surviving prefill worker (no pages exist yet to salvage) — zero
    client-visible failures, token parity, zero recompiles."""
    fleet = DisaggFleet([_engine("prefill"), _engine("prefill")],
                        [_engine("decode")]).start()
    victim = fleet.prefill[0].engine
    chaos.arm(f"replica:{victim.replica_id}:kill", times=1)
    futs = [fleet.submit(p, max_new_tokens=b)
            for p, b in zip(PROMPTS, BUDGETS)]
    outs = [f.result(300) for f in futs]
    snap = fleet.snapshot()
    _assert_parity(outs, control_tokens)
    assert snap["failed"] == 0, snap
    assert snap["prefill_failovers"] >= 1, snap
    assert snap["post_warmup_compiles"] == 0, snap
    fleet.close()


def test_one_worker_of_each_kind_killed_and_one_trace_draws_the_handoff(
        control_tokens):
    """Two prefill + two decode workers, ONE of each kind killed in the
    same stream: zero client-visible failures, every output the unified
    control's, zero recompiles (the two tests above kill one kind each).
    And the chrome export of a LIVE fleet's trace: one trace_id draws
    the router's row, a prefill worker's row, a paired kv_transfer
    arrow and the decode worker's row (the exporter's arrows alone, on
    a hand-made trace, are tests/test_observe_reqtrace.py's)."""
    tracer = ReqTracer(sample_rate=1.0)
    fleet = DisaggFleet([_engine("prefill"), _engine("prefill")],
                        [_engine("decode"), _engine("decode")],
                        tracer=tracer).start()
    pf_victim = fleet.prefill[0].engine
    dec_victim = fleet.decode[0].engine
    chaos.arm(f"replica:{pf_victim.replica_id}:kill", times=1)
    futs = [fleet.submit(p, max_new_tokens=b)
            for p, b in zip(PROMPTS, BUDGETS)]
    _kill_mid_generation(dec_victim)
    outs = [f.result(300) for f in futs]
    snap = fleet.snapshot()
    _assert_parity(outs, control_tokens)
    assert snap["failed"] == 0, snap
    assert snap["prefill_failovers"] >= 1, snap
    assert snap["decode_failovers"] >= 1, snap
    assert snap["parity_failed"] == 0, snap
    assert snap["post_warmup_compiles"] == 0, snap
    assert snap["handoffs"] >= len(PROMPTS), snap

    r0 = outs[0]
    pf_ids = {h.replica_id for h in fleet.prefill}
    dec_ids = {h.replica_id for h in fleet.decode}
    assert r0.hops[0] in pf_ids and r0.hops[-1] in dec_ids, r0.hops
    events = tracer.export_chrome_trace()["traceEvents"]
    mine = [e for e in events
            if e.get("args", {}).get("trace_id") == r0.trace_id]
    rows = {e["pid"] for e in mine if e.get("ph") == "X"}
    # a replica's row is its id + 1; row 0 is the router's
    assert rows >= {0, r0.hops[0] + 1, r0.hops[-1] + 1}, rows
    arrows = {}
    for e in mine:
        if e["name"] == "kv_transfer" and e.get("ph") in ("s", "f"):
            arrows.setdefault(e["id"], []).append(e)
    assert arrows, mine
    assert all(sorted(x["ph"] for x in pair) == ["f", "s"]
               for pair in arrows.values()), arrows
    # the LAST arrow leaves a prefill worker's row and lands on the row
    # of the decode worker that served the request
    last = max(arrows.values(), key=lambda pair: min(x["ts"] for x in pair))
    src = next(e for e in last if e["ph"] == "s")
    dst = next(e for e in last if e["ph"] == "f")
    assert src["pid"] - 1 in pf_ids and dst["pid"] == r0.hops[-1] + 1, \
        (src["pid"], dst["pid"], r0.hops)
    fleet.close()


def test_scale_up_down_zero_recompiles(control_tokens):
    """add_worker (the Autoscaler's zero-reject path) warms a newcomer
    mid-traffic and re-opens the fleet-wide zero-compile window;
    remove_worker retires it invisibly; the last worker of a phase is
    protected."""
    fleet = DisaggFleet([_engine("prefill")], [_engine("decode")],
                        decode_factory=lambda: _engine("decode")
                        ).start()
    half = len(PROMPTS) // 2
    futs = [fleet.submit(p, max_new_tokens=b)
            for p, b in zip(PROMPTS[:half], BUDGETS[:half])]
    h = fleet.add_worker(DECODE)
    assert h.phase == DECODE
    futs += [fleet.submit(p, max_new_tokens=b)
             for p, b in zip(PROMPTS[half:], BUDGETS[half:])]
    outs = [f.result(300) for f in futs]
    snap = fleet.snapshot()
    _assert_parity(outs, control_tokens)
    assert snap["failed"] == 0, snap
    assert snap["scale_ups"] == 1
    # the newcomer's warmup compiles must NOT count against the fleet
    assert snap["post_warmup_compiles"] == 0, snap
    rid = fleet.remove_worker(DECODE)
    assert rid == h.replica_id  # newest live one
    assert fleet.snapshot()["scale_downs"] == 1
    with pytest.raises(ValueError):
        fleet.remove_worker(DECODE)     # last live decode worker
    with pytest.raises(ValueError):
        fleet.add_worker(PREFILL)       # no prefill_factory given
    fleet.close()


class _FakeFleet:
    """Duck-typed DisaggFleet for deterministic Autoscaler policy
    tests — no engines, no compiles, just worker-count bookkeeping."""

    def __init__(self):
        self.counts = {PREFILL: 1, DECODE: 1}
        self._next = 2
        self._event_log = None
        self.calls = []

    def live_workers(self, phase):
        return self.counts[phase]

    def add_worker(self, phase):
        self.counts[phase] += 1
        self._next += 1
        self.calls.append(("up", phase))
        return type("H", (), {"replica_id": self._next - 1})()

    def remove_worker(self, phase):
        if self.counts[phase] <= 1:
            raise ValueError("last worker")
        self.counts[phase] -= 1
        self.calls.append(("down", phase))
        return self._next - 1


def test_autoscaler_deterministic_scripted_load(tmp_path):
    """The policy under an injectable clock + scripted signals:
    firing scales up (bounded by max_workers + cooldown), sustained
    quiet scales down (bounded by min_workers), every decision is
    returned AND evented."""
    log = RunEventLog(str(tmp_path / "scale.jsonl"))
    fleet = _FakeFleet()
    sc = Autoscaler(fleet, None, max_workers={PREFILL: 2, DECODE: 3},
                    cooldown_s=10.0, quiet_s=30.0, event_log=log)
    fire = {"disagg_prefill_wait_p99": {"firing": True, "value": 1500.0}}
    calm = {}

    # t=0: prefill rule firing -> scale up once
    d = sc.evaluate(now=0.0, signals=fire)
    assert [x["action"] for x in d] == ["up"]
    assert d[0]["phase"] == PREFILL and d[0]["value"] == 1500.0
    assert fleet.counts[PREFILL] == 2
    # t=5: still firing but inside the cooldown -> no action
    assert sc.evaluate(now=5.0, signals=fire) == []
    # t=12: cooled, but already at max_workers -> no action
    assert sc.evaluate(now=12.0, signals=fire) == []
    assert fleet.counts[PREFILL] == 2
    # quiet starts at t=20; t=45 is only 25s quiet -> hold
    assert sc.evaluate(now=20.0, signals=calm) == []
    assert sc.evaluate(now=45.0, signals=calm) == []
    # t=55: 35s quiet and cooled -> scale down (decode holds: at min)
    d = sc.evaluate(now=55.0, signals=calm)
    assert [x["action"] for x in d] == ["down"]
    assert d[0]["phase"] == PREFILL
    assert fleet.counts == {PREFILL: 1, DECODE: 1}
    # both phases at min_workers -> quiet forever changes nothing
    assert sc.evaluate(now=500.0, signals=calm) == []
    assert [x["action"] for x in sc.decisions] == ["up", "down"]
    log.close()
    kinds = [e.get("event")
             for e in read_events(str(tmp_path / "scale.jsonl"))]
    assert kinds.count("autoscale_up") == 1
    assert kinds.count("autoscale_down") == 1


def test_paged_import_rows_roundtrip():
    """Op-level exactness: rows imported into pool A, gathered back
    out, imported into pool B through a DIFFERENT page table, and
    gathered again reproduce the committed rows bitwise; rows past
    NumValid never land."""
    from paddle_tpu.ops.paged_kv import paged_import_rows

    rng = np.random.RandomState(3)
    n_pages, page, c, maxp = 9, 4, 6, 2
    t_cap = maxp * page
    rows = jnp.asarray(rng.randn(t_cap, c).astype(np.float32))
    nv = 6                               # committed rows; 2 are garbage
    pt_a = jnp.asarray(np.array([2, 5], np.int32))
    pt_b = jnp.asarray(np.array([7, 1], np.int32))
    poison = jnp.full((n_pages, page, c), -99.0, jnp.float32)

    pool_a = paged_import_rows(poison, rows, pt_a, jnp.int32(nv))
    got_a = np.asarray(pool_a[pt_a]).reshape(t_cap, c)
    np.testing.assert_array_equal(got_a[:nv], np.asarray(rows)[:nv])
    # positions past NumValid dropped: the poison survives
    assert np.all(got_a[nv:] == -99.0)

    pool_b = paged_import_rows(poison, jnp.asarray(got_a), pt_b,
                               jnp.int32(nv))
    got_b = np.asarray(pool_b[pt_b]).reshape(t_cap, c)
    np.testing.assert_array_equal(got_b[:nv], np.asarray(rows)[:nv])
    # pages outside either table untouched
    untouched = sorted(set(range(n_pages))
                       - set(np.asarray(pt_b).tolist()))
    assert np.all(np.asarray(pool_b)[untouched] == -99.0)


def test_role_and_geometry_validation():
    """Misconfiguration fails loudly at construction: wrong roles,
    mismatched KV geometry (would recompile the fixed-shape import),
    and client entry through the wrong phase door."""
    pf, dec = _engine("prefill"), _engine("decode")
    with pytest.raises(ValueError, match="role"):
        DisaggFleet([dec], [dec])
    with pytest.raises(ValueError, match="role"):
        DisaggFleet([pf], [_engine("unified")])
    other = DecodeEngine(
        _lm(), DecodeConfig(num_slots=2, page_size=8, max_len=48,
                            prefill_buckets=(8,), decode_chunk=2,
                            kv_dtype="float32"),
        role="decode", memory_budget_bytes=False)
    with pytest.raises(ValueError, match="geometry"):
        DisaggFleet([pf], [other])
    with pytest.raises(ValueError):
        DisaggFleet([pf], [])
    # a decode-role engine only admits via import_handoff
    with pytest.raises(ValueError, match="import_handoff"):
        dec.submit(PROMPTS[0], max_new_tokens=4)
    # a prefill-role engine rejects direct handoff import
    with pytest.raises(ValueError):
        pf.import_handoff({"kind": "handoff"})
