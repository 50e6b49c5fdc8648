#!/bin/sh
# CI entry (reference analog: paddle/scripts/paddle_build.sh).
# Runs the full gate: native build, test suite on the virtual 8-device
# CPU mesh, API-stability diff, multichip dryrun compile check.
# Everything here runs on the CPU; bench.py refuses to run without a
# chip, and the chip is reached only by `python chip_smoke.py` through
# the chip tool (.claude/skills/verify/SKILL.md).
set -e
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu

echo "== native components =="
sh paddle_tpu/native/build.sh
sh paddle_tpu/native/build_demo.sh

echo "== tests (virtual 8-device CPU mesh) =="
python -m pytest tests/ -q

echo "== API stability =="
python tools/diff_api.py

echo "== multichip dryrun (8 virtual devices) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"

echo "== memory observability smoke (cpu) =="
# ISSUE 6 tentpole: the fit planner's probe-extrapolated peak must land
# within its recorded tolerance (PLAN_FIT_REL_TOL) of the real
# buffer-assignment measurement on this backend, and the serving
# bucket-ladder validation must reject an impossible bucket BEFORE
# compiling the ladder (docs/OBSERVE.md memory pillar)
python - <<'EOF'
import tempfile
import numpy as np
import jax

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.observe.memory import PLAN_FIT_REL_TOL, compiled_peak_bytes
from paddle_tpu.serving import (BucketConfig, BucketMemoryError,
                                ServingEngine)

main, startup = fluid.Program(), fluid.Program()
scope = fluid.Scope()
with fluid.program_guard(main, startup), fluid.scope_guard(scope):
    x = layers.data(name="x", shape=[32], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    pred = layers.fc(layers.fc(x, size=64, act="relu"), size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor()
    exe.run(startup)
    cand = {"x": jax.ShapeDtypeStruct((64, 32), "float32"),
            "y": jax.ShapeDtypeStruct((64, 1), "float32")}
    plan = observe.plan_fit(main, cand, fetch_list=[loss], exe=exe)
    comp = exe.compiled_step(
        main, feed={"x": np.zeros((64, 32), "f4"),
                    "y": np.zeros((64, 1), "f4")}, fetch_list=[loss])
    actual = compiled_peak_bytes(comp)
    assert actual, "backend exposed no memory analysis"
    rel = abs(plan["predicted_peak_bytes"] - actual) / actual
    assert rel <= PLAN_FIT_REL_TOL, \
        f"plan_fit off by {rel:.1%} (> {PLAN_FIT_REL_TOL:.0%}): " \
        f"{plan['predicted_peak_bytes']} vs {actual}"

# impossible bucket -> structured rejection before the ladder compiles
d = tempfile.mkdtemp()
main2, startup2 = fluid.Program(), fluid.Program()
scope2 = fluid.Scope()
with fluid.program_guard(main2, startup2), fluid.scope_guard(scope2):
    xi = layers.data("x", shape=[16], append_batch_size=True)
    pi = layers.fc(layers.fc(xi, size=32, act="relu"), size=4)
    exe2 = fluid.Executor(); exe2.run(startup2)
    fluid.io.save_inference_model(d, ["x"], [pi], exe2,
                                  main_program=main2)
try:
    ServingEngine(d, {"x": np.zeros(16, np.float32)},
                  buckets=BucketConfig((1, 2, 4, 8)),
                  memory_budget_bytes=4096).start()
    raise AssertionError("impossible bucket was not rejected")
except BucketMemoryError as e:
    bad = e.as_dict()["offending_buckets"]
    assert bad and bad[-1]["batch_size"] == 8, bad
print("memory smoke OK:",
      {"predicted": plan["predicted_peak_bytes"], "measured": actual,
       "rel_err": round(rel, 4), "tol": PLAN_FIT_REL_TOL,
       "ladder_rejected": [b["batch_size"] for b in bad]})
EOF

echo "== fused recurrence kernel parity (cpu, interpret mode) =="
# ISSUE 5: the kernel's interpret-mode parity suite (fwd + grad vs the
# scan reference) is run explicitly so the --pallas-rnn path can't rot.
python -m pytest tests/test_pallas_recurrence.py -q

echo "== head-major layout smoke (cpu) =="
# ISSUE 8: the longctx-stack program built head-major (flash self+cross
# Pallas + fused-CE) must carry ZERO transpose traffic at the flash
# kernel boundaries.  Three chip-free proofs, strongest first:
# (1) the TPU-lowered (Mosaic, not interpreter) flash fwd+bwd module
#     contains zero stablehlo.transpose; (2) the built program contains
#     zero `transpose` fluid ops (the baseline layout has them at every
#     kernel boundary); (3) observe.cost's boundary audit over the
#     compiled step reports no copy/transpose adjoining a flash custom
#     call (vacuous on the interpreting CPU backend — the same call is
#     the on-chip check — but the plumbing is exercised end-to-end).
python - <<'EOF'
import numpy as np
import jax, jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import transformer
from paddle_tpu.observe import cost as obs_cost
import paddle_tpu.ops.pallas.flash_attention as fa
from paddle_tpu.ops.pallas import force_mosaic_lowering

# (1) Mosaic-lowered head-major flash fwd+bwd: zero transposes
import jax.export
n, h, t, d = 1, 2, 256, 128
q = jnp.zeros((n, t, h * d), jnp.float32)
b = jnp.zeros((n, 1, 1, t), jnp.float32)
def step(q, k, v, b):
    loss = lambda q, k, v, b: jnp.sum(fa.pallas_flash_attention(
        q, k, v, bias=b, causal=True, layout="nthd", n_head=h) ** 2)
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(q, k, v, b)
with force_mosaic_lowering():
    mlir = jax.export.export(jax.jit(step), platforms=["tpu"])(
        q, q, q, b).mlir_module()
assert mlir.count("tpu_custom_call") >= 3, "Mosaic kernels missing"
assert "stablehlo.transpose" not in mlir, \
    "transpose at a flash kernel boundary in the TPU lowering"

# (2)+(3) the longctx stack (flash self+cross Pallas + fused-CE) built
# head-major at a CPU-sized shape
main, startup = fluid.Program(), fluid.Program()
scope = fluid.Scope()
with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
        fluid.unique_name.guard():
    m = transformer.build_model(
        src_vocab_size=128, trg_vocab_size=128, max_length=128,
        n_layer=2, n_head=4, d_model=64, d_inner_hid=128, dropout=0.1,
        use_flash=True, flash_pallas=True, flash_cross=True,
        use_fused_ce=True, head_major=True)
    n_transpose = sum(1 for op in main.global_block().ops
                      if op.type == "transpose")
    assert n_transpose == 0, f"{n_transpose} transpose ops in the " \
        "head-major longctx program"
    exe = fluid.Executor()
    exe.run(startup)
    feed = {k: jnp.asarray(v) for k, v in
            transformer.make_fake_batch(2, 128, 120, 120).items()}
    compiled = exe.compiled_step(main, feed=feed, fetch_list=[m["loss"]])
    proto = obs_cost.compiled_hlo_proto(compiled)
offenders = obs_cost.flash_boundary_layout(proto)
assert offenders == [], f"layout instrs at flash boundaries: {offenders}"
assert obs_cost.copyish_instructions(proto, op_types={"transpose"}) == []
share = obs_cost.layout_byte_share(proto)
assert 0.0 <= share < 1.0
print("head-major layout smoke OK:",
      {"mosaic_custom_calls": mlir.count("tpu_custom_call"),
       "program_transpose_ops": n_transpose,
       "boundary_offenders": len(offenders),
       "layout_share": round(share, 4)})
EOF

echo "== serving engine smoke (cpu) =="
# the production-serving contract end-to-end: engine start (bucket
# warmup) -> concurrent requests -> drain, with ZERO XLA compiles
# after warmup and every answer matching a per-request reference
# (docs/SERVING.md)
python - <<'EOF'
import tempfile, threading
import numpy as np
import jax

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.observe import runtime_stats
from paddle_tpu.serving import BucketConfig, ServingEngine

rng = np.random.RandomState(0)
d = tempfile.mkdtemp()
main, startup = fluid.Program(), fluid.Program()
scope = fluid.Scope()
with fluid.program_guard(main, startup), fluid.scope_guard(scope):
    x = layers.data("x", shape=[16], append_batch_size=True)
    pred = layers.fc(layers.fc(x, size=32, act="relu"), size=4)
    exe = fluid.Executor()
    exe.run(startup)
    fluid.io.save_inference_model(d, ["x"], [pred], exe,
                                  main_program=main)
xs = rng.rand(32, 16).astype(np.float32)
ref = fluid.Predictor(d)
refs = [ref.run({"x": xs[i:i + 1]})[0][0] for i in range(32)]

engine = ServingEngine(d, {"x": np.zeros(16, np.float32)},
                       buckets=BucketConfig((1, 2, 4, 8)),
                       max_wait_ms=5, queue_capacity=64).start()
snap = runtime_stats.snapshot()
outs = [None] * 32
def client(i):
    outs[i] = engine.infer({"x": xs[i]}, timeout_s=120)[0]
threads = [threading.Thread(target=client, args=(i,)) for i in range(32)]
[t.start() for t in threads]; [t.join() for t in threads]
assert engine.drain(timeout_s=60), "drain timed out"
engine.close()
for i in range(32):
    np.testing.assert_allclose(outs[i], refs[i], rtol=1e-5, atol=1e-6)
compiles = runtime_stats.delta(snap)["compiles"]
assert compiles == 0, f"{compiles} XLA compiles AFTER warmup (shape leak)"
s = engine.stats.snapshot()
assert s["completed"] == 32 and s["post_warmup_compiles"] == 0
print("serving smoke OK:",
      {k: s[k] for k in ("completed", "batches", "batch_occupancy",
                         "post_warmup_compiles")})
EOF

echo "== continuous-batching decode smoke (cpu) =="
# ISSUE 12 tentpole: the paged-KV decode engine end-to-end — requests
# JOIN open slots mid-generation (more requests than slots), a
# deliberately tight pool forces at least one preemption, drain
# resolves everything, and the whole stream performs ZERO XLA compiles
# after warmup (fixed-shape executables across any join/leave/preempt
# pattern).  Parity: the continuous-batching tokens must be identical
# to the SAME requests decoded one-at-a-time in a single-slot engine —
# a request's output may not depend on who shared the batch (the
# full-KV reference parity runs in tests/test_paged_decode.py below).
python - <<'EOF'
import numpy as np
import jax

from paddle_tpu.models.decoder_lm import DecoderLM, make_prompts
from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

lm = DecoderLM(vocab_size=96, n_layer=2, n_head=2, d_model=32,
               d_inner=64, kv_dtype="float32", seed=3)
prompts = make_prompts(6, 96, min_len=3, max_len=14, seed=2)
budgets = [8, 3, 10, 5, 7, 4]

# continuous: 2 slots, pool below 2x worst case -> joins + preemption
cfg = DecodeConfig(num_slots=2, page_size=4, max_len=40, num_pages=11,
                   prefill_buckets=(8, 16), decode_chunk=4,
                   kv_dtype="float32")
eng = DecodeEngine(lm, cfg, memory_budget_bytes=False).start()
snap = runtime_stats.snapshot()
futs = [eng.submit(p, max_new_tokens=b, priority=i % 2)
        for i, (p, b) in enumerate(zip(prompts, budgets))]
outs = [f.result(300).tolist() for f in futs]
assert eng.drain(120), "drain timed out"
compiles = runtime_stats.delta(snap)["compiles"]
s = eng.stats.snapshot()
eng.close()
assert compiles == 0, f"{compiles} XLA compiles AFTER warmup (shape leak)"
assert s["post_warmup_compiles"] == 0 and s["completed"] == 6, s
assert s["prefills"] >= 3, f"no mid-generation joins happened: {s}"
assert s["tokens_generated"] == sum(budgets)

# one-at-a-time isolation reference (single-slot engine)
cfg1 = DecodeConfig(num_slots=1, page_size=4, max_len=40, num_pages=10,
                    prefill_buckets=(8, 16), decode_chunk=4,
                    kv_dtype="float32")
solo = DecodeEngine(lm, cfg1, memory_budget_bytes=False).start()
refs = [solo.generate(p, max_new_tokens=b, timeout_s=300).tolist()
        for p, b in zip(prompts, budgets)]
solo.close()
assert outs == refs, "continuous-batching tokens depend on batch-mates"
print("decode smoke OK:",
      {k: s[k] for k in ("completed", "prefills", "preemptions",
                         "slot_occupancy", "kv_page_utilization",
                         "post_warmup_compiles")})
EOF
python -m pytest tests/test_paged_decode.py -q

echo "== speculative decode smoke (cpu) =="
# ISSUE 20 tentpole: DecodeEngine(speculate_k=4) commits token
# sequences BIT-IDENTICAL to the sequential engine across mid-stream
# joins AND a forced preemption, performs ZERO XLA compiles after
# warmup (the folded verify batch is one fixed shape for any accept
# pattern), and the accept-rate telemetry section accounts for every
# committed token (docs/SERVING.md §speculate)
python - <<'EOF'
import numpy as np
import jax

from paddle_tpu.models.decoder_lm import DecoderLM, make_prompts
from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.serving import DecodeConfig, DecodeEngine

def mk():
    return DecoderLM(vocab_size=48, n_layer=2, n_head=2, d_model=32,
                     d_inner=64, kv_dtype="float32", seed=7)

cfg = DecodeConfig(num_slots=2, page_size=4, max_len=40, num_pages=11,
                   prefill_buckets=(8, 16), decode_chunk=4,
                   kv_dtype="float32")
# 5 short requests exercise mid-stream joins; the trailing lo/hi pair
# (two 24-token budgets against an 11-page pool) forces an eviction
prompts = list(make_prompts(5, 48, min_len=3, max_len=14, seed=11)) \
    + [np.arange(1, 8, dtype=np.int64), np.arange(2, 9, dtype=np.int64)]
budgets = [8, 3, 10, 5, 7, 24, 24]
prios = [0, 1, 0, 1, 0, 0, 5]

def run_stream(**kw):
    eng = DecodeEngine(mk(), cfg, memory_budget_bytes=False,
                       **kw).start()
    snap = runtime_stats.snapshot()
    futs = [eng.submit(p, max_new_tokens=b, priority=pr)
            for p, b, pr in zip(prompts, budgets, prios)]
    outs = [f.result(300).tolist() for f in futs]
    assert eng.drain(timeout_s=120), "drain timed out"
    compiles = runtime_stats.delta(snap)["compiles"]
    s = eng.stats.snapshot()
    eng.close()
    return outs, compiles, s

ref, _, _ = run_stream()
got, compiles, s = run_stream(speculate_k=4)
assert got == ref, "speculative tokens diverged from sequential"
assert compiles == 0, f"{compiles} XLA compiles AFTER warmup"
assert s["post_warmup_compiles"] == 0 and s["completed"] == 7, s
assert s["preemptions"] >= 1, f"pool did not force a preemption: {s}"
spec = s["speculation"]
assert spec["speculate_k"] == 4 and spec["verify_dispatches"] >= 1
assert spec["emitted_tokens"] + s["prefill_joins"] == \
    s["tokens_generated"], (spec, s["tokens_generated"])
print("speculative decode smoke OK:",
      {k: spec[k] for k in ("speculate_k", "verify_dispatches",
                            "accept_rate", "accept_hist",
                            "speculation_efficiency")},
      {"preemptions": s["preemptions"],
       "post_warmup_compiles": s["post_warmup_compiles"]})
EOF
python -m pytest tests/test_speculate.py -q

echo "== serving fleet chaos smoke (cpu) =="
# ISSUE 14 tentpole: kill one replica mid-stream under load -> zero
# client-visible failures and every output token-identical to an
# uninterrupted control engine (greedy failover identity, committed
# prefixes verified); then fleet.reload() rolls the SAME weights
# through the survivors under load -> zero drops, zero recompiles,
# responses tagged with the new model version.  Fleet-wide
# post_warmup_compiles stays 0 across both events.
#
# ISSUE 15 rides the same fleet: (a) per-request tracing — the killed
# request's SINGLE trace_id must export a chrome trace showing
# queue -> dispatch -> failover-hop -> completion across two replica
# rows; (b) the unified metrics exporter — /metrics must expose
# families from >=4 subsystems with serving_post_warmup_compiles
# readable as a 0 gauge, and tools/metrics_dump.py must scrape it.
python - <<'EOF'
import json, subprocess, sys, tempfile, time, urllib.request, re
import numpy as np
import jax

import paddle_tpu as fluid
from paddle_tpu.core.executor import Executor, scope_guard
from paddle_tpu.models.decoder_lm import DecoderLM, make_prompts
from paddle_tpu.observe import ReqTracer
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import DecodeConfig, DecodeEngine, Fleet, FleetConfig

def mk():
    lm = DecoderLM(vocab_size=96, n_layer=2, n_head=2, d_model=32,
                   d_inner=64, kv_dtype="float32", seed=5)
    cfg = DecodeConfig(num_slots=2, page_size=4, max_len=48,
                       num_pages=24, prefill_buckets=(8, 16),
                       decode_chunk=2, kv_dtype="float32")
    return DecodeEngine(lm, cfg, memory_budget_bytes=False)

prompts = make_prompts(6, 96, min_len=3, max_len=12, seed=9)
budgets = [18, 16, 20, 14, 18, 16]

ctrl = mk().start()
control = [ctrl.generate(p, max_new_tokens=b, timeout_s=300).tolist()
           for p, b in zip(prompts, budgets)]
ctrl.close()

engines = [mk(), mk()]
tracer = ReqTracer(sample_rate=1.0)
fleet = Fleet(engines, FleetConfig(), tracer=tracer).start()
futs = [fleet.submit(p, max_new_tokens=b)
        for p, b in zip(prompts, budgets)]
end = time.monotonic() + 60
while engines[0].stats.tokens_generated < 2 and time.monotonic() < end:
    time.sleep(0.002)
chaos.kill_replica(engines[0])  # mid-generation replica death
resps = [f.result(300) for f in futs]
outs = [r.tokens.tolist() for r in resps]
snap = fleet.snapshot()
assert outs == control, "failover broke greedy token identity"
assert snap["failed"] == 0 and snap["failovers"] >= 1, snap
assert snap["parity_checked"] >= 1 and snap["parity_failed"] == 0, snap
assert snap["ejects"] == 1 and snap["post_warmup_compiles"] == 0, snap

# -- ISSUE 15 chaos trace proof: ONE trace_id across both replicas ----
killed = [r for r in resps if r.failovers >= 1][0]
assert killed.trace_id and 0 in killed.hops and killed.hops[-1] == 1, \
    (killed.trace_id, killed.hops)
t = tracer.trace(killed.trace_id)
names = t.span_names()
assert "join_wait" in names and "dispatch" in names, names
fo = t.find("failover")[0]
assert fo.attrs["from_replica"] == 0 and fo.attrs["to_replica"] == 1, \
    fo.attrs
assert "complete" in names, names
assert set(t.replica_ids()) == {0, 1}, t.replica_ids()
ct = tracer.export_chrome_trace("/tmp/fleet_chaos_trace.json")
rows = {e["pid"] for e in ct["traceEvents"] if e.get("ph") == "X"
        and e["args"].get("trace_id") == killed.trace_id}
assert len(rows) >= 3, rows  # router row + BOTH replica rows
print("chaos trace proof OK:",
      {"trace_id": killed.trace_id, "hops": killed.hops,
       "rows": sorted(rows),
       "exported": "/tmp/fleet_chaos_trace.json"})

# -- ISSUE 15 metrics smoke: scrape the live fleet's exporter ---------
srv = fleet.start_metrics_server()   # 127.0.0.1, ephemeral port
body = urllib.request.urlopen(srv.url + "/metrics",
                              timeout=10).read().decode()
urllib.request.urlopen(srv.url + "/healthz", timeout=10).read()
m = re.search(r'^serving_post_warmup_compiles\{[^}]*\} (\d+)$',
              body, re.M)
assert m and m.group(1) == "0", "serving_post_warmup_compiles gauge"
subsystems = {ln.split("_")[0] for ln in body.splitlines()
              if ln and not ln.startswith("#")}
present = subsystems & {"serving", "fleet", "runtime", "reqtrace",
                        "process", "memory"}
assert len(present) >= 4, subsystems
dump = subprocess.run(
    [sys.executable, "tools/metrics_dump.py", "--url",
     srv.url + "/metrics", "--grep", "fleet_"],
    capture_output=True, text=True, timeout=60)
assert dump.returncode == 0, dump.stderr
assert "fleet_failovers_total" in dump.stdout, dump.stdout[:500]
print("metrics export smoke OK:",
      {"subsystems": sorted(present),
       "families": len([ln for ln in body.splitlines()
                        if ln.startswith("# TYPE")])})

with tempfile.TemporaryDirectory() as d:
    with scope_guard(engines[1].scope):
        fluid.io.save_sharded(Executor(), d,
                              main_program=engines[1].model.step["main"])
    futs = [fleet.submit(p, max_new_tokens=b)
            for p, b in zip(prompts, budgets)]
    info = fleet.reload(d)          # rolling swap under load
    outs2 = [f.result(300).tokens.tolist() for f in futs]
    post = fleet.generate(prompts[0], max_new_tokens=4, timeout_s=300)
assert outs2 == control, "reload perturbed in-flight tokens"
assert info["compiles"] == 0 and info["version"] == 1, info
assert post.model_version == 1, post.model_version
snap = fleet.snapshot()
assert snap["failed"] == 0 and snap["post_warmup_compiles"] == 0, snap
fleet.close()
print("fleet chaos smoke OK:",
      {k: snap[k] for k in ("completed", "failovers", "parity_checked",
                            "ejects", "reloads", "reload_pause_ms",
                            "post_warmup_compiles")})
EOF
python -m pytest tests/test_fleet.py -q

echo "== SLO alert + flight recorder smoke (cpu) =="
# ISSUE 17 (observe pillar 9): a synthetic SLO breach against a toy
# registry must walk the rule to firing, expose it on the /alerts
# route AND as the `alerts` family on /metrics, write exactly one
# rate-limited diagnostic bundle with a readable manifest, and
# tools/metrics_dump.py --alerts must render it.  Pure host — the
# engine only reads registry snapshots.
python - <<'EOF'
import json, os, subprocess, sys, tempfile, urllib.request
import jax

from paddle_tpu.observe.alerts import AlertEngine, ThresholdRule
from paddle_tpu.observe.flightrec import FlightRecorder
from paddle_tpu.observe.registry import (MetricsRegistry, MetricsServer,
                                         gauge)

reg = MetricsRegistry()
ttft = [120.0]                              # the mutable toy SLI
reg.register("toy", lambda: [gauge("toy_ttft_p99_ms", "", ttft[0])])
eng = AlertEngine(reg, rules=[
    ThresholdRule("toy_ttft_slo", "toy_ttft_p99_ms", op=">",
                  threshold=500.0, clear=400.0)], event_log=None)
reg.register("alerts", eng.collector())
d = tempfile.mkdtemp(prefix="alert_smoke_")
rec = FlightRecorder(d, registry=reg, min_interval_s=3600.0)
rec.attach_engine(eng)

eng.evaluate(now=0.0)
assert eng.firing() == [] and rec.bundles == []
ttft[0] = 900.0                             # synthetic SLO breach
eng.evaluate(now=1.0)
assert eng.firing() == ["toy_ttft_slo"], eng.state()
assert len(rec.bundles) == 1, rec.snapshot()
man = json.load(open(os.path.join(rec.bundles[0], "MANIFEST.json")))
assert man["context"]["rule"] == "toy_ttft_slo" and not man["errors"]
assert json.load(open(os.path.join(
    rec.bundles[0], "metrics.json")))["toy_ttft_p99_ms"]
# flap guard: a second breach pass inside the rate window writes no
# second bundle (already firing -> no transition; and rate-limited)
eng.evaluate(now=2.0)
assert len(rec.bundles) == 1

srv = MetricsServer(reg, alerts_fn=eng.state).start()
alerts = json.loads(urllib.request.urlopen(
    srv.url + "/alerts", timeout=10).read().decode())
assert alerts["firing"] == ["toy_ttft_slo"], alerts
text = urllib.request.urlopen(
    srv.url + "/metrics", timeout=10).read().decode()
assert 'alerts_firing{rule="toy_ttft_slo",severity="page"} 1' in text
dump = subprocess.run(
    [sys.executable, "tools/metrics_dump.py", "--url",
     srv.url + "/metrics", "--alerts"],
    capture_output=True, text=True, timeout=60)
assert dump.returncode == 0, dump.stderr
assert "toy_ttft_slo" in dump.stdout and "firing" in dump.stdout
# hysteresis resolve: back under the CLEAR threshold
ttft[0] = 100.0
eng.evaluate(now=3.0)
assert eng.firing() == [], eng.state()
srv.close(); eng.close()
print("alerts smoke OK:",
      {"bundle": os.path.basename(rec.bundles[0]),
       "files": sorted(man["files"]),
       "fired": alerts["rules"][0]["fired_count"]})
EOF
python -m pytest tests/test_alerts.py -q

echo "== disagg serving chaos smoke (cpu) =="
# ISSUE 18 tentpole: phase-disaggregated fleet (2 prefill + 2 decode
# workers), kill ONE worker of EACH kind mid-stream -> zero
# client-visible failures and every output token-identical to the
# unified control engine (the parity contract holds across the KV-page
# handoff AND across both failover kinds); fleet-wide
# post_warmup_compiles stays 0 — the fixed-shape import scatter never
# recompiles the decode executable.  The chrome trace proof: ONE
# trace_id draws prefill-worker row -> kv_transfer flow arrow ->
# decode-worker row.
python - <<'EOF'
import json, time
import numpy as np
import jax

from paddle_tpu.models.decoder_lm import DecoderLM, make_prompts
from paddle_tpu.observe import ReqTracer
from paddle_tpu.resilience import chaos
from paddle_tpu.serving import DecodeConfig, DecodeEngine, DisaggFleet

def mk(role):
    lm = DecoderLM(vocab_size=96, n_layer=2, n_head=2, d_model=32,
                   d_inner=64, kv_dtype="float32", seed=5)
    cfg = DecodeConfig(num_slots=2, page_size=4, max_len=48,
                       num_pages=24, prefill_buckets=(8, 16),
                       decode_chunk=2, kv_dtype="float32")
    return DecodeEngine(lm, cfg, role=role, memory_budget_bytes=False)

prompts = make_prompts(8, 96, min_len=3, max_len=12, seed=9)
budgets = [18, 16, 20, 14, 18, 16, 15, 17]

ctrl = mk("unified").start()
control = [ctrl.generate(p, max_new_tokens=b, timeout_s=300).tolist()
           for p, b in zip(prompts, budgets)]
ctrl.close()

tracer = ReqTracer(sample_rate=1.0)
fleet = DisaggFleet([mk("prefill"), mk("prefill")],
                    [mk("decode"), mk("decode")],
                    tracer=tracer).start()
pf_victim = fleet.prefill[0].engine
dec_victim = fleet.decode[0].engine
chaos.arm(f"replica:{pf_victim.replica_id}:kill", times=1)
futs = [fleet.submit(p, max_new_tokens=b)
        for p, b in zip(prompts, budgets)]
end = time.monotonic() + 60
while dec_victim.stats.tokens_generated < 2 and time.monotonic() < end:
    time.sleep(0.002)
chaos.kill_replica(dec_victim)      # mid-generation decode death
resps = [f.result(300) for f in futs]
chaos.clear()
outs = [list(r.tokens) for r in resps]
snap = fleet.snapshot()
assert outs == control, "disagg chaos broke greedy token identity"
assert snap["failed"] == 0, snap
assert snap["prefill_failovers"] >= 1, snap
assert snap["decode_failovers"] >= 1, snap
assert snap["parity_failed"] == 0, snap
assert snap["post_warmup_compiles"] == 0, snap
assert snap["handoffs"] >= len(prompts), snap
assert snap["pages_transferred"] > 0, snap

# -- the one-trace handoff proof: prefill row -> arrow -> decode row --
r0 = resps[0]
pf_ids = {h.replica_id for h in fleet.prefill}
dec_ids = {h.replica_id for h in fleet.decode}
assert r0.hops[0] in pf_ids and r0.hops[-1] in dec_ids, r0.hops
t = tracer.trace(r0.trace_id)
assert "kv_transfer" in t.span_names(), t.span_names()
ct = tracer.export_chrome_trace("/tmp/disagg_chaos_trace.json")
xs = [e for e in ct["traceEvents"] if e.get("ph") == "X"
      and e["args"].get("trace_id") == r0.trace_id]
rows = {e["pid"] for e in xs}
# router row + the prefill worker's row + the decode worker's row
assert rows >= {0, r0.hops[0] + 1, r0.hops[-1] + 1}, rows
flows = [e for e in ct["traceEvents"] if e["name"] == "kv_transfer"
         and e.get("ph") in ("s", "f")
         and e["args"].get("trace_id") == r0.trace_id]
by_id = {}
for e in flows:
    by_id.setdefault(e["id"], []).append(e)
# every arrow is a paired s/f (one per handoff hop of this request)
assert by_id, flows
assert all(sorted(x["ph"] for x in v) == ["f", "s"]
           for v in by_id.values()), flows
# the FINAL arrow lands on the decode worker that served the request,
# leaving from a prefill-worker row
last = max(by_id.values(), key=lambda v: min(x["ts"] for x in v))
src = next(e for e in last if e["ph"] == "s")
dst = next(e for e in last if e["ph"] == "f")
assert src["pid"] - 1 in pf_ids and dst["pid"] == r0.hops[-1] + 1, \
    (src["pid"], dst["pid"], r0.hops)
fleet.close()
print("disagg chaos smoke OK:",
      {k: snap[k] for k in ("completed", "handoffs", "pages_transferred",
                            "prefill_failovers", "decode_failovers",
                            "parity_checked", "post_warmup_compiles")},
      {"trace_id": r0.trace_id, "rows": sorted(rows),
       "exported": "/tmp/disagg_chaos_trace.json"})
EOF
python -m pytest tests/test_disagg.py -q

echo "== resilience chaos smoke (cpu) =="
# the fault-tolerance contract end-to-end (docs/RESILIENCE.md): inject
# NaN at step 3 -> the guard skips exactly that update; corrupt the
# newest checkpoint shard -> a restarted Trainer resumes from the last
# good serial with a ckpt_fallback event; an executor failure burst
# flips the serving breaker to DEGRADED and a half-open probe recovers
# it to RUNNING.  No unstructured crash anywhere.
python - <<'EOF'
import os, tempfile, time
import numpy as np
import jax

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.contrib import CheckpointConfig, Trainer
from paddle_tpu.resilience import FlakyPredictor, chaos, enable_update_guard
from paddle_tpu.serving import (BucketConfig, CircuitBreaker,
                                CircuitOpenError, ExecutorFailureError,
                                ServingEngine)

d = tempfile.mkdtemp()
log = os.path.join(d, "events.jsonl")

def train_func():
    x = layers.data(name="x", shape=[4], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    pred = layers.fc(x, size=1)
    return layers.mean(layers.square_error_cost(pred, y))

def opt_func():
    return fluid.optimizer.SGDOptimizer(learning_rate=0.1)

def reader():
    r = np.random.RandomState(0)
    for _ in range(6):
        yield {"x": r.rand(8, 4).astype(np.float32),
               "y": r.rand(8, 1).astype(np.float32)}

# -- NaN at step 3: guard skips exactly that update --------------------
t = Trainer(train_func, opt_func,
            checkpoint_config=CheckpointConfig(os.path.join(d, "ck"),
                                               step_interval=2),
            telemetry=observe.TelemetryConfig(interval=100,
                                              log_path=log))
enable_update_guard(t.train_program)
t.train(num_epochs=1, reader=chaos.nan_reader(reader, at_step=3))
tel = t.last_telemetry  # the end-of-train window flush
assert tel.steps == 6 and tel.skipped_update_steps == 1, tel.as_dict()
params = {v.name: np.asarray(t.scope.find_var(v.name))
          for v in t.train_program.list_vars() if v.persistable}
assert all(np.isfinite(p).all() for p in params.values()), \
    "NaN leaked into parameters past the guard"
ids = t._list_checkpoints()
assert ids, "no checkpoints saved"

# -- corrupt newest shard: resume falls back to the prior serial -------
chaos.corrupt_shard(os.path.join(d, "ck", f"ckpt_{ids[-1]}"))
t2 = Trainer(train_func, opt_func,
             checkpoint_config=CheckpointConfig(os.path.join(d, "ck"),
                                                step_interval=2),
             telemetry=observe.TelemetryConfig(interval=100,
                                               log_path=log))
events = observe.read_events(log)
falls = [e for e in events if e["event"] == "ckpt_fallback"]
resumes = [e for e in events if e["event"] == "ckpt_resume"]
assert falls and falls[-1]["serial"] == ids[-1] \
    and falls[-1]["error"]["error"] == "checkpoint_corrupt", falls[-1:]
assert resumes and resumes[-1]["serial"] == ids[-2] \
    and resumes[-1]["fallback"] is True, resumes[-1:]

# -- serving breaker: failure burst -> DEGRADED -> probe -> RUNNING ----
md = os.path.join(d, "model")
main, startup = fluid.Program(), fluid.Program()
scope = fluid.Scope()
with fluid.program_guard(main, startup), fluid.scope_guard(scope):
    x = layers.data("x", shape=[8], append_batch_size=True)
    pred = layers.fc(x, size=4)
    exe = fluid.Executor(); exe.run(startup)
    fluid.io.save_inference_model(md, ["x"], [pred], exe,
                                  main_program=main)
engine = ServingEngine(
    FlakyPredictor(fluid.Predictor(md), fail_first=2),
    {"x": np.zeros(8, np.float32)}, buckets=BucketConfig((1, 2)),
    max_wait_ms=0, queue_capacity=8,
    breaker=CircuitBreaker(failure_threshold=2, cooldown_s=0.2))
engine.start()
x0 = np.ones(8, np.float32)
for _ in range(2):
    try:
        engine.infer({"x": x0}, timeout_s=60)
        raise AssertionError("injected executor failure not raised")
    except ExecutorFailureError as e:
        assert e.as_dict()["error"] == "executor_failure"
assert engine.health()["state"] == "degraded", engine.health()
try:
    engine.infer({"x": x0}, timeout_s=60)
    raise AssertionError("expected circuit_open fast-reject")
except CircuitOpenError as e:
    assert e.as_dict()["error"] == "circuit_open"
time.sleep(0.25)
engine.infer({"x": x0}, timeout_s=60)   # half-open probe succeeds
assert engine.health()["state"] == "running", engine.health()
engine.close()
print("chaos smoke OK:",
      {"skipped_update_steps": tel.skipped_update_steps,
       "ckpt_fallback_serial": falls[-1]["serial"],
       "resumed_serial": resumes[-1]["serial"],
       "breaker": engine.health()["breaker"]["state"]})
EOF

echo "== numerics provenance chaos smoke (cpu) =="
# ISSUE 11 tentpole (docs/OBSERVE.md pillar 6): chaos.poison_feed-inject
# NaN into one named feed -> the device-side per-op bitmap must
# attribute the poison to EXACTLY the first fluid op consuming that
# feed (type + index + group), the update guard must keep the run
# alive (exactly one skipped update, params finite), and the Trainer
# must emit a `nonfinite_provenance` event carrying the same join.
python - <<'EOF'
import os, tempfile
import numpy as np
import jax

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.contrib import Trainer
from paddle_tpu.resilience import chaos, enable_update_guard

d = tempfile.mkdtemp()
log = os.path.join(d, "numerics.jsonl")

def train_func():
    x = layers.data(name="x", shape=[4], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h = layers.fc(x, size=8, act="relu", name="ffn_in")
    pred = layers.fc(h, size=1, name="ffn_out")
    return layers.mean(layers.square_error_cost(pred, y))

def reader():
    r = np.random.RandomState(0)
    for _ in range(6):
        yield {"x": r.rand(8, 4).astype(np.float32),
               "y": r.rand(8, 1).astype(np.float32)}

t = Trainer(train_func,
            lambda: fluid.optimizer.SGDOptimizer(learning_rate=0.1),
            telemetry=observe.TelemetryConfig(interval=100,
                                              log_path=log,
                                              numerics=True))
enable_update_guard(t.train_program)
# poison feed "y" at step 3: the NaN must be attributed to the FIRST
# fluid op that consumes y, not to op 0 and not to a bare counter
t.train(num_epochs=1, reader=chaos.nan_reader(reader, at_step=3,
                                              names=["y"]))
tel = t.last_telemetry
ops = t.train_program.global_block().ops
exp = next(i for i, op in enumerate(ops)
           if "y" in op.desc.input_names())
fno = tel.first_nonfinite_op
assert fno is not None, tel.as_dict()
assert fno["op_index"] == exp and fno["op_type"] == ops[exp].desc.type \
    and "group" in fno, (fno, exp, ops[exp].desc.type)
# the run stayed ALIVE through the poison: guard skipped exactly that
# update and no NaN reached the parameters
assert tel.steps == 6 and tel.skipped_update_steps == 1, tel.as_dict()
params = {v.name: np.asarray(t.scope.find_var(v.name))
          for v in t.train_program.list_vars() if v.persistable}
assert all(np.isfinite(p).all() for p in params.values()), \
    "NaN leaked into parameters past the guard"
# per-group dynamics: the named layers report, and group grad norms
# compose to the global one (consistency contract)
assert "ffn_in" in tel.groups and "ffn_out" in tel.groups, tel.groups
events = observe.read_events(log)
prov = [e for e in events if e["event"] == "nonfinite_provenance"]
assert prov and prov[-1]["first_nonfinite_op"]["op_index"] == exp \
    and prov[-1]["skipped_update_steps"] == 1, prov[-1:]
t.stop()
print("numerics provenance smoke OK:",
      {"op": f"{fno['op_index']}:{fno['op_type']}",
       "group": fno.get("group"),
       "skipped": tel.skipped_update_steps,
       "groups": sorted(tel.groups)})
EOF

echo "== divergence autopilot chaos smoke (cpu) =="
# ISSUE 19 tentpole (docs/RESILIENCE.md §autopilot): a NaN window
# injected mid-run must recover with ZERO human action — in-process
# rollback to the newest verified-good serial, quarantine of the
# poisoned data window (recovery_rollback + data_quarantine events),
# wall clock attributed to the goodput `recovery` category, and final
# params BIT-IDENTICAL to a control run that never saw the
# quarantined batches.
python - <<'EOF'
import os, tempfile
import numpy as np
import jax

import paddle_tpu as fluid
from paddle_tpu import layers, observe, resilience
from paddle_tpu.contrib import CheckpointConfig, Trainer
from paddle_tpu.resilience import chaos, enable_update_guard

d = tempfile.mkdtemp()

def train_func():
    x = layers.data(name="x", shape=[4], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    pred = layers.fc(x, size=1)
    return layers.mean(layers.square_error_cost(pred, y))

def opt_func():
    return fluid.optimizer.SGDOptimizer(learning_rate=0.1)

def reader():
    r = np.random.RandomState(11)
    for _ in range(12):
        yield {"x": r.rand(8, 4).astype(np.float32),
               "y": r.rand(8, 1).astype(np.float32)}

log = os.path.join(d, "auto.jsonl")
t = Trainer(train_func, opt_func,
            checkpoint_config=CheckpointConfig(os.path.join(d, "ck"),
                                               step_interval=2),
            telemetry=observe.TelemetryConfig(interval=1,
                                              log_path=log),
            autopilot=resilience.AutopilotConfig(
                skip_streak=1, loss_spike_z=None, grad_norm_z=None))
enable_update_guard(t.train_program)
# poison position 5 mid-stream: NO human action from here on
t.train(num_epochs=1,
        reader=chaos.nan_reader(reader, at_step=5, names=["y"]))
snap = t.autopilot.snapshot()
assert snap["rollbacks"] == 1 and snap["halted"] == 0, snap
assert snap["quarantined_batches"] == 2, snap

events = observe.read_events(log)
kinds = [e["event"] for e in events]
rb = kinds.index("recovery_rollback")   # raises if absent
dq = kinds.index("data_quarantine")
assert rb < dq and "recovery_halt" not in kinds, kinds
rbe = events[rb]
assert (rbe["from_step"], rbe["to_step"]) == (4, 6), rbe

rep = t.goodput()
assert rep["categories_s"]["recovery"] > 0, rep["categories_s"]

params = {v.name: np.asarray(t.scope.find_var(v.name))
          for v in t.train_program.list_vars()
          if v.persistable and "__" not in v.name}

# control: the same stream minus the quarantined positions [4, 6)
def control_reader():
    for i, b in enumerate(reader()):
        if i not in (4, 5):
            yield b

ctl = Trainer(train_func, opt_func,
              checkpoint_config=CheckpointConfig(
                  os.path.join(d, "ck_ctl"), step_interval=2),
              telemetry=observe.TelemetryConfig(interval=1))
enable_update_guard(ctl.train_program)
ctl.train(num_epochs=1, reader=lambda: control_reader())
want = {v.name: np.asarray(ctl.scope.find_var(v.name))
        for v in ctl.train_program.list_vars()
        if v.persistable and "__" not in v.name}
assert params and set(params) == set(want)
for name in params:
    assert np.isfinite(params[name]).all(), name
    np.testing.assert_array_equal(params[name], want[name],
                                  err_msg=name)
t.stop(); ctl.stop()
print("autopilot chaos smoke OK:",
      {"rollbacks": snap["rollbacks"],
       "quarantined": snap["quarantined_batches"],
       "window": (rbe["from_step"], rbe["to_step"]),
       "recovery_s": rep["categories_s"]["recovery"],
       "bit_identical_params": sorted(params)})
EOF

echo "== goodput ledger smoke (cpu) =="
# ISSUE 16 tentpole (docs/OBSERVE.md pillar 8): a short Trainer run with
# a deliberately slow reader + periodic checkpoint saves must yield a
# ledger whose categories sum EXACTLY to the wall clock (idle is the
# residual), attribute the reader sleeps to data_stall and the save
# blocking to checkpoint, print the human table, and scale the headline
# MFU down to effective_mfu — never up.
python - <<'EOF'
import os, tempfile, time
import numpy as np
import jax

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.contrib import CheckpointConfig, Trainer
from paddle_tpu.observe import format_goodput_table
from paddle_tpu.observe.goodput import CATEGORIES

d = tempfile.mkdtemp()

def train_func():
    x = layers.data(name="x", shape=[4], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    pred = layers.fc(x, size=1)
    return layers.mean(layers.square_error_cost(pred, y))

def reader():
    r = np.random.RandomState(0)
    for _ in range(6):
        time.sleep(0.02)            # the input-pipeline stall
        yield {"x": r.rand(8, 4).astype(np.float32),
               "y": r.rand(8, 1).astype(np.float32)}

t = Trainer(train_func,
            lambda: fluid.optimizer.SGDOptimizer(learning_rate=0.1),
            checkpoint_config=CheckpointConfig(os.path.join(d, "ck"),
                                               step_interval=2))
t.train(num_epochs=1, reader=reader)
rep = t.goodput(mfu=0.3254)
cats = rep["categories_s"]
assert set(cats) == set(CATEGORIES), cats
assert abs(sum(cats.values()) - rep["wall_s"]) < 1e-3, \
    (sum(cats.values()), rep["wall_s"])
assert abs(sum(rep["fractions"].values()) - 1.0) < 1e-4, rep["fractions"]
assert rep["steps"] == 6 and rep["replay_steps"] == 0, rep
assert cats["data_stall"] >= 0.05, cats        # 6 x 20ms reader sleeps
assert cats["checkpoint"] > 0, cats            # blocking snapshot phases
assert rep["effective_mfu"] <= rep["mfu"], rep # goodput never scales UP
# effective_mfu is computed from the UNROUNDED step fraction inside
# report(); recomputing from the rounded goodput can differ by 1e-6
assert abs(rep["effective_mfu"] - 0.3254 * rep["goodput"]) < 2e-6
print(format_goodput_table(rep))
t.stop()
print("goodput smoke OK:",
      {"wall_s": rep["wall_s"], "goodput": rep["goodput"],
       "effective_mfu": rep["effective_mfu"],
       "data_stall_s": cats["data_stall"],
       "checkpoint_s": cats["checkpoint"]})
EOF

echo "== gang-chaos smoke (cpu) =="
# ISSUE 9 (docs/RESILIENCE.md, distributed failure model): a REAL
# 2-worker gang under the self-healing supervisor — SIGKILL a random
# rank (the coordinator included; the supervisor hosts the
# coordination service) mid-train: the survivor must detect within
# the configured heartbeat miss budget (structured PeerLostError
# naming the dead rank, exit 43, no hang, no orphans), the supervisor
# relaunches once, and the restarted gang's final params must be
# BIT-identical to an uninterrupted control gang.  Then the poisoned
# barrier: a rank already waiting in a checkpoint barrier when a peer
# poisons the gang must abort in seconds, not the barrier timeout.
python tests/test_gang.py --ci-smoke

echo "== crash-resume smoke (cpu) =="
# ISSUE 7 (docs/RESILIENCE.md, preemption): SIGKILL a REAL training
# subprocess at a random mid step, relaunch, auto-resume — final
# params must be BIT-identical to an uninterrupted control and no
# torn checkpoint may be loadable (trainer state written strictly
# last); then the SIGTERM drain path — the worker must exit with the
# DISTINCT preempt code (77, not 143) after writing an emergency
# checkpoint (ckpt_emergency event), and its resumed run must match
# the control bit-for-bit too.
python tests/test_preempt.py --ci-smoke

echo "== hybrid-parallel smoke: fsdp ZeRO + dpxmp + reshard-load (cpu) =="
# ISSUE 13 tentpole: (1) an fsdp mesh must ZeRO-shard optimizer state —
# per-device resident opt-state bytes from the SHARDED compile drop
# >=1.7x at fsdp=2 and ~N/1 at fsdp=8; (2) a dp×mp mesh with
# Megatron-sharded params trains with loss parity vs the single-device
# twin, int8 grad sync deterministic on the composed mesh; (3) a
# checkpoint saved on a dp=8 virtual mesh RESUMES on dp=4 and dp=2×mp=2
# meshes with bit-identical logical params (the reshard-load contract)
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
python - <<'EOF'
import tempfile
import numpy as np
import jax

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.parallel import GradSyncConfig, make_mesh
from paddle_tpu.parallel.strategies import ShardingRules

def build():
    x = layers.data("x", shape=[32], dtype="float32")
    y = layers.data("y", shape=[1], dtype="float32")
    h = layers.fc(x, size=128, act="relu", name="ffn_in")
    pred = layers.fc(h, size=1, name="ffn_out")
    loss = layers.mean(layers.square_error_cost(pred, y))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(loss)
    return loss

def rules():
    return ShardingRules(rules=[(r"ffn_in\S*\.w", (None, "mp")),
                                (r"ffn_out\S*\.w", ("mp", None))])

def batches(n, seed=0):
    r = np.random.RandomState(seed)
    return [{"x": r.randn(64, 32).astype(np.float32),
             "y": r.randn(64, 1).astype(np.float32)} for _ in range(n)]

def run(mesh_axes, grad_sync=None, mp=False, steps=3, ckpt=None,
        load=None, opt_bytes=False):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    scope = fluid.Scope()
    out = {}
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        loss = build()
        exe = fluid.Executor()
        exe.run(startup)
        if mesh_axes:
            bs = fluid.BuildStrategy()
            bs.grad_sync = grad_sync
            if mp:
                bs.sharding_rules = rules()
            fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name, build_strategy=bs,
                mesh=make_mesh(mesh_axes))
        if load:
            fluid.io.load_sharded(exe, load, main_program=main,
                                  mesh=make_mesh(mesh_axes)
                                  if mesh_axes else None)
            out["loaded"] = {
                v.name: np.asarray(scope.find_var(v.name))
                for v in main.list_vars() if v.persistable}
            return out
        losses = []
        for b in batches(steps):
            (lv,) = exe.run(main, feed=b, fetch_list=[loss])
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
        out["losses"] = np.asarray(losses)
        if opt_bytes:
            rep = observe.sharded_memory_report(
                main, feed=batches(1)[0], fetch_list=[loss],
                scope=scope)
            out["opt_bytes"] = observe.resident_state_bytes(rep)
        if ckpt:
            fluid.io.save_sharded(exe, ckpt, main_program=main)
            out["saved"] = {
                v.name: np.asarray(scope.find_var(v.name))
                for v in main.list_vars() if v.persistable}
    return out

# (1) ZeRO memory
base = run({"dp": 2}, opt_bytes=True)["opt_bytes"]
f2 = run({"fsdp": 2}, opt_bytes=True)["opt_bytes"]
f8 = run({"fsdp": 8}, opt_bytes=True)["opt_bytes"]
assert base / f2 >= 1.7, (base, f2)
assert base / f8 >= 8 * 0.75, (base, f8)

# (2) dp×mp parity + composed int8 determinism
single = run(None)["losses"]
dpmp = run({"dp": 4, "mp": 2}, mp=True)["losses"]
np.testing.assert_allclose(dpmp, single, rtol=1e-5, atol=1e-7)
cfg = GradSyncConfig("int8", min_quant_numel=1)
i8a = run({"dp": 4, "mp": 2}, grad_sync=cfg, mp=True)["losses"]
i8b = run({"dp": 4, "mp": 2}, grad_sync=cfg, mp=True)["losses"]
assert np.array_equal(i8a, i8b), "composed-mesh int8 not deterministic"
assert np.isfinite(i8a).all()

# (3) reshard-load: save at dp=8, resume at dp=4 and dp=2×mp=2
d = tempfile.mkdtemp(prefix="hybrid_reshard_")
saved = run({"dp": 8}, ckpt=d)["saved"]
for axes, mp_on in (({"dp": 4}, False), ({"dp": 2, "mp": 2}, True)):
    got = run(axes, mp=mp_on, load=d)["loaded"]
    for k, want in saved.items():
        assert np.array_equal(got[k], want), (axes, k)
print("hybrid-parallel smoke OK:",
      {"opt_bytes_dp2": base, "fsdp2": f2, "fsdp8": f8,
       "zero_drop_fsdp2": round(base / f2, 2),
       "zero_drop_fsdp8": round(base / f8, 2),
       "dpxmp_parity": True, "int8_composed_deterministic": True,
       "reshard_bit_identical": ["dp4", "dp2mp2"]})
EOF

echo "== quantized all-reduce parity smoke (8 virtual devices, cpu) =="
# ISSUE 10: the EQuARX blockwise-int8 exchange must stay (1) within
# its analytic error bound of the exact sum, (2) bitwise
# deterministic, (3) bit-exact below the quantization floor; and a
# 3-step int8-synced dp training run must track the explicit-bf16
# control arm (full suite: tests/test_quantized_allreduce.py +
# tests/test_grad_sync.py).
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
python - <<'EOF'
import numpy as np
import jax, jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.collectives import (all_reduce,
                                             quantized_all_reduce)

mesh = make_mesh({"dp": 8})
rng = np.random.RandomState(0)
x = rng.randn(8, 70000).astype(np.float32)
q = np.asarray(quantized_all_reduce(jnp.asarray(x), mesh, "dp"))
exact = x.mean(0)
rel = np.abs(q - exact).max() / np.abs(exact).max()
assert rel < 0.05, f"quantized mean off by {rel:.3f}"
q2 = np.asarray(quantized_all_reduce(jnp.asarray(x), mesh, "dp"))
assert (q == q2).all(), "quantized all-reduce not deterministic"
small = jnp.asarray(rng.randn(8, 200).astype(np.float32))
assert (np.asarray(quantized_all_reduce(small, mesh, "dp", op="sum"))
        == np.asarray(all_reduce(small, mesh, "dp", op="sum"))).all(), \
    "below-floor tensor did not ride the exact psum"

def run(mode):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        xv = layers.data("x", shape=[32], dtype="float32")
        yv = layers.data("y", shape=[1], dtype="float32")
        pred = layers.fc(layers.fc(xv, size=128, act="relu"), size=1)
        loss = layers.mean(layers.square_error_cost(pred, yv))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        bs = fluid.BuildStrategy()
        bs.grad_sync = mode
        fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, build_strategy=bs,
            mesh=make_mesh({"dp": 8}))
        r2 = np.random.RandomState(1)
        out = []
        for _ in range(3):
            (lv,) = exe.run(main, feed={
                "x": r2.randn(64, 32).astype(np.float32),
                "y": r2.randn(64, 1).astype(np.float32)},
                fetch_list=[loss])
            out.append(float(np.asarray(lv).reshape(-1)[0]))
    return np.asarray(out)

bf16, int8 = run("bf16"), run("int8")
drel = np.abs(int8 - bf16).max() / np.abs(bf16).max()
assert drel < 1e-2, f"int8 trajectory off bf16 by {drel:.2e}"
assert np.isfinite(int8).all()
print("quantized all-reduce smoke OK:",
      {"mean_rel_err": round(float(rel), 5),
       "deterministic": True, "floor_exact": True,
       "traj_rel_dev": round(float(drel), 6)})
EOF

echo "== perf gate (synthetic-regression smoke) =="
# the gate logic must actually catch a regression: a synthetic 10%
# throughput/MFU drop against the recorded chip baseline -> exit 1;
# the unmodified baseline against itself -> exit 0
python - <<'EOF'
import json, subprocess, sys
sys.path.insert(0, "tools")
from perf_gate import load_bench_artifact
base = load_bench_artifact("BENCH_r05.json")
ok = {"metric": "ci_smoke", "value": 1, "detail": base["detail"]}
json.dump(ok, open("/tmp/perf_gate_ok.json", "w"))
bad = json.loads(json.dumps(ok))
for m in bad["detail"].values():
    for k in ("tokens_per_sec", "imgs_per_sec", "examples_per_sec",
              "mfu"):
        if k in m:
            m[k] *= 0.9
json.dump(bad, open("/tmp/perf_gate_bad.json", "w"))
gate = [sys.executable, "tools/perf_gate.py", "--baseline",
        "BENCH_r05.json", "--candidate"]
r = subprocess.run(gate + ["/tmp/perf_gate_ok.json"],
                   capture_output=True, text=True)
assert r.returncode == 0, "gate false-failed:\n" + r.stderr
r = subprocess.run(gate + ["/tmp/perf_gate_bad.json"],
                   capture_output=True, text=True)
assert r.returncode == 1, \
    f"gate MISSED a 10% synthetic regression (rc={r.returncode}):\n" \
    + r.stdout + r.stderr
print("perf gate smoke OK: clean pass + synthetic 10% regression "
      "caught")
EOF

echo "CI OK"
