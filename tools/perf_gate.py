"""Performance regression gate: compare a fresh bench artifact against
a recorded baseline with per-metric tolerances.

Two modes, matching what each environment can actually verify:

- THROUGHPUT mode (default; run where a chip produced the candidate):
  per-model comparison of mfu / tokens_per_sec / imgs_per_sec /
  examples_per_sec (regression = relative drop beyond tolerance) and
  serving compute_ms (regression = relative increase).  Exit 1 on any
  regression, with a per-metric report.  Candidates tagged `profiled`
  are rejected outright — profiler-inflated numbers must never be
  gated (or baselined) as if clean.
- SCHEMA mode (--schema; run on every bench line a chip call brings
  back — `bench.py` measures on the chip only, so no CPU step runs it;
  the chip procedure in .claude/skills/verify/SKILL.md does):
  validate that a bench JSON line carries the observability contract —
  metric/value/unit/vs_baseline/detail plus compile_s/retraces/
  peak_mem_bytes/run_id/git_sha (docs/OBSERVE.md), and per training
  entry the checkpoint-cost fields (ckpt_blocking_ms/ckpt_write_ms,
  docs/RESILIENCE.md), the numerics-observability fields
  (grad_norm_last / update_ratio_worst, docs/OBSERVE.md pillar 6) and
  the goodput-ledger fields (goodput / effective_mfu /
  badput_breakdown, pillar 8) — so a line that lost a contract field
  is caught in the call that produced it, before anything is gated or
  baselined on it.

Baselines load from either a raw bench JSON line/file or a driver
wrapper ({"tail": ..., "parsed": ...}); a truncated wrapper tail (the
BENCH_r05.json case) is salvaged entry-by-entry with a balanced-brace
scan so the recorded chip numbers stay usable as a gate baseline.

Usage:
    python tools/perf_gate.py --baseline BENCH_r05.json \
        --candidate fresh.json [--tol-mfu 0.05] [--tol-throughput 0.07]
    python tools/perf_gate.py --schema --candidate line.json

Exit codes: 0 pass, 1 regression/schema violation, 2 unusable inputs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# headline metrics: higher is better, keyed by per-model detail entries
# (requests_per_sec = the serving_engine offered-load line;
# tokens_per_sec + examples_per_sec both gate the scan-bound lstm
# entry — throughput, not MFU, is the tracked axis there because the
# scan path's MFU numerator counts loop bodies once, see bench_lstm;
# the per_device_* trio gates dp-mesh entries — aggregate throughput
# can mask a per-device regression when the mesh grew, so both gate)
_THROUGHPUT_KEYS = ("tokens_per_sec", "imgs_per_sec",
                    "examples_per_sec", "requests_per_sec",
                    "per_device_tokens_per_sec",
                    "per_device_imgs_per_sec",
                    "per_device_examples_per_sec")
# serving latency: lower is better
_LATENCY_KEYS = ("compute_ms",)

# every bench line (success AND failure) must carry mem_breakdown —
# None on failure lines, the per-bucket byte dict (observe.memory) on
# measured ones; presence is the schema contract
_SCHEMA_FIELDS = ("metric", "value", "unit", "vs_baseline", "detail",
                  "compile_s", "retraces", "peak_mem_bytes",
                  "mem_breakdown", "run_id", "git_sha")


def _salvage_detail(tail: str):
    """Recover per-model entries from a truncated driver `tail`: scan
    for '"name": {' and balanced-brace-parse each object, keeping the
    ones that look like bench model entries."""
    import re

    out = {}
    i = 0
    pat = re.compile(r'"([A-Za-z0-9_]+)":\s*\{')
    while True:
        m = pat.search(tail, i)
        if not m:
            break
        depth = 0
        j = m.end() - 1
        while j < len(tail):
            if tail[j] == "{":
                depth += 1
            elif tail[j] == "}":
                depth -= 1
                if depth == 0:
                    break
            j += 1
        if depth != 0:
            break  # object itself truncated: stop
        try:
            obj = json.loads(tail[m.end() - 1:j + 1])
        except json.JSONDecodeError:
            i = m.end()
            continue
        if isinstance(obj, dict) and any(
                k in obj for k in ("mfu",) + _THROUGHPUT_KEYS
                + ("p50_ms", "error")):
            out[m.group(1)] = obj
            i = j + 1
        else:
            i = m.end()
    return out


def load_bench_artifact(path: str):
    """A bench artifact dict ({metric, value, detail, ...}) from a raw
    bench line/file or a driver wrapper, salvaging truncated tails."""
    with open(path) as f:
        raw = f.read()
    obj = None
    try:
        obj = json.loads(raw)
    except json.JSONDecodeError:
        for ln in reversed(raw.splitlines()):
            ln = ln.strip()
            if not ln.startswith("{"):
                continue
            try:
                obj = json.loads(ln)
                break
            except json.JSONDecodeError:
                continue
    if obj is None:
        raise ValueError(f"{path}: no parseable JSON")
    if isinstance(obj, dict) and "detail" in obj:
        return obj
    if isinstance(obj, dict) and ("tail" in obj or "parsed" in obj):
        parsed = obj.get("parsed")
        if isinstance(parsed, dict) and "detail" in parsed:
            return parsed
        tail = obj.get("tail") or ""
        for ln in reversed(tail.splitlines()):
            ln = ln.strip()
            if ln.startswith("{"):
                try:
                    inner = json.loads(ln)
                    if "detail" in inner:
                        return inner
                except json.JSONDecodeError:
                    pass
        detail = _salvage_detail(tail)
        if detail:
            return {"metric": "salvaged", "value": None,
                    "detail": detail, "salvaged": True}
    raise ValueError(f"{path}: not a bench artifact (no detail)")


def check_schema(candidate):
    errors = [f"missing field {f!r}" for f in _SCHEMA_FIELDS
              if f not in candidate]
    if not isinstance(candidate.get("detail"), dict):
        errors.append("detail is not an object")
        return errors
    # checkpoint-cost observability (ISSUE 7): every measured TRAINING
    # entry (it carries last_loss; serving/failure lines do not) must
    # report what a sharded save at that scale steals from the step
    # loop (ckpt_blocking_ms, None when the probe itself failed) vs
    # what the async writer hides (ckpt_write_ms)
    for name, entry in candidate["detail"].items():
        if not isinstance(entry, dict) or "error" in entry:
            continue
        if "last_loss" in entry and "ckpt_blocking_ms" not in entry:
            errors.append(f"detail.{name}: training entry missing "
                          f"ckpt_blocking_ms (async-checkpoint cost "
                          f"observability)")
        if "last_loss" in entry:
            # numerics observability (observe pillar 6): a training
            # entry must carry the window's grad norm and worst-group
            # update ratio (None only when measured --no-telemetry),
            # so divergence/dead-layer evidence rides the artifact
            for field in ("grad_norm_last", "update_ratio_worst"):
                if field not in entry:
                    errors.append(f"detail.{name}: training entry "
                                  f"missing {field!r} (numerics "
                                  f"observability, docs/OBSERVE.md "
                                  f"pillar 6)")
            # wall-clock goodput (observe pillar 8): a training entry
            # must decompose its harness wall next to the headline —
            # goodput (step fraction), effective_mfu (headline x
            # goodput) and the badput_breakdown category fractions
            for field in ("goodput", "effective_mfu",
                          "badput_breakdown"):
                if field not in entry:
                    errors.append(f"detail.{name}: training entry "
                                  f"missing {field!r} (goodput "
                                  f"ledger, docs/OBSERVE.md pillar 8)")
        # span-derived phase breakdown (ISSUE 15, observe pillar 7): a
        # serving latency number without its queue/form/dispatch
        # decomposition cannot answer "where did the time go" — the
        # offered-load entries must carry the tracer-derived keys next
        # to their e2e/TTFT/TPOT numbers
        _PHASE_KEYS = {
            "serving_engine": ("queue_wait_ms_p50", "queue_wait_ms_p99",
                               "batch_form_ms_p50", "dispatch_ms_p50"),
            "serving_decode": ("join_wait_ms_p50", "dispatch_ms_p50"),
            "serving_fleet": ("join_wait_ms_p50", "dispatch_ms_p50"),
        }
        for prefix, keys in _PHASE_KEYS.items():
            if name == prefix or (name.startswith(prefix)
                                  and prefix != "serving_engine"):
                for field in keys:
                    if field not in entry:
                        errors.append(
                            f"detail.{name}: missing {field!r} "
                            f"(span-derived phase breakdown, observe "
                            f"pillar 7)")
                break
        if name.startswith("serving_fleet"):
            # fleet contract (ISSUE 14, docs/SERVING.md §fleet): a
            # replicated-serving entry must carry the offered-load
            # throughput, the failover/hedge/retry evidence, the
            # reload pause, and the fleet-wide zero-recompile proof —
            # a req/s number that silently dropped requests or
            # recompiled mid-roll is not a resilience number
            for field in ("requests_per_sec", "failover_count",
                          "hedged", "retried", "reload_pause_ms",
                          "post_warmup_compiles"):
                if field not in entry:
                    errors.append(f"detail.{name}: fleet entry "
                                  f"missing {field!r} (fleet "
                                  f"resilience contract)")
            if entry.get("post_warmup_compiles"):
                errors.append(
                    f"detail.{name}: {entry['post_warmup_compiles']} "
                    f"post-warmup compile(s) — a shape leaked or a "
                    f"reload recompiled (the fleet-wide zero-recompile "
                    f"contract)")
            if entry.get("zero_client_failures") is False:
                errors.append(
                    f"detail.{name}: client-visible failures during "
                    f"the chaos run (the zero-failure fleet contract)")
        if name.startswith("serving_disagg"):
            # disagg contract (ISSUE 18, docs/SERVING.md §disagg): a
            # phase-disaggregated entry must carry the JOINT client
            # TTFT (submit -> first token across the prefill hop), the
            # steady decode throughput, the measured handoff tax
            # (latency + pages moved), and the fleet-wide
            # zero-recompile proof — the KV-page import must never
            # recompile the decode executable
            for field in ("ttft_p99_ms", "tokens_per_sec",
                          "handoff_ms_p50", "pages_transferred",
                          "post_warmup_compiles"):
                if field not in entry:
                    errors.append(f"detail.{name}: disagg entry "
                                  f"missing {field!r} (disagg serving "
                                  f"contract)")
            if entry.get("post_warmup_compiles"):
                errors.append(
                    f"detail.{name}: {entry['post_warmup_compiles']} "
                    f"post-warmup compile(s) — a handoff import or "
                    f"scale event recompiled (the disagg fleet-wide "
                    f"zero-recompile contract)")
            if entry.get("zero_client_failures") is False:
                errors.append(
                    f"detail.{name}: client-visible failures during "
                    f"the disagg run (the zero-failure contract)")
            if entry.get("token_parity_vs_unified") is False:
                errors.append(
                    f"detail.{name}: disagg tokens diverged from the "
                    f"unified fleet (greedy decode must be "
                    f"bit-identical across the KV handoff)")
        if name.startswith("serving_decode"):
            # decode contract (ISSUE 12, docs/SERVING.md §decode): a
            # continuous-batching decode entry must carry the
            # steady-state throughput, the scheduler's occupancy/
            # preemption telemetry, and the zero-recompile proof —
            # a tokens/s number without them is not interpretable
            for field in ("tokens_per_sec", "slot_occupancy",
                          "kv_page_utilization", "preemptions",
                          "post_warmup_compiles"):
                if field not in entry:
                    errors.append(f"detail.{name}: decode entry "
                                  f"missing {field!r} (decode "
                                  f"telemetry contract)")
            if entry.get("post_warmup_compiles"):
                errors.append(
                    f"detail.{name}: {entry['post_warmup_compiles']} "
                    f"post-warmup compile(s) — a shape leaked across "
                    f"joins/leaves/preemptions (the zero-recompile "
                    f"decode contract)")
        if entry.get("speculate"):
            # speculative contract (ISSUE 20, docs/SERVING.md
            # §speculate): a speculative entry must carry the accept
            # rate with its k+1-bin histogram, the measured speedup
            # against the sequential twin, and the token-parity proof
            # — a speculative tokens/s number whose committed stream
            # diverged from greedy decode is wrong, not fast
            for field in ("accept_rate", "accept_hist",
                          "speculation_efficiency",
                          "speedup_vs_sequential", "token_parity",
                          "post_warmup_compiles"):
                if field not in entry:
                    errors.append(f"detail.{name}: speculative entry "
                                  f"missing {field!r} (speculative "
                                  f"decode contract)")
            if entry.get("token_parity") is False:
                errors.append(
                    f"detail.{name}: speculative tokens diverged from "
                    f"the sequential engine (verified acceptance must "
                    f"be bit-identical to greedy decode)")
            hist = entry.get("accept_hist")
            if (isinstance(hist, list)
                    and len(hist) != int(entry["speculate"]) + 1):
                errors.append(
                    f"detail.{name}: accept_hist has {len(hist)} bins "
                    f"for k={entry['speculate']} (want k+1)")
            if entry.get("post_warmup_compiles"):
                errors.append(
                    f"detail.{name}: {entry['post_warmup_compiles']} "
                    f"post-warmup compile(s) in a speculative run — "
                    f"draft/verify must compile inside the warmup "
                    f"window for ANY accept pattern")
        if "mesh" in entry:
            # mesh contract (ISSUE 10 + 13, docs/DIST.md): a multi-chip
            # entry must carry per-device AND aggregate throughput, the
            # comm-bucket bytes, and — since the fsdp/ZeRO axis — the
            # per-device optimizer-state bytes of the sharded step (a
            # mesh number without its memory footprint cannot back a
            # ZeRO claim); the mesh itself must name its axes
            for field in ("n_devices", "comm_bytes", "grad_sync",
                          "opt_state_bytes_per_device"):
                if field not in entry:
                    errors.append(f"detail.{name}: mesh entry missing "
                                  f"{field!r}")
            if not (isinstance(entry["mesh"], dict) and entry["mesh"]
                    and all(isinstance(s, int) and s >= 1
                            for s in entry["mesh"].values())):
                errors.append(f"detail.{name}: mesh entry's mesh must "
                              f"be a non-empty axis->size dict, got "
                              f"{entry['mesh']!r}")
            if not any(k.startswith("per_device_") for k in entry):
                errors.append(f"detail.{name}: mesh entry missing "
                              f"per_device_* throughput")
    return errors


def _compare_entry(name, base, cand, tol_mfu, tol_tp, tol_lat,
                   regressions, report, tol_mem=0.10, tol_ls=0.02,
                   tol_comm=0.10, tol_gp=0.05, tol_ar=0.05):
    if "error" in cand and "error" not in base:
        regressions.append(f"{name}: candidate errored: "
                           f"{cand['error']}")
        return
    if base.get("mesh") != cand.get("mesh") or \
            base.get("grad_sync") != cand.get("grad_sync"):
        # a dp entry gates only against the SAME mesh + sync mode —
        # comparing dp8 throughput to a single-chip baseline (or int8
        # to bf16) would be apples-to-oranges in both directions
        report.append(f"{name}: mesh/grad_sync mismatch "
                      f"({base.get('mesh')}/{base.get('grad_sync')} vs "
                      f"{cand.get('mesh')}/{cand.get('grad_sync')}) — "
                      f"not compared")
        return
    if cand.get("skipped_update_steps"):
        # bench honesty: a throughput number that "improved" by
        # skipping optimizer math is not a number at all
        regressions.append(
            f"{name}: {cand['skipped_update_steps']} optimizer "
            f"update(s) SKIPPED inside the measured window (non-finite "
            f"taint) — throughput/MFU not comparable")
    # base mfu can legitimately round to 0.0 (CPU-smoke dp entries);
    # only a nonzero baseline can gate a relative drop
    if base.get("mfu") and "mfu" in cand:
        drop = (base["mfu"] - cand["mfu"]) / base["mfu"]
        line = (f"{name}.mfu: {base['mfu']:.4f} -> {cand['mfu']:.4f} "
                f"({-drop:+.2%})")
        report.append(line)
        if drop > tol_mfu:
            regressions.append(line + f" exceeds tol {tol_mfu:.0%}")
    for key in _THROUGHPUT_KEYS:
        if key in base and key in cand and base[key]:
            drop = (base[key] - cand[key]) / base[key]
            line = (f"{name}.{key}: {base[key]:.1f} -> "
                    f"{cand[key]:.1f} ({-drop:+.2%})")
            report.append(line)
            if drop > tol_tp:
                regressions.append(line + f" exceeds tol {tol_tp:.0%}")
    for key in _LATENCY_KEYS:
        if key in base and key in cand and base[key]:
            rise = (cand[key] - base[key]) / base[key]
            line = (f"{name}.{key}: {base[key]:.3f} -> "
                    f"{cand[key]:.3f} ({rise:+.2%})")
            report.append(line)
            if rise > tol_lat:
                regressions.append(line + f" exceeds tol {tol_lat:.0%}")
    # peak memory: higher is worse (closer to OOM at the same shape).
    # Compared only when BOTH sides measured a buffer-assignment peak —
    # pre-r06 baselines carry no mem_breakdown and are skipped, and
    # the estimate-quality "module-shapes" fallback never gates against
    # a real buffer_assignment number (different accounting)
    bmb, cmb = base.get("mem_breakdown"), cand.get("mem_breakdown")
    if isinstance(bmb, dict) and isinstance(cmb, dict) \
            and bmb.get("peak_bytes") and cmb.get("peak_bytes") \
            and bmb.get("source") == cmb.get("source"):
        rise = (cmb["peak_bytes"] - bmb["peak_bytes"]) \
            / bmb["peak_bytes"]
        line = (f"{name}.peak_hbm: {bmb['peak_bytes'] / 1e6:.1f}MB -> "
                f"{cmb['peak_bytes'] / 1e6:.1f}MB ({rise:+.2%})")
        report.append(line)
        if rise > tol_mem:
            regressions.append(line + f" exceeds tol {tol_mem:.0%}")
    # layout traffic: the layout-bucket byte share of the step
    # (transpose/copy — the r05 longctx finding).  ABSOLUTE share-point
    # increase gates: after the head-major layout (ISSUE 8) deleted the
    # boundary transposes, a change that quietly reintroduces them is a
    # regression even when throughput noise hides it at small steps.
    bls, cls = base.get("layout_share"), cand.get("layout_share")
    if isinstance(bls, (int, float)) and isinstance(cls, (int, float)):
        rise = cls - bls
        line = (f"{name}.layout_share: {bls:.4f} -> {cls:.4f} "
                f"({rise:+.4f})")
        report.append(line)
        if rise > tol_ls:
            regressions.append(
                line + f" exceeds tol +{tol_ls:.2f} share points")
    # dp comm traffic: modeled per-device collective bytes per step
    # (same mesh + grad_sync guaranteed above).  Growth beyond
    # tolerance is a regression even when throughput noise hides it —
    # gradient-exchange bytes creeping back is exactly what the
    # quantized path exists to prevent.
    bcb, ccb = base.get("comm_bytes"), cand.get("comm_bytes")
    if isinstance(bcb, (int, float)) and isinstance(ccb, (int, float)) \
            and bcb:
        rise = (ccb - bcb) / bcb
        line = (f"{name}.comm_bytes: {bcb / 1e6:.1f}MB -> "
                f"{ccb / 1e6:.1f}MB ({rise:+.2%})")
        report.append(line)
        if rise > tol_comm:
            regressions.append(line + f" exceeds tol {tol_comm:.0%}")
    # wall-clock goodput (observe pillar 8): the step share of the
    # harness wall.  ABSOLUTE share-point drop gates, and ONLY between
    # same-shaped runs (same measured step count) — the warmup/compile
    # split scales with steps, so cross-shape goodput fractions are
    # apples-to-oranges (the same-source rule, like mem_breakdown's
    # source match above)
    bgp, cgp = base.get("goodput"), cand.get("goodput")
    if isinstance(bgp, (int, float)) and isinstance(cgp, (int, float)) \
            and base.get("steps") == cand.get("steps"):
        fall = bgp - cgp
        line = (f"{name}.goodput: {bgp:.4f} -> {cgp:.4f} "
                f"({-fall:+.4f})")
        report.append(line)
        if fall > tol_gp:
            regressions.append(
                line + f" exceeds tol -{tol_gp:.2f} share points")
    # speculative accept rate (ISSUE 20): the drafter's health number.
    # ABSOLUTE drop gates, and only between same-k speculative runs —
    # on the deterministic CPU stream the accept rate is a pure
    # function of drafter + model + prompts, so a fall means drafting
    # quality regressed even when wall-clock noise hides it.  The
    # speedup itself is NOT gated here (host-timing noise); the
    # accept rate is its noise-free proxy.
    bar, car = base.get("accept_rate"), cand.get("accept_rate")
    if isinstance(bar, (int, float)) and isinstance(car, (int, float)) \
            and base.get("speculate") == cand.get("speculate"):
        fall = bar - car
        line = (f"{name}.accept_rate: {bar:.4f} -> {car:.4f} "
                f"({-fall:+.4f})")
        report.append(line)
        if fall > tol_ar:
            regressions.append(
                line + f" exceeds tol -{tol_ar:.2f} (drafting quality "
                f"regressed)")
    # ZeRO opt-state footprint: per-device resident accumulator bytes
    # of the sharded step (same mesh + grad_sync guaranteed above) —
    # creeping back up means the fsdp sharding quietly stopped applying
    bob, cob = (base.get("opt_state_bytes_per_device"),
                cand.get("opt_state_bytes_per_device"))
    if isinstance(bob, (int, float)) and isinstance(cob, (int, float)) \
            and bob:
        rise = (cob - bob) / bob
        line = (f"{name}.opt_state_bytes_per_device: "
                f"{bob / 1e6:.1f}MB -> {cob / 1e6:.1f}MB ({rise:+.2%})")
        report.append(line)
        if rise > tol_mem:
            regressions.append(line + f" exceeds tol {tol_mem:.0%}")


def gate(baseline, candidate, tol_mfu=0.05, tol_tp=0.07, tol_lat=0.10,
         tol_mem=0.10, tol_ls=0.02, tol_comm=0.10, tol_gp=0.05,
         tol_ar=0.05, allow_missing=False):
    """(regressions, report_lines, compared_count).  Only entries whose
    device kind matches are compared — a CPU smoke candidate never
    false-fails against chip numbers."""
    regressions, report = [], []
    compared = 0
    base_detail = baseline.get("detail", {})
    cand_detail = candidate.get("detail", {})
    for name, base in sorted(base_detail.items()):
        if not isinstance(base, dict):
            continue
        cand = cand_detail.get(name)
        if cand is None:
            if not allow_missing:
                regressions.append(
                    f"{name}: present in baseline, missing from "
                    f"candidate (pass --allow-missing for partial "
                    f"--model runs)")
            continue
        bdev, cdev = base.get("device"), cand.get("device")
        if bdev and cdev and bdev != cdev:
            report.append(f"{name}: device mismatch ({bdev!r} vs "
                          f"{cdev!r}) — not compared")
            continue
        compared += 1
        _compare_entry(name, base, cand, tol_mfu, tol_tp, tol_lat,
                       regressions, report, tol_mem=tol_mem,
                       tol_ls=tol_ls, tol_comm=tol_comm, tol_gp=tol_gp,
                       tol_ar=tol_ar)
        if "int8" in base and isinstance(cand.get("int8"), dict) \
                and "error" not in base["int8"]:
            if "error" in cand["int8"]:
                regressions.append(
                    f"{name}.int8: candidate errored: "
                    f"{cand['int8']['error']}")
            else:
                _compare_entry(f"{name}.int8", base["int8"],
                               cand["int8"], tol_mfu, tol_tp, tol_lat,
                               regressions, report)
    return regressions, report, compared


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--baseline", default="BENCH_r05.json")
    p.add_argument("--candidate", required=True,
                   help="fresh bench artifact (the one JSON line, a "
                        "file holding it, or a driver wrapper)")
    p.add_argument("--schema", action="store_true",
                   help="validate the bench-line observability schema "
                        "instead of comparing numbers")
    p.add_argument("--tol-mfu", type=float, default=0.05,
                   help="tolerated relative MFU drop (default 5%%)")
    p.add_argument("--tol-throughput", type=float, default=0.07,
                   help="tolerated relative throughput drop "
                        "(default 7%% — bench noise at 60 steps)")
    p.add_argument("--tol-latency", type=float, default=0.10,
                   help="tolerated relative serving-latency increase")
    p.add_argument("--tol-peak-mem", type=float, default=0.10,
                   help="tolerated relative peak-HBM increase per "
                        "entry (mem_breakdown.peak_bytes; a step "
                        "quietly growing toward OOM is a regression "
                        "even when throughput holds)")
    p.add_argument("--tol-layout-share", type=float, default=0.02,
                   help="tolerated ABSOLUTE increase in an entry's "
                        "layout_share (the layout-bucket byte "
                        "fraction, observe.cost) — transpose traffic "
                        "creeping back after the head-major layout "
                        "(ISSUE 8) is a regression even when "
                        "throughput noise hides it")
    p.add_argument("--tol-comm-bytes", type=float, default=0.10,
                   help="tolerated relative increase in a dp entry's "
                        "comm_bytes (modeled per-device collective "
                        "bytes per step, observe.cost comm bucket) — "
                        "gradient-exchange traffic creeping back is a "
                        "regression even when throughput noise hides "
                        "it.  Compared only between entries with the "
                        "same mesh AND grad_sync mode")
    p.add_argument("--tol-goodput", type=float, default=0.05,
                   help="tolerated ABSOLUTE drop in a training entry's "
                        "goodput fraction (observe pillar 8 wall-clock "
                        "ledger).  Compared only between entries that "
                        "measured the SAME step count — the harness "
                        "warmup/compile split scales with steps, so "
                        "cross-shape goodput is not comparable (the "
                        "same-source rule)")
    p.add_argument("--tol-accept-rate", type=float, default=0.05,
                   help="tolerated ABSOLUTE drop in a speculative "
                        "entry's accept_rate (ISSUE 20) — on the "
                        "deterministic CPU stream the accept rate is "
                        "a pure function of drafter + model + "
                        "prompts, so a fall means drafting quality "
                        "regressed even when timing noise hides it. "
                        "Compared only between same-k runs")
    p.add_argument("--allow-missing", action="store_true",
                   help="baseline entries absent from the candidate "
                        "are not regressions (partial --model runs)")
    args = p.parse_args()

    try:
        candidate = load_bench_artifact(args.candidate)
    except (OSError, ValueError) as e:
        print(f"perf_gate: cannot load candidate: {e}",
              file=sys.stderr)
        return 2

    if args.schema:
        errors = check_schema(candidate)
        if errors:
            print("perf_gate SCHEMA FAIL:\n  " + "\n  ".join(errors),
                  file=sys.stderr)
            return 1
        print(f"perf_gate schema OK: {args.candidate} carries "
              f"{len(_SCHEMA_FIELDS)} contract fields "
              f"(metric={candidate['metric']!r})")
        return 0

    try:
        baseline = load_bench_artifact(args.baseline)
    except (OSError, ValueError) as e:
        print(f"perf_gate: cannot load baseline: {e}", file=sys.stderr)
        return 2

    if candidate.get("profiled"):
        print("perf_gate: candidate was captured under --profile — "
              "profiler-inflated numbers are not gateable", file=sys.stderr)
        return 2
    if candidate.get("nonfinite_flag") or \
            candidate.get("skipped_update_steps"):
        print("perf_gate: candidate measured windows contained "
              f"non-finite steps (nonfinite={candidate.get('nonfinite_steps')}, "
              f"skipped_updates={candidate.get('skipped_update_steps')})"
              " — numbers produced while training was diverging or "
              "updates were skipped are not gateable", file=sys.stderr)
        return 2

    regressions, report, compared = gate(
        baseline, candidate, tol_mfu=args.tol_mfu,
        tol_tp=args.tol_throughput, tol_lat=args.tol_latency,
        tol_mem=args.tol_peak_mem, tol_ls=args.tol_layout_share,
        tol_comm=args.tol_comm_bytes, tol_gp=args.tol_goodput,
        tol_ar=args.tol_accept_rate, allow_missing=args.allow_missing)
    for line in report:
        print("  " + line)
    if compared == 0:
        print("perf_gate: no comparable entries (device mismatch or "
              "disjoint models) — refusing to report a vacuous pass",
              file=sys.stderr)
        return 2
    if regressions:
        print("perf_gate REGRESSIONS:\n  " + "\n  ".join(regressions),
              file=sys.stderr)
        return 1
    print(f"perf_gate OK: {compared} model entr"
          f"{'y' if compared == 1 else 'ies'} within tolerance "
          f"(baseline {os.path.basename(args.baseline)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
