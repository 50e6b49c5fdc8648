"""A/B benchmark driver (VERDICT r3 item 1b): run bench.py once per
perf-feature configuration on the real chip and write a combined
AB artifact with the winners, so every bench default reflects a
measured win.

Usage: python tools/run_ab.py [--steps N] [--out AB_r12.json]
Each variant is a separate bench.py subprocess, one after the other;
this parent never imports jax, so each child gets the chip.  The
children share the persistent compilation cache
(paddle_tpu/compile_cache.py).  r11: every pair's summary carries goodput
context (`<name>_goodput` — each side's harness-wall step fraction +
effective_mfu, observe pillar 8) so a throughput verdict bought with
badput is visible in the artifact itself.  r12: the speculative-decode
pair (`decode_spec_k4`, ISSUE 20) compares same-stream twins measured
INSIDE one variant entry — bench --speculate runs the sequential twin
itself, asserts token parity, and records both tokens/s.

r06 added the scan-bound lstm variants (unroll sweep + the Pallas fused
recurrence kernel vs the scan base).  r08 adds the dp-mesh pair
(ISSUE 10): dp8_bf16 (implicit GSPMD gradient all-reduce) vs
dp8_int8ar (EQuARX blockwise-int8 quantized exchange, --grad-sync
int8), with per-pair comm_bytes context in the summary — on the 8-CPU
virtual mesh the pair records correctness + comm-byte deltas; the
grad-sync default only flips on a chip throughput win.  r10 adds the
hybrid-parallel ladder (ISSUE 13): fsdp2/4/8 (ZeRO-sharded optimizer
state — the summary's fsdp_opt_state_scaling records the per-device
opt-state byte drop vs dp8) and the composed dp2mp2 pair (Megatron mp
sharding × dp, int8 riding the psum-form exchange).  The fsdp claim
is MEMORY; throughput decides defaults, device-tagged as always.  r07 added the
head-major layout
variants (ISSUE 8): transformer_headmajor / transformer_pallas_headmajor
record the layout at the short-seq headline shape — the latter is the
r05 pallas-attn crossover question (136.7k vs 157.1k tok/s at len256:
does deleting the boundary transposes flip it?) — and
longctx_8k_headmajor is the headline lever (the r05 profile's ~15.9 s
of copy/transpose).  Every transformer/longctx entry now carries
`layout_share` so the summary's throughput verdicts come with the
layout-traffic delta attached.  bench.py refuses to run without a
chip, so every entry written from here on is a chip entry.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

VARIANTS = [
    # (key, argv fragment)
    ("resnet50_nchw", ["--model", "resnet50", "--layout", "NCHW"]),
    ("resnet50_nhwc", ["--model", "resnet50", "--layout", "NHWC"]),
    # flags are explicit on both sides so the variant set stays
    # meaningful if a default ever flips.  NOTE the r05 lesson baked
    # into wins(): fused-CE's higher MFU at len256 was a NUMERATOR
    # artifact (dense-equivalent twin vs the base program's own XLA
    # count) while wall-clock lost — wins() therefore compares
    # throughput, which is numerator-free.
    ("transformer_base", ["--model", "transformer", "--no-fused-ce"]),
    ("transformer_fused_ce", ["--model", "transformer", "--fused-ce"]),
    ("transformer_fused_qkv", ["--model", "transformer", "--fused-qkv",
                               "--no-fused-ce"]),
    ("transformer_fused_both", ["--model", "transformer", "--fused-ce",
                                "--fused-qkv"]),
    ("transformer_pallas_attn", ["--model", "transformer",
                                 "--pallas-attn", "--no-fused-ce"]),
    # head-major layouts (ISSUE 8): activations stay in the flash
    # kernels' head-grouped convention end-to-end — zero transposes at
    # kernel boundaries.  NOTE head-major also routes decoder CROSS
    # attention through the flash op (the composed path would
    # reintroduce the transposes), recorded in each entry's
    # head_major/flash fields.
    ("transformer_headmajor", ["--model", "transformer",
                               "--head-major", "--no-fused-ce"]),
    # the r05 short-seq crossover question: pallas-attn lost 136.7k vs
    # 157.1k tok/s at len256 with the transpose round-trip; this is the
    # same kernel with the round-trip deleted
    ("transformer_pallas_headmajor", ["--model", "transformer",
                                      "--pallas-attn", "--head-major",
                                      "--no-fused-ce"]),
    # long-context (VERDICT r4 item 7): Pallas flash (self+cross) +
    # fused-CE + recompute is the default longctx stack; the xla twin
    # runs the same shape through the XLA flash composition to check
    # the kernel actually pays at 8k
    ("longctx_8k_pallas", ["--model", "longctx"]),
    # the XLA flash composition CANNOT fit 8k without remat (r05 chip:
    # 38.45G HBM needed, jax AD keeps per-layer attention residuals the
    # Pallas kernel's custom VJP recomputes from lse) — so the xla side
    # runs its best VIABLE config (with recompute); the pallas side
    # runs its own best (without).  Backend-best vs backend-best.
    ("longctx_8k_xla", ["--model", "longctx", "--xla-attn",
                        "--recompute"]),
    # the longctx default flipped to no-recompute after this A/B
    # measured 0.3035 vs 0.2405 (bs2/8k fits without remat); the
    # recompute variant stays recorded for the memory-constrained case
    ("longctx_8k_recompute", ["--model", "longctx", "--recompute"]),
    # head-major longctx: THE identified r05 lever — the recorded
    # device profile showed ~15.9 s copy/transpose in-flight against
    # ~5.0 s flash-kernel time; head-major deletes that traffic class
    ("longctx_8k_headmajor", ["--model", "longctx", "--head-major"]),
    # shape probes (r05 chip session): both LOSE to the defaults
    # (bs4 longctx 0.2322 vs 0.2405; bs128 transformer 0.3046 vs
    # 0.3254 — bs64/len256 confirmed as the sweet spot)
    ("longctx_8k_bs4", ["--model", "longctx", "--batch", "4"]),
    ("transformer_bs128", ["--model", "transformer", "--batch", "128"]),
    # scaling proof: 16k tokens on ONE chip, MFU RISES with T (flash
    # fraction grows; dense attention stopped existing back at 8k)
    ("longctx_16k_bs1", ["--model", "longctx", "--seq", "16384",
                         "--batch", "1"]),
    # scan-bound lstm (ISSUE 5): the r05 outlier at 0.078 MFU.  The
    # unroll sweep is the cheap XLA-side lever (bit-identical
    # numerics); pallas_rnn is the fused recurrence kernel.  wins()
    # compares tokens/sec as everywhere — lstm MFU numerators are NOT
    # comparable across these variants (scan entries count loop bodies
    # once, pallas entries use the kernel registry).
    ("lstm_base", ["--model", "lstm"]),
    ("lstm_unroll2", ["--model", "lstm", "--rnn-unroll", "2"]),
    ("lstm_unroll4", ["--model", "lstm", "--rnn-unroll", "4"]),
    ("lstm_unroll8", ["--model", "lstm", "--rnn-unroll", "8"]),
    ("lstm_pallas_rnn", ["--model", "lstm", "--pallas-rnn"]),
    # dp-mesh gradient exchange (ISSUE 10, docs/DIST.md): the bf16 side
    # is the default implicit GSPMD all-reduce, the int8 side the
    # EQuARX blockwise-quantized two-phase exchange.  On the 8-CPU
    # virtual mesh this pair records CORRECTNESS + the comm-bytes delta
    # (each entry carries comm_bytes from the sharded step's comm
    # bucket); the wall-clock verdict that could flip the --grad-sync
    # default needs a real multi-chip slice, per the device-tag rule.
    ("dp8_bf16", ["--model", "transformer", "--mesh", "dp=8"]),
    ("dp8_int8ar", ["--model", "transformer", "--mesh", "dp=8",
                    "--grad-sync", "int8"]),
    # r10 (ISSUE 13): the fsdp/ZeRO ladder — same data-parallel math
    # as dp=N (loss parity pinned in tests/test_hybrid_parallel.py)
    # with optimizer state sharded ~1/N per device.  The A/B claim is
    # MEMORY (each entry's opt_state_bytes_per_device, summarized as
    # fsdp_opt_state_scaling); throughput decides defaults as
    # everywhere, per the device-tag rule.
    ("fsdp2", ["--model", "transformer", "--mesh", "fsdp=2"]),
    ("fsdp4", ["--model", "transformer", "--mesh", "fsdp=4"]),
    ("fsdp8", ["--model", "transformer", "--mesh", "fsdp=8"]),
    # the composed dp×mp mesh record: Megatron-sharded params + data
    # parallelism in ONE entry (keyed transformer_dp2mp2), with the
    # int8 exchange riding the psum-form on the composed mesh
    ("dp2mp2", ["--model", "transformer", "--mesh", "dp=2,mp=2"]),
    ("dp2mp2_int8ar", ["--model", "transformer", "--mesh", "dp=2,mp=2",
                       "--grad-sync", "int8"]),
    # r09: the paged-KV decode cache precision pair (ISSUE 12 stretch).
    # int8 pools halve KV bytes vs bf16 (per-row f32 scale sidecars,
    # the blockwise scheme of parallel/collectives.py) — whether that
    # converts to tokens/s depends on whether decode attention is
    # pool-bandwidth-bound at the benched geometry.  wins() compares
    # the decode entry's tokens_per_sec as everywhere; the kv default
    # stays bf16 pending a chip wall-clock win (device-tag rule).
    ("serving_decode_kv_bf16", ["--model", "serving_decode"]),
    ("serving_decode_kv_int8", ["--model", "serving_decode",
                                "--kv-int8"]),
    # r12: speculative decode (ISSUE 20).  The sequential side of this
    # pair is measured INSIDE the variant itself — bench --speculate
    # runs a sequential twin engine over the same stream/arch first
    # (token parity asserted) and records sequential_tokens_per_sec —
    # so the verdict compares same-stream twins, never the
    # differently-shaped serving_decode entry above.
    ("serving_decode_spec_k4", ["--model", "serving_decode",
                                "--speculate", "4"]),
]


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_tag():
    """This invocation's provenance stamp.  Made here, without
    paddle_tpu.observe.events: importing the package imports jax, and
    this parent stays off jax so that each child gets the chip."""
    import uuid

    try:
        r = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                           capture_output=True, text=True, timeout=5,
                           cwd=_ROOT)
        sha = r.stdout.strip() if r.returncode == 0 else None
    except (OSError, subprocess.SubprocessError):
        sha = None
    return {"run_id": uuid.uuid4().hex[:12], "git_sha": sha or None}


def run_variant(args, extra):
    # one process per chip: the bench.py child needs it, so this parent
    # must never have touched jax (a parent that holds the chip makes
    # the child fail or hang)
    assert "jax" not in sys.modules, \
        "run_ab's parent imported jax; its bench.py children need the chip"
    cmd = ([sys.executable, "bench.py", "--steps", str(args.steps)]
           + (args.bench_args.split() if args.bench_args else [])
           + extra)
    t0 = time.time()
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=args.timeout,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
    except subprocess.TimeoutExpired:
        return {"error": f"variant timed out after {args.timeout}s"}
    line = None
    for ln in reversed(r.stdout.strip().splitlines()):
        ln = ln.strip()
        if ln.startswith("{"):
            line = ln
            break
    if line is None:
        tail = (r.stderr or r.stdout).strip().splitlines()[-3:]
        return {"error": "no JSON line: " + " | ".join(tail)}
    out = json.loads(line)
    out["wall_s"] = round(time.time() - t0, 1)
    return out


# variant key -> the bench model its argv requests; the throughput
# lookup below must read THAT model's detail entry, not whatever dict
# order yields first (ADVICE r5: a longctx line carrying an extra
# sub-entry would have fed the wrong model's tok/s into the summary)
_VARIANT_MODEL = {
    key: argv[argv.index("--model") + 1]
    for key, argv in VARIANTS if "--model" in argv
}


def _model_entries(detail, model):
    """Sub-entries belonging to `model`: exact key or model-prefixed
    (bench keys resolved shapes into names like longctx_8k,
    resnet50_frozen)."""
    return [sub for name, sub in detail.items()
            if isinstance(sub, dict)
            and (name == model or name.startswith(model + "_"))]


def measure(results, k):
    """Comparable scalar for variant k, or None for NO DATA.

    A failed bench prints {"metric": "bench_failed", "value": 0.0}
    (and run_variant itself may record {"error": ...}): both are NO
    DATA, never a 0.0 that hands the other side a vacuous win.
    The lookup is keyed by the variant's EXPECTED model (falling back
    to a sole sub-entry for foreign/legacy artifacts): multi-entry
    details must never contribute another model's number.
    Prefers THROUGHPUT over MFU: variants can carry different MFU
    numerators (the program's own XLA count vs the dense-equivalent
    twin for Pallas/remat configs), and the r05 chip session caught
    fused-CE "winning" on MFU while losing wall-clock.  tok/s and
    img/s are numerator-free.  No throughput recorded -> None; falling
    back to the MFU value would re-open the cross-numerator comparison
    this function exists to prevent."""
    d = results.get(k, {})
    if "error" in d or "failed" in d or \
            d.get("metric") == "bench_failed":
        return None
    detail = d.get("detail") or {}
    model = _VARIANT_MODEL.get(k)
    if model is not None:
        subs = _model_entries(detail, model)
    else:
        # unknown variant key (hand-rolled artifact): only an
        # unambiguous single-entry detail is trustworthy
        subs = [sub for sub in detail.values() if isinstance(sub, dict)]
        if len(subs) != 1:
            return None
    for sub in subs:
        for key in ("tokens_per_sec", "imgs_per_sec",
                    "examples_per_sec"):
            if key in sub:
                return sub[key]
    return None


def mem_measure(results, k):
    """Peak device bytes for variant k, or None for NO DATA.

    Prefers the expected model entry's `mem_breakdown.peak_bytes`
    (buffer-assignment analysis of the measured step, observe.memory)
    and falls back to the line's host-side `peak_mem_bytes` high-water
    mark.  Same no-data discipline as measure(): a failed variant
    contributes None, never a 0 that fakes a memory win."""
    d = results.get(k, {})
    if "error" in d or "failed" in d or \
            d.get("metric") == "bench_failed":
        return None
    detail = d.get("detail") or {}
    model = _VARIANT_MODEL.get(k)
    subs = (_model_entries(detail, model) if model is not None
            else [sub for sub in detail.values() if isinstance(sub, dict)])
    for sub in subs:
        mb = sub.get("mem_breakdown")
        if isinstance(mb, dict) and mb.get("peak_bytes"):
            return int(mb["peak_bytes"])
    return d.get("peak_mem_bytes") or None


def layout_measure(results, k):
    """The variant's layout_share (layout-bucket byte fraction of the
    measured step, bench.py/_layout_fields), or None for NO DATA —
    context for the head-major pairs; throughput still decides."""
    d = results.get(k, {})
    if "error" in d or "failed" in d or \
            d.get("metric") == "bench_failed":
        return None
    detail = d.get("detail") or {}
    model = _VARIANT_MODEL.get(k)
    subs = (_model_entries(detail, model) if model is not None
            else [sub for sub in detail.values() if isinstance(sub, dict)])
    for sub in subs:
        if isinstance(sub.get("layout_share"), (int, float)):
            return sub["layout_share"]
    return None


def comm_measure(results, k):
    """The variant's comm_bytes (modeled per-device collective bytes
    per step from the sharded compiled module's comm bucket,
    bench.py/_comm_fields), or None for NO DATA — the context every dp
    pair carries: an int8 "win" that didn't actually shrink the
    gradient exchange would be noise, and a loss that did shrink it is
    still the lever to retune.  Throughput decides, as everywhere."""
    d = results.get(k, {})
    if "error" in d or "failed" in d or \
            d.get("metric") == "bench_failed":
        return None
    detail = d.get("detail") or {}
    model = _VARIANT_MODEL.get(k)
    subs = (_model_entries(detail, model) if model is not None
            else [sub for sub in detail.values() if isinstance(sub, dict)])
    for sub in subs:
        if isinstance(sub.get("comm_bytes"), (int, float)):
            return sub["comm_bytes"]
    return None


def opt_state_measure(results, k):
    """The variant's opt_state_bytes_per_device (resident per-device
    accumulator bytes of the sharded step, bench.py/_opt_state_fields),
    or None for NO DATA — the fsdp/ZeRO pairs' point: the memory claim
    is only real if the sharded step's buffer assignment shows it."""
    d = results.get(k, {})
    if "error" in d or "failed" in d or \
            d.get("metric") == "bench_failed":
        return None
    detail = d.get("detail") or {}
    model = _VARIANT_MODEL.get(k)
    subs = (_model_entries(detail, model) if model is not None
            else [sub for sub in detail.values() if isinstance(sub, dict)])
    for sub in subs:
        if isinstance(sub.get("opt_state_bytes_per_device"),
                      (int, float)):
            return sub["opt_state_bytes_per_device"]
    return None


def goodput_measure(results, k):
    """The variant's (goodput, effective_mfu) pair from the expected
    model entry (observe pillar 8: the harness-wall step fraction and
    the headline scaled by it), or None for NO DATA.  Context only —
    a variant whose throughput "win" came with a goodput collapse
    (e.g. a compile-storm per run) is visible in the same artifact;
    throughput still decides, as everywhere."""
    d = results.get(k, {})
    if "error" in d or "failed" in d or \
            d.get("metric") == "bench_failed":
        return None
    detail = d.get("detail") or {}
    model = _VARIANT_MODEL.get(k)
    subs = (_model_entries(detail, model) if model is not None
            else [sub for sub in detail.values() if isinstance(sub, dict)])
    for sub in subs:
        if isinstance(sub.get("goodput"), (int, float)):
            return {"goodput": sub["goodput"],
                    "effective_mfu": sub.get("effective_mfu")}
    return None


def wins(results, a, b):
    # a missing side must yield "no data", never a vacuous win —
    # AB wins gate bench defaults (CLAUDE.md measured-wins-only).
    # THROUGHPUT decides (the r05 MFU-numerator lesson); the memory
    # delta rides the summary via mem_measure for context only.
    ma, mb = measure(results, a), measure(results, b)
    if ma is None or mb is None:
        return None
    return ma > mb


# summary pairs: "<name>_wins" (throughput verdict) + the peak-memory
# context keys.  longctx_recompute documents the r05 remat decision in
# BYTES as well as MFU: remat won memory and lost throughput — both
# sides of that trade now live in the artifact.
_PAIRS = {
    "nhwc": ("resnet50_nhwc", "resnet50_nchw"),
    "fused_ce": ("transformer_fused_ce", "transformer_base"),
    "fused_qkv": ("transformer_fused_qkv", "transformer_base"),
    "pallas_attn": ("transformer_pallas_attn", "transformer_base"),
    "longctx_pallas": ("longctx_8k_pallas", "longctx_8k_xla"),
    "longctx_recompute": ("longctx_8k_recompute", "longctx_8k_pallas"),
    # head-major layout verdicts (ISSUE 8): throughput decides as
    # everywhere; the layout_share delta rides compute_summary so the
    # traffic deletion is visible next to the wall-clock verdict
    "headmajor": ("transformer_headmajor", "transformer_base"),
    "pallas_attn_headmajor": ("transformer_pallas_headmajor",
                              "transformer_base"),
    "longctx_headmajor": ("longctx_8k_headmajor", "longctx_8k_pallas"),
    "lstm_unroll2": ("lstm_unroll2", "lstm_base"),
    "lstm_unroll4": ("lstm_unroll4", "lstm_base"),
    "lstm_unroll8": ("lstm_unroll8", "lstm_base"),
    "lstm_pallas_rnn": ("lstm_pallas_rnn", "lstm_base"),
    # the quantized gradient exchange vs the implicit bf16 all-reduce
    # at the same dp degree; per-pair comm-bytes context rides the
    # summary (<name>_comm_bytes)
    "dp8_int8ar": ("dp8_int8ar", "dp8_bf16"),
    # fsdp-vs-dp at the same device count: the ZeRO memory claim
    # (opt-state + peak deltas ride the summary); throughput still
    # decides defaults
    "fsdp8_zero": ("fsdp8", "dp8_bf16"),
    # the composed-mesh int8 exchange (psum-form) vs its bf16 twin
    "dp2mp2_int8ar": ("dp2mp2_int8ar", "dp2mp2"),
    # int8 KV pools vs the bf16 default for continuous-batching decode
    "decode_kv_int8": ("serving_decode_kv_int8",
                       "serving_decode_kv_bf16"),
}

# intra-entry pairs: both sides live in ONE variant's entry (the bench
# measured them as same-stream twins in the same process).  The
# speculative pair is the canonical case — speedup_vs_sequential is
# spec tokens/s over the sequential twin's, with token parity asserted
# before either number is recorded.
_TWIN_PAIRS = {
    "decode_spec_k4": ("serving_decode_spec_k4", {
        "a_key": "tokens_per_sec",
        "b_key": "sequential_tokens_per_sec",
        "context": ("accept_rate", "accept_hist",
                    "speculation_efficiency", "speedup_vs_sequential",
                    "token_parity", "post_warmup_compiles"),
    }),
}


def compute_summary(results):
    out = {}
    for name, (a, b) in _PAIRS.items():
        out[f"{name}_wins"] = wins(results, a, b)
        pa, pb = mem_measure(results, a), mem_measure(results, b)
        if pa is not None and pb is not None:
            # positive = variant a needs MORE memory than b; the
            # throughput verdict above still decides defaults, but a
            # loss bought with a big memory saving (remat) or a win
            # paid for in HBM is now visible in the same artifact
            out[f"{name}_mem_delta_bytes"] = pa - pb
            out[f"{name}_mem_peaks"] = {a: pa, b: pb}
        la, lb = layout_measure(results, a), layout_measure(results, b)
        if la is not None and lb is not None:
            # negative = variant a moves FEWER layout bytes than b —
            # the head-major traffic-deletion claim, recorded next to
            # the throughput verdict that decides the default
            out[f"{name}_layout_share"] = {a: la, b: lb}
        ca, cb = comm_measure(results, a), comm_measure(results, b)
        if ca is not None and cb is not None:
            # the dp pairs' point: how many collective bytes each side
            # actually moves per step (int8's claim is ~half); recorded
            # next to the throughput verdict that decides the default
            out[f"{name}_comm_bytes"] = {a: ca, b: cb}
        oa, ob = (opt_state_measure(results, a),
                  opt_state_measure(results, b))
        if oa is not None and ob is not None:
            # the fsdp pairs' point: per-device resident opt-state
            # bytes — the ZeRO ~1/N claim in the artifact itself
            out[f"{name}_opt_state_bytes"] = {a: oa, b: ob}
        ga, gb = (goodput_measure(results, a),
                  goodput_measure(results, b))
        if ga is not None and gb is not None:
            # goodput context (observe pillar 8) next to the verdict:
            # each side's harness-wall step fraction + effective_mfu,
            # so a throughput win bought with badput (compile storms,
            # ckpt stalls) is visible in the same artifact
            out[f"{name}_goodput"] = {a: ga, b: gb}
    for name, (variant, spec) in _TWIN_PAIRS.items():
        d = results.get(variant, {})
        detail = d.get("detail") or {}
        entry = None
        for sub_name, sub in detail.items():
            if isinstance(sub, dict) and spec["b_key"] in sub:
                entry = sub
                break
        if entry is None or "error" in (d or {}):
            out[f"{name}_wins"] = None
            continue
        ma, mb = entry.get(spec["a_key"]), entry.get(spec["b_key"])
        out[f"{name}_wins"] = (None if not (ma and mb) else ma > mb)
        out[f"{name}_twin"] = {spec["a_key"]: ma, spec["b_key"]: mb,
                               **{c: entry.get(c)
                                  for c in spec["context"]}}
    # the ZeRO scaling record (ISSUE 13 acceptance): opt-state bytes
    # per device across the fsdp ladder vs the dp=8 replicated
    # baseline — drop >=1.7x at fsdp=2, ~N/1 at fsdp=4/8 (the pinned
    # chip-free assert lives in tests/test_hybrid_parallel.py; this is
    # the recorded artifact form)
    base = opt_state_measure(results, "dp8_bf16")
    ladder = {n: opt_state_measure(results, f"fsdp{n}")
              for n in (2, 4, 8)}
    if base and all(v for v in ladder.values()):
        out["fsdp_opt_state_scaling"] = {
            "dp8_bytes": base,
            **{f"fsdp{n}_bytes": v for n, v in ladder.items()},
            **{f"fsdp{n}_drop_x": round(base / v, 3)
               for n, v in ladder.items()},
            "zero_scaling_ok": bool(
                base / ladder[2] >= 1.7
                and base / ladder[4] >= 4 * 0.75
                and base / ladder[8] >= 8 * 0.75),
        }
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--timeout", type=int, default=1200)
    p.add_argument("--out", default="AB_r12.json")
    p.add_argument("--only", default=None,
                   help="comma-separated variant keys to run")
    p.add_argument("--bench-args", default=None,
                   help="extra bench.py args prepended to every "
                        "variant (e.g. '--batch 16')")
    args = p.parse_args()

    run_tag = _run_tag()
    results = {}
    if args.only and os.path.exists(args.out):
        # selective re-run (post-fix retest): keep the other variants'
        # recorded entries, replace only the re-run ones.  A corrupt
        # artifact (torn write from a killed run) must not crash the
        # retest — start fresh and say so.
        try:
            with open(args.out) as f:
                loaded = json.load(f)
            if not isinstance(loaded, dict):
                raise ValueError(f"expected a dict, got "
                                 f"{type(loaded).__name__}")
        except (OSError, ValueError) as e:
            print(f"warning: existing {args.out} unreadable ({e}); "
                  f"starting fresh", file=sys.stderr)
            loaded = {}
        results = {k: v for k, v in loaded.items() if k != "summary"}
        # auditability: every kept entry must say which run produced it;
        # pre-observability artifacts get an explicit unknown marker
        for v in results.values():
            if isinstance(v, dict) and "run_id" not in v:
                v["run_id"] = None
                v["merged_pre_provenance"] = True
    for key, extra in VARIANTS:
        if args.only and key not in args.only.split(","):
            continue
        print(f"=== {key}: bench.py {' '.join(extra)}", file=sys.stderr)
        out = run_variant(args, extra)
        # the bench line already carries its own run_id/git_sha when the
        # bench ran far enough to print one; error entries get this
        # invocation's tag so they are attributable too
        out.setdefault("run_id", run_tag["run_id"])
        out.setdefault("git_sha", run_tag["git_sha"])
        results[key] = out
        print(json.dumps({key: results[key]}), file=sys.stderr)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)

    summary = compute_summary(results)
    summary["run_id"] = run_tag["run_id"]
    summary["git_sha"] = run_tag["git_sha"]
    results["summary"] = summary
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
