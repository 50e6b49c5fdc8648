"""Roofline analysis for the headline models, rebuilt on observe.cost
(ISSUE 2 tentpole; supersedes the ROOFLINE_r05.json methodology).

The r05 artifact computed rooflines from XLA's aggregate cost analysis
and produced an IMPOSSIBLE result: a ResNet MFU "ceiling" of 0.269
against a measured 0.309 — because `bytes accessed` sums
per-instruction estimates inside fusions and overcounts real HBM
traffic.  This version computes both roofline inputs analytically from
the optimized HLO module (paddle_tpu/observe/cost.py):

- flops: per-instruction contraction math (exact for dot, near-exact
  for conv), with Pallas custom calls carrying their registered
  dense-equivalent kernel costs — --flash programs no longer need a
  twin;
- bytes: the materialized-buffers model — each post-fusion kernel
  reads its operands once and writes its output once.  A minimum-
  traffic model, so the derived ceiling is a true upper bound and can
  never undercut an honest measurement.

The roofline lower bound on step time is

    t_lb = max(flops / peak_flops, bytes / hbm_bw)

and the implied MFU ceiling is t_compute / t_lb.  Each entry also
reports the layout/copy/transpose byte share (the r05 longctx
transpose finding as a standard diagnostic) and XLA's aggregate bytes
for comparison with the superseded methodology.

INTERNAL CONSISTENCY: before writing the artifact, every config with
an already-recorded measured MFU (BENCH artifacts, --measured) is
checked — a ceiling below a recorded measurement raises instead of
writing another impossible artifact.

Run on the real chip: `python tools/roofline.py [--model all|resnet50|
transformer] [--flash] [--out ROOFLINE_r06.json]`.  It needs the
chip: bench._peak_flops raises on a device it has no peak for.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_DEFAULT_MEASURED = ("docs/BENCH_r05_interim.json", "BENCH_r05.json")


def _roofline(totals, peak, bw):
    flops = float(totals["flops"])
    nbytes = float(totals["bytes"])
    t_compute = flops / peak
    t_memory = nbytes / bw
    t_lb = max(t_compute, t_memory)
    bucket_bytes = totals.get("bucket_bytes", {})
    layout_bytes = bucket_bytes.get("layout", 0.0)
    return {
        "flops": flops,
        "bytes": nbytes,
        # predicted peak HBM of this config's step (buffer-assignment
        # allocation total, observe.memory) — the "shape-limited"
        # verdicts now carry their memory evidence in the same row
        "peak_hbm_bytes": totals.get("peak_hbm_bytes"),
        "bytes_model": "materialized-buffers",
        "xla_aggregate_flops": totals.get("xla_aggregate_flops"),
        "pallas_registry_flops": totals.get("pallas_flops", 0.0),
        "custom_calls": totals.get("custom_calls", 0),
        "layout_bytes_frac": (round(layout_bytes / nbytes, 4)
                              if nbytes else None),
        "arith_intensity_flops_per_byte":
            round(flops / nbytes, 2) if nbytes else None,
        "t_compute_ms": round(t_compute * 1e3, 3),
        "t_memory_ms": round(t_memory * 1e3, 3),
        "bound": "compute" if t_compute >= t_memory else "memory",
        "mfu_ceiling": round(t_compute / t_lb, 4) if t_lb else None,
        "roofline_step_time_ms": round(t_lb * 1e3, 3),
    }


def _resnet_costs(batch_size, data_format, use_amp=True):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet
    from paddle_tpu.observe import cost as obs_cost

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        model = resnet.build_model(dataset="flowers", depth=50,
                                   class_dim=1000, learning_rate=0.1,
                                   use_amp=use_amp,
                                   data_format=data_format)
        exe = fluid.Executor()
        exe.run(startup)
        feed = {"data": rng.rand(batch_size, 3, 224, 224)
                .astype(np.float32),
                "label": rng.randint(0, 1000, (batch_size, 1))
                .astype(np.int32)}
        return obs_cost.program_costs(main, feed=feed,
                                      fetch_list=[model["loss"]],
                                      exe=exe)


def _transformer_costs(batch_size, max_length, use_flash, use_amp=True,
                       use_fused_ce=False, flash_pallas=False):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer
    from paddle_tpu.observe import cost as obs_cost

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        model = transformer.build_model(
            src_vocab_size=32000, trg_vocab_size=32000,
            max_length=max_length, n_layer=6, n_head=8, d_model=512,
            d_inner_hid=2048, dropout=0.1, use_amp=use_amp,
            use_flash=use_flash, use_fused_ce=use_fused_ce,
            flash_pallas=flash_pallas)
        exe = fluid.Executor()
        exe.run(startup)
        batch = transformer.make_fake_batch(batch_size, max_length,
                                            32000, 32000)
        feed = {k: np.asarray(v) for k, v in batch.items()}
        return obs_cost.program_costs(main, feed=feed,
                                      fetch_list=[model["loss"]],
                                      exe=exe)


def _lstm_costs(batch_size, max_len=128, pallas_rnn=False,
                rnn_unroll=1):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import stacked_dynamic_lstm as lstm
    from paddle_tpu.observe import cost as obs_cost

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        model = lstm.build_model(max_len=max_len, use_amp=False,
                                 pallas_rnn=pallas_rnn,
                                 rnn_unroll=rnn_unroll)
        exe = fluid.Executor()
        exe.run(startup)
        feed = {k: np.asarray(v) for k, v in
                lstm.make_fake_batch(batch_size, max_len).items()}
        return obs_cost.program_costs(main, feed=feed,
                                      fetch_list=[model["loss"]],
                                      exe=exe)


def _load_measured(paths):
    """{bench_detail_key: measured_mfu} from recorded bench artifacts
    (first artifact that loads wins per key)."""
    from perf_gate import load_bench_artifact

    measured = {}
    for path in paths:
        if not os.path.exists(path):
            continue
        try:
            art = load_bench_artifact(path)
        except Exception as e:  # noqa: BLE001
            print(f"warning: could not load measured artifact "
                  f"{path!r}: {e}", file=sys.stderr)
            continue
        for key, entry in art.get("detail", {}).items():
            if isinstance(entry, dict) and "mfu" in entry:
                measured.setdefault(key, entry["mfu"])
    return measured


# roofline config key -> the bench detail key measuring the SAME
# program (only same-program pairs are comparable; a dense-variant
# ceiling says nothing about the flash program's measurement)
def _measured_key(config_key):
    if config_key.startswith("resnet50_nchw_bs128"):
        return "resnet50"
    if config_key == "transformer_bs64_len256_flash":
        return "transformer"
    if config_key == "lstm_bs128_len128_scan":
        # the scan-bound outlier — comparable now that while bodies
        # carry their trip count (the ×1 undercount made the r05 lstm
        # "roofline" fiction); the bench program is the scan path
        return "lstm"
    return None


def _check_consistency(results, measured):
    """A ceiling below an already-recorded measurement of the same
    config is an accounting bug, not a finding — refuse to write it."""
    for key, entry in results.items():
        if not isinstance(entry, dict) or "mfu_ceiling" not in entry:
            continue
        mkey = _measured_key(key)
        if mkey is None or mkey not in measured:
            continue
        ceiling = entry["mfu_ceiling"]
        got = measured[mkey]
        entry["measured_mfu"] = got
        entry["headroom"] = round(ceiling - got, 4)
        if ceiling + 1e-3 < got:
            raise RuntimeError(
                f"internal consistency violation: {key} mfu_ceiling "
                f"{ceiling} < recorded measured MFU {got} ({mkey}) — "
                f"the bytes/flop accounting is overcounting again; "
                f"refusing to write an impossible roofline artifact")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="all",
                   choices=["all", "resnet50", "transformer", "lstm"])
    p.add_argument("--batch", type=int, default=0)
    p.add_argument("--layout", default="NCHW", choices=["NCHW", "NHWC"])
    p.add_argument("--flash", action="store_true",
                   help="also analyze the Pallas-flash transformer "
                        "program (registry flop injection) alongside "
                        "the XLA flash composition")
    p.add_argument("--measured", nargs="*", default=None,
                   help="recorded bench artifacts for the internal "
                        "consistency check (default: "
                        + ", ".join(_DEFAULT_MEASURED) + ")")
    p.add_argument("--out", default="ROOFLINE_r06.json")
    args = p.parse_args()

    from bench import _peak_flops
    from paddle_tpu.observe import cost as obs_cost

    peak, kind = _peak_flops()
    _, bw = obs_cost.device_peaks(kind)
    if bw is None:
        bw = 819e9  # CPU smoke: assume v5e HBM, recorded via `device`

    from paddle_tpu.observe.memory import device_memory_budget

    results = {"device": kind, "peak_flops": peak, "hbm_bw": bw,
               # None on backends reporting no budget (CPU smoke) —
               # per-row peak_hbm_bytes is then structure evidence
               # only, not a fit verdict (docs/OBSERVE.md caveat)
               "hbm_budget_bytes": device_memory_budget(),
               "methodology": "observe.cost analytic "
                              "(materialized-buffers bytes, registry "
                              "Pallas flops, buffer-assignment peak "
                              "HBM); supersedes ROOFLINE_r05.json"}
    if args.model in ("all", "resnet50"):
        totals = _resnet_costs(args.batch or 128, args.layout)
        results[f"resnet50_{args.layout.lower()}_bs"
                f"{args.batch or 128}"] = _roofline(totals, peak, bw)
    if args.model in ("all", "transformer"):
        bs = args.batch or 64
        totals = _transformer_costs(bs, 256, True)
        results[f"transformer_bs{bs}_len256_flash"] = _roofline(
            totals, peak, bw)
        if args.flash:
            totals = _transformer_costs(bs, 256, True,
                                        flash_pallas=True)
            results[f"transformer_bs{bs}_len256_pallas"] = _roofline(
                totals, peak, bw)
    if args.model in ("all", "lstm"):
        # scan path: while bodies × trip count (the r05 fiction fix);
        # pallas path: the fused-recurrence program with its registry
        # kernel costs — both programs the lstm A/B actually runs
        bs = args.batch or 128
        totals = _lstm_costs(bs)
        results[f"lstm_bs{bs}_len128_scan"] = _roofline(totals, peak, bw)
        totals = _lstm_costs(bs, pallas_rnn=True)
        results[f"lstm_bs{bs}_len128_pallas"] = _roofline(totals, peak,
                                                          bw)

    measured = _load_measured(args.measured
                              if args.measured is not None
                              else _DEFAULT_MEASURED)
    _check_consistency(results, measured)

    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
