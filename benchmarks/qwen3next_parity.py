#!/usr/bin/env python3
"""The system against the plain reference at Qwen3-Next-80B-A3B's
published widths and the cell's sizes (4 layers: three Gated DeltaNet
layers and one gated full-attention layer, 1 x 16384 positions, 16 held
experts of 512, 18992 vocabulary rows), on the chip, outside any timed
window.

    python3 benchmarks/qwen3next_parity.py --seed <n> [--seed <n> ...]

For each seed: one sequence of 16385 ids (Zipf-like over the
vocabulary slice, as the cell draws them), the embedding table and
every matrix N(0, 0.02), `A_log`, `dt_bias` and the norms' scales from
the seed, all as the cell draws them, through

1. the system, AMP off, matmuls at "highest" precision: the forward and
   backward Program of `qwen3-next-80b-a3b` as the timed step builds it
   (`Program -> Executor.run`: every layer a recompute segment, the
   chunked scan of `ops/pallas/gated_delta.py` with its Pallas kernels
   as Mosaic compiles them at 32 value heads of 128 x 128 and 256
   chunks, the flash kernels at 16 / 2 heads of 256 with the two-kernel
   backward, the expert op that holds experts 0-15 of 512 under the
   soft-max router, the gated shared expert) against
   `reference_qwen3next` (THE RECURRENCE A POSITION AT A TIME, in
   recomputed runs of 256 positions; attention 512 query rows at a time,
   256 in its backward pass, under an explicit mask, key/value heads
   repeated; every layer recomputed in its backward pass, so that it
   fits): the logits of the last 256 positions, the loss, every token's
   ten experts in every layer, the held experts' counts, and the
   gradient of EVERY parameter leaf as the norm of the difference over
   the norm of the reference's, worst leaf;
2. the system as the cell runs it (bf16 AMP, default precision) against
   the same reference, the logits on the positions whose ten experts
   agree with the reference's in every layer (a near tie in the router
   flips under bfloat16 and sends the token through another expert,
   which is another function, not an error; the share of (token, layer)
   choices that disagree is reported and bounded).

The reference's gradients go to the host (1.7 GB the system's own run
needs on the device).  What no run of this script sees: the optimizer
(tests/test_qwen3next_parity.py holds the norm's decay on the CPU).

Not a reader and not `run.py`'s `correct` (which cannot be extended
without an edit to `run.py`): the builder's own check, PERF.md has its
numbers.  Exits non-zero on a CPU, on a miss of any limit, and if
bfloat16 compute would pass the float32 limits (a limit that loose
checks nothing).  The limits and the readings they stand between are
beside the limits below and in PERF.md section 6 (PR 44).  The system's
side, the comparison and the gradient norms are `mellum_parity.py`'s
(the same Program -> Executor.run path and the same fetches); the
reference, the limits and the checks are this file's.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import mellum_parity as base  # noqa: E402
import reference_qwen3next as reference  # noqa: E402
import run as bench_run  # noqa: E402

# The limits, each between two readings (my chip runs, PR 44, seeds
# 2147484001, 2147489629, 2147490211, 2147490319; PERF.md section 6).
# Float32: ten experts of 512 under a soft-max leave near ties that
# float32 itself breaks otherwise, in 10 .. 160 of 655,360 (token,
# layer) choices (1.5e-5 .. 2.4e-4; no seed read none).  A flipped
# choice is another function only where it reaches a HELD expert (16 of
# 512), and then for every later position too (the state and the full
# layer carry it on): the held experts' counts tell the two cases apart.
# SAME ROWS (three seeds): on the positions whose experts agree the
# logits read 2.2e-5 .. 7.4e-5 (of logits up to 4.7), the loss 0 ..
# 9.5e-7, the worst gradient leaf 3.1e-5 .. 4.4e-5 (the full layer's
# q, k and their norms: Mosaic's flash kernels, the other cells'
# 2.8e-5; a linear layer's A_log).  THE SCAN'S STATE ROUNDED TO
# bfloat16 after every chunk, all else float32 (a scratch copy, seed
# 2147484001, same rows): logits 2.5e-3, the worst leaf 4.3e-4
# (dt_bias, A_log), the loss 0: those two limits stand at the geometric
# mean of the two sides (5.8 x and 3.1 x of room each way), so that
# state fails both.  OTHER ROWS (seed 2147490211, 160 choices flipped):
# logits 1.5e-3, the loss 1.9e-5, the worst leaf 1.06e-2 (a held
# expert's w1: its rows are other rows), against bf16 AMP's 0.15 and
# 0.18: the geometric means again.  bf16 AMP as the cell runs it, four
# seeds: logits 0.15 .. 0.25, the loss 1.5e-5 .. 2.3e-4 (a mean over
# 16384 tokens: it can come out under the float32 reading and decides
# nothing), choices flipped 20.5% .. 21.6% (a fifth of the tokens meet
# a tie within bfloat16's reach in some layer), the worst leaf 0.18 ..
# 0.20 (an expert's).
F32_LOGIT_LIMIT = 4e-4              # the held experts' rows agree
F32_GRAD_LIMIT = 1.4e-4
F32_LOSS_LIMIT = 1e-5
F32_LOGIT_LIMIT_OTHER_ROWS = 1.5e-2  # a flipped choice reached them
F32_GRAD_LIMIT_OTHER_ROWS = 4.5e-2
F32_LOSS_LIMIT_OTHER_ROWS = 1e-4
F32_FLIPPED_SHARE_LIMIT = 1e-3      # 2.4e-4 against bf16 AMP's 0.205
# bf16 AMP: some three times the largest reading (a share: twice)
BF16_LOGIT_LIMIT = 0.75
BF16_LOSS_LIMIT = 0.001
BF16_FLIPPED_SHARE_LIMIT = 0.4
BF16_GRAD_LIMIT = 0.6

def check_seed(config, family, seed, ref=reference):
    t0 = time.perf_counter()
    exe, main, scope, model = base.build_forward(config, family, seed)
    cell = {"batch_per_chip": 1, "chips": 1,
            "length": config["sequence_length"]}
    feed = family.make_batch(config, cell, np.random.default_rng(seed))
    want = base.run_reference(config, main, scope, feed, ref)
    f32 = base.compare(base.run_system(exe, main, scope, model, feed, False),
                       want)
    bf16 = base.compare(base.run_system(exe, main, scope, model, feed, True),
                        want)
    routers = [n for n in want["grad_names"] if n.endswith(".router")]
    same_rows = f32["counts_equal"]
    logit_limit, grad_limit, loss_limit = (
        (F32_LOGIT_LIMIT, F32_GRAD_LIMIT, F32_LOSS_LIMIT) if same_rows else
        (F32_LOGIT_LIMIT_OTHER_ROWS, F32_GRAD_LIMIT_OTHER_ROWS,
         F32_LOSS_LIMIT_OTHER_ROWS))
    checks = {
        "f32_logits": f32["logit_err_max"] is not None
        and f32["logit_err_max"] <= logit_limit,
        "f32_loss": f32["loss_err"] <= loss_limit,
        "f32_routing": f32["flipped_share"] <= F32_FLIPPED_SHARE_LIMIT,
        "f32_held_counts": same_rows or f32["flipped_share"] > 0.0,
        "share_is_a_share": all(
            0 < rows < cell["length"] * config["num_experts_per_tok"]
            for rows in f32["held_rows"]),
        # every leaf but the share's routers (held constant by the
        # builder, on both sides) gets a gradient that is compared
        "grads_are_compared": f32["grad_dead_leaves"] == routers,
        "f32_grads": f32["grad_err_worst"] <= grad_limit,
        "bf16_grads": bf16["grad_err_worst"] <= BF16_GRAD_LIMIT,
        "bf16_logits": bf16["logit_err_max"] is not None
        and bf16["logit_err_max"] <= BF16_LOGIT_LIMIT,
        "bf16_loss": bf16["loss_err"] <= BF16_LOSS_LIMIT,
        "bf16_flipped": bf16["flipped_share"] <= BF16_FLIPPED_SHARE_LIMIT,
        # bfloat16 compute misses the float32 limits, the wider ones
        # too (but the loss's: see above)
        "bf16_fails_f32_limits": bf16["logit_err_max"] is not None
        and bf16["logit_err_max"] > F32_LOGIT_LIMIT_OTHER_ROWS
        and bf16["flipped_share"] > F32_FLIPPED_SHARE_LIMIT
        and bf16["grad_err_worst"] > F32_GRAD_LIMIT_OTHER_ROWS}
    return {"seed": seed, "seconds": time.perf_counter() - t0,
            "f32": f32, "bf16": bf16, "checks": checks,
            "ok": all(checks.values())}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    _, config, family = bench_run.load_cell("qwen3next-16k", (HERE,))
    device = bench_run.require_tpu(1, (HERE,))
    results = []
    for seed in args.seed:
        results.append(check_seed(config, family, seed))
        print(json.dumps(results[-1]), flush=True)
    ok = all(r["ok"] for r in results)
    print(json.dumps({"ok": ok, "device": device["kind"],
                      "seeds": args.seed}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
