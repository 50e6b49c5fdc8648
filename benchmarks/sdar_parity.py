#!/usr/bin/env python3
"""The system against the plain reference at SDAR-30B-A3B's published
widths and the cell's sizes (6 layers, one document of 8192 positions
fed as 8192 + 8192 rows, 16 held experts of 128, 18992 vocabulary
rows), on the chip, outside any timed window.

    python3 benchmarks/sdar_parity.py --seed <n> [--seed <n> ...]

For each seed: one document (Zipf-like ids over the vocabulary slice,
as the cell draws them), noised on the host as the cell noises it
(B 4, t_b ~ U(1e-3, 1)), the embedding table N(0, 1) as the cell draws
it and every matrix N(0, 0.02) from the seed (NOT the timed cell's
0.002, under which attention and the experts are a thousandth of the
logits: at 0.02 every path weighs in what is compared, as
`mellum_parity.py` sets out), through

1. the system, AMP off, matmuls at "highest" precision: the forward and
   backward Program of `sdar-30b-a3b` as the timed step builds it
   (`Program -> Executor.run`: every layer a recompute segment, the
   Pallas band kernels of `flash_attention.py` under the block-diffusion
   mask as Mosaic compiles them at 32 / 4 heads of 128 over 16384 rows,
   RoPE with restarting positions, the expert op that holds experts
   0-15 of 128 under the soft-max router, the head over the noised
   half, the weighted loss) against `reference_sdar` (attention 512
   query rows at a time, 256 in its backward pass, under an explicit
   mask built from (half, position), key/value heads repeated, every
   layer recomputed in its backward pass, so that it fits): the logits
   of the noised half's last 256 positions, the loss, every row's eight
   experts in every layer, the held experts' counts, and the gradient
   of EVERY parameter leaf as the norm of the difference over the norm
   of the reference's, worst leaf;
2. the system as the cell runs it (bf16 AMP, default precision) against
   the same reference, the logits on the positions whose eight experts
   agree with the reference's in every layer.

Not a reader and not `run.py`'s `correct` (which cannot be extended
without an edit to `run.py`): the builder's own check, PERF.md has its
numbers.  Exits non-zero on a CPU, on a miss of any limit, and if
bfloat16 compute would pass the float32 limits (a limit that loose
checks nothing).  The comparison's arithmetic is `mellum_parity.py`'s
(`compare`, `grad_errors`, `build_forward`, `run_system`); the limits
and the readings they stand between are beside the limits below and in
PERF.md section 6 (PR 47).
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
import reference_sdar as reference  # noqa: E402
import run as bench_run  # noqa: E402

Q_BLOCK = 512           # rows of the reference's scores at a time
GRAD_Q_BLOCK = 256      # and in its backward pass
FEEDS = ("tokens", "labels", "loss_weights")
# The limits, each between two readings (my chip runs, PR 47, seeds
# 2147491007, 2147491129, 2147491131; PERF.md section 6).  SAME ROWS
# (the held experts' counts agree; every seed here): float32 against
# float32 differs in summation order only (Mosaic's flash kernels, the
# sorted expert rows): logits 1.12e-5 .. 1.15e-5 (of logits up to 4.6),
# the loss 0 .. 9.5e-7, the worst gradient leaf 2.96e-5 .. 3.37e-5
# (layer 0's q and k projections: the flash kernels, the other cells'
# 2.8e-5), 0 .. 7 of 786,432 routing choices flipped (0 .. 5.1e-5),
# none of them to or from a held expert.  THE FLASH KERNELS' SOFT-MAX
# STATISTICS ROUNDED TO bfloat16 (running maximum, normaliser and
# logsumexp; all else float32; a scratch copy, seed 2147491007):
# logits 1.12e-2, the loss 1.6e-4, the worst leaf 1.70e-2, 0.85% of
# the choices flipped and the held counts no longer equal.  The
# same-rows limits stand at the geometric mean of the two sides or
# nearer the float32 one (logits x 35 / x 28 of room, leaves x 9 /
# x 57, the loss x 21 / x 8); that run fails the flipped share too
# (8.5e-3 against 1e-3: x 20 over float32's largest, x 8.5 under).
# OTHER ROWS (a flipped choice reached a held expert, and a row sent
# through another expert is another function): NO seed of this PR read
# them in float32, so these three stand between `qwen3next-16k`'s
# float32 readings of that case (logits 1.5e-3, the worst leaf 1.06e-2,
# the loss 1.9e-5: the same router mode over a share) and the
# bfloat16-statistics run above, which reads other rows and must fail
# them: 1.12e-2 / 1.70e-2 / 1.6e-4.
F32_LOGIT_LIMIT = 4e-4              # the held experts' rows agree
F32_GRAD_LIMIT = 3e-4
F32_LOSS_LIMIT = 2e-5
F32_LOGIT_LIMIT_OTHER_ROWS = 4e-3   # a flipped choice reached them
F32_GRAD_LIMIT_OTHER_ROWS = 1.3e-2
F32_LOSS_LIMIT_OTHER_ROWS = 6e-5
F32_FLIPPED_SHARE_LIMIT = 1e-3
# bf16 AMP as the cell runs it, three seeds: logits 0.0198 .. 0.0217 on
# the rows whose experts agree, the loss 2.9e-6 .. 1.4e-4 (a weighted
# mean over 8192 positions: it can come out under a float32 reading and
# decides nothing), choices flipped 2.6% .. 2.9%, the worst leaf 0.042
# .. 0.085 (a held expert's): some three times the largest reading
BF16_LOGIT_LIMIT = 0.065
BF16_LOSS_LIMIT = 5e-4
BF16_FLIPPED_SHARE_LIMIT = 0.09
BF16_GRAD_LIMIT = 0.25

_JITTED = {}


def run_reference(config, main, scope, feed, ref=reference):
    """The reference's numbers on the HOST (`mellum_parity.
    run_reference` with the weights among the feeds)."""
    import jax
    import jax.numpy as jnp

    params = ref.params_from_list(
        [scope.find_var(p.name) for p in main.all_parameters()], config)
    fed = [jnp.asarray(feed[k]) for k in FEEDS]

    def forward(params, tokens, labels, weights):
        total, parts = ref.loss(params, tokens, labels, weights, config,
                                Q_BLOCK)
        return (total, parts["logits"][0, -base.LAST:],
                jnp.stack(parts["counts"]), jnp.stack(parts["experts"]))

    def flat_grads(params, tokens, labels, weights):
        _, g = ref.loss_and_grads(params, tokens, labels, weights, config,
                                  GRAD_Q_BLOCK)
        return ref.flat_leaves(g)

    if ref not in _JITTED:          # one program each for every seed
        _JITTED[ref] = (jax.jit(forward), jax.jit(flat_grads))
    forward_fn, grads_fn = _JITTED[ref]
    total, logits, counts, chosen = forward_fn(params, *fed)
    want = {"loss": float(total), "logits": np.asarray(logits),
            "counts": np.asarray(counts).astype(np.int64),
            "experts": np.sort(np.asarray(chosen), axis=-1),
            "grad_names": ref.leaf_names(config)}
    del total, logits, counts, chosen
    want["grads"] = [np.asarray(g) for g in grads_fn(params, *fed)]
    return want


def held_rows_report(config, f32, feed):
    """What the repeated mask id did to the held rows: the share of
    each layer's routed rows (2 L x k) that reached this chip's
    experts, 12.5% under uniform routing."""
    rows = 2 * config["sequence_length"] * config["num_experts_per_tok"]
    return {"held_row_share_by_layer": [r / rows for r in f32["held_rows"]],
            "masked_share": float((feed["loss_weights"] > 0).mean())}


def check_seed(config, family, seed, ref=reference):
    t0 = time.perf_counter()
    exe, main, scope, model = base.build_forward(config, family, seed)
    cell = {"batch_per_chip": 1, "chips": 1,
            "length": config["sequence_length"]}
    feed = family.make_batch(config, cell, np.random.default_rng(seed))
    want = run_reference(config, main, scope, feed, ref)
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
    routed_rows = 2 * cell["length"] * config["num_experts_per_tok"]
    checks = {
        "f32_logits": f32["logit_err_max"] is not None
        and f32["logit_err_max"] <= logit_limit,
        "f32_loss": f32["loss_err"] <= loss_limit,
        "f32_routing": f32["flipped_share"] <= F32_FLIPPED_SHARE_LIMIT,
        "f32_held_counts": same_rows or f32["flipped_share"] > 0.0,
        "share_is_a_share": all(0 < rows < routed_rows
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
        # bfloat16 compute misses the float32 limits, the wider ones too
        "bf16_fails_f32_limits": bf16["logit_err_max"] is not None
        and bf16["logit_err_max"] > F32_LOGIT_LIMIT_OTHER_ROWS
        and bf16["flipped_share"] > F32_FLIPPED_SHARE_LIMIT
        and bf16["grad_err_worst"] > F32_GRAD_LIMIT_OTHER_ROWS}
    return {"seed": seed, "seconds": time.perf_counter() - t0,
            "f32": f32, "bf16": bf16, "checks": checks,
            "rows": held_rows_report(config, f32, feed),
            "ok": all(checks.values())}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    _, config, family = bench_run.load_cell("sdar-8k", (HERE,))
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
