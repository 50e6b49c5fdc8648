#!/usr/bin/env python3
"""The system against the plain reference at Laguna-XS.2's published
widths and the cell's sizes (5 layers [full-dense, sliding x 3, full],
query heads [48, 64, 64, 64, 48] over 8 key/value heads of 128, 1 x
16384 positions, 32 held experts of 256, 12544 vocabulary rows), on the
chip, outside any timed window.

    python3 benchmarks/laguna_parity.py --seed <n> [--seed <n> ...]

For each seed: one sequence of 16385 ids (Zipf-like over the
vocabulary slice, as the cell draws them), the embedding table N(0, 1)
as the cell draws it and every matrix N(0, 0.02) from the seed (NOT
the timed cell's 0.002, under which attention and the experts are a
thousandth of the logits: at 0.02 every path weighs in what is
compared, as `mellum_parity.py` sets out), through

1. the system, AMP off, matmuls at "highest" precision: the forward and
   backward Program of `laguna-xs.2` as the timed step builds it
   (`Program -> Executor.run`: every layer a recompute segment, the
   Pallas band kernels of `flash_attention.py` as Mosaic compiles them
   at 64 / 8 heads of 128 under the window of 512 with the forward tile
   the window chose, and at 48 / 8 over the whole prefix, the fused
   QK-norm + RoPE kernels with YaRN's frequencies over HALF the head as
   the op's host constants, the head gate, the dense layer, the expert
   op that holds experts 0-31 of 256 under the soft-max router with the
   routed sum x 2.5, the shared expert) against `reference_laguna`
   (attention 512 query rows at a time, 256 in its backward pass, under
   an explicit mask, key/value heads repeated, every layer recomputed in
   its backward pass, so that it fits; the vocabulary's 12544 columns in
   one piece: 822 MB of float32 logits fit): the logits of the last 256
   positions, the loss, every token's eight experts in every sparse
   layer, the held experts' counts, and the gradient of EVERY parameter
   leaf as the norm of the difference over the norm of the reference's,
   worst leaf;
2. the system as the cell runs it (bf16 AMP, default precision) against
   the same reference, the logits on the positions whose eight experts
   agree with the reference's in every layer (a near tie in the router
   flips under bfloat16 and sends the token through another expert,
   which is another function, not an error; the share of (token, layer)
   choices that disagree is reported and bounded).

The reference's gradients go to the host (2.8 GB the system's own run
needs on the device).  What no run of this script sees: the optimizer
(tests/test_laguna_parity.py compares one AdamW step with the
reference, on the CPU).

Not a reader and not `run.py`'s `correct` (which cannot be extended
without an edit to `run.py`): the builder's own check, PERF.md has its
numbers.  Exits non-zero on a CPU, on a miss of any limit, and if
bfloat16 compute would pass the float32 limits (a limit that loose
checks nothing).  The limits and the readings they stand between are
beside the limits below and in PERF.md section 6 (PR 51).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for path in (REPO, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import run as bench_run  # noqa: E402

LAST = 256
Q_BLOCK = 512           # rows of the reference's scores at a time
GRAD_Q_BLOCK = 256      # and in its backward pass
# The limits, each between two readings (my chip runs, PR 51, five
# seeds; PERF.md section 6).  Float32, where no choice of the router
# flipped (one seed of five): logits 1.9e-5, the worst leaf 4.3e-5
# (Mosaic's flash kernels and the QK-norm scales of the window layers,
# the other cells' 2.8e-5 .. 4.2e-5).  Eight experts of 256 under a
# soft-max leave near ties that float32 itself breaks otherwise, in 10,
# 20, 30 and 130 of 655,360 choices (1.5e-5 .. 2.0e-4), and a token
# sent through another expert is another function: with them the logits
# read 1.7e-5 .. 1.4e-4 and the worst leaf 4.2e-5 .. 5.7e-3 (an
# expert's, in the layer where most of one seed's 30 fell; 5.1e-5 with
# 130 elsewhere), against 2.3e-2 / 7.5e-2 under bfloat16: the limits
# stand about half way (in logarithm) between.  The loss 0 .. 9.5e-7
# against 3.9e-5 .. 1.5e-4.
F32_LOGIT_LIMIT = 1e-4              # no choice flipped
F32_GRAD_LIMIT = 1e-3
F32_LOGIT_LIMIT_FLIPPED = 2e-3      # some did, within the share below
F32_GRAD_LIMIT_FLIPPED = 0.02
F32_LOSS_LIMIT = 1e-5
F32_FLIPPED_SHARE_LIMIT = 0.002     # 1311 of 655,360; bfloat16 flips 4.5%
# bf16 AMP as the cell runs it: some three times the largest reading
# (logits 0.023 .. 0.024, the loss 3.9e-5 .. 1.5e-4, choices flipped
# 4.5% .. 4.7%, the worst leaf, an expert's, 0.075 .. 0.080)
BF16_LOGIT_LIMIT = 0.08
BF16_LOSS_LIMIT = 0.002
BF16_FLIPPED_SHARE_LIMIT = 0.12
BF16_GRAD_LIMIT = 0.2
FEEDS = ("tokens", "labels")


def build_forward(config, family, seed):
    """The forward and backward Program (no optimizer) at the published
    widths, as the timed step builds it (the recipe's `recompute`), its
    weights from the seed, AMP off.  `model["grads"]`: the gradient of
    every parameter, in `all_parameters()`' order."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    training = config["training"]
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        model = decoder.build_model(
            max_length=config["sequence_length"], with_optimizer=False,
            aux_loss_weight=training["aux_loss_weight"],
            z_loss_weight=training["z_loss_weight"],
            recompute=training["recompute"],
            embedding_init_range=training.get("embedding_init_range"),
            **family.architecture(config))
        model["grads"] = [g for _, g in
                          fluid.append_backward(model["loss"])]
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
    return exe, main, scope, model


def run_system(exe, main, scope, model, feed, amp):
    import jax
    import paddle_tpu as fluid

    main._amp_lists = fluid.amp.AutoMixedPrecisionLists() if amp else None
    main._bump()
    routed = len(model["counts"])
    with jax.default_matmul_precision("default" if amp else "highest"):
        out = exe.run(main, feed=feed, scope=scope, return_numpy=False,
                      fetch_list=[model["loss"], model["logits"]]
                      + model["counts"] + model["experts"]
                      + model["grads"])
    return {"loss": float(np.asarray(out[0]).reshape(())),
            "grads": list(out[2 + 2 * routed:]),      # on the device
            "logits": np.asarray(out[1][0, -LAST:], np.float32),
            "counts": np.stack([np.asarray(c) for c in
                                out[2:2 + routed]]).astype(np.int64),
            "experts": np.stack([np.sort(np.asarray(e), axis=-1)
                                 for e in out[2 + routed:2 + 2 * routed]])}


_JITTED = {}


def run_reference(config, main, scope, feed, ref=None):
    """The reference's numbers on the HOST.  `ref`: a stand-in for
    `reference_laguna` (a scratch copy broken on purpose, to show that
    the limits catch it)."""
    import jax
    import jax.numpy as jnp

    if ref is None:
        import reference_laguna as ref
    params = ref.params_from_list(
        [scope.find_var(p.name) for p in main.all_parameters()], config)
    ids = [jnp.asarray(feed[k]) for k in FEEDS]

    def forward(params, tokens, labels):
        total, parts = ref.loss(params, tokens, labels, config, Q_BLOCK)
        return (total, parts["logits"][0, -LAST:], jnp.stack(parts["counts"]),
                jnp.stack(parts["experts"]))

    def flat_grads(params, tokens, labels):
        _, g = ref.loss_and_grads(params, tokens, labels, config,
                                  GRAD_Q_BLOCK)
        return ref.flat_leaves(g, config)

    if ref not in _JITTED:          # one program each for every seed
        _JITTED[ref] = (jax.jit(forward), jax.jit(flat_grads))
    forward_fn, grads_fn = _JITTED[ref]
    total, logits, counts, chosen = forward_fn(params, *ids)
    want = {"loss": float(total), "logits": np.asarray(logits),
            "counts": np.asarray(counts).astype(np.int64),
            "experts": np.sort(np.asarray(chosen), axis=-1),
            "grad_names": ref.leaf_names(config)}
    del total, logits, counts, chosen
    want["grads"] = [np.asarray(g) for g in grads_fn(params, *ids)]
    return want


def grad_errors(got, want, names):
    """|g - g_ref| / |g_ref| of every leaf (2-norms); a leaf the
    reference gives no gradient (a share's router, whose routing
    weights the builder holds constant) must get none."""
    errs, dead = {}, []
    for name, g, w in zip(names, got, want):
        g = np.asarray(g, np.float32).reshape(w.shape).astype(np.float64)
        norm = float(np.linalg.norm(w))
        if norm == 0.0:
            dead.append(name)
            errs[name] = 0.0 if float(np.linalg.norm(g)) == 0.0 \
                else float("inf")
        else:
            errs[name] = float(np.linalg.norm(g - w)) / norm
    worst = max(errs, key=errs.get)
    return {"grad_err_worst": errs[worst], "grad_err_worst_leaf": worst,
            "grad_err": errs, "grad_dead_leaves": dead}


def compare(got, want):
    """Errors of one system run against the reference; logits over the
    last LAST positions whose experts agree in every layer.  `experts`
    is (layers, tokens, k), sorted along k."""
    same = (got["experts"] == want["experts"]).all(axis=-1)   # (L, T)
    tail = same.all(axis=0)[-LAST:]
    err = np.abs(got["logits"] - want["logits"])
    return {**grad_errors(got["grads"], want["grads"], want["grad_names"]),
            "logit_err_max": float(err[tail].max()) if tail.any() else None,
            "logit_err_all_max": float(err.max()),
            "logit_abs_max": float(np.abs(want["logits"]).max()),
            "loss_err": abs(got["loss"] - want["loss"]),
            "loss": got["loss"], "loss_reference": want["loss"],
            "flipped_share": float(1.0 - same.mean()),
            "flipped_in_tail": int((~tail).sum()),
            "counts_equal": bool((got["counts"] == want["counts"]).all()),
            "held_rows": [int(c.sum()) for c in got["counts"]]}


def check_seed(config, family, seed, ref=None):
    t0 = time.perf_counter()
    exe, main, scope, model = build_forward(config, family, seed)
    cell = {"batch_per_chip": 1, "chips": 1,
            "length": config["sequence_length"]}
    feed = family.make_batch(config, cell, np.random.default_rng(seed))
    want = run_reference(config, main, scope, feed, ref)
    f32 = compare(run_system(exe, main, scope, model, feed, False), want)
    bf16 = compare(run_system(exe, main, scope, model, feed, True), want)
    routers = [n for n in want["grad_names"] if n.endswith(".router")]
    flipped = f32["flipped_share"] > 0.0
    logit_limit = F32_LOGIT_LIMIT_FLIPPED if flipped else F32_LOGIT_LIMIT
    grad_limit = F32_GRAD_LIMIT_FLIPPED if flipped else F32_GRAD_LIMIT
    checks = {
        "f32_logits": f32["logit_err_max"] is not None
        and f32["logit_err_max"] <= logit_limit,
        "f32_loss": f32["loss_err"] <= F32_LOSS_LIMIT,
        "f32_routing": f32["flipped_share"] <= F32_FLIPPED_SHARE_LIMIT,
        "f32_held_counts": f32["counts_equal"]
        or f32["flipped_share"] > 0.0,
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
        # bfloat16 compute misses the float32 limits, the wider ones too
        "bf16_fails_f32_limits": bf16["logit_err_max"] is not None
        and bf16["logit_err_max"] > F32_LOGIT_LIMIT_FLIPPED
        and bf16["flipped_share"] > F32_FLIPPED_SHARE_LIMIT
        and bf16["grad_err_worst"] > F32_GRAD_LIMIT_FLIPPED
        and bf16["loss_err"] > F32_LOSS_LIMIT}
    return {"seed": seed, "seconds": time.perf_counter() - t0,
            "f32": f32, "bf16": bf16, "checks": checks,
            "ok": all(checks.values())}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--workload", default="laguna-16k")
    args = ap.parse_args(argv)
    _, config, family = bench_run.load_cell(args.workload, (HERE,))
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
