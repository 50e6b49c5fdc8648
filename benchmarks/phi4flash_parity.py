#!/usr/bin/env python3
"""The system against the plain reference at Phi-4-mini-flash-reasoning's
published widths and the cell's sizes (published layers 14-19: mamba,
window attention, the exporting mamba, the whole-prefix layer that
exports K and V, a gated memory unit, a cross-attention layer; hidden
2560, 40 / 20 heads of 64, 5120 channels x 16 states, 1 x 8192
positions, 25088 vocabulary rows), on the chip, outside any timed
window.

    python3 benchmarks/phi4flash_parity.py --seed <n> [--seed <n> ...]

For each seed: one sequence of 8193 ids (Zipf-like over the vocabulary
slice, as the cell draws them), the weights as the cell draws them from
the seed (N(0, 0.02), the state-space parameters as the published class
starts them) with the biases and the norms' scales redrawn (they start
at 0 and 1, where a wrong term would be compared at nothing), through

1. the system, AMP off, matmuls at "highest" precision: the forward and
   backward Program of the cell as the timed step builds it (`Program ->
   Executor.run`: every layer a recompute segment, `selective_scan_fwd`
   / `_bwd` and the biased `short_conv` kernels as Mosaic compiles them
   at 8192 x 5120 x 16, ONE grouped flash call a layer on heads padded
   to 128 lanes at 40 / 20 heads under the window of 512 and over the
   whole prefix, `diff_combine`, the memory unit and the cross layer
   reading layers 16's and 17's work across segments) against
   `reference_phi4flash` (the scan one position at a time in blocks of
   256 positions, attention 512 query rows at a time under an explicit
   mask with FOUR soft-max products a pair, every layer recomputed in
   its backward pass, so that it fits): the logits of the last 256
   positions, the loss, and the gradient of EVERY parameter leaf as the
   norm of the difference over the norm of the reference's, worst leaf
   (named beside it: `A_log`, `W_x`, the four lambda vectors, layer 16's
   `W_u`, reached only through the memory unit and its own gate, and
   layer 17's key and value projections are among them);
2. the system as the cell runs it (bf16 AMP, default precision) against
   the same reference;
3. on the first seed, the REFERENCE with a bfloat16 scan state, and
   with bfloat16 lambda / sub-layer norm operands, against itself in
   float32: each must miss a float32 limit, the logits' or the
   gradients' (a limit that loose checks nothing).

Not a reader and not `run.py`'s `correct` (which cannot be extended
without an edit to `run.py`): the builder's own check, PERF.md has its
numbers.  Exits non-zero on a CPU and on a miss of any limit.  The
limits and the readings they stand between are beside the limits below
and in PERF.md section 6 (PR 53).
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
TIME_BLOCK = 256        # positions of its scan kept at a time
# The limits, each between two readings (my chip runs, PR 53, four
# seeds; PERF.md section 6).  Float32 (both sides float32 at "highest":
# summation order, Mosaic's flash kernels over zero-padded lanes, the
# chunked scan against the position-by-position one, the kernels'
# softplus series): logits 7.5e-6 .. 8.1e-6 of values up to 6.4, the
# loss 0 .. 9.5e-7, the worst leaf 1.1e-4 .. 6.9e-4 (a value bias or
# the lambda vectors of the window layer: sums that cancel; the scan's
# `w_x` 6.7e-5).  Lowered on purpose, the reference against itself: a
# bfloat16 scan state reads 3.9e-3 on the logits and 0.036 .. 0.56 on
# a leaf (`w_x`, the step's bias), bfloat16 lambda and sub-norm
# operands 1.0e-2 and 0.014 .. 0.040; bf16 AMP 0.098 .. 0.101 and
# 0.041 .. 0.18.  The logit and gradient limits stand about half way
# (in logarithm) between the largest float32 reading and the smallest
# lowered one.  The loss hardly moves with the precision (bf16 AMP
# 6.7e-6 .. 3.8e-4, a bfloat16 scan state 9.5e-7): it is held, and no
# lowered run is asked to miss it.
F32_LOGIT_LIMIT = 2e-4
F32_GRAD_LIMIT = 3e-3
F32_LOSS_LIMIT = 1e-5
# bf16 AMP as the cell runs it: some three times the largest reading
# (the worst leaf 0.18: the window layer's lambda vectors, one scalar's
# gradient, a sum over every position that cancels)
BF16_LOGIT_LIMIT = 0.3
BF16_LOSS_LIMIT = 0.0012
BF16_GRAD_LIMIT = 0.55
FEEDS = ("tokens", "labels")


def build_forward(config, family, seed):
    """The forward and backward Program (no optimizer) at the published
    widths, as the timed step builds it (the recipe's `recompute`), its
    weights from the seed, AMP off.  `model["grads"]`: the gradient of
    every parameter, in `all_parameters()`' order."""
    import jax.numpy as jnp
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
            aux_loss_weight=0.0, z_loss_weight=0.0,
            recompute=training["recompute"],
            initializer_range=training["initializer_range"],
            **family.architecture(config))
        model["grads"] = [g for _, g in
                          fluid.append_backward(model["loss"])]
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
        redraw = np.random.default_rng(seed)
        for p in main.all_parameters():
            value = np.asarray(scope.find_var(p.name))
            if value.ndim == 1 and np.ptp(value) == 0.0:
                # a bias, a norm's scale, D: not compared at 0 or 1
                scope.set_var(p.name, jnp.asarray(
                    value + redraw.normal(size=value.shape) * 0.1,
                    value.dtype))
    return exe, main, scope, model


def run_system(exe, main, scope, model, feed, amp):
    import jax
    import paddle_tpu as fluid

    main._amp_lists = fluid.amp.AutoMixedPrecisionLists() if amp else None
    main._bump()
    with jax.default_matmul_precision("default" if amp else "highest"):
        out = exe.run(main, feed=feed, scope=scope, return_numpy=False,
                      fetch_list=[model["loss"], model["logits"]]
                      + model["grads"])
    return {"loss": float(np.asarray(out[0]).reshape(())),
            "grads": list(out[2:]),                     # on the device
            "logits": np.asarray(out[1][0, -LAST:], np.float32)}


_JITTED = {}


def run_reference(config, main, scope, feed, stand_in=None):
    """The reference's numbers on the HOST.  `stand_in`: a precision
    lowered on purpose (`reference_phi4flash.decoder_layer`), to show
    that the limits catch it."""
    import jax
    import jax.numpy as jnp
    import reference_phi4flash as ref

    params = ref.params_from_list(
        [scope.find_var(p.name) for p in main.all_parameters()], config)
    ids = [jnp.asarray(feed[k]) for k in FEEDS]
    how = dict(q_block=Q_BLOCK, time_block=TIME_BLOCK, stand_in=stand_in)
    key = json.dumps({k: str(v) for k, v in (stand_in or {}).items()})

    def forward(params, tokens, labels):
        total, parts = ref.loss(params, tokens, labels, config,
                                remat=False, **how)
        return total, parts["logits"][0, -LAST:]

    def flat_grads(params, tokens, labels):
        _, g = ref.loss_and_grads(params, tokens, labels, config, **how)
        return ref.flat_leaves(g, config)

    if key not in _JITTED:          # one program each for every seed
        _JITTED[key] = (jax.jit(forward), jax.jit(flat_grads))
    forward_fn, grads_fn = _JITTED[key]
    total, logits = forward_fn(params, *ids)
    want = {"loss": float(total), "logits": np.asarray(logits),
            "grad_names": ref.leaf_names(config)}
    del total, logits
    want["grads"] = [np.asarray(g) for g in grads_fn(params, *ids)]
    return want


def grad_errors(got, want, names):
    """|g - g_ref| / |g_ref| of every leaf (2-norms); every leaf has a
    gradient.  A key bias moves every score of a query alike, which a
    soft-max does not see: its gradient is 0 but for rounding, and is
    held against the norm of the same layer's QUERY bias's."""
    errs = {}
    norms = {name: float(np.linalg.norm(w)) for name, w in zip(names, want)}
    for name, g, w in zip(names, got, want):
        g = np.asarray(g, np.float32).reshape(w.shape).astype(np.float64)
        norm = norms[name]
        if name.endswith(".bk"):
            norm = max(norm, norms[name[:-1] + "q"])
        errs[name] = float(np.linalg.norm(g - w)) / norm if norm \
            else float("inf")
    worst = max(errs, key=errs.get)
    return {"grad_err_worst": errs[worst], "grad_err_worst_leaf": worst,
            "grad_err": errs}


def compare(got, want):
    err = np.abs(got["logits"] - want["logits"])
    return {**grad_errors(got["grads"], want["grads"], want["grad_names"]),
            "logit_err_max": float(err.max()),
            "logit_abs_max": float(np.abs(want["logits"]).max()),
            "loss_err": abs(got["loss"] - want["loss"]),
            "loss": got["loss"], "loss_reference": want["loss"]}


def misses_f32(c):
    return {"logits": c["logit_err_max"] > F32_LOGIT_LIMIT,
            "grads": c["grad_err_worst"] > F32_GRAD_LIMIT,
            "loss": c["loss_err"] > F32_LOSS_LIMIT}


def check_seed(config, family, seed, stand_ins=False):
    import jax.numpy as jnp

    t0 = time.perf_counter()
    exe, main, scope, model = build_forward(config, family, seed)
    cell = {"batch_per_chip": 1, "chips": 1,
            "length": config["sequence_length"]}
    feed = family.make_batch(config, cell, np.random.default_rng(seed))
    want = run_reference(config, main, scope, feed)
    f32 = compare(run_system(exe, main, scope, model, feed, False), want)
    bf16 = compare(run_system(exe, main, scope, model, feed, True), want)
    checks = {
        "f32_logits": f32["logit_err_max"] <= F32_LOGIT_LIMIT,
        "f32_loss": f32["loss_err"] <= F32_LOSS_LIMIT,
        "f32_grads": f32["grad_err_worst"] <= F32_GRAD_LIMIT,
        "bf16_grads": bf16["grad_err_worst"] <= BF16_GRAD_LIMIT,
        "bf16_logits": bf16["logit_err_max"] <= BF16_LOGIT_LIMIT,
        "bf16_loss": bf16["loss_err"] <= BF16_LOSS_LIMIT,
        # bfloat16 compute misses the float32 limits (the loss's aside)
        "bf16_fails_f32_limits": misses_f32(bf16)["logits"]
        and misses_f32(bf16)["grads"]}
    lowered = {}
    if stand_ins:
        for name, stand_in in [
                ("bf16_scan_state", {"state_dtype": jnp.bfloat16}),
                ("bf16_lambda_subnorm", {"lam_dtype": jnp.bfloat16})]:
            got = run_reference(config, main, scope, feed, stand_in)
            lowered[name] = c = compare(got, want)
            c.pop("grad_err")
            c["misses"] = misses_f32(c)
            # by one of the limits, not by each
            checks[name + "_fails_an_f32_limit"] = any(c["misses"].values())
    return {"seed": seed, "seconds": time.perf_counter() - t0,
            "f32": f32, "bf16": bf16, "lowered": lowered, "checks": checks,
            "ok": all(checks.values())}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--workload", default="phi4flash-8k")
    args = ap.parse_args(argv)
    _, config, family = bench_run.load_cell(args.workload, (HERE,))
    device = bench_run.require_tpu(1, (HERE,))
    results = []
    for i, seed in enumerate(args.seed):
        results.append(check_seed(config, family, seed, stand_ins=i == 0))
        print(json.dumps(results[-1]), flush=True)
    ok = all(r["ok"] for r in results)
    line = json.dumps({"ok": ok, "device": device["kind"],
                       "seeds": args.seed})
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "phi4flash_parity.log"),
              "a") as f:
        f.writelines(json.dumps(r) + "\n" for r in results)
    print(line, flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
