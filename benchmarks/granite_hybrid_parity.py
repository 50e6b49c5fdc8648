#!/usr/bin/env python3
"""The system against the plain reference at Granite-4.0-H-Micro's
published widths and the cell's sizes (published layers 0-9: mamba x 5,
attention, mamba x 4; hidden 2048, 64 state-space heads of 64 x 128
states, 32 / 8 attention heads of 64, the 8192-wide MLP, 1 x 8192
positions, 12544 vocabulary rows, the four multipliers), on the chip,
outside any timed window.

    python3 benchmarks/granite_hybrid_parity.py --seed <n> [--seed <n> ...]

For each seed: one sequence of 8193 ids (Zipf-like over the vocabulary
slice, as the cell draws them), the weights as the cell draws them from
the seed (N(0, 0.02), the state-space parameters as the published class
starts them) with the convolution's bias, D and the norms' scales
redrawn (they start at 0 and 1, where a wrong term would be compared at
nothing), through

1. the system, AMP off, matmuls at "highest" precision: the forward and
   backward Program of the cell as the timed step builds it (`Program ->
   Executor.run`: every layer a recompute segment, `ssd_scan_fwd` /
   `_bwd` and the biased `short_conv` kernels as Mosaic compiles them at
   8192 x 64 heads x 64 x 128 and 4352 channels, the gated norm, the
   `flash_gqa` kernels at 32 / 8 heads under the scale 1/64, the four
   multipliers) against `reference_granite_hybrid` (the scan one
   position at a time in blocks of 256 positions, attention 512 query
   rows at a time under an explicit mask, every layer recomputed in its
   backward pass, so that it fits): the logits of the last 256
   positions, the loss, and the gradient of EVERY parameter leaf as the
   norm of the difference over the norm of the reference's, worst leaf
   (named beside it: `A_log`, `dt_bias`, `D`, the gated norm's scale,
   every mamba layer's three in projections and the attention layer's
   `W_q` are among them);
2. the system as the cell runs it (bf16 AMP, default precision) against
   the same reference;
3. the scan's kernels ALONE on bfloat16 operands at the cell's shape
   (1 x 8192 x 64 heads of 64 x 128 states, the step and the rates
   float32, as the timed step hands them over) against `scan_xla` on
   the same operands: y and the six gradients (x, the step, the rate,
   B, C, the skip), each as the norm of the difference over the norm.
   The two lowerings round the same operands and differ in how they
   sum, so this holds what 2. cannot: the gradient of a chunk's
   cumulative decay is a sum that cancels all but the pairs that
   straddle a position, and its two sides must be made of operands
   rounded ALIKE (`ssd_scan.py`); made otherwise, the step's gradient
   reads 1.4 % off (0.11 % as the kernels stand) and 2.'s limits pass
   it.  The rate's gradient is reported and not held (beside the
   limit: why);
4. on the first seed, the REFERENCE with a bfloat16 scan state, with a
   bfloat16 decay, with bfloat16 gated-norm operands and with an
   attention scale of 1/8 (64^-1/2) in place of 1/64, against itself in
   float32: each must miss a float32 limit, the logits' or the
   gradients' (a limit that loose checks nothing).

Not a reader and not `run.py`'s `correct` (which cannot be extended
without an edit to `run.py`): the builder's own check, PERF.md has its
numbers.  Exits non-zero on a CPU and on a miss of any limit.  The
limits and the readings they stand between are beside the limits below
and in PERF.md section 6 (PR 58).
"""

from __future__ import annotations

import argparse
import functools
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
# The limits, each between two readings (my chip runs, PR 58, nine
# seeds; PERF.md section 6).  Float32 (both sides float32 at "highest":
# summation order, Mosaic's flash kernels, the chunked scan against the
# position-by-position one): logits 7.2e-7 .. 1.6e-6 of values up to
# 1.45, the loss 0 .. 9.5e-7, the worst leaf 4.7e-4 .. 2.1e-3,
# always a `dt_bias` or an `A_log` (gamma's gradient is a sum that
# cancels all but the pairs that straddle a position: float32's rounding
# of the whole is what is left; `ssd_scan.py`); every other leaf 6.4e-5
# (`w_dt`) or under, the attention layer's `wq` / `wk` 2.3e-5, the rest
# ~2e-6.  Lowered on purpose, the reference against itself, on two
# seeds (11, 515), logits / worst leaf: a bfloat16 scan state 1.2e-4 /
# 0.064 and 5.9e-5 / 0.089, a bfloat16 decay 4.3e-4 / 0.47 and 4.3e-5 /
# 0.084, bfloat16 gated-norm operands 2.5e-3 / 0.020 and 2.8e-3 /
# 8.3e-3, an attention scale of 1/8 6.4e-3 / 8.1 and 5.8e-3 / 8.3; bf16
# AMP 6.6e-3 .. 9.5e-3 and 0.069 .. 0.149.  The logit limit stands
# about half way (in logarithm) between the largest float32 reading and
# the smallest lowered one (1.6e-6 and 4.3e-5), the gradient limit
# between 2.1e-3 and 8.3e-3 with the more room below it (fresh seeds
# read the float32 leaf anywhere in 4.7e-4 .. 2.1e-3): every lowered
# run missed BOTH, and needs to miss one.  The loss hardly moves with
# the precision (bf16 AMP 7.6e-6 .. 1.9e-5, lowered 0 .. 9.5e-7): it is
# held, and no lowered run is asked to miss it.
F32_LOGIT_LIMIT = 1.5e-5
F32_GRAD_LIMIT = 6e-3
F32_LOSS_LIMIT = 1e-5
# bf16 AMP as the cell runs it: two to three times the largest reading
# (the worst leaf 0.069 .. 0.149 over nine seeds: a `dt_bias` or an
# `A_log`, sixty-four scalars' gradients, each a sum over every position
# that cancels; it has no upper reading, which is why the scan's kernels
# are held alone below)
BF16_LOGIT_LIMIT = 0.03
BF16_LOSS_LIMIT = 6e-5
BF16_GRAD_LIMIT = 0.3
# The kernels alone on bfloat16 operands against `scan_xla` on the same
# (my chip runs, PR 58: fourteen seeds here, `tools/time_ssd_scan.py`'s):
# as the kernels stand the step's gradient reads 1.050e-3 .. 1.079e-3,
# B's and C's 2.1e-5 .. 6.0e-5, x's 6.0e-6 .. 1.3e-5, y 4.6e-6 ..
# 8.3e-6, the skip's 1.5e-7 .. 2.5e-7; with gamma's gradient made of
# operands rounded differently on its two sides (this PR's first draft,
# call 1) the step's read 1.4e-2.  The limit stands between 1.08e-3 and
# 1.4e-2 and is held by y and every gradient BUT the rate's, which is
# reported and not held: dA is the sum over a head's 8192 positions of
# the same per-position terms the step's gradient holds one by one
# (XLA sums what the backward kernel writes), the sum cancels, and its
# relative error moves 3.5e-4 .. 6.0e-3 with the seed where the first
# draft read 8e-3 on one: no limit stands between those.
SCAN_LIMIT = 3e-3
SCAN_GRADS = ("dx", "ddt", "da", "db", "dc", "dd")
SCAN_HELD = ("y", "dx", "ddt", "db", "dc", "dd")
FEEDS = ("tokens", "labels")
STAND_INS = (("bf16_scan_state", "state_dtype"),
             ("bf16_decay", "decay_dtype"),
             ("bf16_gated_norm", "norm_dtype"))


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
    lowered on purpose (`reference_granite_hybrid.decoder_layer`), to show
    that the limits catch it."""
    import jax
    import jax.numpy as jnp
    import reference_granite_hybrid as ref

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
    gradient."""
    errs = {}
    for name, g, w in zip(names, got, want):
        g = np.asarray(g, np.float32).reshape(w.shape).astype(np.float64)
        norm = float(np.linalg.norm(w))
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


def scan_gradient_errors(config, seed):
    """The scan's kernels on bfloat16 x, B, C (the step, the rates and
    the skip float32) against `scan_xla` on the same operands, at the
    configuration's heads and length (the cell's): y and the six
    gradients under one cotangent, |got - want| / |want|.  The
    steps log-uniform in [0.001, 0.1] and the rates -1 .. -H, as the
    published class starts them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import ssd_scan as ssd

    t = config["sequence_length"]
    heads, states = config["mamba_n_heads"], config["mamba_d_state"]
    width = heads * config["mamba_d_head"]
    r = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(r.normal(size=shape), jnp.bfloat16)

    step = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), size=(1, t, heads)))
    xs = (draw(1, t, width), jnp.asarray(step, jnp.float32),
          -jnp.arange(1, heads + 1, dtype=jnp.float32),
          draw(1, t, states), draw(1, t, states),
          jnp.asarray(1 + 0.1 * r.normal(size=(heads,)), jnp.float32))
    ct = draw(1, t, width)

    def err(got, want):
        got, want = (np.asarray(x, np.float64) for x in (got, want))
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    chunk = config["mamba_chunk_size"]
    both = []
    # `ssd_scan` takes the kernels by the shape alone (a toy's heads of
    # 16 are `scan_xla`'s on both sides, and `kernels` says so)
    for fn in (ssd.ssd_scan, ssd.scan_xla):
        def y_and_grads(ct, *xs, fn=fn):
            y, vjp = jax.vjp(functools.partial(fn, chunk=chunk), *xs)
            return (y,) + vjp(ct)

        both.append(jax.jit(y_and_grads)(ct, *xs))
    return {"kernels": ssd.ssd_scan_takes(
                t, heads, config["mamba_d_head"], states, chunk=chunk),
            **{name: err(g, w)
               for name, g, w in zip(("y",) + SCAN_GRADS, *both)}}


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
    scan = scan_gradient_errors(config, seed)
    checks = {
        "bf16_scan_kernels_against_xla": scan["kernels"] and max(
            scan[k] for k in SCAN_HELD) <= SCAN_LIMIT,
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
        cases = [(name, {key: jnp.bfloat16}) for name, key in STAND_INS]
        cases.append(("attention_scale_an_eighth", {
            "attention_scale": (config["hidden_size"]
                                // config["num_attention_heads"]) ** -0.5}))
        for name, stand_in in cases:
            got = run_reference(config, main, scope, feed, stand_in)
            lowered[name] = c = compare(got, want)
            c.pop("grad_err")
            c["misses"] = misses_f32(c)
            # by one of the limits, not by each
            checks[name + "_fails_an_f32_limit"] = any(c["misses"].values())
    return {"seed": seed, "seconds": time.perf_counter() - t0,
            "f32": f32, "bf16": bf16, "bf16_scan_against_xla": scan,
            "lowered": lowered, "checks": checks,
            "ok": all(checks.values())}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--workload", default="granite4h-8k")
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
    with open(os.path.join(REPO, "chiprun_out",
                           "granite_hybrid_parity.log"), "a") as f:
        f.writelines(json.dumps(r) + "\n" for r in results)
    print(line, flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
