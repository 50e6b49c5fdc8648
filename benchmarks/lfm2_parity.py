#!/usr/bin/env python3
"""The system against the plain reference at LFM2's published widths
and the cell's 8192 positions, on the chip, outside any timed window.

    python3 benchmarks/lfm2_parity.py --seed <n> [--seed <n> ...]

For each seed: one 8192-token sequence (Zipf-like ids from the
vocabulary slice, as the cell draws them), N(0, 0.02) weights from the
seed and a selection bias drawn N(0, 0.1) (the start-up value is zero;
drawn, "choose on score + bias, weigh with the score" is compared),
through

1. the system, AMP off, matmuls at "highest" precision: the forward
   Program of `lfm2-24b-a2b` (`Program -> Executor.run`: the short
   convolutions, the Pallas grouped-query flash kernel, the expert op
   that holds experts 0-7 of 64) against `reference_lfm2.forward`
   (attention 1024 query rows at a time, so that it fits): logits of
   the last 256 positions, the loss, every token's four experts in
   every routed layer, the held experts' counts; and the BACKWARD
   pass (`append_backward` on the same Program: the Pallas kernels
   `flash_gqa_dkv` / `flash_gqa_dq` as Mosaic compiles them at 32 / 8
   heads, `short_conv`'s recomputing backward, the share's masked
   backward) against `jax.grad` of the reference (jitted, attention
   512 query rows at a time, every layer recomputed in its backward
   pass, so that it fits): the gradient of EVERY parameter, as the
   norm of the difference over the norm of the reference's, worst
   leaf;
2. the system as the cell runs it (bf16 AMP, default precision)
   against the same reference, on the positions whose four experts
   agree with the reference's in every routed layer: a near tie in
   the router flips under bfloat16 and sends the token through another
   expert, which is another function, not an error; the share of
   (token, layer) choices that disagree is reported and bounded.  Its
   gradients are compared the same way, over all tokens (a re-routed
   token's part is in the reading).

What no run of this script sees: the optimizer (`adam` with decoupled
decay: tests/test_decoder_ops.py; the training step: tests/
test_lfm2_parity.py, on the CPU) and the `BiasOut` update
(tests/test_expert_share.py).

Not a reader and not `run.py`'s `correct` (which cannot be extended
without an edit to `run.py`): the builder's own check, PERF.md has its
numbers.  Exits non-zero on a CPU, on a miss of any limit, and if
bfloat16 compute would pass the float32 limits (a limit that loose
checks nothing).

The limits stand between two readings each (my chip runs, PR 30,
seventeen seeds, ten of them with gradients; PERF.md section 6):
float32 logits 2.6e-6 .. 3.1e-6 against 3.7e-2 .. 4.6e-2 under
bfloat16 (0.35 .. 0.50 with the re-routed tokens); the loss 0 ..
1.9e-6 against 3.7e-5 .. 4.0e-4; (token, layer) choices routed
otherwise 0 against 3.0% .. 3.5%; the worst leaf's gradient error
2.8e-5 .. 3.1e-5 (the attention layer's wq / wk and their norms; every
other leaf under 2.2e-6) against 0.126 .. 0.156 under bfloat16 (the
expert layers, whose re-routed tokens are in it; bfloat16's BEST leaf
reads 7.5e-3).
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
Q_BLOCK = 1024          # rows of the reference's scores at a time
GRAD_Q_BLOCK = 512      # and in its backward pass (12 GB planned at 1024)
BIAS_STD = 0.1
# each float32 limit: some 35x the largest float32 reading (the loss:
# ten float32 steps at 9.4), and under the smallest bfloat16 reading,
# which must NOT pass
F32_LOGIT_LIMIT = 1e-4
F32_LOSS_LIMIT = 1e-5
F32_FLIPPED_SHARE_LIMIT = 0.0005      # 16 of 32768 choices
# bfloat16 as the cell runs it: some twice the largest reading
BF16_LOGIT_LIMIT = 0.08
BF16_FLIPPED_SHARE_LIMIT = 0.07
# the worst leaf's |g - g_ref| / |g_ref| (2-norms over the leaf): 32x
# the largest float32 reading, a 126th of the smallest bfloat16 one;
# and twice the largest bfloat16 reading
F32_GRAD_LIMIT = 1e-3
BF16_GRAD_LIMIT = 0.3


def build_forward(config, family, seed):
    """The forward and backward Program (no optimizer) at the published
    widths, its weights and selection biases from the seed, AMP off.
    `model["grads"]`: the gradient of every parameter, in
    `all_parameters()`' order."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        model = decoder.build_model(
            max_length=config["sequence_length"], with_optimizer=False,
            aux_loss_weight=config["training"]["aux_loss_weight"],
            z_loss_weight=config["training"]["z_loss_weight"],
            **family.EQUATIONS,
            **{k: config[k] for k in family.ARCHITECTURE})
        model["grads"] = [g for _, g in
                          fluid.append_backward(model["loss"])]
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
    rng = np.random.default_rng(seed)
    biases = []
    for name in sorted(n for n in main.global_block().vars
                       if n.endswith(".expert_bias")):
        shape = np.asarray(scope.find_var(name)).shape
        biases.append(rng.normal(0.0, BIAS_STD, shape).astype(np.float32))
        scope.set_var(name, biases[-1])
    return exe, main, scope, model, biases


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


def run_reference(config, main, scope, feed, biases):
    import jax.numpy as jnp
    import reference_lfm2 as ref

    params = ref.params_from_list(
        [scope.find_var(p.name) for p in main.all_parameters()], config,
        biases)
    total, parts = ref.loss(params, jnp.asarray(feed["tokens"]),
                            jnp.asarray(feed["labels"]), config, Q_BLOCK)
    want = {"loss": float(total),
            "logits": np.asarray(parts["logits"][0, -LAST:]),
            "counts": np.stack([np.asarray(c) for c in
                                parts["counts"]]).astype(np.int64),
            "experts": np.stack([np.sort(np.asarray(e), axis=-1)
                                 for e in parts["experts"]])}
    del total, parts
    want["grad_names"], want["grads"] = reference_grads(
        config, params, feed)
    return want


_GRADS = {}


def reference_grads(config, params, feed):
    """(names, gradients) of every parameter in the builder's creation
    order, `jax.grad` of the reference's loss, one jitted program (the
    same for every seed)."""
    import jax
    import jax.numpy as jnp
    import reference_lfm2 as ref

    names = ["embed"]
    for i in range(config["num_hidden_layers"]):
        names += [f"layer{i}.{k}" for k in ref.layer_keys(config, i)]
    names += ["final_norm", "head"]

    def flat_grads(params, tokens, labels):
        _, g = ref.loss_and_grads(params, tokens, labels, config,
                                   GRAD_Q_BLOCK)
        flat = [g["embed"]]
        for i, layer in enumerate(g["layers"]):
            flat += [layer[k] for k in ref.layer_keys(config, i)]
        return flat + [g["final_norm"], g["head"]]

    if "fn" not in _GRADS:
        _GRADS["fn"] = jax.jit(flat_grads)
    return names, _GRADS["fn"](params, jnp.asarray(feed["tokens"]),
                               jnp.asarray(feed["labels"]))


def grad_errors(got, want, names):
    """|g - g_ref| / |g_ref| of every leaf (2-norms, on the device);
    a leaf the reference gives no gradient (a share's router, whose
    routing weights the builder holds constant) must get none."""
    import jax.numpy as jnp

    errs, dead = {}, []
    for name, g, w in zip(names, got, want):
        g = jnp.asarray(g, jnp.float32).reshape(w.shape)
        norm = float(jnp.linalg.norm(w))
        if norm == 0.0:
            dead.append(name)
            errs[name] = 0.0 if float(jnp.linalg.norm(g)) == 0.0 \
                else float("inf")
        else:
            errs[name] = float(jnp.linalg.norm(g - w)) / norm
    worst = max(errs, key=errs.get)
    return {"grad_err_worst": errs[worst], "grad_err_worst_leaf": worst,
            "grad_err": errs, "grad_dead_leaves": dead}


def compare(got, want):
    """Errors of one system run against the reference; logits over the
    last LAST positions whose experts agree in every routed layer.
    `experts` is (layers, tokens, k), sorted along k."""
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
            "held_rows": [int(c.sum()) for c in got["counts"]],
            "held_rows_reference": [int(c.sum()) for c in want["counts"]]}


def check_seed(config, family, seed):
    t0 = time.perf_counter()
    exe, main, scope, model, biases = build_forward(config, family, seed)
    cell = {"batch_per_chip": 1, "chips": 1,
            "length": config["sequence_length"]}
    feed = family.make_batch(config, cell, np.random.default_rng(seed))
    want = run_reference(config, main, scope, feed, biases)
    f32 = compare(run_system(exe, main, scope, model, feed, False), want)
    bf16 = compare(run_system(exe, main, scope, model, feed, True), want)
    routers = [n for n in want["grad_names"] if n.endswith(".router")]
    checks = {
        "f32_logits": f32["logit_err_max"] is not None
        and f32["logit_err_max"] <= F32_LOGIT_LIMIT,
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
        "f32_grads": f32["grad_err_worst"] <= F32_GRAD_LIMIT,
        "bf16_grads": bf16["grad_err_worst"] <= BF16_GRAD_LIMIT,
        "bf16_logits": bf16["logit_err_max"] is not None
        and bf16["logit_err_max"] <= BF16_LOGIT_LIMIT,
        "bf16_flipped": bf16["flipped_share"] <= BF16_FLIPPED_SHARE_LIMIT,
        # bfloat16 compute misses the float32 limits: the logits' and
        # the routing's every time (by two orders of magnitude), the
        # loss's on most seeds (a mean over 8192 tokens hides much)
        "bf16_fails_f32_limits": bf16["logit_err_max"] > F32_LOGIT_LIMIT
        and bf16["flipped_share"] > F32_FLIPPED_SHARE_LIMIT
        and bf16["grad_err_worst"] > F32_GRAD_LIMIT}
    return {"seed": seed, "seconds": time.perf_counter() - t0,
            "f32": f32, "bf16": bf16, "checks": checks,
            "ok": all(checks.values())}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--workload", default="lfm2-8k")
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
