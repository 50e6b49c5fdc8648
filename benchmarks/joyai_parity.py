#!/usr/bin/env python3
"""The system against the plain reference at JoyAI-LLM-Flash's
published widths and the cell's 8192 positions, on the chip, outside
any timed window.

    python3 benchmarks/joyai_parity.py --seed <n> [--seed <n> ...]

For each seed: one sequence of 8194 ids (Zipf-like, from the vocabulary
slice, as the cell draws them: tokens, labels, the module's targets),
N(0, 0.02) weights from the seed and a selection bias drawn N(0, 0.1)
in every routed layer (the start-up value is zero; drawn, "choose on
score + bias, weigh with the score" is compared), through

1. the system, AMP off, matmuls at "highest" precision: the forward
   and backward Program of `joyai-llm-flash` (`Program ->
   Executor.run`: latent attention through the Pallas kernels
   `flash_mla_fwd` / `_dkv` / `_dq` as Mosaic compiles them at 32
   heads, the expert op that holds experts 0-7 of 256, the shared
   expert, the prediction module re-entering the table and the head)
   against `reference_joyai` (attention 1024 query rows at a time, 512
   in its backward pass with every block recomputed, so that it fits):
   the main model's and the module's logits over the last 256
   positions, both losses, every token's eight experts in every routed
   layer (the module's too), the held experts' counts, and the
   gradient of EVERY parameter as the norm of the difference over the
   norm of the reference's, worst leaf (the reference's gradients in
   the published per-head layout are mapped back onto the system's
   column blocks, `grads_to_list`);
2. the system as the cell runs it (bf16 AMP, default precision)
   against the same reference, on the positions whose experts agree
   with the reference's in every routed layer: a near tie in the
   router flips under bfloat16 and sends the token through another
   expert, which is another function, not an error; the share of
   (token, layer) choices that disagree is reported and bounded.

What no run of this script sees: the optimizer and the `BiasOut`
update (tests/test_joyai_parity.py compares one AdamW step and the
bias's move with the reference, on the CPU).

Not a reader and not `run.py`'s `correct` (which cannot be extended
without an edit to `run.py`): the builder's own check, PERF.md has its
numbers.  Exits non-zero on a CPU, on a miss of any limit, and if
bfloat16 compute would pass the float32 limits (a limit that loose
checks nothing).

The limits are `lfm2_parity.py`'s, each moved only for the reason
written beside it, and each stands between two readings (my chip runs,
PR 32, seven seeds; PERF.md section 6): float32 logits 4.1e-6 .. 4.9e-6
against 4.2e-2 .. 5.0e-2 under bfloat16 (0.29 .. 0.55 with the
re-routed tokens); the losses 0 .. 1.9e-6 against 6.4e-5 .. 3.4e-4;
(token, layer) choices routed otherwise 0 .. 2.4e-5 (one of 40960)
against 6.9% .. 7.9%; the worst leaf's gradient error 2.80e-5 ..
2.85e-5 (always layer 0's latent attention, Mosaic's `flash_mla_dkv` /
`_dq`; every expert leaf <= 5.1e-6) against 0.14 .. 0.20 under
bfloat16 (the expert layers, whose re-routed tokens are in it;
bfloat16's BEST leaf reads 6.6e-3).
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
GRAD_Q_BLOCK = 512      # and in its backward pass
BIAS_STD = 0.1
F32_LOGIT_LIMIT = 1e-4
F32_LOSS_LIMIT = 1e-5
F32_FLIPPED_SHARE_LIMIT = 0.0005
F32_GRAD_LIMIT = 1e-3
BF16_LOGIT_LIMIT = 0.08
# moved from lfm2_parity.py's 0.07: a token's sorted eight of 256
# differ where ANY of eight boundaries sits in a near tie, against four
# of 64 there, and five routed layers are read, not four (readings in
# the docstring); some twice the largest
BF16_FLIPPED_SHARE_LIMIT = 0.15
BF16_GRAD_LIMIT = 0.3
FEEDS = ("tokens", "labels", "next_labels")


def bias_names(main):
    """The selection biases in creation order: the main model's routed
    layers, then the module's."""
    return [n for n in main.global_block().vars
            if n.endswith(".expert_bias")]


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
    training = config["training"]
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        model = decoder.build_model(
            max_length=config["sequence_length"], with_optimizer=False,
            aux_loss_weight=training["aux_loss_weight"],
            z_loss_weight=training["z_loss_weight"],
            mtp_loss_weight=training["mtp_loss_weight"],
            **family.architecture(config))
        model["grads"] = [g for _, g in
                          fluid.append_backward(model["loss"])]
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
    rng = np.random.default_rng(seed)
    biases = []
    for name in bias_names(main):
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
                      fetch_list=[model["ce"], model["mtp_ce"],
                                  model["logits"], model["mtp_logits"]]
                      + model["counts"] + model["experts"]
                      + model["grads"])
    return {"ce": float(np.asarray(out[0]).reshape(())),
            "mtp_ce": float(np.asarray(out[1]).reshape(())),
            "logits": np.asarray(out[2][0, -LAST:], np.float32),
            "mtp_logits": np.asarray(out[3][0, -LAST:], np.float32),
            "grads": list(out[4 + 2 * routed:]),      # on the device
            "counts": np.stack([np.asarray(c) for c in
                                out[4:4 + routed]]).astype(np.int64),
            "experts": np.stack([np.sort(np.asarray(e), axis=-1)
                                 for e in out[4 + routed:4 + 2 * routed]])}


_GRADS = {}


def run_reference(config, main, scope, feed, biases):
    """The reference's numbers on the HOST (its gradients are 2 GB the
    system's own step needs on the device)."""
    import jax
    import jax.numpy as jnp
    import reference_joyai as ref

    weight = config["training"]["mtp_loss_weight"]
    params = ref.params_from_list(
        [scope.find_var(p.name) for p in main.all_parameters()], config,
        biases)
    ids = [jnp.asarray(feed[k]) for k in FEEDS]
    _, parts = ref.loss(params, *ids, config, weight, Q_BLOCK)
    want = {"ce": float(parts["ce"]), "mtp_ce": float(parts["mtp_ce"]),
            "logits": np.asarray(parts["logits"][0, -LAST:]),
            "mtp_logits": np.asarray(parts["mtp_logits"][0, -LAST:]),
            "counts": np.stack([np.asarray(c) for c in
                                parts["counts"]]).astype(np.int64),
            "experts": np.stack([np.sort(np.asarray(e), axis=-1)
                                 for e in parts["experts"]])}
    del parts

    def flat_grads(params, *ids):
        _, g = ref.loss_and_grads(params, *ids, config, weight,
                                  GRAD_Q_BLOCK)
        return ref.grads_to_list(g, config)

    if "fn" not in _GRADS:          # one jitted program for every seed
        _GRADS["fn"] = jax.jit(flat_grads)
    want["grad_names"] = ref.system_names(config)
    want["grads"] = [np.asarray(g) for g in _GRADS["fn"](params, *ids)]
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
    """Errors of one system run against the reference; logits (the main
    model's and the module's) over the last LAST positions whose
    experts agree in every routed layer.  `experts` is (layers, tokens,
    k), sorted along k."""
    same = (got["experts"] == want["experts"]).all(axis=-1)   # (L, T)
    tail = same.all(axis=0)[-LAST:]
    err = np.maximum(np.abs(got["logits"] - want["logits"]),
                     np.abs(got["mtp_logits"] - want["mtp_logits"]))
    return {**grad_errors(got["grads"], want["grads"], want["grad_names"]),
            "logit_err_max": float(err[tail].max()) if tail.any() else None,
            "logit_err_all_max": float(err.max()),
            "logit_abs_max": float(np.abs(want["logits"]).max()),
            "loss_err": max(abs(got["ce"] - want["ce"]),
                            abs(got["mtp_ce"] - want["mtp_ce"])),
            "ce": got["ce"], "ce_reference": want["ce"],
            "mtp_ce": got["mtp_ce"], "mtp_ce_reference": want["mtp_ce"],
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
        # bfloat16 compute misses the float32 limits
        "bf16_fails_f32_limits": bf16["logit_err_max"] > F32_LOGIT_LIMIT
        and bf16["flipped_share"] > F32_FLIPPED_SHARE_LIMIT
        and bf16["grad_err_worst"] > F32_GRAD_LIMIT}
    return {"seed": seed, "seconds": time.perf_counter() - t0,
            "f32": f32, "bf16": bf16, "checks": checks,
            "ok": all(checks.values())}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--workload", default="joyai-8k")
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
