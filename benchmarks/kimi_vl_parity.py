#!/usr/bin/env python3
"""The system against the plain reference at Kimi-VL-A3B's published
widths and the cell's sizes (a tower of 8 layers of 16 heads of 72 lanes
over 24576 packed patches of the cell's sixteen images, the projector,
a decoder of 5 layers over 8192 positions, 8 held experts of 64, 20480
vocabulary rows), on the chip, outside any timed window.

    python3 benchmarks/kimi_vl_parity.py --seed <n> [--seed <n> ...]
        [--rotary bfloat16]

For each seed: one batch as the cell draws it (the images' aspects and
order, the text between them, N(0, 1) pixels), every matrix and both
tables N(0, 0.02) from the seed, through

1. the system, AMP off, matmuls at "highest" precision: the forward and
   backward Program of `kimi-vl-a3b` as the timed step builds it (ONE
   `Program -> Executor.run` that holds tower, projector, merge and
   decoder: every layer a recompute segment, `flash_segment_fwd` /
   `_bwd` as Mosaic compiles them in float32 at heads laid out at 128
   lanes, the position table through the collator's taps, the
   latent-attention kernels at 16 heads, the expert op that holds
   experts 0-7 of 64) against `reference_kimi_vl` (the tower ONE IMAGE
   AT A TIME in the published row-major order under full soft-max
   attention, bicubic interpolation from its definition, the merger's
   permutation; the decoder's scores 512 query rows at a time, 256 in
   its backward pass; every layer recomputed in its backward pass, so
   that it fits): the logits of the last 256 positions, the weighted
   loss, every token's six experts in every routed layer, the held
   experts' counts, and the gradient of EVERY parameter leaf of tower,
   projector and decoder as the norm of the difference over the norm of
   the reference's, worst leaf;
2. the system as the cell runs it (bf16 AMP, default precision) against
   the same reference, the logits on the positions whose six experts
   agree with the reference's in every layer.

`--rotary bfloat16` is the CONTROL: the same float32 run with the
tower's rotary cos and sin rounded to bfloat16 (a patch of
`ops/decoder.py _cos_sin_two_axes` made here, all else float32); it must
MISS a float32 limit, and the script then exits 0 only if it does.

Not a reader and not `run.py`'s `correct` (which cannot be extended
without an edit to `run.py`): the builder's own check, PERF.md has its
numbers.  Exits non-zero on a CPU, on a miss of any limit, and if
bfloat16 compute would pass the float32 limits.  The system's side, the
comparison and the gradient norms are `mellum_parity.py`'s; the build
(two builders, one Program), the reference's feeds, the limits and the
checks are this file's.
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
import reference_kimi_vl as reference  # noqa: E402
import run as bench_run  # noqa: E402

# The limits (my chip runs, PR 73; PERF.md section 6).  They were written
# from the first round's readings (two seeds and the control, on a layout
# of the images the review took out) and are held as they were; the
# second round ran the script as committed, on ISSUE 73's traffic, to its
# exit code: seeds 2147493001, 2147493103 (one process) and 2147493211,
# 695-835 s a seed, exit 0; `--rotary bfloat16` on the first seed, exit 0
# because it missed.  THREE seeds, not the four R0 c asks: a fourth did
# not fit the chip time left.  Float32, the two seeds whose held experts'
# rows agree: logits 4.3e-6 .. 4.5e-6 (of logits up to 5.0), the loss 0,
# NO (token, layer) choice of 32,768 routed otherwise, the worst gradient
# leaf 2.9e-5 .. 3.0e-5 (layer 0's latent attention, `wkv_a.rope`:
# Mosaic's `flash_mla_dkv`, as `joyai_parity.py` reads it), the tower's
# worst 2.3e-5 .. 2.4e-5 (`vision.layer0.wq`).  The third seed routed TWO
# choices otherwise in float32 (6.1e-5; a held expert's counts differ),
# the first reading the OTHER ROWS limits have of their own: logits
# 4.4e-5, the loss 0, the worst leaf 3.1e-4 (`layer3.w1`, an expert's:
# its rows are other rows), the tower's 8.0e-5 (`vision.patch_w`); those
# limits had been set as `kimi_linear_parity.py`'s readings widen (x 3,
# x 20, x 5 of the same-rows limits) and hold it with 2 x and 19 x of
# room.  bf16 AMP as the cell runs it, three seeds: logits 0.056 .. 0.057
# on the positions whose experts agree, the loss 1.1e-4 .. 3.0e-4,
# choices routed otherwise 32.8 % .. 33.2 % (six of 64 under sigmoid
# scores that start within 1e-2 of one another: a third of the choices
# is a near tie in bfloat16), the worst leaf 0.33 .. 0.75 (an expert's
# matrix).  THE TOWER'S ROTARY COS AND SIN ROUNDED TO bfloat16, all else
# float32 (`--rotary bfloat16`): logits 2.6e-4, the worst leaf 2.3e-3
# (`vision.layer4.bk`), three choices routed otherwise (9.2e-5; the first
# round's control read six, 1.8e-4), the held counts equal, the loss 0.
# The float32 limits of logits and gradients stand at the geometric mean
# of the exact run and the control (3.4e-5 and 2.9e-4: 7 x and 8 x of
# room each way), and the control misses both; the loss's between 0 and
# bf16 AMP's 1.1e-4.  THE SHARE'S LIMIT SEPARATES NOTHING: an exact run
# read 6.1e-5 and the control 9.2e-5, both under it; it only says that
# float32 routes as the reference but for a near tie or three.
F32_LOGIT_LIMIT = 3.4e-5            # the held experts' rows agree
F32_GRAD_LIMIT = 2.9e-4
F32_LOSS_LIMIT = 6e-6
F32_LOGIT_LIMIT_OTHER_ROWS = 1e-4   # a flipped choice reached them
F32_GRAD_LIMIT_OTHER_ROWS = 5.8e-3
F32_LOSS_LIMIT_OTHER_ROWS = 3e-5
F32_FLIPPED_SHARE_LIMIT = 1e-4
# bf16 AMP: some three times the largest reading (a share: twice)
BF16_LOGIT_LIMIT = 0.17
BF16_LOSS_LIMIT = 1.1e-3
BF16_FLIPPED_SHARE_LIMIT = 0.67
BF16_GRAD_LIMIT = 1.3
FEEDS = ("tokens", "labels", "loss_weights", "pixel_values")
_JITTED = {}


def build_forward(config, family, seed):
    """The forward and backward Program (no optimizer) at the published
    widths, as the timed step builds it (`family.build_model`: the
    tower, then the decoder that reads its rows at the placeholder
    ids); weights from the seed, AMP off."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        model = family.build_model(config, with_optimizer=False)
        model["grads"] = [g for _, g in
                          fluid.append_backward(model["loss"])]
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
    return exe, main, scope, model


def run_reference(config, main, scope, feed, grids):
    """The reference's numbers on the HOST (one jitted pair a set of
    grids: the images' shapes are static in it)."""
    import jax
    import jax.numpy as jnp

    params = reference.params_from_list(
        [scope.find_var(p.name) for p in main.all_parameters()], config)
    feeds = [jnp.asarray(feed[k]) for k in FEEDS]

    def forward(params, *feeds):
        total, parts = reference.loss(params, *feeds, config, grids,
                                      base.Q_BLOCK, True)
        return (total, parts["logits"][0, -base.LAST:],
                jnp.stack(parts["counts"]), jnp.stack(parts["experts"]))

    def flat_grads(params, *feeds):
        _, g = reference.loss_and_grads(params, *feeds, config, grids,
                                        base.GRAD_Q_BLOCK)
        return reference.grads_to_list(g, config)

    if grids not in _JITTED:
        _JITTED.clear()             # a seed's images are its own
        _JITTED[grids] = (jax.jit(forward), jax.jit(flat_grads))
    forward_fn, grads_fn = _JITTED[grids]
    total, logits, counts, chosen = forward_fn(params, *feeds)
    want = {"loss": float(total), "logits": np.asarray(logits),
            "counts": np.asarray(counts).astype(np.int64),
            "experts": np.sort(np.asarray(chosen), axis=-1),
            "grad_names": reference.system_names(config)}
    del total, logits, counts, chosen
    want["grads"] = [np.asarray(g) for g in grads_fn(params, *feeds)]
    return want


def rotary_in_bfloat16():
    """The control: the tower's rotary cos and sin rounded to bfloat16
    (`reduce_precision`: the chip's compiler takes a float32 -> bfloat16
    -> float32 pair of converts out again, and did, PR 73)."""
    import jax

    from paddle_tpu.ops import decoder as ops

    exact = ops._cos_sin_two_axes

    def rounded(*args):
        return tuple(jax.lax.reduce_precision(x, exponent_bits=8,
                                              mantissa_bits=7)
                     for x in exact(*args))

    ops._cos_sin_two_axes = rounded


def check_seed(config, family, cell, seed, control=False):
    t0 = time.perf_counter()
    exe, main, scope, model = build_forward(config, family, seed)
    rng = np.random.default_rng(seed)
    grids = tuple(family.draw_grids(cell, rng))
    feed = family.make_batch(config, dict(cell, batch_per_chip=1, chips=1),
                             rng, grids=grids)
    want = run_reference(config, main, scope, feed, (grids,))
    f32 = base.compare(base.run_system(exe, main, scope, model, feed, False),
                       want)
    routers = [n for n in want["grad_names"] if n.endswith(".router")]
    same_rows = f32["counts_equal"]
    logit_limit, grad_limit, loss_limit = (
        (F32_LOGIT_LIMIT, F32_GRAD_LIMIT, F32_LOSS_LIMIT) if same_rows else
        (F32_LOGIT_LIMIT_OTHER_ROWS, F32_GRAD_LIMIT_OTHER_ROWS,
         F32_LOSS_LIMIT_OTHER_ROWS))
    tower = {n: e for n, e in f32["grad_err"].items()
             if n.startswith("vision.")}
    f32["grad_err_worst_tower_leaf"] = max(tower, key=tower.get)
    f32["grad_err_worst_tower"] = max(tower.values())
    checks = {
        "f32_logits": f32["logit_err_max"] is not None
        and f32["logit_err_max"] <= logit_limit,
        "f32_loss": f32["loss_err"] <= loss_limit,
        "f32_routing": f32["flipped_share"] <= F32_FLIPPED_SHARE_LIMIT,
        "f32_held_counts": same_rows or f32["flipped_share"] > 0.0,
        "share_is_a_share": all(
            0 < rows < cell["length"] * config["num_experts_per_tok"]
            for rows in f32["held_rows"]),
        "grads_are_compared": f32["grad_dead_leaves"] == routers,
        "f32_grads": f32["grad_err_worst"] <= grad_limit}
    result = {"seed": seed, "grids": grids, "f32": f32}
    if control:
        result["control_fails_a_limit"] = not (
            checks["f32_logits"] and checks["f32_grads"]
            and checks["f32_routing"])
        result["ok"] = result["control_fails_a_limit"]
    else:
        bf16 = base.compare(
            base.run_system(exe, main, scope, model, feed, True), want)
        checks.update({
            "bf16_grads": bf16["grad_err_worst"] <= BF16_GRAD_LIMIT,
            "bf16_logits": bf16["logit_err_max"] is not None
            and bf16["logit_err_max"] <= BF16_LOGIT_LIMIT,
            "bf16_loss": bf16["loss_err"] <= BF16_LOSS_LIMIT,
            "bf16_flipped": bf16["flipped_share"]
            <= BF16_FLIPPED_SHARE_LIMIT,
            "bf16_fails_f32_limits": bf16["logit_err_max"] is not None
            and bf16["logit_err_max"] > F32_LOGIT_LIMIT_OTHER_ROWS
            and bf16["grad_err_worst"] > F32_GRAD_LIMIT_OTHER_ROWS})
        result.update(bf16=bf16, ok=all(checks.values()))
    return dict(result, checks=checks, seconds=time.perf_counter() - t0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--rotary", choices=("float32", "bfloat16"),
                    default="float32")
    args = ap.parse_args(argv)
    cell, config, family = bench_run.load_cell("kimivl-8k", (HERE,))
    device = bench_run.require_tpu(1, (HERE,))
    control = args.rotary == "bfloat16"
    if control:
        rotary_in_bfloat16()
    results = []
    for seed in args.seed:
        results.append(check_seed(config, family, cell, seed, control))
        print(json.dumps(results[-1]), flush=True)
    ok = all(r["ok"] for r in results)
    print(json.dumps({"ok": ok, "device": device["kind"],
                      "seeds": args.seed, "rotary": args.rotary}),
          flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
