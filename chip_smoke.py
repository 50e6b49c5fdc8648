#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the main path once on a TPU through the entry points a user
calls (`import paddle_tpu as fluid`; Program/program_guard -> layers ->
optimizer.minimize -> Executor.run; DecodeEngine.submit), at full model
widths (the Transformer and ResNet-50 of `BENCHMARK.json`'s cells; a
4-layer decoder of 8 heads x 64 behind the engine), with random weights
from a seed:

    python chip_smoke.py             one chip: dropout_mask,
                                     train_transformer,
                                     train_resnet50,
                                     train_recompute, serve_decode
    python chip_smoke.py --chips 4   the four-chip host: dropout_mask
                                     over dp=4, then the same
                                     Transformer on one device and on
                                     dp=4, on one device and on dp=2 x
                                     mp=2, and nothing else

One process, JAX touched once, no children.  Every phase prints one
JSON line (compile seconds, run seconds, first/last loss or tokens
served, peak device bytes); the LAST line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Any device that is not a TPU, any phase that fails, any kernel that was
interpreted or answered by its XLA twin -> the traceback and a
non-zero exit; nothing is caught and carried past.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import json
import sys
import time

import numpy as np

# Transformer-base (Vaswani et al. 2017, Table 3: 6 layers, 8 heads,
# d_model 512, FFN 2048, dropout 0.1; vocabulary 32000 a side) at batch
# 64 x 256 positions, as benchmarks/configs/transformer-base.json has it
TRANSFORMER = dict(src_vocab_size=32000, trg_vocab_size=32000,
                   max_length=256, n_layer=6, n_head=8, d_model=512,
                   d_inner_hid=2048, dropout=0.1, use_flash=True,
                   use_amp=True)
TRANSFORMER_BATCH = 64
# the lr schedule is not a width: the recipe's 4000-step Noam warm-up
# moves nothing in a handful of steps, so every Transformer run here
# warms up over 40 and must see its loss fall
TRANSFORMER_WARMUP = 40
STEPS = 8                # training steps per phase
REQUESTS = 32            # decode requests in serve_decode
RESNET_BATCH = 128
# a decoder small enough to compile in seconds whose two layers are
# recompute segments holding a flash call (4 heads of 128, the plain
# kernel of ops/pallas/flash_attention.py); one packed sequence a step
RECOMPUTE_DECODER = dict(
    max_length=2048, hidden_size=512, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=1024,
    num_experts=0, num_experts_per_tok=0, norm_topk_prob=False,
    num_dense_layers=2, vocab_size=4096, rope_theta=1e4, rms_norm_eps=1e-6,
    recompute="layer", learning_rate=1e-3, warmup_steps=4)
DECODER = dict(vocab_size=8192, n_layer=4, n_head=8, d_model=512,
               d_inner=1024, kv_dtype="bfloat16", seed=0)
DECODE = dict(num_slots=16, page_size=16, max_len=512, num_pages=384,
              prefill_buckets=(32, 64, 128), decode_chunk=16,
              kv_dtype="bfloat16")
PROMPT_LEN = (8, 120)    # mixed prompt lengths, all three buckets
NEW_TOKENS = (48, 96)    # per-request budgets
# bf16 pools, f32 scores in the kernel vs the twin's bf16 MXU einsum:
# first-step logits (|logit| ~ 1) must agree to this absolute tolerance
LOGIT_ATOL = 5e-2
# dp / dp x mp loss vs one device.  Step 0 (same weights, forward
# only): the tolerance tests/test_hybrid_parallel.py pins in f32.  The
# later steps, after real bf16-AMP Adam updates whose reductions run in
# another order on a mesh: a bound a wrong gradient exchange would
# break (the loss falls by ~7% over the run) and rounding does not
FORWARD_RTOL = 1e-5
TRAJECTORY_RTOL = 2e-3


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes():
    """The allocator's high-water marks, the largest over the local
    devices: `in_use` counts live arrays, `reserved` also what running
    programs set aside for their temporaries — the one to hold against
    the 16 GB.  Never reset: a phase reports the peak up to its end."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    return {"in_use": max(int(s["peak_bytes_in_use"]) for s in stats),
            "reserved": max(int(s["peak_bytes_reserved"]) for s in stats)}


def require_tpu(count):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU was found — jax.devices()[0] is "
                 f"{devs[0].platform!r} ({devs[0].device_kind!r}); this "
                 f"script measures nothing on a stand-in")
    if len(devs) < count:
        sys.exit(f"chip_smoke: --chips {count} needs {count} devices, "
                 f"jax reports {len(devs)}")
    from paddle_tpu.ops import pallas

    if pallas.interpret():
        sys.exit("chip_smoke: the Pallas tier would interpret its "
                 "kernels on this backend")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# --------------------------------------------------------------------------
# training phases
# --------------------------------------------------------------------------

def _train(phase, build, feed, mesh_axes=None, inspect=None,
           masks=(0, 0), kept=(0, 0)):
    """Build under a fresh Program pair and run STEPS Executor steps
    on one fixed batch; the loss must be finite and fall, and no step
    after the first may compile.  `masks`: the `dropout` ops the step's
    build must count as drawn by (the Pallas kernel, jax.random).
    `kept`: the attention calls whose residuals its recompute segments
    keep, and their bytes.
    Over a mesh every step after the first must put its feeds and
    nothing else: the state lies where the step left it.
    `inspect(main, scope, loss, feed)` runs last, inside the guards;
    its dict joins the phase line.  Returns (per-step losses, that
    dict)."""
    import paddle_tpu as fluid
    from paddle_tpu.observe.monitoring import (format_cold_run,
                                               runtime_stats)

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = fluid.Scope()
    cold_before = runtime_stats.snapshot()["cold_runs"]
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        loss = build()["loss"]
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
        if mesh_axes:
            _data_parallel(main, loss, mesh_axes)
        else:
            import jax

            feed = jax.device_put(feed)  # once, not once per step

        def step():
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
            return float(np.asarray(lv).reshape(-1)[0])  # host: synced

        snap = runtime_stats.snapshot()
        t0 = time.perf_counter()
        losses = [step()]
        first_s = time.perf_counter() - t0
        cold = runtime_stats.delta(snap)
        # the start-up run and the first step, as the program saw them
        emit(phase + ".cold_runs", runs=[
            format_cold_run(r) for r in runtime_stats.cold_runs()[
                cold_before - runtime_stats.snapshot()["cold_runs"]:]])
        snap = runtime_stats.snapshot()
        t0 = time.perf_counter()
        losses += [step() for _ in range(STEPS - 1)]
        run_s = time.perf_counter() - t0
        late = runtime_stats.delta(snap)
        # the step's state: the persistable vars and the RNG key
        n_state = 1 + sum(v.persistable and scope.has_var(v.name)
                          for v in main.global_block().vars.values())
        extra = inspect(main, scope, loss, feed) if inspect else {}
    assert np.isfinite(losses).all(), (phase, losses)
    assert late["compiles"] == 0, \
        f"{phase}: {late['compiles']} compile(s) after the first step"
    placed = (late["place_puts"] / (STEPS - 1),
              late["place_skips"] / (STEPS - 1))
    assert placed == ((len(feed), n_state) if mesh_axes else (0, 0)), \
        f"{phase}: a step (put, passed through) {placed} arrays, " \
        f"feeds {len(feed)}, state {n_state}"
    drawn = (cold["dropout_masks_kernel"], cold["dropout_masks_xla"])
    assert drawn == masks, \
        f"{phase}: dropout masks by (kernel, jax.random) {drawn}, " \
        f"expected {masks}"
    residuals = (cold["recompute_kept_residuals"],
                 cold["recompute_kept_bytes"])
    assert residuals == kept, \
        f"{phase}: recompute segments keep (calls, bytes) {residuals}, " \
        f"expected {kept}"
    assert losses[-1] < losses[0], \
        f"{phase}: loss did not fall over {STEPS} steps: {losses}"
    emit(phase, steps=STEPS, compiles=cold["compiles"],
         compile_s=round(cold["compile_time_s"], 2),
         first_step_s=round(first_s, 2), run_s=round(run_s, 3),
         step_ms=round(1e3 * run_s / (STEPS - 1), 2),
         first_loss=losses[0], last_loss=losses[-1],
         dropout_masks_kernel=drawn[0], dropout_masks_xla=drawn[1],
         place_puts_per_step=placed[0], place_skips_per_step=placed[1],
         recompute_kept_residuals=residuals[0],
         recompute_kept_bytes=residuals[1],
         peak_bytes=peak_bytes(), **extra)
    del exe, scope, main, startup
    gc.collect()
    return losses, extra


# `dropout` ops in the Transformer's step
TRANSFORMER_MASKS = 38


def _transformer_build(**changed):
    from paddle_tpu.models import transformer

    return transformer.build_model(**{
        "warmup_steps": TRANSFORMER_WARMUP, **TRANSFORMER, **changed})


def _transformer_feed():
    from paddle_tpu.models import transformer

    return transformer.make_fake_batch(
        TRANSFORMER_BATCH, TRANSFORMER["max_length"],
        TRANSFORMER["src_vocab_size"], TRANSFORMER["trg_vocab_size"])


def train_transformer():
    _train("train_transformer", _transformer_build, _transformer_feed(),
           masks=(TRANSFORMER_MASKS, 0))


def train_resnet50():
    from paddle_tpu.models import resnet

    rng = np.random.RandomState(0)
    feed = {"data": rng.rand(RESNET_BATCH, 3, 224, 224)
            .astype(np.float32),
            "label": rng.randint(0, 1000, (RESNET_BATCH, 1))
            .astype(np.int32)}
    _train("train_resnet50",
           lambda: resnet.build_model(dataset="flowers", depth=50,
                                      class_dim=1000, learning_rate=0.01,
                                      use_amp=True),
           feed)


def train_recompute():
    """A decoder whose layers are recompute segments: each keeps its
    flash kernel's output and logsumexp (`runtime_stats.
    recompute_kept_*` around the step's build), and the compiled step
    holds each layer's forward kernel once."""
    from paddle_tpu.models import decoder

    arch = RECOMPUTE_DECODER
    t, width = arch["max_length"], arch["hidden_size"]
    heads, depth = arch["num_attention_heads"], arch["num_hidden_layers"]
    tokens = np.random.RandomState(0).randint(
        0, arch["vocab_size"], (1, t + 1)).astype(np.int64)

    def kernels(main, scope, loss, feed):
        import paddle_tpu as fluid

        text = fluid.Executor(fluid.TPUPlace(0)).compiled_step(
            main, feed, [loss], scope=scope).as_text()
        calls = [ln for ln in text.splitlines()
                 if "tpu_custom_call" in ln and " custom-call(" in ln]
        found = {k: sum(f"pallas_{k}/" in ln for ln in calls)
                 for k in ("flash_fwd", "flash_dkv")}
        assert found == {"flash_fwd": depth, "flash_dkv": depth}, \
            f"train_recompute: the step's flash kernels are {found}"
        return {"flash_kernels": found}

    # o in bf16 + 8 float32 sublanes of logsumexp a head, a layer
    _train("train_recompute", lambda: decoder.build_model(**arch),
           {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]},
           inspect=kernels,
           kept=(depth, depth * (t * width * 2 + heads * 8 * t * 4)))


# --------------------------------------------------------------------------
# the dropout keep-mask kernel (ops/pallas/dropout_mask.py)
# --------------------------------------------------------------------------

# the Transformer step's two mask shapes: residual / embedding dropout
# and the decoder's cross-attention weights
MASK_SHAPES = ((64, 256, 512), (64, 8, 256, 256))
SIGMAS = 4.0


def _within(name, got, want, n):
    """`got`, a share of `n` independent positions, within SIGMAS
    standard deviations of `want`."""
    sigma = (want * (1.0 - want) / n) ** 0.5
    assert abs(got - want) <= SIGMAS * sigma, \
        f"dropout_mask: {name} {got!r} is {abs(got - want) / sigma:.1f} " \
        f"sigma from {want} (n {n})"
    return round((got - want) / sigma, 2)


def dropout_mask(mesh_axes=None):
    """The `dropout` op on this backend, at the step's two mask shapes:
    the masks must come from the kernel, keep 1 - p of the positions,
    be a function of the key alone, and be independent between ops,
    steps, adjacent blocks, neighbouring rows and columns at the
    tilings' periods, and (on a mesh) ranks; the gradient is the mask
    over 1 - p exactly.  A share of n positions may lie SIGMAS standard
    deviations from its expectation."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe.monitoring import runtime_stats
    from paddle_tpu.ops.pallas.dropout_mask import tiling
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.mesh import executing_mesh

    ranks = mesh_axes["dp"] if mesh_axes else 1
    mesh = make_mesh(mesh_axes) if mesh_axes else None

    def op(key, op_index, x, p):
        return get_op_impl("dropout")(
            OpContext(key, op_index), {"X": [x]},
            {"dropout_prob": p,
             "dropout_implementation": "upscale_in_train"})

    def masks(keys, x, p):
        """Masks (as the op's `Mask` output, 1 = keep) of: step 0 op 3,
        the same again, step 0 op 4, step 1 op 3; and d sum(Out) / dx
        of the first."""
        with (executing_mesh(mesh, "dp") if mesh is not None
              else contextlib.nullcontext()):
            out = [op(keys[0], 3, x, p)["Mask"][0],
                   op(keys[0], 3, x, p)["Mask"][0],
                   op(keys[0], 4, x, p)["Mask"][0],
                   op(keys[1], 3, x, p)["Mask"][0]]
            grad = jax.grad(lambda v: jnp.sum(
                op(keys[0], 3, v, p)["Out"][0]))(x)
        return out, grad

    def stats(keys, x, p, b, chunk):
        (a, again, other_op, other_step), grad = masks(keys, x, p)
        m = a.reshape(-1, a.shape[-1]) > 0
        blocks = m.reshape(-1, b, m.shape[-1])
        per_rank = m.reshape(ranks, -1)
        share = lambda v: jnp.mean(v.astype(jnp.float32))  # noqa: E731
        return {
            "keep": share(m),
            "same_key_equal": jnp.all(a == again),
            "op_diff": share(a != other_op),
            "step_diff": share(a != other_step),
            "block_diff": share(blocks[0] != blocks[1]),
            "rank_diff": (share(per_rank[0] != per_rank[1])
                          if ranks > 1 else jnp.float32(0)),
            # the periods of the tilings: a sublane, a 32-bit tile, an
            # 8-bit tile, the kernel's inner-loop trip; a lane, a lane
            # tile
            **{f"row_lag{k}_diff": share(m[k:] != m[:-k])
               for k in (1, 8, 32, chunk)},
            **{f"col_lag{k}_diff": share(m[:, k:] != m[:, :-k])
               for k in (1, 128)},
            "constant_rows": jnp.sum(jnp.all(m, 1) | ~jnp.any(m, 1)),
            "constant_block_cols": jnp.sum(jnp.all(blocks, 1)
                                           | ~jnp.any(blocks, 1)),
            "grad_is_mask_over_keep": jnp.all(
                grad == a * jnp.float32(1.0 / (1.0 - p))),
        }

    for shape, p in [(s, 0.1) for s in MASK_SHAPES] + [(MASK_SHAPES[0],
                                                        0.5)]:
        shape = (shape[0] * ranks,) + shape[1:]
        local_rows = int(np.prod(shape[:-1])) // ranks
        b, chunk = tiling(local_rows, shape[-1])
        assert local_rows // b >= 2, (shape, b)
        n = int(np.prod(shape))
        keys = jax.random.split(jax.random.PRNGKey(20270927 + n), 2)
        x = jnp.ones(shape, jnp.float32)
        if mesh is not None:
            x = jax.device_put(x, NamedSharding(mesh, P("dp")))
        snap = runtime_stats.snapshot()
        got = jax.jit(stats, static_argnums=(2, 3, 4))(keys, x, p, b, chunk)
        drawn = runtime_stats.delta(snap)
        got = {k: np.asarray(v).item() for k, v in got.items()}
        assert (drawn["dropout_masks_kernel"], drawn["dropout_masks_xla"]) \
            == (5, 0), drawn
        z = {"keep": _within("keep rate", got["keep"], 1.0 - p, n)}
        for k, v in got.items():
            if not k.endswith("_diff") or (k == "rank_diff" and ranks == 1):
                continue
            count = {"block_diff": b * shape[-1],
                     "rank_diff": n // ranks}.get(k, n)
            z[k] = _within(k, v, 2.0 * p * (1.0 - p), count)
        assert got["same_key_equal"] and got["grad_is_mask_over_keep"], got
        assert got["constant_rows"] == 0 \
            and got["constant_block_cols"] == 0, got
        emit("dropout_mask", shape=list(shape), p=p, ranks=ranks,
             block_rows=b, chunk_rows=chunk, sigmas=z, **got)


# --------------------------------------------------------------------------
# serving phase
# --------------------------------------------------------------------------

def _serve(lm, prompts, budgets):
    """One DecodeEngine over `lm`: warm up, serve every request, drain.
    Returns (tokens per request, stats snapshot — its "warmup" entry
    holds the compile count and seconds —, serve seconds, the decode
    step's compiled text)."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    engine = DecodeEngine(lm, DecodeConfig(**DECODE),
                          queue_capacity=4 * len(prompts))
    engine.start()
    text = engine._decode_exec.as_text()  # the executable that serves
    t0 = time.perf_counter()
    futs = [engine.submit(p, max_new_tokens=int(b))
            for p, b in zip(prompts, budgets)]
    outs = [f.result(600).tolist() for f in futs]  # host tokens: synced
    serve_s = time.perf_counter() - t0
    assert engine.drain(120), "decode engine did not drain"
    snap = engine.stats.snapshot()
    engine.close()
    return outs, snap, serve_s, text


def _first_step_logits(lm_pallas, lm_twin, prompts):
    """Prefill one batch of ragged prompts into fresh pools through the
    Executor, then run the FIRST decode step on both models from the
    same pools and return both logit arrays (S, vocab)."""
    import jax.numpy as jnp

    import paddle_tpu as fluid

    s, page = DECODE["num_slots"], DECODE["page_size"]
    bucket = max(DECODE["prefill_buckets"])
    maxp = DECODE["max_len"] // page
    per_slot = bucket // page + 1            # pages: prompt + one step
    lens = np.array([len(p) for p in prompts[:s]], np.int32)
    tokens = np.zeros((s, bucket), np.int32)
    for i, p in enumerate(prompts[:s]):
        tokens[i, :len(p)] = p
    page_table = np.zeros((s, maxp), np.int32)
    page_table[:, :per_slot] = np.arange(s * per_slot).reshape(s, -1)
    pools = lm_pallas.fresh_pools(DECODE["num_pages"], page)
    cache_names = lm_pallas.cache_feed_names()
    exe = fluid.Executor(fluid.TPUPlace(0))

    pre = lm_pallas.prefill(bucket)
    with fluid.scope_guard(lm_pallas.init_params()):
        got = exe.run(pre["main"],
                      feed=dict(pools, tokens=tokens, seq_len=lens,
                                last_idx=(lens - 1).reshape(s, 1),
                                page_table=page_table),
                      fetch_list=[pre["next_token"]] + pre["cache_outs"],
                      return_numpy=False)
    first_tok = np.asarray(got[0]).astype(np.int32)
    pools = dict(zip(cache_names, got[1:]))
    out = []
    for lm in (lm_pallas, lm_twin):
        st = lm.step
        with fluid.scope_guard(lm.init_params()):
            (lg,) = exe.run(st["main"],
                            feed=dict(pools, tokens=first_tok,
                                      write_pos=lens, lengths=lens + 1,
                                      active=np.ones((s,), np.int32),
                                      page_table=page_table),
                            fetch_list=[st["logits"]])
        out.append(np.asarray(jnp.asarray(lg, jnp.float32)))
    return out


def serve_decode():
    from paddle_tpu.models.decoder_lm import DecoderLM, make_prompts

    prompts = make_prompts(REQUESTS, DECODER["vocab_size"],
                           min_len=PROMPT_LEN[0], max_len=PROMPT_LEN[1],
                           seed=0)
    budgets = np.random.RandomState(1).randint(
        NEW_TOKENS[0], NEW_TOKENS[1] + 1, REQUESTS)
    lm = DecoderLM(use_pallas=True, **DECODER)
    twin = DecoderLM(use_pallas=False, **DECODER)

    outs, snap, serve_s, text = _serve(lm, prompts, budgets)
    assert "tpu_custom_call" in text, \
        "the decode step holds no Mosaic kernel: the twin answered"
    assert [len(o) for o in outs] == [int(b) for b in budgets], \
        "a request did not run to its budget"
    assert snap["post_warmup_compiles"] == 0, snap["post_warmup_compiles"]
    t_outs, t_snap, t_serve_s, t_text = _serve(twin, prompts, budgets)
    assert "tpu_custom_call" not in t_text, "the twin ran a kernel"
    assert t_snap["post_warmup_compiles"] == 0

    lg, t_lg = _first_step_logits(lm, twin, prompts)
    assert lg.shape == (DECODE["num_slots"], DECODER["vocab_size"])
    assert np.isfinite(lg).all() and np.isfinite(t_lg).all()
    err = float(np.abs(lg - t_lg).max())
    assert err <= LOGIT_ATOL, \
        f"first-step logits: kernel vs XLA twin differ by {err}"
    same = sum(a == b for o, t in zip(outs, t_outs) for a, b in zip(o, t))
    total = sum(len(o) for o in outs)
    emit("serve_decode", requests=REQUESTS, tokens_served=total,
         warmup=snap["warmup"],
         serve_s=round(serve_s, 3), twin_serve_s=round(t_serve_s, 3),
         post_warmup_compiles=snap["post_warmup_compiles"],
         preemptions=snap["preemptions"], prefills=snap["prefills"],
         decode_dispatches=snap["decode_dispatches"],
         kernel_in_decode_step=True,
         logit_max_abs_diff=err, logit_atol=LOGIT_ATOL,
         logit_max_abs=float(np.abs(t_lg).max()),
         token_agreement=round(same / total, 4),
         peak_bytes=peak_bytes())


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------

def _data_parallel(main, loss, mesh_axes):
    import paddle_tpu as fluid
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.strategies import megatron_transformer_rules

    bs = fluid.BuildStrategy()
    if mesh_axes.get("mp", 1) > 1:
        bs.sharding_rules = megatron_transformer_rules()
    fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, build_strategy=bs,
        mesh=make_mesh(mesh_axes))


def _residency(main, scope, loss, feed):
    """Where the state lives after the steps, and what the compiled
    step exchanges: every persistable var's shards by device, and the
    collectives in the SPMD step's text."""
    per_device = {}
    n_sharded = 0
    for var in main.global_block().vars.values():
        if not var.persistable or not scope.has_var(var.name):
            continue
        arr = scope.find_var(var.name)
        shards = getattr(arr, "addressable_shards", None)
        if not shards:
            continue
        for sh in shards:
            per_device[sh.device.id] = (per_device.get(sh.device.id, 0)
                                        + sh.data.nbytes)
        n_sharded += shards[0].data.shape != arr.shape
    text = main._compiled_wrapper.compiled_step(
        feed, [loss.name], scope).as_text()
    return {"state_devices": sorted(per_device),
            "state_bytes_per_device": sorted(per_device.values()),
            "vars_sharded": int(n_sharded),
            "all_reduces": text.count(" all-reduce("),
            "all_gathers": text.count(" all-gather("),
            "reduce_scatters": text.count(" reduce-scatter(")}


def four_chips():
    """One device against a mesh, step for step.  dp=4 draws its
    dropout masks with the kernel, each chip its own rows, and they
    must be the one-device step's masks: dropout stays on.  dp=2 x
    mp=2 keeps jax.random.bernoulli (ops/pallas/dropout_mask.py), other
    masks than the kernel's, so that pair runs without dropout, as
    every parity test of the repo does; and with twice the warm-up,
    because without dropout the 40-step schedule overshoots at step 6
    (loss 9.58 -> 9.95 -> 9.61) and the overshoot magnifies rounding to
    0.6% (my chip run, PR 27)."""
    feed = _transformer_feed()
    seen = {}
    for name, axes, changed, masks in (
            ("transformer_dp4", {"dp": 4}, {}, (TRANSFORMER_MASKS, 0)),
            ("transformer_dp2mp2", {"dp": 2, "mp": 2},
             {"dropout": 0.0, "warmup_steps": 2 * TRANSFORMER_WARMUP},
             (0, 0))):
        build = functools.partial(_transformer_build, **changed)
        single, _ = _train(name + "_1dev", build, feed, masks=masks)
        single = np.asarray(single)
        losses, r = _train(name, build, feed, mesh_axes=axes,
                           inspect=_residency, masks=masks)
        r["rel"] = np.abs(np.asarray(losses) - single) / np.abs(single)
        emit(name + "_parity", losses=losses, single=single.tolist(),
             step0_rel_diff=float(r["rel"][0]),
             forward_rtol=FORWARD_RTOL,
             max_rel_diff=float(r["rel"].max()),
             trajectory_rtol=TRAJECTORY_RTOL)
        seen[name] = r
    # every mesh's evidence is printed before the first check can fail
    for name, r in seen.items():
        assert r["rel"][0] <= FORWARD_RTOL, (name, r["rel"])
        assert r["rel"].max() <= TRAJECTORY_RTOL, (name, r["rel"])
        assert len(r["state_devices"]) == 4, (name, r)
        assert r["all_reduces"] > 0, (name, r)
    dp, mp = seen["transformer_dp4"], seen["transformer_dp2mp2"]
    # dp replicates the state: four equal copies; mp shards the
    # Megatron-ruled matrices, so every device holds less
    assert len(set(dp["state_bytes_per_device"])) == 1, dp
    assert mp["vars_sharded"] > 0, mp
    assert max(mp["state_bytes_per_device"]) \
        < min(dp["state_bytes_per_device"]), (mp, dp)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the one-device vs dp4 vs dp2xmp2 "
                         "Transformer comparison on the four-chip host")
    args = ap.parse_args()

    from paddle_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = require_tpu(args.chips)
    emit("start", cache_dir=cache_dir, **device)
    t0 = time.perf_counter()
    if args.chips == 4:
        dropout_mask({"dp": 4})
        four_chips()
    else:
        dropout_mask()
        train_transformer()
        train_resnet50()
        train_recompute()
        serve_decode()
    emit("done", total_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
