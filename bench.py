"""Benchmark harness — prints ONE JSON line with the headline metric.

reference: benchmark/fluid/fluid_benchmark.py (imgs/sec reporting with
--use_fake_data).  Headline metric (BASELINE.json): min train MFU over
ResNet-50 (imgs/sec/chip) and Transformer (tokens/sec/chip) against the
chip's bf16 peak (north star: >=35% MFU).  All five BASELINE.json
tracked configs have entries: ResNet-50, Transformer, BERT-base,
stacked dynamic LSTM, DeepFM; plus serving latency (bf16 + int8, bs8
latency shape + bs64 throughput shape) and the dynamic-batching
ServingEngine offered-load line (`serving_engine`, docs/SERVING.md).

Honesty rules:
- ResNet's headline entry uses data_mode="synthetic" (FRESH on-device
  batch every step); the frozen-feed ceiling (reference --use_fake_data
  upper bound) is recorded alongside as `resnet50_frozen`.
- MFU numerators come from XLA's own cost analysis of the compiled
  step.  Pallas custom calls are INVISIBLE to that count, so
  Pallas-active configs add each custom call's registered
  dense-equivalent cost (ops/pallas KERNEL_COSTS via observe.cost —
  the standard flash-attention MFU convention: same logical math,
  skipped masked blocks not credited, backward recompute not
  double-counted).  The twin (`_dense_equiv_flops`) remains the
  numerator only for recompute configs (remat double-counts in any
  HLO-side count) and for the XLA flash composition (bert).
- No stand-in: a missing backend, a device kind without a row in
  _PEAK_FLOPS, or a failed model still prints the JSON line but the
  process exits non-zero.  One process holds the chip; bench.py starts
  no child that needs it.

Run on the real TPU chip: `python bench.py [--model all|resnet50|
transformer|bert|lstm|deepfm|serving|serving_engine] [--batch N] [--steps N]
[--no-amp] [--no-flash] [--data synthetic|frozen|host]`.  Default 60
timed steps: a ~3 s timed window keeps MFU stable run-to-run.

Multi-chip (docs/DIST.md): `--mesh dp=N` (or `dp=2,mp=2`, `fsdp=4`)
benches the training models over a device mesh — global-batch feeds
shard over the data axes (dp + fsdp), an mp axis applies the Megatron
transformer rules, an fsdp axis ZeRO-shards optimizer state.  Entries
key `<model>_dp8` / `<model>_dp2mp2` and carry per_device_*
throughput next to the aggregate, MFU against the aggregate peak, the
sharded step's comm-bucket bytes, and opt_state_bytes_per_device;
`--grad-sync int8` swaps the gradient all-reduce for the EQuARX
blockwise-quantized exchange (opt-in, no ledger row; psum-form on
composed meshes).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

# bf16 peak TFLOP/s by device kind (MXU peak; all models bench in bf16)
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,   # v6e (Trillium)
    "TPU v6e": 918e12,
}


def _peak_flops():
    """(bf16 peak FLOP/s, device kind) of the device this process
    runs on.  A device without a row in _PEAK_FLOPS is an error: an
    MFU against an assumed peak is not a measurement."""
    import jax

    kind = jax.devices()[0].device_kind
    for key, val in _PEAK_FLOPS.items():
        if kind.startswith(key):
            return val, kind
    raise RuntimeError(
        f"bench.py has no peak-FLOP/s row for device kind {kind!r} "
        f"(known: {sorted(_PEAK_FLOPS)}); it measures on a TPU only")


_PROFILE_DIR = None  # set by --profile; wraps every timed window
_TELEMETRY = True    # --no-telemetry disables the in-step accumulator
_GUARD = False       # --guard enables the non-finite update guard


def _enable_observability(program):
    """Bench honesty (resilience satellite): training configs run with
    the device-side telemetry accumulator ON so every JSON line can
    carry nonfinite_steps/skipped_update_steps — a throughput number
    produced while gradients were NaN (or, with --guard, while
    optimizer updates were being SKIPPED) must be visible to perf_gate,
    not laundered into a headline.  Must run before the Executor builds
    the step fn."""
    if _GUARD:
        from paddle_tpu import resilience

        resilience.enable_update_guard(program)  # implies telemetry
    if _TELEMETRY or _GUARD:
        from paddle_tpu import observe

        # observe pillar 6 rides the same accumulator: per-group
        # dynamics + first-nonfinite provenance, so every training
        # entry can attribute a tainted window to a fluid op/layer
        # (implies enable_telemetry)
        observe.enable_numerics(program)


def _fetch_tel(program, scope):
    """One host sync: the measured window's telemetry (None when
    telemetry is off).  The program join lets a latched nonfinite
    bitmap name its fluid op in the entry."""
    if not getattr(program, "_telemetry_enabled", False):
        return None
    from paddle_tpu import observe

    return observe.fetch_telemetry(scope, reset=True, program=program)


def _tel_fields(tel):
    """The honesty fields every training entry carries.  None = this
    run measured without telemetry (--no-telemetry) — explicitly
    unknown, not clean.  grad_norm_last + the worst-group update ratio
    (observe pillar 6) make divergence visible next to the throughput
    number; first_nonfinite_op appears only when a window tripped."""
    if tel is None:
        return {"nonfinite_steps": None, "skipped_update_steps": None,
                "grad_norm_last": None, "update_ratio_worst": None}
    from paddle_tpu import observe

    wg, wr = observe.worst_update_ratio(tel.groups)
    out = {"nonfinite_steps": max(tel.nonfinite_grad_steps,
                                  tel.nonfinite_loss_steps),
           "skipped_update_steps": tel.skipped_update_steps,
           "grad_norm_last": round(tel.grad_norm_last, 6),
           "update_ratio_worst": (round(wr, 8) if wr is not None
                                  else None)}
    if wg is not None:
        out["update_ratio_worst_group"] = wg
    if tel.first_nonfinite_op is not None:
        out["first_nonfinite_op"] = tel.first_nonfinite_op
    return out


def _new_ledger():
    """A GoodputLedger with its wall window already open (observe
    pillar 8): each training bench fn owns one so its entry can carry
    the goodput decomposition next to the MFU headline."""
    from paddle_tpu.observe import GoodputLedger

    led = GoodputLedger()
    led.open_window()
    return led


def _goodput_fields(ledger, mfu=None):
    """Close the entry's ledger window and stamp the goodput fields
    every training entry carries: `goodput` (step fraction of wall),
    `effective_mfu` = headline MFU x goodput, and `badput_breakdown`
    (every non-step category's wall fraction — compile, data_stall,
    checkpoint, ... idle).  The bench wall here is the measurement
    harness's own anatomy (warmup compiles, the throwaway ckpt save),
    honest context for the headline, not a production goodput claim."""
    if ledger is None:
        return {}
    from paddle_tpu.observe.goodput import GOODPUT_CATEGORY

    ledger.close_window()
    rep = ledger.report(mfu=mfu)
    out = {"goodput": rep["goodput"],
           "badput_breakdown": {c: f for c, f in rep["fractions"].items()
                                if c != GOODPUT_CATEGORY}}
    if mfu is not None:
        out["effective_mfu"] = rep["effective_mfu"]
    return out


def _timed_loop(exe, program, feed_dev, loss, steps, warmup, scope=None,
                ledger=None):
    """Device-resident data loop: feeds are placed on device once; the
    timed window is ONE host dispatch chaining `steps` training steps
    on-chip; a final fetch synchronizes and validates the loss.  With
    --profile DIR the timed window is captured as a jax.profiler trace
    (the input for closing the MFU gap: op-level device timelines, HBM
    traffic).
    Returns (elapsed_s, last_loss, telemetry-of-the-timed-window)."""
    import contextlib

    def _phase(label, n):
        # warmup/chain dispatches are step-shaped work too; their XLA
        # compile wall is re-attributed to "compile" by the ledger
        return (ledger.phase("step", label=label, steps=n)
                if ledger is not None else contextlib.nullcontext())

    with _phase("warmup", warmup):
        for _ in range(warmup):
            exe.run(program, feed=feed_dev, fetch_list=[loss])
    with _phase("chain_warm", steps):
        exe.run(program, feed=feed_dev, fetch_list=[loss],
                iterations=steps)
    if scope is not None:
        # drop the warmup accumulation: the reported counters must
        # describe exactly the measured window
        _fetch_tel(program, scope)
    if _PROFILE_DIR:
        import jax

        trace_cm = jax.profiler.trace(_PROFILE_DIR)
    else:
        trace_cm = contextlib.nullcontext()
    with trace_cm:
        with _phase("timed", steps):
            t0 = time.perf_counter()
            (lv,) = exe.run(program, feed=feed_dev, fetch_list=[loss],
                            iterations=steps)
            elapsed = time.perf_counter() - t0
    tel = _fetch_tel(program, scope) if scope is not None else None
    return elapsed, float(np.asarray(lv).reshape(-1)[0]), tel


def _mem_fields(exe, program, feed, loss, scope=None):
    """`mem_breakdown` for one training entry: per-bucket byte sums
    (params / optimizer_state / gradients / activations / workspace,
    donated, peak_bytes) of the measured step's buffer assignment
    (observe.memory).  Reuses the executor's memoized AOT compile —
    cost_analysis already paid it — so this is pure proto parsing.  A
    backend without memory analysis degrades to the module-shapes
    estimate (tagged via "source"), and any failure is recorded
    in-band rather than killing the entry."""
    try:
        from paddle_tpu import observe

        return {"mem_breakdown": observe.step_mem_breakdown(
            program, feed=feed, fetch_list=[loss], scope=scope,
            exe=exe)}
    except Exception as e:  # noqa: BLE001 — observability must not
        #                     take down the measurement it describes
        return {"mem_breakdown": {"error": f"{type(e).__name__}: {e}"}}


def _ckpt_fields(exe, program, scope=None, ledger=None):
    """Async-checkpoint observability for one training entry (ISSUE 7
    satellite): one full sharded save of the measured program's state
    into a throwaway dir, split into its blocking (device→host
    snapshot) and background (serialize+manifest) portions —
    `ckpt_blocking_ms` is what a save at this scale would steal from
    the step loop, `ckpt_write_ms` what the async writer hides.
    Failures are recorded in-band; the measurement they would describe
    is already taken."""
    import shutil
    import tempfile

    try:
        import contextlib

        from paddle_tpu import io as fluid_io
        from paddle_tpu.core.executor import scope_guard

        d = tempfile.mkdtemp(prefix="bench_ckpt_")
        try:
            cm = scope_guard(scope) if scope is not None \
                else contextlib.nullcontext()
            led_cm = (ledger.phase("checkpoint", label="throwaway_save")
                      if ledger is not None else contextlib.nullcontext())
            with cm, led_cm:
                job = fluid_io.save_sharded(exe, d,
                                            main_program=program,
                                            async_=True).result(120)
            if ledger is not None and job.write_ms:
                # the async writer's overlapped work: background side
                # channel, never a wall category
                ledger.note_background("ckpt_write",
                                       job.write_ms / 1000.0)
            return {"ckpt_blocking_ms": round(job.snapshot_ms, 3),
                    "ckpt_write_ms": round(job.write_ms or 0.0, 3),
                    "ckpt_bytes": job.bytes_total}
        finally:
            shutil.rmtree(d, ignore_errors=True)
    except Exception as e:  # noqa: BLE001 — observability must not
        #                     take down the measurement it describes
        return {"ckpt_blocking_ms": None,
                "ckpt_error": f"{type(e).__name__}: {e}"}


def _predictor_mem(predictor):
    """`mem_breakdown` of a serving entry: buffer accounting of the
    predictor's largest compiled executable (no fluid program here, so
    buckets are params vs workspace/activations by HLO scope only)."""
    try:
        from paddle_tpu import observe
        from paddle_tpu.observe.memory import memory_report

        compiled_cache = getattr(predictor, "_compiled", None) or {}
        if not compiled_cache:
            return {"mem_breakdown": None}
        best = None
        for entry in compiled_cache.values():
            rep = memory_report(compiled=entry)
            if best is None or rep["peak_bytes"] > best["peak_bytes"]:
                best = rep
        out = dict(best["breakdown"])
        out["source"] = best["source"]
        return {"mem_breakdown": out}
    except Exception as e:  # noqa: BLE001
        return {"mem_breakdown": {"error": f"{type(e).__name__}: {e}"}}


def _mfu_result(step_flops, steps, elapsed, extra, n_devices=1,
                ledger=None):
    if step_flops <= 0:
        raise RuntimeError(
            "XLA cost_analysis returned no flops; refusing to report a "
            "fabricated MFU")
    peak, kind = _peak_flops()
    # step_flops is the GLOBAL-batch program's algorithmic count, so
    # the dp denominator is the aggregate peak of the whole mesh
    out = {"mfu": round((step_flops * steps / elapsed)
                        / (peak * n_devices), 4),
           "step_flops": step_flops, "device": kind, "steps": steps}
    out.update(_goodput_fields(ledger, mfu=out["mfu"]))
    out.update(extra)
    return out


def _parse_mesh(spec: str):
    """--mesh "dp=8" (or "dp=2,mp=2", "fsdp=4") -> ordered axis dict.
    Any named axis parses; "dp"/"fsdp" shard the batch (fsdp
    additionally ZeRO-shards optimizer state), "mp" turns on the
    Megatron transformer rules (docs/DIST.md §hybrid)."""
    axes = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        name = name.strip()
        try:
            n = int(size)
        except ValueError:
            n = 0
        if not name or n < 1:
            raise ValueError(
                f"--mesh wants 'axis=N[,axis=N...]' (e.g. dp=8); got "
                f"{spec!r}")
        axes[name] = n
    return axes


def _mesh_key(mesh_axes) -> str:
    """Unambiguous entry-key suffix for a mesh: "_dp8", "_dp2mp2",
    "_fsdp4" — one token per axis, no separators, so a multi-axis key
    can never collide with two single-axis runs' keys."""
    return "_" + "".join(f"{a}{s}" for a, s in mesh_axes.items())


def _dp_compile(program, loss, mesh_axes, grad_sync):
    """Wrap a built training program for the mesh bench: feeds get a
    batch-dim PartitionSpec over the data axes (dp + fsdp,
    ShardingRules.feed_spec_for), params replicate (the
    ParallelExecutor AllReduce mode) unless the mesh has an "mp" axis —
    then the Megatron transformer rules shard them — and optimizer
    state ZeRO-shards over an "fsdp" axis when present
    (strategies.zero_axis).  Gradients all-reduce implicitly via GSPMD
    — or explicitly, blockwise-int8-quantized, with --grad-sync int8
    (docs/DIST.md).  Executor.run routes through the wrapper
    automatically from here on."""
    import paddle_tpu as fluid
    from paddle_tpu.parallel import make_mesh

    mesh = make_mesh(mesh_axes)
    bs = fluid.BuildStrategy()
    bs.grad_sync = grad_sync
    if mesh_axes.get("mp", 1) > 1:
        from paddle_tpu.parallel.strategies import \
            megatron_transformer_rules

        bs.sharding_rules = megatron_transformer_rules()
    fluid.CompiledProgram(program).with_data_parallel(
        loss_name=loss.name, build_strategy=bs, mesh=mesh)
    return mesh


def _comm_fields(program, feed, loss, scope):
    """Communication accounting of one dp-mesh entry, from the SHARDED
    (post-SPMD) compiled step's `comm` bucket in observe.cost —
    all-reduce / all-gather / reduce-scatter / all-to-all /
    collective-permute instructions.  `comm_bytes` is the modeled
    PER-DEVICE bytes touched by collectives in one step (the same
    materialized-buffer accounting every other bucket uses),
    `comm_share` its fraction of the step's total modeled bytes.
    Time attribution joins through observe.op_cost_table when a
    profile trace is captured (--profile); the bytes are the standing
    artifact field.  Failures record in-band, never killing the
    entry."""
    try:
        from paddle_tpu.observe import cost as obs_cost

        wrapper = getattr(program, "_compiled_wrapper", None)
        compiled = wrapper.compiled_step(feed, [loss.name], scope)
        rows = obs_cost.instruction_costs(
            obs_cost.compiled_hlo_proto(compiled))
        comm = sum(obs_cost.per_step(r, "bytes") for r in rows
                   if r["bucket"] == "comm")
        total = sum(obs_cost.per_step(r, "bytes") for r in rows
                    if r["bucket"] != "noop")
        return {"comm_bytes": comm,
                "comm_share": round(comm / total, 4) if total else 0.0,
                "comm_instructions": sum(
                    1 for r in rows if r["bucket"] == "comm")}
    except Exception as e:  # noqa: BLE001 — observability must not
        #                     take down the measurement it describes
        return {"comm_bytes": None,
                "comm_error": f"{type(e).__name__}: {e}"}


def _opt_state_fields(program, feed, loss, scope):
    """Per-device optimizer-state accounting of the SHARDED step
    (ISSUE 13): `opt_state_bytes_per_device` is the resident
    accumulator bytes one device holds (observe.resident_state_bytes
    over the sharded compile's buffer assignment) — the number the
    fsdp/ZeRO A/B claims drops ~1/N.  Failures record in-band."""
    try:
        from paddle_tpu import observe

        rep = observe.sharded_memory_report(
            program, feed=feed, fetch_list=[loss], scope=scope)
        return {"opt_state_bytes_per_device":
                observe.resident_state_bytes(rep),
                "params_bytes_per_device":
                observe.resident_state_bytes(rep, bucket="params")}
    except Exception as e:  # noqa: BLE001 — observability must not
        #                     take down the measurement it describes
        return {"opt_state_bytes_per_device": None,
                "opt_state_error": f"{type(e).__name__}: {e}"}


def _dp_fields(program, feed, loss, scope, mesh_axes, grad_sync,
               agg_throughput: dict):
    """The per-entry mesh contract (perf_gate --schema enforces it on
    mesh entries): the mesh (per-axis sizes), device count, grad-sync
    mode, PER-DEVICE throughput next to the aggregate, the comm-bucket
    bytes, and the per-device optimizer-state bytes of the sharded
    step."""
    n_dev = 1
    for s in mesh_axes.values():
        n_dev *= s
    out = {"mesh": dict(mesh_axes), "n_devices": n_dev,
           "grad_sync": grad_sync}
    for key, val in agg_throughput.items():
        out[f"per_device_{key}"] = round(val / n_dev, 2)
    out.update(_comm_fields(program, feed, loss, scope))
    out.update(_opt_state_fields(program, feed, loss, scope))
    return out


def bench_resnet50(batch_size: int, steps: int, warmup: int,
                   use_amp: bool = True, data_mode: str = "synthetic",
                   data_format: str = "NCHW", mesh_axes=None,
                   grad_sync=None):
    """data_mode:
    - "synthetic" (default): FRESH random batch generated on device
      every step (random ops prepended to the program)
    - "frozen": one device-resident batch reused every step (reference
      --use_fake_data upper bound; recorded as the ceiling)
    - "host": fresh numpy batches through the double-buffered
      DeviceFeeder prefetch pipeline (includes host→device transfer)
    """
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    if data_mode not in ("frozen", "synthetic", "host"):
        raise ValueError(f"unknown data_mode {data_mode!r}")
    if mesh_axes and data_mode == "host":
        raise ValueError(
            "--mesh with --data host is not wired: the prefetch "
            "pipeline feeds per-batch host arrays; dp entries use "
            "synthetic (recorded as frozen) or frozen")
    dp_note = None
    if mesh_axes and data_mode == "synthetic":
        # on-device synthetic generation carries no sharding
        # annotation, so GSPMD would replicate the generated batch (and
        # with it most of the step) over dp — the dp entry would bench
        # redundant compute and call it scaling.  The dp resnet entry
        # therefore uses the frozen device feed (the batch-dim
        # PartitionSpec comes from the feed) and SAYS so.
        data_mode = "frozen"
        dp_note = ("synthetic generation has no sharding annotation; "
                   "dp entry measured with the frozen device feed")
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    ledger = _new_ledger()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        model = resnet.build_model(dataset="flowers", depth=50,
                                   class_dim=1000, learning_rate=0.1,
                                   use_amp=use_amp,
                                   data_format=data_format)
        _enable_observability(main)
        exe = fluid.Executor()
        if mesh_axes:
            _dp_compile(main, model["loss"], mesh_axes, grad_sync)

        if data_mode == "synthetic":
            # per-step RNG advance makes every iteration's batch
            # distinct, including inside chained iterations
            block = main.global_block()
            block.prepend_op(
                "randint", outputs={"Out": ["label"]},
                attrs={"shape": [batch_size, 1], "low": 0, "high": 1000,
                       "dtype": "int32"})
            block.prepend_op(
                "uniform_random", outputs={"Out": ["data"]},
                attrs={"shape": [batch_size, 3, 224, 224], "min": 0.0,
                       "max": 1.0, "dtype": "float32"})
        exe.run(startup)

        if data_mode == "synthetic":
            feed = {}
        elif data_mode != "host":
            feed = {
                "data": jax.device_put(
                    rng.rand(batch_size, 3, 224, 224).astype(np.float32)),
                "label": jnp.asarray(rng.randint(0, 1000, (batch_size, 1)),
                                     dtype=jnp.int32),
            }
        if data_mode == "host":
            from paddle_tpu.data.pipeline import DeviceFeeder

            def reader():
                r = np.random.RandomState(1)
                while True:
                    yield {
                        "data": r.rand(batch_size, 3, 224,
                                       224).astype(np.float32),
                        "label": r.randint(
                            0, 1000, (batch_size, 1)).astype(np.int32),
                    }

            dev_feeder = DeviceFeeder(reader, capacity=3).start()
            try:
                feeder = iter(dev_feeder)
                with ledger.phase("step", label="warmup", steps=warmup):
                    for _ in range(warmup):
                        exe.run(main, feed=next(feeder),
                                fetch_list=[model["loss"]])
                _fetch_tel(main, scope)  # drop warmup accumulation
                t0 = time.perf_counter()
                lv = None
                for _ in range(steps):
                    with ledger.phase("data_stall", label="next"):
                        batch = next(feeder)
                    with ledger.phase("step", label="timed", steps=1):
                        (lv,) = exe.run(main, feed=batch,
                                        fetch_list=[model["loss"]])
                elapsed = time.perf_counter() - t0
                tel = _fetch_tel(main, scope)
                last_loss = float(np.asarray(lv).reshape(-1)[0])
                cost = exe.cost_analysis(main, feed=next(feeder),
                                         fetch_list=[model["loss"]])
                mem = _mem_fields(exe, main, next(feeder),
                                  model["loss"])
            finally:
                dev_feeder.reset()
        else:
            cost = exe.cost_analysis(main, feed=feed,
                                     fetch_list=[model["loss"]])
            elapsed, last_loss, tel = _timed_loop(
                exe, main, feed, model["loss"], steps, warmup,
                scope=scope, ledger=ledger)
            mem = _mem_fields(exe, main, feed, model["loss"])
        ck = _ckpt_fields(exe, main, scope, ledger=ledger)
        imgs_per_sec = batch_size * steps / elapsed
        dp = {}
        n_dev = 1
        if mesh_axes:
            dp = _dp_fields(main, feed, model["loss"], scope,
                            mesh_axes, grad_sync,
                            {"imgs_per_sec": round(imgs_per_sec, 2)})
            n_dev = dp["n_devices"]
            if dp_note:
                dp["dp_data_note"] = dp_note
    return _mfu_result(
        float(cost.get("flops", 0.0)), steps, elapsed,
        {"imgs_per_sec": round(imgs_per_sec, 2),
         "batch_size": batch_size, "amp": use_amp,
         "data_mode": data_mode, "data_format": data_format,
         "last_loss": last_loss,
         **_tel_fields(tel), **mem, **ck, **dp,
         "vs_cpu_baseline_81.69": round(imgs_per_sec / 81.69, 3)},
        n_devices=n_dev, ledger=ledger)


def _layout_fields(exe, program, feed, loss):
    """`layout_share` for a transformer/longctx entry: the LAYOUT
    bucket's fraction of the measured step's modeled HBM bytes
    (observe.cost.layout_byte_share over the optimized module — copy/
    transpose/bitcast-convert instructions and fusions rooted at one).
    This is the r05 longctx diagnostic (~15.9 s copy/transpose vs
    ~5.0 s kernel) as a standing artifact field; tools/perf_gate.py
    gates its regression (--tol-layout-share) so transpose traffic can
    never silently creep back after the head-major layout (ISSUE 8)
    deleted it.  Reuses the memoized AOT compile — pure proto parsing;
    failures are recorded in-band, never killing the entry."""
    try:
        from paddle_tpu.observe import cost as obs_cost

        compiled = exe.compiled_step(program, feed=feed,
                                     fetch_list=[loss])
        share = obs_cost.layout_byte_share(
            obs_cost.compiled_hlo_proto(compiled))
        return {"layout_share": round(share, 4)}
    except Exception as e:  # noqa: BLE001 — observability must not
        #                     take down the measurement it describes
        return {"layout_share": None,
                "layout_share_error": f"{type(e).__name__}: {e}"}


def _registry_flops(exe, program, feed, loss):
    """MFU numerator for a Pallas-active program, computed NATIVELY:
    XLA's aggregate flops of the optimized step (custom calls count
    zero there) plus each custom call's dense-equivalent cost from the
    Pallas kernel registry (ops/pallas KERNEL_COSTS, injected by
    observe.cost at the custom-call instructions).  Replaces the
    dense-twin workaround as the primary numerator.

    Returns (step_flops, flop_count_tag)."""
    from paddle_tpu.observe import cost as obs_cost

    compiled = exe.compiled_step(program, feed=feed, fetch_list=[loss])
    totals = obs_cost.total_costs(obs_cost.compiled_hlo_proto(compiled))
    xla_flops = obs_cost.compiled_xla_flops(compiled)
    if totals["custom_calls"] == 0:
        # CPU smoke backend: the interpret-mode kernels traced into
        # plain XLA ops, so XLA's own count already includes them
        return xla_flops, "xla(interpreted-pallas)"
    if totals["pallas_matched"] < totals["custom_calls"]:
        raise RuntimeError(
            f"{totals['custom_calls'] - totals['pallas_matched']} custom "
            f"call(s) without a registered kernel cost — refusing to "
            f"report an MFU whose numerator silently drops kernel flops "
            f"(register costs in ops/pallas or use the dense twin)")
    return (xla_flops + totals["pallas_flops"],
            f"xla+pallas-registry({totals['pallas_matched']} calls)")


def _dense_equiv_flops(feed, build_no_flash, platform=None):
    """Flop count for a flash-attention program: XLA cost analysis of
    the SAME model compiled WITHOUT the Pallas kernel (custom calls
    report zero flops; the dense composition is the logical-math
    equivalent the flash kernel computes).

    platform="cpu" compiles the twin for CPU instead of the chip: at
    long sequence the dense twin CANNOT exist on the TPU (seq 8k needs
    a 73 GB dense-score program — XLA:TPU refuses at compile time,
    which is the whole point of flash).  Flop counts are a property of
    the HLO, not the backend; the dominant dot flops are identical."""
    import contextlib

    import jax

    import paddle_tpu as fluid

    ctx = (jax.default_device(jax.devices(platform)[0]) if platform
           else contextlib.nullcontext())
    main2, startup2 = fluid.Program(), fluid.Program()
    scope2 = fluid.Scope()
    with ctx, fluid.program_guard(main2, startup2), \
            fluid.scope_guard(scope2):
        model2 = build_no_flash()
        exe2 = fluid.Executor()
        exe2.run(startup2)
        cost = exe2.cost_analysis(main2, feed=feed,
                                  fetch_list=[model2["loss"]])
    return float(cost.get("flops", 0.0))


def bench_transformer(batch_size: int, steps: int, warmup: int,
                      max_length: int = 256, use_amp: bool = True,
                      use_flash: bool = True, use_fused_ce: bool = False,
                      fused_qkv: bool = False, moe_experts: int = 0,
                      flash_pallas: bool = False,
                      recompute: bool = False,
                      head_major: bool = False,
                      mesh_axes=None, grad_sync=None):
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer

    def build(flash, fused_ce=use_fused_ce, fq=None, moe=None,
              pallas=None, rc=None, hm=None):
        return transformer.build_model(
            src_vocab_size=32000, trg_vocab_size=32000,
            max_length=max_length, n_layer=6, n_head=8, d_model=512,
            d_inner_hid=2048, dropout=0.1, use_flash=flash,
            use_amp=use_amp, use_fused_ce=fused_ce,
            fused_qkv=fused_qkv if fq is None else fq,
            moe_experts=moe_experts if moe is None else moe,
            flash_pallas=flash_pallas if pallas is None else pallas,
            recompute=recompute if rc is None else rc,
            flash_cross=flash and max_length > 1024,
            head_major=head_major if hm is None else hm)

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    ledger = _new_ledger()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        model = build(use_flash)
        _enable_observability(main)
        exe = fluid.Executor()
        if mesh_axes:
            _dp_compile(main, model["loss"], mesh_axes, grad_sync)
        exe.run(startup)
        feed = {k: jnp.asarray(v) for k, v in
                transformer.make_fake_batch(batch_size, max_length,
                                            32000, 32000).items()}
        pallas_active = (use_flash and flash_pallas) or use_fused_ce
        if recompute:
            # twin-program numerator: a remat program DOUBLE-counts the
            # recomputed forward in any HLO-side count — the twin (no
            # Pallas, no recompute) carries the algorithmic flop count
            step_flops = _dense_equiv_flops(
                feed, lambda: build(False, fused_ce=False, fq=False,
                                    pallas=False, rc=False, hm=False),
                platform="cpu" if max_length > 1024 else None)
            flop_src = ("dense-equivalent(cpu-twin)"
                        if max_length > 1024 else "dense-equivalent")
        elif pallas_active:
            # native numerator: Pallas custom calls report zero flops
            # to XLA, so their registered dense-equivalent costs are
            # added at the custom-call instructions (observe.cost)
            step_flops, flop_src = _registry_flops(exe, main, feed,
                                                   model["loss"])
        else:
            cost = exe.cost_analysis(main, feed=feed,
                                     fetch_list=[model["loss"]])
            step_flops = float(cost.get("flops", 0.0))
            flop_src = "xla"
        elapsed, last_loss, tel = _timed_loop(exe, main, feed,
                                              model["loss"], steps,
                                              warmup, scope=scope,
                                              ledger=ledger)
        mem = _mem_fields(exe, main, feed, model["loss"])
        layout = _layout_fields(exe, main, feed, model["loss"])
        ck = _ckpt_fields(exe, main, scope, ledger=ledger)
        tokens_per_sec = round(batch_size * max_length * steps
                               / elapsed, 1)
        dp = {}
        n_dev = 1
        if mesh_axes:
            dp = _dp_fields(main, feed, model["loss"], scope,
                            mesh_axes, grad_sync,
                            {"tokens_per_sec": tokens_per_sec})
            n_dev = dp["n_devices"]
    return _mfu_result(
        step_flops, steps, elapsed,
        {"tokens_per_sec": tokens_per_sec,
         "batch_size": batch_size, "max_length": max_length,
         "amp": use_amp, "flash": use_flash,
         "flash_pallas": flash_pallas, "fused_ce": use_fused_ce,
         "fused_qkv": fused_qkv, "moe_experts": moe_experts,
         "recompute": recompute, "head_major": head_major,
         "flop_count": flop_src,
         "last_loss": last_loss,
         **_tel_fields(tel), **mem, **layout, **ck, **dp},
        n_devices=n_dev, ledger=ledger)


def bench_bert(batch_size: int, steps: int, warmup: int,
               max_len: int = 128, use_amp: bool = True,
               use_flash: bool = True, mesh_axes=None, grad_sync=None):
    """BERT-base pretraining (BASELINE.json tracked config #3): MLM+NSP
    step, tokens/sec + MFU."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    def build(flash):
        return bert.build_model(max_len=max_len, use_flash=flash,
                                use_amp=use_amp)

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    ledger = _new_ledger()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        model = build(use_flash)
        _enable_observability(main)
        exe = fluid.Executor()
        if mesh_axes:
            _dp_compile(main, model["loss"], mesh_axes, grad_sync)
        exe.run(startup)
        feed = {k: jnp.asarray(v) for k, v in
                bert.make_fake_batch(batch_size, max_len).items()}
        if use_flash:
            step_flops = _dense_equiv_flops(feed,
                                            lambda: build(False))
        else:
            cost = exe.cost_analysis(main, feed=feed,
                                     fetch_list=[model["loss"]])
            step_flops = float(cost.get("flops", 0.0))
        elapsed, last_loss, tel = _timed_loop(exe, main, feed,
                                              model["loss"], steps,
                                              warmup, scope=scope,
                                              ledger=ledger)
        mem = _mem_fields(exe, main, feed, model["loss"])
        ck = _ckpt_fields(exe, main, scope, ledger=ledger)
        tokens_per_sec = round(batch_size * max_len * steps / elapsed, 1)
        dp = {}
        n_dev = 1
        if mesh_axes:
            dp = _dp_fields(main, feed, model["loss"], scope,
                            mesh_axes, grad_sync,
                            {"tokens_per_sec": tokens_per_sec})
            n_dev = dp["n_devices"]
    return _mfu_result(
        step_flops, steps, elapsed,
        {"tokens_per_sec": tokens_per_sec,
         "batch_size": batch_size, "max_len": max_len, "amp": use_amp,
         "flash": use_flash,
         "flop_count": "dense-equivalent" if use_flash else "xla",
         "last_loss": last_loss,
         **_tel_fields(tel), **mem, **ck, **dp},
        n_devices=n_dev, ledger=ledger)


def bench_lstm(batch_size: int, steps: int, warmup: int,
               max_len: int = 128, pallas_rnn: bool = False,
               rnn_unroll: int = 1):
    """Stacked dynamic LSTM LM (BASELINE.json tracked config #4,
    reference benchmark/fluid/models/stacked_dynamic_lstm.py):
    tokens/sec through the recurrence.  The scan path serializes 128
    small matmuls per layer, so MFU against the MXU peak is reported
    for context but throughput is the tracked axis (perf_gate compares
    tokens_per_sec/examples_per_sec, numerator-free).

    The two scan-bound levers (docs/RNN.md): --rnn-unroll N unrolls
    the lax.scan body; --pallas-rnn swaps the recurrence for the blocked fused Pallas kernel
    (ops/pallas/recurrence.py), whose custom calls take their MFU
    numerator from the kernel cost registry.  The scan path's MFU
    numerator is XLA's aggregate, which counts while BODIES ONCE
    (undercounts the recurrence by ~T) — tagged, kept for artifact
    continuity with r05; the trip-corrected analytic number is
    observe.cost's (`while_trip_count`)."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import stacked_dynamic_lstm as lstm

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    ledger = _new_ledger()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        model = lstm.build_model(max_len=max_len, use_amp=False,
                                 pallas_rnn=pallas_rnn,
                                 rnn_unroll=rnn_unroll)
        _enable_observability(main)
        exe = fluid.Executor()
        exe.run(startup)
        feed = {k: jnp.asarray(v) for k, v in
                lstm.make_fake_batch(batch_size, max_len).items()}
        if pallas_rnn:
            step_flops, flop_src = _registry_flops(exe, main, feed,
                                                   model["loss"])
        else:
            cost = exe.cost_analysis(main, feed=feed,
                                     fetch_list=[model["loss"]])
            step_flops = float(cost.get("flops", 0.0))
            flop_src = "xla(loop-bodies-once)"
        elapsed, last_loss, tel = _timed_loop(exe, main, feed,
                                              model["loss"], steps,
                                              warmup, scope=scope,
                                              ledger=ledger)
        mem = _mem_fields(exe, main, feed, model["loss"])
        ck = _ckpt_fields(exe, main, scope, ledger=ledger)
    return _mfu_result(
        step_flops, steps, elapsed,
        {"tokens_per_sec": round(batch_size * max_len * steps / elapsed,
                                 1),
         "examples_per_sec": round(batch_size * steps / elapsed, 1),
         "batch_size": batch_size, "max_len": max_len,
         "pallas_rnn": pallas_rnn, "rnn_unroll": rnn_unroll,
         "flop_count": flop_src,
         "last_loss": last_loss,
         **_tel_fields(tel), **mem, **ck}, ledger=ledger)


def bench_deepfm(batch_size: int, steps: int, warmup: int,
                 mesh_axes=None, grad_sync=None):
    """DeepFM CTR (tracked config #5): examples/sec on the sparse path
    (is_sparse lookups → SelectedRows-style grads, lazy Adam row
    updates) + a bytes/flops roofline context from XLA cost analysis —
    gather/scatter-bound, so the meaningful axis is throughput vs the
    HBM-bandwidth bound, not MXU MFU."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import deepfm

    main_p, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    ledger = _new_ledger()
    with fluid.program_guard(main_p, startup), fluid.scope_guard(scope):
        model = deepfm.build_model()
        _enable_observability(main_p)
        exe = fluid.Executor()
        if mesh_axes:
            _dp_compile(main_p, model["loss"], mesh_axes, grad_sync)
        exe.run(startup)
        feed = {k: jnp.asarray(v)
                for k, v in deepfm.make_fake_batch(batch_size).items()}
        cost = exe.cost_analysis(main_p, feed=feed,
                                 fetch_list=[model["loss"]])
        elapsed, last_loss, tel = _timed_loop(exe, main_p, feed,
                                              model["loss"], steps,
                                              warmup, scope=scope,
                                              ledger=ledger)
        mem = _mem_fields(exe, main_p, feed, model["loss"])
        ck = _ckpt_fields(exe, main_p, scope, ledger=ledger)
        examples_per_sec = round(batch_size * steps / elapsed, 1)
        dp = {}
        if mesh_axes:
            dp = _dp_fields(main_p, feed, model["loss"], scope,
                            mesh_axes, grad_sync,
                            {"examples_per_sec": examples_per_sec})
    _, kind = _peak_flops()
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    # v5e HBM ~819 GB/s: what fraction of the bandwidth roofline the
    # sparse step achieves (the CTR analog of MFU)
    hbm_frac = (bytes_acc * steps / elapsed) / 819e9 if bytes_acc else 0.0
    return {
        "examples_per_sec": examples_per_sec,
        "device": kind,
        "batch_size": batch_size,
        "steps": steps,
        "sparse_grads": True,
        "step_bytes_accessed": bytes_acc,
        "hbm_roofline_frac": round(hbm_frac, 4),
        "last_loss": last_loss,
        # no MXU MFU here (bandwidth-bound entry), so effective_mfu
        # scales the HBM roofline fraction instead
        **_goodput_fields(ledger, mfu=round(hbm_frac, 4)),
        **_tel_fields(tel), **mem, **ck, **dp,
    }


def bench_serving(batch_size: int, iters: int = 50):
    """ResNet-50 inference latency through the AOT Predictor (reference
    inference/tests/api/analyzer_resnet50_tester.cc latency runs), bf16
    float path; plus an int8 path (QAT-calibrated scales frozen via
    convert_to_int8) for the quantized-serving latency line."""
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    rng = np.random.RandomState(0)
    results = {}
    with tempfile.TemporaryDirectory() as d:
        main_p, startup = fluid.Program(), fluid.Program()
        scope = fluid.Scope()
        with fluid.program_guard(main_p, startup), \
                fluid.scope_guard(scope):
            model = resnet.build_model(dataset="flowers", depth=50,
                                       class_dim=1000,
                                       with_optimizer=False)
            exe = fluid.Executor()
            exe.run(startup)
            fluid.io.save_inference_model(
                d, ["data"], [model["predict"]], exe, main_program=main_p)
        feed = {"data": rng.rand(batch_size, 3, 224,
                                 224).astype(np.float32)}
        predictor = fluid.Predictor(d)
        results["fp"] = predictor.benchmark(feed, iters=iters, warmup=5)

        try:
            # int8: QAT-transpile, calibrate moving scales with a few
            # forward batches, freeze + convert.  Failures here must not
            # discard the already-measured fp numbers — they land in
            # out["int8"]["error"] instead.
            import os

            main_q, startup_q = fluid.Program(), fluid.Program()
            scope_q = fluid.Scope()
            dq = os.path.join(d, "int8_model")
            with fluid.program_guard(main_q, startup_q), \
                    fluid.scope_guard(scope_q):
                model_q = resnet.build_model(dataset="flowers", depth=50,
                                             class_dim=1000,
                                             with_optimizer=False)
                fluid.QuantizeTranspiler().training_transpile(main_q,
                                                              startup_q)
                exe = fluid.Executor()
                exe.run(startup_q)
                for i in range(3):   # calibrate activation scales
                    exe.run(main_q,
                            feed={"data": rng.rand(8, 3, 224, 224)
                                  .astype(np.float32)},
                            fetch_list=[model_q["predict"]])
                infer_q = main_q.clone(for_test=True)
                fluid.io.save_inference_model(
                    dq, ["data"], [infer_q.global_block().var(
                        model_q["predict"].name)], exe, main_program=infer_q)
            cfg = fluid.AnalysisConfig(dq)
            cfg.enable_int8()
            pred_q = fluid.Predictor(cfg)
            if pred_q.int8_converted:
                results["int8"] = pred_q.benchmark(feed, iters=iters,
                                                   warmup=5)
                results["int8"]["converted_ops"] = len(pred_q.int8_converted)
            else:
                # an expected-but-missing int8 path must be VISIBLE in
                # the report, not silently absent
                results["int8"] = {
                    "error": "convert_to_int8 converted no ops (QAT "
                             "pattern or calibrated scales missing)"}
        except Exception as e:  # noqa: BLE001
            import traceback

            traceback.print_exc()
            results["int8"] = {"error": f"{type(e).__name__}: {e}"}

    _, kind = _peak_flops()
    fp = results["fp"]
    out = {"p50_ms": round(fp["p50_ms"], 3),
           "mean_ms": round(fp["mean_ms"], 3),
           "compute_ms": round(fp["compute_ms"], 3),
           "imgs_per_sec": round(batch_size / (fp["compute_ms"] / 1e3),
                                 1),
           "batch_size": batch_size, "device": kind,
           **_predictor_mem(predictor)}
    if results.get("int8", {}).get("error"):
        out["int8"] = results["int8"]
    elif "int8" in results:
        q = results["int8"]
        out["int8"] = {
            "compute_ms": round(q["compute_ms"], 3),
            "p50_ms": round(q["p50_ms"], 3),
            "imgs_per_sec": round(batch_size / (q["compute_ms"] / 1e3),
                                  1),
            "converted_ops": q["converted_ops"],
            "speedup_vs_fp": round(fp["compute_ms"] / q["compute_ms"],
                                   3),
        }
        if batch_size <= 8:
            # VERDICT r5: at bs<=8 ResNet inference is latency-bound —
            # per-dispatch overhead dominates and the int8 MXU win
            # (1.08x at bs8, r05) sits inside run-to-run noise.  The
            # serving_bs64 entry is the throughput shape where the win
            # is driver-recorded.
            out["int8"]["note"] = (
                f"bs{batch_size} is latency-bound: speedup_vs_fp is "
                "noise-dominated at this shape; see serving_bs64 for "
                "the throughput-shape int8 win")
    return out


def bench_serving_engine(batch_size: int, n_requests: int = 0,
                         max_wait_ms: float = 5.0):
    """Offered-load serving benchmark: the dynamic-batching
    serving.ServingEngine vs per-request Predictor dispatch on the same
    ResNet-50 inference model.

    The per-call `serving` entries above measure one synchronous
    request at a time, so per-request throughput is bound by the
    host's dispatch round trip.  The engine line answers the
    production question instead:
    with many concurrent callers (closed-loop, 2×batch_size clients),
    how many requests/s does dynamic batching sustain, at what
    latency percentiles, and with how much padding waste — and it must
    do so with ZERO XLA compiles after the bucket warmup
    (post_warmup_compiles is part of the artifact)."""
    import tempfile
    import threading

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet
    from paddle_tpu.serving import BucketConfig, ServingEngine

    rng = np.random.RandomState(0)
    n_requests = n_requests or 6 * batch_size
    with tempfile.TemporaryDirectory() as d:
        main_p, startup = fluid.Program(), fluid.Program()
        scope = fluid.Scope()
        with fluid.program_guard(main_p, startup), \
                fluid.scope_guard(scope):
            model = resnet.build_model(dataset="flowers", depth=50,
                                       class_dim=1000,
                                       with_optimizer=False)
            exe = fluid.Executor()
            exe.run(startup)
            fluid.io.save_inference_model(
                d, ["data"], [model["predict"]], exe,
                main_program=main_p)
        imgs = rng.rand(n_requests, 3, 224, 224).astype(np.float32)

        # per-request baseline FIRST (its bs-1 compile must not land in
        # the engine's post-warmup window): single caller, one image
        # per dispatch — what a frontend without batching gets
        predictor = fluid.Predictor(d)
        m = min(n_requests, 24)
        predictor.run({"data": imgs[0:1]})  # compile + warm
        t0 = time.perf_counter()
        for i in range(m):
            predictor.run({"data": imgs[i:i + 1]})
        per_req_rps = m / (time.perf_counter() - t0)

        # engine on the SAME predictor (shares device weights): bucket
        # ladder {1, batch_size} keeps warmup to two compiles.  The
        # pillar-7 tracer rides at sample_rate=0: per-phase histograms
        # are exact over every request regardless of sampling, and the
        # guard-discipline tests pin that tracing adds zero device work
        from paddle_tpu.observe import ReqTracer

        tracer = ReqTracer(sample_rate=0.0)
        engine = ServingEngine(
            predictor.clone(), {"data": imgs[0]},
            buckets=BucketConfig((1, batch_size)
                                 if batch_size > 1 else (1,)),
            max_wait_ms=max_wait_ms, queue_capacity=4 * batch_size,
            tracer=tracer)
        engine.start()
        n_clients = min(2 * batch_size, n_requests)
        errors = []

        def client(k):
            try:
                for i in range(k, n_requests, n_clients):
                    engine.infer({"data": imgs[i]}, timeout_s=300)
            except Exception as e:  # noqa: BLE001 — recorded, reraised
                errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        if errors:
            raise RuntimeError(
                f"{len(errors)} serving clients failed: {errors[:3]}")
        snap = engine.stats.snapshot()
        engine.close()

    _, kind = _peak_flops()
    e2e = snap["e2e_ms"]
    phases = tracer.phase_summary()

    def _ph(name, p):
        return phases.get(name, {}).get(f"p{p}_ms")

    return {
        "requests_per_sec": round(n_requests / elapsed, 1),
        "per_request_rps": round(per_req_rps, 1),
        "batching_speedup": round((n_requests / elapsed) / per_req_rps,
                                  3),
        "p50_ms": e2e["p50_ms"], "p95_ms": e2e["p95_ms"],
        "p99_ms": e2e["p99_ms"],
        # span-derived phase breakdown (observe pillar 7): where a
        # request's time went — queueing vs batch padding vs the
        # executable — next to the e2e percentiles they compose into
        "queue_wait_ms_p50": _ph("queue_wait", 50),
        "queue_wait_ms_p99": _ph("queue_wait", 99),
        "batch_form_ms_p50": _ph("batch_form", 50),
        "dispatch_ms_p50": _ph("dispatch", 50),
        "exec_per_req_ms": snap["exec_per_req_ms"],
        "batch_occupancy": snap["batch_occupancy"],
        "padding_waste": snap["padding_waste"],
        "post_warmup_compiles": snap["post_warmup_compiles"],
        "warmup": snap.get("warmup"),
        "batch_size": batch_size, "n_requests": n_requests,
        "n_clients": n_clients, "device": kind,
        **_predictor_mem(engine.predictor),
    }


def _repeat_heavy_prompts(n, vocab, lo, hi, seed=0):
    """Repeat-heavy synthetic stream (ISSUE 20): short random motifs
    tiled to ragged prompt lengths — the regime prompt-lookup drafting
    serves (the accept-rate analog of code/prose repetition; purely
    random prompts under-sell ANY drafter and over-sell none)."""
    rng = np.random.RandomState(seed)
    prompts = []
    for _ in range(n):
        motif = rng.randint(1, vocab, size=rng.randint(2, 5))
        length = rng.randint(lo, hi + 1)
        prompts.append(np.tile(motif, -(-length // len(motif)))
                       [:length].astype(np.int64))
    return prompts


def bench_serving_decode(n_requests: int = 0, kv_int8: bool = False,
                         max_new_tokens: int = 0, speculate: int = 0):
    """Continuous-batching autoregressive decode under an offered-load
    ragged request stream (ISSUE 12, docs/SERVING.md §decode).

    A decoder-only LM serves prompts of random ragged lengths through
    the paged-KV DecodeEngine: more requests than slots, so requests
    JOIN open slots mid-generation (prefill-on-join), leave as they
    finish, and may be preempted when the pool — deliberately sized
    below the worst case — runs dry.  The headline is steady-state
    generated tokens/s; the entry carries the full decode telemetry
    (slot occupancy, KV-page pool utilization, preemptions, TTFT vs
    TPOT) and post_warmup_compiles,
    which MUST be 0: any compile after warmup means a shape leaked
    across a join/leave/preempt pattern.

    kv_int8=True swaps the KV pools for int8 + per-row scale
    sidecars; the default stays bf16 (no ledger row on either side).

    speculate=K runs the ISSUE 20 acceptance protocol: a sequential
    twin engine runs the SAME stream first (token parity is asserted,
    its tokens/s is the speedup denominator), then the speculative
    engine with the host n-gram drafter; the entry carries
    accept_rate, the k+1-bin accept histogram,
    speculation_efficiency, speedup_vs_sequential, token_parity and
    post_warmup_compiles (must be 0)."""
    from paddle_tpu.models.decoder_lm import DecoderLM, make_prompts
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    arch = dict(vocab_size=8192, n_layer=4, n_head=8, d_model=512,
                d_inner=1024)
    num_slots, page, max_len, chunk = 16, 16, 512, 16
    buckets = (32, 64, 128)
    max_new = max_new_tokens or 96
    n_requests = n_requests or 64
    prompt_lo, prompt_hi = 8, 128
    kv_dtype = "int8" if kv_int8 else "bfloat16"
    lm = DecoderLM(use_pallas=True, kv_dtype=kv_dtype, seed=0, **arch)
    max_pages = -(-max_len // page)
    # pool deliberately BELOW slots*worst-case: memory follows the
    # ragged truth; the preemption counter records where it pinched
    num_pages = max(max_pages + 1, int(0.75 * num_slots * max_pages))
    cfg = DecodeConfig(num_slots=num_slots, page_size=page,
                       max_len=max_len, num_pages=num_pages,
                       prefill_buckets=buckets, decode_chunk=chunk,
                       kv_dtype=kv_dtype)
    from paddle_tpu.observe import ReqTracer

    if speculate:
        # the ISSUE 20 acceptance stream: repeat-heavy prompts and
        # generation-dominated budgets — the speculative win is fewer
        # SERIAL forwards per token, visible once decode dominates
        prompts = _repeat_heavy_prompts(n_requests, arch["vocab_size"],
                                        prompt_lo, prompt_hi, seed=0)
    else:
        prompts = make_prompts(n_requests, arch["vocab_size"],
                               min_len=prompt_lo, max_len=prompt_hi,
                               seed=0)
    rng = np.random.RandomState(1)
    budgets = rng.randint(max(2, max_new // 2), max_new + 1,
                          n_requests)

    def run_stream(spec_k):
        tracer = ReqTracer(sample_rate=0.0)  # exact phase hists only
        engine = DecodeEngine(lm, cfg, queue_capacity=4 * n_requests,
                              tracer=tracer, speculate_k=spec_k)
        engine.start()
        t0 = time.perf_counter()
        futs = [engine.submit(p, max_new_tokens=int(b))
                for p, b in zip(prompts, budgets)]
        outs = [f.result(1200) for f in futs]
        elapsed = time.perf_counter() - t0
        engine.drain(120)
        snap = engine.stats.snapshot()
        mem = _decode_mem(engine)
        engine.close()
        return outs, elapsed, snap, mem, tracer

    spec_extra = {}
    if speculate:
        # sequential twin FIRST over the same stream: the honest
        # denominator for speedup_vs_sequential and the parity pin
        s_outs, s_elapsed, _s_snap, _m, _t = run_stream(0)
    outs, elapsed, snap, mem, tracer = run_stream(speculate)
    if speculate:
        parity = all(list(o) == list(s)
                     for o, s in zip(outs, s_outs))
        assert parity, \
            "speculative tokens diverged from the sequential engine"
        sec = snap["speculation"]
        seq_tps = sum(len(o) for o in s_outs) / s_elapsed
        spec_extra = {
            "speculate": speculate,
            "drafter": "ngram",
            "accept_rate": sec["accept_rate"],
            "accept_hist": sec["accept_hist"],
            "speculation_efficiency": sec["speculation_efficiency"],
            "verify_dispatches": sec["verify_dispatches"],
            "drafted_tokens": sec["drafted_tokens"],
            "accepted_tokens": sec["accepted_tokens"],
            "sequential_tokens_per_sec": round(seq_tps, 1),
            "speedup_vs_sequential": round(
                (sum(len(o) for o in outs) / elapsed) / seq_tps, 3),
            "token_parity": parity,
        }
    tokens_total = sum(len(o) for o in outs)
    assert tokens_total == snap["tokens_generated"], \
        (tokens_total, snap["tokens_generated"])
    _, kind = _peak_flops()
    kv_bytes = sum(
        int(np.prod(s.shape, dtype=np.int64))
        * np.dtype(s.dtype).itemsize
        for s in lm.pool_specs(num_pages, page).values())
    return {
        "tokens_per_sec": round(tokens_total / elapsed, 1),
        "requests_per_sec": round(n_requests / elapsed, 2),
        "n_requests": n_requests,
        "tokens_generated": tokens_total,
        "ttft_p50_ms": snap["ttft_ms"]["p50_ms"],
        "ttft_p95_ms": snap["ttft_ms"]["p95_ms"],
        "tpot_p50_ms": snap["tpot_ms"]["p50_ms"],
        # span-derived phase breakdown (observe pillar 7): how long a
        # request waited to JOIN an open slot vs the dispatches that
        # served it — the continuous-batching decomposition of TTFT
        "join_wait_ms_p50": tracer.phase_summary()
        .get("join_wait", {}).get("p50_ms"),
        "dispatch_ms_p50": tracer.phase_summary()
        .get("dispatch", {}).get("p50_ms"),
        "slot_occupancy": snap["slot_occupancy"],
        "kv_page_utilization": snap["kv_page_utilization"],
        "peak_pages_in_use": snap["peak_pages_in_use"],
        "preemptions": snap["preemptions"],
        "prefills": snap["prefills"],
        "decode_dispatches": snap["decode_dispatches"],
        "decode_iterations": snap["decode_iterations"],
        "post_warmup_compiles": snap["post_warmup_compiles"],
        "warmup": snap.get("warmup"),
        "kv_dtype": kv_dtype,
        "num_slots": num_slots, "page_size": page,
        "num_pages": num_pages, "max_len": max_len,
        "decode_chunk": chunk, "kv_pool_bytes": int(kv_bytes),
        "device": kind,
        **spec_extra,
        **mem,
    }


def _decode_mem(engine):
    """mem_breakdown of the steady-state resident executable (the
    verify program when the engine speculates, else the decode
    chunk): weights + pools + workspace."""
    try:
        from paddle_tpu.observe.memory import memory_report

        rep = memory_report(
            compiled=engine._verify_exec or engine._decode_exec)
        out = dict(rep["breakdown"])
        out["source"] = rep["source"]
        return {"mem_breakdown": out}
    except Exception as e:  # noqa: BLE001 — observability must not
        #                     take down the measurement it describes
        return {"mem_breakdown": {"error": f"{type(e).__name__}: {e}"}}


def bench_serving_fleet(n_requests: int = 0, n_replicas: int = 2,
                        speculate: int = 0):
    """Offered-load closed loop over an N-replica decode fleet with a
    SCRIPTED mid-run replica kill and a rolling hot weight reload —
    the serving-resilience proof line (ISSUE 14, docs/SERVING.md
    §fleet).

    Phase A submits half the stream and immediately fault-injects
    replica 0 (chaos.kill_replica drives the real scheduler-death
    path), so its in-flight generations fail over to survivors and
    regenerate token-identically (the fleet verifies committed
    prefixes; a parity break fails the run).  Phase B submits the rest
    and rolls the SAME weights through the survivors mid-stream
    (fleet.reload: evacuate → io.load_sharded → same-shape swap).  The
    headline is requests/s sustained ACROSS both events with zero
    client-visible failures; the entry carries the failover/hedge/
    retry counters, reload_pause_ms, and the fleet-wide
    post_warmup_compiles == 0 proof."""
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import Executor, scope_guard
    from paddle_tpu.models.decoder_lm import DecoderLM, make_prompts
    from paddle_tpu.resilience import chaos
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.fleet import Fleet, FleetConfig

    arch = dict(vocab_size=8192, n_layer=4, n_head=8, d_model=512,
                d_inner=1024)
    num_slots, page, max_len, chunk = 8, 16, 256, 8
    buckets = (32, 64)
    max_new = 48
    n_requests = n_requests or 48
    prompt_lo, prompt_hi = 8, 64

    def mk_engine(spec_k=speculate):
        lm = DecoderLM(kv_dtype="bfloat16", seed=0, **arch)
        cfg = DecodeConfig(num_slots=num_slots, page_size=page,
                           max_len=max_len,
                           prefill_buckets=buckets,
                           decode_chunk=chunk, kv_dtype="bfloat16")
        return DecodeEngine(lm, cfg, queue_capacity=4 * n_requests,
                            memory_budget_bytes=False,
                            speculate_k=spec_k)

    from paddle_tpu.observe import ReqTracer

    if speculate:
        max_new = max_new * 2  # generation-dominated (ISSUE 20 stream)
        prompts = _repeat_heavy_prompts(n_requests, arch["vocab_size"],
                                        prompt_lo, prompt_hi, seed=0)
    else:
        prompts = make_prompts(n_requests, arch["vocab_size"],
                               min_len=prompt_lo, max_len=prompt_hi,
                               seed=0)
    rng = np.random.RandomState(1)
    budgets = rng.randint(max(2, max_new // 2), max_new + 1,
                          n_requests)
    spec_extra = {}
    if speculate:
        # sequential twin: the same stream (WITHOUT the chaos kill /
        # reload — a clean denominator) through a non-speculative
        # fleet, for speedup_vs_sequential and the parity pin
        sfleet = Fleet([mk_engine(0) for _ in range(n_replicas)],
                       FleetConfig()).start()
        t0 = time.perf_counter()
        futs = [sfleet.submit(p, max_new_tokens=int(b))
                for p, b in zip(prompts, budgets)]
        s_outs = [f.result(1200) for f in futs]
        s_elapsed = time.perf_counter() - t0
        sfleet.close()
        s_tokens = sum(len(r.tokens) for r in s_outs)

    tracer = ReqTracer(sample_rate=0.0)  # tail (failovers) still kept
    engines = [mk_engine() for _ in range(n_replicas)]
    fleet = Fleet(engines, FleetConfig(), tracer=tracer).start()
    half = n_requests // 2
    with tempfile.TemporaryDirectory() as ckpt_dir:
        with scope_guard(engines[0].scope):
            fluid.io.save_sharded(
                Executor(), ckpt_dir,
                main_program=engines[0].model.step["main"])
        t0 = time.perf_counter()
        futs = [fleet.submit(p, max_new_tokens=int(b))
                for p, b in zip(prompts[:half], budgets[:half])]
        chaos.kill_replica(engines[0])  # the scripted mid-run death
        outs = [f.result(1200) for f in futs]
        futs = [fleet.submit(p, max_new_tokens=int(b))
                for p, b in zip(prompts[half:], budgets[half:])]
        reload_info = fleet.reload(ckpt_dir)
        outs += [f.result(1200) for f in futs]
        elapsed = time.perf_counter() - t0
    snap = fleet.snapshot()
    survivors = [h.engine for h in fleet.replicas if not h.dead]
    mem = _decode_mem(survivors[0]) if survivors else {}
    phases = fleet.tracer.phase_summary()
    fleet.close()
    tokens_total = sum(len(r.tokens) for r in outs)
    assert snap["failed"] == 0, snap
    assert snap["parity_failed"] == 0, snap
    assert tokens_total == int(np.sum(budgets)), \
        (tokens_total, int(np.sum(budgets)))
    if speculate:
        parity = all(list(r.tokens) == list(s.tokens)
                     for r, s in zip(outs, s_outs))
        assert parity, ("speculative fleet tokens diverged from the "
                        "sequential fleet (across kill + reload)")
        sec = snap["engines"]["speculation"]
        seq_tps = s_tokens / s_elapsed
        spec_extra = {
            "speculate": speculate,
            "drafter": "ngram",
            "accept_rate": sec["accept_rate"],
            "accept_hist": sec["accept_hist"],
            "speculation_efficiency": sec["speculation_efficiency"],
            "verify_dispatches": sec["verify_dispatches"],
            "sequential_tokens_per_sec": round(seq_tps, 1),
            "speedup_vs_sequential": round(
                (tokens_total / elapsed) / seq_tps, 3),
            "token_parity": parity,
        }
    _, kind = _peak_flops()
    return {
        "requests_per_sec": round(n_requests / elapsed, 2),
        "tokens_per_sec": round(tokens_total / elapsed, 1),
        "n_requests": n_requests,
        "n_replicas": n_replicas,
        "tokens_generated": tokens_total,
        "failover_count": snap["failovers"],
        "hedged": snap["hedges"],
        "retried": snap["retries"],
        "ejects": snap["ejects"],
        "saturated_rejects": snap["saturated"],
        "parity_checked": snap["parity_checked"],
        "reload_pause_ms": snap["reload_pause_ms"],
        "reload_seconds": reload_info["seconds"],
        "model_version": snap["model_version"],
        "zero_client_failures": snap["failed"] == 0,
        "post_warmup_compiles": snap["post_warmup_compiles"],
        "e2e_p50_ms": snap["e2e_ms"]["p50_ms"],
        "e2e_p99_ms": snap["e2e_ms"]["p99_ms"],
        # span-derived phase breakdown (observe pillar 7), fleet-wide
        # across replicas and failover hops
        "join_wait_ms_p50": phases.get("join_wait", {}).get("p50_ms"),
        "dispatch_ms_p50": phases.get("dispatch", {}).get("p50_ms"),
        "failover_ms_p50": phases.get("failover", {}).get("p50_ms"),
        "num_slots": num_slots, "page_size": page,
        "decode_chunk": chunk, "kv_dtype": "bfloat16",
        "device": kind,
        **spec_extra,
        **mem,
    }


def bench_serving_disagg(n_requests: int = 0, speculate: int = 0):
    """Disaggregated prefill/decode serving vs the unified fleet at
    the SAME replica count — the phase-specialization proof line
    (ISSUE 18, docs/SERVING.md §disagg).

    Two closed-loop runs over the SAME prompt stream and budgets:

    - control: a unified 2-replica Fleet (every replica prefills AND
      decodes; a slot is held for the whole generation, so queued
      prompts wait for completions before they see a first token);
    - disagg: 1 prefill worker + 1 decode worker behind the
      DisaggFleet phase router.  Prefill slots recycle per dispatch
      (the ladder never waits on a generation), pages hand off to the
      decode worker via the fixed-shape import scatter.  Geometry
      convention: the decode worker's slot count equals the unified
      fleet's TOTAL (it holds every in-flight generation; affordable
      at equal memory because it compiles no prefill ladder — the
      prefill worker holds no steady-state KV).

    Headline = joint client TTFT p99 (disagg: submit → handoff first
    token at the router; unified: the engine TTFT clocked from
    submit, so both include queue wait) + steady tokens/s, plus the
    handoff tax (handoff_ms_p50, pages/bytes transferred) and the
    fleet-wide post_warmup_compiles == 0 proof — the import path must
    never recompile the decode executable."""
    from paddle_tpu.models.decoder_lm import DecoderLM, make_prompts
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.disagg import DisaggFleet
    from paddle_tpu.serving.fleet import Fleet, FleetConfig

    arch = dict(vocab_size=8192, n_layer=4, n_head=8, d_model=512,
                d_inner=1024)
    num_slots, page, max_len, chunk = 8, 16, 256, 8
    buckets = (32, 64)
    max_new = 48
    n_requests = n_requests or 48
    prompt_lo, prompt_hi = 8, 64

    from paddle_tpu.observe import ReqTracer

    def mk_engine(role="unified", slots=num_slots, spec_k=0):
        lm = DecoderLM(kv_dtype="bfloat16", seed=0, **arch)
        cfg = DecodeConfig(num_slots=slots, page_size=page,
                           max_len=max_len,
                           prefill_buckets=buckets,
                           decode_chunk=chunk, kv_dtype="bfloat16")
        return DecodeEngine(lm, cfg, role=role,
                            queue_capacity=4 * n_requests,
                            memory_budget_bytes=False,
                            speculate_k=spec_k)

    if speculate:
        max_new = max_new * 2  # generation-dominated (ISSUE 20 stream)
        prompts = _repeat_heavy_prompts(n_requests, arch["vocab_size"],
                                        prompt_lo, prompt_hi, seed=0)
    else:
        prompts = make_prompts(n_requests, arch["vocab_size"],
                               min_len=prompt_lo, max_len=prompt_hi,
                               seed=0)
    rng = np.random.RandomState(1)
    budgets = rng.randint(max(2, max_new // 2), max_new + 1,
                          n_requests)

    def run(fleet):
        t0 = time.perf_counter()
        futs = [fleet.submit(p, max_new_tokens=int(b))
                for p, b in zip(prompts, budgets)]
        outs = [f.result(1200) for f in futs]
        elapsed = time.perf_counter() - t0
        tokens = sum(len(r.tokens) for r in outs)
        return outs, tokens, elapsed

    # -- control: unified 2-replica fleet over the same stream ----------
    ufleet = Fleet([mk_engine(), mk_engine()], FleetConfig()).start()
    u_outs, u_tokens, u_elapsed = run(ufleet)
    u_ttft = ufleet.merged_stats().ttft_ms.summary()
    usnap = ufleet.snapshot()
    ufleet.close()
    assert usnap["failed"] == 0, usnap
    assert u_tokens == int(np.sum(budgets)), (u_tokens,
                                              int(np.sum(budgets)))

    # -- disagg: 1 prefill + 1 decode at the same replica count ---------
    tracer = ReqTracer(sample_rate=0.0)  # tail keeps still live
    dfleet = DisaggFleet([mk_engine("prefill")],
                         [mk_engine("decode", slots=2 * num_slots,
                                    spec_k=speculate)],
                         FleetConfig(), tracer=tracer).start()
    d_outs, d_tokens, d_elapsed = run(dfleet)
    dsnap = dfleet.snapshot()
    dspec = dfleet.merged_stats("decode").snapshot().get("speculation")
    mem = _decode_mem(dfleet.decode[0].engine)
    dfleet.close()
    assert dsnap["failed"] == 0, dsnap
    assert dsnap["parity_failed"] == 0, dsnap
    assert d_tokens == int(np.sum(budgets)), (d_tokens,
                                              int(np.sum(budgets)))
    # greedy decode ⇒ the disagg path must be BIT-IDENTICAL to the
    # unified fleet on every request (same weights, same prompts)
    parity = all(list(u.tokens) == list(d.tokens)
                 for u, d in zip(u_outs, d_outs))
    assert parity, "disagg tokens diverged from the unified fleet"
    assert dsnap["post_warmup_compiles"] == 0, dsnap

    ttft_p99 = dsnap["ttft_ms"]["p99_ms"]
    u_ttft_p99 = u_ttft["p99_ms"]
    toks_s = round(d_tokens / d_elapsed, 1)
    u_toks_s = round(u_tokens / u_elapsed, 1)
    spec_extra = {}
    if speculate:
        # the unified control IS the sequential twin here (it never
        # speculates), so the existing parity pin and its tokens/s
        # double as the speculative contract keys
        spec_extra = {
            "speculate": speculate,
            "drafter": "ngram",
            "accept_rate": dspec["accept_rate"],
            "accept_hist": dspec["accept_hist"],
            "speculation_efficiency": dspec["speculation_efficiency"],
            "verify_dispatches": dspec["verify_dispatches"],
            "sequential_tokens_per_sec": u_toks_s,
            "speedup_vs_sequential": round(toks_s / u_toks_s, 3),
            "token_parity": parity,
        }
    _, kind = _peak_flops()
    return {
        # joint (cross-phase) client metrics — the comparison keys
        "ttft_p99_ms": ttft_p99,
        "ttft_p50_ms": dsnap["ttft_ms"]["p50_ms"],
        "tokens_per_sec": toks_s,
        "requests_per_sec": round(n_requests / d_elapsed, 2),
        "e2e_p50_ms": dsnap["e2e_ms"]["p50_ms"],
        "e2e_p99_ms": dsnap["e2e_ms"]["p99_ms"],
        # the handoff tax, measured
        "handoff_ms_p50": dsnap["handoff_ms"]["p50_ms"],
        "handoff_ms_p99": dsnap["handoff_ms"]["p99_ms"],
        "handoffs": dsnap["handoffs"],
        "pages_transferred": dsnap["pages_transferred"],
        "kv_bytes_transferred": dsnap["bytes_transferred"],
        # unified control at the same replica count / stream
        "unified_ttft_p99_ms": u_ttft_p99,
        "unified_tokens_per_sec": u_toks_s,
        "unified_e2e_p99_ms": usnap["e2e_ms"]["p99_ms"],
        "unified_post_warmup_compiles": usnap["post_warmup_compiles"],
        "wins_ttft": bool(ttft_p99 < u_ttft_p99),
        "wins_tokens": bool(toks_s > u_toks_s),
        "token_parity_vs_unified": parity,
        "zero_client_failures": dsnap["failed"] == 0
                                and usnap["failed"] == 0,
        "post_warmup_compiles": dsnap["post_warmup_compiles"],
        "n_requests": n_requests,
        "tokens_generated": d_tokens,
        "n_prefill_workers": 1, "n_decode_workers": 1,
        "prefill_slots": num_slots, "decode_slots": 2 * num_slots,
        "page_size": page, "decode_chunk": chunk,
        "kv_dtype": "bfloat16",
        "device": kind,
        **spec_extra,
        **mem,
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="all",
                   choices=["all", "resnet50", "transformer", "bert",
                            "lstm", "deepfm", "serving",
                            "serving_engine", "serving_decode",
                            "serving_fleet", "serving_disagg",
                            "longctx"])
    p.add_argument("--batch", type=int, default=0)
    p.add_argument("--mesh", default=None, metavar="dp=N[,mp=M]",
                   help="bench the training models (resnet50/"
                        "transformer/bert/deepfm) over a device mesh, "
                        "e.g. --mesh dp=8, --mesh dp=2,mp=2, --mesh "
                        "fsdp=4: the --batch is the GLOBAL batch, "
                        "feeds shard over the data axes (dp + fsdp) "
                        "via GSPMD and grads all-reduce implicitly; "
                        "an mp axis applies the Megatron transformer "
                        "rules; an fsdp axis ZeRO-shards optimizer "
                        "state ~1/N per device.  Entries gain "
                        "per_device_* throughput + comm_bytes + "
                        "opt_state_bytes_per_device and key as "
                        "<model>_dp2mp2-style.  The devices must "
                        "exist (docs/DIST.md)")
    p.add_argument("--grad-sync", default="none",
                   choices=["none", "bf16", "int8"],
                   help="dp gradient-exchange mode (needs --mesh): "
                        "none = implicit GSPMD all-reduce (default); "
                        "bf16 = explicit shard_map exchange, exact "
                        "psum (the A/B control arm); int8 = EQuARX "
                        "blockwise-int8 two-phase quantized "
                        "all-reduce (collectives.quantized_all_reduce,"
                        " docs/DIST.md).  Default none: no ledger "
                        "row on either side")
    p.add_argument("--seq", type=int, default=0,
                   help="longctx: sequence length (default 8192)")
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--no-amp", action="store_true")
    p.add_argument("--no-flash", action="store_true")
    p.add_argument("--layout", default="NCHW",
                   choices=["NCHW", "NHWC"],
                   help="resnet50 conv stack layout (NHWC = TPU "
                        "channels-last)")
    p.add_argument("--fused-ce", dest="fused_ce", action="store_true",
                   default=None,
                   help="transformer: fused vocab projection+CE Pallas "
                        "kernel (ops/pallas/vocab_ce.py).  Default OFF "
                        "at len256: its reported MFU (0.3289, dense-"
                        "equivalent numerator) exceeds base but WALL "
                        "CLOCK lost 154.0k vs 157.1k tok/s (r05, "
                        "pre-ledger; not measured on the current "
                        "code) — throughput decides; defaults ON at "
                        "8k (longctx)")
    p.add_argument("--no-fused-ce", dest="fused_ce",
                   action="store_false",
                   help="disable the fused vocab-CE kernel everywhere "
                        "(incl. the longctx model, where it is "
                        "otherwise the default)")
    p.add_argument("--fused-qkv", action="store_true",
                   help="transformer: Megatron-style single fused QKV "
                        "projection in self-attention")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="transformer: swap FFN sublayers for switch-MoE "
                        "blocks with this many experts (0 = dense)")
    p.add_argument("--recompute", action="store_true",
                   help="transformer: rematerialize encoder/decoder "
                        "layers (HBM for FLOPs; pair with a larger "
                        "--batch)")
    p.add_argument("--pallas-rnn", action="store_true",
                   help="lstm: route every dynamic_lstm recurrence "
                        "through the blocked fused Pallas kernel "
                        "(ops/pallas/recurrence.py; default stays "
                        "scan: no ledger row on either side)")
    p.add_argument("--rnn-unroll", type=int, default=1,
                   help="lstm: lax.scan unroll factor for the "
                        "recurrence (bit-identical numerics; "
                        "default 1: no ledger row)")
    p.add_argument("--pallas-attn", action="store_true",
                   help="transformer: route flash attention through "
                        "the tiled Pallas kernel instead of the XLA "
                        "composition (A/B candidate)")
    p.add_argument("--head-major", action="store_true",
                   help="transformer/longctx: keep attention "
                        "activations in the flash kernels' head-major "
                        "head-grouped layout end-to-end — zero "
                        "transpose traffic at kernel boundaries "
                        "(ISSUE 8, docs/LAYOUT.md).  Forces the flash "
                        "op for decoder cross attention.  Default "
                        "off: no ledger row on either side")
    p.add_argument("--kv-int8", action="store_true",
                   help="serving_decode: int8 KV-cache pools with "
                        "per-row scale sidecars (the blockwise scheme "
                        "of parallel/collectives.py) instead of the "
                        "bf16 default (no ledger row on either "
                        "side)")
    p.add_argument("--speculate", type=int, default=0, metavar="K",
                   help="serving_decode/serving_fleet/serving_disagg: "
                        "speculative decoding with K-token n-gram "
                        "drafts per verified step (ISSUE 20).  The "
                        "entry runs a sequential twin over the same "
                        "stream and carries accept_rate + "
                        "speedup_vs_sequential + token_parity; "
                        "post_warmup_compiles must stay 0")
    p.add_argument("--xla-attn", action="store_true",
                   help="longctx: force the XLA flash composition "
                        "instead of the Pallas kernel (the longctx "
                        "default is Pallas; this is its A/B twin)")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="capture a jax.profiler trace of each timed "
                        "window into DIR (feeds the MFU-gap analysis)")
    p.add_argument("--data", default="synthetic",
                   choices=["synthetic", "frozen", "host"],
                   help="resnet50 input mode: fresh on-device synthetic "
                        "per step (default, the honest number), frozen "
                        "device batch (ceiling), or host batches via "
                        "the prefetch pipeline")
    p.add_argument("--no-telemetry", dest="telemetry",
                   action="store_false",
                   help="measure WITHOUT the in-step telemetry "
                        "accumulator (nonfinite/skipped counters then "
                        "report null — explicitly unknown, not clean)")
    p.add_argument("--guard", action="store_true",
                   help="enable the resilience non-finite update guard "
                        "on the benched training programs (skipped "
                        "updates are counted and flagged)")
    p.add_argument("--model-deadline", type=int,
                   default=int(os.environ.get(
                       "BENCH_MODEL_DEADLINE_S", 900)),
                   help="per-model wall-clock budget; a hung model "
                        "records an error instead of burning the run "
                        "(0 disables)")
    p.add_argument("--flight-dir", default=None, metavar="DIR",
                   help="observe pillar 9: run an AlertEngine "
                        "(compile-storm/nonfinite tripwires) for the "
                        "bench and write a diagnostic flight bundle "
                        "there on every model failure/hang — failed "
                        "entries carry alerts_fired + flight_bundle")
    args = p.parse_args()
    amp = not args.no_amp

    if args.profile:
        global _PROFILE_DIR
        _PROFILE_DIR = args.profile
    global _TELEMETRY, _GUARD
    _TELEMETRY = args.telemetry
    _GUARD = args.guard

    mesh_axes = _parse_mesh(args.mesh) if args.mesh else None
    grad_sync = None if args.grad_sync == "none" else args.grad_sync
    if grad_sync and not mesh_axes:
        p.error("--grad-sync needs --mesh (it is the dp gradient-"
                "exchange mode)")
    # run provenance (observe pillar 3): every JSON line — including
    # the backend-failure one — is traceable to a run-id + git sha
    from paddle_tpu.observe import events as _obs_events

    run_id = _obs_events.new_run_id()
    repo_dir = os.path.dirname(os.path.abspath(__file__))
    run_sha = _obs_events.git_sha(repo_dir)

    from paddle_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        # first (and only) backend contact, in THIS process: one
        # process per chip.  A hung init is the chip tool's timeout to
        # catch; a missing backend or a device bench.py has no peak
        # for fails here, with the JSON line and a non-zero exit
        _peak_flops()
    except RuntimeError as e:
        import sys

        print(json.dumps({
            "metric": "bench_failed",
            "value": 0.0,
            "unit": "backend unavailable",
            "vs_baseline": 0.0,
            "detail": {"backend": {"error": str(e)}},
            "compile_s": 0.0,
            "retraces": 0,
            "peak_mem_bytes": None,
            "mem_breakdown": None,
            "run_id": run_id,
            "git_sha": run_sha,
        }))
        sys.exit(1)

    from paddle_tpu.observe import monitoring as _obs_monitoring

    run_snap = _obs_monitoring.runtime_stats.snapshot()

    # observe pillar 9 (opt-in): a host-only AlertEngine watching the
    # run's own runtime counters, and a FlightRecorder that captures
    # the evidence bundle the moment a model fails or hangs
    _alert_eng = None
    _flight_rec = None
    if args.flight_dir:
        from paddle_tpu.observe.alerts import AlertEngine, ThresholdRule
        from paddle_tpu.observe.flightrec import FlightRecorder
        from paddle_tpu.observe.registry import (MetricsRegistry,
                                                 standard_collectors)

        _areg = standard_collectors(MetricsRegistry())
        _alert_eng = AlertEngine(_areg, rules=[
            ThresholdRule(
                "bench_compile_storm", "runtime_retraces_total",
                op=">", threshold=0.05, window_s=120.0,
                description="retrace storm during bench"),
        ], interval_s=10.0)
        _areg.register("alerts", _alert_eng.collector())
        # every failing model gets its own bundle: the per-model
        # SIGALRM deadline means failures can be ~15 min apart, but a
        # cascade (dead backend) must not be rate-limited away
        _flight_rec = FlightRecorder(args.flight_dir, registry=_areg,
                                     min_interval_s=0.0)
        _flight_rec.attach_engine(_alert_eng)
        _alert_eng.start()

    detail = {}

    # a stale snapshot from a PREVIOUS run must not masquerade as this
    # run's evidence if we die before the first model completes
    try:
        os.remove("bench_partial.json")
    except OSError:
        pass

    def _headline_of(v):
        for k in ("mfu", "examples_per_sec", "imgs_per_sec",
                  "requests_per_sec", "error"):
            if k in v:
                return v[k]
        return "?"

    def _snapshot():
        # a driver-timeout kill must never again leave ZERO evidence
        # (r03: rc=124, nothing printed): after every model the
        # cumulative detail lands in bench_partial.json on disk and a
        # snapshot line on stderr; the one-line stdout contract is
        # untouched (final line only)
        import sys

        try:
            with open("bench_partial.json", "w") as f:
                json.dump({"partial": True, "detail": detail}, f,
                          indent=1)
        except OSError:
            pass
        print("bench snapshot: " + json.dumps(
            {k: _headline_of(v) for k, v in detail.items()}),
            file=sys.stderr)

    def _run(name, fn, *fn_args, **fn_kwargs):
        # one failing config must not cost the other entries their
        # lines: its error is recorded, the run goes on, and main()
        # exits non-zero after printing the JSON line
        import sys
        import traceback

        from paddle_tpu.observe import monitoring as _obs

        from paddle_tpu.resilience.watchdog import Deadline

        snap = _obs.runtime_stats.snapshot()
        try:
            # per-model SIGALRM watchdog (resilience.Deadline): a hung
            # compile/dispatch becomes a recorded per-model error
            # instead of eating the driver's whole timeout
            with Deadline(args.model_deadline, what=f"{name} bench"):
                detail[name] = fn(*fn_args, **fn_kwargs)
        except BaseException as e:
            if isinstance(e, (KeyboardInterrupt, SystemExit)):
                raise
            traceback.print_exc()
            # which anatomy phase died (DispatchWatchdog's proxy): no
            # completed dispatch inside the region = it never got past
            # the first compile; otherwise steps were flowing and a
            # mid-run step/fetch is what hung or threw
            d = _obs.runtime_stats.delta(snap)
            detail[name] = {
                "error": f"{type(e).__name__}: {e}",
                "hang_phase": ("first_compile" if d["dispatches"] == 0
                               else "hung_step"),
            }
            if _alert_eng is not None:
                # pillar 9: the failure line carries what was firing
                # at the moment of death plus the evidence bundle
                _alert_eng.evaluate()
                detail[name]["alerts_fired"] = _alert_eng.firing()
                detail[name]["flight_bundle"] = _flight_rec.record(
                    f"bench_{name}_{detail[name]['hang_phase']}",
                    context=dict(detail[name]), force=True)
            print(f"warning: {name} bench failed, continuing",
                  file=sys.stderr)
        # observability stamp (observe pillar 2): compile wall-time and
        # retraces attributable to THIS model's region (cost_analysis
        # twin compiles included — they are real compile time this
        # config spends), plus the allocator's high-water mark so an
        # almost-OOM config is visible in the artifact.  Attached even
        # to failed entries — a compile-storm-then-die is exactly the
        # evidence wanted.
        delta = _obs.runtime_stats.delta(snap)
        detail[name]["compile_s"] = round(delta["compile_time_s"], 3)
        detail[name]["retraces"] = delta["retraces"]
        detail[name]["peak_mem_bytes"] = _obs.peak_memory_bytes()
        _snapshot()

    # mesh entries key as <model>_<mesh> (transformer_dp8,
    # transformer_dp2mp2, transformer_fsdp4): a mesh number must never
    # collide with (or gate against) the single-device entry of the
    # same model — or a different mesh's — in an artifact
    mesh_sfx = _mesh_key(mesh_axes) if mesh_axes else ""
    dp_kw = {"mesh_axes": mesh_axes, "grad_sync": grad_sync}

    if args.model in ("all", "resnet50"):
        _run("resnet50" + mesh_sfx, bench_resnet50, args.batch or 128,
             args.steps, args.warmup, use_amp=amp, data_mode=args.data,
             data_format=args.layout, **dp_kw)
        if args.model == "all" and args.data == "synthetic" \
                and not mesh_axes:
            # record the frozen-feed ceiling alongside the honest
            # number — same layout, or the "ceiling" is a different
            # program (dp entries already measure the frozen feed)
            _run("resnet50_frozen", bench_resnet50, args.batch or 128,
                 args.steps, args.warmup, use_amp=amp,
                 data_mode="frozen", data_format=args.layout)
    if args.model in ("all", "transformer"):
        _run("transformer" + mesh_sfx, bench_transformer,
             args.batch or 64, args.steps, args.warmup, use_amp=amp,
             use_flash=not args.no_flash,
             use_fused_ce=bool(args.fused_ce),
             fused_qkv=args.fused_qkv, moe_experts=args.moe_experts,
             flash_pallas=args.pallas_attn, recompute=args.recompute,
             head_major=args.head_major, **dp_kw)
    if args.model in ("all", "bert"):
        _run("bert" + mesh_sfx, bench_bert, args.batch or 32,
             args.steps, args.warmup, use_amp=amp,
             use_flash=not args.no_flash, **dp_kw)
    if args.model in ("all", "lstm"):
        _run("lstm", bench_lstm, args.batch or 128, args.steps,
             args.warmup, pallas_rnn=args.pallas_rnn,
             rnn_unroll=args.rnn_unroll)
    if args.model in ("all", "deepfm"):
        _run("deepfm" + mesh_sfx, bench_deepfm, args.batch or 4096,
             args.steps, args.warmup, **dp_kw)
    if args.model in ("all", "serving"):
        # the driver's default `--model all` invocation must capture the
        # serving + int8 lines too (VERDICT r3 weak #4)
        _run("serving", bench_serving, 8 if args.model == "all"
             else (args.batch or 8))
        if args.model == "all":
            # throughput-shape serving entry (VERDICT r5 do-this #4):
            # bs64 is where the int8 MXU win clears dispatch noise —
            # the bs8 line above stays as the latency-shape record
            _run("serving_bs64", bench_serving, 64)
    if args.model in ("all", "serving_engine"):
        # production-serving proof point: dynamic batching under
        # concurrent offered load vs per-request dispatch, zero
        # post-warmup compiles (docs/SERVING.md)
        _run("serving_engine", bench_serving_engine,
             args.batch or (16 if args.model == "all" else 32))
    if args.model in ("all", "serving_decode"):
        # generative-decode proof point (ISSUE 12): continuous
        # batching + paged KV under an offered-load ragged request
        # stream; post_warmup_compiles in the entry must be 0
        if args.speculate and args.model == "serving_decode":
            _run(f"serving_decode_spec_k{args.speculate}",
                 bench_serving_decode, n_requests=args.batch or 0,
                 kv_int8=args.kv_int8, speculate=args.speculate)
        else:
            _run("serving_decode", bench_serving_decode,
                 n_requests=args.batch or 0, kv_int8=args.kv_int8)
            if args.model == "all":
                # the speculative proof line rides `--model all`
                # (ISSUE 20): k=4 n-gram drafting + its sequential
                # twin on the repeat-heavy stream
                spec_k = args.speculate or 4
                _run(f"serving_decode_spec_k{spec_k}",
                     bench_serving_decode, n_requests=0,
                     speculate=spec_k)
    if args.model in ("all", "serving_fleet"):
        # serving-resilience proof line (ISSUE 14): offered load across
        # a scripted replica kill + rolling hot weight reload — zero
        # client-visible failures and zero fleet-wide post-warmup
        # compiles by contract (perf_gate --schema enforces the keys)
        if args.speculate and args.model == "serving_fleet":
            _run(f"serving_fleet_spec_k{args.speculate}",
                 bench_serving_fleet, n_requests=args.batch or 0,
                 speculate=args.speculate)
        else:
            _run("serving_fleet", bench_serving_fleet,
                 n_requests=args.batch or 0)
    if args.model in ("all", "serving_disagg"):
        # phase-disaggregation proof line (ISSUE 18): prefill/decode
        # workers + KV-page handoff vs the unified fleet at the same
        # replica count — joint TTFT p99 + steady tokens/s + the
        # handoff tax, zero post-warmup compiles fleet-wide (the
        # import scatter never recompiles the decode executable)
        if args.speculate and args.model == "serving_disagg":
            _run(f"serving_disagg_spec_k{args.speculate}",
                 bench_serving_disagg, n_requests=args.batch or 0,
                 speculate=args.speculate)
        else:
            _run("serving_disagg", bench_serving_disagg,
                 n_requests=args.batch or 0)
    if args.model in ("all", "longctx"):
        # long-context proof point (VERDICT r4 item 7): seq 8k with the
        # O(T)-memory stack — Pallas flash for self AND cross
        # attention, fused vocab-CE (no (B,T,32k) logits in HBM),
        # per-layer recompute.  Runs AFTER the headline models so a
        # long-sequence OOM/compile failure can't cost their entries.
        # recompute default OFF here: bs2/8k activations fit in HBM and
        # r05 read 0.306 vs 0.243 MFU (pre-ledger; not measured on the
        # current code) — remat is for when memory does NOT
        # fit (--recompute re-enables; the recompute variant stays
        # recorded in the artifact).  fused-CE default ON at 8k+
        # (unlike the short-seq transformer) — --no-fused-ce still
        # turns it off for kernel A/Bs.  Entry key names the resolved
        # sequence length so a --seq override can't mislabel its
        # artifact entry.
        # non-multiple-of-1024 (or sub-1k) --seq values must not floor
        # to a colliding/degenerate "longctx_0k"-style key
        seq = args.seq or 8192
        seq_key = (f"longctx_{seq // 1024}k" if seq % 1024 == 0
                   else f"longctx_{seq}")
        _run(seq_key, bench_transformer,
             args.batch or 2, max(args.steps // 4, 3), 1,
             max_length=seq, use_amp=amp, use_flash=True,
             use_fused_ce=args.fused_ce is not False,
             flash_pallas=not args.xla_attn,
             recompute=args.recompute,
             head_major=args.head_major)

    # headline = min MFU across the two NORTH-STAR models (BASELINE.json
    # names ResNet-50 + Transformer for the >=35% bar); bert/lstm/deepfm
    # report in detail.  A failed headline model must be visible at the
    # TOP level, not just buried in detail.
    failed = sorted(k for k, v in detail.items() if "error" in v)
    headline = [detail[k + mesh_sfx]["mfu"]
                for k in ("resnet50", "transformer")
                if "mfu" in detail.get(k + mesh_sfx, {})]
    if headline:
        metric = (f"min_train_mfu_resnet50_transformer{mesh_sfx}"
                  if len(headline) > 1
                  else f"{args.model}{mesh_sfx}_train_mfu")
        if failed:
            metric += "_PARTIAL_FAILURE"
        result = {
            "metric": metric,
            "value": round(min(headline), 4),
            "unit": "MFU (fraction of bf16 peak)",
            "vs_baseline": round(min(headline) / 0.35, 3),  # north star
            "detail": detail,
        }
        if failed:
            result["failed"] = failed
    elif (args.model not in ("all", "resnet50", "transformer")
          and any("mfu" in d for d in detail.values())):
        # a specifically-requested non-headline model: report its MFU
        # (when "all" ran and BOTH north-star models failed, fall
        # through to bench_failed instead of faking a green headline)
        mfus = [d["mfu"] for d in detail.values() if "mfu" in d]
        result = {
            "metric": f"{args.model}_train_mfu",
            "value": round(min(mfus), 4),
            "unit": "MFU (fraction of bf16 peak)",
            "vs_baseline": round(min(mfus) / 0.35, 3),
            "detail": detail,
        }
        if failed:
            result["metric"] += "_PARTIAL_FAILURE"
            result["failed"] = failed
    elif "serving" in detail and "imgs_per_sec" in detail["serving"]:
        d = detail["serving"]
        # reference-published ResNet-50 inference: 217.69 img/s bs16
        # MKL-DNN Xeon (benchmark/IntelOptimizedPaddle.md:83-89).
        # `value` is device-compute throughput with host dispatch
        # amortized (see p50_ms for e2e); the reference number is e2e.
        result = {
            "metric": "resnet50_serving_compute_imgs_per_sec",
            "value": d["imgs_per_sec"],
            "unit": ("imgs/sec (dispatch-amortized compute %.2fms; "
                     "e2e p50 %.2fms incl. host dispatch)"
                     % (d["compute_ms"], d["p50_ms"])),
            "vs_baseline": round(d["imgs_per_sec"] / 217.69, 3),
            "detail": detail,
        }
    elif ("serving_engine" in detail
          and "requests_per_sec" in detail["serving_engine"]):
        d = detail["serving_engine"]
        # offered-load throughput with dynamic batching; vs_baseline is
        # the speedup over per-request dispatch measured in the SAME
        # run (>1.0 = batching pays; the acceptance bar for the
        # serving subsystem)
        result = {
            "metric": "resnet50_serving_engine_requests_per_sec",
            "value": d["requests_per_sec"],
            "unit": ("req/s offered-load (%.1fx vs per-request; p50 "
                     "%.1fms p99 %.1fms; %d post-warmup compiles)"
                     % (d["batching_speedup"], d["p50_ms"],
                        d["p99_ms"], d["post_warmup_compiles"])),
            "vs_baseline": d["batching_speedup"],
            "detail": detail,
        }
    elif any(k.startswith("serving_decode")
             and "tokens_per_sec" in v for k, v in detail.items()):
        key = next(k for k in (["serving_decode"] + sorted(detail))
                   if k in detail and k.startswith("serving_decode")
                   and "tokens_per_sec" in detail[k])
        d = detail[key]
        if d.get("speculate"):
            result = {
                "metric": f"decoder_{key}_tokens_per_sec",
                "value": d["tokens_per_sec"],
                "unit": ("generated tokens/s speculative k=%d "
                         "(accept rate %.2f, %.2fx vs sequential, "
                         "parity %s, %d post-warmup compiles)"
                         % (d["speculate"], d["accept_rate"] or 0.0,
                            d["speedup_vs_sequential"],
                            d["token_parity"],
                            d["post_warmup_compiles"])),
                # the acceptance bar for the speculative subsystem:
                # >1.0 = speculation pays on this stream
                "vs_baseline": d["speedup_vs_sequential"],
                "detail": detail,
            }
        else:
            result = {
                "metric": "decoder_serving_decode_tokens_per_sec",
                "value": d["tokens_per_sec"],
                "unit": ("generated tokens/s offered-load (occupancy "
                         "%.2f, pool util %.2f, %d preemptions, %d "
                         "post-warmup compiles)"
                         % (d["slot_occupancy"] or 0.0,
                            d["kv_page_utilization"] or 0.0,
                            d["preemptions"],
                            d["post_warmup_compiles"])),
                "vs_baseline": 0.0,  # first recorded decode line
                "detail": detail,
            }
    elif any(k.startswith("serving_fleet")
             and "requests_per_sec" in v for k, v in detail.items()):
        key = next(k for k in (["serving_fleet"] + sorted(detail))
                   if k in detail and k.startswith("serving_fleet")
                   and "requests_per_sec" in detail[k])
        d = detail[key]
        result = {
            "metric": f"decoder_{key}_requests_per_sec",
            "value": d["requests_per_sec"],
            "unit": ("req/s offered-load across a replica kill + "
                     "weight roll (%d failovers, reload pause %.1fms, "
                     "%d post-warmup compiles)"
                     % (d["failover_count"], d["reload_pause_ms"],
                        d["post_warmup_compiles"])),
            "vs_baseline": 0.0,  # first recorded fleet line
            "detail": detail,
        }
    elif any(k.startswith("serving_disagg")
             and "tokens_per_sec" in v for k, v in detail.items()):
        key = next(k for k in (["serving_disagg"] + sorted(detail))
                   if k in detail and k.startswith("serving_disagg")
                   and "tokens_per_sec" in detail[k])
        d = detail[key]
        result = {
            "metric": f"decoder_{key}_tokens_per_sec",
            "value": d["tokens_per_sec"],
            "unit": ("tok/s 1P+1D disagg vs unified %.1f (TTFT p99 "
                     "%.1fms vs %.1fms, handoff p50 %.2fms, %d pages, "
                     "%d post-warmup compiles)"
                     % (d["unified_tokens_per_sec"], d["ttft_p99_ms"],
                        d["unified_ttft_p99_ms"], d["handoff_ms_p50"],
                        d["pages_transferred"],
                        d["post_warmup_compiles"])),
            "vs_baseline": 0.0,  # first recorded disagg line
            "detail": detail,
        }
    elif "examples_per_sec" in detail.get("deepfm", {}):
        d = detail["deepfm"]
        result = {
            "metric": "deepfm_train_examples_per_sec",
            "value": d["examples_per_sec"],
            "unit": "examples/sec/chip",
            "vs_baseline": 0.0,  # no reference-published CTR number
            "detail": detail,
        }
    else:
        result = {
            "metric": "bench_failed",
            "value": 0.0,
            "unit": "see detail errors",
            "vs_baseline": 0.0,
            "detail": detail,
        }
        if failed:
            result["failed"] = failed
    # bench honesty (resilience satellite): the one JSON line carries
    # the measured windows' nonfinite/skipped-update totals, and a run
    # whose throughput was "earned" while updates were being skipped is
    # flagged — perf_gate refuses to gate a tainted candidate
    nonf = sum(v.get("nonfinite_steps") or 0 for v in detail.values()
               if isinstance(v, dict))
    skipped = sum(v.get("skipped_update_steps") or 0
                  for v in detail.values() if isinstance(v, dict))
    result["nonfinite_steps"] = nonf
    result["skipped_update_steps"] = skipped
    if nonf or skipped:
        result["nonfinite_flag"] = True
    # whole-run observability totals + provenance on the one JSON line
    run_delta = _obs_monitoring.runtime_stats.delta(run_snap)
    result["compile_s"] = round(run_delta["compile_time_s"], 3)
    result["retraces"] = run_delta["retraces"]
    result["peak_mem_bytes"] = _obs_monitoring.peak_memory_bytes()
    # top-line mem_breakdown = the single hungriest entry's buffer
    # accounting (the binding constraint for "does this run fit"),
    # tagged with which model it came from; every line carries the key
    # (perf_gate --schema enforces it), None when nothing measured one
    hungriest = None
    for name, v in detail.items():
        mb = v.get("mem_breakdown") if isinstance(v, dict) else None
        if isinstance(mb, dict) and mb.get("peak_bytes"):
            if hungriest is None \
                    or mb["peak_bytes"] > hungriest["peak_bytes"]:
                hungriest = dict(mb, model=name)
    result["mem_breakdown"] = hungriest
    result["run_id"] = run_id
    result["git_sha"] = run_sha
    if args.profile:
        # profiler-inflated numbers must be distinguishable from clean
        # runs (bench-honesty gate)
        result["profiled"] = args.profile
    if _alert_eng is not None:
        # pillar 9 rides the one JSON line: what fired over the whole
        # run and where the evidence bundles landed
        _alert_eng.evaluate()
        _alert_eng.close()
        result["alerts_fired"] = _alert_eng.firing()
        result["flight_bundles"] = _flight_rec.snapshot()["bundles"]
    if not failed and result["metric"] != "bench_failed":
        # the incremental snapshot is crash evidence only — it must
        # never outlive a clean run (a grep for "mfu" should find the
        # real artifacts, not a partial)
        try:
            os.remove("bench_partial.json")
        except OSError:
            pass
    print(json.dumps(result))
    if failed or result["metric"] == "bench_failed":
        import sys

        sys.exit(1)


if __name__ == "__main__":
    main()
