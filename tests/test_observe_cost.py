"""observe.cost — analytic HLO flop/byte accounting, the Pallas kernel
cost registry, and the per-op cost table (ISSUE 2 tentpole).

Pins the contracts the perf story now rests on:
- analytic per-instruction flops agree with XLA's own cost_analysis()
  aggregate on dot/conv programs (the numerator is not invented);
- the Pallas registry formulas match the dense twin's XLA count on
  flash-attention and vocab-CE shapes (the native MFU numerator is the
  same number the twin workaround produced);
- the materialized-buffers bytes model and the layout/copy/transpose
  bucket exist and fire on a program with a forced layout transpose
  (the r05 longctx diagnostic, chip-free);
- op_cost_table produces per-fluid-op rows for a transformer train
  step on the CPU backend, and joins measured time from a captured
  trace.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.observe import cost


def _xla_flops(compiled):
    analyses = compiled.cost_analysis()
    if isinstance(analyses, (list, tuple)):
        analyses = analyses[0]
    return float(analyses.get("flops", 0.0))


def _totals(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return (cost.total_costs(cost.compiled_hlo_proto(compiled)),
            _xla_flops(compiled))


def test_analytic_flops_match_xla_on_dot_program():
    x = jnp.ones((256, 512), jnp.float32)
    y = jnp.ones((512, 128), jnp.float32)

    def f(x, y):
        return jax.nn.relu(x @ y + 1.0).sum()

    totals, xla = _totals(f, x, y)
    assert xla > 3e7  # dot-dominated
    assert abs(totals["flops"] - xla) / xla < 0.02, (totals["flops"],
                                                     xla)


def test_analytic_flops_match_xla_on_batched_dot():
    a = jnp.ones((4, 64, 96), jnp.float32)
    b = jnp.ones((4, 96, 32), jnp.float32)
    totals, xla = _totals(
        lambda a, b: jnp.einsum("bij,bjk->bik", a, b), a, b)
    assert totals["flops"] == xla  # contraction math is exact


def test_analytic_flops_match_xla_on_conv_program():
    x = jnp.ones((4, 32, 32, 16), jnp.float32)
    w = jnp.ones((3, 3, 16, 32), jnp.float32)

    def f(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")).sum()

    totals, xla = _totals(f, x, w)
    assert xla > 3e7
    assert abs(totals["flops"] - xla) / xla < 0.02


def test_layout_bucket_fires_on_forced_transpose():
    # returning the transposed array forces a physical layout change
    # into the entry computation (copy or transpose instruction)
    x = jnp.ones((128, 64), jnp.float32)

    def f(x):
        return jnp.transpose(x, (1, 0)) + 0.0, (x * 2.0).sum()

    compiled = jax.jit(f).lower(x).compile()
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    layout = [r for r in rows if r["bucket"] == "layout"]
    assert layout, [r["opcode"] for r in rows]
    # the transpose moves the whole buffer: read + write >= 2x payload
    assert sum(r["bytes"] for r in layout) >= 2 * 128 * 64 * 4


def test_materialized_bytes_below_xla_aggregate():
    # the min-traffic model must not exceed XLA's (overcounting)
    # aggregate on a fusion-heavy program — that inversion is exactly
    # what produced the impossible r05 roofline ceiling
    x = jnp.ones((256, 256), jnp.float32)

    def f(x):
        y = jax.nn.relu(x @ x + x)
        return (y * y + 3.0).sum()

    compiled = jax.jit(f).lower(x).compile()
    analyses = compiled.cost_analysis()
    if isinstance(analyses, (list, tuple)):
        analyses = analyses[0]
    totals = cost.total_costs(cost.compiled_hlo_proto(compiled))
    assert totals["bytes"] > 0
    assert totals["bytes"] <= float(analyses.get("bytes accessed",
                                                 float("inf")))


# -- Pallas cost registry vs the dense twin --------------------------------

def test_flash_registry_matches_dense_twin():
    from paddle_tpu.ops.attention import _xla_attention
    from paddle_tpu.ops.pallas.flash_attention import attention_cost

    n, h, t, d = 2, 4, 256, 128
    scale = d ** -0.5
    q = jnp.ones((n, h, t, d), jnp.float32)
    do = jnp.ones_like(q)

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(
            lambda a, b, c: _xla_attention(a, b, c, None, scale, True),
            q, k, v)
        return o, vjp(do)

    dense = _xla_flops(jax.jit(fwd_bwd).lower(q, q, q, do).compile())
    registry, _bytes = attention_cost(n * h, t, t, d)
    rel = abs(registry - dense) / dense
    assert rel < 0.05, (registry, dense, rel)


@pytest.mark.parametrize("layout", ["nhtd", "nthd"])
def test_single_backward_kernel_carries_dqs_count(layout):
    """`flash_dkv` names the two-kernel path's dk / dv kernel (two
    gradients out, or three with a bias's (nh, t_k, 1) column) and the
    single backward kernel (dq, dk, dv): the second carries dq's
    dense-equivalent work too, so with `flash_fwd` a step's count is
    the dense twin's on both paths: six dots' worth (the recomputed
    scores are never credited)."""
    from paddle_tpu.ops.pallas import KERNEL_COSTS
    from paddle_tpu.ops.pallas.flash_attention import attention_cost

    n, h, t, d = 2, 4, 256, 128
    x = ((n * h, t, d), 2) if layout == "nhtd" else ((n, t, h * d), 2)
    stat, db = ((n * h, 8, t), 4), ((n * h, t, 1), 4)
    bwd_in = [x, x, x, x, x, stat]
    fwd = KERNEL_COSTS["flash_fwd"]([x, x, x], [x, stat])
    one = KERNEL_COSTS["flash_dkv"](bwd_in, [x, x, x])
    dkv = KERNEL_COSTS["flash_dkv"](bwd_in, [x, x])
    dq = KERNEL_COSTS["flash_dq"](bwd_in, [x])
    assert one[0] == dkv[0] + dq[0]
    assert KERNEL_COSTS["flash_dkv"](bwd_in + [db], [x, x, db])[0] == dkv[0]
    registry, _bytes = attention_cost(n * h, t, t, d)   # the dense twin's,
    assert fwd[0] + one[0] == registry      # test_flash_registry_matches...
    scores = n * h * t * t
    assert 2 * d * 6 * scores <= fwd[0] + one[0] <= (2 * d * 6 + 16) * scores
    # bytes: every operand and result once
    assert one[1] == 2 * 8 * n * h * t * d + 4 * n * h * 8 * t


def test_vocab_ce_registry_matches_dense_twin():
    from paddle_tpu.ops.pallas.vocab_ce import vocab_ce_cost

    n, d, v = 1024, 256, 4096
    eps = 0.1
    h = jnp.ones((n, d), jnp.float32)
    w = jnp.ones((d, v), jnp.float32)
    lbl = jnp.zeros((n,), jnp.int32)

    def dense(h, w):
        z = (h @ w).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        zt = jnp.take_along_axis(z, lbl.reshape(-1, 1),
                                 axis=-1)[..., 0]
        return jnp.sum(lse - (1.0 - eps) * zt
                       - (eps / v) * jnp.sum(z, axis=-1))

    twin = _xla_flops(jax.jit(
        lambda h, w: jax.value_and_grad(dense, argnums=(0, 1))(h, w)
    ).lower(h, w).compile())
    registry, _bytes = vocab_ce_cost(n, d, v)
    rel = abs(registry - twin) / twin
    assert rel < 0.05, (registry, twin, rel)


def test_kernel_costs_registered_for_every_scoped_kernel():
    # the bench numerator REFUSES custom calls without a registered
    # cost; every name= passed to pallas_call must therefore have one
    from paddle_tpu.ops import pallas as pallas_pkg
    from paddle_tpu.ops.pallas import (  # noqa: F401
        flash_attention, recurrence, vocab_ce)

    expected = {"flash_fwd", "flash_dkv", "flash_dq",
                "vocab_ce_fwd", "vocab_ce_dh", "vocab_ce_dw",
                "lstm_fwd", "lstm_bwd"}
    assert expected <= set(pallas_pkg.KERNEL_COSTS), \
        sorted(pallas_pkg.KERNEL_COSTS)
    # and the registered fns compute from custom-call operand shapes
    q = ((8, 256, 64), 2)
    flops, nbytes = pallas_pkg.KERNEL_COSTS["flash_fwd"](
        [q, q, q], [q, ((8, 256), 4)])
    assert flops > 4 * 8 * 256 * 256 * 64
    assert nbytes > 0


# -- the per-op table on a real fluid program ------------------------------

def _transformer_step():
    from paddle_tpu.models import transformer

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        model = transformer.build_model(
            src_vocab_size=512, trg_vocab_size=512, max_length=64,
            n_layer=2, n_head=2, d_model=64, d_inner_hid=128,
            dropout=0.1, use_amp=False, use_flash=True)
        exe = fluid.Executor()
        exe.run(startup)
        feed = {k: jnp.asarray(v) for k, v in
                transformer.make_fake_batch(2, 64, 512, 512).items()}
    return main, scope, exe, feed, model


def test_op_cost_table_transformer_train_step():
    main, scope, exe, feed, model = _transformer_step()
    with fluid.scope_guard(scope):
        rows = observe.op_cost_table(main, feed=feed,
                                     fetch_list=[model["loss"]],
                                     exe=exe)
    assert rows
    for r in rows:
        for key in ("op_type", "bucket", "flops", "bytes", "time_ms",
                    "achieved_flops_frac", "arith_intensity"):
            assert key in r, (key, sorted(r))
    buckets = {r["bucket"] for r in rows}
    # matmul attribution: the projection mats and the flash_attention
    # op carry the dot flops
    mm = {r["op_type"] for r in rows if r["bucket"] == "matmul"}
    assert {"mul", "flash_attention"} <= mm, mm
    # the layout/copy/transpose bucket is DISTINCT and non-empty even
    # at baseline shapes (transpose fluid ops around attention)
    assert "layout" in buckets, buckets
    layout_ops = {r["op_type"] for r in rows if r["bucket"] == "layout"}
    assert "transpose" in layout_ops, layout_ops
    # flops are dominated by attributed matmul work, not invented
    total = sum(r["flops"] for r in rows)
    mm_flops = sum(r["flops"] for r in rows if r["bucket"] == "matmul")
    assert mm_flops > 0.5 * total
    # bucket_summary rolls up without losing anything
    summary = observe.bucket_summary(rows)
    assert abs(sum(b["flops"] for b in summary.values()) - total) < 1
    assert "layout" in summary
    # formatting smoke (the human-facing diagnostic)
    text = observe.format_cost_table(rows)
    assert "layout" in text and "matmul" in text


@pytest.mark.parametrize("xla_from", ["program_costs",
                                      "executor_cost_analysis"])
def test_op_cost_table_against_xla_aggregate(xla_from):
    # whole-program analytic flops track XLA's aggregate on the real
    # train step too (CPU backend: no custom calls, so the counts are
    # directly comparable); the aggregate as `program_costs` carries it
    # and as `Executor.cost_analysis` returns it, from the one compile
    main, scope, exe, feed, model = _transformer_step()
    with fluid.scope_guard(scope):
        totals = observe.program_costs(main, feed=feed,
                                       fetch_list=[model["loss"]],
                                       exe=exe)
        compiled = exe.compiled_step(main, feed=feed,
                                     fetch_list=[model["loss"]],
                                     scope=scope)
        if xla_from == "program_costs":
            xla = totals["xla_aggregate_flops"]
        else:
            xla = exe.cost_analysis(main, feed=feed,
                                    fetch_list=[model["loss"]],
                                    scope=scope)["flops"]
            assert xla == totals["xla_aggregate_flops"]
    assert xla > 0
    # XLA's aggregate counts a while body ONCE; the analytic total
    # carries the trip count (the dropout RNG's 5-round threefry loops
    # here), so take that excess out before comparing
    loops = [r for r in cost.instruction_costs(
        cost.compiled_hlo_proto(compiled)) if r["opcode"] == "while"]
    assert all(r["trip_count"] for r in loops), loops
    # (a counted loop's own row carries no cost: its body's rows do,
    # per call; `body_flops` is the body over all trips)
    assert all(r["flops"] == 0 for r in loops)
    once = totals["flops"] - sum(
        r["body_flops"] * (1.0 - 1.0 / r["trip_count"]) for r in loops)
    assert abs(once - xla) / xla < 0.05, (totals["flops"], once, xla)


def test_a_conditional_costs_its_heaviest_branch_not_the_sum():
    """One branch of a `conditional` runs.  Its row carries no cost
    and the rows of its heaviest branch follow it (`branch_of`), so
    the table still sums to one call; `every_branch` lists them all,
    each instruction under its own name and bucket, which is what a
    trace is joined to; nested in a loop, the same rule by FLOPs."""
    w = {n: jnp.ones((n, n), jnp.float32) for n in (32, 64, 128)}

    def at(n):
        return lambda x: jnp.sum(jnp.tanh(x[:n, :n] @ w[n]))

    def f(i, x):
        return jax.lax.switch(i, [at(32), at(64), at(128)], x)

    compiled = jax.jit(f).lower(jnp.int32(0),
                                jnp.ones((128, 128), jnp.float32)).compile()
    proto = cost.compiled_hlo_proto(compiled)
    rows = cost.instruction_costs(proto)
    (cond,) = [r for r in rows if r["opcode"] == "conditional"]
    assert cond["bucket"] == "branch" and cond["flops"] == cond["bytes"] == 0
    inside = [r for r in rows if r["branch_of"] == cond["name"]]
    dots = [r["flops"] for r in inside if r["bucket"] == "matmul"]
    assert dots == [2.0 * 128 ** 3]              # the 128-row branch only
    assert not [r for r in rows if r["branch_of"] is None
                and r["bucket"] == "matmul"]
    every = cost.instruction_costs(proto, every_branch=True)
    assert sorted(r["flops"] for r in every if r["bucket"] == "matmul") == [
        2.0 * n ** 3 for n in (32, 64, 128)]
    assert len({r["name"] for r in every}) == len(every)
    # a trace is joined to every branch: whichever ran, its ops are
    # rows with a bucket of their own, not a loop body's
    pmap = observe.trace.program_map(proto)
    assert all(pmap[r["name"]]["bucket"] == r["bucket"] for r in every)
    total = cost.total_costs(proto)["flops"]
    assert 2.0 * 128 ** 3 <= total < 2.0 * 128 ** 3 + 2.0 * 64 ** 3

    def looped(i, x):
        return jax.lax.fori_loop(
            0, 16, lambda _, c: 0.5 * c + f(i, x + c), 0.0)

    compiled = jax.jit(looped).lower(
        jnp.int32(0), jnp.ones((128, 128), jnp.float32)).compile()
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    (loop,) = [r for r in rows if r["opcode"] == "while"]
    assert 16 * 2.0 * 128 ** 3 <= loop["body_flops"] < 16 * (
        2.0 * 128 ** 3 + 2.0 * 64 ** 3)
    # ... and the rows of the body say the same, each FLOP once: the
    # heaviest branch's dot is a row of the loop's, 16 trips a step
    assert loop["flops"] == 0
    inside = [r for r in rows if r["loop_of"] == loop["name"]]
    assert [(r["flops"], r["trips"]) for r in inside
            if r["bucket"] == "matmul"] == [(2.0 * 128 ** 3, 16)]
    assert sum(cost.per_step(r, "flops") for r in inside) \
        == loop["body_flops"]


def test_op_cost_table_joins_profile_time(tmp_path):
    # end-to-end: cost rows join measured per-instruction device time
    # from a jax.profiler trace (XLA:CPU emits per-instruction events)
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = layers.data(name="x", shape=[64], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        feed = {"x": rng.rand(32, 64).astype(np.float32),
                "y": rng.rand(32, 1).astype(np.float32)}
        exe.run(main, feed=feed, fetch_list=[loss])  # compile outside
        trace_dir = os.path.join(str(tmp_path), "trace")
        with jax.profiler.trace(trace_dir):
            exe.run(main, feed=feed, fetch_list=[loss])
        rows = observe.op_cost_table(main, feed=feed,
                                     fetch_list=[loss], exe=exe,
                                     profile_dir=trace_dir)
    timed = [r for r in rows if r["time_ms"]]
    assert timed, [(r["op_type"], r["time_ms"]) for r in rows]


def test_fluid_op_of_sees_through_transform_wrappers():
    # value_and_grad wraps scopes: jvp(...) forward, transpose(jvp(...))
    # backward — attribution must survive both (the pre-ISSUE-2 regex
    # lost every fwd/bwd instruction to [unattributed])
    assert observe.fluid_op_of(
        "jit(step)/jit(main)/jvp(mul:3)/dot_general") == "mul"
    assert observe.fluid_op_of(
        "jit(step)/transpose(jvp(softmax:25))/mul") == "softmax"
    assert observe.fluid_op_of("jit(step)/jvp(fc_0)/add") is None


# -- loop-aware attribution (ISSUE 5: the scan ×1 undercount fix) ----------

def _scan_compiled(T=32, N=16, H=64):
    from jax import lax

    def f(xs, w, h0):
        def step(h, x):
            h = jnp.tanh(x + h @ w)
            return h, h
        _hl, hs = lax.scan(step, h0, xs)
        return hs.sum()

    xs = jnp.ones((T, N, H), jnp.float32)
    w = jnp.ones((H, H), jnp.float32)
    h0 = jnp.ones((N, H), jnp.float32)
    g = jax.value_and_grad(f, argnums=(0, 1))
    return jax.jit(g).lower(xs, w, h0).compile(), (T, N, H)


def test_while_trip_count_recovered_from_scan():
    compiled, (T, N, H) = _scan_compiled()
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    whiles = [r for r in rows if r["opcode"] == "while"]
    assert whiles, "expected scan-emitted while loops at entry"
    for r in whiles:
        assert r["trip_count"] == T, (r["name"], r["trip_count"])
        assert r["bucket"] == "loop"


def test_scan_body_flops_multiplied_by_trip_count():
    # the acceptance criterion: no more ×1 undercount.  XLA's own
    # aggregate counts the while bodies ONCE; the analytic totals must
    # carry the full T× recurrence work (fwd dot + 2 bwd dots).
    compiled, (T, N, H) = _scan_compiled()
    totals = cost.total_costs(cost.compiled_hlo_proto(compiled))
    xla = cost.compiled_xla_flops(compiled)
    analytic_bound = T * 2 * N * H * H * 3
    assert totals["flops"] >= 0.9 * analytic_bound, (totals["flops"],
                                                     analytic_bound)
    assert totals["flops"] > 2 * xla, (totals["flops"], xla)


def test_data_dependent_while_gets_loud_loopq_bucket():
    from jax import lax

    def f(x):
        w = jnp.eye(8) * 1.01

        def cond(c):
            v, _ = c
            return jnp.sum(v) < 100.0

        def body(c):
            v, i = c
            return v @ w + 0.1, i + 1

        v, _ = lax.while_loop(cond, body, (x, 0))
        return v.sum()

    compiled = jax.jit(f).lower(jnp.ones((8, 8), jnp.float32)).compile()
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    whiles = [r for r in rows if r["opcode"] == "while"]
    assert whiles
    for r in whiles:
        assert r["trip_count"] is None
        assert r["bucket"] == "[loop?]"


def test_op_cost_table_lstm_step_attributes_trip_multiplied_flops():
    """The lstm acceptance check chip-free: the dynamic_lstm-attributed
    rows of a tiny train step must carry at least T× the per-step
    recurrent GEMM (fwd), i.e. the scan body was multiplied, not
    counted once."""
    B, T, H = 4, 16, 8
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = layers.data(name="x", shape=[T, 4 * H], dtype="float32",
                        lod_level=1)
        lstm_out, _cell = layers.dynamic_lstm(x, size=4 * H,
                                              use_peepholes=False)
        last = layers.sequence_pool(lstm_out, pool_type="max")
        loss = layers.mean(layers.fc(last, size=1))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        feed = {"x": rng.rand(B, T, 4 * H).astype(np.float32),
                "x.seq_len": np.full((B,), T, np.int32)}
        rows = observe.op_cost_table(main, feed=feed,
                                     fetch_list=[loss], exe=exe)
    lstm_flops = sum(r["flops"] for r in rows
                     if r["op_type"] == "dynamic_lstm")
    # fwd recurrence alone: T steps of 2*B*H*4H; bwd adds ~2x more
    fwd_gemm = T * 2 * B * H * 4 * H
    assert lstm_flops >= fwd_gemm, (lstm_flops, fwd_gemm)
    buckets = {r["bucket"] for r in rows
               if r["op_type"] == "dynamic_lstm"}
    assert "loop" in buckets, buckets


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _window_dim(**fields):
    """A serialized WindowDimension from its field numbers."""
    numbers = {"size": 1, "stride": 2, "pad_low": 3, "pad_high": 4,
               "rhs_dilate": 5, "lhs_dilate": 6}
    return b"".join(_varint(numbers[k] << 3) + _varint(v % (1 << 64))
                    for k, v in fields.items())


@pytest.mark.parametrize("size,kernel,out,dim,want", [
    # VALID 3-tap window over 8: every pair reads an element
    (8, 3, 6, dict(size=3, stride=1), 18),
    # SAME padding: the two edge outputs lose one tap each
    (8, 3, 8, dict(size=3, stride=1, pad_low=1, pad_high=1), 22),
    # ResNet's stem: 7 taps, stride 2, padding 2/3 over 224 -> 112;
    # outputs 0, 111 and 110 lose 2, 3 and 1 taps
    (224, 7, 112, dict(size=7, stride=2, pad_low=2, pad_high=3), 778),
    # a TPU batched dot: the batch of 64 as a spatial dimension, a
    # window of 64 of which the dilation leaves one tap per output
    (64, 64, 64, dict(size=64, stride=63, lhs_dilate=64), 64),
    # negative padding (a cropped input)
    (8, 3, 4, dict(size=3, stride=1, pad_low=-2), 12),
])
def test_conv_flops_count_valid_window_positions(size, kernel, out, dim,
                                                 want):
    assert cost._valid_positions(size, kernel, out,
                                 _window_dim(**dim)) == want


def _ld(fno, payload):
    return _varint((fno << 3) | 2) + _varint(len(payload)) + payload


def _vi(fno, n):
    return _varint(fno << 3) + _varint(n)


def _instr(name, opcode, iid, operands=(), called=()):
    """A serialized HloInstructionProto (name=1 opcode=2 id=35
    operand_ids=36 called_computation_ids=38)."""
    return (_ld(1, name.encode()) + _ld(2, opcode.encode()) + _vi(35, iid)
            + b"".join(_vi(36, o) for o in operands)
            + b"".join(_vi(38, c) for c in called))


def _comp(name, cid, instrs, root):
    return (_ld(1, name.encode()) + b"".join(_ld(2, i) for i in instrs)
            + _vi(5, cid) + _vi(6, root))


def test_async_wrappers_are_layout_unless_they_wrap_a_collective():
    """The TPU compiler's `slice-start` / `slice-done` pairs are
    `async-start` / `async-done` around a computation: data movement,
    and `comm` where the computation's root is a collective."""
    module = (
        _ld(1, b"jit_step")
        + _ld(3, _comp("async_slice", 1, [
            _instr("p", "parameter", 1), _instr("s", "slice", 2, [1])], 2))
        + _ld(3, _comp("async_ar", 2, [
            _instr("p", "parameter", 1),
            _instr("ar", "all-reduce", 2, [1])], 2))
        + _ld(3, _comp("main", 3, [
            _instr("x", "parameter", 1),
            _instr("slice-start.1", "async-start", 2, [1], [1]),
            _instr("slice-done.1", "async-done", 3, [2]),
            _instr("ar-start.1", "async-start", 4, [3], [2]),
            _instr("ar-update.1", "async-update", 5, [4]),
            _instr("ar-done.1", "async-done", 6, [5])], 6))
        + _vi(6, 3))
    buckets = {r["name"]: r["bucket"] for r in cost.instruction_costs(module)}
    assert buckets == {"x": "noop", "slice-start.1": "layout",
                       "slice-done.1": "layout", "ar-start.1": "comm",
                       "ar-update.1": "comm", "ar-done.1": "comm"}
    assert cost.HloModule(module).name == "jit_step"


# --------------------------------------------------------------------------
# whose work a scopeless instruction is (PR 49): `cost.DefUse`, the
# owner keys of every row, `source` of the `layout` rows
# --------------------------------------------------------------------------

F32, TUPLE = 11, 13
MUL, RELU = "jit(step)/jvp(mul:3)/dot_general", "jit(step)/jvp(relu:4)/max"
MUL_BWD = "jit(step)/transpose(jvp(mul:3))/dot_general"


def _array(*dims):
    """A serialized f32 ShapeProto (element_type=2 dimensions=3)."""
    return _vi(2, F32) + b"".join(_vi(3, d) for d in dims)


def _tuple_of(*shapes):
    return _vi(2, TUPLE) + b"".join(_ld(4, s) for s in shapes)


A, B = _array(8, 4), _array(4, 8)


def _op(name, opcode, iid, operands=(), called=(), scope="", shape=A,
        index=None, number=None, config=b""):
    """`_instr` with metadata.op_name (7: 2), shape (3), tuple_index
    (13), parameter_number (9) and backend_config (43)."""
    buf = _instr(name, opcode, iid, operands, called) + _ld(3, shape)
    if scope:
        buf += _ld(7, _ld(2, scope.encode()))
    if index is not None:
        buf += _vi(13, index)
    if number is not None:
        buf += _vi(9, number)
    if config:
        buf += _ld(43, config)
    return buf


def _module(*comps, entry, schedule=None):
    """A serialized HloModuleProto; `schedule`: {computation id:
    instruction ids in the order they run} (7: sequences=1, a map)."""
    buf = _ld(1, b"jit_step") + b"".join(_ld(3, c) for c in comps) \
        + _vi(6, entry)
    for cid, ids in (schedule or {}).items():
        packed = b"".join(_varint(i) for i in ids)
        buf += _ld(7, _ld(1, _vi(1, cid) + _ld(2, _ld(1, packed))))
    return buf


def _one_consumer():
    return _module(_comp("main", 1, [
        _op("w", "parameter", 1, number=2),
        _op("copy.1", "copy", 2, [1]),
        _op("fusion.2", "fusion", 3, [2],
            scope="jit(step)/jvp(attention/mul:3)/dot_general"),
        _op("tuple.3", "tuple", 4, [3])], 4), entry=1)


def _two_consumers(schedule):
    # the computation lists relu's fusion first; the schedule runs
    # mul's first
    return _module(_comp("main", 1, [
        _op("x", "parameter", 1, number=0),
        _op("copy.1", "copy", 2, [1]),
        _op("fusion.relu", "fusion", 3, [2], scope=RELU),
        _op("fusion.mul", "fusion", 4, [2], scope=MUL_BWD),
        _op("tuple.5", "tuple", 5, [3, 4])], 5), entry=1,
        schedule={1: [1, 2, 4, 3, 5]} if schedule else None)


def _ends_at_root():
    return _module(_comp("main", 1, [
        _op("x", "parameter", 1, number=0),
        _op("fusion.1", "fusion", 2, [1], scope=MUL),
        _op("bitcast.2", "bitcast", 3, [2]),
        _op("copy.3", "copy", 4, [3]),
        _op("tuple.4", "tuple", 5, [4])], 5), entry=1)


def _through_transparent():
    pair = _tuple_of(B, A, _array())
    return _module(_comp("main", 1, [
        _op("x", "parameter", 1, number=0),
        _op("w", "parameter", 2, number=1, shape=B),
        _op("tuple.1", "tuple", 3, [1, 2], shape=_tuple_of(A, B)),
        _op("opt-barrier.2", "opt-barrier", 4, [3],
            shape=_tuple_of(A, B)),
        _op("get-tuple-element.3", "get-tuple-element", 5, [4], index=1,
            shape=B),
        _op("bitcast.4", "bitcast", 6, [5], shape=B, scope=RELU),
        _op("copy-start.5", "copy-start", 7, [6], shape=pair),
        _op("copy-done.5", "copy-done", 8, [7], shape=B),
        _op("slice-start.6", "async-start", 9, [8],
            shape=_tuple_of(_tuple_of(B), _array(2, 8), _array())),
        _op("slice-done.6", "async-done", 10, [9], shape=_array(2, 8)),
        _op("fusion.7", "fusion", 11, [10], scope=MUL),
        _op("tuple.8", "tuple", 12, [11])], 12), entry=1)


def _loop():
    carry = _tuple_of(A, B)
    body = _comp("body", 2, [
        _op("p", "parameter", 1, number=0, shape=carry),
        _op("get-tuple-element.1", "get-tuple-element", 2, [1], index=0),
        _op("get-tuple-element.2", "get-tuple-element", 3, [1], index=1,
            shape=B),
        _op("copy.body", "copy", 4, [2]),
        _op("fusion.body", "fusion", 5, [4],
            scope="jit(step)/scan:5/while/body/mul:2/dot_general"),
        _op("copy.carried", "copy", 6, [3], shape=B),
        _op("tuple.3", "tuple", 7, [5, 6], shape=carry)], 7)
    cond = _comp("cond", 3, [_op("p", "parameter", 1, number=0,
                                 shape=carry)], 1)
    main = _comp("main", 1, [
        _op("x", "parameter", 1, number=0, shape=carry),
        _op("while.1", "while", 2, [1], [2, 3], shape=carry,
            scope="jit(step)/scan:5/while",
            config=b'{"known_trip_count":{"n":"4"}}')], 2)
    return _module(body, cond, main, entry=1)


def _branches():
    def branch(cid, scope):
        return _comp(f"branch{cid}", cid, [
            _op(f"p{cid}", "parameter", 1, number=0),
            _op(f"copy.b{cid}", "copy", 2, [1]),
            _op(f"fusion.b{cid}", "fusion", 3, [2], scope=scope)], 3)

    main = _comp("main", 1, [
        _op("pred", "parameter", 1, number=0, shape=_array()),
        _op("x", "parameter", 2, number=1),
        _op("conditional.1", "conditional", 3, [1, 2, 2], [2, 3],
            scope="jit(step)/moe:1/cond")], 3)
    return _module(branch(2, MUL), branch(3, RELU), main, entry=1)


def _nobody():
    return _module(_comp("main", 1, [
        _op("x", "parameter", 1, number=0),
        _op("copy.1", "copy", 2, [1]),
        _op("tuple.2", "tuple", 3, [2])], 3), entry=1)


def _owner(**want):
    return dict({"owner_via": "consumer", "owner_consumers": 1}, **want)


OWNER_CASES = {
    # a copy of a step input that one scoped instruction reads: its
    # op's, forward, and `state` with the parameter's number and shape
    "one_scoped_consumer": (_one_consumer, {"copy.1": _owner(
        owner="fusion.2", owner_op_type="mul", owner_phase="forward",
        owner_name_scope="attention", source="state", source_parameter=2, source_shape="f32[8,4]")}),
    # two: the first to RUN, by the schedule where there is one, by
    # the computation's own list where not; both are counted
    "two_consumers_by_the_schedule": (lambda: _two_consumers(True), {
        "copy.1": _owner(owner="fusion.mul", owner_op_type="mul",
                         owner_phase="backward", owner_consumers=2)}),
    "two_consumers_by_the_list": (lambda: _two_consumers(False), {
        "copy.1": _owner(owner="fusion.relu", owner_op_type="relu",
                         owner_consumers=2)}),
    # no consumer before the root: the nearest scoped one behind it
    "ends_at_the_root": (_ends_at_root, {
        "copy.3": dict(owner="fusion.1", owner_via="producer",
                       owner_op_type="mul", owner_consumers=0,
                       source="activation", source_parameter=None),
        "fusion.1": dict(owner="fusion.1", owner_via="scope"),
        "x": _owner(owner="fusion.1")}),
    # through tuple, opt-barrier, get-tuple-element, a SCOPED bitcast
    # and both asynchronous pairs; the chain back picks the tuple's
    # element that was asked for
    "through_the_transparent_ones": (_through_transparent, {
        "copy-done.5": _owner(owner="fusion.7", source="state",
                              source_parameter=1,
                              source_shape="f32[4,8]", shape="f32[4,8]"),
        "copy-start.5": _owner(owner="fusion.7", source="state",
                               shape="f32[4,8]"),
        "slice-start.6": _owner(owner="fusion.7", source="state",
                                shape="f32[2,8]", shape_bytes=64.0),
        "slice-done.6": _owner(owner="fusion.7", source="state",
                               source_parameter=1),
        "bitcast.4": dict(owner_via="scope", owner_op_type="relu"),
        "w": _owner(owner="fusion.7")}),
    # a counted loop's body is a computation of its own: its carry is
    # `carry`, and what is only written back has nobody in there
    "inside_a_counted_loop": (_loop, {
        "copy.body": _owner(owner="fusion.body", owner_op_type="mul",
                            source="carry",
                            source_shape="f32[8,4]", loop_of="while.1",
                            trips=4),
        "copy.carried": dict(owner_via="none", source="carry",
                             source_shape="f32[4,8]",
                             source_parameter=None),
        "while.1": dict(owner_via="scope", owner_op_type="scan")}),
    # every branch of a conditional has its own map
    "both_branches_of_a_conditional": (_branches, {
        "copy.b2": _owner(owner="fusion.b2", owner_op_type="mul",
                          source="activation", branch_of="conditional.1"),
        "copy.b3": _owner(owner="fusion.b3", owner_op_type="relu",
                          source="activation", source_shape=None),
        "x": _owner(owner="conditional.1", owner_op_type="moe")}),
    "nobody": (_nobody, {"copy.1": dict(
        owner=None, owner_via="none", owner_op_type=None,
        owner_phase="other", owner_name_scope="", owner_consumers=0,
        source="state", source_parameter=0)}),
}


@pytest.mark.parametrize("case", sorted(OWNER_CASES))
def test_a_scopeless_instruction_is_handed_to_the_op_it_works_for(case):
    build, expect = OWNER_CASES[case]
    rows = {r["name"]: r
            for r in cost.instruction_costs(build(), every_branch=True)}
    for name, want in expect.items():
        for key, value in want.items():
            assert rows[name][key] == value, (name, key, rows[name][key])
    for r in rows.values():
        assert (r["source"] is not None) == (r["bucket"] == "layout")
        assert (r["owner"] is None) == (r["owner_via"] == "none")
        assert r["owner_via"] in cost.OWNER_VIAS + ("none",)


def test_owner_keys_of_compiled_programs():
    """The same on what XLA:CPU compiles: a step input is its first
    scoped reader's, scopeless work behind a scoped product with no
    consumer before the root is the product's, and a transposed step
    input is `state` with its parameter."""
    def step(x, w):
        with jax.named_scope("mul:1"):
            y = x @ w
        return jnp.tanh(y), jnp.transpose(w).copy()

    proto = cost.compiled_hlo_proto(
        jax.jit(step).lower(jnp.ones((64, 32)),
                            jnp.ones((32, 48))).compile())
    rows = cost.instruction_costs(proto)
    product, = [r for r in rows if r["op_type"] == "mul"]
    assert product["owner_via"] == "scope"
    for r in rows:
        if r["opcode"] == "parameter":
            assert (r["owner"], r["owner_via"]) == (product["name"],
                                                    "consumer")
    after = [r for r in rows if product["name"] in r["operands"]
             and r["op_type"] is None and r["bucket"] == "elementwise"]
    assert after and all((r["owner_op_type"], r["owner_via"])
                         == ("mul", "producer") for r in after)
    of_w = [r for r in rows if r["bucket"] == "layout"]
    assert of_w and all(
        (r["source"], r["source_parameter"], r["source_shape"],
         r["owner_via"]) == ("state", 1, "f32[32,48]", "none")
        for r in of_w)
    # the old readers of the same rows, re-found on them
    assert {r["name"] for r in cost.copyish_instructions(proto)} \
        == {r["name"] for r in rows if r["copyish"]} \
        >= {r["name"] for r in of_w}
    assert cost.flash_boundary_layout(proto) == []


def test_owner_keys_around_and_inside_a_compiled_scan():
    """A `lax.scan` under a fluid scope: a copy of a step input on its
    way into the loop is the loop's op's and `state`; the body's rows
    carry the loop and its trips, own themselves by their scopes, and
    no `layout` row in there reads `state`."""
    def step(x, w):
        def body(c, _):
            with jax.named_scope("mul:2"):
                return jnp.tanh(jnp.transpose(c) @ w), None
        with jax.named_scope("scan:1"):
            return jax.lax.scan(body, x, None, length=5)[0]

    compiled = jax.jit(step).lower(jnp.ones((16, 16)),
                                   jnp.ones((16, 16))).compile()
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    loop, = [r for r in rows if r["opcode"] == "while"]
    assert (loop["owner_op_type"], loop["owner_via"]) == ("scan", "scope")
    for r in rows:
        if r["loop_of"] is None and r["source"] == "state":
            assert (r["owner"], r["owner_via"]) == (loop["name"],
                                                    "consumer")
    inside = [r for r in rows if r["loop_of"]]
    assert inside and all(r["trips"] == 5 for r in inside)
    assert not [r for r in inside if r["source"] == "state"]
    scoped = [r for r in inside if r["op_type"] == "mul"]
    assert scoped and all(r["owner_via"] == "scope" for r in scoped)
    assert not [r for r in inside if r["owner_via"] == "none"]


def _owner_events():
    """`program_map` of `_through_transparent` and one step's events:
    the copy pair, the slice pair, the product, one unknown."""
    from paddle_tpu.observe import trace

    step = "jit_step(7)"
    ops = [("%copy-start.5 = (f32[4,8]) copy-start(%bitcast.4)", 1.0, 0.1),
           ("%copy-done.5 = f32[4,8] copy-done(%copy-start.5)", 1.1, 0.3),
           ("%slice-done.6 = f32[2,8] async-done(%slice-start.6)",
            1.4, 0.2),
           ("%fusion.7 = f32[8,4] fusion(%slice-done.6)", 1.6, 1.0),
           ("%fusion.999 = f32[4]{0} fusion(f32[4]{0} %p)", 2.6, 0.4)]
    programs = {step: trace.program_map(_through_transparent())}
    return ops, [(step, 0.9, 3.0)], programs


def test_join_events_carries_the_owner_keys():
    from paddle_tpu.observe import trace

    ops, modules, programs = _owner_events()
    rows = {r["instruction"]: r
            for r in trace.join_events(ops, modules, programs)}
    assert set(trace.OWNER_KEYS) <= set(rows["copy-done.5"])
    done = rows["copy-done.5"]
    assert (done["op_type"], done["owner_op_type"], done["owner_via"],
            done["owner_phase"], done["owner"]) == (
        None, "mul", "consumer", "forward", "fusion.7")
    assert (done["bucket"], done["source"], done["source_parameter"],
            done["source_shape"], done["shape"]) == (
        "layout", "state", 1, "f32[4,8]", "f32[4,8]")
    assert rows["slice-done.6"]["shape_bytes"] == 64.0
    assert rows["fusion.7"]["owner_via"] == "scope"
    # an instruction that is in no map has nobody
    lost = rows["fusion.999"]
    assert (lost["joined"], lost["owner_via"], lost["owner_op_type"],
            lost["source"]) == (False, "none", None, None)
    # self times still sum to the busy union
    assert sum(r["self_s"] for r in rows.values()) == pytest.approx(2.0)
    # a map from before the owner keys: an instruction owns itself by
    # its scope, or nobody does
    old = {"jit_step(7)": {
        "fusion.7": {"op_name": MUL, "bucket": "matmul", "flops": 1.0,
                     "bytes": 1.0},
        "copy-done.5": {"op_name": "", "bucket": "layout", "flops": 0.0,
                        "bytes": 1.0}}}
    rows = {r["instruction"]: r
            for r in trace.join_events(ops, modules, old)}
    assert rows["fusion.7"]["owner_via"] == "scope"
    assert rows["fusion.7"]["owner_op_type"] == "mul"
    assert rows["copy-done.5"]["owner_via"] == "none"


def test_the_profiler_report_sums_by_owner_and_to_the_busy_time(
        monkeypatch):
    """`profiler.profiler(sorted_key=...)` prints `format_op_table`:
    a scopeless copy's time is its owner's there, `[unattributed]` is
    what has none, and the rows still sum to the busy time."""
    from paddle_tpu.observe import trace

    ops, modules, programs = _owner_events()
    monkeypatch.setattr(
        trace, "op_rows",
        lambda profile_dir, windows=None, chips=None:
        trace.join_events(ops, modules, programs))
    table = {r["op_type"]: r for r in trace.op_time_table("nowhere")}
    assert set(table) == {"mul", "[unattributed]"}
    assert table["mul"]["total_ms"] == pytest.approx(1600.0)
    assert table["mul"]["calls"] == 4
    assert table["[unattributed]"]["total_ms"] == pytest.approx(400.0)
    assert sum(r["total_ms"] for r in table.values()) == pytest.approx(
        2000.0)
    assert sum(r["ratio"] for r in table.values()) == pytest.approx(1.0)
    report = trace.format_op_table("nowhere", sorted_key="total")
    assert report.index("mul") < report.index("[unattributed]")
