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
