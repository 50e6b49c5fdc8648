"""The chip's compiler on the other kernel families, each alone: latent
attention (`ops/pallas/flash_mla.py`) at `joyai-8k`'s shape, the chunked
delta-rule scan (`ops/pallas/gated_delta.py`) and grouped flash
attention at d_head 256 at `qwen3next-16k`'s, the lane-decayed delta rule
(`ops/pallas/channel_delta.py`) at `kimilinear-8k`'s, a head's lane
statistic (`ops/pallas/head_norm.py`) at both, the scalar-a-head scan
(`ops/pallas/ssd_scan.py`) and grouped flash attention under a scale of
2^-6 at `granite4h-8k`'s, the short convolution
(`ops/pallas/short_conv.py`) at that cell's and `lfm2-8k`'s, the fused
vocabulary cross-entropy, paged attention, the fused LSTM recurrence;
and the cost table over whole steps it compiled (every Mosaic kernel has
a registered cost, the TPU's dots are matmul rows).  tests/chip_compile.py
says why and how, and why these share a file.
"""

from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import force_mosaic_lowering
from chip_compile import (BF16, F32, I32, I8, _compile, _compile_args,
                          _kernels, _lower_args, _precision, _sites)


def _latent_attention_lowered(one_chip, dtype):
    """The gradient of the `latent_attention` op at `joyai-8k`'s shape,
    lowered for the described chip, with what needs no compile
    asserted: the backward pass the trace took is the single kernel
    (the counters), and the step lowers to two kernels by name."""
    from paddle_tpu.core.registry import OpContext, get_op_impl

    n, t, heads = 1, 8192, 32
    impl = get_op_impl("latent_attention")

    def loss(q_nope, q_rope, k_nope, k_rope, v):
        with jax.named_scope("latent_attention/latent_attention:9"):
            o = impl(OpContext(jax.random.PRNGKey(0), 0),
                     {"QNope": [q_nope], "QRope": [q_rope],
                      "KNope": [k_nope], "KRope": [k_rope], "V": [v]},
                     {"n_head": heads})["Out"][0]
        return jnp.sum(o.astype(F32))

    widths = (heads * 128, heads * 64, heads * 128, 64, heads * 128)
    args = [jax.ShapeDtypeStruct((n, t, w), dtype, sharding=one_chip)
            for w in widths]
    lowered, took = _lower_args(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))), *args,
        precision=_precision(dtype))
    assert (took["flash_mla_backward_fused"],
            took["flash_mla_backward_split"]) == (1, 0)
    assert _sites(lowered) == {"flash_mla_fwd": 1, "flash_mla_dkv": 1}
    return lowered


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_latent_attention_kernels_at_the_published_shapes_by_their_trace(
        one_chip, dtype):
    """Tier-1's stand-in for the two cases below, which are `slow`."""
    _latent_attention_lowered(one_chip, dtype)


# slow, 63 s and 162 s.  The driver's chip runs of `joyai-8k` and
# `kimilinear-8k` guard the bfloat16 case (a kernel Mosaic refuses is a
# failed cell); float32 at "highest" is `benchmarks/joyai_parity.py`'s,
# which no driver's run reaches: nothing on the chip guards it between
# runs of `-m slow -k latent`.  The stand-in above holds the traces
@pytest.mark.slow
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_latent_attention_kernels_at_the_published_shapes(one_chip, dtype):
    """What `joyai-8k`'s step hands the chip's compiler that no other
    cell does (1 x 8192 tokens, 32 heads of 128 unrotated + 64 rotary
    lanes, values of 128, ONE rotary key head): the kernels of
    `ops/pallas/flash_mla.py` through the `latent_attention` op, in the
    cell's bfloat16 and in the parity script's float32 at "highest".  A
    head's 64 rotary lanes are half a tile: the kernels block heads in
    pairs, take the rotary key as a (rows, 64) block of the whole minor
    dim, copy it across a tile's halves and fold its gradient's halves
    in VMEM.  The rotary key and its gradient stay (N, T, 64) and v
    stays 128 a head: nothing 32 x 192 wide exists.  At this length
    the backward pass is ONE kernel, `flash_mla_dkv` grown by dq's two
    dots, whose 17 MB of float32 accumulators (dq of a pair's whole
    sequence, the rotary key's gradient) Mosaic must take in VMEM in
    both dtypes; no partial of dq (32 heads x 192 = 6144 wide) reaches
    HBM."""
    from paddle_tpu.observe import cost

    n, t, heads = 1, 8192, 32
    compiled = _latent_attention_lowered(one_chip, dtype).compile()
    proto = cost.compiled_hlo_proto(compiled)
    rows = cost.instruction_costs(proto)
    assert sorted(r["kernel"] for r in rows if r["kernel"]) == [
        "flash_mla_dkv", "flash_mla_fwd"]
    assert {r["op_type"] for r in rows if r["kernel"]} == {
        "latent_attention"}
    totals = cost.total_costs(proto)
    assert totals["custom_calls"] == totals["pallas_matched"] == 2
    # dense-equivalent: scores 192 and values 128 forward; dv, dp 128
    # and dk, dq 192 backward, 2 FLOP a lane
    scores = n * heads * t * t
    assert totals["pallas_flops"] >= 2 * (320 + 640) * scores
    text = compiled.as_text()
    assert f"[{n},{t},{heads * 192}]" not in text
    if dtype == BF16:
        assert f"bf16[{n},{t},64]" in text      # the rotary key's gradient


def _segment_attention_lowered(one_chip, dtype):
    """`kimivl-8k`'s tower attention, forward and backward through the
    `segment_attention` op with its `Positions`, traced and lowered for
    the described chip, nothing compiled: 24576 packed rows, 16 heads of
    72 lanes (laid out at 128 for the kernels and turned by
    `ops/pallas/head_lanes.py`'s two), segments of at most 4096 rows."""
    from paddle_tpu.core.registry import OpContext, get_op_impl

    n, p, heads, d = 1, 24576, 16, 72
    op = get_op_impl("segment_attention")

    def loss(q, k, v, seg, yx):
        o = op(OpContext(None, 0), {"Q": [q], "K": [k], "V": [v],
                                    "SegmentIds": [seg], "Positions": [yx]},
               {"n_head": heads, "max_segment_rows": 4096,
                "theta": 10000.0})["Out"][0]
        return jnp.sum(o.astype(F32))

    x = jax.ShapeDtypeStruct((n, p, heads * d), dtype, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((n, p), I32, sharding=one_chip)
    yx = jax.ShapeDtypeStruct((n, p, 2), I32, sharding=one_chip)
    lowered, took = _lower_args(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                                x, x, x, seg, yx, precision=_precision(dtype))
    assert (took["flash_segment_calls"], took["flash_segment_xla_calls"],
            took["flash_segment_tiles_total"]) == (1, 0, 16 * 24 * 24)
    assert (took["flash_segment_lane_kernel_calls"],
            took["flash_segment_lane_xla_calls"]) == (1, 0)
    # call sites: q, k, v to the kernels' lanes (one jitted pass, which
    # the backward rule calls again) and do, o; o and three gradients back
    assert _sites(lowered) == {"flash_segment_fwd": 1,
                               "flash_segment_bwd": 1,
                               "head_lanes_to_tiles": 2,
                               "head_lanes_from_tiles": 2}
    return lowered


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_segment_attention_kernels_at_the_cells_shape_by_their_trace(
        one_chip, dtype):
    """Tier-1's stand-in for the float32 case below, which is `slow`;
    the bfloat16 case compiles here (12-31 s): the list of visits is a
    scalar-prefetched table made from a DEVICE array, which only
    Mosaic's own compile proves."""
    lowered = _segment_attention_lowered(one_chip, dtype)
    if dtype == BF16:
        text = lowered.compile().as_text()
        # the two attention kernels and the five passes around them
        assert _kernels(text) == 7
        # heads of 72 lanes meet the kernels at 128: q, k, v, o and
        # their gradients are (1, 24576, 2048) there, and no view of a
        # head's 72 lanes or of its 36 pairs is made on the way
        assert "bf16[1,24576,2048]" in text
        assert "[1,24576,16,72]" not in text
        assert "[1,24576,16,36,2]" not in text


# slow, 26 s.  float32 at "highest" is `benchmarks/kimi_vl_parity.py`'s,
# which no driver's run reaches: nothing on the chip guards it between
# runs of `-m slow -k segment_attention`
@pytest.mark.slow
def test_segment_attention_kernels_in_float32_at_the_cells_shape(one_chip):
    """The parity script's float32 run at tiles of 1024 x 1024: float32
    operand tiles, four float32 score blocks and 12.6 MB of dq under
    the limit the backward kernel asks for."""
    text = _segment_attention_lowered(one_chip, F32).compile().as_text()
    assert _kernels(text) == 7


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_gated_delta_scan_kernels_at_the_published_shapes(one_chip, dtype):
    """What `qwen3next-16k`'s step hands the chip's compiler that no
    other cell does, first half: the `gated_delta_rule` op at 1 x 16384
    positions, 16 key and 32 value heads of 128, in the cell's bfloat16
    and in the parity script's float32 at "highest".  Five Mosaic
    kernels under a gradient: the chunk-local part's
    `gated_delta_inverse` (which writes (I + A)^-1, two heads a float32
    tile: what a recompute segment keeps), `gated_delta_operands_fwd`
    (which reads it) and `gated_delta_operands_bwd`, each a grid of 16
    key heads x 32 blocks of 8 chunks; the forward rule's
    `gated_delta_fwd` (which also writes the 256 chunk-entry states a
    head) and `gated_delta_bwd`, each a grid of 32 heads x 32 blocks
    carrying a (128, 128) float32 state in VMEM scratch.  Since PR 72
    the kernels address the op's own arrays (QKV in, Out (N, T, Hv x
    128) out, dOut in by lane block; dQKV out, left in HBM and written
    by the backward kernel's own async copies, three a grid step, under
    DMA semaphores): nothing of XLA's stands at their boundary."""
    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe import cost
    from paddle_tpu.observe.monitoring import runtime_stats

    n, t, hk, hv, d = 1, 16384, 16, 32, 128
    impl = get_op_impl("gated_delta_rule")

    def loss(qkv, ba, a_log, dt_bias):
        with jax.named_scope("linear_attention/gated_delta_rule:9"):
            o = impl(OpContext(jax.random.PRNGKey(0), 0),
                     {"QKV": [qkv], "BA": [ba], "ALog": [a_log],
                      "DtBias": [dt_bias]},
                     {"n_key_head": hk, "n_value_head": hv, "key_dim": d,
                      "value_dim": d})["Out"][0]
        return jnp.sum(o.astype(F32))

    args = [jax.ShapeDtypeStruct(shape, kind, sharding=one_chip)
            for shape, kind in (((n, t, (2 * hk + hv) * d), dtype),
                                ((n, t, 2 * hv), dtype), ((hv,), F32),
                                ((hv,), F32))]
    prec = "default" if dtype == BF16 else "highest"
    before = runtime_stats.snapshot()
    with force_mosaic_lowering(), jax.default_matmul_precision(prec):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))) \
            .lower(*args).compile()
    took = runtime_stats.delta(before)
    # the forward rule's call and the backward's: 256 chunks x 32 heads
    for kind in ("gated_delta", "gated_delta_operand"):
        assert (took[f"{kind}_calls"], took[f"{kind}_chunks"]) == (
            2, 2 * 256 * 32), kind
    assert took["gated_delta_inverse_calls"] == 1
    # all four of them on the op's own arrays
    assert took["gated_delta_flat_calls"] == 4
    proto = cost.compiled_hlo_proto(compiled)
    rows = cost.instruction_costs(proto)
    # q's and k's l2norm is the chunk-local kernels' own since PR 69
    # (they read QKV as it lies and return the raw lanes' gradient): no
    # head-statistic pass of `ops/pallas/head_norm.py` (4 calls, 4 * t
    # rows before), and q and k inside QKV count as their lanes' bytes
    assert (took["head_norm_calls"], took["head_norm_rows"]) == (0, 0)
    assert sorted(r["kernel"] for r in rows if r["kernel"]) == [
        "gated_delta_bwd", "gated_delta_fwd", "gated_delta_inverse",
        "gated_delta_operands_bwd", "gated_delta_operands_fwd"]
    assert {r["op_type"] for r in rows if r["kernel"]} == {
        "gated_delta_rule"}
    totals = cost.total_costs(proto)
    assert totals["custom_calls"] == totals["pallas_matched"] == 5
    size = 2 if dtype == BF16 else 4
    by = {r["kernel"]: r["bytes"] for r in rows if r["kernel"]}
    heads, tiles = n * t * d * size, n * hk * 256 * 8 * 128 * 4
    assert by["gated_delta_inverse"] == (
        hk * heads + tiles + n * hk * t * 128 * 4)
    assert by["gated_delta_operands_fwd"] == (
        (2 * hk + hv) * heads + tiles + n * hk * t * 128 * 4
        + 4 * hv * heads + hv * n * t * 64 * size)
    assert by["gated_delta_operands_bwd"] == (
        by["gated_delta_operands_fwd"] + (2 * hk + hv) * heads + tiles)
    # no float32 view of q or k a head: nothing for the chip to re-lay
    text = compiled.as_text()
    assert f"f32[{n},{t},{hk},{d}]" not in text
    # and no pass of XLA's over an activation beside the kernels (PR
    # 72): no head-major o to transpose, no v cut out of QKV, no three
    # gradients padded to QKV's width and added (which ran in float32)
    kind = "bf16" if dtype == BF16 else "f32"
    made = [line.split(" = ", 1)[1] for line in text.splitlines()
            if " = " in line and "parameter(" not in line
            and "get-tuple-element(" not in line]

    def xla_makes(*dims):       # what XLA writes of that shape
        shape = "[" + ",".join(map(str, dims)) + "]"
        return [m for m in made
                if m.startswith((kind + shape, "f32" + shape))]

    assert not xla_makes(n, t, hv, d) and not xla_makes(hv, t, d)
    assert not [m for m in xla_makes(n, t, hv * d)
                if " slice(" in m or " copy(" in m]
    assert not xla_makes(n, t, (2 * hk + hv) * d)
    # dQKV is the backward kernel's own result
    assert f"({kind}[{n},{t},{(2 * hk + hv) * d}]" in text
    # no scan reader may take the chunk-local kernels for scan kernels:
    # they match by prefix (`benchmarks/kernel_counts.py kernel_ms_per_step`)
    scan = [r for r in rows if (r["kernel"] or "").startswith(
        ("gated_delta_fwd", "gated_delta_bwd"))]
    chunk = (3 + 6) * 2 * 64 * d * d + (1 + 2) * 2 * 64 * 64 * d
    assert sum(r["flops"] for r in scan) == 256 * 32 * chunk
    # the chunk-local part: K K^T, Q K^T a key head; W, U, the
    # substitution, and the backward's eight products and three (C, C)
    # ones a value head
    local = 256 * (16 * 2 * 2 * 64 * 64 * d + 32 * (
        (2 + 8) * 2 * 64 * 64 * d + 2 * 64 ** 3 / 3 + 3 * 2 * 64 ** 3))
    assert totals["pallas_flops"] == pytest.approx(
        256 * 32 * chunk + local, rel=1e-9)
    # the states that enter the chunks, in the operands' dtype
    assert f"{kind}[{hv},{256 * d},{d}]" in text


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_channel_delta_kernels_at_the_published_shapes(one_chip, dtype):
    """What `kimilinear-8k`'s step hands the chip's compiler that no
    other cell does: the `channel_delta_rule` op at 1 x 8192 positions,
    32 heads of 128 x 128 under a decay a key lane, in the cell's
    bfloat16 and in the parity script's float32 at "highest".  Five
    Mosaic kernels under a gradient: the chunk-local part's
    `channel_delta_inverse` (which writes (I + A)^-1, two heads a float32
    tile, and P: what a recompute segment keeps), `_operands_fwd` (which
    reads the inverse) and `_operands_bwd`, each a grid of 16 head pairs
    x 32 blocks of 4 chunks; the forward rule's `channel_delta_fwd`
    (which also writes the 128 chunk-entry states a head, transposed) and
    `channel_delta_bwd`, each a grid of 32 heads x 16 blocks carrying a
    (128, 128) float32 state in VMEM scratch."""
    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe import cost
    from paddle_tpu.observe.monitoring import runtime_stats

    n, t, h, d = 1, 8192, 32, 128
    impl = get_op_impl("channel_delta_rule")

    def loss(qkv, gate, beta, a_log, dt_bias):
        with jax.named_scope("channel_delta_attention/channel_delta_rule:9"):
            o = impl(OpContext(jax.random.PRNGKey(0), 0),
                     {"QKV": [qkv], "Gate": [gate], "Beta": [beta],
                      "ALog": [a_log], "DtBias": [dt_bias]},
                     {"n_head": h, "key_dim": d, "value_dim": d})["Out"][0]
        return jnp.sum(o.astype(F32))

    args = [jax.ShapeDtypeStruct(shape, kind, sharding=one_chip)
            for shape, kind in (((n, t, 3 * h * d), dtype),
                                ((n, t, h * d), dtype), ((n, t, h), dtype),
                                ((h,), F32), ((h * d,), F32))]
    prec = "default" if dtype == BF16 else "highest"
    before = runtime_stats.snapshot()
    with force_mosaic_lowering(), jax.default_matmul_precision(prec):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))) \
            .lower(*args).compile()
    took = runtime_stats.delta(before)
    # the forward rule's call and the backward's: 128 chunks x 32 heads;
    # the inverse kernel is a third chunk-local call
    assert (took["channel_delta_calls"], took["channel_delta_chunks"]) == (
        2, 2 * 128 * 32)
    assert (took["channel_delta_operand_calls"],
            took["channel_delta_operand_chunks"]) == (3, 3 * 128 * 32)
    assert took["gated_delta_calls"] == 0
    proto = cost.compiled_hlo_proto(compiled)
    rows = cost.instruction_costs(proto)
    # q's and k's l2norm is the chunk-local kernels' own since PR 69
    # (they read QKV as it lies; kb = beta k is made of the raw k and
    # takes k's 1 / norm there): no head-statistic pass of
    # `ops/pallas/head_norm.py` (4 calls, 4 * t rows before), and q and
    # k inside QKV count as their lanes' bytes
    assert (took["head_norm_calls"], took["head_norm_rows"]) == (0, 0)
    assert sorted(r["kernel"] for r in rows if r["kernel"]) == [
        "channel_delta_bwd", "channel_delta_fwd", "channel_delta_inverse",
        "channel_delta_operands_bwd", "channel_delta_operands_fwd"]
    assert {r["op_type"] for r in rows if r["kernel"]} == {
        "channel_delta_rule"}
    totals = cost.total_costs(proto)
    assert totals["custom_calls"] == totals["pallas_matched"] == 5
    size = 2 if dtype == BF16 else 4
    by = {r["kernel"]: r["bytes"] for r in rows if r["kernel"]}
    lanes = n * t * h * d
    assert by["channel_delta_inverse"] == (
        3 * lanes * size + lanes * 4            # q, k, kb and g
        + n * h // 2 * t * 128 * 4 + n * h * t * 64 * size)
    assert by["channel_delta_operands_fwd"] == (
        4 * lanes * size + lanes * 4 + n * h // 2 * t * 128 * 4
        + 4 * lanes * size)
    scan = (3 + 6) * 2 * 64 * d * d + (1 + 2) * 2 * 64 * 64 * d
    local = 64 * ((2 + 2 + 8) * 2 * 64 * d + 2 * 64 * 64 / 3
                  + 2 * 2 * 64 * 64)
    assert totals["pallas_flops"] == pytest.approx(
        128 * 32 * (scan + local), rel=1e-9)
    # the states that enter the chunks, in the operands' dtype, and the
    # one float32 tensor a lane: g (and its gradient)
    kind = "bf16" if dtype == BF16 else "f32"
    assert f"{kind}[{h},{128 * d},{d}]" in compiled.as_text()


@pytest.mark.parametrize("form", ["silu", "gated"])
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_short_conv_kernels_at_the_published_shapes(one_chip, dtype, form):
    """The `short_conv` op and its gradient at the two cells' shapes:
    `qwen3next-16k`'s (1, 16384, 8192) x 4 taps under a SiLU and
    `lfm2-8k`'s gated (1, 8192, 3 x 2048) x 3 taps, in the cells'
    bfloat16 and the parity scripts' float32.  The shape rule takes
    both, so the output with its gradient is TWO Mosaic kernels,
    `short_conv_fwd` and `short_conv_bwd` (which recomputes the
    convolution from X); each has a registered cost in bytes and no
    FLOP, and sits under the op's scope.  The gated
    form's full-width tiles (256 rows x 6144 lanes in, as many out)
    are what claims VMEM past Mosaic's default 16 MiB."""
    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe import cost
    from paddle_tpu.observe.monitoring import runtime_stats

    t, d, taps = (16384, 8192, 4) if form == "silu" else (8192, 2048, 3)
    wide = 1 if form == "silu" else 3
    impl = get_op_impl("short_conv")

    def both(x, w, ct):
        def fn(x, w):
            with jax.named_scope("linear_attention/short_conv:7"):
                return impl(OpContext(jax.random.PRNGKey(0), 0),
                            {"X": [x], "Filter": [w]},
                            {"activation": "silu"} if form == "silu"
                            else {})["Out"][0]

        o, vjp = jax.vjp(fn, x, w)
        return o, vjp(ct)

    before = runtime_stats.snapshot()
    compiled = _compile_args(
        jax.jit(both),
        jax.ShapeDtypeStruct((1, t, wide * d), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((d, taps), F32, sharding=one_chip),
        jax.ShapeDtypeStruct((1, t, d), dtype, sharding=one_chip))
    took = runtime_stats.delta(before)
    assert (took["short_convs_kernel"], took["short_convs_xla"]) == (1, 0)
    proto = cost.compiled_hlo_proto(compiled)
    rows = cost.instruction_costs(proto)
    assert sorted(r["kernel"] for r in rows if r["kernel"]) == [
        "short_conv_bwd", "short_conv_fwd"]
    assert {r["op_type"] for r in rows if r["op_type"]} == {"short_conv"}
    assert not any(r["bucket"] in ("matmul", "conv") for r in rows)
    totals = cost.total_costs(proto)
    assert totals["custom_calls"] == totals["pallas_matched"] == 2
    assert totals["pallas_flops"] == 0
    # X and Out forward; X, dOut and dX backward; once each
    item = 2 if dtype == BF16 else 4
    tile = t * d * item
    by = {r["kernel"]: r["bytes"] for r in rows if r["kernel"]}
    assert by["short_conv_fwd"] == (wide + 1) * tile + d * taps * 4
    assert by["short_conv_bwd"] == (2 * wide + 1) * tile + (
        1 + 8) * d * taps * 4
    # nothing float32 as large as X is written between the kernels
    assert compiled.memory_analysis().temp_size_in_bytes < tile // 4


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_selective_scan_kernels_at_the_published_shapes(one_chip, dtype):
    """The `selective_scan` op and its seven gradients at `phi4flash-8k`'s
    shape, (1, 8192, 5120) channels x 16 states, in the cell's bfloat16
    and the parity script's float32, and the biased SiLU convolution
    that feeds it (5120 channels x 4 taps + a bias): the shape rule
    takes both, so the scan with its gradient is TWO Mosaic kernels,
    `selective_scan_fwd` and `selective_scan_bwd` (which rebuilds a
    chunk's states from its entry state in VMEM), and the convolution
    two more; each has a registered cost, none of it on the MXU, and
    sits under its op's scope.  The backward kernel's state scratch
    ((256 + 1) x 16 rows x 256 lanes float32) and its tiles are what
    claim VMEM past Mosaic's default 16 MiB."""
    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe import cost
    from paddle_tpu.observe.monitoring import runtime_stats

    t, d, s, taps = 8192, 5120, 16, 4
    scan, conv = get_op_impl("selective_scan"), get_op_impl("short_conv")
    slots = ("Delta", "ALog", "B", "C", "D", "DeltaBias")

    def both(x, w, bias, ct, *rest):
        def fn(x, w, bias, *rest):
            ctx = OpContext(jax.random.PRNGKey(0), 0)
            with jax.named_scope("state_space/short_conv:3"):
                u = conv(ctx, {"X": [x], "Filter": [w], "Bias": [bias]},
                         {"activation": "silu"})["Out"][0]
            with jax.named_scope("state_space/selective_scan:9"):
                return scan(ctx, dict({"U": [u]}, **{
                    k: [v] for k, v in zip(slots, rest)}), {})["Out"][0]

        o, vjp = jax.vjp(fn, x, w, bias, *rest)
        return o, vjp(ct)

    def spec(shape, kind):
        return jax.ShapeDtypeStruct(shape, kind, sharding=one_chip)

    wide, narrow = spec((1, t, d), dtype), spec((1, t, s), dtype)
    before = runtime_stats.snapshot()
    compiled = _compile_args(
        jax.jit(both), wide, spec((d, taps), F32), spec((d,), F32), wide,
        wide, spec((d, s), F32), narrow, narrow, spec((d,), F32),
        spec((d,), F32))
    took = runtime_stats.delta(before)
    assert (took["selective_scans_kernel"], took["selective_scans_xla"],
            took["selective_scan_chunks"]) == (2, 0, 2 * 32)
    assert (took["short_convs_kernel"], took["short_convs_xla"],
            took["short_conv_bias_calls"]) == (1, 0, 1)
    proto = cost.compiled_hlo_proto(compiled)
    rows = cost.instruction_costs(proto)
    assert sorted(r["kernel"] for r in rows if r["kernel"]) == [
        "selective_scan_bwd", "selective_scan_fwd", "short_conv_bwd",
        "short_conv_fwd"]
    by_kernel = {r["kernel"]: r["op_type"] for r in rows if r["kernel"]}
    assert by_kernel["selective_scan_fwd"] == "selective_scan"
    assert by_kernel["short_conv_bwd"] == "short_conv"
    assert not any(r["bucket"] in ("matmul", "conv") for r in rows)
    totals = cost.total_costs(proto)
    assert totals["custom_calls"] == totals["pallas_matched"] == 4
    flops = {r["kernel"]: r["flops"] for r in rows if r["kernel"]}
    assert flops["selective_scan_fwd"] == 8 * t * d * s
    assert flops["selective_scan_bwd"] == 24 * t * d * s
    # the states that enter the 32 chunks, float32: 10.5 MB
    assert f"f32[1,32,{s},{d}]" in compiled.as_text()


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_ssd_scan_kernels_at_the_published_shapes(one_chip, dtype):
    """The `ssd_scan` op and its seven gradients at `granite4h-8k`'s
    shape, (1, 8192) positions x 64 heads of 64 x 128 states in chunks
    of 256, in the cell's bfloat16 and the parity script's float32, and
    the biased SiLU convolution that feeds it (4352 = 34 x 128 channels
    x 4 taps + a bias: x, B and C together), whose output IS the scan's
    operand (PR 70: xBC whole under three block specs, d xBC one array
    from a chunk-wide output block with a dynamic 128-aligned lane
    offset): the shape rule takes both,
    so the scan with its gradient is TWO Mosaic kernels, `ssd_scan_fwd`
    and `ssd_scan_bwd` (which rebuilds a chunk's masks in VMEM and
    transposes its G there), and the convolution two more; each has a
    registered cost, the scan's the FLOP the chunked form executes, and
    sits under its op's scope.  No (chunks, heads, 256, 256) decay mask
    is a tensor of the compiled text: what leaves the kernels float32
    is the entry states (67 MB) and a (position, head)'s scalars.  No x
    is cut out of xBC and no d xBC glued together: nothing of x's width
    or wider is a slice or a concatenation there."""
    import re

    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe import cost
    from paddle_tpu.observe.monitoring import runtime_stats
    from paddle_tpu.ops.pallas import ssd_scan as kernels

    t, heads, p, s, taps = 8192, 64, 64, 128, 4
    d, wide = heads * p, heads * p + 2 * s
    scan, conv = get_op_impl("ssd_scan"), get_op_impl("short_conv")

    def both(xbc, w, bias, ct, dt, a_log, skip, dt_bias):
        def fn(xbc, w, bias, dt, a_log, skip, dt_bias):
            ctx = OpContext(jax.random.PRNGKey(0), 0)
            with jax.named_scope("state_space_duality/short_conv:3"):
                u = conv(ctx, {"X": [xbc], "Filter": [w], "Bias": [bias]},
                         {"activation": "silu"})["Out"][0]
            with jax.named_scope("state_space_duality/ssd_scan:9"):
                return scan(ctx, {
                    "XBC": [u], "Dt": [dt], "ALog": [a_log], "D": [skip],
                    "DtBias": [dt_bias]}, {"n_groups": 1, "d_state": s,
                                           "chunk_size": 256})["Out"][0]

        o, vjp = jax.vjp(fn, xbc, w, bias, dt, a_log, skip, dt_bias)
        return o, vjp(ct)

    def spec(shape, kind):
        return jax.ShapeDtypeStruct(shape, kind, sharding=one_chip)

    assert kernels.ssd_scan_takes(t, heads, p, s, 1, 256)
    before = runtime_stats.snapshot()
    compiled = _compile_args(
        jax.jit(both), spec((1, t, wide), dtype), spec((wide, taps), F32),
        spec((wide,), F32), spec((1, t, d), dtype), spec((1, t, heads), dtype),
        spec((heads,), F32), spec((heads,), F32), spec((heads,), F32))
    took = runtime_stats.delta(before)
    assert (took["ssd_scans_kernel"], took["ssd_scans_xla"],
            took["ssd_scan_chunks"]) == (2, 0, 2 * 32)
    assert (took["short_convs_kernel"], took["short_convs_xla"],
            took["short_conv_bias_calls"]) == (1, 0, 1)
    proto = cost.compiled_hlo_proto(compiled)
    rows = cost.instruction_costs(proto)
    assert sorted(r["kernel"] for r in rows if r["kernel"]) == [
        "short_conv_bwd", "short_conv_fwd", "ssd_scan_bwd", "ssd_scan_fwd"]
    by_kernel = {r["kernel"]: r["op_type"] for r in rows if r["kernel"]}
    assert by_kernel["ssd_scan_fwd"] == by_kernel["ssd_scan_bwd"] == "ssd_scan"
    assert by_kernel["short_conv_bwd"] == "short_conv"
    totals = cost.total_costs(proto)
    assert totals["custom_calls"] == totals["pallas_matched"] == 4
    flops = {r["kernel"]: r["flops"] for r in rows if r["kernel"]}
    a_chunk = 2 * 256 * 256 * 128, 2 * 256 * 256 * 64, 2 * 256 * 128 * 64
    assert flops["ssd_scan_fwd"] == 32 * (
        a_chunk[0] + heads * (a_chunk[1] + 2 * a_chunk[2]))
    assert flops["ssd_scan_bwd"] == 32 * (
        3 * a_chunk[0] + heads * (3 * a_chunk[1] + 5 * a_chunk[2]))
    text = compiled.as_text()
    # the states that enter the 32 chunks, a pair of heads a tile: 67 MB
    assert f"f32[1,32,{heads // 2},{s},128]" in text
    assert not re.search(r"f32\[[0-9,]*256,256\]", text)
    assert not re.search(
        rf"= \w+\[1,{t},({d}|{wide})\]\S* (slice|concatenate)\(", text)


def _flash_gqa_lowered_under(one_chip, scale):
    """The gradient of `lfm2-8k`'s and `granite4h-8k`'s attention call
    (32 / 8 heads of 64, 8192 positions, bfloat16) under `scale`,
    lowered for the described chip: the single backward kernel by the
    trace's counters, two kernels by name."""
    from paddle_tpu.ops.pallas import flash_gqa
    from paddle_tpu.ops.pallas.flash_attention import \
        pallas_flash_attention

    n, t, heads, kv = 1, 8192, 32, 8
    assert flash_gqa.default_blocks(t) == (1024, 1024)

    def loss(q, k, v):
        with jax.named_scope("full_attention/flash_attention:9"):
            o = pallas_flash_attention(q, k, v, None, scale, True,
                                       layout="nthd", n_head=heads,
                                       n_kv_head=kv)
        return jnp.sum(o.astype(F32))

    lowered, took = _lower_args(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
        *[jax.ShapeDtypeStruct((n, t, h * 64), BF16, sharding=one_chip)
          for h in (heads, kv, kv)])
    assert (took["flash_gqa_backward_fused"],
            took["flash_gqa_backward_split"]) == (1, 0)
    assert _sites(lowered) == {"flash_gqa_fwd": 1, "flash_gqa_dkv": 1}
    return lowered


@pytest.mark.parametrize("scale", [2.0 ** -6, 64 ** -0.5],
                         ids=["power_of_two", "root"])
def test_flash_gqa_under_either_scale_by_its_trace(one_chip, scale):
    """Tier-1's stand-in for the test below, which is `slow`: under both
    scales the trace takes the single backward kernel and the step
    lowers to the same two names."""
    _flash_gqa_lowered_under(one_chip, scale)


# slow, 53 s (two compiles).  The driver's chip runs of `granite4h-8k`
# (2^-6) and `lfm2-8k` (64^-1/2) guard that Mosaic takes each; that both
# hand the compiler the SAME kernels at the same cost waits for this test
@pytest.mark.slow
def test_flash_gqa_under_a_scale_that_is_a_power_of_two(one_chip):
    """`granite4h-8k`'s attention call: `lfm2-8k`'s geometry (32 / 8
    heads of 64, 8192 positions, bfloat16) under the scale 2^-6 where
    that cell's is 64^-1/2.  A power of two rides on q exactly
    (`flash_gqa.py`), any other scale on the scores: the kernels the
    compiler gets are the same three names, forward at the 1024 x 1024
    tiles PR 56 chose and ONE backward kernel, under both scales."""
    from paddle_tpu.observe import cost

    kernels = {}
    for scale in (2.0 ** -6, 64 ** -0.5):
        compiled = _flash_gqa_lowered_under(one_chip, scale).compile()
        rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
        kernels[scale] = sorted(
            (r["kernel"], r["flops"]) for r in rows if r["kernel"])
    assert kernels[2.0 ** -6] == kernels[64 ** -0.5]
    assert [k for k, _ in kernels[2.0 ** -6]] == ["flash_gqa_dkv",
                                                 "flash_gqa_fwd"]


# heads, d_head, lanes that turn
ROPE_SHAPES = {
    "q_128": (32, 128, None), "k_128": (4, 128, None),
    "q_256_quarter": (16, 256, 64), "k_256_quarter": (2, 256, 64),
}


@pytest.mark.parametrize("shape", list(ROPE_SHAPES))
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_rope_kernels_at_the_published_shapes(one_chip, dtype, shape):
    """The `rope` op and its gradient at the cells' shapes, 1 x 16384
    rows: `mellum2-16k`'s and `sdar-8k`'s q and k (32 and 4 heads of
    128, each normed), `qwen3next-16k`'s (16 and 2 heads of 256 of
    which 64 lanes turn, zero-centred norm, no slice and no concatenate
    of the projection), in the cells' bfloat16 and the parity scripts'
    float32.  The rule takes all (and leaves `ouro-4k`'s bare turn to
    XLA), so the output with its gradient is TWO Mosaic kernels,
    `rope_fwd` and `rope_bwd` (which recomputes the norm from X); each
    has a registered cost in bytes and no FLOP and sits under the op's
    scope."""
    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe import cost
    from paddle_tpu.observe.monitoring import runtime_stats

    from paddle_tpu.ops.pallas.rope import rope_kernel_takes

    heads, d, rotary = ROPE_SHAPES[shape]
    t = 16384
    assert not rope_kernel_takes(4096, 16, 128, normed=False)
    impl = get_op_impl("rope")
    attrs = {"n_head": heads, "theta": 1e6, "epsilon": 1e-6,
             "zero_centered": d == 256}
    if rotary:
        attrs["rotary_dim"] = rotary

    def both(x, w, ct):
        def fn(x, w):
            with jax.named_scope("full_attention/rope:7"):
                return impl(OpContext(jax.random.PRNGKey(0), 0),
                            {"X": [x], "Scale": [w]},
                            attrs)["Out"][0]

        o, vjp = jax.vjp(fn, x, w)
        return o, vjp(ct)

    before = runtime_stats.snapshot()
    compiled = _compile_args(
        jax.jit(both),
        jax.ShapeDtypeStruct((1, t, heads * d), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((d,), F32, sharding=one_chip),
        jax.ShapeDtypeStruct((1, t, heads * d), dtype, sharding=one_chip))
    took = runtime_stats.delta(before)
    assert (took["ropes_kernel"], took["ropes_xla"]) == (1, 0)
    proto = cost.compiled_hlo_proto(compiled)
    rows = cost.instruction_costs(proto)
    assert sorted(r["kernel"] for r in rows if r["kernel"]) == [
        "rope_bwd", "rope_fwd"]
    assert {r["op_type"] for r in rows if r["op_type"]} == {"rope"}
    assert not any(r["bucket"] in ("matmul", "conv") for r in rows)
    totals = cost.total_costs(proto)
    assert totals["custom_calls"] == totals["pallas_matched"] == 2
    assert totals["pallas_flops"] == 0
    # X and Out forward, X, dOut and dX backward, once each; the
    # (T, D) float32 tables (two, or three where a part turns), the
    # scale, and backward its 8 sublanes of partial sums
    tile = t * heads * d * (2 if dtype == BF16 else 4)
    small = (3 if rotary else 2) * t * d * 4 + d * 4
    by = {r["kernel"]: r["bytes"] for r in rows if r["kernel"]}
    assert by["rope_fwd"] == 2 * tile + small
    assert by["rope_bwd"] == 3 * tile + small + 8 * d * 4
    # nothing as large as X is written between the kernels
    assert compiled.memory_analysis().temp_size_in_bytes < max(
        tile // 4, 4 * t * d * 4)


# rows, heads, the gate's squash: the output norm a head of the two
# delta-rule mixers
HEAD_NORM_SHAPES = {
    "16384_x_32_silu": (16384, 32, "silu"),
    "8192_x_32_sigmoid": (8192, 32, "sigmoid"),
}


@pytest.mark.parametrize("shape", list(HEAD_NORM_SHAPES))
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_head_norm_kernels_at_the_published_shapes(one_chip, dtype, shape):
    """`rms_norm(group_size=128)` under a gate and its gradient at the
    shapes of `qwen3next-16k`'s and `kimilinear-8k`'s output norms (32
    heads of 128, silu and sigmoid), in the cells' bfloat16 and the
    parity scripts' float32: TWO Mosaic kernels, `head_norm_fwd` and
    `head_norm_bwd` (which recomputes a head's rstd from X), each with
    a registered cost in bytes and no FLOP, under the op's scope; no
    dot, no float32 view a head and nothing as large as X between the
    kernels.  (The l2norm form, X a lane range of QKV, compiles inside
    the two delta-rule ops' cases above.)"""
    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe import cost
    from paddle_tpu.observe.monitoring import runtime_stats
    from paddle_tpu.ops.pallas import head_norm

    t, heads, squash = HEAD_NORM_SHAPES[shape]
    d = 128
    impl = get_op_impl("rms_norm")

    def both(x, w, gate, ct):
        def fn(x, w, gate):
            with jax.named_scope("linear_attention/rms_norm:7"):
                return impl(OpContext(jax.random.PRNGKey(0), 0),
                            {"X": [x], "Scale": [w], "Gate": [gate]},
                            {"group_size": d, "epsilon": 1e-6,
                             "gate_activation": squash})["Y"][0]

        o, vjp = jax.vjp(fn, x, w, gate)
        return o, vjp(ct)

    wide = jax.ShapeDtypeStruct((1, t, heads * d), dtype, sharding=one_chip)
    before = runtime_stats.snapshot()
    compiled = _compile_args(
        jax.jit(both), wide,
        jax.ShapeDtypeStruct((d,), F32, sharding=one_chip), wide, wide)
    took = runtime_stats.delta(before)
    assert (took["head_norm_calls"], took["head_norm_rows"]) == (2, 2 * t)
    proto = cost.compiled_hlo_proto(compiled)
    rows = cost.instruction_costs(proto)
    assert sorted(r["kernel"] for r in rows if r["kernel"]) == [
        "head_norm_bwd", "head_norm_fwd"]
    assert {r["op_type"] for r in rows if r["op_type"]} == {"rms_norm"}
    assert not any(r["bucket"] in ("matmul", "conv") for r in rows)
    totals = cost.total_costs(proto)
    assert totals["custom_calls"] == totals["pallas_matched"] == 2
    assert totals["pallas_flops"] == 0
    # X, Gate and Y forward; X, dY, Gate, dX and dGate backward, once
    # each; the scale, and backward its 8 sublanes of partial sums a
    # grid step
    tile = t * heads * d * (2 if dtype == BF16 else 4)
    by = {r["kernel"]: r["bytes"] for r in rows if r["kernel"]}
    assert by["head_norm_fwd"] == 3 * tile + d * 4
    tr, lb = head_norm._tiles(t, heads * d, 0, tile // (t * heads * d), 5)
    assert by["head_norm_bwd"] == 5 * tile + d * 4 + (
        (t // tr) * (heads * d // lb) * 8 * d * 4)
    assert f"f32[1,{t},{heads},{d}]" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < tile // 4


def _band_call_lowered(one_chip, dtype, heads, hkv, d, window=None):
    """The gradient of a causal band call (`heads` query heads over
    `hkv` key/value heads of `d`, 1 x 16384, under a window or none),
    lowered for the described chip, with what needs no compile
    asserted: the single backward kernel is inside its budget at this
    shape and the trace took it (the counters), and the step lowers to
    one forward and one backward kernel by name, no `_dq`.  (the lowered
    function, the counters)"""
    from paddle_tpu.ops.pallas import flash_attention as fa

    n, t = 1, 16384
    assert fa.band_backward_fits(t, d)

    def loss(q, k, v):
        return jnp.sum(fa.pallas_flash_attention(
            q, k, v, causal=True, layout="nthd", n_head=heads,
            n_kv_head=hkv, window=window).astype(F32))

    lowered, took = _lower_args(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
        *[jax.ShapeDtypeStruct((n, t, h * d), dtype, sharding=one_chip)
          for h in (heads, hkv, hkv)],
        precision=_precision(dtype))
    assert (took["flash_attention_backward_fused"],
            took["flash_attention_backward_split"]) == (1, 0)
    prefix = "flash_window_" if window else "flash_"
    assert _sites(lowered) == {prefix + "fwd": 1, prefix + "dkv": 1}
    return lowered, took


def _head_dim_256_lowered(one_chip, dtype):
    from paddle_tpu.ops.pallas.flash_attention import (_fwd_vmem_params,
                                                       band_backward_fits)

    t, d = 16384, 256
    assert band_backward_fits(t, d) and not band_backward_fits(t + 1024, d)
    assert bool(_fwd_vmem_params(1024, 1024, d, 4)) and not any(
        _fwd_vmem_params(1024, 1024, width, size)
        for width, size in ((256, 2), (128, 4), (128, 2)))
    return _band_call_lowered(one_chip, dtype, 16, 2, d)[0]


def test_grouped_flash_at_head_dim_256_in_float32_by_its_trace(one_chip):
    """Tier-1's stand-in for `[f32]` below, which is `slow`: both sides
    of the 48 MiB budget, which forward claims the VMEM limit, the path
    the trace took and the kernels' names."""
    _head_dim_256_lowered(one_chip, F32)


@pytest.mark.parametrize("dtype", [
    BF16,
    # slow, 49 s.  `qwen3next-16k` runs bfloat16; float32 at "highest" is
    # `benchmarks/qwen3next_parity.py`'s: nothing on the chip guards it
    # between runs of `-m slow -k head_dim_256`
    pytest.param(F32, marks=pytest.mark.slow)], ids=["bf16", "f32"])
def test_grouped_flash_at_head_dim_256_compiles_one_backward_kernel(
        one_chip, dtype):
    """Second half: causal flash attention at 16 query heads of 256
    over 2 key/value heads, 1 x 16384.  One head's dq with its key/value
    head's dk and dv, whole sequences of float32, is 48 MiB at this head
    size: the edge of the single backward kernel's budget
    (`band_backward_fits`; PR 54).  Mosaic takes the 48 MiB of scratch
    beside 1024 x 1024 score blocks at a 256-deep contraction under the
    100 MiB the call names, so the text holds TWO custom calls and no
    `flash_dq`; one more block of positions is past the budget.  In the
    parity script's float32 the FORWARD kernel's tiles pass Mosaic's
    default 16 MiB of scoped VMEM too (27.5 MiB: the chip refused the
    call, PR 44) and it claims the limit (`_fwd_vmem_params`), which the
    bfloat16 call does not."""
    text = _head_dim_256_lowered(one_chip, dtype).compile().as_text()
    assert _kernels(text) == 2
    for kernel in ("flash_fwd", "flash_dkv"):
        assert f"pallas_{kernel}" in text
    assert "pallas_flash_dq" not in text


def _head_count_lowered(one_chip, dtype, heads, window):
    """`_band_call_lowered` at one of `laguna-16k`'s two geometries,
    with the tiles its rule takes and which kind of call the trace
    counted."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    assert fa._band_blocks(16384, None, None, window)[0] == (
        (512, 512) if window else (1024, 1024))
    lowered, took = _band_call_lowered(one_chip, dtype, heads, 8, 128,
                                       window)
    if window:
        assert (took["flash_window_calls"], took["flash_grouped_calls"]) \
            == (1, 0)
        assert took["flash_window_pairs_allowed"] == 8257792
        assert took["flash_window_entries_computed"] == 64 * 512 * 512
        assert (took["flash_window_blocks_visited"],
                took["flash_window_blocks_allowed"]) == (64, 63)
        assert (took["flash_window_forward_whole_band"],
                took["flash_window_forward_tiled"]) == (1, 0)
    else:
        assert (took["flash_window_calls"], took["flash_grouped_calls"]) \
            == (0, 1)
    return lowered


def test_the_full_layers_band_kernels_in_float32_by_their_trace(one_chip):
    """Tier-1's stand-in for `[48h_full-f32]` below, which is `slow`."""
    _head_count_lowered(one_chip, F32, 48, None)


@pytest.mark.parametrize("heads, window, dtype", [
    (64, 512, BF16), (48, None, BF16), (64, 512, F32),
    # slow, 50 s.  `laguna-16k` runs bfloat16; float32 at "highest" is
    # `benchmarks/laguna_parity.py`'s: nothing on the chip guards it
    # between runs of `-m slow -k head_count`
    pytest.param(48, None, F32, marks=pytest.mark.slow)],
    ids=["64h_window512-bf16", "48h_full-bf16", "64h_window512-f32",
         "48h_full-f32"])
def test_band_kernels_at_a_head_count_a_layer_type(one_chip, dtype, heads,
                                                   window):
    """`laguna-16k`'s two geometries, 1 x 16384 at d_head 128 over 8
    key/value heads: 64 query heads (groups of 8) under a window of 512
    keys, whose forward is the whole-band step since PR 60 (grid (8
    key/value heads, 32 query tiles of 512): the group's eight heads a
    `fori_loop` over the lane tiles of a (512, 1024) q / o block, two
    key tiles a step, 64 a head computed, the first query tile's
    clamped one masked whole), and 48 query heads (groups of SIX) over
    the whole prefix at 1024 x 1024.  One forward and ONE backward
    kernel each (24 MiB of dq, dk, dv in VMEM), bfloat16 as the cell
    runs them and float32 as `benchmarks/laguna_parity.py` does."""
    n, t, hkv, d = 1, 16384, 8, 128
    text = _head_count_lowered(one_chip, dtype, heads,
                               window).compile().as_text()
    assert _kernels(text) == 2
    prefix = "flash_window_" if window else "flash_"
    for kernel in ("fwd", "dkv"):
        assert f"pallas_{prefix}{kernel}" in text
    # dk, dv leave 8 heads wide, never the query heads' width
    assert f"[{n},{t},{hkv * d}]" in text


@pytest.mark.parametrize("rows, dtype", [(16384, BF16), (16384, F32),
                                         (32768, BF16), (34816, BF16)],
                         ids=["sdar_8k-bf16", "sdar_8k-f32", "budget_edge",
                              "past_budget"])
def test_flash_under_the_block_diffusion_mask_at_the_cells_shape(
        one_chip, rows, dtype):
    """`ops/pallas/flash_block_diffusion.py` as `sdar-8k` asks it, 6
    calls a step: one document of 8192 positions as 16384 rows (clean,
    then noised), blocks of 4, 32 query heads of 128 over 4 key/value
    heads, 1024 x 1024 tiles on a grid of 80 VISITS a head (a
    scalar-prefetched table; three branches of a kernel: a whole tile,
    a tile under the mask by block id, and eight unrolled 128 x 128
    squares of a noised tile against itself, on dynamic slices of the
    refs, the logsumexp row's lanes among them).  Two custom calls: the
    forward kernel and ONE backward kernel that holds dq of a query
    head and dk, dv of its key/value head full-length (24 MiB); in the
    parity script's float32 at "highest" too.  A document of 16384 (48
    MiB) is the budget's edge and still one kernel (PR 54); one of
    17408 takes the two kernels that hold tiles only, `_dkv` over a
    table that holds the group's heads.  Each declares the cost of the
    pairs the MASK allows, 67,141,632 a head at 8192."""
    from paddle_tpu.observe import cost
    from paddle_tpu.observe.monitoring import runtime_stats
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import flash_block_diffusion as fbd

    h, hkv, d = 32, 4, 128
    fused = fa.band_backward_fits(rows, d)
    assert fused == (rows <= 32768)
    assert fbd.block_diffusion_takes(rows, 4)

    def loss(q, k, v):
        with jax.named_scope("flash_attention:9"):
            o = fa.pallas_flash_attention(
                q, k, v, None, d ** -0.5, False, layout="nthd", n_head=h,
                n_kv_head=hkv, block_diffusion=4)
        return jnp.sum(o.astype(F32))

    before = runtime_stats.snapshot()
    args = [jax.ShapeDtypeStruct((1, rows, heads * d), dtype,
                                 sharding=one_chip)
            for heads in (h, hkv, hkv)]
    prec = "default" if dtype == BF16 else "highest"
    with force_mosaic_lowering(), jax.default_matmul_precision(prec):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *args).compile()
    took = runtime_stats.delta(before)
    assert (took["flash_attention_backward_fused"],
            took["flash_attention_backward_split"]) == (int(fused),
                                                        int(not fused))
    assert took["flash_block_diffusion_calls"] == 2
    assert took["flash_block_diffusion_blocks_visited"] \
        == took["flash_block_diffusion_blocks_allowed"] \
        == took["flash_block_diffusion_grid_steps"] \
        == 2 * fbd._DiffusionBand(rows, 1024, 4).blocks_allowed
    assert _kernels(compiled.as_text()) == (2 if fused else 3)
    rows_ = {r["kernel"]: r for r in cost.instruction_costs(
        cost.compiled_hlo_proto(compiled)) if r["kernel"]}
    prefix = "flash_block_diffusion_"
    assert sorted(rows_) == [prefix + k for k in
                             (("dkv", "fwd") if fused
                              else ("dkv", "dq", "fwd"))]
    assert {r["op_type"] for r in rows_.values()} == {"flash_attention"}
    if rows == 16384:
        pairs = h * 67141632
        item = jnp.dtype(dtype).itemsize
        assert rows_[prefix + "fwd"]["flops"] == pairs * (4 * d + 8)
        assert rows_[prefix + "dkv"]["flops"] == pairs * (8 * d + 8)
        assert rows_[prefix + "fwd"]["bytes"] == rows * d * item * (
            2 * h + 2 * hkv)
        assert rows_[prefix + "dkv"]["bytes"] == rows * d * item * (
            4 * h + 4 * hkv)


def test_fused_vocab_ce_fwd_bwd(one_chip):
    from paddle_tpu.ops.pallas.vocab_ce import fused_vocab_ce

    tokens, d, vocab = 64 * 256, 512, 32000

    def loss(hidden, w, labels):
        return jnp.sum(fused_vocab_ce(hidden, w, labels, 0.1))

    text = _compile(jax.grad(loss, argnums=(0, 1)), one_chip,
                    ((tokens, d), BF16), ((d, vocab), BF16),
                    ((tokens,), I32))
    assert _kernels(text) >= 2


# (S, H, d, P, page, maxp): chip_smoke's serve_decode geometry (16
# slots, 8 heads x 64, 384 pages of 16, 512-token slots) and d_head 128
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("geom", [(16, 8, 64, 384, 16, 32),
                                  (16, 4, 128, 384, 16, 32)],
                         ids=["serve_decode", "d_head128"])
def test_paged_attention(one_chip, geom, int8):
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_attention

    s, h, d, p, page, maxp = geom
    pool = ((p, page, h * d), I8 if int8 else BF16)
    specs = [((s, h * d), BF16), pool, pool, ((s, maxp), I32),
             ((s,), I32)]
    if int8:
        specs += [((p, page, 1), F32)] * 2

    def fn(q, k, v, pt, ln, ks=None, vs=None):
        return ragged_paged_attention(q, k, v, pt, ln, n_head=h,
                                      k_scales=ks, v_scales=vs)

    assert _kernels(_compile(fn, one_chip, *specs)) == 1


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_fused_lstm_fwd_bwd(one_chip, dtype):
    """The stacked LSTM's width (N=128, H=512; it builds f32): the
    backward takes 27 MiB of VMEM at the default time block, over
    Mosaic's 16 MiB default; the kernel raises the limit."""
    from paddle_tpu.ops.pallas.recurrence import fused_lstm

    n, t, hid = 128, 128, 512

    def loss(x, w):
        hs, _cs, _h, c_last = fused_lstm(x, w)
        return jnp.sum(hs.astype(F32)) + jnp.sum(c_last.astype(F32))

    text = _compile(jax.grad(loss, argnums=(0, 1)), one_chip,
                    ((n, t, 4 * hid), dtype), ((hid, 4 * hid), dtype))
    assert _kernels(text) == 2


def test_fused_lstm_never_blocks_shape_inference():
    """Build-time shape inference traces the kernel with a huge
    stand-in batch and must get its shapes: the kernel leaves the VMEM
    verdict to Mosaic at compile time and raises nothing before (an
    early raise left the LSTM layer's output shapeless and the next
    fc's weight (1, 4H) — found on the chip, PR 21)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import stacked_dynamic_lstm as lstm

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        lstm.build_model(max_len=16, use_amp=False, pallas_rnn=True)
    assert main.global_block().var("lstm_0.tmp_0").shape == (-1, 16, 512)


def test_kernel_cost_registry_covers_a_whole_step_on_the_tpu(one_chip):
    """The stacked-LSTM train step with the fused kernel, compiled for
    the chip: every Mosaic kernel in it has a registered cost, and the
    TPU compiler's own bookkeeping custom calls (ConcatBitcast, ...)
    are not mistaken for kernels (a miscount that once made the cost
    table refuse the step on the chip, PR 21)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import stacked_dynamic_lstm as lstm
    from paddle_tpu.observe import cost

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        model = lstm.build_model(max_len=16, use_amp=False,
                                 pallas_rnn=True)
        exe = fluid.Executor()
        exe.run(startup)
        feed = {k: jnp.asarray(v)
                for k, v in lstm.make_fake_batch(8, 16).items()}
        step, state, feeds = exe._prepare(
            main, feed, [model["loss"].name], scope, 1, True)

        def described(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=one_chip)

        compiled = _compile_args(step, jax.tree.map(described, state),
                                 jax.tree.map(described, feeds))
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    targets = {r["custom_call_target"] for r in rows
               if r["opcode"] == "custom-call"}
    assert "tpu_custom_call" in targets
    totals = cost.total_costs(cost.compiled_hlo_proto(compiled))
    assert totals["custom_calls"] == totals["pallas_matched"] > 0, totals
    assert totals["pallas_flops"] > 0


def test_tpu_dots_are_matmul_rows_with_xlas_flops(one_chip):
    """The TPU compiler writes every dot as a `convolution` (a batched
    one over its batch dimensions, with a window as large as the batch
    of which a dilation leaves one position valid): observe.cost must
    still bucket it `matmul`, a real convolution `conv`, and count the
    FLOPs XLA's own cost analysis counts."""
    from paddle_tpu.observe import cost

    def forward(q, k, v):
        with jax.named_scope("flash_attention:3"):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
            p = jax.nn.softmax(s.astype(F32), -1).astype(BF16)
            return jnp.einsum("bhqk,bhkd->bhqd", p, v).astype(F32).sum()

    # forward and backward: five batched dots (the forward alone is
    # matched to a fused attention of the compiler's own)
    attention = jax.grad(forward, argnums=(0, 1, 2))

    def stem(x, w):
        with jax.named_scope("conv2d:0"):
            return jax.lax.conv_general_dilated(x, w, (2, 2), "SAME")

    qkv = [jax.ShapeDtypeStruct((8, 8, 256, 64), BF16, sharding=one_chip)] * 3
    img = [jax.ShapeDtypeStruct(s, BF16, sharding=one_chip)
           for s in ((8, 3, 224, 224), (64, 3, 7, 7))]
    for fn, args, bucket, op, dots in ((attention, qkv, "matmul",
                                        "flash_attention", 5),
                                       (stem, img, "conv", "conv2d", 1)):
        compiled = _compile_args(jax.jit(fn), *args)
        assert " convolution(" in compiled.as_text()
        assert " dot(" not in compiled.as_text()
        rows = [r for r in cost.instruction_costs(
            cost.compiled_hlo_proto(compiled))
            if r["bucket"] in ("matmul", "conv")]
        assert len(rows) == dots
        assert {r["bucket"] for r in rows} == {bucket}
        assert {r["op_type"] for r in rows} == {op}
        total = sum(r["flops"] for r in cost.instruction_costs(
            cost.compiled_hlo_proto(compiled)))
        assert total == pytest.approx(cost.compiled_xla_flops(compiled),
                                      rel=0.02)
    # the stem by hand: SAME padding clips 3 of 7 window positions at
    # each edge, which a count of window sizes would miss
    assert sum(r["flops"] for r in rows) < 2 * 8 * 64 * 112 * 112 * 3 * 49
