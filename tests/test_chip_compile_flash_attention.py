"""The chip's compiler on `ops/pallas/flash_attention.py`'s forward and
backward, on `flash_gqa.py`'s head-pair kernels, on `flash_mla.py` where
its backward's shape rule changes its mind (the budget's edge and beyond
it; the cell's own shape is tests/test_chip_compile_kernels.py), and on
`dropout_mask.py`, on one chip and under a dp=4 mesh.  tests/chip_compile.py
says why and how, and why these share a file.  The single backward
kernel of `flash_attention.py` is tests/test_chip_compile_flash.py.
"""

from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp

from chip_compile import (BF16, F32, _compile, _compile_args, _kernels,
                          _lower_args, _precision, _sites)


# (N, H, T, D): the Transformer at batch 64 x 256 and at 2 x 8192
@pytest.mark.parametrize("shape", [(64, 8, 256, 64), (2, 8, 8192, 64)],
                         ids=["bs64_len256", "bs2_len8192"])
def test_flash_attention_fwd_bwd(one_chip, shape):
    from paddle_tpu.ops.pallas.flash_attention import \
        pallas_flash_attention

    def loss(q, k, v):
        o = pallas_flash_attention(q, k, v, None, shape[3] ** -0.5, True)
        return jnp.sum(o.astype(F32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    *[(shape, BF16)] * 3)
    assert _kernels(text) >= 2, "forward and backward kernels expected"


def test_flash_attention_head_major_entry(one_chip):
    """layout="nthd": (N, T, H*D) head-grouped operands with the
    key-padding bias, at d_head 128 — the width at which one head is a
    whole lane tile of the grouped minor dim."""
    from paddle_tpu.ops.pallas.flash_attention import \
        pallas_flash_attention

    n, t, h, d = 64, 256, 4, 128

    def loss(q, k, v, bias):
        o = pallas_flash_attention(q, k, v, bias, d ** -0.5, True,
                                   layout="nthd", n_head=h)
        return jnp.sum(o.astype(F32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    *[((n, t, h * d), BF16)] * 3, ((n, 1, 1, t), F32))
    assert _kernels(text) >= 2


# (N, T, query heads, key/value heads) at d_head 64, head-major: the
# lfm2-8k cell's attention layer, the Transformer's heads, and a
# sequence past the single backward kernel's budget
def _head_pairs_lowered(one_chip, geometry, dtype):
    """The gradient of the head-major call at d_head 64, lowered for the
    described chip, and what its trace shows with no compile: which
    backward path the shape rule took (the counters say), the forward's
    tiles, the kernels' names.  (the lowered function, fused)"""
    from paddle_tpu.ops.pallas import flash_gqa
    from paddle_tpu.ops.pallas.flash_attention import \
        pallas_flash_attention

    n, t, heads, kv = geometry
    fused = flash_gqa.fused_backward_fits(t)
    assert fused == (t <= 8192)
    assert flash_gqa.default_blocks(t) == (1024, 1024)

    def loss(q, k, v):
        with jax.named_scope("flash_attention:9"):
            o = pallas_flash_attention(q, k, v, None, 0.125, True,
                                       layout="nthd", n_head=heads,
                                       n_kv_head=kv)
        return jnp.sum(o.astype(F32))

    args = [jax.ShapeDtypeStruct((n, t, h * 64), dtype, sharding=one_chip)
            for h in (heads, kv, kv)]
    lowered, took = _lower_args(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))), *args,
        precision=_precision(dtype))
    assert (took["flash_gqa_backward_fused"],
            took["flash_gqa_backward_split"]) == (
                (1, 0) if fused else (0, 1))
    assert _sites(lowered) == dict.fromkeys(
        ["flash_gqa_fwd", "flash_gqa_dkv"] + ["flash_gqa_dq"] * (not fused),
        1)
    return lowered, fused


def test_the_head_pairs_in_float32_at_the_cells_shape_by_their_trace(
        one_chip):
    """Tier-1's stand-in for the case below that is `slow`."""
    _head_pairs_lowered(one_chip, (1, 8192, 32, 8), F32)


@pytest.mark.parametrize("geometry, dtype", [
    ((1, 8192, 32, 8), BF16),
    # slow, 49 s.  `lfm2-8k` runs bfloat16: float32 at "highest" is
    # `benchmarks/lfm2_parity.py`'s, which no driver's run reaches.
    # Nothing on the chip guards it between runs of `-m slow -k d_head_64`;
    # the stand-in above holds its trace
    pytest.param((1, 8192, 32, 8), F32, marks=pytest.mark.slow),
    ((64, 256, 8, 8), BF16), ((64, 256, 8, 8), F32),
    ((1, 32768, 8, 2), BF16)],
    ids=["lfm2_8k_gqa_32_over_8-bf16", "lfm2_8k_gqa_32_over_8-f32",
         "bs64_len256_mha-bf16", "bs64_len256_mha-f32",
         "beyond_the_budget_32k-bf16"])
def test_flash_attention_head_major_at_d_head_64_blocks_head_pairs(
        one_chip, geometry, dtype):
    """At d_head 64 a head is half a lane tile of the (N, T, H*D)
    operand, which Mosaic does not take as a block (the refusal this
    test replaced): `ops/pallas/flash_gqa.py` blocks heads in pairs,
    reads grouped key/value heads where they lie (K and V stay
    (N, T, Hkv*64): nothing in the step is Hq heads wide but q, o and
    their gradients), and its kernels compile forward and backward, in
    the cell's bfloat16 and in the parity script's float32 at
    "highest".  The backward pass is ONE kernel, `flash_gqa_dkv` grown
    by dq's dot, whose 1.5 KiB a position of float32 accumulators (dq
    of a query tile's whole sequence, dk and dv of its key/value
    tile: 12 MiB at 8192) Mosaic must take in VMEM in both dtypes; at
    32768 positions they pass the budget and the two kernels that hold
    blocks only stay.  The counter says which path the trace took.  The
    forward (PR 56) runs the largest tile its rule can choose, 1024 x
    1024 over the two query pairs of a key/value head (four heads'
    float32 score tiles a step, past Mosaic's default 16 MiB: it names
    the VMEM limit as the backward does), in both dtypes."""
    from paddle_tpu.observe import cost

    n, t, heads, kv = geometry
    lowered, fused = _head_pairs_lowered(one_chip, geometry, dtype)
    compiled = lowered.compile()
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    assert sorted(r["kernel"] for r in rows if r["kernel"]) == (
        ["flash_gqa_dkv", "flash_gqa_fwd"] if fused else
        ["flash_gqa_dkv", "flash_gqa_dq", "flash_gqa_fwd"])
    assert {r["op_type"] for r in rows if r["kernel"]} == {
        "flash_attention"}
    totals = cost.total_costs(cost.compiled_hlo_proto(compiled))
    assert totals["custom_calls"] == totals["pallas_matched"] == (
        2 if fused else 3)
    # dense-equivalent: 4 matmuls' worth forward, 8 backward, a score
    scores = n * heads * t * t
    assert totals["pallas_flops"] >= 12 * 64 * scores
    # dk, dv leave the kernel key/value heads wide, once
    text = compiled.as_text()
    assert f"bf16[{n},{t},{kv * 64}]" in text or dtype == F32


def _latent_backward_lowered(one_chip, t, dtype, fused):
    """The gradient of a latent-attention call of 8 heads at `t`
    positions, lowered for the described chip, with what needs no
    compile asserted: the shape rule's side, the path the trace took
    (the counters), the kernels' names."""
    from paddle_tpu.ops.pallas import flash_mla

    assert flash_mla.fused_backward_fits(t) == fused
    assert not flash_mla.fused_backward_fits(t + 1024) or not fused
    n, heads = 1, 8
    widths = (heads * 128, heads * 64, heads * 128, 64, heads * 128)
    args = [jax.ShapeDtypeStruct((n, t, w), dtype, sharding=one_chip)
            for w in widths]
    lowered, took = _lower_args(
        jax.jit(jax.grad(
            lambda *a: jnp.sum(flash_mla.flash_mla(*a).astype(F32)),
            argnums=(0, 1, 2, 3, 4))), *args,
        precision=_precision(dtype))
    assert (took["flash_mla_backward_fused"],
            took["flash_mla_backward_split"]) == (
                (1, 0) if fused else (0, 1))
    assert _sites(lowered) == dict.fromkeys(
        ["flash_mla_fwd", "flash_mla_dkv"] + ["flash_mla_dq"] * (not fused),
        1)
    return lowered


def test_latent_attention_backward_at_its_budgets_edge_by_its_trace(
        one_chip):
    """Tier-1's stand-in for `[edge]` below, which is `slow`: the rule
    says the single kernel on this side of 32 MiB and two past it, the
    trace takes the single one, the step lowers to two kernels."""
    _latent_backward_lowered(one_chip, 16384, F32, True)


@pytest.mark.parametrize("t, dtype, fused", [
    # slow, 169 s.  No cell runs latent attention at 16384 positions in
    # float32 (`joyai-8k` and `kimilinear-8k`: 8192, bfloat16): NOTHING on
    # the chip guards that Mosaic takes the 32 MiB of accumulators between
    # runs of `-m slow -k latent`; the stand-in above holds the rule's
    # side and the trace
    pytest.param(16384, F32, True, marks=pytest.mark.slow),
    (32768, BF16, False)], ids=["edge", "beyond"])
def test_latent_attention_backward_follows_the_budget(one_chip, t, dtype,
                                                       fused):
    """The shape rule's two sides.  At the accumulators' budget (2 KiB
    a position: 16384 positions are its 32 MiB) Mosaic still takes the
    single backward kernel, with float32 operands, the larger blocks;
    past it the two backward kernels stay, which hold blocks only.  The
    counter says which path the trace took."""
    from paddle_tpu.observe import cost

    compiled = _latent_backward_lowered(one_chip, t, dtype, fused).compile()
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    assert sorted(r["kernel"] for r in rows if r["kernel"]) == (
        ["flash_mla_dkv", "flash_mla_fwd"] if fused else
        ["flash_mla_dkv", "flash_mla_dq", "flash_mla_fwd"])


def _rng_reader():
    """The benchmark's own reader of `rng_evals_per_step`."""
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:       # the reader imports step_anatomy
        sys.path.insert(0, bench)
    from run import load_module

    return load_module(os.path.join(bench, "layer_metrics",
                                    "rng_evals_per_step.py"))


def _residual_loss(dropout, h, w, res, gamma, beta):
    """dot -> dropout -> residual add -> layer norm, as a Transformer
    sublayer ends: every cotangent the backward needs."""
    z = (dropout(jnp.einsum("btd,de->bte", h, w)) + res).astype(F32)
    mean = z.mean(-1, keepdims=True)
    norm = (z - mean) * jax.lax.rsqrt(z.var(-1, keepdims=True) + 1e-5)
    return (norm * gamma + beta).astype(BF16).astype(F32).sum()


def _attention_loss(dropout, scores, v):
    """soft-max -> dropout -> weights @ v, the composed attention."""
    p = dropout(jax.nn.softmax(scores.astype(F32), -1).astype(BF16))
    return jnp.einsum("bhqk,bhkd->bhqd", p, v).astype(F32).sum()


@pytest.mark.parametrize("loss,shapes", [
    (_residual_loss, (((8, 256, 512), BF16), ((512, 512), BF16),
                      ((8, 256, 512), BF16), ((512,), F32), ((512,), F32))),
    (_attention_loss, (((8, 8, 256, 256), BF16), ((8, 8, 256, 64), BF16))),
], ids=["dot_dropout_add_layernorm", "softmax_dropout_matmul"])
def test_dropout_mask_is_generated_once_and_outside_the_dots(one_chip, loss,
                                                             shapes):
    """The `dropout` op's own lowering, forward and backward, compiled
    for the chip: the mask is one `pallas_dropout_mask` custom call a
    `dropout` op (a custom call cannot be cloned into the fusions that
    read it, which is what XLA did to the threefry generator: PERF.md,
    PR 25), the step holds no XLA generator at all, and its dots are
    still there (the TPU compiler writes dots as `convolution`).  The
    judge is the benchmark's own reader of `rng_evals_per_step`."""
    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe import cost
    from paddle_tpu.observe.monitoring import runtime_stats

    reader = _rng_reader()

    def step(key, *args):
        def dropout(x):
            with jax.named_scope("dropout:7"):
                return get_op_impl("dropout")(
                    OpContext(key, 7), {"X": [x]},
                    {"dropout_prob": 0.1,
                     "dropout_implementation": "upscale_in_train"}
                )["Out"][0]

        return jax.grad(lambda *a: loss(dropout, *a),
                        argnums=tuple(range(len(args))))(*args)

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (((2,), jnp.uint32),) + shapes]
    snap = runtime_stats.snapshot()
    compiled = _compile_args(jax.jit(step), *args)
    drawn = runtime_stats.delta(snap)
    assert (drawn["dropout_masks_kernel"], drawn["dropout_masks_xla"]) \
        == (1, 0)
    module = cost.HloModule(cost.compiled_hlo_proto(compiled))
    assert reader.rng_instructions(module) == {}
    assert not any(reader.generators(c)
                   for c in module.computations.values()
                   if c.id != module.entry_id)
    rows = [r for r in cost.instruction_costs(module) if r["kernel"]]
    assert [(r["kernel"], r["op_type"], r["flops"]) for r in rows] == [
        ("dropout_mask", "dropout", 0)]
    # the registered cost: the mask's byte an element and the seeds
    n_mask = 1
    for d in shapes[0][0]:
        n_mask *= d
    assert rows[0]["bytes"] == n_mask + 3 * 4
    assert " convolution(" in compiled.as_text()    # the dots are there


def test_dropout_mask_under_a_dp_mesh_is_drawn_per_chip(dp4_mesh):
    """GSPMD cannot partition a custom call: under the `{"dp": 4}`
    mesh of the described 2x2 the op maps the kernel over the batch
    axis itself, so each chip draws its own quarter of the mask (the
    custom call's result has the per-chip leading dimension) and no
    mask is gathered."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe import cost
    from paddle_tpu.parallel.mesh import executing_mesh

    mesh = dp4_mesh
    reader = _rng_reader()
    shape = (4 * 16, 256, 512)

    def step(key, h, w, res, gamma, beta):
        def dropout(x):
            with jax.named_scope("dropout:7"), executing_mesh(mesh, "dp"):
                return get_op_impl("dropout")(
                    OpContext(key, 7), {"X": [x]},
                    {"dropout_prob": 0.1,
                     "dropout_implementation": "upscale_in_train"}
                )["Out"][0]

        return jax.grad(lambda *a: _residual_loss(dropout, *a),
                        argnums=(0, 1))(h, w, res, gamma, beta)

    rep, batch = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    args = [jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
            jax.ShapeDtypeStruct(shape, BF16, sharding=batch),
            jax.ShapeDtypeStruct((512, 512), BF16, sharding=rep),
            jax.ShapeDtypeStruct(shape, BF16, sharding=batch),
            jax.ShapeDtypeStruct((512,), F32, sharding=rep),
            jax.ShapeDtypeStruct((512,), F32, sharding=rep)]
    compiled = _compile_args(jax.jit(step), *args)
    module = cost.HloModule(cost.compiled_hlo_proto(compiled))
    assert reader.rng_instructions(module) == {}
    calls = [i for c in module.computations.values()
             for i in c.instructions if i.opcode == "custom-call"
             and "pallas_dropout_mask" in i.op_name]
    assert [tuple(i.shape.dims) for i in calls] == [(16 * 256, 512)]
    text = compiled.as_text()
    assert " all-reduce(" in text       # dW is summed over the chips
    assert not [line for line in text.splitlines()
                if " all-gather(" in line
                and (" s8[" in line or " pred[" in line)]
