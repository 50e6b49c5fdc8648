"""The chip's compiler on the main path's kernels, at real widths.

Interpret mode and `jax.export` (tests/test_pallas_lowering.py) both
stop before Mosaic compiles: kernels that passed every such test were
refused by the TPU's compiler for a lane slice not aligned to the
tiling (paged attention) and for more scoped VMEM than a kernel may
claim (fused LSTM).  The compiler is installed here and compiles for a
chip that is DESCRIBED, not attached — so each kernel of the main path
is compiled once for one v5e device at the width chip_smoke.py and
bench.py run it, and the compiled text must hold the Mosaic custom
call.  Nothing runs: this says nothing about results or times.

The topology is described inside a module-scoped fixture (never at
import: only one process may hold the TPU library, and every xdist
worker imports every test file), in the test's own process, with the
persistent compilation cache off.  All such tests live in THIS file.
"""

from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import force_mosaic_lowering


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever the library raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topology):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(scope="module")
def dp4_mesh(topology):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(topology.devices).reshape(4), ("dp",))


def _compile_args(fn, *args):
    """Compile an already-jittable `fn` for the described chip from
    ShapeDtypeStruct arguments that carry its sharding."""
    # conftest asks for "highest" matmul precision (f64 references);
    # the program runs at the default, and Mosaic refuses an fp32
    # contraction of bf16 operands
    with force_mosaic_lowering(), jax.default_matmul_precision("default"):
        return fn.lower(*args).compile()


def _compile(fn, sharding, *specs):
    """Compile `fn` for the described chip from (shape, dtype) specs
    and return the compiled text."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]
    return _compile_args(jax.jit(fn), *args).as_text()


def _kernels(text):
    return text.count("tpu_custom_call")


BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


# (N, H, T, D): the Transformer bench's shape and the longctx one
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
@pytest.mark.parametrize("geometry, dtype", [
    ((1, 8192, 32, 8), BF16), ((1, 8192, 32, 8), F32),
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
    blocks only stay.  The counter says which path the trace took."""
    from paddle_tpu.observe import cost
    from paddle_tpu.observe.monitoring import runtime_stats
    from paddle_tpu.ops.pallas import flash_gqa
    from paddle_tpu.ops.pallas.flash_attention import \
        pallas_flash_attention

    n, t, heads, kv = geometry
    fused = flash_gqa.fused_backward_fits(t)
    assert fused == (t <= 8192)

    def loss(q, k, v):
        with jax.named_scope("flash_attention:9"):
            o = pallas_flash_attention(q, k, v, None, 0.125, True,
                                       layout="nthd", n_head=heads,
                                       n_kv_head=kv)
        return jnp.sum(o.astype(F32))

    args = [jax.ShapeDtypeStruct((n, t, h * 64), dtype, sharding=one_chip)
            for h in (heads, kv, kv)]
    prec = "default" if dtype == BF16 else "highest"
    before = runtime_stats.snapshot()
    with force_mosaic_lowering(), jax.default_matmul_precision(prec):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
            .lower(*args).compile()
    took = runtime_stats.delta(before)
    assert (took["flash_gqa_backward_fused"],
            took["flash_gqa_backward_split"]) == (
                (1, 0) if fused else (0, 1))
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
    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe import cost
    from paddle_tpu.observe.monitoring import runtime_stats

    n, t, heads = 1, 8192, 32
    impl = get_op_impl("latent_attention")

    def loss(q_nope, q_rope, k_nope, k_rope, v):
        with jax.named_scope("latent_attention/latent_attention:9"):
            o = impl(OpContext(jax.random.PRNGKey(0), 0),
                     {"QNope": [q_nope], "QRope": [q_rope],
                      "KNope": [k_nope], "KRope": [k_rope], "V": [v]},
                     {"n_head": heads, "use_pallas": True})["Out"][0]
        return jnp.sum(o.astype(F32))

    widths = (heads * 128, heads * 64, heads * 128, 64, heads * 128)
    args = [jax.ShapeDtypeStruct((n, t, w), dtype, sharding=one_chip)
            for w in widths]
    prec = "default" if dtype == BF16 else "highest"
    before = runtime_stats.snapshot()
    with force_mosaic_lowering(), jax.default_matmul_precision(prec):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))) \
            .lower(*args).compile()
    took = runtime_stats.delta(before)
    assert (took["flash_mla_backward_fused"],
            took["flash_mla_backward_split"]) == (1, 0)
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


@pytest.mark.parametrize("t, dtype, fused", [
    (16384, F32, True), (32768, BF16, False)], ids=["edge", "beyond"])
def test_latent_attention_backward_follows_the_budget(one_chip, t, dtype,
                                                       fused):
    """The shape rule's two sides.  At the accumulators' budget (2 KiB
    a position: 16384 positions are its 32 MiB) Mosaic still takes the
    single backward kernel, with float32 operands, the larger blocks;
    past it the two backward kernels stay, which hold blocks only.  The
    counter says which path the trace took."""
    from paddle_tpu.observe import cost
    from paddle_tpu.observe.monitoring import runtime_stats
    from paddle_tpu.ops.pallas import flash_mla

    assert flash_mla.fused_backward_fits(t) == fused
    assert not flash_mla.fused_backward_fits(t + 1024) or not fused
    n, heads = 1, 8
    widths = (heads * 128, heads * 64, heads * 128, 64, heads * 128)
    args = [jax.ShapeDtypeStruct((n, t, w), dtype, sharding=one_chip)
            for w in widths]
    prec = "default" if dtype == BF16 else "highest"
    before = runtime_stats.snapshot()
    with force_mosaic_lowering(), jax.default_matmul_precision(prec):
        compiled = jax.jit(jax.grad(
            lambda *a: jnp.sum(flash_mla.flash_mla(*a).astype(F32)),
            argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    took = runtime_stats.delta(before)
    assert (took["flash_mla_backward_fused"],
            took["flash_mla_backward_split"]) == (
                (1, 0) if fused else (0, 1))
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    assert sorted(r["kernel"] for r in rows if r["kernel"]) == (
        ["flash_mla_dkv", "flash_mla_fwd"] if fused else
        ["flash_mla_dkv", "flash_mla_dq", "flash_mla_fwd"])


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
    """bench_lstm's width (N=128, H=512; f32 is what it builds): the
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
    are not mistaken for kernels — that miscount made
    `bench.py --model lstm --pallas-rnn` refuse to report on the chip
    (PR 21)."""
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


def test_olmoe_step_kernels_at_the_published_shapes(one_chip):
    """What `olmoe-4k`'s step hands the chip's compiler that no other
    cell does, at OLMoE-1B-7B's widths (4 x 4096 tokens, 16 heads of
    128, 64 experts of 2048 x 1024, 8 a token): the causal head-major
    flash call with no bias, forward and backward, and the dropless
    expert op, whose nine grouped matmuls are the Pallas kernels of
    `ops/pallas/grouped_matmul.py` under the name `ragged_dot` (PR 40;
    the TPU compiler's own lowering of `jax.lax.ragged_dot` before),
    static shapes whatever the routing.  `observe.cost` must name every kernel and count T*k rows
    of work for a grouped matmul, never E x dense."""
    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe import cost
    from paddle_tpu.ops.pallas.flash_attention import \
        pallas_flash_attention

    n, t, heads, d, e, h, k = 4, 4096, 16, 128, 64, 1024, 8
    hidden = heads * d

    def attention(q, k_, v):
        with jax.named_scope("flash_attention:9"):
            o = pallas_flash_attention(q, k_, v, None, d ** -0.5, True,
                                       layout="nthd", n_head=heads)
        return jnp.sum(o.astype(F32))

    compiled = _compile_args(
        jax.jit(jax.grad(attention, argnums=(0, 1, 2))),
        *[jax.ShapeDtypeStruct((n, t, hidden), BF16, sharding=one_chip)] * 3)
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    # the backward pass is ONE kernel at this shape (PR 37;
    # tests/test_chip_compile_flash.py has its cases)
    assert sorted(r["kernel"] for r in rows if r["kernel"]) == [
        "flash_dkv", "flash_fwd"]
    assert {r["op_type"] for r in rows if r["kernel"]} == {
        "flash_attention"}

    impl = get_op_impl("moe_dropless")

    def experts(x, gate, w1, w3, w2):
        with jax.named_scope("moe_dropless:12"):
            o = impl(OpContext(jax.random.PRNGKey(0), 0),
                     {"X": [x], "GateW": [gate], "W1": [w1], "W3": [w3],
                      "W2": [w2]}, {"top_k": k})
        return (jnp.sum(o["Out"][0].astype(F32)) + o["AuxLoss"][0][0]
                + o["ZLoss"][0][0])

    shapes = [(n, t, hidden), (hidden, e), (e, hidden, h), (e, hidden, h),
              (e, h, hidden)]
    compiled = _compile_args(
        jax.jit(jax.grad(experts, argnums=(0, 1, 2, 3, 4))),
        *[jax.ShapeDtypeStruct(s, BF16, sharding=one_chip)
          for s in shapes])
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    matmuls = [r for r in rows if r["kernel"] == "ragged_dot"]
    assert len(matmuls) == 9            # 3 forward, 3 dX, 3 dW
    per_matmul = 2.0 * n * t * k * hidden * h
    assert {r["flops"] for r in matmuls} == {per_matmul}
    assert {r["bucket"] for r in matmuls} == {"custom_call"}
    assert not any(r["bucket"] == "matmul" and r["flops"] > per_matmul
                   for r in rows)       # nothing E x dense beside them
    totals = cost.total_costs(cost.compiled_hlo_proto(compiled))
    assert totals["custom_calls"] == totals["pallas_matched"] >= 9
    # the routing never reaches a shape: no dynamic dimension anywhere
    assert "<=" not in compiled.as_text().split("ENTRY")[1].split("\n")[0]


def _computation(text, name):
    """The lines of computation `name` in a compiled module's text."""
    body = text.split(f"\n%{name} (", 1)[1]
    return body[:body.index("\n}\n")].split("\n")[1:]


def test_lfm2_share_layer_and_short_conv_at_the_published_shapes(
        one_chip, monkeypatch):
    """What `lfm2-8k`'s step hands the chip's compiler beside the
    attention kernels, at LFM2-24B-A2B's widths (1 x 8192 tokens, a
    router over 64 experts, 8 of them held at 2048 x 1536, 4 a token).
    The expert op that holds a share compiles to two `conditional`s,
    forward and backward, of three branches: its sorted rows at 6144,
    12288 and T*k = 32768 rows, eleven Mosaic grouped matmuls a size,
    the kernels of `ops/pallas/grouped_matmul.py` under the
    `moe_dropless` scope in every branch (PR 40; the compiler's own
    lowering of `jax.lax.ragged_dot` before)
    (three forward; backward the two up-projections again and six
    more: the down-projection's result would serve the router's
    gradient alone, which a program that runs a share holds back);
    static shapes whatever the routing, nothing 64 experts wide but
    the router; the smallest branch writes one T*k-row buffer each
    way, the gather back to token order; and the plan needs less
    memory than the section differentiated on T*k rows (a quarter less
    before PR 40; an eighth since, the kernels having taken the masks'
    buffers out of the section differentiated as it stands).  The gated
    short convolution is XLA fusions with no kernel and no dot."""
    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe import cost
    from paddle_tpu.ops import moe_dropless

    t, hidden, e, held, h, k = 8192, 2048, 64, 8, 1536, 4
    sizes = moe_dropless.row_buffer_sizes(t, k, e, held)
    assert sizes == (6144, 12288, t * k)
    impl = get_op_impl("moe_dropless")
    attrs = {"top_k": k, "routing": "sigmoid", "norm_topk_prob": True,
             "experts_held": [0, held], "router_gradient": False}

    def experts(x, gate, bias, w1, w3, w2):
        with jax.named_scope("moe_dropless:12"):
            o = impl(OpContext(jax.random.PRNGKey(0), 0),
                     {"X": [x], "GateW": [gate], "Bias": [bias],
                      "W1": [w1], "W3": [w3], "W2": [w2]}, attrs)
        # not linear in Out: a share's routing weights are constants of
        # the backward pass, and a linear loss would need no forward
        return jnp.sum(jnp.sin(o["Out"][0].astype(F32)))

    shapes = [((1, t, hidden), BF16), ((hidden, e), BF16), ((e,), F32),
              ((held, hidden, h), BF16), ((held, hidden, h), BF16),
              ((held, h, hidden), BF16)]

    def compile_layer():
        return _compile_args(
            jax.jit(jax.grad(experts, argnums=(0, 1, 3, 4, 5))),
            *[jax.ShapeDtypeStruct(s, d, sharding=one_chip)
              for s, d in shapes])

    compiled = compile_layer()
    text = compiled.as_text()
    assert f"[{e},{hidden},{h}]" not in text     # no absent expert's weight
    assert "<=" not in text.split("ENTRY")[1].split("\n")[0]
    branches = [line.split("branch_computations={")[1].split("}")[0]
                .replace("%", "").split(", ")
                for line in text.split("\n") if " conditional(" in line]
    assert [len(b) for b in branches] == [3, 3]  # forward, backward
    for smallest, _, _ in branches:
        lines = _computation(text, smallest)
        assert not any(f"[{t * k},{h}]" in line.split(" = ")[1].split("(")[0]
                       for line in lines if " = " in line)
        wide = [line for line in lines if " = " in line and
                f"[{t * k},{hidden}]" in line.split(" = ")[1].split("(")[0]]
        assert len(wide) == 1 and " fusion(" in wide[0], wide  # the gather

    proto = cost.compiled_hlo_proto(compiled)
    every = cost.instruction_costs(proto, every_branch=True)
    matmuls = [r for r in every if r["kernel"] == "ragged_dot"]
    assert {r["bucket"] for r in matmuls} == {"custom_call"}
    assert all(r["branch_of"] for r in matmuls)
    # every branch's are the Pallas kernels, under the op's scope: the
    # step holds no `ragged-dot` of the compiler's
    assert {r["pallas_kernel"] for r in matmuls} == {"ragged_dot"}
    assert {r["op_type"] for r in matmuls} == {"moe_dropless"}
    assert "ragged-dot" not in text
    by_size = {}
    for r in matmuls:
        by_size[r["flops"]] = by_size.get(r["flops"], 0) + 1
    assert by_size == {2.0 * rows * hidden * h: 11 for rows in sizes}
    # a table that sums to a step lists the heaviest branch alone
    rows = cost.instruction_costs(proto)
    assert [r["flops"] for r in rows if r["kernel"] == "ragged_dot"] == [
        2.0 * t * k * hidden * h] * 11
    inside = [r for r in every if r["branch_of"] and r["bucket"] in (
        "elementwise", "layout", "matmul")]
    assert inside and {r["op_type"] for r in inside
                       if r["op_type"]} == {"moe_dropless"}

    # the section on T*k rows, differentiated as it stands (the parent
    # of PR 31): what the switch is measured against
    monkeypatch.setattr(moe_dropless, "row_buffer_sizes",
                        lambda t, k, e, count: (t * k,))
    full = compile_layer()
    assert " conditional(" not in full.as_text()
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < 0.9 * full.memory_analysis().temp_size_in_bytes
    # and in bytes.  What plans the most is the catch-all's backward:
    # the T*k rows, the two up-projections and three gradients as wide
    # at once, which the compiler's own ragged dots took as fused
    # operands and a kernel takes from HBM (713 MiB; 556 at the parent,
    # whose section as it stands planned 994 to this one's 803).  No
    # step's peak is there: `lfm2-8k` reads `hbm_peak_gb` 4.07 for the
    # parent's 4.12 (PERF.md, PR 40)
    assert temporaries <= 720 << 20

    conv = get_op_impl("short_conv")

    def short_conv(bcu, w):
        with jax.named_scope("short_conv:7"):
            o = conv(OpContext(jax.random.PRNGKey(0), 0),
                     {"X": [bcu], "Filter": [w]}, {})
        return jnp.sum(o["Out"][0].astype(F32))

    compiled = _compile_args(
        jax.jit(jax.grad(short_conv, argnums=(0, 1))),
        jax.ShapeDtypeStruct((1, t, 3 * hidden), BF16, sharding=one_chip),
        jax.ShapeDtypeStruct((hidden, 3), F32, sharding=one_chip))
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    assert not any(r["kernel"] for r in rows)
    assert not any(r["bucket"] in ("matmul", "conv") for r in rows)
    assert {r["op_type"] for r in rows if r["op_type"]} == {"short_conv"}


# the sorted-row buffers of the four cells with routed experts: tokens,
# experts a token, experts, experts held (None: all), D, H
EXPERT_CELLS = {
    "mellum2-16k": (16384, 8, 64, 8, 2304, 896),
    "lfm2-8k": (8192, 4, 64, 8, 2048, 1536),
    "joyai-8k": (8192, 8, 256, 8, 2048, 768),
    "olmoe-4k": (4 * 4096, 8, 64, None, 2048, 1024),
}


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_grouped_matmul_kernels_at_the_cells_shapes(one_chip, cell):
    """`ops/pallas/grouped_matmul.py`: forward, dX and dW at the tiles
    its rule takes for a cell's up- and down-projection, within
    Mosaic's default scoped VMEM (no `vmem_limit_bytes`): bf16 at the
    smallest and the largest row buffer, float32 at the smallest.  The cost table
    counts 2 x rows x K x N for each, dW by its result's rank."""
    from paddle_tpu.observe import cost
    from paddle_tpu.ops import moe_dropless
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul

    t, k, e, held, d, h = EXPERT_CELLS[cell]
    groups = e if held is None else held
    sizes = ((t * k,) if held is None
             else moe_dropless.row_buffer_sizes(t, k, e, held))

    def vjp(lhs, rhs, counts, ct):
        out, pull = jax.vjp(lambda l, r: grouped_matmul(l, r, counts),
                            lhs, rhs)
        return (out,) + pull(ct)

    cases = [(sizes[0], BF16), (sizes[-1], BF16), (sizes[0], F32)]
    for rows, dtype in dict.fromkeys(cases):
        for kk, nn in ((d, h), (h, d)):
            compiled = _compile_args(jax.jit(vjp), *[
                jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in (((rows, kk), dtype), ((groups, kk, nn), dtype),
                              ((groups,), I32), ((rows, nn), dtype))])
            assert "vmem_limit_bytes" not in compiled.as_text()
            table = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
            kernels = [r for r in table if r["kernel"] == "ragged_dot"]
            assert [r["pallas_kernel"] for r in kernels] == ["ragged_dot"] * 3
            assert {r["flops"] for r in kernels} == {2.0 * rows * kk * nn}


def test_a_looped_step_with_flash_kernels_in_the_scans_body(one_chip):
    """What a looped decoder's step hands the chip's compiler that no
    other cell does, at the published widths (1 x 4096 tokens, 16 heads
    of 128, FFN 5632, the whole 49152-row head; depth cut to ONE layer
    for the test's time, 4 trips as published): the Mosaic flash
    kernels inside a `lax.scan`'s body and inside its transpose, each
    layer pass and each trip's head a recompute segment in the body,
    bf16 AMP.  The loops are counted (trip count 4), the body's
    instructions are cost rows of their own under their kernels' names
    and the `ut_loop` scope, the weights' bf16 copies are made outside
    the loops, and the forward loop hands its transpose the segments'
    inputs and the flash kernel's two residuals (PR 39), nothing else
    of a layer."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import decoder
    from paddle_tpu.observe import cost, trace

    t, d, dff, vocab, trips = 4096, 2048, 5632, 49152, 4
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        model = decoder.build_model(
            max_length=t, hidden_size=d, num_hidden_layers=1,
            num_attention_heads=16, num_key_value_heads=16,
            intermediate_size=dff, num_experts=0, num_experts_per_tok=0,
            norm_topk_prob=False, num_dense_layers=1, vocab_size=vocab,
            rope_theta=1e6, rms_norm_eps=1e-6, total_ut_steps=trips,
            sandwich_norm=True, qk_norm=None, exit_gate="sigmoid",
            exit_entropy_weight=0.1, recompute="layer")
        # the state by its shapes: no start-up run at this size
        for var in main.global_block().vars.values():
            if var.persistable and all(int(s) > 0 for s in var.shape):
                scope.set_var(var.name, jax.ShapeDtypeStruct(
                    tuple(int(s) for s in var.shape),
                    np.dtype(str(var.dtype))))
        feed = {k: jnp.zeros((1, t), jnp.int64)
                for k in ("tokens", "labels")}
        exe = fluid.Executor()
        step, state, feeds = exe._prepare(
            main, feed, [model["loss"].name], scope, 1, True)

        def described(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

        compiled = _compile_args(step, jax.tree.map(described, state),
                                 jax.tree.map(described, feeds))
    assert [len(b.ops) for b in main.blocks][1] > 20     # ONE sub-block
    proto = cost.compiled_hlo_proto(compiled)
    rows = cost.instruction_costs(proto)
    loops = [r for r in rows if r["opcode"] == "while"]
    assert [r["trip_count"] for r in loops] == [trips, trips]
    assert all(r["bucket"] == "loop" and r["flops"] == 0 for r in loops)
    inside = [r for r in rows if r["loop_of"]]
    assert all(r["trips"] == trips for r in inside)
    # the forward kernel in the forward loop and NOT again in the
    # backward loop's recomputed layer pass (the segment keeps its
    # output and logsumexp); the single backward kernel once
    assert sorted(r["kernel"] for r in inside if r["kernel"]) == [
        "flash_dkv", "flash_fwd"]
    assert not [r for r in rows if r["kernel"] and not r["loop_of"]]
    pmap = trace.program_map(proto)
    for r in inside:
        if r["kernel"]:
            assert r["pallas_kernel"] and r["flops"] > 0
            assert "ut_loop" in trace.name_scope_of(
                pmap[r["name"]]["op_name"]).split("/")
    # the loop's matmuls carry their FLOPs per call: a layer pass's
    # seven products forward, again recomputed, twice that backward,
    # and the head's three; nothing of them outside the loops
    tokens = float(t)
    layer = 2 * tokens * (4 * d * d + 3 * d * dff)
    head = 2 * tokens * d * vocab
    matmul = sum(r["flops"] for r in inside if r["bucket"] == "matmul")
    assert matmul == pytest.approx(4 * layer + 4 * head, rel=0.02)
    assert sum(cost.per_step(r, "flops") for r in rows
               if r["bucket"] == "matmul") == pytest.approx(
        trips * (4 * layer + 4 * head), rel=0.02)
    totals = cost.total_costs(proto)
    assert totals["custom_calls"] == totals["pallas_matched"] == 2
    # the weights' bf16 copies are loop-invariant: no float32 weight
    # enters a loop's body to be cast there once a trip
    module = cost.HloModule(proto)
    bodies = [module.computations[c] for loop in module.entry.instructions
              if loop.opcode == "while" for c in loop.called_ids]
    weights = {(d, dff), (dff, d), (d, vocab)}
    for body in bodies:
        for instr in body.instructions:
            if instr.opcode == "parameter":
                continue
            assert not (instr.opcode == "convert"
                        and tuple(instr.shape.dims) in weights), instr.name
    text = compiled.as_text()
    forward = [ln for ln in text.splitlines() if " while(" in ln][0]
    # what the forward loop saves for its transpose: the float32 input
    # of the layer's segment and of the head's, stacked over the trips
    # (2 x 134 MB) and the flash kernel's output and logsumexp (67 +
    # 8.4 MB), not the layer's activations
    assert forward.count(f"f32[{trips},1,{t},{d}]") == 2
    assert forward.count(f"bf16[{trips},1,{t},{d}]") == 1
    assert forward.count(f"f32[{trips},16,8,{t}]") == 1
    assert f"[{trips},1,{t},{dff}]" not in forward
    assert f"{t},{vocab}]" not in forward
