"""The `rope` op's Pallas kernels (`ops/pallas/rope.py`) through the
interpreter, against the composition that stands (`ops/decoder.py
_rope`): Out, dX and dScale over a whole head of 128, a head of 256 of
which 64 lanes turn, restarting positions, an Offset, scaled
frequencies with their factor, a zero-centred scale, the grouped
widths of 32 and 4 heads; the norm in the op against the two
ops it replaces; the shapes the rule leaves to the composition; the
two counters.  `tests/test_chip_compile_kernels.py` hands the same
kernels to the chip's compiler at the cells' shapes.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpContext, get_op_impl
from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops import decoder
from paddle_tpu.ops.pallas import rope as rk

EPS = 1e-6
T = 128
# case -> (heads, d_head, the op's attrs beside n_head, an Offset)
CASES = {
    "whole_head_128": (2, 128, {}, None),
    "head_256_quarter": (2, 256, {"rotary_dim": 64}, None),
    "period": (2, 128, {"period": T // 2}, None),
    "offset": (2, 128, {}, 37),
    "inv_freq_and_factor": (2, 128, {
        "inv_freq": list(decoder.rope_frequencies(
            128, "yarn", 1e4, factor=8.0,
            original_max_position_embeddings=64)[0]),
        "attention_factor": 1.2079}, None),
    "zero_centered": (2, 256, {"zero_centered": True,
                                     "rotary_dim": 64}, None),
    "gqa_q_32_heads": (32, 128, {}, None),
    "gqa_k_4_heads": (4, 128, {}, None),
}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def f32(x):
    return np.asarray(x, np.float32)


def operands(case, dtype, t=T, seed=0):
    heads, d, attrs, offset = CASES[case]
    r = np.random.default_rng(seed)
    shape = (2, t, heads * d)
    centre = 0.0 if attrs.get("zero_centered") else 1.0
    scale = jnp.asarray(centre + 0.3 * r.normal(size=(d,)), jnp.float32)
    return (jnp.asarray(r.normal(size=shape), dtype), scale,
            jnp.asarray(r.normal(size=shape), dtype),
            dict(attrs, n_head=heads, theta=1e4, epsilon=EPS),
            None if offset is None else jnp.asarray([offset], jnp.int32))


def run_op(x, scale, attrs, offset=None, name="rope"):
    ins = {"X": [x], "Scale": [] if scale is None else [scale],
           "Offset": [] if offset is None else [offset]}
    return list(get_op_impl(name)(OpContext(jax.random.PRNGKey(0), 0), ins,
                                  attrs).values())[0][0]


def composition(x, scale, attrs, offset):
    """`_rope` as the op calls it where the rule says no."""
    heads = attrs["n_head"]
    d = x.shape[-1] // heads
    cos, sin = decoder._cos_sin(x.shape[1], attrs.get("rotary_dim") or d,
                                attrs, offset)
    if scale is not None and attrs.get("zero_centered"):
        scale = 1.0 + scale
    return decoder._rope(x, scale, cos, sin, heads, EPS,
                         bool(attrs.get("interleave")))


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case", list(CASES))
def test_kernels_match_the_composition(case, dtype):
    """The op (which the rule sends to the kernels) against `_rope`:
    float32 within 2e-6 of the largest value; bfloat16 within one step
    of the result's last place (both round the same float32 value,
    whose products they may add fused or not) and the scale's float32
    gradient within 1e-5.  One traced call counts once."""
    x, scale, ct, attrs, offset = operands(case, DTYPES[dtype])
    heads = attrs["n_head"]
    assert rk.rope_kernel_takes(T, heads, x.shape[-1] // heads,
                                attrs.get("rotary_dim"),
                                itemsize=x.dtype.itemsize)
    before = runtime_stats.snapshot()
    got, got_vjp = jax.vjp(lambda x, s: run_op(x, s, attrs, offset),
                           x, scale)
    took = runtime_stats.delta(before)
    assert (took["ropes_kernel"], took["ropes_xla"]) == (1, 0)
    want, want_vjp = jax.vjp(lambda x, s: composition(x, s, attrs, offset),
                             x, scale)
    assert got.dtype == want.dtype == x.dtype
    step = 2e-6 if dtype == "f32" else 2.0 ** -8
    for a, b in zip((got,) + got_vjp(ct), (want,) + want_vjp(ct)):
        assert a.shape == b.shape and a.dtype == b.dtype
        rel = 1e-5 if a.ndim == 1 else step
        assert np.abs(f32(a) - f32(b)).max() <= rel * np.abs(f32(b)).max()


def test_the_turn_is_the_textbook_rotation():
    """Against numpy in float64, so that kernel and composition do not
    share a mistake: rows at their positions, lanes past `rotary_dim`
    untouched, the norm a head."""
    x, scale, _, attrs, _ = operands("head_256_quarter", jnp.float32)
    got = np.asarray(run_op(x, scale, attrs), np.float64)
    heads, d, r = 2, 256, 64
    xs = np.asarray(x, np.float64).reshape(2, T, heads, d)
    xs = xs / np.sqrt((xs ** 2).mean(-1, keepdims=True) + EPS) \
        * np.asarray(scale, np.float64)
    ang = np.arange(T)[:, None] * 1e4 ** (-np.arange(0, r, 2) / r)[None]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    x1, x2 = xs[..., :r // 2], xs[..., r // 2:r]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           xs[..., r:]], -1).reshape(2, T, heads * d)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


@pytest.mark.parametrize("case", ["whole_head_128", "zero_centered"])
def test_the_norm_in_the_op_is_the_two_ops_it_replaces(case):
    """`rms_norm(group_size=D)` then `rope`, in float32 where no
    rounding separates the two: the fused op's result and gradients."""
    x, scale, ct, attrs, _ = operands(case, jnp.float32)
    d = x.shape[-1] // attrs["n_head"]
    turn = {k: v for k, v in attrs.items()
            if k not in ("epsilon", "zero_centered")}

    def two_ops(x, s):
        y = run_op(x, s, {"group_size": d, "epsilon": EPS, "zero_centered":
                          attrs.get("zero_centered", False)}, name="rms_norm")
        return run_op(y, None, turn)

    got, got_vjp = jax.vjp(lambda x, s: run_op(x, s, attrs), x, scale)
    want, want_vjp = jax.vjp(two_ops, x, scale)
    for a, b in zip((got,) + got_vjp(ct), (want,) + want_vjp(ct)):
        np.testing.assert_allclose(f32(a), f32(b), rtol=0,
                                   atol=1e-5 * np.abs(f32(b)).max())


@pytest.mark.parametrize("case", ["bare", "bare_quarter", "head_64",
                                  "interleave", "one_row", "odd_rows",
                                  "pairs_with_norm"])
def test_the_rule_leaves_other_calls_to_the_composition(case):
    """A bare turn (no Scale: XLA fuses it into its neighbours), over a
    whole head and over a quarter of one; a head of 64; pairs; a decode
    step's single row (with its Offset); rows without a whole tile: the
    composition's result, counted as such; with a Scale too."""
    t, heads, d, attrs, norm = {
        "bare": (T, 2, 128, {}, False),
        "bare_quarter": (T, 2, 256, {"rotary_dim": 64}, False),
        "head_64": (T, 4, 64, {}, True),
        "interleave": (T, 2, 128, {"interleave": True}, False),
        "one_row": (1, 2, 128, {}, True),
        "odd_rows": (24, 2, 128, {}, True),
        "pairs_with_norm": (T, 2, 128, {"interleave": True}, True),
    }[case]
    assert not rk.rope_kernel_takes(t, heads, d, attrs.get("rotary_dim"),
                                    attrs.get("interleave", False), norm)
    r = np.random.default_rng(2)
    x = jnp.asarray(r.normal(size=(2, t, heads * d)), jnp.float32)
    scale = jnp.asarray(1 + 0.3 * r.normal(size=(d,)), jnp.float32) \
        if norm else None
    offset = jnp.asarray([5], jnp.int32) if case == "one_row" else None
    attrs = dict(attrs, n_head=heads, theta=1e4, epsilon=EPS)
    before = runtime_stats.snapshot()
    got, vjp = jax.vjp(lambda x: run_op(x, scale, attrs, offset), x)
    took = runtime_stats.delta(before)
    assert (took["ropes_kernel"], took["ropes_xla"]) == (0, 1)
    want, want_vjp = jax.vjp(lambda x: composition(x, scale, attrs, offset),
                             x)
    np.testing.assert_array_equal(f32(got), f32(want))
    np.testing.assert_allclose(f32(vjp(got)[0]), f32(want_vjp(want)[0]),
                               rtol=1e-6, atol=1e-6)


def test_the_rule_reads_the_shape_alone():
    """The cells' shapes, the edges, and a row tile that shrinks until
    the backward pass's three double-buffered tiles fit VMEM."""
    takes = rk.rope_kernel_takes
    assert takes(16384, 32, 128) and takes(16384, 4, 128)
    assert takes(16384, 16, 256, 64) and takes(16384, 2, 256, 64)
    assert takes(16384, 32, 128, itemsize=4) and takes(4096, 16, 128)
    assert not takes(16384, 32, 64) and not takes(16384, 32, 128, 63)
    assert not takes(16384, 32, 128, interleave=True)
    assert not takes(16384, 32, 128, normed=False)
    assert not takes(1, 32, 128) and not takes(8, 32, 128)
    assert rk._row_tile(16384, 4096, 2) == rk.ROW_TILE
    assert rk._row_tile(16384, 64 * 256, 4) == 128
    assert rk._row_tile(16384, 128 * 256, 4) == 64
    assert rk._row_tile(48, 4096, 2) == 16
    assert not takes(16384, 1024, 256, itemsize=4)


def test_a_program_build_neither_traces_nor_counts_the_op():
    """`rope`'s layer declares its output's shape; shape inference at
    the stand-in batch must not trace a kernel.  The scale it creates
    is `rms_norm`'s by name, shape and start."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    before = runtime_stats.snapshot()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[T, 512], dtype="float32")
        y = layers.rope(x, 4, norm=True, epsilon=EPS, zero_centered=True)
        z = layers.rope(x, 2, rotary_dim=64)
    assert tuple(y.shape)[1:] == (T, 512) == tuple(z.shape)[1:]
    took = runtime_stats.delta(before)
    assert (took["ropes_kernel"], took["ropes_xla"]) == (0, 0)
    (scale,) = main.all_parameters()
    assert tuple(scale.shape) == (128,) and scale.name.startswith("rms_norm")
    op = [op for op in main.global_block().ops if op.type == "rope"][0]
    assert op.input("Scale") == [scale.name]
    assert op.attrs["zero_centered"] and op.attrs["epsilon"] == EPS
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = np.random.default_rng(0).normal(size=(1, T, 512)).astype("float32")
    before = runtime_stats.snapshot()
    got, = exe.run(main, feed={"x": feed}, fetch_list=[y])
    took = runtime_stats.delta(before)
    assert (took["ropes_kernel"], took["ropes_xla"]) == (1, 0)   # z: pruned
    want = composition(jnp.asarray(feed), jnp.zeros((128,), jnp.float32),
                       {"n_head": 4, "theta": 1e4, "zero_centered": True},
                       None)
    np.testing.assert_allclose(got, f32(want), rtol=0, atol=1e-5)
