"""A head's lane statistic by the Pallas kernels
(`ops/pallas/head_norm.py`) through the interpreter, against the
composition that stands (`head_norm_xla`, the (.., H, 128) view): the
result and every gradient (dX, dGate, dScale) of the three forms the ops
use (a delta rule's l2norm with its constant, the norm a head under
silu(gate) and under sigmoid(gate), the scale from 1 or zero-centred),
float32 and bfloat16, 16 and 32 heads; X as a lane range of a wider
array; numpy in float64, so that kernel and composition do not share a
mistake; the shapes the rule leaves to the composition; the counter;
the three ops that call it; a trace through the interpreter that a
lowering for the chip must not get back.
`tests/test_chip_compile_kernels.py` hands the same kernels to the
chip's compiler at the cells' shapes.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.export  # a submodule: not auto-imported
import jax.numpy as jnp

from paddle_tpu.core.registry import OpContext, get_op_impl
from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops.pallas import force_mosaic_lowering
from paddle_tpu.ops.pallas import head_norm as hn

ROWS = 32
# form -> (gated, `head_norm`'s keywords)
FORMS = {
    "l2norm": (False, dict(denom=1.0, eps=1e-6, constant=128 ** -0.5)),
    "silu": (True, dict(denom=128.0, eps=1e-5)),
    "silu_zero_centered": (True, dict(denom=128.0, eps=1e-5,
                                      zero_centered=True)),
    "sigmoid": (True, dict(denom=128.0, eps=1e-5,
                           gate_activation="sigmoid")),
    "sigmoid_zero_centered": (True, dict(
        denom=128.0, eps=1e-5, gate_activation="sigmoid",
        zero_centered=True)),
}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def f32(x):
    return np.asarray(x, np.float32)


def operands(form, dtype, width, rows=ROWS, array=None, seed=0):
    """((x, scale, gate) or (x,), the cotangent): x (2, rows / 2,
    `array` or `width`)."""
    gated, kw = FORMS[form]
    r = np.random.default_rng(seed)
    draw = lambda w: jnp.asarray(  # noqa: E731
        r.normal(size=(2, rows // 2, w)), dtype)
    x, ct = draw(array or width), draw(width)
    if not gated:
        return (x,), ct
    centre = 0.0 if kw.get("zero_centered") else 1.0
    scale = jnp.asarray(centre + 0.3 * r.normal(size=128), jnp.float32)
    return (x, scale, draw(width)), ct


def by_kernel(form, lanes=None):
    gated, kw = FORMS[form]
    if gated:
        return lambda x, s, g: hn.head_norm(x, s, g, lanes=lanes, **kw)
    return lambda x: hn.head_norm(x, lanes=lanes, **kw)


def by_view(form, lanes=None, group=128):
    """`head_norm_xla` on the sliced range: what the rule's "no" runs."""
    gated, kw = FORMS[form]

    def fn(x, s=None, g=None):
        if lanes:
            x = x[..., lanes[0]:lanes[0] + lanes[1]]
        return hn.head_norm_xla(x, s, g, hn.Form(0, x.shape[-1], **kw),
                                group)
    return fn


def agree(got, want, dtype):
    """float32 within 2e-6 of the largest value; bfloat16 within one
    step of the result's last place (both round the same float32 value,
    whose products they may add fused or not); the scale's float32
    gradient within 1e-5."""
    step = 2e-6 if dtype == "f32" else 2.0 ** -8
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        rel = 1e-5 if a.ndim == 1 else step
        assert np.abs(f32(a) - f32(b)).max() <= rel * np.abs(f32(b)).max()


@pytest.mark.parametrize("width", [2048, 4096])
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("form", list(FORMS))
def test_kernels_match_the_composition(form, dtype, width):
    """`qwen3next-16k`'s 16 heads and `kimilinear-8k`'s 32.  One traced
    forward and one traced backward call count once each, with their
    rows."""
    xs, ct = operands(form, DTYPES[dtype], width)
    assert hn.head_norm_takes(128, width, ROWS)
    before = runtime_stats.snapshot()
    got, got_vjp = jax.vjp(by_kernel(form), *xs)
    grads = got_vjp(ct)
    took = runtime_stats.delta(before)
    assert (took["head_norm_calls"], took["head_norm_rows"]) == (2, 2 * ROWS)
    want, want_vjp = jax.vjp(by_view(form), *xs)
    assert got.dtype == xs[0].dtype and got.shape == ct.shape
    agree((got,) + grads, (want,) + want_vjp(ct), dtype)


# array's width, X's (first lane, lanes): q and k inside QKV (the lane
# tile 1024), a range that starts on its third head (the lane tile 128),
# one whose width is three heads (the same)
RANGES = {
    "q_of_qkv": (3 * 1024, (0, 1024)),
    "k_of_qkv": (3 * 1024, (1024, 1024)),
    "from_the_third_head": (1024, (256, 512)),
    "three_heads": (1024, (512, 384)),
}


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case", list(RANGES))
def test_an_operand_that_is_a_lane_range_of_a_wider_array(case, dtype):
    """The block's lane index picks the range; the array's other lanes
    get a gradient of exactly 0, as a slice's do."""
    array, lanes = RANGES[case]
    xs, ct = operands("l2norm", DTYPES[dtype], lanes[1], array=array)
    assert hn.head_norm_takes(128, lanes[1], ROWS, lanes[0])
    before = runtime_stats.snapshot()
    got, got_vjp = jax.vjp(by_kernel("l2norm", lanes), *xs)
    (dx,) = got_vjp(ct)
    assert runtime_stats.delta(before)["head_norm_calls"] == 2
    want, want_vjp = jax.vjp(by_view("l2norm", lanes), *xs)
    assert got.shape == ct.shape and dx.shape == xs[0].shape
    agree((got, dx), (want,) + want_vjp(ct), dtype)
    outside = np.ones(array, bool)
    outside[lanes[0]:lanes[0] + lanes[1]] = False
    assert not f32(dx)[..., outside].any()


@pytest.mark.parametrize("form", ["l2norm", "silu", "sigmoid_zero_centered"])
def test_the_formula_is_the_textbook_one(form):
    """Against numpy in float64: a head is 128 lanes side by side, the
    scale is the one the heads share, the gate is squashed a lane."""
    gated, kw = FORMS[form]
    xs, _ = operands(form, jnp.float32, 512)
    got = np.asarray(by_kernel(form)(*xs), np.float64)
    x = np.asarray(xs[0], np.float64).reshape(2, ROWS // 2, 4, 128)
    y = x / np.sqrt((x ** 2).sum(-1, keepdims=True) / kw["denom"]
                    + kw["eps"]) * kw.get("constant", 1.0)
    if gated:
        scale, gate = (np.asarray(a, np.float64) for a in xs[1:])
        gate = gate.reshape(x.shape)
        sig = 1 / (1 + np.exp(-gate))
        y = y * (scale + (1.0 if kw.get("zero_centered") else 0.0)) * (
            sig if kw.get("gate_activation") == "sigmoid" else gate * sig)
    np.testing.assert_allclose(got, y.reshape(got.shape), rtol=0, atol=2e-5)


# what the rule leaves to the composition: group, width, rows, first lane
LEFT = {
    "rows_no_whole_tile": (128, 512, 24, 0),
    "one_row": (128, 512, 1, 0),
    "a_group_of_64": (64, 512, 32, 0),
    "a_group_of_256": (256, 512, 32, 0),
    "a_range_inside_a_head": (128, 256, 32, 64),
}


@pytest.mark.parametrize("case", list(LEFT))
def test_shapes_the_rule_leaves_to_the_composition(case):
    """No kernel is traced (the counter reads 0) and the result is the
    view's to the bit: it IS the view."""
    group, width, rows, start = LEFT[case]
    assert not hn.head_norm_takes(group, width, rows, start)
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(rows, start + width)), jnp.bfloat16)
    gate = jnp.asarray(r.normal(size=(rows, width)), jnp.bfloat16)
    scale = jnp.asarray(1 + 0.3 * r.normal(size=group), jnp.float32)
    lanes = (start, width) if start else None
    kw = dict(denom=float(group), eps=1e-5)
    before = runtime_stats.snapshot()
    got, vjp = jax.vjp(lambda x, s, g: hn.head_norm(
        x, s, g, group=group, lanes=lanes, **kw), x, scale, gate)
    vjp(gate)
    took = runtime_stats.delta(before)
    assert (took["head_norm_calls"], took["head_norm_rows"]) == (0, 0)
    want = hn.head_norm_xla(x[..., start:], scale, gate,
                            hn.Form(0, width, **kw), group)
    np.testing.assert_array_equal(f32(got), f32(want))


def test_a_gate_activation_that_is_not_built_raises():
    x = jnp.ones((16, 128), jnp.float32)
    with pytest.raises(NotImplementedError, match="tanh"):
        hn.head_norm(x, gate=x, gate_activation="tanh")


def run_op(name, ins, attrs):
    return list(get_op_impl(name)(
        OpContext(jax.random.PRNGKey(0), 0),
        {k: [] if v is None else [v] for k, v in ins.items()},
        attrs).values())[0][0]


@pytest.mark.parametrize("gate_activation", ["silu", "sigmoid"])
@pytest.mark.parametrize("group, calls", [(128, 2), (64, 0)])
def test_rms_norm_a_group_goes_by_the_shape_alone(group, calls,
                                                  gate_activation):
    """`rms_norm(group_size=)` under either gate: the kernels where a
    group is a lane tile, the view where it is not, one formula: both
    against the op a head at a time (no `group_size`: the plain norm
    over a (.., H, g) tensor's minor dim)."""
    form = "silu" if gate_activation == "silu" else "sigmoid"
    (x, scale, gate), ct = operands(form, jnp.float32, 512)
    scale = scale[:group]
    attrs = {"epsilon": 1e-5, "gate_activation": gate_activation}

    def grouped(x, s, g):
        return run_op("rms_norm", {"X": x, "Scale": s, "Gate": g},
                      dict(attrs, group_size=group))

    def a_head_at_a_time(x, s, g):
        split = x.shape[:-1] + (-1, group)
        return run_op("rms_norm", {"X": x.reshape(split), "Scale": s,
                                   "Gate": g.reshape(split)},
                      attrs).reshape(x.shape)

    before = runtime_stats.snapshot()
    got, got_vjp = jax.vjp(grouped, x, scale, gate)
    grads = got_vjp(ct)
    assert runtime_stats.delta(before)["head_norm_calls"] == calls
    want, want_vjp = jax.vjp(a_head_at_a_time, x, scale, gate)
    for a, b in zip((got,) + grads, (want,) + want_vjp(ct)):
        np.testing.assert_allclose(f32(a), f32(b), rtol=0,
                                   atol=1e-5 * np.abs(f32(b)).max())


def test_a_program_build_infers_the_shape_and_counts_nothing():
    """`layers.rms_norm(group_size=128)` infers its output's shape by
    evaluating the op at the stand-in batch, a million sequences whose
    rows ARE whole tiles: the kernels are traced there and the counter,
    which is of what steps trace, does not tick."""
    import paddle_tpu as fluid

    before = runtime_stats.snapshot()
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[64, 256], dtype="float32")
        gate = fluid.layers.data(name="g", shape=[64, 256], dtype="float32")
        y = fluid.layers.rms_norm(x, group_size=128, gate=gate,
                                  gate_activation="sigmoid")
        assert y.shape == (-1, 64, 256)
    took = runtime_stats.delta(before)
    assert (took["head_norm_calls"], took["head_norm_rows"]) == (0, 0)


def _delta_ops(t):
    """op -> (its inputs at 2 heads of 128 x 128 and `t` positions, its
    attrs, q's constant)."""
    r = np.random.default_rng(0)
    h, d = 2, 128
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        r.normal(size=shape), jnp.float32)
    return {
        "gated_delta_rule": (
            {"QKV": draw(1, t, 3 * h * d), "BA": draw(1, t, 2 * h),
             "ALog": draw(h), "DtBias": draw(h)},
            {"n_key_head": h, "n_value_head": h, "key_dim": d,
             "value_dim": d}),
        "channel_delta_rule": (
            {"QKV": draw(1, t, 3 * h * d), "Gate": draw(1, t, h * d),
             "Beta": draw(1, t, h), "ALog": draw(h), "DtBias": draw(h * d)},
            {"n_head": h, "key_dim": d, "value_dim": d}),
    }


@pytest.mark.parametrize("t, calls", [(64, 2), (24, 0)])
@pytest.mark.parametrize("op", ["gated_delta_rule", "channel_delta_rule"])
def test_a_delta_rules_l2norm_goes_by_the_shape_alone(op, t, calls,
                                                      monkeypatch):
    """q and k of both delta-rule ops.  Where the chunk-local kernels
    run (`channel_delta_rule` here: two heads of 128) they take the
    l2norm themselves (PR 69) and no head-statistic call is traced;
    elsewhere (`gated_delta_rule` at one value head a key head, which
    its chunk-local kernels do not take) two kernel calls a
    traced forward where the rows are whole tiles, none where they are
    not.  The op's result is the same whichever ran: against the view
    before the XLA lowering, everything switched off by the rules."""
    # (the shared helper: the op as ONE compiled function)
    from op_test import run_op as compiled_op

    from paddle_tpu.ops.pallas import channel_delta, gated_delta

    ins, attrs = _delta_ops(t)[op]
    inside = op == "channel_delta_rule"
    before = runtime_stats.snapshot()
    got = compiled_op(op, ins, attrs)
    took = runtime_stats.delta(before)
    assert (took["head_norm_calls"], took["head_norm_rows"]) == (
        (0, 0) if inside else (calls, calls * t))
    # (the inverse kernel and the forward one)
    assert took["channel_delta_operand_calls"] == (2 if inside else 0)
    monkeypatch.setattr(hn, "head_norm_takes", lambda *a: False)
    monkeypatch.setattr(channel_delta, "kernel_takes", lambda *a: False)
    monkeypatch.setattr(gated_delta, "kernel_takes", lambda *a: False)
    before = runtime_stats.snapshot()
    want = compiled_op(op, ins, attrs)
    took = runtime_stats.delta(before)
    assert (took["head_norm_calls"],
            took["channel_delta_operand_calls"]) == (0, 0)
    np.testing.assert_allclose(f32(got), f32(want), rtol=0,
                               atol=2e-5 * np.abs(f32(want)).max())


def test_a_trace_through_the_interpreter_is_not_a_lowering_for_the_chip():
    """The hazard PR 63 found: the jitted passes are traced once a
    shape, so a process that ran a shape through the interpreter (every
    CPU test) would hand that trace to a later lowering for the chip at
    the same shape if the interpret gate were not part of the passes'
    key.  Interpreter first, then `jax.export` for the TPU: the
    artifact holds the two Mosaic kernels, and the interpreter's
    programs are still what the CPU runs after."""
    xs, ct = operands("sigmoid", jnp.bfloat16, 256, rows=64, seed=3)

    def both(x, s, g):
        y, vjp = jax.vjp(by_kernel("sigmoid"), x, s, g)
        return (y,) + vjp(ct)

    # (a jit of its own each time: the outer trace is not the one at stake)
    fresh = lambda: jax.jit(lambda *xs: both(*xs))  # noqa: E731
    first = fresh()(*xs)
    with force_mosaic_lowering():
        text = jax.export.export(fresh(), platforms=["tpu"])(
            *xs).mlir_module()
    assert text.count("tpu_custom_call") == 2
    again = fresh()(*xs)
    for a, b in zip(first, again):
        np.testing.assert_array_equal(f32(a), f32(b))
