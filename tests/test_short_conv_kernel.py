"""The `short_conv` op's Pallas kernels (`ops/pallas/short_conv.py`)
through the interpreter, against the composition that stands
(`ops/decoder.py _silu_conv` / `_short_conv`): both forms at shapes the
rule takes, with two row tiles and, ungated, two channel tiles; Out,
dX and dFilter; causality; the seam between two row tiles; the zeros
before row 0; the shapes the rule leaves to the composition; the two
counters.  `tests/test_chip_compile_kernels.py` hands the same kernels
to the chip's compiler at the cells' shapes.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpContext, get_op_impl
from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops import decoder
from paddle_tpu.ops.pallas import short_conv as sc

# form -> (X's shape, D, taps, row tile, channel tile, the composition)
FORMS = {
    "silu": ((2, 512, 256), 256, 4, 256, 128, decoder._silu_conv),
    "gated": ((1, 512, 3 * 128), 128, 3, 256, None, decoder._short_conv),
}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def operands(form, dtype, seed=0):
    shape, d, taps = FORMS[form][:3]
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=shape), dtype),
            jnp.asarray(r.normal(size=(d, taps)) * 0.5, jnp.float32),
            jnp.asarray(r.normal(size=shape[:2] + (d,)), dtype))


def kernel(form):
    _, _, _, tr, td, _ = FORMS[form]
    return lambda x, w: sc.short_conv_kernel(x, w, form == "gated", tr, td)


def f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("form", list(FORMS))
def test_kernels_match_the_composition(form, dtype):
    """float32 within 1e-6 of the largest value; bfloat16 within the
    composition's own rounding (one step of the result's last place:
    both round the same float32 sum, whose taps they may add fused or
    not) and the filter's float32 gradient within 1e-6 in both."""
    x, w, ct = operands(form, DTYPES[dtype])
    got, got_vjp = jax.vjp(kernel(form), x, w)
    want, want_vjp = jax.vjp(FORMS[form][-1], x, w)
    assert got.dtype == want.dtype == x.dtype
    step = 1e-6 if dtype == "f32" else 2.0 ** -8
    for a, b in zip((got,) + got_vjp(ct), (want,) + want_vjp(ct)):
        assert a.shape == b.shape and a.dtype == b.dtype
        rel = 1e-6 if a.dtype == jnp.float32 else step
        assert np.abs(f32(a) - f32(b)).max() <= rel * np.abs(f32(b)).max()


@pytest.mark.parametrize("form", list(FORMS))
def test_forward_is_causal_and_backward_anticausal(form):
    """A change at row t moves no row before t; a cotangent at row t
    reaches no row of dX after t.  Row 300: inside the second row
    tile, across a chunk's edge from row 320."""
    x, w, ct = operands(form, jnp.float32)
    at = 300
    base = kernel(form)(x, w)
    moved = kernel(form)(x.at[:, at].add(1.0), w)
    diff = np.abs(f32(moved) - f32(base)).max(axis=(0, 2))
    assert not diff[:at].any() and diff[at] > 0
    taps = w.shape[1]
    assert not diff[at + taps:].any() and diff[at + taps - 1] > 0
    only = jnp.zeros_like(ct).at[:, at].set(ct[:, at])
    dx = jax.vjp(kernel(form), x, w)[1](only)[0]
    rows = np.abs(f32(dx)).max(axis=(0, 2))
    if form == "gated":     # dC = dy * conv stays at row t
        rows = np.abs(f32(dx)[..., :128]).max(axis=(0, 2))
    assert not rows[at + 1:].any() and rows[at] > 0
    assert not rows[:at - taps + 1].any() and rows[at - taps + 1] > 0


@pytest.mark.parametrize("form", list(FORMS))
def test_an_impulse_crosses_the_seam_of_two_row_tiles(form):
    """An impulse in the last row of the first row tile shows in the
    first L - 1 rows of the next with the filter's taps, and a
    cotangent in the first row of the second tile reaches the last
    L - 1 rows of the first."""
    shape, d, taps, tr = FORMS[form][:4]
    w = jnp.asarray(np.random.default_rng(1).normal(size=(d, taps)),
                    jnp.float32)
    x = jnp.zeros(shape, jnp.float32)
    if form == "gated":     # B = u = 1 at the impulse, C = 1 everywhere
        x = x.at[..., d:2 * d].set(1.0).at[:, tr - 1, :d].set(1.0) \
            .at[:, tr - 1, 2 * d:].set(1.0)
        act = lambda v: v                       # noqa: E731
    else:
        x = x.at[:, tr - 1].set(1.0)
        act = lambda v: v / (1 + np.exp(-v))    # noqa: E731
    got = f32(kernel(form)(x, w))
    for k in range(taps):   # row tr - 1 + k reads the impulse at tap L-1-k
        np.testing.assert_allclose(
            got[0, tr - 1 + k], act(f32(w)[:, taps - 1 - k]), rtol=1e-6)
    assert not got[:, :tr - 1].any() and not got[:, tr - 1 + taps:].any()
    # the other way: dz[t] = sum_j w[:, j] * dconv[t + L-1-j]
    x, _, _ = operands(form, jnp.float32)
    if form == "gated":     # dconv = dy * C with C = 1, dB = dz * u, u = 1
        x = x.at[..., d:].set(1.0)
    else:                   # conv = 0: silu'(0) = 1/2
        x = jnp.zeros_like(x)
    only = jnp.zeros(shape[:2] + (d,), jnp.float32).at[:, tr].set(1.0)
    dx = f32(jax.vjp(kernel(form), x, w)[1](only)[0])[..., :d]
    scale = 1.0 if form == "gated" else 0.5
    for k in range(taps):
        np.testing.assert_allclose(dx[0, tr - k], scale * f32(w)[:, taps - 1 - k],
                                   rtol=1e-6)
    assert not dx[:, :tr - taps + 1].any() and not dx[:, tr + 1:].any()


@pytest.mark.parametrize("form", list(FORMS))
def test_the_rows_before_the_first_read_zeros(form):
    """Rows 0..L-2 see fewer taps, not a neighbouring tile, batch row
    or channel tile: row 0 is the last tap alone."""
    x, w, _ = operands(form, jnp.float32)
    d = w.shape[0]
    got = f32(kernel(form)(x, w))
    z = f32(x) if form == "silu" else f32(x)[..., :d] * f32(x)[..., 2 * d:]
    conv = z[:, 0] * f32(w)[:, -1]
    want = conv / (1 + np.exp(-conv)) if form == "silu" \
        else f32(x)[:, 0, d:2 * d] * conv
    np.testing.assert_allclose(got[:, 0], want, rtol=1e-5, atol=1e-6)


def run_op(x, w, activation=None):
    attrs = {"activation": activation} if activation else {}
    return get_op_impl("short_conv")(
        OpContext(jax.random.PRNGKey(0), 0),
        {"X": [x], "Filter": [w]}, attrs)["Out"][0]


@pytest.mark.parametrize("case", ["op_sweep", "odd_width", "short_rows",
                                  "long_filter", "wide_gated_tile"])
def test_the_rule_leaves_other_shapes_to_the_composition(case):
    """The op sweep's 9 x 6 case, a width that is no multiple of 128,
    rows without a whole tile, more taps than a halo, a gated tile
    that would not fit VMEM: the composition's result, unchanged."""
    t, d, taps, gated = {
        "op_sweep": (9, 6, 3, True), "odd_width": (512, 192, 4, False),
        "short_rows": (96, 128, 4, False), "long_filter": (512, 128, 10, False),
        "wide_gated_tile": (8192, 32768, 3, True)}[case]
    assert not sc.short_conv_kernel_takes(t, d, taps, gated)
    if case == "wide_gated_tile":
        return
    r = np.random.default_rng(2)
    x = jnp.asarray(r.normal(size=(2, t, (3 if gated else 1) * d)),
                    jnp.float32)
    w = jnp.asarray(r.normal(size=(d, taps)), jnp.float32)
    before = runtime_stats.snapshot()
    got = run_op(x, w, None if gated else "silu")
    took = runtime_stats.delta(before)
    assert (took["short_convs_kernel"], took["short_convs_xla"]) == (0, 1)
    want = (decoder._short_conv if gated else decoder._silu_conv)(x, w)
    np.testing.assert_array_equal(f32(got), f32(want))


@pytest.mark.parametrize("form", list(FORMS))
def test_the_op_takes_the_kernels_and_counts_them(form):
    """The op sends the cells' kind of shape to the kernels (same
    result as calling them), counts a call traced each way, and a
    gradient of the op traces it once."""
    x, w, ct = operands(form, jnp.bfloat16)
    shape, d, taps = FORMS[form][:3]
    assert sc.short_conv_kernel_takes(shape[1], d, taps, form == "gated")
    assert sc.short_conv_kernel_takes(16384, 8192, 4)
    assert sc.short_conv_kernel_takes(8192, 2048, 3, True)
    assert sc.short_conv_kernel_takes(8192, 2048, 3, True, itemsize=4)
    activation = "silu" if form == "silu" else None

    def loss(x, w):
        return jnp.sum(run_op(x, w, activation).astype(jnp.float32)
                       * ct.astype(jnp.float32))

    before = runtime_stats.snapshot()
    dx, dw = jax.grad(loss, argnums=(0, 1))(x, w)
    took = runtime_stats.delta(before)
    assert (took["short_convs_kernel"], took["short_convs_xla"]) == (1, 0)
    want = jax.vjp(FORMS[form][-1], x, w)[1](ct)
    assert np.abs(f32(dx) - f32(want[0])).max() <= 2.0 ** -8 * np.abs(
        f32(want[0])).max()
    np.testing.assert_allclose(f32(dw), f32(want[1]), rtol=1e-4, atol=1e-4)


def test_a_program_build_neither_traces_nor_counts_the_op():
    """`short_conv`'s layer declares its output's shape; shape
    inference at the stand-in batch must not trace a kernel."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    before = runtime_stats.snapshot()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[512, 256], dtype="float32")
        y = layers.short_conv(x, 4, activation="silu")
        g = layers.short_conv(layers.data("bcu", shape=[512, 384],
                                          dtype="float32"), 3)
    assert tuple(y.shape)[1:] == (512, 256) and tuple(g.shape)[1:] == (512, 128)
    took = runtime_stats.delta(before)
    assert (took["short_convs_kernel"], took["short_convs_xla"]) == (0, 0)
