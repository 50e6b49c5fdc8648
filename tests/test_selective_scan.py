"""The `selective_scan` op (`ops/decoder.py`, `ops/pallas/
selective_scan.py`) against the position-by-position recurrence: the
forward and all seven gradients, the XLA lowering and the two Pallas
kernels through the interpreter, across a chunk boundary (the state and
the adjoint carried in VMEM from chunk to chunk, over two channel
tiles), at a T that is no whole chunk (falls back, and the counter says
so), with a step large enough that a decay underflows to 0, and the
bias of `short_conv`.

Tolerance: float32 on both sides; the chunked forms sum in another
order and the kernels' softplus is a series where the step is small:
2e-5 of the largest entry (largest seen 2e-6).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpContext, get_op_impl
from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops import decoder as ops_decoder
from paddle_tpu.ops.pallas import selective_scan as scan
from paddle_tpu.ops.pallas import short_conv as conv_kernels
from op_test import with_pull_back

TOL = 2e-5
SLOTS = ("U", "Delta", "ALog", "B", "C", "D", "DeltaBias")


def recurrence(u, delta, a_log, b, c, d, bias):
    """The op as it is written, one position at a time."""
    dt = jax.nn.softplus(delta + bias)
    a = -jnp.exp(a_log)

    def step(s, xs):
        dt_t, u_t, b_t, c_t = xs
        s = jnp.exp(dt_t[..., None] * a) * s \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return s, jnp.einsum("nds,ns->nd", s, c_t) + d * u_t

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (dt, u, b, c))
    _, y = jax.lax.scan(
        step, jnp.zeros(u.shape[:1] + a.shape, jnp.float32), xs)
    return jnp.moveaxis(y, 0, 1)


def operands(n, t, d, s=16, seed=0, bias=(-6.0, -2.0)):
    r = np.random.default_rng(seed)
    f32 = jnp.float32
    return (jnp.asarray(r.normal(size=(n, t, d)), f32),
            jnp.asarray(r.normal(size=(n, t, d)) * 0.5, f32),
            jnp.asarray(np.log(np.tile(np.arange(1, s + 1.0), (d, 1))), f32),
            jnp.asarray(r.normal(size=(n, t, s)), f32),
            jnp.asarray(r.normal(size=(n, t, s)), f32),
            jnp.asarray(r.normal(size=(d,)), f32),
            jnp.asarray(r.uniform(*bias, size=(d,)), f32))


def op(*xs):
    impl = get_op_impl("selective_scan")
    return impl(OpContext(None), {k: [x] for k, x in zip(SLOTS, xs)},
                {})["Out"][0]


def check(xs, kernel, tol=TOL):
    t, d, s = xs[0].shape[1], xs[0].shape[2], xs[2].shape[1]
    assert scan.selective_scan_takes(t, d, s) == kernel
    ct = jnp.asarray(np.random.default_rng(9).normal(size=xs[0].shape),
                     jnp.float32)
    before = runtime_stats.snapshot()
    got = with_pull_back(op, ct)(*xs)
    took = runtime_stats.delta(before)
    want = with_pull_back(recurrence, ct)(*xs)
    for name, g, w in zip(("y",) + SLOTS, got, want):
        w = np.asarray(w)
        assert np.abs(w).max() > 0, name
        np.testing.assert_allclose(np.asarray(g), w, rtol=0,
                                   atol=tol * np.abs(w).max(), err_msg=name)
    return took


def test_the_kernels_across_a_chunk_boundary_and_two_channel_tiles():
    # 2 sequences x 2 chunks of 256; 384 channels: three forward tiles of
    # 128, three backward tiles
    took = check(operands(2, 2 * scan.CHUNK, 384), kernel=True)
    # the forward rule and the backward rule: a kernel call each
    assert took["selective_scans_kernel"] == 2
    assert took["selective_scans_xla"] == 0
    assert took["selective_scan_chunks"] == 2 * (2 * 2)


@pytest.mark.parametrize("t, d, s", [(100, 48, 16), (2 * scan.XLA_CHUNK, 128, 8)])
def test_a_shape_the_kernels_do_not_tile_falls_back_and_says_so(t, d, s):
    took = check(operands(1, t, d, s), kernel=False)
    assert took["selective_scans_kernel"] == 0
    assert took["selective_scan_chunks"] == 0
    assert took["selective_scans_xla"] > 0


@pytest.mark.parametrize("t, kernel", [(scan.CHUNK, True), (96, False)])
def test_a_step_so_large_that_a_decay_underflows(t, kernel):
    """dt up to ~12 against rates up to 16: exp(-190) is 0 in float32.
    Nothing divides by a decay: the state restarts and the gradients
    stay finite and right."""
    xs = operands(1, t, 128, bias=(4.0, 12.0))
    assert float(jnp.exp(-16.0 * 10.0)) == 0.0
    check(xs, kernel)


def test_softplus_holds_float32_where_the_step_is_small():
    x = jnp.linspace(-16.0, 16.0, 4001, dtype=jnp.float32)
    want = np.logaddexp(np.asarray(x, np.float64), 0.0)
    np.testing.assert_allclose(np.asarray(scan.softplus(x), np.float64),
                               want, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(jax.vmap(jax.grad(scan.softplus))(x)),
        1.0 / (1.0 + np.exp(-np.asarray(x, np.float64))), rtol=1e-6)


@pytest.mark.parametrize("t, d", [(128, 256), (40, 24)])
def test_short_conv_adds_its_bias_before_the_activation(t, d):
    """silu(conv(x) + b): the op against the composition written out,
    by the kernels (whole tiles) and by the XLA lowering, with the
    bias's gradient; without a bias the op is what it was."""
    r = np.random.default_rng(0)
    x, ct = (jnp.asarray(r.normal(size=(2, t, d)), jnp.float32)
             for _ in range(2))
    w = jnp.asarray(r.normal(size=(d, 4)), jnp.float32)
    b = jnp.asarray(r.normal(size=(d,)), jnp.float32)
    kernel = conv_kernels.short_conv_kernel_takes(t, d, 4, False, 4)
    assert kernel == (d == 256)
    impl = get_op_impl("short_conv")

    def op(x, w, b=None):
        ins = {"X": [x], "Filter": [w]}
        if b is not None:
            ins["Bias"] = [b]
        return impl(OpContext(None), ins, {"activation": "silu"})["Out"][0]

    def written_out(x, w, b):
        padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        return jax.nn.silu(sum(w[:, j] * padded[:, j:j + t]
                               for j in range(4)) + b)

    before = runtime_stats.snapshot()
    y, vjp = jax.vjp(op, x, w, b)
    took = runtime_stats.delta(before)
    want_y, want_vjp = jax.vjp(written_out, x, w, b)
    for g, want in zip((y,) + vjp(ct), (want_y,) + want_vjp(ct)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    assert took["short_conv_bias_calls"] == 1
    assert (took["short_convs_kernel"], took["short_convs_xla"]) == (
        (1, 0) if kernel else (0, 1))
    np.testing.assert_allclose(
        np.asarray(op(x, w)), np.asarray(written_out(x, w, 0.0)),
        rtol=1e-5, atol=1e-5)


def test_a_bias_on_the_gated_form_is_refused():
    impl = get_op_impl("short_conv")
    with pytest.raises(ValueError, match="Bias"):
        impl(OpContext(None), {"X": [jnp.zeros((1, 8, 12))],
                               "Filter": [jnp.zeros((4, 3))],
                               "Bias": [jnp.zeros((4,))]}, {})
    assert ops_decoder._silu_conv(jnp.ones((1, 4, 2)), jnp.ones((2, 3)),
                                  None).shape == (1, 4, 2)
