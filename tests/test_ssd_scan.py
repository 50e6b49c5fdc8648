"""The `ssd_scan` op (`ops/decoder.py`, `ops/pallas/ssd_scan.py`)
against the position-by-position recurrence on the (heads, d_head,
d_state) state: the forward and all seven gradients, the XLA lowering
(any chunk, T padded, several groups) and the two Pallas kernels
through the interpreter, across a chunk boundary and two head blocks
(the state and dL/dS carried in VMEM from chunk to chunk, dB and dC
summed over the head blocks in the output block), at a T that is no
whole chunk (falls back, and the counter says so), with a step large
enough that a chunk's decay underflows to 0, dB and dC as the sum over
heads, and the gated norm.

Tolerance: float32 on both sides at "highest"; the chunked form sums in
another order, takes exp of a DIFFERENCE of cumulative sums where the
recurrence multiplies decays, and the op's softplus is a series where
the step is small: 2e-5 of the largest entry (largest seen 3e-6).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpContext, get_op_impl
from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops.pallas import ssd_scan as scan
from op_test import with_pull_back

TOL = 2e-5
SLOTS = ("X", "Dt", "ALog", "B", "C", "D", "DtBias")


def recurrence(x, dt, a_log, b, c, d, bias, groups=1):
    """The op as it is written, one position at a time."""
    n, t, width = x.shape
    heads = a_log.shape[0]
    p, states = width // heads, b.shape[2] // groups
    dt = jax.nn.softplus(dt + bias)
    a = -jnp.exp(a_log)
    of_head = np.arange(heads) // (heads // groups)

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs            # (N,H,P) (N,H) (N,G,S) x 2
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, of_head, None, :]
        return s, jnp.einsum("nhps,nhs->nhp", s, c_t[:, of_head]) \
            + d[:, None] * x_t

    xs = (x.reshape(n, t, heads, p), dt, b.reshape(n, t, groups, states),
          c.reshape(n, t, groups, states))
    _, y = jax.lax.scan(step, jnp.zeros((n, heads, p, states), jnp.float32),
                        tuple(jnp.moveaxis(v, 1, 0) for v in xs))
    return jnp.moveaxis(y, 0, 1).reshape(n, t, width)


def operands(n, t, heads, p=scan.HEAD_DIM, s=scan.STATE, groups=1, seed=0,
             bias=(-6.0, -2.0)):
    r = np.random.default_rng(seed)
    f32 = jnp.float32

    def draw(*shape, scale=1.0):
        return jnp.asarray(r.normal(size=shape) * scale, f32)

    return (draw(n, t, heads * p), draw(n, t, heads, scale=0.5),
            jnp.asarray(np.log(np.arange(1, heads + 1.0)), f32),
            draw(n, t, groups * s), draw(n, t, groups * s), draw(heads),
            jnp.asarray(r.uniform(*bias, size=(heads,)), f32))


def op(*xs, chunk=scan.CHUNK, groups=1):
    impl = get_op_impl("ssd_scan")
    return impl(OpContext(None), {k: [x] for k, x in zip(SLOTS, xs)},
                {"chunk_size": chunk, "n_groups": groups})["Out"][0]


def check(xs, kernel, chunk=scan.CHUNK, groups=1, tol=TOL):
    t, heads = xs[0].shape[1], xs[2].shape[0]
    assert scan.ssd_scan_takes(
        t, heads, xs[0].shape[2] // heads, xs[3].shape[2] // groups, groups,
        chunk) == kernel
    ct = jnp.asarray(np.random.default_rng(9).normal(size=xs[0].shape),
                     jnp.float32)
    before = runtime_stats.snapshot()
    got = with_pull_back(
        functools.partial(op, chunk=chunk, groups=groups), ct)(*xs)
    took = runtime_stats.delta(before)
    want = with_pull_back(
        functools.partial(recurrence, groups=groups), ct)(*xs)
    for name, g, w in zip(("y",) + SLOTS, got, want):
        w = np.asarray(w)
        assert np.abs(w).max() > 0, name
        np.testing.assert_allclose(np.asarray(g), w, rtol=0,
                                   atol=tol * np.abs(w).max(), err_msg=name)
    return took


def test_the_kernels_across_a_chunk_boundary_and_two_head_blocks():
    # 2 sequences x 2 chunks of 256; 16 heads: two blocks of 8
    took = check(operands(2, 2 * scan.CHUNK, 2 * scan.HEAD_BLOCK),
                 kernel=True)
    # the forward rule and the backward rule: a kernel call each
    assert took["ssd_scans_kernel"] == 2
    assert took["ssd_scans_xla"] == 0
    assert took["ssd_scan_chunks"] == 2 * (2 * 2)


@pytest.mark.parametrize("t, heads, p, s, chunk, groups", [
    (40, 4, 16, 32, 8, 1),                  # the parity preset's sizes
    (300, 8, 64, 128, 256, 1),              # T is no whole chunk
    (32, 4, 16, 32, 8, 2)])                 # two groups of B and C
def test_a_shape_the_kernels_do_not_tile_falls_back_and_says_so(
        t, heads, p, s, chunk, groups):
    # nor another chunk, other states, or heads that fill no block
    assert scan.ssd_scan_takes(256, 8, 64, 128, 1, 256)
    assert not scan.ssd_scan_takes(256, 8, 64, 128, 1, 64)
    assert not scan.ssd_scan_takes(256, 8, 64, 64, 1, 256)
    assert not scan.ssd_scan_takes(256, 12, 64, 128, 1, 256)
    took = check(operands(1, t, heads, p, s, groups), kernel=False,
                 chunk=chunk, groups=groups)
    assert took["ssd_scans_kernel"] == 0
    assert took["ssd_scan_chunks"] == 0
    assert took["ssd_scans_xla"] > 0


@pytest.mark.parametrize("t, kernel", [(scan.CHUNK, True), (96, False)])
def test_a_step_so_large_that_a_chunks_decay_underflows(t, kernel):
    """dt up to ~12 against rates up to 8: a chunk's cumulative decay
    exp(-25000) and most of its mask are 0 in float32.  No exponent is
    positive and nothing divides by a decay: the state restarts and the
    gradients stay finite and right."""
    xs = operands(1, t, 8, bias=(4.0, 12.0))
    assert float(jnp.exp(-8.0 * 10.0 * 16)) == 0.0
    check(xs, kernel, chunk=scan.CHUNK if kernel else 32)


@pytest.mark.parametrize("kernel", [True, False])
def test_db_and_dc_are_the_sums_over_the_heads(kernel):
    """Sixteen heads that all carry head 0's x, step, rate and skip: y
    is head 0's in every head, and dB and dC are sixteen times what one
    head alone gives (B and C are shared by the group's heads)."""
    t, heads = (scan.CHUNK, 16) if kernel else (32, 4)
    sizes = {} if kernel else dict(p=16, s=32)
    chunk = scan.CHUNK if kernel else 8
    x, dt, a_log, b, c, d, bias = operands(1, t, 1, **sizes)
    ct = jnp.asarray(np.random.default_rng(3).normal(size=x.shape),
                     jnp.float32)

    def tiled(v, axis):
        return jnp.concatenate([v] * heads, axis=axis)

    def many(b, c):
        return op(tiled(x, 2), tiled(dt, 2), tiled(a_log, 0), b, c,
                  tiled(d, 0), tiled(bias, 0), chunk=chunk)

    def one(b, c):
        return recurrence(x, dt, a_log, b, c, d, bias)

    assert scan.ssd_scan_takes(t, heads, x.shape[2], b.shape[2],
                               chunk=chunk) == kernel
    y, *got = with_pull_back(many, tiled(ct, 2))(b, c)
    want_y, *want = with_pull_back(one, ct)(b, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(tiled(want_y, 2)),
                               rtol=0, atol=TOL * np.abs(want_y).max())
    for g, w in zip(got, want):
        w = heads * np.asarray(w)
        np.testing.assert_allclose(np.asarray(g), w, rtol=0,
                                   atol=TOL * np.abs(w).max())


def test_operands_that_are_no_heads_over_positions_raise():
    x, dt, a_log, b, c, d, bias = operands(1, 16, 4, 16, 32)
    with pytest.raises(ValueError, match="are not H heads"):
        op(x[:, :, :63], dt, a_log, b, c, d, bias, chunk=8)
    with pytest.raises(ValueError, match="are not H heads"):
        op(x, dt, a_log, b, c[:, :8], d, bias, chunk=8)
    with pytest.raises(ValueError, match="are not H heads"):
        op(x, dt, a_log, b, c, d, bias, chunk=8, groups=3)


def test_the_gated_norm_gates_before_it_normalises():
    """rms_norm(x * silu(z)) * w against the composition written out,
    with all three gradients; NOT rms_norm(x) * w * silu(z), which the
    `rms_norm` op's own gate computes."""
    r = np.random.default_rng(1)
    x, z, ct = (jnp.asarray(r.normal(size=(2, 5, 64)), jnp.float32)
                for _ in range(3))
    w = jnp.asarray(r.normal(size=(64,)), jnp.float32)
    impl = get_op_impl("gated_rms_norm")
    attrs = {"epsilon": 1e-5}

    def fused(x, z, w):
        return impl(OpContext(None), {"X": [x], "Gate": [z], "Scale": [w]},
                    attrs)["Y"][0]

    def written_out(x, z, w):
        g = x * jax.nn.silu(z)
        return g * jax.lax.rsqrt(
            jnp.mean(g * g, axis=-1, keepdims=True) + 1e-5) * w

    before = runtime_stats.snapshot()
    y, vjp = jax.vjp(fused, x, z, w)
    assert runtime_stats.delta(before)["gated_rms_norm_calls"] == 1
    want_y, want_vjp = jax.vjp(written_out, x, z, w)
    for g, want in zip((y,) + vjp(ct), (want_y,) + want_vjp(ct)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    after = get_op_impl("rms_norm")(
        OpContext(None), {"X": [x], "Gate": [z], "Scale": [w]},
        {"epsilon": 1e-5})["Y"][0]
    assert np.abs(np.asarray(after) - np.asarray(y)).max() > 0.1


def test_the_registered_cost_is_the_chunked_forms_products():
    """What `observe/cost.py` injects at the custom calls: the FLOP the
    kernels execute, 4.26 M a token a layer forward at 64 heads (the
    sequential form has 2.10 M)."""
    shapes = [((1, 8192, 4096), 2)]
    flops, nbytes = scan.fwd_cost(shapes, None)
    assert nbytes is None
    assert flops / 8192 == 2 * 256 * 128 + 64 * (2 * 256 * 64 + 4 * 128 * 64)
    assert scan.bwd_cost(shapes, None)[0] / 8192 == 3 * 2 * 256 * 128 + 64 * (
        3 * 2 * 256 * 64 + 5 * 2 * 128 * 64)
