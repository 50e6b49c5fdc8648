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


def joint_op(xbc, dt, a_log, d, bias, states, chunk=scan.CHUNK, groups=1):
    """The op on its own operand: [x | B | C] as one array."""
    impl = get_op_impl("ssd_scan")
    ins = {"XBC": xbc, "Dt": dt, "ALog": a_log, "D": d, "DtBias": bias}
    return impl(OpContext(None), {k: [x] for k, x in ins.items()},
                {"chunk_size": chunk, "n_groups": groups,
                 "d_state": states})["Out"][0]


def op(x, dt, a_log, b, c, d, bias, chunk=scan.CHUNK, groups=1):
    """The op with x, B and C laid side by side for it: autodiff cuts
    the joint gradient back into the three."""
    return joint_op(jnp.concatenate([x, b, c], axis=2), dt, a_log, d, bias,
                    b.shape[2] // groups, chunk, groups)


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
        op(x, dt, a_log, b, c, d, bias, chunk=8, groups=3)
    a = -jnp.exp(a_log)
    with pytest.raises(ValueError, match="are not H heads"):
        scan.scan_joint(jnp.concatenate([x, b, c], axis=2), dt[:, :, :3], a,
                        d, d_state=32, chunk=8)
    # what only operands that lie apart can get wrong
    with pytest.raises(ValueError, match="are not H heads"):
        scan.ssd_scan(x, dt, a, b, c[:, :8], d, chunk=8)
    with pytest.raises(ValueError, match="are not H heads"):
        scan.ssd_scan(x, dt, a, b[:, :, :31], c[:, :, :31], d, chunk=8,
                      groups=2)


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
    shapes = [((1, 8192, 4096 + 2 * 128), 2)]        # xBC first
    flops, nbytes = scan.fwd_cost(shapes, None)
    assert nbytes is None
    assert flops / 8192 == 2 * 256 * 128 + 64 * (2 * 256 * 64 + 4 * 128 * 64)
    assert scan.bwd_cost(shapes, None)[0] / 8192 == 3 * 2 * 256 * 128 + 64 * (
        3 * 2 * 256 * 64 + 5 * 2 * 128 * 64)


# -- the scan's operand is the convolution's result, whole (PR 70) ------
#
# The kernels block x, B and C out of xBC's lanes (one array under three
# block specs), write d xBC as ONE array (dB and dC rounded once into its
# last 256 lanes), and a recompute segment keeps xBC beside y and the
# entry states, so its backward pass convolves no second time.

def joint_operands(heads, dtype, t=2 * scan.CHUNK, seed=4):
    """(xbc, dt, a, d) as `scan_joint` takes them (the step after its
    softplus, the rates negative) and a cotangent of y."""
    r = np.random.default_rng(seed)
    f32 = jnp.float32
    width = heads * scan.HEAD_DIM
    step = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), size=(1, t, heads)))
    return (jnp.asarray(r.normal(size=(1, t, width + 2 * scan.STATE)), dtype),
            jnp.asarray(step, f32),
            -jnp.arange(1, heads + 1, dtype=f32),
            jnp.asarray(1 + 0.1 * r.normal(size=(heads,)), f32)), \
        jnp.asarray(r.normal(size=(1, t, width)), dtype)


def sliced(xbc, dt, a, d):
    """`scan_xla` on the three slices of xBC: what the kernels are held
    to, and the form the step ran before (a split, then the scan)."""
    width = xbc.shape[2] - 2 * scan.STATE
    return scan.scan_xla(xbc[..., :width], dt, a,
                         xbc[..., width:width + scan.STATE],
                         xbc[..., width + scan.STATE:], d)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it, a
    kernel's own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _kernels(jaxpr):
    import re

    return sorted(re.search(r"pallas_(\w+)",
                            str(e.source_info.name_stack)).group(1)
                  for e in _eqns(jaxpr) if e.primitive.name == "pallas_call")


@pytest.mark.parametrize("heads", [8, 16])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 6e-3)],
                         ids=["float32", "bfloat16"])
def test_the_kernels_on_the_joint_operand(heads, dtype, tol):
    """y, d xBC (x's lanes, B's and C's), d dt, dA and dD of the kernels
    on xBC as it lies against `scan_xla` on its three slices, by the
    norm of the difference over the norm: float32 to rounding; under
    bfloat16 operands two roundings of every result to bfloat16 (3.1e-3
    the largest seen here, d xBC; the chip's parity run holds the same
    seven under 3e-3 at 8192 positions)."""
    xs, ct = joint_operands(heads, dtype)
    assert scan.ssd_scan_takes(xs[0].shape[1], heads, scan.HEAD_DIM,
                               scan.STATE)
    before = runtime_stats.snapshot()
    got = with_pull_back(scan.scan_joint, ct)(*xs)
    took = runtime_stats.delta(before)
    assert (took["ssd_scans_kernel"], took["ssd_scans_xla"]) == (2, 0)
    want = with_pull_back(sliced, ct)(*xs)
    width = heads * scan.HEAD_DIM
    assert got[1].shape == xs[0].shape and got[1].dtype == dtype

    def parts(y, dxbc, *rest):
        return (y, dxbc[..., :width], dxbc[..., width:width + scan.STATE],
                dxbc[..., width + scan.STATE:]) + rest

    for name, g, w in zip(("y", "dx", "dB", "dC", "ddt", "dA", "dD"),
                          parts(*got), parts(*want)):
        g, w = (np.asarray(v, np.float64) for v in (g, w))
        assert np.linalg.norm(w) > 0, name
        assert np.linalg.norm(g - w) <= tol * np.linalg.norm(w), name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_operands_that_lie_apart_are_the_joint_call_to_the_bit(dtype):
    """`ssd_scan(x, dt, a, b, c, d)` lays the three side by side and is
    `scan_joint` on that: y and every gradient bit for bit, d x, dB and
    dC the three cuts of d xBC."""
    (xbc, dt, a, d), ct = joint_operands(8, dtype)
    width = xbc.shape[2] - 2 * scan.STATE
    x, b, c = (xbc[..., :width], xbc[..., width:width + scan.STATE],
               xbc[..., width + scan.STATE:])
    y, dx, ddt, da, db, dc, dd = with_pull_back(scan.ssd_scan, ct)(
        x, dt, a, b, c, d)
    want = with_pull_back(scan.scan_joint, ct)(xbc, dt, a, d)
    for g, w in zip((y, jnp.concatenate([dx, db, dc], axis=2), ddt, da, dd),
                    want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def test_the_backward_pass_writes_one_gradient_of_xbc_and_glues_nothing():
    """The backward rule's jaxpr: `ssd_scan_bwd` alone, ONE result as
    wide as xBC (no d x beside a dB and a dC), and no `concatenate` of
    anything as wide as x; the forward's: no slice as wide as x (B^T and
    C^T, 128 lanes each, are all that is cut from xBC)."""
    (xbc, dt, a, d), ct = joint_operands(8, jnp.bfloat16)
    width = xbc.shape[2] - 2 * scan.STATE
    _, pull = jax.vjp(scan.scan_joint, xbc, dt, a, d)
    backward = jax.make_jaxpr(pull)(ct).jaxpr
    assert _kernels(backward) == ["ssd_scan_bwd"]
    call, = (e for e in _eqns(backward) if e.primitive.name == "pallas_call")
    wide = [v.aval.shape for v in call.outvars
            if v.aval.shape[:2] == xbc.shape[:2]]   # a row a position
    assert wide == [xbc.shape]
    assert backward.outvars[0].aval.shape == xbc.shape
    both = list(_eqns(backward)) + list(_eqns(
        jax.make_jaxpr(scan.scan_joint)(xbc, dt, a, d).jaxpr))
    assert not [e for e in both if e.primitive.name == "concatenate"
                and e.outvars[0].aval.shape[-1] >= width]
    assert not [e for e in both if e.primitive.name in (
        "slice", "dynamic_slice") and e.outvars[0].aval.shape[-1] >= width]


@pytest.mark.parametrize("policy", [True, False], ids=["kept", "no_policy"])
def test_a_segment_around_convolution_and_scan_convolves_once(policy):
    """A recompute segment (`jax.checkpoint` under the executor's
    policy) around the mixer's biased convolution and its scan: the
    forward rule names THREE values, y, the entry states and xBC, so
    the differentiated step holds each forward kernel once; with the
    policy taken away (the inputs alone are kept) both run twice."""
    from paddle_tpu.ops import pallas as pallas_tier

    heads, t, taps = 8, 2 * scan.CHUNK, 4
    (xbc, dt, a, d), ct = joint_operands(heads, jnp.bfloat16)
    r = np.random.default_rng(2)
    w = jnp.asarray(r.normal(size=(xbc.shape[2], taps)) / taps, jnp.float32)
    bias = jnp.asarray(r.normal(size=(xbc.shape[2],)), jnp.float32)
    conv = get_op_impl("short_conv")

    def mixer(u, w, bias, dt, a, d):
        with pallas_tier.tracing_segment():
            v = conv(OpContext(None), {"X": [u], "Filter": [w],
                                       "Bias": [bias]},
                     {"activation": "silu"})["Out"][0]
            return scan.scan_joint(v, dt, a, d)

    segment = jax.checkpoint(
        mixer, policy=pallas_tier.segment_policy() if policy else None)

    def step(*xs):
        return jax.vjp(segment, *xs)[1](ct)

    before = runtime_stats.snapshot()
    jaxpr = jax.make_jaxpr(step)(xbc, w, bias, dt, a, d).jaxpr
    took = runtime_stats.delta(before)
    assert took["short_convs_kernel"] >= 1 and took["short_convs_xla"] == 0
    names = [e.params["name"] for e in _eqns(jaxpr)
             if e.primitive.name == "name"]
    assert set(names) == set(pallas_tier.SSD_RESIDUALS) and len(
        pallas_tier.SSD_RESIDUALS) == 3
    y_bytes = t * heads * scan.HEAD_DIM * 2
    states = (t // scan.CHUNK) * (heads // 2) * scan.STATE * scan.LANES * 4
    calls = took["recompute_kept_residuals"]
    assert calls >= 1 and took["recompute_kept_bytes"] == calls * (
        y_bytes + states + xbc.size * 2)
    again = [] if policy else ["short_conv_fwd", "ssd_scan_fwd"]
    assert _kernels(jaxpr) == sorted(
        ["short_conv_fwd", "ssd_scan_fwd", "short_conv_bwd", "ssd_scan_bwd"]
        + again)
