"""Pallas flash attention vs composed XLA reference (interpret mode on
CPU; the same kernel runs compiled on TPU)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _ref_attention(q, k, v, bias=None, scale=None, causal=False):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum("nhqd,nhkd->nhqk", q, k) * scale
    if bias is not None:
        s = s + bias
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((t_q, t_k), bool)), s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    return jnp.einsum("nhqk,nhkd->nhqd", p.astype(q.dtype), v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    import paddle_tpu.ops.pallas.flash_attention as fa

    rng = np.random.RandomState(0)
    n, h, t, d = 1, 2, 256, 128
    q = jnp.asarray(rng.randn(n, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(n, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(n, h, t, d), jnp.float32)
    got = _interpreted(fa, q, k, v, None, None, causal)
    want = _ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_padding_bias():
    import paddle_tpu.ops.pallas.flash_attention as fa

    rng = np.random.RandomState(1)
    n, h, t, d = 2, 1, 128, 128
    q = jnp.asarray(rng.randn(n, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(n, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(n, h, t, d), jnp.float32)
    lens = np.array([96, 128])
    bias = np.zeros((n, 1, 1, t), np.float32)
    for i, L in enumerate(lens):
        bias[i, :, :, L:] = -1e9
    bias = jnp.asarray(bias)
    got = _interpreted(fa, q, k, v, bias, None, False)
    want = _ref_attention(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("t,causal", [(320, False), (384, True), (320, True)])
def test_flash_nondivisible_tk(t, causal):
    """Regression: t_k % block_k != 0 must mask the padded k-tail
    (round-1 review finding)."""
    import paddle_tpu.ops.pallas.flash_attention as fa

    rng = np.random.RandomState(3)
    n, h, d = 1, 2, 128
    q = jnp.asarray(rng.randn(n, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(n, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(n, h, t, d), jnp.float32)
    got = _interpreted(fa, q, k, v, None, None, causal, block_k=256)
    want = _ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_grad_matches_reference():
    import paddle_tpu.ops.pallas.flash_attention as fa

    rng = np.random.RandomState(2)
    n, h, t, d = 1, 1, 128, 128
    q = jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.5
    k = jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.5
    v = jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.5

    def loss_flash(q, k, v):
        return jnp.sum(_interpreted(fa, q, k, v, None, None, False) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref_attention(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("t,causal,with_bias",
                         [(320, True, False), (320, False, True),
                          (256, True, True)])
def test_flash_bwd_kernel_edge_cases(t, causal, with_bias):
    """Tiled Pallas backward: non-divisible lengths, causal masking and
    bias gradients must all match the XLA composition."""
    import paddle_tpu.ops.pallas.flash_attention as fa

    rng = np.random.RandomState(7)
    n, h, d = 1, 2, 128
    q = jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.3
    bias = None
    if with_bias:
        b = np.zeros((n, 1, 1, t), np.float32)
        b[:, :, :, t - 32:] = -1e9
        bias = jnp.asarray(b)

    def loss_flash(q, k, v):
        o = _interpreted(fa, q, k, v, bias, None, causal, block_q=128,
                         block_k=256)
        return jnp.sum(o ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref_attention(q, k, v, bias=bias,
                                      causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_flash_bwd_bias_grad():
    """db must equal the XLA-composed bias gradient (per-batch additive
    key bias, summed over heads and q)."""
    import paddle_tpu.ops.pallas.flash_attention as fa

    rng = np.random.RandomState(8)
    n, h, t, d = 2, 2, 128, 128
    q = jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.3
    bias0 = jnp.asarray(rng.randn(n, 1, 1, t).astype(np.float32)) * 0.1

    def loss_flash(b):
        return jnp.sum(_interpreted(fa, q, k, v, b, None, False) ** 2)

    def loss_ref(b):
        return jnp.sum(_ref_attention(q, k, v, bias=b) ** 2)

    db_flash = jax.grad(loss_flash)(bias0)
    db_ref = jax.grad(loss_ref)(bias0)
    np.testing.assert_allclose(np.asarray(db_flash), np.asarray(db_ref),
                               rtol=5e-3, atol=5e-3)


# -- the backward pass: one kernel where the call allows it -----------------
#
# `flash_attention.py _flash_bwd`: the single kernel (p and ds once a
# block pair, dq of the head's whole sequence in float32 VMEM) against
# the two kernels that hold blocks only, which recompute the scores; the
# rule that chooses between them from the call alone; the counters.
# Mosaic's own checks are tests/test_chip_compile_flash.py's.

def _backward_path(monkeypatch, path):
    """Send the backward pass down `path` the only way there is: the
    shape rule's budget (no option chooses)."""
    import paddle_tpu.ops.pallas.flash_attention as fa

    monkeypatch.setattr(fa, "FUSED_ACCUMULATOR_BUDGET",
                        {"one_kernel": 1 << 40, "two_kernels": 0}[path])


def _took(before):
    from paddle_tpu.observe.monitoring import runtime_stats

    took = runtime_stats.delta(before)
    return (took["flash_attention_backward_fused"],
            took["flash_attention_backward_split"])


def _snapshot():
    from paddle_tpu.observe.monitoring import runtime_stats

    return runtime_stats.snapshot()


def _qkvw(n, h, t, d, seed, dtype=jnp.float32):
    """q, k, v and a cotangent weight, (N, H, T, D)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [(jax.random.normal(k, (n, h, t, d)) * 0.5).astype(dtype)
            for k in ks]


def _head_major(x):
    n, h, t, d = x.shape
    return jnp.moveaxis(x, 1, 2).reshape(n, t, h * d)


def _flash_pull(q, k, v, w, layout, causal, block_q, block_k):
    """The kernels' forward pass, run once, and what gives dq, dk, dv of
    sum(o * w) through them, as (N, H, T, D) whatever the layout the
    kernels saw: the backward rule is traced whenever it is called, so
    one forward serves both backward paths."""
    import paddle_tpu.ops.pallas.flash_attention as fa

    n, h, t, d = q.shape
    if layout == "nthd":
        q, k, v, w = (_head_major(x) for x in (q, k, v, w))
    _, pull = jax.vjp(lambda q, k, v: fa.pallas_flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        layout=layout, n_head=h if layout == "nthd" else None), q, k, v)

    def grads():
        got = pull(w)
        if layout == "nthd":
            got = [jnp.moveaxis(g.reshape(n, t, h, d), 2, 1) for g in got]
        return got

    return grads


BLOCKS = [(1, 128, 128), (2, 128, 128), (4, 128, 128), (2, 128, 256),
          (2, 256, 128), (4, 128, 256)]
BLOCK_IDS = ["1_block", "2_blocks", "4_blocks", "wide_k", "wide_q",
             "4_blocks_wide_k"]


def _operands(blocks, block_q, block_k):
    """q, k, v and the weight of one geometry in float32: one sequence,
    two heads of 128, T of `blocks` of the larger block."""
    t = blocks * max(block_q, block_k)
    return _qkvw(1, 2, t, 128, seed=blocks + block_k)


@functools.cache
def _forward(blocks, block_q, block_k, causal, layout, dtype):
    return _flash_pull(*(x.astype(dtype)
                         for x in _operands(blocks, block_q, block_k)),
                       layout, causal, block_q, block_k)


@functools.cache
def _path_grads(path, blocks, block_q, block_k, causal, layout, dtype):
    """dq, dk, dv of one geometry through the kernels on `path` with the
    operands in `dtype`, which the counters must say the traced
    backward took.  Once a module: the two tests below read the same
    calls, and both paths the same forward pass."""
    grads = _forward(blocks, block_q, block_k, causal, layout, dtype)
    with pytest.MonkeyPatch.context() as patch:
        _backward_path(patch, path)
        before = _snapshot()
        got = grads()
        assert _took(before) == ((1, 0) if path == "one_kernel" else (0, 1))
    return got


@functools.cache
def _reference_grads(blocks, block_q, block_k, causal):
    """dq, dk, dv of the dense composition on `_path_grads`' float32
    operands of one geometry: once a module, whatever the path, the
    layout and the kernels' dtype."""
    q, k, v, w = _operands(blocks, block_q, block_k)
    return jax.jit(jax.grad(
        lambda *a: jnp.sum(_ref_attention(*a, causal=causal) * w),
        argnums=(0, 1, 2)))(q, k, v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("layout", ["nhtd", "nthd"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("blocks, block_q, block_k", BLOCKS, ids=BLOCK_IDS)
@pytest.mark.parametrize("path", ["one_kernel", "two_kernels"])
def test_both_backward_paths_give_the_reference_gradients(
        path, blocks, block_q, block_k, causal, layout, dtype):
    """dq, dk and dv, the single backward kernel and the two, over T of
    1, 2 and 4 blocks (diagonal, below-diagonal and skipped block pairs)
    and block_q != block_k (a dq block then completes off the
    diagonal's corner, and a pass over the query blocks may complete
    two or none), causal and not, in both operand layouts; the counters
    say which path a traced backward took."""
    got = _path_grads(path, blocks, block_q, block_k, causal, layout, dtype)
    want = _reference_grads(blocks, block_q, block_k, causal)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == r.shape and g.dtype == dtype, name
        if dtype == jnp.float32:    # this file's limits for a gradient
            np.testing.assert_allclose(g, r, rtol=5e-3, atol=5e-3,
                                       err_msg="d" + name)
        else:       # p and ds are cast to 8 bits of mantissa before a dot
            np.testing.assert_allclose(
                g.astype(jnp.float32), r, err_msg="d" + name,
                atol=4e-2 * float(jnp.abs(r).max()))


# three of the geometries above (their calls are made once), and four
# query blocks over ONE key block
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("layout", ["nhtd", "nthd"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("blocks, block_q, block_k", [
    (4, 128, 128), (2, 128, 256), (2, 256, 128), (1, 128, 512)],
    ids=["square", "wide_k", "wide_q", "4_q_a_k"])
def test_the_two_backward_paths_agree_to_the_bit(blocks, block_q, block_k,
                                                  causal, layout, dtype):
    """Same terms in the same order: the single kernel sums dk / dv
    over the query blocks and dq over the key blocks as the two do, from
    the same p and ds."""
    one = _path_grads("one_kernel", blocks, block_q, block_k, causal, layout,
                      dtype)
    two = _path_grads("two_kernels", blocks, block_q, block_k, causal, layout,
                      dtype)
    for a, b in zip(one, two):
        np.testing.assert_array_equal(a, b)


def test_the_call_alone_chooses_the_backward_path():
    """The single kernel holds one head's dq, 4 * d bytes a position:
    both cells' 4096 positions at d_head 128 fit the budget, a sequence
    past it does not; a bias, position offsets, a returned logsumexp,
    cross-attention and a ragged last block keep the two kernels.  A
    traced backward says which it took, and `flash_dq` exists on the
    two-kernel path only."""
    import paddle_tpu.ops.pallas.flash_attention as fa

    d = 128
    edge = fa.FUSED_ACCUMULATOR_BUDGET // (4 * d)
    fits = fa.fused_backward_fits
    assert fits(4096, 4096, d, 256, 1024)
    assert fits(4096, 4096, d, fa.DEFAULT_BWD_BLOCK_Q, fa.DEFAULT_BWD_BLOCK_K)
    assert fits(edge, edge, d, 1024, 1024)
    assert not fits(edge + 1024, edge + 1024, d, 1024, 1024)
    assert fits(2 * edge, 2 * edge, d // 2, 1024, 1024)     # bytes, not T
    assert not fits(4096, 4096, d, 256, 1024, bias=True)
    assert not fits(4096, 4096, d, 256, 1024, offsets=True)
    assert not fits(4096, 4096, d, 256, 1024, lse_cotangent=True)
    assert not fits(4096, 2048, d, 256, 1024)               # cross
    assert not fits(4000, 4000, d, 256, 1024)               # ragged
    assert fits(320, 320, d, 512, 1024)         # one block, clamped to T

    def kernels(t, n=1, h=2, **kw):
        shape = jax.ShapeDtypeStruct((n, t, h * d), jnp.bfloat16)
        bias = kw.pop("bias", None)

        def loss(q, k, v):
            out = fa.pallas_flash_attention(
                q, k, v, bias, causal=True, layout="nthd", n_head=h, **kw)
            return sum(jnp.sum(o.astype(jnp.float32))
                       for o in jax.tree.leaves(out))

        before = _snapshot()
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shape, shape, shape).as_text(debug_info=True)
        return (sorted(name for name in ("flash_fwd", "flash_dkv",
                                         "flash_dq")
                       if f"pallas_{name}" in text), *_took(before))

    one = (["flash_dkv", "flash_fwd"], 1, 0)
    two = (["flash_dkv", "flash_dq", "flash_fwd"], 0, 1)
    assert kernels(4096) == one                         # ouro-4k's call
    assert kernels(4096, n=4, h=16) == one              # olmoe-4k's
    assert kernels(edge + 1024) == two
    assert kernels(4096, bias=jnp.zeros((1, 1, 1, 4096))) == two
    assert kernels(4096, q_offset=0, k_offset=0) == two
    assert kernels(4096, return_lse=True) == two
    assert kernels(320, block_q=128, block_k=256) == two


def test_the_backward_pass_takes_its_own_blocks_where_they_divide_t():
    """The statistics are block-free, so the backward pass has blocks
    of its own; a sequence that is not a whole number of them keeps the
    forward's, and a block size the caller gives holds for both
    passes."""
    import paddle_tpu.ops.pallas.flash_attention as fa

    fq, fk = fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K
    bq, bk = fa.DEFAULT_BWD_BLOCK_Q, fa.DEFAULT_BWD_BLOCK_K

    def grids(t, **blocks):
        shape = jax.ShapeDtypeStruct((1, t, 2 * 128), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(fa.pallas_flash_attention(
                *a, causal=True, layout="nthd", n_head=2, **blocks)
                .astype(jnp.float32)), argnums=(0, 1, 2)))(*[shape] * 3)
        return [e.params["grid_mapping"].grid for e in jaxpr.eqns
                if e.primitive.name == "pallas_call"]

    def cdiv(a, b):
        return -(-a // min(b, a))

    t = 4096
    assert grids(t) == [(2, cdiv(t, fq), cdiv(t, fk)),
                        (2, cdiv(t, bk), cdiv(t, bq))]
    assert grids(t, block_q=128, block_k=256) == [(2, 32, 16), (2, 16, 32)]
    # 1280 = 5 x 256: ragged for a 1024-wide block, so the forward's
    # blocks and the two kernels
    assert grids(1280, block_q=None, block_k=1024) == [
        (2, 5, 2), (2, 2, 5), (2, 5, 2)]


def test_gradient_through_a_checkpointed_scan_body_equals_the_unrolled():
    """`ouro-4k`'s form: the call inside a `jax.checkpoint` segment
    inside a `lax.scan` body (its backward runs in the scan's transpose,
    the forward kernel a second time).  The gradient equals that of the
    Python loop without checkpoint, and ONE body is traced: one single
    backward kernel whatever the trip count."""
    import paddle_tpu.ops.pallas.flash_attention as fa

    n, h, t, d, trips = 1, 2, 512, 128, 3
    x0 = jax.random.normal(jax.random.PRNGKey(3), (n, t, h * d)) * 0.5
    w = jax.random.normal(jax.random.PRNGKey(4), (3, h * d, h * d)) \
        * (h * d) ** -0.5

    def layer(x, w):
        q, k, v = (x @ w[i] for i in range(3))
        return x + fa.pallas_flash_attention(
            q, k, v, causal=True, block_q=128, block_k=256, layout="nthd",
            n_head=h)

    def scanned(x, w):
        body = jax.checkpoint(layer)
        x, _ = jax.lax.scan(lambda c, _: (body(c, w), None), x, None,
                            length=trips)
        return jnp.sum(x ** 2)

    def unrolled(x, w):
        for _ in range(trips):
            x = layer(x, w)
        return jnp.sum(x ** 2)

    before = _snapshot()
    got = jax.grad(scanned, argnums=(0, 1))(x0, w)
    assert _took(before) == (1, 0)
    before = _snapshot()
    want = jax.grad(unrolled, argnums=(0, 1))(x0, w)
    assert _took(before) == (trips, 0)
    for g, r in zip(got, want):
        np.testing.assert_allclose(
            g, r, rtol=1e-5, atol=1e-5 * float(jnp.abs(r).max()))


# -- helpers ---------------------------------------------------------------


def _interpreted(fa, q, k, v, bias, scale, causal, **kw_extra):
    """On the CPU backend the module auto-selects Pallas interpret mode
    (flash_attention._interpret), so this just calls through."""
    return fa.pallas_flash_attention(q, k, v, bias=bias, scale=scale,
                                     causal=causal, **kw_extra)


def test_transformer_flash_pallas_matches_xla_flash():
    """build_model(flash_pallas=True) — the full NMT transformer
    training through the tiled Pallas kernel (decoder self-attn uses
    in-kernel causal masking + key-padding bias) — tracks the XLA-flash
    trajectory."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer

    def run(pallas):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 11
        scope = fluid.Scope()
        losses = []
        with fluid.program_guard(main, startup), \
                fluid.scope_guard(scope), fluid.unique_name.guard():
            m = transformer.build_model(
                src_vocab_size=64, trg_vocab_size=64, max_length=8,
                n_layer=1, n_head=2, d_model=16, d_inner_hid=32,
                dropout=0.0, use_flash=True, flash_pallas=pallas)
            exe = fluid.Executor()
            exe.run(startup)
            feed = transformer.make_fake_batch(4, 8, 60, 60)
            for _ in range(3):
                lv, = exe.run(main, feed=feed, fetch_list=[m["loss"]])
                losses.append(float(np.asarray(lv).reshape(-1)[0]))
        return losses

    pallas = run(True)
    xla = run(False)
    assert pallas[-1] < pallas[0]
    np.testing.assert_allclose(pallas, xla, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("layout", ["nhtd", "nthd"])
def test_pallas_request_with_rich_bias_is_an_error(layout):
    """A program that asks for the Pallas kernel gets the kernel or an
    error: a (Tq, Tk)-shaped bias, which the kernel cannot take, used to
    fall to the XLA composition without a word."""
    from op_test import run_op

    n, h, t, d = 1, 2, 128, 64
    rng = np.random.RandomState(0)
    shape = (n, h, t, d) if layout == "nhtd" else (n, t, h * d)
    x = rng.randn(*shape).astype(np.float32)
    bias = np.zeros((n, 1, t, t), np.float32)
    attrs = {"use_pallas": True, "scale": d ** -0.5, "layout": layout,
             "n_head": h}
    with pytest.raises(ValueError, match="key-padding bias"):
        run_op("flash_attention",
               {"Q": x, "K": x, "V": x, "Bias": bias}, attrs)
    # the same request without use_pallas is the XLA composition
    out = run_op("flash_attention", {"Q": x, "K": x, "V": x, "Bias": bias},
                 dict(attrs, use_pallas=False))
    assert np.isfinite(out).all()
