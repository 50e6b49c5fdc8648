"""Pallas flash attention vs composed XLA reference (interpret mode on
CPU; the same kernel runs compiled on TPU)."""

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
