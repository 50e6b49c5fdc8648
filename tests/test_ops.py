"""Per-op numeric tests (reference: test_<op>_op.py files, 352 of them).

Forward checks against numpy reference math; gradient checks analytic
(jax AD) vs numeric finite differences via the OpTest harness.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from op_test import check_grad, check_output, run_op
from paddle_tpu.core.registry import OpContext, get_op_impl

rng = np.random.RandomState(42)


# --------------------------------------------------------------------------
# forward correctness
# --------------------------------------------------------------------------

def test_elementwise_add_axis_broadcast():
    x = rng.randn(2, 3, 4).astype(np.float32)
    y = rng.randn(3).astype(np.float32)
    check_output("elementwise_add", {"X": x, "Y": y},
                 x + y.reshape(1, 3, 1), attrs={"axis": 1})


def test_elementwise_trailing_broadcast():
    x = rng.randn(2, 3, 4).astype(np.float32)
    y = rng.randn(4).astype(np.float32)
    check_output("elementwise_mul", {"X": x, "Y": y}, x * y,
                 attrs={"axis": -1})


def test_mul_flattens():
    x = rng.randn(2, 3, 4).astype(np.float32)
    y = rng.randn(12, 5).astype(np.float32)
    check_output("mul", {"X": x, "Y": y},
                 (x.reshape(2, 12) @ y).reshape(2, 5),
                 attrs={"x_num_col_dims": 1, "y_num_col_dims": 1},
                 rtol=1e-4)


def test_matmul_transpose():
    x = rng.randn(2, 4, 3).astype(np.float32)
    y = rng.randn(2, 4, 5).astype(np.float32)
    check_output("matmul", {"X": x, "Y": y},
                 np.einsum("bij,bik->bjk", x, y),
                 attrs={"transpose_X": True}, rtol=1e-4)


def test_softmax_matches_numpy():
    x = rng.randn(3, 7).astype(np.float32)
    e = np.exp(x - x.max(-1, keepdims=True))
    check_output("softmax", {"X": x}, e / e.sum(-1, keepdims=True),
                 rtol=1e-5)


def test_softmax_with_cross_entropy():
    x = rng.randn(4, 5).astype(np.float32)
    lbl = np.array([[0], [3], [2], [4]], dtype=np.int64)
    e = np.exp(x - x.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    expected = -np.log(p[np.arange(4), lbl[:, 0]]).reshape(4, 1)
    check_output("softmax_with_cross_entropy",
                 {"Logits": x, "Label": lbl}, expected, out_slot="Loss",
                 rtol=1e-4)


def test_cross_entropy_ignore_index():
    p = np.full((3, 4), 0.25, dtype=np.float32)
    lbl = np.array([[1], [0], [2]], dtype=np.int64)
    got = run_op("cross_entropy", {"X": p, "Label": lbl},
                 attrs={"ignore_index": 0}, out_slot="Y")
    assert got[1, 0] == 0.0
    np.testing.assert_allclose(got[0, 0], -np.log(0.25), rtol=1e-5)


def test_batch_norm_train_stats():
    x = rng.randn(4, 3, 5, 5).astype(np.float32) * 2 + 1
    scale = np.ones(3, np.float32)
    bias = np.zeros(3, np.float32)
    mean = np.zeros(3, np.float32)
    var = np.ones(3, np.float32)
    y = run_op("batch_norm",
               {"X": x, "Scale": scale, "Bias": bias, "Mean": mean,
                "Variance": var},
               attrs={"momentum": 0.9, "epsilon": 1e-5}, out_slot="Y")
    # normalized output has ~zero mean, unit var per channel
    np.testing.assert_allclose(y.mean(axis=(0, 2, 3)), 0, atol=1e-5)
    np.testing.assert_allclose(y.std(axis=(0, 2, 3)), 1, atol=1e-2)


def test_conv2d_matches_direct():
    x = rng.randn(1, 1, 5, 5).astype(np.float32)
    w = rng.randn(1, 1, 3, 3).astype(np.float32)
    got = run_op("conv2d", {"Input": x, "Filter": w},
                 attrs={"strides": [1, 1], "paddings": [0, 0],
                        "dilations": [1, 1]}, out_slot="Output")
    expected = np.zeros((1, 1, 3, 3), np.float32)
    for i in range(3):
        for j in range(3):
            expected[0, 0, i, j] = (x[0, 0, i:i+3, j:j+3] * w[0, 0]).sum()
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


def test_conv2d_transpose_shape_and_values():
    # output size (H-1)*s - 2p + k
    x = rng.randn(1, 2, 4, 4).astype(np.float32)
    w = rng.randn(2, 3, 3, 3).astype(np.float32)
    got = run_op("conv2d_transpose", {"Input": x, "Filter": w},
                 attrs={"strides": [2, 2], "paddings": [1, 1],
                        "dilations": [1, 1]}, out_slot="Output")
    assert got.shape == (1, 3, 7, 7)
    # scatter-accumulate reference
    expected = np.zeros((1, 3, 9, 9), np.float32)
    for ci in range(2):
        for co in range(3):
            for i in range(4):
                for j in range(4):
                    expected[0, co, 2*i:2*i+3, 2*j:2*j+3] += \
                        x[0, ci, i, j] * w[ci, co]
    expected = expected[:, :, 1:-1, 1:-1]
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-4)


def test_pool2d_avg_exclusive():
    x = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
    got = run_op("pool2d", {"X": x},
                 attrs={"pooling_type": "avg", "ksize": [2, 2],
                        "strides": [2, 2], "paddings": [0, 0]})
    expected = np.array([[[[2.5, 4.5], [10.5, 12.5]]]], np.float32)
    np.testing.assert_allclose(got, expected)


def test_reduce_ops():
    x = rng.randn(3, 4, 5).astype(np.float32)
    check_output("reduce_sum", {"X": x}, x.sum(axis=1),
                 attrs={"dim": [1], "keep_dim": False}, rtol=1e-5)
    check_output("reduce_max", {"X": x},
                 np.array([x.max()], np.float32).reshape(1,),
                 attrs={"reduce_all": True}, rtol=1e-6)


def test_topk_and_accuracy():
    x = np.array([[0.1, 0.9, 0.0], [0.8, 0.1, 0.1]], np.float32)
    vals = run_op("top_k", {"X": x}, attrs={"k": 1})
    np.testing.assert_allclose(vals, [[0.9], [0.8]])
    idx = run_op("top_k", {"X": x}, attrs={"k": 1}, out_slot="Indices")
    lbl = np.array([[1], [0]], np.int64)
    acc = run_op("accuracy", {"Out": vals, "Indices": idx, "Label": lbl},
                 out_slot="Accuracy")
    np.testing.assert_allclose(acc, [1.0])


def test_lookup_table_padding_idx():
    w = rng.randn(10, 4).astype(np.float32)
    ids = np.array([[1], [0], [5]], np.int64)
    got = run_op("lookup_table", {"Ids": ids, "W": w},
                 attrs={"padding_idx": 0})
    np.testing.assert_allclose(got[0], w[1])
    np.testing.assert_allclose(got[1], 0.0)


def test_dropout_test_mode_scales():
    x = np.ones((4, 4), np.float32)
    got = run_op("dropout", {"X": x},
                 attrs={"dropout_prob": 0.3, "is_test": True})
    np.testing.assert_allclose(got, 0.7, rtol=1e-6)


def _dropout(key, idx, x, **attrs):
    outs = get_op_impl("dropout")(OpContext(key, idx),
                                  {"X": [jnp.asarray(x)]}, attrs)
    return outs["Out"][0], outs["Mask"][0]


@pytest.mark.parametrize("impl", ["upscale_in_train", "downgrade_in_infer"])
@pytest.mark.parametrize("p,idx,shape,dtype", [
    (0.1, 7, (4, 16, 32), "float32"),
    (0.5, 0, (8, 128), "bfloat16"),
    (0.3, 31, (2, 2, 8, 8), "float32"),
])
def test_dropout_masks_are_jax_bernoulli_of_the_ops_key(impl, p, idx, shape,
                                                        dtype):
    """The mask is pinned (generated once), not changed: bit for bit
    `bernoulli(fold_in(step key, op index), 1 - p)`, and `Out` is the
    plain select on it."""
    key = jax.random.PRNGKey(1234)
    x = jnp.asarray(rng.randn(*shape), dtype)
    got, mask = _dropout(key, idx, x, dropout_prob=p,
                         dropout_implementation=impl)
    keep = jax.random.bernoulli(jax.random.fold_in(key, idx), 1.0 - p,
                                shape)
    kept = x / (1.0 - p) if impl == "upscale_in_train" else x
    want = jnp.where(keep, kept, 0.0).astype(dtype)
    assert got.dtype == mask.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(mask.astype("float32")),
                                  np.asarray(keep, np.float32))
    np.testing.assert_array_equal(np.asarray(got.astype("float32")),
                                  np.asarray(want.astype("float32")))
    assert 0.0 < float(keep.mean()) < 1.0


@pytest.mark.parametrize("impl,scale", [("upscale_in_train", 1.0 / 0.75),
                                        ("downgrade_in_infer", 1.0)])
def test_dropout_gradient_is_the_mask_times_the_cotangent(impl, scale):
    key = jax.random.PRNGKey(5)
    x = jnp.asarray(rng.randn(6, 40).astype(np.float32))
    g = jnp.asarray(rng.randn(6, 40).astype(np.float32))
    attrs = dict(dropout_prob=0.25, dropout_implementation=impl)
    (out, mask), vjp = jax.vjp(lambda v: _dropout(key, 3, v, **attrs), x)
    dx, = vjp((g, jnp.zeros_like(mask)))
    want = jnp.where(mask.astype(bool), g * np.float32(scale), 0.0)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(want),
                               rtol=1e-6, atol=0)
    np.testing.assert_array_equal(np.asarray(dx) == 0,
                                  np.asarray(mask) == 0)


@pytest.mark.parametrize("attrs,factor", [
    (dict(dropout_prob=0.3, is_test=True), 0.7),
    (dict(dropout_prob=0.3, is_test=True,
          dropout_implementation="upscale_in_train"), 1.0),
    (dict(dropout_prob=0.0), 1.0),
    (dict(dropout_prob=0.0, dropout_implementation="upscale_in_train"), 1.0),
])
def test_dropout_without_a_mask_draws_nothing(attrs, factor):
    """`is_test` and `p == 0` need no key at all, and give a mask of
    ones."""
    x = rng.randn(4, 4).astype(np.float32)
    got, mask = _dropout(None, 0, x, **attrs)
    np.testing.assert_allclose(np.asarray(got), x * np.float32(factor),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(mask), 1.0)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_dropout_under_checkpoint_recomputes_the_same_mask(jit):
    """Inside a recompute segment the backward regenerates the mask
    from the same key: the gradient it applies is the forward's."""
    key = jax.random.PRNGKey(9)
    x = jnp.asarray(rng.randn(8, 64).astype(np.float32))
    attrs = dict(dropout_prob=0.4,
                 dropout_implementation="upscale_in_train")

    def loss(v, k):
        out, mask = _dropout(k, 11, v * 2.0, **attrs)
        return jnp.sum(out * out), mask

    plain = jax.value_and_grad(loss, has_aux=True)
    remat = jax.value_and_grad(jax.checkpoint(loss), has_aux=True)
    if jit:
        plain, remat = jax.jit(plain), jax.jit(remat)
    (l0, m0), g0 = plain(x, key)
    (l1, m1), g1 = remat(x, key)
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))
    assert float(l0) == float(l1)
    # where the forward dropped, the recomputed backward drops too
    np.testing.assert_array_equal(np.asarray(g1) == 0, np.asarray(m0) == 0)


def test_sequence_pool_masks_padding():
    x = np.ones((2, 4, 3), np.float32)
    x[0, 2:] = 99.0  # padding rows, must be ignored
    sl = np.array([2, 4], np.int32)
    got = run_op("sequence_pool", {"X": x, "SeqLen": sl},
                 attrs={"pooltype": "AVERAGE"})
    np.testing.assert_allclose(got[0], 1.0)
    got_last = run_op("sequence_pool", {"X": x, "SeqLen": sl},
                      attrs={"pooltype": "LAST"})
    np.testing.assert_allclose(got_last[0], 1.0)  # row 1, not padding


def test_sequence_softmax_ignores_padding():
    x = np.zeros((1, 4), np.float32)
    sl = np.array([2], np.int32)
    got = run_op("sequence_softmax", {"X": x, "SeqLen": sl})
    np.testing.assert_allclose(got, [[0.5, 0.5, 0.0, 0.0]], atol=1e-6)


def test_dynamic_lstm_freezes_after_length():
    n, t, h = 2, 5, 3
    x = rng.randn(n, t, 4 * h).astype(np.float32)
    w = rng.randn(h, 4 * h).astype(np.float32) * 0.1
    sl = np.array([2, 5], np.int32)
    hidden = run_op("dynamic_lstm",
                    {"Input": x, "Weight": w, "SeqLen": sl},
                    attrs={"use_peepholes": False}, out_slot="Hidden")
    # row 0 state frozen after step 2
    np.testing.assert_allclose(hidden[0, 2], hidden[0, 1], rtol=1e-6)
    np.testing.assert_allclose(hidden[0, 4], hidden[0, 1], rtol=1e-6)
    assert not np.allclose(hidden[1, 4], hidden[1, 1])


def test_dynamic_gru_reference_convention():
    """h = (1-u)*h_prev + u*candidate (reference
    math/detail/gru_kernel.h:62)."""
    n, t, h = 1, 1, 2
    # zero recurrent weight so gates come purely from the input
    w = np.zeros((h, 3 * h), np.float32)
    big = 100.0  # saturates sigmoid -> u == 1
    x = np.zeros((n, t, 3 * h), np.float32)
    x[0, 0, :h] = big          # update gate -> 1
    x[0, 0, 2 * h:] = 0.5      # candidate pre-activation
    h0 = np.full((n, h), 0.9, np.float32)
    out_h = run_op("dynamic_gru", {"Input": x, "Weight": w, "H0": h0},
                   out_slot="Hidden")
    # u==1 must TAKE the candidate (tanh(0.5)), not keep h_prev
    np.testing.assert_allclose(out_h[0, 0], np.tanh(0.5), rtol=1e-5)


def test_flash_attention_matches_composed():
    n, h, t, d = 2, 2, 8, 4
    q = rng.randn(n, h, t, d).astype(np.float32)
    k = rng.randn(n, h, t, d).astype(np.float32)
    v = rng.randn(n, h, t, d).astype(np.float32)
    scale = d ** -0.5
    logits = np.einsum("nhqd,nhkd->nhqk", q, k) * scale
    e = np.exp(logits - logits.max(-1, keepdims=True))
    w = e / e.sum(-1, keepdims=True)
    expected = np.einsum("nhqk,nhkd->nhqd", w, v)
    check_output("flash_attention", {"Q": q, "K": k, "V": v}, expected,
                 rtol=1e-4, atol=1e-5)
    # causal: position 0 attends only to itself
    got = run_op("flash_attention", {"Q": q, "K": k, "V": v},
                 attrs={"causal": True})
    np.testing.assert_allclose(got[:, :, 0], v[:, :, 0], rtol=1e-4)


def test_flash_attention_grad():
    n, h, t, d = 1, 1, 4, 4
    check_grad("flash_attention",
               {"Q": rng.randn(n, h, t, d).astype(np.float32),
                "K": rng.randn(n, h, t, d).astype(np.float32),
                "V": rng.randn(n, h, t, d).astype(np.float32)},
               "Q", max_relative_error=1e-2)


def test_lr_schedule_noam():
    step = np.array([100.0], np.float32)
    got = run_op("lr_schedule", {"Step": step},
                 attrs={"kind": "noam", "d_model": 512,
                        "warmup_steps": 4000})
    expected = 512 ** -0.5 * min(100 ** -0.5, 100 * 4000 ** -1.5)
    np.testing.assert_allclose(got, [expected], rtol=1e-5)


def test_lr_schedule_piecewise():
    for s, e in [(5, 0.1), (15, 0.01), (25, 0.001)]:
        got = run_op("lr_schedule", {"Step": np.array([float(s)], np.float32)},
                     attrs={"kind": "piecewise",
                            "boundaries": [10.0, 20.0],
                            "values": [0.1, 0.01, 0.001]})
        np.testing.assert_allclose(got, [e], rtol=1e-6)


# --------------------------------------------------------------------------
# gradient checks (analytic vs numeric)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("op,ins,attrs,slot,out_slot", [
    ("relu", {"X": rng.randn(3, 4).astype(np.float32) + 0.1}, {}, "X", "Out"),
    ("tanh", {"X": rng.randn(3, 4).astype(np.float32)}, {}, "X", "Out"),
    ("sigmoid", {"X": rng.randn(3, 4).astype(np.float32)}, {}, "X", "Out"),
    ("softmax", {"X": rng.randn(2, 5).astype(np.float32)}, {}, "X", "Out"),
    ("elementwise_mul",
     {"X": rng.randn(2, 3).astype(np.float32),
      "Y": rng.randn(3).astype(np.float32)}, {"axis": 1}, "X", "Out"),
    ("mul", {"X": rng.randn(2, 3).astype(np.float32),
             "Y": rng.randn(3, 4).astype(np.float32)},
     {"x_num_col_dims": 1, "y_num_col_dims": 1}, "Y", "Out"),
    ("layer_norm", {"X": rng.randn(2, 6).astype(np.float32),
                    "Scale": rng.rand(6).astype(np.float32) + 0.5,
                    "Bias": rng.randn(6).astype(np.float32)},
     {"begin_norm_axis": 1}, "X", "Y"),
    ("softmax_with_cross_entropy",
     {"Logits": rng.randn(3, 4).astype(np.float32),
      "Label": np.array([[0], [2], [1]], np.int64)}, {}, "Logits", "Loss"),
])
def test_grad_matches_numeric(op, ins, attrs, slot, out_slot):
    check_grad(op, ins, slot, attrs=attrs, out_slot=out_slot)


def test_conv2d_grad():
    check_grad("conv2d",
               {"Input": rng.randn(1, 2, 5, 5).astype(np.float32),
                "Filter": rng.randn(3, 2, 3, 3).astype(np.float32) * 0.5},
               "Filter",
               attrs={"strides": [1, 1], "paddings": [1, 1],
                      "dilations": [1, 1]},
               out_slot="Output", max_relative_error=1e-2)


def test_pool2d_with_index_argmax():
    """Mask must contain real flattened-H*W argmax positions
    (round-1 review finding)."""
    x = rng.randn(2, 3, 6, 6).astype(np.float32)
    attrs = {"ksize": [2, 2], "strides": [2, 2]}
    outs = run_op("pool2d_with_index", {"X": x}, attrs=attrs)
    mask = run_op("pool2d_with_index", {"X": x}, attrs=attrs,
                  out_slot="Mask")
    # numpy reference
    want_o = np.zeros((2, 3, 3, 3), np.float32)
    want_m = np.zeros((2, 3, 3, 3), np.int64)
    for n in range(2):
        for c in range(3):
            for i in range(3):
                for j in range(3):
                    win = x[n, c, 2*i:2*i+2, 2*j:2*j+2]
                    a = np.argmax(win)
                    want_o[n, c, i, j] = win.flat[a]
                    di, dj = divmod(a, 2)
                    want_m[n, c, i, j] = (2*i + di) * 6 + (2*j + dj)
    np.testing.assert_allclose(outs, want_o)
    np.testing.assert_array_equal(mask, want_m)


def test_interpolate_align_corners_bilinear():
    """align_corners=True must use scale (in-1)/(out-1) — the reference
    default (operators/interpolate_op.cc)."""
    x = rng.randn(1, 1, 4, 4).astype(np.float32)
    got = run_op("interpolate", {"X": x},
                 attrs={"out_h": 7, "out_w": 7,
                        "interp_method": "bilinear",
                        "align_corners": True})
    ys = np.linspace(0, 3, 7)
    want = np.zeros((1, 1, 7, 7), np.float32)
    for i, sy in enumerate(ys):
        for j, sx in enumerate(ys):
            y0, x0 = int(np.floor(sy)), int(np.floor(sx))
            y1, x1 = min(y0 + 1, 3), min(x0 + 1, 3)
            wy, wx = sy - y0, sx - x0
            want[0, 0, i, j] = (
                x[0, 0, y0, x0] * (1-wy) * (1-wx)
                + x[0, 0, y0, x1] * (1-wy) * wx
                + x[0, 0, y1, x0] * wy * (1-wx)
                + x[0, 0, y1, x1] * wy * wx)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fused_label_smooth_ce_matches_composition():
    """softmax_with_cross_entropy(label_smooth_eps=eps) must equal the
    one_hot → label_smooth → soft-label CE composition it replaces
    (models/transformer.py loss path)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.registry import OpContext, get_op_impl

    rng = np.random.RandomState(0)
    B, V = 6, 37
    logits = jnp.asarray(rng.randn(B, V).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, V, (B, 1)).astype(np.int64))
    eps = 0.1
    impl = get_op_impl("softmax_with_cross_entropy")
    ctx = OpContext(jax.random.PRNGKey(0))
    fused = impl(ctx, {"Logits": [logits], "Label": [labels]},
                 {"label_smooth_eps": eps})["Loss"][0]
    onehot = jax.nn.one_hot(labels[:, 0], V)
    smooth = (1 - eps) * onehot + eps / V
    soft = impl(ctx, {"Logits": [logits], "Label": [smooth]},
                {"soft_label": True})["Loss"][0]
    np.testing.assert_allclose(np.asarray(fused), np.asarray(soft),
                               rtol=1e-5, atol=1e-6)
