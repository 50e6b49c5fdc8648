"""Neural-net ops: activations, conv/pool, normalization, dropout, losses.

Covers the reference groups "Activations", "Conv/vision", "Softmax/loss"
(SURVEY.md §2.2; reference files: paddle/fluid/operators/activation_op.cc,
conv_op.cc, pool_op.cc, batch_norm_op.cc, layer_norm_op.cc, softmax_op.cc,
cross_entropy_op.cc, softmax_with_cross_entropy_op.cc, dropout_op.cc).
All ops are traceable jnp/lax; XLA maps convs and matmuls onto the MXU and
fuses the elementwise ops around them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.registry import register_op
from .common import first, opt_in, out, pair, to_jnp_dtype


# --------------------------------------------------------------------------
# Activation family (reference activation_op.cc — one kernel family)
# --------------------------------------------------------------------------

def _register_act(name, fn):
    @register_op(name)
    def impl(ctx, ins, attrs, _fn=fn):
        return out(Out=_fn(first(ins, "X"), attrs))


_register_act("sigmoid", lambda x, a: jax.nn.sigmoid(x))
_register_act("logsigmoid", lambda x, a: jax.nn.log_sigmoid(x))
_register_act("exp", lambda x, a: jnp.exp(x))
_register_act("relu", lambda x, a: jax.nn.relu(x))
_register_act("tanh", lambda x, a: jnp.tanh(x))
_register_act("tanh_shrink", lambda x, a: x - jnp.tanh(x))
_register_act("softshrink", lambda x, a: jnp.sign(x) * jnp.maximum(
    jnp.abs(x) - a.get("lambda", 0.5), 0.0))
_register_act("hard_shrink", lambda x, a: jnp.where(
    jnp.abs(x) > a.get("threshold", 0.5), x, 0.0))
_register_act("sqrt", lambda x, a: jnp.sqrt(x))
_register_act("rsqrt", lambda x, a: lax.rsqrt(x))
_register_act("abs", lambda x, a: jnp.abs(x))
_register_act("ceil", lambda x, a: jnp.ceil(x))
_register_act("floor", lambda x, a: jnp.floor(x))
_register_act("cos", lambda x, a: jnp.cos(x))
_register_act("sin", lambda x, a: jnp.sin(x))
_register_act("round", lambda x, a: jnp.round(x))
_register_act("reciprocal", lambda x, a: 1.0 / x)
_register_act("log", lambda x, a: jnp.log(x))
_register_act("square", lambda x, a: jnp.square(x))
_register_act("softplus", lambda x, a: jax.nn.softplus(x))
_register_act("softsign", lambda x, a: jax.nn.soft_sign(x))
_register_act("brelu", lambda x, a: jnp.clip(
    x, a.get("t_min", 0.0), a.get("t_max", 24.0)))
_register_act("leaky_relu", lambda x, a: jax.nn.leaky_relu(
    x, a.get("alpha", 0.02)))
_register_act("soft_relu", lambda x, a: jnp.log1p(jnp.exp(jnp.clip(
    x, -a.get("threshold", 40.0), a.get("threshold", 40.0)))))
_register_act("elu", lambda x, a: jax.nn.elu(x, a.get("alpha", 1.0)))
_register_act("relu6", lambda x, a: jnp.clip(x, 0.0, a.get("threshold", 6.0)))
_register_act("pow", lambda x, a: jnp.power(x, a.get("factor", 1.0)))
_register_act("stanh", lambda x, a: a.get("scale_b", 1.7159) * jnp.tanh(
    a.get("scale_a", 0.67) * x))
_register_act("hard_sigmoid", lambda x, a: jnp.clip(
    a.get("slope", 0.2) * x + a.get("offset", 0.5), 0.0, 1.0))
_register_act("swish", lambda x, a: x * jax.nn.sigmoid(a.get("beta", 1.0) * x))
_register_act("gelu", lambda x, a: jax.nn.gelu(
    x, approximate=a.get("approximate", False)))
_register_act("sign", lambda x, a: jnp.sign(x))
_register_act("thresholded_relu", lambda x, a: jnp.where(
    x > a.get("threshold", 1.0), x, 0.0))


@register_op("prelu")
def prelu(ctx, ins, attrs):
    x, alpha = first(ins, "X"), first(ins, "Alpha")
    mode = attrs.get("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    elif mode == "all":
        alpha = alpha.reshape(())
    return out(Out=jnp.where(x > 0, x, alpha * x))


@register_op("selu")
def selu(ctx, ins, attrs):
    return out(Out=jax.nn.selu(first(ins, "X")))


@register_op("softmax")
def softmax(ctx, ins, attrs):
    x = first(ins, "X")
    axis = attrs.get("axis", -1)
    return out(Out=jax.nn.softmax(x, axis=axis))


@register_op("log_softmax")
def log_softmax(ctx, ins, attrs):
    return out(Out=jax.nn.log_softmax(first(ins, "X"),
                                      axis=attrs.get("axis", -1)))


# --------------------------------------------------------------------------
# Convolution / pooling (NCHW like the reference; XLA handles layout)
# --------------------------------------------------------------------------

def _conv_padding(padding, spatial):
    if isinstance(padding, str):
        return padding
    p = pair(padding, spatial)
    return [(int(x), int(x)) for x in p]


@register_op("conv2d")
def conv2d(ctx, ins, attrs):
    """reference: operators/conv_op.cc (+cudnn variant).  Input NCHW,
    Filter OIHW, groups supported (depthwise = groups == C_in).

    data_format="NHWC" runs the conv channels-last (filters stay OIHW
    in storage; XLA relayouts) — on TPU the lane dimension wants the
    feature axis minor, so NHWC avoids the relayout transposes XLA
    otherwise inserts around NCHW convs."""
    x, w = first(ins, "Input"), first(ins, "Filter")
    strides = pair(attrs.get("strides", 1))
    dilations = pair(attrs.get("dilations", 1))
    groups = attrs.get("groups", 1) or 1
    fmt = attrs.get("data_format", "NCHW")
    if fmt not in ("NCHW", "NHWC"):
        raise ValueError(f"conv2d data_format must be NCHW or NHWC, "
                         f"got {fmt!r}")
    o = lax.conv_general_dilated(
        x, w,
        window_strides=strides,
        padding=_conv_padding(attrs.get("paddings", 0), 2),
        rhs_dilation=dilations,
        dimension_numbers=(fmt, "OIHW", fmt),
        feature_group_count=groups,
        # no preferred_element_type: the MXU accumulates bf16 convs in
        # f32 internally, and a widened output dtype breaks the conv
        # transpose rule under AD (f32 cotangent vs bf16 filter)
    )
    return {"Output": [o.astype(x.dtype)]}


@register_op("depthwise_conv2d")
def depthwise_conv2d(ctx, ins, attrs):
    attrs = dict(attrs)
    x = first(ins, "Input")
    attrs["groups"] = x.shape[1]
    return conv2d(ctx, ins, attrs)


@register_op("conv3d")
def conv3d(ctx, ins, attrs):
    x, w = first(ins, "Input"), first(ins, "Filter")
    o = lax.conv_general_dilated(
        x, w,
        window_strides=pair(attrs.get("strides", 1), 3),
        padding=_conv_padding(attrs.get("paddings", 0), 3),
        rhs_dilation=pair(attrs.get("dilations", 1), 3),
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=attrs.get("groups", 1) or 1,
    )
    return {"Output": [o]}


def _conv_transpose_nd(ins, attrs, nd):
    """Shared conv{2,3}d_transpose lowering (reference:
    operators/conv_transpose_op.cc registers both on one kernel) —
    filter layout (C_in, C_out/groups, *k); output size
    (H-1)*stride - 2*pad + k_eff.  Implemented as a fractionally-strided
    conv (lhs_dilation) so XLA maps it onto the MXU like a regular
    conv."""
    x, w = first(ins, "Input"), first(ins, "Filter")
    strides = pair(attrs.get("strides", 1), nd)
    pads = pair(attrs.get("paddings", 0), nd)
    dilations = pair(attrs.get("dilations", 1), nd)
    groups = attrs.get("groups", 1) or 1
    c_in = w.shape[0]
    c_out_per_g = w.shape[1]
    ks = w.shape[2:]
    # (C_in, C_out/g, *k) -> grouped (C_out, C_in/g, *k), flipped.
    wg = w.reshape((groups, c_in // groups, c_out_per_g) + ks)
    wg = jnp.moveaxis(wg, 2, 1)
    wg = wg.reshape((groups * c_out_per_g, c_in // groups) + ks)
    wg = jnp.flip(wg, axis=tuple(range(2, 2 + nd)))
    padding = []
    for (k, p, d) in zip(ks, pads, dilations):
        k_eff = (k - 1) * d + 1
        padding.append((k_eff - 1 - p, k_eff - 1 - p))
    spatial = "DHW"[-nd:]
    dn = (f"NC{spatial}", f"OI{spatial}", f"NC{spatial}")
    o = lax.conv_general_dilated(
        x, wg,
        window_strides=(1,) * nd,
        padding=padding,
        lhs_dilation=strides,
        rhs_dilation=dilations,
        dimension_numbers=dn,
        feature_group_count=groups,
    )
    return {"Output": [o]}


@register_op("conv2d_transpose")
def conv2d_transpose(ctx, ins, attrs):
    return _conv_transpose_nd(ins, attrs, 2)


@register_op("conv3d_transpose")
def conv3d_transpose(ctx, ins, attrs):
    return _conv_transpose_nd(ins, attrs, 3)


@register_op("pool2d")
def pool2d(ctx, ins, attrs):
    """reference: operators/pool_op.cc — max/avg, global option,
    exclusive avg-count semantics.  data_format NCHW (default) or
    NHWC (spatial axes (1, 2))."""
    x = first(ins, "X")
    ptype = attrs.get("pooling_type", "max")
    fmt = attrs.get("data_format", "NCHW")
    sp = (2, 3) if fmt == "NCHW" else (1, 2)
    if attrs.get("global_pooling", False):
        o = (jnp.max(x, axis=sp, keepdims=True) if ptype == "max"
             else jnp.mean(x, axis=sp, keepdims=True))
        return out(Out=o)
    ksize = pair(attrs["ksize"])
    strides = pair(attrs.get("strides", 1))
    pads = pair(attrs.get("paddings", 0))
    if fmt == "NCHW":
        window = (1, 1) + ksize
        stride = (1, 1) + strides
        padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pads)
    else:
        window = (1,) + ksize + (1,)
        stride = (1,) + strides + (1,)
        padding = ((0, 0),) + tuple((p, p) for p in pads) + ((0, 0),)
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        o = lax.reduce_window(x, init, lax.max, window, stride, padding)
    else:
        s = lax.reduce_window(x, 0.0, lax.add, window, stride, padding)
        if attrs.get("exclusive", True) and any(p > 0 for p in pads):
            ones = jnp.ones(x.shape[sp[0]:sp[1] + 1], x.dtype)
            cnt = lax.reduce_window(ones, 0.0, lax.add, ksize, strides,
                                    tuple((p, p) for p in pads))
            cnt = (cnt[None, None] if fmt == "NCHW"
                   else cnt[None, :, :, None])
            o = s / cnt
        else:
            o = s / float(ksize[0] * ksize[1])
    return out(Out=o.astype(x.dtype))


@register_op("pool2d_with_index")
def pool2d_with_index(ctx, ins, attrs):
    """reference: operators/pool_with_index_op.cc — max pool returning the
    flattened H*W position of each window max (consumed by unpool)."""
    x = first(ins, "X")
    n, c, h, w = x.shape
    if attrs.get("global_pooling", False):
        # reference pool_with_index_op.cc:48 — ksize becomes the full
        # spatial extent and paddings are ignored
        kh, kw = h, w
        sh, sw = h, w
        ph, pw = 0, 0
    else:
        kh, kw = pair(attrs["ksize"])
        sh, sw = pair(attrs.get("strides", 1))
        ph, pw = pair(attrs.get("paddings", 0))
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w + 2 * pw - kw) // sw + 1
    neg = jnp.asarray(-jnp.inf, x.dtype) if jnp.issubdtype(
        x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
    xp = jnp.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)),
                 constant_values=neg)
    pos = jnp.arange(h * w, dtype=jnp.int32).reshape(1, 1, h, w)
    pos = jnp.broadcast_to(pos, (n, c, h, w))
    posp = jnp.pad(pos, ((0, 0), (0, 0), (ph, ph), (pw, pw)),
                   constant_values=-1)
    vals, idxs = [], []
    for i in range(kh):
        for j in range(kw):
            sl = (slice(None), slice(None),
                  slice(i, i + (oh - 1) * sh + 1, sh),
                  slice(j, j + (ow - 1) * sw + 1, sw))
            vals.append(xp[sl])
            idxs.append(posp[sl])
    v = jnp.stack(vals)                     # (kh*kw, N, C, OH, OW)
    am = jnp.argmax(v, axis=0)
    o = jnp.take_along_axis(v, am[None], axis=0)[0]
    mask = jnp.take_along_axis(jnp.stack(idxs), am[None], axis=0)[0]
    return {"Out": [o.astype(x.dtype)], "Mask": [mask]}


# --------------------------------------------------------------------------
# Normalization
# --------------------------------------------------------------------------

@register_op("batch_norm")
def batch_norm(ctx, ins, attrs):
    """reference: operators/batch_norm_op.cc — NCHW, running-stat update in
    forward; moving stats excluded from autodiff via stop_gradient."""
    x = first(ins, "X")
    scale = first(ins, "Scale")
    bias = first(ins, "Bias")
    mean_in = first(ins, "Mean")
    var_in = first(ins, "Variance")
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False)
    layout = attrs.get("data_layout", "NCHW")

    axes = (0,) + tuple(range(2, x.ndim)) if layout == "NCHW" else \
        tuple(range(x.ndim - 1))
    cshape = [1] * x.ndim
    c_axis = 1 if layout == "NCHW" else x.ndim - 1
    cshape[c_axis] = x.shape[c_axis]

    if is_test or attrs.get("use_global_stats", False):
        mean_b, var_b = mean_in, var_in
        mean_out, var_out = mean_in, var_in
        saved_mean, saved_var = mean_in, var_in
    else:
        xf = x.astype(jnp.float32)
        mean_b = jnp.mean(xf, axis=axes)
        var_b = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(mean_b)
        mean_out = lax.stop_gradient(
            momentum * mean_in + (1 - momentum) * mean_b)
        var_out = lax.stop_gradient(
            momentum * var_in + (1 - momentum) * var_b)
        saved_mean, saved_var = mean_b, var_b

    inv = lax.rsqrt(var_b.astype(jnp.float32) + eps)
    y = (x.astype(jnp.float32) - mean_b.reshape(cshape)) * \
        (inv * scale.astype(jnp.float32)).reshape(cshape) + \
        bias.astype(jnp.float32).reshape(cshape)
    return {
        "Y": [y.astype(x.dtype)],
        "MeanOut": [mean_out],
        "VarianceOut": [var_out],
        "SavedMean": [saved_mean],
        "SavedVariance": [saved_var],
    }


@register_op("layer_norm")
def layer_norm(ctx, ins, attrs):
    x = first(ins, "X")
    scale = opt_in(ins, "Scale")
    bias = opt_in(ins, "Bias")
    begin = attrs.get("begin_norm_axis", 1)
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    norm_shape = x.shape[begin:]
    if scale is not None:
        y = y * scale.reshape(norm_shape).astype(jnp.float32)
    if bias is not None:
        y = y + bias.reshape(norm_shape).astype(jnp.float32)
    return {
        "Y": [y.astype(x.dtype)],
        "Mean": [jnp.squeeze(mean, axes)],
        "Variance": [jnp.squeeze(var, axes)],
    }


@register_op("group_norm")
def group_norm(ctx, ins, attrs):
    x = first(ins, "X")  # NCHW
    scale = opt_in(ins, "Scale")
    bias = opt_in(ins, "Bias")
    groups = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    g = x.reshape((n, groups, c // groups) + x.shape[2:])
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.var(g, axis=axes, keepdims=True)
    y = ((g - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    cshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(cshape)
    if bias is not None:
        y = y + bias.reshape(cshape)
    return {"Y": [y], "Mean": [jnp.squeeze(mean)], "Variance": [jnp.squeeze(var)]}


@register_op("lrn")
def lrn(ctx, ins, attrs):
    x = first(ins, "X")  # NCHW
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    return {"Out": [x / jnp.power(k + alpha * acc, beta)],
            "MidOut": [k + alpha * acc]}


@register_op("l2_normalize")
def l2_normalize(ctx, ins, attrs):
    x = first(ins, "X")
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    return {"Out": [x / jnp.maximum(norm, eps)], "Norm": [norm]}


@register_op("dropout")
def dropout(ctx, ins, attrs):
    x = first(ins, "X")
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test", False) or p == 0.0:
        scale_at_infer = attrs.get("is_test", False) and \
            impl == "downgrade_in_infer"
        y = x * (1.0 - p) if scale_at_infer else x
        return {"Out": [y], "Mask": [jnp.ones_like(x)]}
    from ..observe.monitoring import runtime_stats
    from .pallas import interpret

    key = ctx.rng()
    keep = None
    if not interpret():     # off the TPU not even imported: Pallas is 1 s
        from .pallas.dropout_mask import dropout_keep_mask

        # the chip's own generator draws the mask (a Pallas kernel,
        # PERF.md PR 27); None where that kernel does not engage
        keep = dropout_keep_mask(key, p, x.shape)
    runtime_stats.record_dropout_mask(kernel=keep is not None)
    if keep is None:
        # pinned: the generator (20 rounds of threefry an element) is
        # cheap elementwise HLO, and unpinned XLA clones it into every
        # fusion that reads the mask, the backward's too, rather than
        # write one byte an element once (PERF.md, PR 25)
        keep = jax.lax.optimization_barrier(
            jax.random.bernoulli(key, 1.0 - p, x.shape))
    if impl == "upscale_in_train":
        y = jnp.where(keep, x / (1.0 - p), 0.0)
    else:
        y = jnp.where(keep, x, 0.0)
    return {"Out": [y.astype(x.dtype)], "Mask": [keep.astype(x.dtype)]}


# --------------------------------------------------------------------------
# Losses
# --------------------------------------------------------------------------

@register_op("cross_entropy")
def cross_entropy(ctx, ins, attrs):
    """reference: operators/cross_entropy_op.cc — X is probabilities;
    ignore_index zeroes the loss for matching labels."""
    x, label = first(ins, "X"), first(ins, "Label")
    eps = 1e-12
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, eps)), axis=-1,
                        keepdims=True)
    else:
        lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 \
            else label
        ignore = attrs.get("ignore_index", -100)
        valid = lbl != ignore
        safe_lbl = jnp.where(valid, lbl, 0)
        picked = jnp.take_along_axis(
            x, safe_lbl[..., None].astype(jnp.int32), axis=-1)
        loss = -jnp.log(jnp.maximum(picked, eps))
        loss = jnp.where(valid[..., None], loss, 0.0)
    return out(Y=loss)


@register_op("softmax_with_cross_entropy")
def softmax_with_cross_entropy(ctx, ins, attrs):
    logits, label = first(ins, "Logits"), first(ins, "Label")
    lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    log_sm = logits - lse
    if attrs.get("soft_label", False):
        if float(attrs.get("label_smooth_eps", 0.0) or 0.0):
            raise ValueError(
                "label_smooth_eps only folds into hard-label CE; with "
                "soft_label=True smooth the label distribution yourself "
                "(layers.label_smooth)")
        loss = -jnp.sum(label * log_sm, axis=-1, keepdims=True)
    else:
        lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 \
            else label
        ignore = attrs.get("ignore_index", -100)
        valid = lbl != ignore
        safe_lbl = jnp.where(valid, lbl, 0)
        if attrs.get("one_hot_pick", False):
            # the same number, read elementwise: the backward pass is
            # a select that fuses, not a scatter
            hot = (jnp.arange(logits.shape[-1], dtype=jnp.int32)
                   == safe_lbl[..., None].astype(jnp.int32))
            picked = jnp.sum(jnp.where(hot, log_sm, 0.0), axis=-1,
                             keepdims=True)
        else:
            picked = jnp.take_along_axis(
                log_sm, safe_lbl[..., None].astype(jnp.int32), axis=-1)
        picked = jnp.where(valid[..., None], picked, 0.0)
        loss = -picked
        eps = float(attrs.get("label_smooth_eps", 0.0) or 0.0)
        if eps:
            # folded label smoothing: with q = (1-eps)·onehot + eps/V,
            #   CE(q) = (1-eps)·(lse - logit_y) + eps·(lse - mean logits)
            mean_logits = jnp.mean(logits, axis=-1, keepdims=True)
            smooth_term = lse - mean_logits
            smooth_term = jnp.where(valid[..., None], smooth_term, 0.0)
            loss = (1.0 - eps) * loss + eps * smooth_term
    return {"Loss": [loss], "Softmax": [jnp.exp(log_sm)]}


@register_op("sigmoid_cross_entropy_with_logits")
def sigmoid_cross_entropy_with_logits(ctx, ins, attrs):
    x, label = first(ins, "X"), first(ins, "Label")
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = attrs.get("ignore_index", -100)
    if ignore >= 0:
        loss = jnp.where(label == ignore, 0.0, loss)
    return out(Out=loss)


@register_op("teacher_student_sigmoid_loss")
def teacher_student_sigmoid_loss(ctx, ins, attrs):
    """Distillation CTR loss: student sigmoid-CE on the click plus, when
    a teacher score is present, sigmoid-CE against it on a clamped
    logit.  NOT in the 1.2 reference tree (VERDICT r3 calls its absence
    trivia); semantics follow the public Paddle op of the same name.
    Label encoding (N, 1), branch boundaries as in the public op
    (label < -1 / < 0 / < 1 / else):
      label < -1         -> clk=0, no teacher
      -1 <= label < 0    -> clk=1, no teacher
      0 <= label < 1     -> clk=0, teacher score = label
      label >= 1         -> clk=1, teacher score = label - 1
    loss = bce(x, clk) [+ bce(clip(x, lo, hi), teacher)]."""
    x, label = first(ins, "X"), first(ins, "Label")
    hi = attrs.get("soft_max_up_bound", 15.0)
    lo = attrs.get("soft_max_lower_bound", -15.0)

    def bce(z, t):
        return jnp.maximum(z, 0.0) - z * t + jnp.log1p(jnp.exp(-jnp.abs(z)))

    clk = jnp.where(label < 0.0, jnp.where(label < -1.0, 0.0, 1.0),
                    jnp.where(label >= 1.0, 1.0, 0.0))
    teacher = jnp.where(label >= 1.0, label - 1.0, label)
    has_teacher = label >= 0.0
    xs = jnp.clip(x, lo, hi)
    loss = bce(x, clk) + jnp.where(has_teacher, bce(xs, teacher), 0.0)
    return out(Y=loss)


@register_op("square_error_cost")
def square_error_cost(ctx, ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    return out(Out=jnp.square(x - y))


@register_op("huber_loss")
def huber_loss(ctx, ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    delta = attrs.get("delta", 1.0)
    r = y - x
    a = jnp.abs(r)
    loss = jnp.where(a <= delta, 0.5 * r * r, delta * (a - 0.5 * delta))
    return {"Out": [loss], "Residual": [r]}


@register_op("smooth_l1_loss")
def smooth_l1_loss(ctx, ins, attrs):
    """reference: operators/smooth_l1_loss_op.cc — diff scaled by
    InsideWeight before the huber transform, result by OutsideWeight."""
    x, y = first(ins, "X"), first(ins, "Y")
    iw = opt_in(ins, "InsideWeight")
    ow = opt_in(ins, "OutsideWeight")
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    if iw is not None:
        d = d * iw
    a = jnp.abs(d)
    elem = jnp.where(a < 1.0 / s2, 0.5 * d * d * s2, a - 0.5 / s2)
    if ow is not None:
        elem = elem * ow
    loss = jnp.sum(elem, axis=tuple(range(1, x.ndim)), keepdims=False)
    return {"Out": [loss.reshape((-1, 1))], "Diff": [d]}


@register_op("log_loss")
def log_loss(ctx, ins, attrs):
    p, label = first(ins, "Predicted"), first(ins, "Labels")
    eps = attrs.get("epsilon", 1e-4)
    loss = -label * jnp.log(p + eps) - (1 - label) * jnp.log(1 - p + eps)
    return out(Loss=loss)


@register_op("hinge_loss")
def hinge_loss(ctx, ins, attrs):
    logits, label = first(ins, "Logits"), first(ins, "Labels")
    return out(Loss=jnp.maximum(0.0, 1.0 - (2 * label - 1) * logits))


@register_op("rank_loss")
def rank_loss(ctx, ins, attrs):
    label = first(ins, "Label")
    left, right = first(ins, "Left"), first(ins, "Right")
    d = left - right
    return out(Out=jnp.log1p(jnp.exp(d)) - label * d)


@register_op("margin_rank_loss")
def margin_rank_loss(ctx, ins, attrs):
    label = first(ins, "Label")
    x1, x2 = first(ins, "X1"), first(ins, "X2")
    margin = attrs.get("margin", 0.0)
    o = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": [o], "Activated": [(o > 0).astype(x1.dtype)]}


@register_op("squared_l2_norm")
def squared_l2_norm(ctx, ins, attrs):
    x = first(ins, "X")
    return out(Out=jnp.sum(jnp.square(x)).reshape((1,)))


@register_op("squared_l2_distance")
def squared_l2_distance(ctx, ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    d = x - y
    return {"Out": [jnp.sum(jnp.square(d), axis=-1, keepdims=True)],
            "sub_result": [d]}


@register_op("l1_norm")
def l1_norm(ctx, ins, attrs):
    return out(Out=jnp.sum(jnp.abs(first(ins, "X"))).reshape((1,)))


@register_op("label_smooth")
def label_smooth(ctx, ins, attrs):
    x = first(ins, "X")
    eps = attrs.get("epsilon", 0.0)
    prior = opt_in(ins, "PriorDist")
    k = x.shape[-1]
    if prior is not None:
        o = (1 - eps) * x + eps * prior
    else:
        o = (1 - eps) * x + eps / k
    return out(Out=o)


@register_op("kldiv_loss")
def kldiv_loss(ctx, ins, attrs):
    x, target = first(ins, "X"), first(ins, "Target")
    loss = target * (jnp.log(jnp.maximum(target, 1e-12)) - x)
    red = attrs.get("reduction", "mean")
    if red == "mean":
        loss = jnp.mean(loss).reshape((1,))
    elif red == "sum":
        loss = jnp.sum(loss).reshape((1,))
    elif red == "batchmean":
        loss = (jnp.sum(loss) / x.shape[0]).reshape((1,))
    return out(Loss=loss)


@register_op("bpr_loss")
def bpr_loss(ctx, ins, attrs):
    x, label = first(ins, "X"), first(ins, "Label")
    lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
    pos = jnp.take_along_axis(x, lbl[..., None].astype(jnp.int32), axis=-1)
    diff = x - pos
    n = x.shape[-1]
    loss = -jnp.sum(jnp.log(jax.nn.sigmoid(-diff)), axis=-1,
                    keepdims=True) / (n - 1)
    return out(Y=loss)


# --------------------------------------------------------------------------
# Metrics (reference: operators/metrics/accuracy_op.cc, auc_op.cc)
# --------------------------------------------------------------------------

@register_op("accuracy")
def accuracy(ctx, ins, attrs):
    indices, label = first(ins, "Indices"), first(ins, "Label")
    lbl = label.reshape((-1, 1))
    correct = jnp.any(indices == lbl, axis=1)
    total = jnp.asarray(indices.shape[0], jnp.int32)
    num_correct = jnp.sum(correct).astype(jnp.int32)
    acc = (num_correct.astype(jnp.float32) / total.astype(jnp.float32))
    return {"Accuracy": [acc.reshape((1,))],
            "Correct": [num_correct.reshape((1,))],
            "Total": [total.reshape((1,))]}


@register_op("auc")
def auc(ctx, ins, attrs):
    """Streaming AUC with persistable stat buffers (reference
    operators/metrics/auc_op.cc): histogram of prediction scores."""
    predict = first(ins, "Predict")
    label = first(ins, "Label")
    stat_pos = first(ins, "StatPos")
    stat_neg = first(ins, "StatNeg")
    num_thresholds = attrs.get("num_thresholds", 4095)
    pos_score = predict[:, 1]
    bucket = jnp.floor(pos_score * num_thresholds).astype(jnp.int32)
    bucket = jnp.clip(bucket, 0, num_thresholds)
    lbl = label.reshape(-1).astype(jnp.float32)
    pos_hist = jnp.zeros_like(stat_pos).at[bucket].add(lbl)
    neg_hist = jnp.zeros_like(stat_neg).at[bucket].add(1.0 - lbl)
    new_pos = stat_pos + pos_hist
    new_neg = stat_neg + neg_hist
    # AUC by trapezoid over descending-threshold cumulative counts.
    tp = jnp.cumsum(new_pos[::-1])
    fp = jnp.cumsum(new_neg[::-1])
    tot_pos = tp[-1]
    tot_neg = fp[-1]
    tpr = tp / jnp.maximum(tot_pos, 1.0)
    fpr = fp / jnp.maximum(tot_neg, 1.0)
    auc_val = jnp.trapezoid(tpr, fpr)
    return {"AUC": [auc_val.reshape((1,))],
            "StatPosOut": [new_pos], "StatNegOut": [new_neg]}


# --------------------------------------------------------------------------
# Misc vision
# --------------------------------------------------------------------------

@register_op("interpolate")
def interpolate(ctx, ins, attrs):
    """reference: operators/interpolate_op.cc — NCHW bilinear/nearest with
    align_corners (default True) and align_mode (0 = half-pixel,
    1 = asymmetric src = dst*scale) sampling conventions."""
    x = first(ins, "X")  # NCHW
    out_h = attrs.get("out_h")
    out_w = attrs.get("out_w")
    method = attrs.get("interp_method", "bilinear")
    align_corners = attrs.get("align_corners", True)
    align_mode = attrs.get("align_mode", 1)
    n, c, h, w = x.shape

    def src_coords(out_n, in_n):
        if align_corners:
            if out_n == 1:
                return jnp.zeros((1,), jnp.float32)
            return jnp.linspace(0.0, in_n - 1.0, out_n)
        scale = in_n / out_n
        d = jnp.arange(out_n, dtype=jnp.float32)
        if align_mode == 0:
            return (d + 0.5) * scale - 0.5
        return d * scale

    ys = jnp.clip(src_coords(out_h, h), 0, h - 1)
    xs = jnp.clip(src_coords(out_w, w), 0, w - 1)
    if method == "nearest":
        # reference interpolate_op.h rounds half-up (int(x + 0.5)),
        # not numpy's half-to-even
        yi = (jnp.floor(ys + 0.5) if align_corners else jnp.floor(ys)
              ).astype(jnp.int32)
        xi = (jnp.floor(xs + 0.5) if align_corners else jnp.floor(xs)
              ).astype(jnp.int32)
        o = x[:, :, yi][:, :, :, xi]
    else:
        y0 = jnp.floor(ys).astype(jnp.int32)
        x0 = jnp.floor(xs).astype(jnp.int32)
        y1 = jnp.minimum(y0 + 1, h - 1)
        x1 = jnp.minimum(x0 + 1, w - 1)
        wy = (ys - y0).astype(jnp.float32)[None, None, :, None]
        wx = (xs - x0).astype(jnp.float32)[None, None, None, :]
        xf = x.astype(jnp.float32)
        g00 = xf[:, :, y0][:, :, :, x0]
        g01 = xf[:, :, y0][:, :, :, x1]
        g10 = xf[:, :, y1][:, :, :, x0]
        g11 = xf[:, :, y1][:, :, :, x1]
        top = g00 * (1 - wx) + g01 * wx
        bot = g10 * (1 - wx) + g11 * wx
        o = top * (1 - wy) + bot * wy
    return out(Out=o.astype(x.dtype))


@register_op("pad2d")
def pad2d(ctx, ins, attrs):
    x = first(ins, "X")
    p = attrs["paddings"]  # [top, bottom, left, right]
    mode = attrs.get("mode", "constant")
    cfg = ((0, 0), (0, 0), (p[0], p[1]), (p[2], p[3]))
    if mode == "constant":
        o = jnp.pad(x, cfg, constant_values=attrs.get("pad_value", 0.0))
    elif mode == "reflect":
        o = jnp.pad(x, cfg, mode="reflect")
    else:
        o = jnp.pad(x, cfg, mode="edge")
    return out(Out=o)


@register_op("grid_sampler")
def grid_sampler(ctx, ins, attrs):
    x, grid = first(ins, "X"), first(ins, "Grid")
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1) * (w - 1) / 2
    gy = (grid[..., 1] + 1) * (h - 1) / 2
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    def sample(xi, yi):
        xi = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
        yi = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
        batch = jnp.arange(n)[:, None, None]
        return x[batch, :, yi, xi]  # (N, Hg, Wg, C)
    w00 = (x0 + 1 - gx) * (y0 + 1 - gy)
    w01 = (gx - x0) * (y0 + 1 - gy)
    w10 = (x0 + 1 - gx) * (gy - y0)
    w11 = (gx - x0) * (gy - y0)
    o = (sample(x0, y0) * w00[..., None] + sample(x0 + 1, y0) * w01[..., None]
         + sample(x0, y0 + 1) * w10[..., None]
         + sample(x0 + 1, y0 + 1) * w11[..., None])
    return {"Output": [jnp.transpose(o, (0, 3, 1, 2))]}


@register_op("space_to_depth")
def space_to_depth(ctx, ins, attrs):
    x = first(ins, "X")
    b = attrs["blocksize"]
    n, c, h, w = x.shape
    o = x.reshape(n, c, h // b, b, w // b, b)
    o = jnp.transpose(o, (0, 3, 5, 1, 2, 4))
    return out(Out=o.reshape(n, c * b * b, h // b, w // b))


@register_op("maxout")
def maxout(ctx, ins, attrs):
    x = first(ins, "X")
    groups = attrs["groups"]
    n, c, h, w = x.shape
    return out(Out=jnp.max(x.reshape(n, c // groups, groups, h, w), axis=2))

@register_op("depthwise_conv2d_transpose")
def depthwise_conv2d_transpose(ctx, ins, attrs):
    """reference: conv_transpose_op.cc:379 registers
    depthwise_conv2d_transpose on the SAME ConvTransposeOp — the
    depthwise-ness is just groups == channels, which
    _conv_transpose_nd already lowers via feature_group_count."""
    return _conv_transpose_nd(ins, attrs, 2)
