"""The small ops of a modern decoder block: `rms_norm` (its scale from
1 or zero-centred, alone or gated), `rope` (rotary positions, over
halves or over pairs, over a whole head or its first lanes), `swiglu`
(the gated FFN activation), `short_conv` (the short causal convolution
that stands where attention does in most layers of a hybrid
conv/attention model, gated or under a SiLU), `latent_attention` (the
attention core of a layer whose keys and values come out of a low-rank
latent, with a rotary part beside it), `gated_delta_rule` (the scan
of a linear-attention layer), `channel_delta_rule` (the same scan under
a decay of each key lane's own), `ssd_scan` and `gated_rms_norm` (the scan
and the gated output norm of a Mamba-2 state-space mixer),
`selective_scan` (the scan of a
state-space mixer with a diagonal state a channel) and `diff_combine`
(what differential attention does with its two soft-max maps'
contexts).

Not in the 1.2 reference (it predates them all); they are ops of
their own, not compositions of `square` / `reduce_mean` / `slice` /
`concat`, so a block costs three named scopes instead of thirty and
the statistics stay in float32 whatever dtype the activations arrive
in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.registry import register_op
from .common import first, opt_in, out


def _over_rms(xf, axes, eps):
    """float32 xf over the root of its mean square along `axes`."""
    return xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
                          + eps)


@register_op("rms_norm")
def rms_norm(ctx, ins, attrs):
    """Y = X * rsqrt(mean(X^2 over the axes from begin_norm_axis) + eps)
    [* Scale].  Statistics and the scaling in float32, Y in X's dtype.
    With `group_size` g the minor dim is read as groups of g (the heads
    of a head-grouped (N, T, H*g) projection), each normalised alone
    and scaled by the one Scale (g,) they share.  `zero_centered`: the
    scale is 1 + Scale (a Scale that starts at 0 and that weight decay
    pulls to 0 leaves the norm ON).  Gate (X's shape): Y is multiplied
    by silu(Gate), or by sigmoid(Gate) under `gate_activation`
    "sigmoid", in float32 (the output norm of a gated mixer).  Groups
    of 128 lanes go to the kernels of `ops/pallas/head_norm.py` on the
    tensor as it lies (`head_norm_takes`), any other to the composition
    over a (.., H, g) view there; `runtime_stats.head_norm_calls`
    counts the kernel calls traced."""
    x = first(ins, "X")
    scale = opt_in(ins, "Scale")
    gate = opt_in(ins, "Gate")
    group = attrs.get("group_size")
    if group:
        from .pallas.head_norm import head_norm

        if x.shape[-1] % int(group):
            raise ValueError(f"rms_norm: minor dim {x.shape[-1]} is not "
                             f"whole groups of {group}")
        # a Pallas pass on the tensor as it lies where a group is a
        # lane tile, else the composition over a (.., H, g) view
        return out(Y=head_norm(
            x, scale, gate, group=int(group), denom=float(group),
            eps=attrs.get("epsilon", 1e-5),
            zero_centered=bool(attrs.get("zero_centered")),
            gate_activation=attrs.get("gate_activation", "silu")))
    begin = attrs.get("begin_norm_axis", -1) % x.ndim
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(begin, x.ndim))
    y = _over_rms(x.astype(jnp.float32), axes, eps)
    if scale is not None:
        scale = scale.reshape(x.shape[begin:]).astype(jnp.float32)
        y = y * (1.0 + scale if attrs.get("zero_centered") else scale)
    if gate is not None:
        squash = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}.get(
            attrs.get("gate_activation", "silu"))
        if squash is None:
            raise NotImplementedError(
                f"rms_norm: gate_activation "
                f"{attrs['gate_activation']!r} is not built")
        y = y * squash(gate.astype(jnp.float32))
    return out(Y=y.astype(x.dtype))


def rope_angles(positions, head_dim, theta):
    """(T, head_dim/2) float32 angles pos * theta^(-2i/head_dim).  The
    frequencies are a host constant, as a checkpoint's `inv_freq`
    buffer is: the TPU's own float32 `pow` is good to 3.6e-6, which at
    position 4095 is 1.5e-2 rad and moves an attention score by a
    bfloat16 rounding's worth (measured, PERF.md PR 26)."""
    inv_freq = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                               / head_dim)
    return (positions.astype(jnp.float32)[:, None]
            * inv_freq.astype(np.float32)[None, :])


def rope_frequencies(head_dim, rope_type="default", rope_theta=10000.0,
                     factor=1.0, original_max_position_embeddings=None,
                     beta_fast=32.0, beta_slow=1.0, attention_factor=None,
                     truncate=True, **_):
    """(inv_freq (head_dim/2,) float64, scale on cos and sin) of a
    config's `rope_parameters`, on the host.  "default":
    theta^(-2i/D), scale 1.  "yarn" (Peng et al. 2023,
    arXiv:2309.00071, as transformers' `_compute_yarn_parameters`
    writes it): the frequencies that turn more than `beta_fast` times
    within the original context are kept, those that turn fewer than
    `beta_slow` times are divided by `factor`, and a linear ramp over
    the dimensions between blends the two; cos and sin carry
    `attention_factor` (0.1 ln factor + 1 where the config gives
    none), so a score carries its square."""
    extra = float(rope_theta) ** (-np.arange(0, head_dim, 2, dtype=np.float64)
                                  / head_dim)
    if rope_type == "default":
        return extra, 1.0
    if rope_type != "yarn":
        raise NotImplementedError(f"rope_type {rope_type!r} is not built")

    def turns_at(rotations):     # the dimension that turns so often
        return (head_dim * np.log(original_max_position_embeddings
                                  / (rotations * 2 * np.pi))
                / (2 * np.log(float(rope_theta))))

    low, high = turns_at(beta_fast), turns_at(beta_slow)
    if truncate:
        low, high = np.floor(low), np.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    if attention_factor is None:
        attention_factor = 0.1 * np.log(factor) + 1.0 if factor > 1 else 1.0
    return (extra / factor * ramp + extra * (1.0 - ramp),
            float(attention_factor))


def _cos_sin(t, rotary, attrs, offset):
    """cos, sin (1, T, 1, rotary/2) float32 of the rows' positions, the
    `attention_factor` on them."""
    pos = jnp.arange(t, dtype=jnp.int32)
    if attrs.get("period"):
        pos = pos % int(attrs["period"])
    if offset is not None:
        pos = pos + offset.reshape(()).astype(jnp.int32)
    inv_freq = attrs.get("inv_freq")
    if inv_freq is None:
        ang = rope_angles(pos, rotary, float(attrs.get("theta", 10000.0)))
    else:
        if len(inv_freq) != rotary // 2:
            raise ValueError(f"rope: {len(inv_freq)} frequencies for a "
                             f"head of {rotary}")
        ang = (pos.astype(jnp.float32)[:, None]
               * np.asarray(inv_freq, np.float32)[None, :])
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    factor = float(attrs.get("attention_factor") or 1.0)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    return cos, sin


def _cos_sin_two_axes(positions, head_dim, theta):
    """cos, sin (N, T, 1, head_dim/2) float32 of (row, column)
    positions: pair 2m turns by column * f_m, pair 2m + 1 by row * f_m,
    f_m = theta^(-4m/head_dim) (host constants, as `rope_angles`')."""
    freq = (1.0 / theta ** (np.arange(0, head_dim, 4, dtype=np.float32)
                            / head_dim)).astype(np.float32)
    pos = positions.astype(jnp.float32)
    row, column = pos[..., 0:1], pos[..., 1:2]
    ang = jnp.stack([column * freq, row * freq], axis=-1)
    ang = ang.reshape(positions.shape[:2] + (1, head_dim // 2))
    return jnp.cos(ang), jnp.sin(ang)


def _turn(xf, cos, sin, n_head, pairs):
    """float32 heads of R lanes, (N, T, H*R) or (N, T, H, R), turned by
    cos, sin: a head as (2, R/2) halves, or as (R/2, 2) pairs; as
    stacked."""
    half = cos.shape[-1]
    xf = xf.reshape(xf.shape[:2] + (n_head,)
                    + ((half, 2) if pairs else (2, half)))
    x1, x2 = (xf[..., 0], xf[..., 1]) if pairs else \
        (xf[..., 0, :], xf[..., 1, :])
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1 if pairs else -2)


def _rope(x, scale, cos, sin, n_head, eps=1e-5, pairs=False):
    """The op as it is written, the reference of its kernels: float32
    from X to Out, no rounding between the norm and the turn."""
    n, t, hd = x.shape
    d, rotary = hd // n_head, 2 * cos.shape[-1]
    xf = x.astype(jnp.float32)
    if scale is not None:
        xf = _over_rms(xf.reshape(n, t, n_head, d), -1, eps) \
            * scale.astype(jnp.float32)
    if rotary == d:
        y = _turn(xf, cos, sin, n_head, pairs)
    else:
        xf = xf.reshape(n, t, n_head, d)
        y = jnp.concatenate(
            [_turn(xf[..., :rotary], cos, sin, n_head, pairs)
             .reshape(n, t, n_head, rotary), xf[..., rotary:]], axis=-1)
    return y.reshape(n, t, hd).astype(x.dtype)


@register_op("rope")
def rope(ctx, ins, attrs):
    """Rotary embedding over the whole head.  X is head-grouped
    (N, T, H*D), what a q or k projection emits; the pair
    (x[i], x[i + D/2]) of every head turns by pos * theta^(-2i/D)
    (rotate-half), or with `interleave` the pair (x[2i], x[2i + 1]).
    Offset (1,) is the position of X's first row (a decode step passes
    the cache length); absent = 0.  `inv_freq` (D/2 numbers, a host
    constant as a checkpoint's buffer is) stands in for theta^(-2i/D),
    and cos and sin are multiplied by `attention_factor`: scaled RoPE
    (`rope_frequencies`).  `rotary_dim` R < D: only the first R lanes
    of every head turn, as a head of R would (pairs (i, i + R/2),
    theta^(-2i/R)); lanes R.. pass through.  `period` P: positions
    restart every P rows (row r stands at r mod P; block-diffusion
    training feeds a clean and a noised copy of a sequence, P = T / 2),
    before the Offset.

    Scale (D,): each head is RMS-normed before it turns (QK-norm a
    head), xhat = x * rsqrt(mean_head(x^2) + `epsilon`) * Scale, or
    * (1 + Scale) with `zero_centered`: what `rms_norm(group_size=D)`
    before this op computes, without the rounding to X's dtype in
    between and without the round trip through HBM.  float32 from X to
    Out, Out in X's dtype.

    Positions (N, T, 2) int32, a (row, column) a row: rotary positions
    over TWO axes (a patch of an image's grid), interleaved by
    frequency: a head's D lanes are D/2 consecutive PAIRS, and with
    f_m = theta^(-4m/D), m = 0 .. D/4 - 1, pair 2m turns by
    column x f_m and pair 2m + 1 by row x f_m.  Always over pairs and
    the whole head; beside an Offset, a `period`, `rotary_dim` or
    scaled frequencies it raises.

    Two lowerings of the one algorithm, chosen by the shape and attrs
    alone (`ops/pallas/rope.py rope_kernel_takes`: a Scale, rotate-half,
    D a multiple of 128, R even, T whole row tiles that fit VMEM): the
    two Pallas kernels there, which read X once and write Out once (the
    backward pass X and dOut, recomputing the norm); everything else
    (a bare turn, which XLA fuses into its neighbours; a head of 64,
    `interleave`, a decode step's single row) the composition `_rope`,
    under `jax.checkpoint` where it norms (X is the residual there
    too).  `runtime_stats.ropes_kernel` / `ropes_xla` count the calls
    traced each way."""
    from ..observe.monitoring import runtime_stats
    from .pallas.rope import rope_kernel, rope_kernel_takes

    x = first(ins, "X")
    offset = opt_in(ins, "Offset")
    scale = opt_in(ins, "Scale")
    n_head = int(attrs["n_head"])
    n, t, hd = x.shape
    d = hd // n_head
    if d * n_head != hd or d % 2:
        raise ValueError(f"rope: minor dim {hd} is not n_head {n_head} "
                         f"heads of an even size")
    rotary = int(attrs.get("rotary_dim") or d)
    if not 0 < rotary <= d or rotary % 2:
        raise ValueError(f"rope: rotary_dim {rotary} is not an even "
                         f"part of a head of {d}")
    pairs = bool(attrs.get("interleave", False))
    eps = float(attrs.get("epsilon", 1e-5))
    if scale is not None:
        if scale.shape != (d,):
            raise ValueError(f"rope: Scale {scale.shape} for a head of {d}")
        scale = scale.astype(jnp.float32)
        if attrs.get("zero_centered"):
            scale = 1.0 + scale
    positions = opt_in(ins, "Positions")
    if positions is not None:
        if offset is not None or rotary != d or d % 4 or scale is not None \
                or any(attrs.get(key) for key in
                       ("period", "inv_freq", "attention_factor")):
            raise NotImplementedError(
                "rope: positions over two axes turn a whole head of a "
                "multiple of 4 lanes, with no Offset, period, Scale or "
                "scaled frequencies")
        cos, sin = _cos_sin_two_axes(positions, d,
                                     float(attrs.get("theta", 10000.0)))
        runtime_stats.record_rope(False)
        return out(Out=_rope(x, None, cos, sin, n_head, pairs=True))
    cos, sin = _cos_sin(t, rotary, attrs, offset)
    kernel = rope_kernel_takes(t, n_head, d, rotary, pairs, scale is not None,
                               x.dtype.itemsize)
    runtime_stats.record_rope(kernel)
    if kernel:
        return out(Out=rope_kernel(x, scale, cos.reshape(t, -1),
                                   sin.reshape(t, -1), n_head, eps))
    if scale is None:
        return out(Out=_rope(x, None, cos, sin, n_head, pairs=pairs))
    return out(Out=jax.checkpoint(
        lambda x, scale: _rope(x, scale, cos, sin, n_head, eps, pairs))(
            x, scale))


def silu_gate(a, b):
    """silu(a) * b, the SwiGLU gate, in the operands' dtype."""
    return jax.nn.silu(a) * b


@register_op("swiglu")
def swiglu(ctx, ins, attrs):
    return out(Out=silu_gate(first(ins, "X"), first(ins, "Y")))


def _causal_conv(z, w):
    """conv[t] = sum_j w[:, j] * z[t - (L-1) + j], z[<0] = 0; float32."""
    taps, t = w.shape[1], z.shape[1]
    z = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    return sum(wf[:, j] * z[:, j:j + t] for j in range(taps))


def _short_conv(bcu, w):
    d = w.shape[0]
    b, c, u = (bcu[..., i * d:(i + 1) * d].astype(jnp.float32)
               for i in range(3))
    return (c * _causal_conv(b * u, w)).astype(bcu.dtype)


def _silu_conv(x, w, bias=None):
    conv = _causal_conv(x.astype(jnp.float32), w)
    if bias is not None:
        conv = conv + bias.astype(jnp.float32)
    return jax.nn.silu(conv).astype(x.dtype)


@register_op("short_conv")
def short_conv(ctx, ins, attrs):
    """The gated short convolution between a block's two projections.
    X is `BCu` (N, T, 3D), what the in-projection emits, split in that
    order; Filter (D, L), one L-tap filter a channel.

        z = B * u
        conv[t] = sum_j Filter[:, j] * z[t - (L-1) + j]     (z[<0] = 0)
        Out = C * conv                                       (N, T, D)

    Causal (position t reads t-L+1..t) and depthwise (a channel reads
    itself only).  One op and one scope; float32 inside, X's dtype out.
    The backward pass recomputes from X: it reads `BCu` and the
    output's gradient and keeps nothing in between.

    `activation` "silu": no gates; X is (N, T, D) and
    Out = silu(conv(X)), the same convolution (what stands before the
    scan of a linear-attention layer); with a Bias (D,), one number a
    channel, Out = silu(conv(X) + Bias) (a state-space mixer's).

    Two lowerings of the one algorithm, chosen by the shape alone
    (`ops/pallas/short_conv.py short_conv_kernel_takes`: D a multiple
    of 128, T of the row tile, the taps within a halo, a gated tile
    that fits VMEM): the two Pallas kernels there, which read X and
    write Out once; everything else (odd widths, short rows) the
    composition below under `jax.checkpoint`, whose float32
    intermediates XLA writes to HBM.  `runtime_stats.
    short_convs_kernel` / `_xla` count the calls traced each way."""
    from ..observe.monitoring import runtime_stats
    from .pallas.short_conv import (biased_conv_kernel, short_conv_kernel,
                                    short_conv_kernel_takes)

    x, w = first(ins, "X"), first(ins, "Filter")
    bias = opt_in(ins, "Bias")
    activation = attrs.get("activation")
    if activation not in (None, "silu"):
        raise NotImplementedError(f"short_conv: activation {activation!r} "
                                  f"is not built")
    wide = 1 if activation else 3
    if x.ndim != 3 or x.shape[-1] != wide * w.shape[0]:
        raise ValueError(f"short_conv: X {x.shape} is not (N, T, "
                         f"{wide if wide > 1 else ''}D) for a Filter "
                         f"{w.shape} of (D, L)")
    gated = not activation
    if bias is not None and (gated or bias.shape != w.shape[:1]):
        raise ValueError(f"short_conv: a Bias {bias.shape} goes with "
                         f"activation='silu' and a Filter {w.shape} of "
                         f"(D, L)")
    kernel = short_conv_kernel_takes(x.shape[1], w.shape[0], w.shape[1],
                                     gated, x.dtype.itemsize)
    runtime_stats.record_short_conv(kernel, bias=bias is not None)
    if bias is not None:
        return out(Out=(biased_conv_kernel if kernel
                        else jax.checkpoint(_silu_conv))(x, w, bias))
    if kernel:
        return out(Out=short_conv_kernel(x, w, gated))
    return out(Out=jax.checkpoint(_short_conv if gated
                                  else _silu_conv)(x, w))


def plain_latent_attention(q_nope, q_rope, k_nope, k_rope, v, n_head, scale):
    """`latent_attention` as it is written: every head's score over its
    unrotated and its rotary lanes, a causal soft-max in float32."""
    n, t, _ = q_nope.shape
    f32 = jnp.float32

    def heads(x):
        return x.astype(f32).reshape(n, t, n_head, -1)

    s = (jnp.einsum("nqhd,nkhd->nhqk", heads(q_nope), heads(k_nope))
         + jnp.einsum("nqhd,nkd->nhqk", heads(q_rope), k_rope.astype(f32)))
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s * scale, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", p, heads(v))
    return o.reshape(n, t, -1).astype(v.dtype)


@register_op("latent_attention")
def latent_attention(ctx, ins, attrs):
    """The attention core of a latent-attention layer, causal, over
    one sequence a row.  Head-major operands as the up-projections emit
    them: QNope, KNope (N, T, H*Dn), QRope (N, T, H*Dr) and V
    (N, T, H*Dv) hold H heads side by side; KRope (N, T, Dr) is ONE
    rotary key head that every query head reads (rotated already, as
    QRope is).

        s_ij = (q_nope_i . k_nope_j + q_rope_i . k_rope_j) / sqrt(Dn + Dr)
        Out  = causal_softmax(s) V                       (N, T, H*Dv)

    Two lowerings, chosen by the shape alone (`flash_mla.flash_mla_takes`:
    Dn 128, Dr 64, Dv 128): the kernels of `ops/pallas/flash_mla.py`,
    which read the operands where they lie (the rotary key is never
    repeated over the heads and V is never padded to the score's
    width), or `plain_latent_attention`."""
    from .pallas.flash_mla import flash_mla, flash_mla_takes

    q_nope, q_rope = first(ins, "QNope"), first(ins, "QRope")
    k_nope, k_rope = first(ins, "KNope"), first(ins, "KRope")
    v = first(ins, "V")
    n_head = int(attrs["n_head"])
    nope, rope_dim = q_nope.shape[-1] // n_head, k_rope.shape[-1]
    if (q_nope.shape[-1] != n_head * nope or k_nope.shape != q_nope.shape
            or q_rope.shape[-1] != n_head * rope_dim
            or v.shape[-1] % n_head):
        raise ValueError(
            f"latent_attention: QNope {q_nope.shape}, QRope {q_rope.shape}, "
            f"KNope {k_nope.shape}, KRope {k_rope.shape}, V {v.shape} are "
            f"not {n_head} heads and one rotary key head")
    scale = (nope + rope_dim) ** -0.5
    if flash_mla_takes(nope, rope_dim, v.shape[-1] // n_head):
        return out(Out=flash_mla(q_nope, q_rope, k_nope, k_rope, v, scale))
    return out(Out=plain_latent_attention(q_nope, q_rope, k_nope, k_rope, v,
                                          n_head, scale))


@register_op("gated_delta_rule")
def gated_delta_rule(ctx, ins, attrs):
    """The mixer core of a gated-delta-rule linear-attention layer
    (arXiv:2412.06464), over one sequence a row.  QKV (N, T, 2 Hk Dk +
    Hv Dv): the convolved projection, q, k (Hk heads of Dk) and v (Hv
    heads of Dv) side by side; BA (N, T, 2 Hv): b then a, one of each a
    value head; ALog, DtBias (Hv,).  In float32:

        q = l2norm(q) * Dk^-1/2;  k = l2norm(k)         (eps 1e-6, a head)
        beta = sigmoid(b);  g = -exp(ALog) * softplus(a + DtBias)
        S'_t = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'_t^T k_t)
        S_t = S'_t + k_t u_t^T;   Out_t = S_t^T q_t      (N, T, Hv Dv)

    with S (Dk, Dv) a value head from 0, value head h reading key head
    h // (Hv / Hk).  The scan runs in chunks of 64 positions
    (`ops/pallas/gated_delta.py`): its dots in QKV's dtype, state,
    decay and the chunk's inverse in float32.  The shape alone sends the
    sequential part to the Pallas kernels there (`gated_delta.kernel_takes`:
    Dk = Dv = 128) and, where two value heads read a key head, the
    chunk-local part to its own two (a chunk's matrices then stay in
    VMEM); elsewhere the first is a `lax.scan` over the chunks and the
    second XLA's batch over all of them.  q, k AND v go in as the
    projection wrote them: QKV once, with where each lies
    (`gated_delta.RawQK`: no slice and no float32 (N, T, H, Dk) view,
    which the chip would re-lay).  The chunk-local kernels block the
    three out of QKV's lanes, take the l2norm of the head they hold and
    hand back ONE dQKV, each lane written once; the scan kernels write
    Out as it lies here, (N, T, Hv Dv), a value head's lanes a grid
    step, and read its cotangent so.  Any other lowering cuts v out,
    goes through `ops/pallas/head_norm.py` first and transposes the
    scan's head-major result."""
    from .pallas import gated_delta

    qkv, ba = first(ins, "QKV"), first(ins, "BA")
    a_log, dt_bias = first(ins, "ALog"), first(ins, "DtBias")
    hk, hv = int(attrs["n_key_head"]), int(attrs["n_value_head"])
    dk, dv = int(attrs["key_dim"]), int(attrs["value_dim"])
    n, t, width = qkv.shape
    if width != 2 * hk * dk + hv * dv or ba.shape != (n, t, 2 * hv) \
            or hv % hk:
        raise ValueError(
            f"gated_delta_rule: QKV {qkv.shape} and BA {ba.shape} are not "
            f"{hk} key heads of {dk}, {hv} value heads of {dv} and two "
            f"gates a value head")
    f32 = jnp.float32

    beta = jax.nn.sigmoid(ba[..., :hv].astype(f32))
    g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
        ba[..., hv:].astype(f32) + dt_bias.astype(f32))
    o = gated_delta.gated_delta_rule(
        qkv, None, None, g, beta,
        use_kernel=gated_delta.kernel_takes(dk, dv),
        raw=gated_delta.RawQK(q=0, k=hk * dk, heads=hk, dim=dk,
                              v=2 * hk * dk, value_dim=dv))
    return out(Out=o.reshape(n, t, hv * dv))


@register_op("channel_delta_rule")
def channel_delta_rule(ctx, ins, attrs):
    """The mixer core of a delta-rule linear-attention layer whose decay
    is a key lane's own (Kimi Delta Attention, arXiv:2510.26692), over
    one sequence a row.  QKV (N, T, 2 H Dk + H Dv): the convolved,
    activated projection, q, k (H heads of Dk) and v (H heads of Dv) side
    by side; Gate (N, T, H Dk) and Beta (N, T, H): the decay's and the
    write strength's PRE-activations; ALog (H,), DtBias (H Dk,).  In
    float32:

        q = l2norm(q) * Dk^-1/2;  k = l2norm(k)         (eps 1e-6, a head)
        beta = sigmoid(Beta)
        g = -exp(ALog[head]) * softplus(Gate + DtBias)  (a head AND lane)
        S'_t = Diag(exp(g_t)) S_{t-1};  u_t = beta_t (v_t - S'_t^T k_t)
        S_t = S'_t + k_t u_t^T;   Out_t = S_t^T q_t      (N, T, H Dv)

    with S (Dk, Dv) a head from 0.  g is the one float32 (N, T, H Dk)
    tensor the op makes; the scan runs in chunks of 64 positions
    (`ops/pallas/channel_delta.py`): its dots in QKV's dtype, state,
    decay, every exponential and the chunk's inverse in float32.  Two
    lowerings of the one recurrence, chosen by the shape alone
    (`channel_delta.kernel_takes`: an even number of heads of 128 x 128,
    chunk blocks of 8): the five Pallas kernels there, or XLA's batch
    over the chunks and a `lax.scan`.  `runtime_stats.channel_delta_calls`
    / `_operand_calls` count the kernel calls traced; a call that fell
    back reads 0.  q and k go in as the projection wrote them, as
    `gated_delta_rule`'s do: the chunk-local kernels take the l2norm."""
    from .pallas import channel_delta
    from .pallas.selective_scan import softplus

    qkv, gate, beta = first(ins, "QKV"), first(ins, "Gate"), first(ins, "Beta")
    a_log, dt_bias = first(ins, "ALog"), first(ins, "DtBias")
    h = int(attrs["n_head"])
    dk, dv = int(attrs["key_dim"]), int(attrs["value_dim"])
    n, t, width = qkv.shape
    if width != h * (2 * dk + dv) or gate.shape != (n, t, h * dk) \
            or beta.shape != (n, t, h):
        raise ValueError(
            f"channel_delta_rule: QKV {qkv.shape}, Gate {gate.shape} and "
            f"Beta {beta.shape} are not {h} heads of {dk} key and {dv} "
            f"value lanes, a decay a key lane and a beta a head")
    f32 = jnp.float32

    rate = jnp.repeat(jnp.exp(a_log.astype(f32)), dk)
    g = -rate * softplus(gate.astype(f32) + dt_bias.astype(f32))
    return out(Out=channel_delta.channel_delta_rule(
        qkv, qkv, qkv[..., 2 * h * dk:], g, jax.nn.sigmoid(beta.astype(f32)),
        use_kernel=channel_delta.kernel_takes(h, dk, dv, t),
        raw=channel_delta.RawQK(q=0, k=h * dk, heads=h, dim=dk)))


@register_op("selective_scan")
def selective_scan(ctx, ins, attrs):
    """The mixer core of a Mamba-1 state-space layer (arXiv:2312.00752),
    over one sequence a row.  U (N, T, D): the convolved, activated
    input; Delta (N, T, D): the step projection's output BEFORE its bias
    and the softplus; B, C (N, T, S): one group, shared by every
    channel; ALog (D, S), D and DeltaBias (D,).  In float32:

        dt = softplus(Delta + DeltaBias);  A = -exp(ALog)
        s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] u_t[c] B_t[n]
        Out_t[c] = sum_n C_t[n] s_t[c, n] + D[c] u_t[c]      (N, T, D)

    with s (D, S) from 0; Out in U's dtype.  Two lowerings of the one
    recurrence, chosen by the shape alone (`ops/pallas/selective_scan.py
    selective_scan_takes`: S = 16, T whole chunks, D whole lane tiles):
    the two Pallas kernels there, whose state never leaves VMEM, or a
    `lax.scan` over chunks with an `associative_scan` inside.
    `runtime_stats.selective_scans_kernel` / `_xla` count the calls
    traced each way."""
    from .pallas import selective_scan as scan

    a = -jnp.exp(first(ins, "ALog").astype(jnp.float32))
    return out(Out=scan.selective_scan(
        first(ins, "U"), first(ins, "Delta"), a, first(ins, "B"),
        first(ins, "C"), first(ins, "D"), first(ins, "DeltaBias")))


@register_op("ssd_scan")
def ssd_scan(ctx, ins, attrs):
    """The mixer core of a Mamba-2 state-space layer (state-space
    duality, arXiv:2405.21060), over one sequence a row.  XBC (N, T,
    H P + 2 G S): the convolved, activated [x | B | C] AS THE
    CONVOLUTION LEAVES IT, H heads of P lanes, then `n_groups` G groups
    of `d_state` S states of B, then of C, a group shared by H / G
    heads; Dt (N, T, H): the step's slice of the in projection BEFORE
    its bias and the softplus; ALog, D and DtBias (H,).  In float32:

        dt = softplus(Dt + DtBias);  A = -exp(ALog)          (a head)
        S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] B_t^T
        Out_t[h] = S_t[h] C_t + D[h] x_t[h]                  (N, T, H P)

    with S[h] (P, S) from 0; Out in XBC's dtype.  The bias and the
    softplus are taken HERE, not in the kernels: the step is a
    (position, head) scalar (2 MB a layer in float32 at 8192 x 64), so
    XLA makes it, its cumulative sums and their exponentials, and
    autodiff their gradients.  Two lowerings of the one recurrence in
    its chunked matrix-product form (chunks of `chunk_size`), chosen by
    the shape alone (`ops/pallas/ssd_scan.py ssd_scan_takes`: heads of
    64 lanes, 128 states, one group, chunks of 256, T whole chunks):
    the two Pallas kernels there, whose state and decay masks never
    leave VMEM, which block x, B and C out of XBC's lanes and write its
    gradient as ONE array (no slice of XBC and no concatenation of
    three gradients in HBM), or the same chunks as XLA einsums under a
    `lax.scan` on the three slices.  `runtime_stats.ssd_scans_kernel` /
    `_xla` count the calls traced each way."""
    from .pallas import selective_scan, ssd_scan as scan

    f32 = jnp.float32
    dt = selective_scan.softplus(first(ins, "Dt").astype(f32)
                                 + first(ins, "DtBias").astype(f32))
    return out(Out=scan.scan_joint(
        first(ins, "XBC"), dt, -jnp.exp(first(ins, "ALog").astype(f32)),
        first(ins, "D"), d_state=int(attrs["d_state"]),
        chunk=int(attrs.get("chunk_size", scan.CHUNK)),
        groups=int(attrs.get("n_groups", 1))))


@register_op("gated_rms_norm")
def gated_rms_norm(ctx, ins, attrs):
    """The output norm of a gated state-space mixer, the gate BEFORE
    the norm: Y = rms_norm(X * silu(Gate)) * Scale over the minor dim.
    The product, the statistics and the scale in float32, one fusion; Y
    in X's dtype."""
    from ..observe.monitoring import runtime_stats

    runtime_stats.record_gated_rms_norm()
    x = first(ins, "X")
    f32 = jnp.float32
    y = x.astype(f32) * jax.nn.silu(first(ins, "Gate").astype(f32))
    y = _over_rms(y, (-1,), attrs.get("epsilon", 1e-5))
    return out(Y=(y * first(ins, "Scale").astype(f32)).astype(x.dtype))


@register_op("diff_combine")
def diff_combine(ctx, ins, attrs):
    """What differential attention (Ye et al., arXiv:2410.05258) does
    with its two maps' contexts.  X (N, T, H * W): the contexts of H
    query heads of W lanes as ONE grouped attention call over 2 x
    `n_kv_pair` key/value heads left them: for key/value pair i the g
    heads that read its FIRST key, then the g that read its second (H =
    2 g n_kv_pair).  LambdaQ1, LambdaK1, LambdaQ2, LambdaK2 (any one
    length) and Scale (W,).  In float32:

        lam = exp(LambdaQ1 . LambdaK1) - exp(LambdaQ2 . LambdaK2) + lambda_init
        ctx[i, e] = rms_norm(x[i, 0, e] - lam x[i, 1, e]) * Scale
                    * (1 - lambda_init)

    Out (N, T, H / 2 * W), pair i g + e at lanes (i g + e) W ..; X's
    dtype."""
    x = first(ins, "X")
    f32 = jnp.float32
    scale = first(ins, "Scale").astype(f32)
    lam_init = float(attrs["lambda_init"])

    def dot(a, b):
        return jnp.sum(first(ins, a).astype(f32) * first(ins, b).astype(f32))

    lam = (jnp.exp(dot("LambdaQ1", "LambdaK1"))
           - jnp.exp(dot("LambdaQ2", "LambdaK2")) + lam_init)
    n, t, width = x.shape
    lanes, pairs = scale.shape[0], int(attrs["n_kv_pair"])
    group = width // (2 * pairs * lanes)
    if 2 * pairs * group * lanes != width:
        raise ValueError(f"diff_combine: X {x.shape} is not two maps of "
                         f"{pairs} key/value pairs' heads of {lanes}")
    # a key/value pair's heads lie side by side, first keys then second:
    # the two maps are the two halves of its lanes (no strided slice)
    xf = x.astype(f32).reshape(n, t, pairs, 2 * group * lanes)
    apart = (xf[..., :group * lanes] - lam * xf[..., group * lanes:]
             ).reshape(n, t, pairs * group, lanes)
    y = _over_rms(apart, -1, float(attrs.get("epsilon", 1e-5)))
    y = y * (scale * (1.0 - lam_init))
    return out(Out=y.reshape(n, t, pairs * group * lanes).astype(x.dtype))
