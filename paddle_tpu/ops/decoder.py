"""The small ops of a modern decoder block: `rms_norm`, `rope`
(rotate-half rotary positions), `swiglu` (the gated FFN activation)
and `short_conv` (the gated short convolution that stands where
attention does in most layers of a hybrid conv/attention model).

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


@register_op("rms_norm")
def rms_norm(ctx, ins, attrs):
    """Y = X * rsqrt(mean(X^2 over the axes from begin_norm_axis) + eps)
    [* Scale].  Statistics and the scaling in float32, Y in X's dtype.
    With `group_size` g the minor dim is read as groups of g (the heads
    of a head-grouped (N, T, H*g) projection), each normalised alone
    and scaled by the one Scale (g,) they share."""
    x = first(ins, "X")
    scale = opt_in(ins, "Scale")
    group = attrs.get("group_size")
    if group:
        if x.shape[-1] % int(group):
            raise ValueError(f"rms_norm: minor dim {x.shape[-1]} is not "
                             f"whole groups of {group}")
        y = rms_norm(ctx, {"X": [x.reshape(x.shape[:-1] + (-1, int(group)))],
                           "Scale": ins.get("Scale", [])},
                     {"epsilon": attrs.get("epsilon", 1e-5)})["Y"][0]
        return out(Y=y.reshape(x.shape))
    begin = attrs.get("begin_norm_axis", -1) % x.ndim
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
                       + eps)
    if scale is not None:
        y = y * scale.reshape(x.shape[begin:]).astype(jnp.float32)
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


@register_op("rope")
def rope(ctx, ins, attrs):
    """Rotate-half rotary embedding over the whole head.  X is
    head-grouped (N, T, H*D), what a q or k projection emits; the pair
    (x[i], x[i + D/2]) of every head turns by pos * theta^(-2i/D).
    Offset (1,) is the position of X's first row (a decode step passes
    the cache length); absent = 0."""
    x = first(ins, "X")
    offset = opt_in(ins, "Offset")
    n_head = int(attrs["n_head"])
    theta = float(attrs.get("theta", 10000.0))
    n, t, hd = x.shape
    d = hd // n_head
    if d * n_head != hd or d % 2:
        raise ValueError(f"rope: minor dim {hd} is not n_head {n_head} "
                         f"heads of an even size")
    pos = jnp.arange(t, dtype=jnp.int32)
    if offset is not None:
        pos = pos + offset.reshape(()).astype(jnp.int32)
    ang = rope_angles(pos, d, theta)                  # (T, D/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32).reshape(n, t, n_head, 2, d // 2)
    x1, x2 = xf[..., 0, :], xf[..., 1, :]
    y = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-2)
    return out(Out=y.reshape(n, t, hd).astype(x.dtype))


def silu_gate(a, b):
    """silu(a) * b, the SwiGLU gate, in the operands' dtype."""
    return jax.nn.silu(a) * b


@register_op("swiglu")
def swiglu(ctx, ins, attrs):
    return out(Out=silu_gate(first(ins, "X"), first(ins, "Y")))


def _short_conv(bcu, w):
    d, taps = w.shape
    f32 = jnp.float32
    b, c, u = (bcu[..., i * d:(i + 1) * d].astype(f32) for i in range(3))
    z = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    t = bcu.shape[1]
    wf = w.astype(f32)
    conv = sum(wf[:, j] * z[:, j:j + t] for j in range(taps))
    return (c * conv).astype(bcu.dtype)


@register_op("short_conv")
def short_conv(ctx, ins, attrs):
    """The gated short convolution between a block's two projections.
    X is `BCu` (N, T, 3D), what the in-projection emits, split in that
    order; Filter (D, L), one L-tap filter a channel.

        z = B * u
        conv[t] = sum_j Filter[:, j] * z[t - (L-1) + j]     (z[<0] = 0)
        Out = C * conv                                       (N, T, D)

    Causal (position t reads t-L+1..t) and depthwise (a channel reads
    itself only).  One op, so that the three elementwise passes are
    one scope and can be one fusion; float32 inside, X's dtype out.
    The backward pass recomputes from X (`jax.checkpoint`): it reads
    `BCu` and the output's gradient and keeps nothing in between."""
    x, w = first(ins, "X"), first(ins, "Filter")
    if x.ndim != 3 or x.shape[-1] != 3 * w.shape[0]:
        raise ValueError(f"short_conv: X {x.shape} is not (N, T, 3D) for "
                         f"a Filter {w.shape} of (D, L)")
    return out(Out=jax.checkpoint(_short_conv)(x, w))
