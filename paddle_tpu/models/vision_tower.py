"""A native-resolution vision tower whose output rows enter a decoder's
stream: built from a published `vision_config`'s own keys, as
`models/decoder.py` is from a language model's.

The images of a step, whatever their shapes, are PACKED along one row
axis of P patches (NaViT, Dehghani et al., arXiv:2307.06304; the tower
of Kimi-VL, Kimi Team, arXiv:2504.07491): `pixel_values` (N, P, C *
patch_size^2) float32, `patch_segments` (N, P) int32 (which image a row
belongs to; negative: a padding row), `patch_yx` (N, P, 2) int32 (the
patch's row and column in its image's grid) and `pos_taps` /
`pos_weights` (N, P, 16) (the bicubic taps of the position table for
that patch, geometry a collator computes from (y, x, h, w)).  P is
static (`patch_rows`); a step with fewer patches pads.  The images lie
on the row axis in the order they stand in the sequence.

    x = pixels W_pe + b_pe + sum_k pos_weights[k] E[pos_taps[k]]
                                         (E (H0 W0, D) learnt, float32)
    a layer, pre-LayerNorm (scale and shift), every projection biased:
      [q | k | v] = LN(x) W_qkv + b
      q, k <- rope over (row, column), pairs interleaved by frequency
      x = x + segment_attention(q, k, v) W_o + b     all-to-all INSIDE an
                                         image, no other image's rows
      x = x + gelu_tanh(LN(x) W_0 + b_0) W_1 + b_1
    x = LN(x)                            the tower's last norm
    z = [LN_p(x_00) | LN_p(x_01) | LN_p(x_10) | LN_p(x_11)]
    rows = gelu(z W_a + b_a) W_b + b_b   (exact GELU) -> the decoder's width

The merger's four patches are the four of a 2 x 2 block of the grid.
The collator hands the patches of an image in MERGE order (a block's
four consecutive, blocks row-major), so the merger is a reshape:
attention is indifferent to the order of rows whose positions travel
with them.  The three projections of one input are three matrices (a
checkpoint's fused W_qkv splits by columns), as the decoder's are.

Name scopes: `vision_tower` (everything up to the last norm),
`vision_attention` inside it (q, k, v, the attention op, which turns q
and k, and the out projection), `vision_projector` (LN_p .. rows).  Under
`recompute="layer"` every tower layer is a recompute segment that
keeps its input and the attention kernels' output and logsumexp, and
the last norm with the projector is one more.  A value the builder
does not build raises.
"""

from __future__ import annotations

import contextlib

from .. import layers
from ..core.program import name_scope, recompute_scope
from ..initializer import Normal
from ..observe.monitoring import runtime_stats
from ..param_attr import ParamAttr

TAPS = 16           # bicubic: 4 rows x 4 columns


@runtime_stats.stage("build_program")
def vision_tower(hidden_size, num_hidden_layers, num_attention_heads,
                 intermediate_size, patch_size, init_pos_emb_height,
                 init_pos_emb_width, merge_kernel_size, text_hidden_size,
                 patch_rows, in_token_limit=None, num_channels=3,
                 layer_norm_eps=1e-5, rope_theta=10000.0,
                 initializer_range=0.02, hidden_act="gelu_pytorch_tanh",
                 projector_hidden_act="gelu", recompute=None):
    """Append the tower and the projector to the default program.
    Returns a dict: `image_rows` (N, patch_rows / (m_h m_w),
    text_hidden_size), `tower_out` (N, patch_rows, hidden_size, after
    the last norm) and `feeds`, the names of its five feeds."""
    if hidden_size % num_attention_heads \
            or (hidden_size // num_attention_heads) % 4:
        raise ValueError(
            f"{num_attention_heads} heads of {hidden_size} lanes are not "
            f"whole heads of a multiple of 4 lanes (two axes of pairs)")
    if list(merge_kernel_size) != [2, 2]:
        raise NotImplementedError(
            f"merge_kernel_size {list(merge_kernel_size)}: only a 2 x 2 "
            f"merger is built")
    if hidden_act != "gelu_pytorch_tanh" or projector_hidden_act != "gelu":
        raise NotImplementedError(
            f"hidden_act {hidden_act!r} / projector_hidden_act "
            f"{projector_hidden_act!r}: the tower's MLP is built with "
            f"tanh-GELU and the projector with exact GELU")
    if recompute not in (None, "layer"):
        raise NotImplementedError(f"recompute {recompute!r} is not built")
    merged = merge_kernel_size[0] * merge_kernel_size[1]
    if patch_rows % merged:
        raise ValueError(f"patch_rows {patch_rows} is no whole number of "
                         f"{merged}-patch blocks")
    heads, d = num_attention_heads, hidden_size

    def weight():
        return ParamAttr(initializer=Normal(0.0, initializer_range))

    def proj(x, size, name):
        return layers.fc(x, size=size, num_flatten_dims=2,
                         param_attr=weight(), name=name)

    def layer_norm(x):
        return layers.layer_norm(x, begin_norm_axis=2,
                                 epsilon=layer_norm_eps)

    def gelu(x, approximate):
        # GELU's argument in float32; the next projection casts again
        return layers.gelu(layers.cast(x, "float32"),
                           approximate=approximate)

    def segment():
        return (recompute_scope() if recompute == "layer"
                else contextlib.nullcontext())

    pixels = layers.data(name="pixel_values", dtype="float32", shape=[
        patch_rows, num_channels * patch_size * patch_size])
    segments = layers.data(name="patch_segments", shape=[patch_rows],
                           dtype="int32")
    yx = layers.data(name="patch_yx", shape=[patch_rows, 2], dtype="int32")
    taps = layers.data(name="pos_taps", shape=[patch_rows, TAPS],
                       dtype="int32")
    tap_weights = layers.data(name="pos_weights", shape=[patch_rows, TAPS],
                              dtype="float32")

    def attention(h):
        with name_scope("vision_attention"):
            # q and k turn inside the attention op, over (row, column)
            ctx = layers.segment_attention(
                *(proj(h, d, "vit_qkv") for _ in "qkv"), segments, heads,
                max_segment_rows=in_token_limit, positions=yx,
                rope_theta=rope_theta)
            return proj(ctx, d, "vit_out")

    with name_scope("vision_tower"):
        x = layers.elementwise_add(
            proj(pixels, d, "patch_embed"),
            layers.table_interp(
                taps, tap_weights,
                (init_pos_emb_height, init_pos_emb_width, d),
                param_attr=weight()))
        for _ in range(num_hidden_layers):
            with segment():
                x = layers.elementwise_add(x, attention(layer_norm(x)))
                mlp = proj(gelu(proj(layer_norm(x), intermediate_size,
                                     "vit_mlp"), True), d, "vit_mlp")
                x = layers.elementwise_add(x, mlp)
    with segment():
        with name_scope("vision_tower"):
            tower_out = layer_norm(x)
        with name_scope("vision_projector"):
            z = layers.reshape(layer_norm(tower_out),
                               [0, patch_rows // merged, merged * d])
            rows = proj(gelu(proj(z, merged * d, "projector"), False),
                        text_hidden_size, "projector")
    return {"image_rows": rows, "tower_out": tower_out,
            "feeds": ["pixel_values", "patch_segments", "patch_yx",
                      "pos_taps", "pos_weights"]}
