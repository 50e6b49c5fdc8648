"""Plain float32 reference of the window / full attention MoE decoder
whose head count follows the layer type (`model_type` "laguna":
Laguna-XS.2's layer equations, ISSUE 51) forward pass, loss and
gradients: the benchmark's own, so that the comparison that decides a
cell's correctness does not move when the program does.

Straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`.  No Program, no Executor, no
AMP, no kernel, no sort: attention materialises its scores (`q_block`
rows at a time where 16384 positions would not fit otherwise) under an
EXPLICIT mask, i - W < j <= i in a `sliding_attention` layer and j <= i
in a `full_attention` layer, and repeats the key/value heads with
`jnp.repeat`; the expert layer is a python loop over the held experts,
each a dense SwiGLU FFN applied to every token and weighted by the
router's weight where the expert is among the token's eight and by zero
where it is not.

    layer l, H_l = num_attention_heads_per_layer[l] query heads:
      h = rms_norm(x);  q = h Wq (H_l heads), k, v = h Wk, h Wv (Hkv)
      q, k = rms_norm of each head over its D lanes
      q, k = rope_kind(q), rope_kind(k)     the first R_kind lanes turn
      c = masked_softmax(q k^T / sqrt(D)) v      head j reads j // (H_l / Hkv)
      g = sigmoid(h Wg)                          (H_l,): one gate a head
      x = x + (c * g[..., None]) Wo
      h = rms_norm(x)
      dense layer:   x = x + (silu(h W1) * (h W3)) W2
      sparse layer:  p = softmax(h Wr) over all E, float32
                     S = the k largest;  w_e = p_e / sum_{S} p
                     x = x + f * sum_{e in S, held here} w_e FFN_e(h)
                           + FFN_shared(h)

with f = `moe_routed_scaling_factor`.  RoPE: `rope_inv_freq` makes a
layer type's frequencies and the scale on cos and sin from the config's
`rope_parameters` group in numpy float64 (a host constant, as a
checkpoint's buffer is) for a head of R = `partial_rotary_factor` x D
lanes: theta^(-2i/R) for "default"; for "yarn" transformers'
`_compute_yarn_parameters` with dim = R and truncation (the
frequencies below dimension `low` kept, those above `high` divided by
`factor`, a linear ramp between; cos and sin times
`attention_factor`).  It is written here from the paper and that
function, independent of `paddle_tpu.ops.decoder.rope_frequencies`.

Where `expert_parallel_size` chips share each layer's experts, the
expert layer is ONE rank's share, as `reference_lfm2.py` sets out: the
router is as wide as published, the weights are the held experts', what
the experts held elsewhere would have added is LEFT OUT, the shared
expert is whole, and `forward` holds the routing weights constant in
the backward pass (`router_gradient=False`) as `models/decoder.py`
does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ATTENTION_KEYS = ("op_norm", "wq", "q_norm", "wk", "k_norm", "wv", "wg",
                  "wo")
DENSE_KEYS = ("ffn_norm", "w1", "w3", "w2")
SPARSE_KEYS = ("ffn_norm", "router", "w1", "w2", "w3", "shared_w1",
               "shared_w3", "shared_w2")


def layer_keys(cfg, i):
    return ATTENTION_KEYS + (DENSE_KEYS if cfg["mlp_layer_types"][i]
                             == "dense" else SPARSE_KEYS)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotary_lanes(cfg, kind):
    """R: the lanes of a head that turn in a layer of type `kind`."""
    share = cfg["rope_parameters"][kind].get(
        "partial_rotary_factor", cfg.get("partial_rotary_factor", 1.0))
    return int(cfg["head_dim"] * share)


def yarn_range(group, dim):
    """(low, high): the dimensions between which YaRN's ramp runs, of a
    head of `dim` rotary lanes."""
    theta = float(group["rope_theta"])
    orig = group["original_max_position_embeddings"]

    def dim_of(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = math.floor(dim_of(group.get("beta_fast", 32)))
    high = math.ceil(dim_of(group.get("beta_slow", 1)))
    return max(low, 0), min(high, dim - 1)


def rope_inv_freq(group, dim):
    """(inv_freq (dim/2,) float64, scale on cos and sin) of one
    `rope_parameters` group over `dim` rotary lanes."""
    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = float(group["rope_theta"]) ** (-i / dim)
    kind = group.get("rope_type", "default")
    if kind == "default":
        return extra, 1.0
    if kind != "yarn":
        raise NotImplementedError(kind)
    factor = float(group["factor"])
    low, high = yarn_range(group, dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    scale = group.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return extra / factor * ramp + extra * (1.0 - ramp), float(scale)


def rope(x, inv_freq, scale):
    """x (N, T, H, D): rotate-half rotary embedding over the first
    R = 2 x len(inv_freq) lanes of each head, positions 0..T-1, cos and
    sin times `scale`; lanes R.. pass through."""
    t, rotary = x.shape[1], 2 * len(inv_freq)
    freqs = (jnp.arange(t, dtype=jnp.float32)[:, None]
             * np.asarray(inv_freq, np.float32)[None, :])
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    turned = x[..., :rotary] * (jnp.cos(emb) * scale) \
        + rotate_half(x[..., :rotary]) * (jnp.sin(emb) * scale)
    return jnp.concatenate([turned, x[..., rotary:]], axis=-1)


def params_from_list(arrays, cfg):
    """The parameter tree from a flat list in the builder's creation
    order: embedding, `layer_keys` per layer, final norm, head."""
    arrays = [jnp.asarray(a, jnp.float32) for a in arrays]
    layers, at = [], 1
    for i in range(cfg["num_hidden_layers"]):
        keys = layer_keys(cfg, i)
        layers.append(dict(zip(keys, arrays[at:at + len(keys)])))
        at += len(keys)
    if len(arrays) != at + 2:
        raise ValueError(f"{len(arrays)} arrays, {at + 2} expected")
    return {"embed": arrays[0], "layers": layers,
            "final_norm": arrays[-2], "head": arrays[-1]}


def leaf_names(cfg):
    names = ["embed"]
    for i in range(cfg["num_hidden_layers"]):
        names += [f"layer{i}.{k}" for k in layer_keys(cfg, i)]
    return names + ["final_norm", "head"]


def flat_leaves(tree, cfg):
    flat = [tree["embed"]]
    for i, layer in enumerate(tree["layers"]):
        flat += [layer[k] for k in layer_keys(cfg, i)]
    return flat + [tree["final_norm"], tree["head"]]


def allowed(q_pos, k_pos, window):
    """The mask: key j is read by query i where j <= i and, under a
    window of W keys (the query's own included), i - W < j."""
    seen = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        seen = seen & (k_pos[None, :] > q_pos[:, None] - window)
    return seen


def attention(h, layer, cfg, kind, heads, q_block=None, remat=False):
    """Grouped-query attention of a layer of type `kind` with `heads`
    query heads: query head a reads key/value head a // (heads / kv
    heads); q, k normalised per head; the layer type's RoPE and mask;
    each head's context times its gate.  `q_block`: rows of the scores
    computed at a time, one block after another (a window layer's block
    reads only the keys that can be allowed; the mask is applied all
    the same)."""
    n, t, _ = h.shape
    kv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    inv_freq, scale = rope_inv_freq(cfg["rope_parameters"][kind],
                                    rotary_lanes(cfg, kind))
    q = rms_norm((h @ layer["wq"]).reshape(n, t, heads, d),
                 layer["q_norm"], eps)
    k = rms_norm((h @ layer["wk"]).reshape(n, t, kv, d), layer["k_norm"],
                 eps)
    v = (h @ layer["wv"]).reshape(n, t, kv, d)
    q, k = rope(q, inv_freq, scale), rope(k, inv_freq, scale)
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    step = q_block or t
    if t % step:
        raise ValueError(f"{t} positions are not whole blocks of {step}")
    # the keys a block of `step` queries can be allowed: all of them, or
    # under a window the block's own and the window - 1 before it
    span = t if window is None else min(t, step + window - 1)

    def block(lo):
        k_lo = jnp.clip(lo + step - span, 0, t - span)
        q_rows = jax.lax.dynamic_slice_in_dim(q, lo, step, axis=1)
        keys = jax.lax.dynamic_slice_in_dim(k, k_lo, span, axis=1)
        values = jax.lax.dynamic_slice_in_dim(v, k_lo, span, axis=1)
        scores = jnp.einsum("nqhd,nkhd->nhqk", q_rows, keys) \
            / jnp.sqrt(float(d))
        seen = allowed(lo + jnp.arange(step), k_lo + jnp.arange(span), window)
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd",
                          jax.nn.softmax(scores, axis=-1), values)

    if remat:
        block = jax.checkpoint(block)
    # one block after another (`lax.map`): the backward pass then adds
    # each block's part of dk, dv into ONE buffer, not a buffer a block
    outs = jax.lax.map(block, jnp.arange(0, t, step))     # (blocks, n, ..)
    ctx = jnp.moveaxis(outs, 0, 1).reshape(n, t, heads, d)
    gate = jax.nn.sigmoid(h @ layer["wg"])                # (n, t, heads)
    return (ctx * gate[..., None]).reshape(n, t, heads * d) @ layer["wo"]


def swiglu_ffn(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def experts(x, layer, cfg, router_gradient=True):
    """x (T, D) -> (y (T, D), counts of the held experts (G,), chosen
    experts (T, k)): the held experts' part of the routed sum, times
    `moe_routed_scaling_factor`.  Soft-max over ALL router outputs in
    float32; the k largest; their weights over their sum; a python loop
    over the HELD experts.  `router_gradient=False`: the weights are
    constants of the backward pass."""
    k = cfg["num_experts_per_tok"]
    e = layer["router"].shape[1]
    held = layer["w1"].shape[0]
    first = cfg.get("expert_parallel_rank", 0) * held
    probs = jax.nn.softmax(x @ layer["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if not router_gradient:
        top_p = jax.lax.stop_gradient(top_p)
    gate = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32)
                   * top_p[..., None], axis=1)          # (T, E)
    y = jnp.zeros_like(x)
    for i in range(held):
        y = y + gate[:, first + i:first + i + 1] * swiglu_ffn(
            x, layer["w1"][i], layer["w3"][i], layer["w2"][i])
    counts = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32),
                     axis=(0, 1))[first:first + held]
    return y * cfg.get("moe_routed_scaling_factor", 1.0), counts, top_e


def decoder_layer(x, layer, i, cfg, q_block=None, remat=False):
    """Layer `i`: x (N, T, D) -> (x, counts (G,), experts (N*T, k));
    the last two None in a dense layer."""
    eps = cfg["rms_norm_eps"]
    n, t, d = x.shape
    x = x + attention(rms_norm(x, layer["op_norm"], eps), layer, cfg,
                      cfg["layer_types"][i],
                      cfg["num_attention_heads_per_layer"][i], q_block,
                      remat)
    h = rms_norm(x, layer["ffn_norm"], eps)
    if cfg["mlp_layer_types"][i] == "dense":
        return x + swiglu_ffn(h, layer["w1"], layer["w3"],
                              layer["w2"]), None, None
    # no exchange sums the ranks' parts of a share's gradient: the
    # builder's decision (models/decoder.py), made here as there
    y, counts, top_e = experts(
        h.reshape(n * t, d), layer, cfg,
        router_gradient=cfg.get("expert_parallel_size", 1) == 1)
    shared = swiglu_ffn(h, layer["shared_w1"], layer["shared_w3"],
                        layer["shared_w2"])
    return x + y.reshape(n, t, d) + shared, counts, top_e


def forward(params, tokens, cfg, q_block=None, remat=False):
    """tokens (N, T) int -> dict(logits (N, T, V), counts [(G,) per
    sparse layer], experts [(N*T, k) per sparse layer]).  `remat`: a
    layer's (and an attention block's) intermediates are computed again
    in the backward pass and not kept, so that the gradients of 16384
    positions fit one chip; the numbers are the same."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        counts, chosen = [], []
        for i, layer in enumerate(params["layers"]):
            def run(x, layer, i=i):
                return decoder_layer(x, layer, i, cfg, q_block, remat)

            x, c, te = (jax.checkpoint(run) if remat else run)(x, layer)
            if c is not None:
                counts.append(c), chosen.append(te)
        x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        return {"logits": x @ params["head"], "counts": counts,
                "experts": chosen}


def loss(params, tokens, labels, cfg, q_block=None, remat=False):
    """(mean token cross-entropy, `forward`'s dict plus `ce`)."""
    out = forward(params, tokens, cfg, q_block, remat)
    logp = jax.nn.log_softmax(out["logits"], axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return ce, dict(out, ce=ce)


def loss_and_grads(params, tokens, labels, cfg, q_block=None):
    """((loss, parts), gradient tree shaped like `params`).  With
    `q_block` the scores go `q_block` rows at a time and every layer is
    recomputed in the backward pass (`remat`)."""
    return jax.value_and_grad(loss, has_aux=True)(
        params, tokens, labels, cfg, q_block, q_block is not None)
