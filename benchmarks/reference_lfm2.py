"""Plain float32 reference of LFM2-24B-A2B's forward pass, loss and
gradients (layer equations as the public `lfm2_moe`
implementation): the benchmark's own copy of the `lfm2_*`
half of `paddle_tpu/models/decoder_reference.py`, so that the
comparison that decides a cell's correctness does not move when the
program does.  `tests/benchmark/test_lfm2_cell.py` holds the two to
the same numbers.

Straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`.  No Program, no Executor,
no AMP, no kernel, no sort: the short convolution is one shifted
product a tap, attention materialises its scores (`q_block` rows at a
time where 8192 positions would not fit otherwise) and repeats the
key/value heads, and the expert layer is a python loop over the held
experts, each a dense SwiGLU FFN applied to every token and weighted
by the router's weight where the expert is among the token's four and
by zero where it is not.

Departures from the published description, each deliberate:

- no auxiliary loss (the configuration has none);
- the selection bias is an input that nothing updates (the
  configuration publishes no update rate);
- where `expert_parallel_size` chips share each layer's experts, the
  expert layer is ONE rank's share: the router is as wide as
  published, the weights are the held experts', and what the experts
  held elsewhere would have added is LEFT OUT of the layer's result,
  here as in the program (size 1, rank 0: the whole layer).
  `experts` differentiates a share truly (the rank's own part of
  every gradient); `forward` makes the builder's decision, as
  `models/decoder.py` does: under `expert_parallel_size` > 1 no
  exchange sums the ranks' parts, so the routing weights are constants
  of the backward pass there (`router_gradient=False`);
- RoPE's frequencies are computed on the host (numpy float32), as a
  checkpoint's `inv_freq` buffer is (PERF.md, PR 26).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta):
    """x (N, T, H, D): rotate-half rotary embedding over the whole
    head, positions 0..T-1."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = (1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
                ).astype(np.float32)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


OPERATOR_KEYS = {
    "conv": ("op_norm", "w_in", "filter", "w_out"),
    "full_attention": ("op_norm", "wq", "q_norm", "wk", "k_norm", "wv",
                       "wo"),
}
FFN_KEYS = {"dense": ("ffn_norm", "w1", "w3", "w2"),
                 "experts": ("ffn_norm", "router", "w1", "w2", "w3")}


def layer_keys(cfg, i):
    """Parameters of layer i in the order `models/decoder.py` creates
    them."""
    ffn = "dense" if i < cfg["num_dense_layers"] else "experts"
    return OPERATOR_KEYS[cfg["layer_types"][i]] + FFN_KEYS[ffn]


def params_from_list(arrays, cfg, biases=None):
    """The parameter tree from a flat list in the builder's creation
    order: embedding, `layer_keys` per layer, final norm, head.
    `biases`: the selection bias (E,) of each routed layer, in order
    (not parameters: no gradient reaches them); None = zeros."""
    arrays = [jnp.asarray(a, jnp.float32) for a in arrays]
    layers, at, routed = [], 1, 0
    for i in range(cfg["num_hidden_layers"]):
        keys = layer_keys(cfg, i)
        layer = dict(zip(keys, arrays[at:at + len(keys)]))
        at += len(keys)
        if "router" in layer:
            e = layer["router"].shape[1]
            layer["bias"] = (jnp.zeros((e,), jnp.float32) if biases is None
                             else jnp.asarray(biases[routed], jnp.float32))
            routed += 1
        layers.append(layer)
    if len(arrays) != at + 2:
        raise ValueError(f"{len(arrays)} arrays, {at + 2} expected")
    return {"embed": arrays[0], "layers": layers,
            "final_norm": arrays[-2], "head": arrays[-1]}


def short_conv(h, layer):
    """h (N, T, D) -> (N, T, D): B, C, u = split3(h W_in); a causal
    depthwise convolution of B * u, one tap at a time; gated by C."""
    d = h.shape[-1]
    bcu = h @ layer["w_in"]
    b, c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    z = b * u
    taps = layer["filter"].shape[1]
    t = h.shape[1]
    conv = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j                  # tap j reads z[t - back]
        shifted = jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, :t - back]], axis=1)
        conv = conv + layer["filter"][:, j] * shifted
    return (c * conv) @ layer["w_out"]


def attention(h, layer, cfg, q_block=None, remat=False):
    """Grouped-query causal attention: query head j reads key/value
    head j // (heads / kv heads); q, k normalised per head.  `q_block`:
    rows of the (T, T) scores computed at a time (so that 8192
    positions fit); None = all at once.  `remat`: a block's scores are
    computed again in the backward pass and not kept."""
    n, t, _ = h.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    eps = cfg["norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    q = rms_norm((h @ layer["wq"]).reshape(n, t, heads, d),
                 layer["q_norm"], eps)
    k = rms_norm((h @ layer["wk"]).reshape(n, t, kv, d), layer["k_norm"],
                 eps)
    v = (h @ layer["wv"]).reshape(n, t, kv, d)
    q, k = rope(q, theta), rope(k, theta)
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    step = q_block or t

    def block(q_rows, k, v, lo):
        scores = jnp.einsum("nqhd,nkhd->nhqk", q_rows, k) \
            / jnp.sqrt(float(d))
        seen = (jnp.arange(t)[None, :]
                <= (lo + jnp.arange(q_rows.shape[1]))[:, None])
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    if remat:
        block = jax.checkpoint(block)
    outs = [block(q[:, lo:lo + step], k, v, lo) for lo in range(0, t, step)]
    return jnp.concatenate(outs, axis=1).reshape(n, t, heads * d) \
        @ layer["wo"]


def experts(x, layer, cfg, router_gradient=True):
    """x (T, D) -> (y (T, D), counts of the held experts (G,), chosen
    experts (T, k)).  Sigmoid scores; the k experts with the largest
    score + bias; weights the unbiased scores over their sum + 1e-6,
    times the scaling factor; a python loop over the HELD experts.
    `router_gradient=False`: the weights are constants of the backward
    pass."""
    k = cfg["num_experts_per_tok"]
    e = layer["router"].shape[1]
    held = layer["w1"].shape[0]
    first = cfg.get("expert_parallel_rank", 0) * held
    scores = jax.nn.sigmoid(x @ layer["router"])
    _, top_e = jax.lax.top_k(scores + layer["bias"], k)
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-6)
    top_s = top_s * cfg["routed_scaling_factor"]
    if not router_gradient:
        top_s = jax.lax.stop_gradient(top_s)
    gate = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32)
                   * top_s[..., None], axis=1)          # (T, E)
    y = jnp.zeros_like(x)
    for i in range(held):
        hidden = jax.nn.silu(x @ layer["w1"][i]) * (x @ layer["w3"][i])
        y = y + gate[:, first + i:first + i + 1] * (hidden @ layer["w2"][i])
    counts = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32),
                     axis=(0, 1))[first:first + held]
    return y, counts, top_e


def decoder_layer(x, layer, kind, cfg, q_block=None, remat=False):
    """One layer: x (N, T, D) -> (x, counts (G,) or None, experts
    (N*T, k) or None)."""
    eps = cfg["norm_eps"]
    n, t, d = x.shape
    h = rms_norm(x, layer["op_norm"], eps)
    x = x + (short_conv(h, layer) if kind == "conv"
             else attention(h, layer, cfg, q_block, remat))
    h = rms_norm(x, layer["ffn_norm"], eps)
    if "router" not in layer:
        return x + (jax.nn.silu(h @ layer["w1"]) * (h @ layer["w3"])
                    ) @ layer["w2"], None, None
    # no exchange sums the ranks' parts of a share's gradient: the
    # builder's decision (models/decoder.py), made here as there
    y, counts, top_e = experts(
        h.reshape(n * t, d), layer, cfg,
        router_gradient=cfg.get("expert_parallel_size", 1) == 1)
    return x + y.reshape(n, t, d), counts, top_e


def forward(params, tokens, cfg, q_block=None, remat=False):
    """tokens (N, T) int -> dict(logits (N, T, V), counts [(G,) per
    routed layer], experts [(N*T, k) per routed layer]).  `remat`: a
    layer's (and an attention block's) intermediates are computed again
    in the backward pass and not kept, so that the gradients of 8192
    positions fit one chip; the numbers are the same."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        counts, chosen = [], []
        for kind, layer in zip(cfg["layer_types"], params["layers"]):
            def run(x, layer, kind=kind):
                return decoder_layer(x, layer, kind, cfg, q_block, remat)

            x, c, te = (jax.checkpoint(run) if remat else run)(x, layer)
            if c is not None:
                counts.append(c), chosen.append(te)
        x = rms_norm(x, params["final_norm"], cfg["norm_eps"])
        return {"logits": x @ params["head"], "counts": counts,
                "experts": chosen}


def loss(params, tokens, labels, cfg, q_block=None, remat=False):
    """(mean token cross-entropy, `forward`'s dict plus `ce`)."""
    out = forward(params, tokens, cfg, q_block, remat)
    logp = jax.nn.log_softmax(out["logits"], axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return ce, dict(out, ce=ce)


def loss_and_grads(params, tokens, labels, cfg, q_block=None):
    """((loss, parts), gradient tree shaped like `params`; the
    selection biases' entries are zeros: nothing reaches them).  With
    `q_block` the scores go `q_block` rows at a time and every layer is
    recomputed in the backward pass (`remat`)."""
    return jax.value_and_grad(loss, has_aux=True)(
        params, tokens, labels, cfg, q_block, q_block is not None)
