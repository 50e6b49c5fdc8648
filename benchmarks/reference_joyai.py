"""Plain float32 reference of JoyAI-LLM-Flash's forward pass, both
losses and gradients (a DeepSeek-V3-shaped decoder: latent attention,
sigmoid-routed experts beside a shared one, a multi-token-prediction
module; layer equations as the public DeepSeek-V3-family
implementation has them).  The benchmark's own, so that the comparison
that decides a cell's correctness does not move when the program does.

Straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`.  No Program, no Executor,
no AMP, no kernel, no sort.  Latent attention keeps the PUBLISHED
parameter layout: `wq_b` (q_lora_rank, H * 192) and `wkv_b`
(kv_lora_rank, H * 256) hold a head's [unrotated 128 | rotary 64] and
its [key 128 | value 128] side by side, `wkv_a` (D, 512 + 64) the
latent and the one rotary key; it concatenates a head's two parts into
192-wide queries and keys, repeats the rotary key over the heads and
materialises the scores (`q_block` rows at a time where 8192 positions
would not fit otherwise).  Rotary positions are the published
`rope_interleave` path: the pairs (2i, 2i+1) are gathered into halves
and the halves rotated, on queries and keys alike (which leaves every
score what rotating the pairs in place gives).  The expert layer is a
python loop over the held experts, each a dense SwiGLU FFN applied to
every token and weighted by the router's weight where the expert is
among the token's eight and by zero where it is not; the shared expert
is one more dense SwiGLU, added whole.

The system holds `wq_b` and `wkv_b` as column blocks of its own (all
heads' unrotated parts, all heads' rotary parts; all heads' keys, all
heads' values: four projections) and `wkv_a` as two;
`params_from_list` maps the system's flat parameter list onto this
layout and `grads_to_list` maps a gradient tree back.

Departures from the published description, each deliberate:

- no auxiliary loss (the configuration has no coefficient);
- the selection bias is an input that nothing updates here (the
  training step's update is `bias_update`, compared on its own);
- where `expert_parallel_size` chips share each layer's experts, the
  routed part is ONE rank's share, as `reference_lfm2.py` has it: the
  router is as wide as published, the weights are the held experts',
  and what the experts held elsewhere would have added is LEFT OUT;
  the shared expert is whole.  `forward` makes the builder's decision:
  under `expert_parallel_size` > 1 the routing weights are constants of
  the backward pass;
- the vocabulary is the slice the configuration states;
- RoPE's frequencies are computed on the host (numpy float32), as a
  checkpoint's `inv_freq` buffer is (PERF.md, PR 26).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NORM_TOPK_EPS = 1e-20


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope_interleaved(x, theta):
    """x (N, T, H, D): gather the pairs (2i, 2i+1) into halves, then
    the rotate-half rotary embedding, positions 0..T-1."""
    n, t, h, d = x.shape
    x = x.reshape(n, t, h, d // 2, 2).swapaxes(-1, -2).reshape(n, t, h, d)
    inv_freq = (1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
                ).astype(np.float32)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


# the system's parameters of one block, in the order
# `models/decoder.py` creates them
ATTENTION_KEYS = ("op_norm", "wq_a", "q_norm", "wq_b.nope", "wq_b.rope",
                  "wkv_a.latent", "kv_norm", "wkv_a.rope", "wkv_b.key",
                  "wkv_b.value", "wo")
FFN_KEYS = {"dense": ("ffn_norm", "w1", "w3", "w2"),
            "experts": ("ffn_norm", "router", "w1", "w2", "w3",
                        "shared_w1", "shared_w3", "shared_w2")}
MTP_KEYS = ("mtp.enorm", "mtp.hnorm", "mtp.eh")


def block_keys(dense):
    return ATTENTION_KEYS + FFN_KEYS["dense" if dense else "experts"]


def system_names(cfg):
    """A name for every parameter of the system, in the builder's
    creation order: embedding, the layers, final norm, head, then the
    module (its two norms, its projection, its block, its final norm;
    the table and the head are the main model's)."""
    names = ["embed"]
    for i in range(cfg["num_hidden_layers"]):
        names += [f"layer{i}.{k}"
                  for k in block_keys(i < cfg["first_k_dense_replace"])]
    names += ["final_norm", "head"]
    if cfg["num_nextn_predict_layers"]:
        names += list(MTP_KEYS) + [f"mtp.block.{k}"
                                   for k in block_keys(False)] + ["mtp.norm"]
    return names


def _per_head(heads, *blocks):
    """Column blocks (R, H*w_j), each H heads side by side, into the
    published (R, H * sum w_j): a head's parts side by side."""
    parts = [b.reshape(b.shape[0], heads, -1) for b in blocks]
    return jnp.concatenate(parts, axis=-1).reshape(blocks[0].shape[0], -1)


def _column_blocks(w, heads, *widths):
    """The inverse of `_per_head`."""
    parts = w.reshape(w.shape[0], heads, -1)
    out, at = [], 0
    for width in widths:
        out.append(parts[:, :, at:at + width].reshape(w.shape[0], -1))
        at += width
    return out


def _block_from_system(flat, cfg):
    heads = cfg["num_attention_heads"]
    layer = {k: v for k, v in flat.items() if "." not in k}
    layer["wq_b"] = _per_head(heads, flat["wq_b.nope"], flat["wq_b.rope"])
    layer["wkv_a"] = jnp.concatenate([flat["wkv_a.latent"],
                                      flat["wkv_a.rope"]], axis=1)
    layer["wkv_b"] = _per_head(heads, flat["wkv_b.key"], flat["wkv_b.value"])
    return layer


def _block_to_system(layer, cfg, dense):
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    flat = dict(layer)
    flat["wq_b.nope"], flat["wq_b.rope"] = _column_blocks(
        layer["wq_b"], heads, cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"])
    flat["wkv_a.latent"] = layer["wkv_a"][:, :rank]
    flat["wkv_a.rope"] = layer["wkv_a"][:, rank:]
    flat["wkv_b.key"], flat["wkv_b.value"] = _column_blocks(
        layer["wkv_b"], heads, cfg["qk_nope_head_dim"], cfg["v_head_dim"])
    return [flat[k] for k in block_keys(dense)]


def params_from_list(arrays, cfg, biases=None):
    """The reference's parameter tree (published layout) from the
    system's flat list in `system_names` order.  `biases`: the
    selection bias (E,) of each routed layer, the module's last (not
    parameters: no gradient reaches them); None = zeros."""
    names = system_names(cfg)
    if len(arrays) != len(names):
        raise ValueError(f"{len(arrays)} arrays, {len(names)} expected")
    flat = {n: jnp.asarray(a, jnp.float32) for n, a in zip(names, arrays)}
    routed = [0]

    def block(prefix):
        layer = _block_from_system(
            {n[len(prefix):]: a for n, a in flat.items()
             if n.startswith(prefix)}, cfg)
        if "router" in layer:
            e = layer["router"].shape[1]
            layer["bias"] = (jnp.zeros((e,), jnp.float32) if biases is None
                             else jnp.asarray(biases[routed[0]],
                                              jnp.float32))
            routed[0] += 1
        return layer

    params = {"embed": flat["embed"],
              "layers": [block(f"layer{i}.")
                         for i in range(cfg["num_hidden_layers"])],
              "final_norm": flat["final_norm"], "head": flat["head"]}
    if cfg["num_nextn_predict_layers"]:
        params["mtp"] = {"enorm": flat["mtp.enorm"],
                         "hnorm": flat["mtp.hnorm"], "eh": flat["mtp.eh"],
                         "block": block("mtp.block."),
                         "norm": flat["mtp.norm"]}
    return params


def grads_to_list(grads, cfg):
    """A gradient tree shaped like `params_from_list`'s, as the flat
    list in `system_names` order (the selection biases left out)."""
    dense = cfg["first_k_dense_replace"]
    flat = [grads["embed"]]
    for i, layer in enumerate(grads["layers"]):
        flat += _block_to_system(layer, cfg, i < dense)
    flat += [grads["final_norm"], grads["head"]]
    if "mtp" in grads:
        m = grads["mtp"]
        flat += [m["enorm"], m["hnorm"], m["eh"]]
        flat += _block_to_system(m["block"], cfg, False) + [m["norm"]]
    return flat


def latent_attention(h, layer, cfg, q_block=None, remat=False):
    """h (N, T, D) -> (N, T, D): queries, keys and values out of their
    low-rank latents, one rotary key for all heads, 192-wide scores."""
    n, t, _ = h.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, theta = cfg["kv_lora_rank"], float(cfg["rope_theta"])
    q = (rms_norm(h @ layer["wq_a"], layer["q_norm"], eps) @ layer["wq_b"]
         ).reshape(n, t, heads, nope + rope)
    ckv = h @ layer["wkv_a"]
    kv = (rms_norm(ckv[..., :rank], layer["kv_norm"], eps) @ layer["wkv_b"]
          ).reshape(n, t, heads, nope + cfg["v_head_dim"])
    k_rope = rope_interleaved(ckv[..., rank:][:, :, None, :], theta)
    q = jnp.concatenate([q[..., :nope],
                         rope_interleaved(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.repeat(k_rope, heads, axis=2)], axis=-1)
    v = kv[..., nope:]
    step = q_block or t

    def block(q_rows, k, v, lo):
        scores = jnp.einsum("nqhd,nkhd->nhqk", q_rows, k) \
            / jnp.sqrt(float(nope + rope))
        seen = (jnp.arange(t)[None, :]
                <= (lo + jnp.arange(q_rows.shape[1]))[:, None])
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    if remat:
        block = jax.checkpoint(block)
    outs = [block(q[:, lo:lo + step], k, v, lo) for lo in range(0, t, step)]
    return jnp.concatenate(outs, axis=1).reshape(n, t, -1) @ layer["wo"]


def swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def experts(x, layer, cfg, router_gradient=True):
    """x (T, D) -> (routed part y (T, D), counts of the held experts
    (G,), chosen experts (T, k)).  Sigmoid scores; the k experts with
    the largest score + bias; weights the unbiased scores over their
    sum + 1e-20, times the scaling factor; a python loop over the HELD
    experts.  `router_gradient=False`: the weights are constants of the
    backward pass."""
    k = cfg["num_experts_per_tok"]
    e = layer["router"].shape[1]
    held = layer["w1"].shape[0]
    first = cfg.get("expert_parallel_rank", 0) * held
    scores = jax.nn.sigmoid(x @ layer["router"])
    _, top_e = jax.lax.top_k(scores + layer["bias"], k)
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True)
                         + NORM_TOPK_EPS)
    top_s = top_s * cfg["routed_scaling_factor"]
    if not router_gradient:
        top_s = jax.lax.stop_gradient(top_s)
    chosen = jax.nn.one_hot(top_e, e, dtype=jnp.float32)
    gate = jnp.sum(chosen * top_s[..., None], axis=1)          # (T, E)
    y = jnp.zeros_like(x)
    for i in range(held):
        y = y + gate[:, first + i:first + i + 1] * swiglu(
            x, layer["w1"][i], layer["w3"][i], layer["w2"][i])
    counts = jnp.sum(chosen, axis=(0, 1))[first:first + held]
    return y, counts, top_e


def decoder_layer(x, layer, cfg, q_block=None, remat=False):
    """One block: x (N, T, D) -> (x, counts (G,) or None, experts
    (N*T, k) or None)."""
    eps = cfg["rms_norm_eps"]
    n, t, d = x.shape
    x = x + latent_attention(rms_norm(x, layer["op_norm"], eps), layer, cfg,
                             q_block, remat)
    h = rms_norm(x, layer["ffn_norm"], eps)
    if "router" not in layer:
        return x + swiglu(h, layer["w1"], layer["w3"], layer["w2"]), \
            None, None
    # no exchange sums the ranks' parts of a share's gradient: the
    # builder's decision (models/decoder.py), made here as there
    hf = h.reshape(n * t, d)
    y, counts, top_e = experts(
        hf, layer, cfg,
        router_gradient=cfg.get("expert_parallel_size", 1) == 1)
    y = y + swiglu(hf, layer["shared_w1"], layer["shared_w3"],
                   layer["shared_w2"])
    return x + y.reshape(n, t, d), counts, top_e


def forward(params, tokens, labels, cfg, q_block=None, remat=False):
    """tokens, labels (N, T) int -> dict(logits (N, T, V), mtp_logits
    (the module's, predicting the labels' successors; None without a
    module), counts [(G,) per routed layer], experts [(N*T, k) per
    routed layer], the module's layer last).  `labels` are the tokens'
    successors: the module embeds them.  `remat`: a block's (and an
    attention block's) intermediates are computed again in the backward
    pass and not kept, so that the gradients of 8192 positions fit one
    chip; the numbers are the same."""
    eps = cfg["rms_norm_eps"]
    counts, chosen = [], []

    def run(x, layer):
        def one(x, layer):
            return decoder_layer(x, layer, cfg, q_block, remat)

        x, c, te = (jax.checkpoint(one) if remat else one)(x, layer)
        if c is not None:
            counts.append(c), chosen.append(te)
        return x

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for layer in params["layers"]:
            x = run(x, layer)
        x = rms_norm(x, params["final_norm"], eps)
        logits = x @ params["head"]
        mtp_logits = None
        if "mtp" in params:
            m = params["mtp"]
            g = jnp.concatenate(
                [rms_norm(params["embed"][labels], m["enorm"], eps),
                 rms_norm(x, m["hnorm"], eps)], axis=-1) @ m["eh"]
            g = rms_norm(run(g, m["block"]), m["norm"], eps)
            mtp_logits = g @ params["head"]
        return {"logits": logits, "mtp_logits": mtp_logits,
                "counts": counts, "experts": chosen}


def cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(params, tokens, labels, next_labels, cfg, mtp_loss_weight,
         q_block=None, remat=False):
    """(ce + mtp_loss_weight x mtp_ce, `forward`'s dict plus `ce` and
    `mtp_ce`)."""
    out = forward(params, tokens, labels, cfg, q_block, remat)
    ce = cross_entropy(out["logits"], labels)
    total, mtp_ce = ce, None
    if out["mtp_logits"] is not None:
        mtp_ce = cross_entropy(out["mtp_logits"], next_labels)
        total = ce + mtp_loss_weight * mtp_ce
    return total, dict(out, ce=ce, mtp_ce=mtp_ce)


def loss_and_grads(params, tokens, labels, next_labels, cfg,
                   mtp_loss_weight, q_block=None):
    """((loss, parts), gradient tree shaped like `params`; the
    selection biases' entries are zeros: nothing reaches them).  With
    `q_block` the scores go `q_block` rows at a time and every block is
    recomputed in the backward pass (`remat`)."""
    return jax.value_and_grad(loss, has_aux=True)(
        params, tokens, labels, next_labels, cfg, mtp_loss_weight, q_block,
        q_block is not None)


def bias_update(bias, chosen, rate):
    """The selection bias after one step: `bias + rate * sign(mean load
    - load)` over all E experts' rows of the step; `chosen` (T, k)."""
    load = jnp.sum(jax.nn.one_hot(chosen, bias.shape[0], dtype=jnp.float32),
                   axis=(0, 1))
    return bias + rate * jnp.sign(jnp.mean(load) - load)
