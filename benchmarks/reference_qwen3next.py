"""Plain float32 reference of the hybrid linear-attention MoE decoder
(`model_type` "qwen3_next": Qwen3-Next-80B-A3B's layer equations, ISSUE
44) forward pass, loss and gradients: the benchmark's own, so that the
comparison that decides a cell's correctness does not move when the
program does.

Straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`.  No Program, no Executor, no
AMP, no kernel, no chunk, no sort.  Every norm of the decoder is
zero-centred, `zrms(x) = x rsqrt(mean x^2 + eps) (1 + w)`, but the
mixer's output norm, whose scale is plain.

    layer i: linear_attention unless (i + 1) % full_attention_interval == 0
    x = x + mixer(zrms(x));  x = x + moe(zrms(x))

Gated DeltaNet mixer (Yang, Kautz, Hatamizadeh, arXiv:2412.06464),
published column order [q | k | v | z] and [b | a]:

    [q | k | v] = silu(causal_depthwise_conv(h W_qkv));  z = h W_z
    q = l2norm(q) Dk^-1/2,  k = l2norm(k)      (a head; eps 1e-6)
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
    a value head h (reading key head h // (Hv / Hk)), S_0 = 0 (Dk, Dv):
      S'_t = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'_t^T k_t)
      S_t = S'_t + k_t u_t^T;   o_t = S_t^T q_t
    y = rms(o) w_o silu(z)  a head;   out = y W_out

THE RECURRENCE IS A `lax.scan` OVER POSITIONS, as written: one rank-one
update a position.  Gated full attention: q, k normalised a head
(zero-centred), rotate-half RoPE over the first `partial_rotary_factor`
of each head's lanes, an explicit causal mask (`q_block` rows of scores
at a time where 16384 positions would not fit otherwise), key/value
heads repeated, the context times sigmoid(h W_gate) before W_o.  Sparse
block: soft-max over ALL router outputs, the k largest, weights over
their sum, a python loop over the HELD experts, plus
sigmoid(h w_sg) x the shared SwiGLU expert.

Where `expert_parallel_size` chips share each layer's experts the
expert layer is ONE rank's share, as `reference_lfm2.py` sets out: the
router is as wide as published, what the experts held elsewhere would
have added is LEFT OUT, the shared expert is whole, and `forward` holds
the routing weights constant in the backward pass as
`models/decoder.py` does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LINEAR_KEYS = ("op_norm", "w_qkv", "conv", "w_z", "w_ba", "A_log", "dt_bias",
               "out_norm", "w_out")
ATTENTION_KEYS = ("op_norm", "wq", "q_norm", "wk", "k_norm", "wv", "w_gate",
                  "wo")
EXPERT_KEYS = ("ffn_norm", "router", "w1", "w2", "w3", "shared_w1",
               "shared_w3", "shared_w2", "shared_gate")
L2_EPS = 1e-6


def layer_types(cfg):
    every = cfg["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(cfg["num_hidden_layers"])]


def layer_keys(kind):
    return (LINEAR_KEYS if kind == "linear_attention"
            else ATTENTION_KEYS) + EXPERT_KEYS


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def zrms(x, weight, eps):
    return rms(x, eps) * (1.0 + weight)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta, rotary):
    """x (N, T, H, D): rotate-half over lanes 0..rotary-1 of each head,
    as a head of `rotary` lanes would turn; the rest passes through."""
    t = x.shape[1]
    inv_freq = 1.0 / float(theta) ** (np.arange(0, rotary, 2,
                                                dtype=np.float64) / rotary)
    freqs = (jnp.arange(t, dtype=jnp.float32)[:, None]
             * np.asarray(inv_freq, np.float32)[None, :])
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    turn, keep = x[..., :rotary], x[..., rotary:]
    return jnp.concatenate(
        [turn * jnp.cos(emb) + rotate_half(turn) * jnp.sin(emb), keep],
        axis=-1)


def params_from_list(arrays, cfg):
    """The parameter tree from a flat list in the builder's creation
    order: embedding, a layer's keys by its kind, final norm, head."""
    arrays = [jnp.asarray(a, jnp.float32) for a in arrays]
    layers, at = [], 1
    for kind in layer_types(cfg):
        keys = layer_keys(kind)
        layers.append(dict(zip(keys, arrays[at:at + len(keys)])))
        at += len(keys)
    if len(arrays) != at + 2:
        raise ValueError(f"{len(arrays)} arrays, {at + 2} expected")
    return {"embed": arrays[0], "layers": layers,
            "final_norm": arrays[-2], "head": arrays[-1]}


def leaf_names(cfg):
    names = ["embed"]
    for i, kind in enumerate(layer_types(cfg)):
        names += [f"layer{i}.{k}" for k in layer_keys(kind)]
    return names + ["final_norm", "head"]


def flat_leaves(tree):
    flat = [tree["embed"]]
    for layer in tree["layers"]:
        kind = "linear_attention" if "w_qkv" in layer else "full_attention"
        flat += [layer[k] for k in layer_keys(kind)]
    return flat + [tree["final_norm"], tree["head"]]


def causal_conv(x, w):
    """x (N, T, C), w (C, L): y[t] = sum_j w[:, j] x[t - (L-1) + j]."""
    taps, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[:, j] * padded[:, j:j + t] for j in range(taps))


def delta_rule(q, k, v, g, beta, segment=None):
    """The recurrence a position at a time.  q, k (N, T, Hv, Dk) (the
    key heads already repeated), v (N, T, Hv, Dv), g, beta (N, T, Hv);
    returns o (N, T, Hv, Dv).  `segment`: the same scan over positions
    written as a scan over runs of `segment` positions whose inner scan
    is recomputed in the backward pass, which then keeps one state a
    run and not one a position (2 MB each at 32 heads of 128 x 128)."""
    n, t, hv, dk = k.shape
    dv = v.shape[-1]

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("nhkv,nhk->nhv", s, k_t))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("nhkv,nhk->nhv", s, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    s0 = jnp.zeros((n, hv, dk, dv), jnp.float32)
    if segment is None or segment >= t:
        _, o = jax.lax.scan(step, s0, xs)
        return jnp.moveaxis(o, 0, 1)
    if t % segment:
        raise ValueError(f"{t} positions are not whole runs of {segment}")

    @jax.checkpoint
    def run(s, xs):
        return jax.lax.scan(step, s, xs)

    _, o = jax.lax.scan(run, s0, tuple(
        x.reshape((t // segment, segment) + x.shape[1:]) for x in xs))
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def linear_attention(h, layer, cfg, segment=None):
    n, t, _ = h.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    qkv = jax.nn.silu(causal_conv(h @ layer["w_qkv"], layer["conv"]))
    z = (h @ layer["w_z"]).reshape(n, t, hv, dv)
    ba = h @ layer["w_ba"]

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + L2_EPS)

    q = l2norm(qkv[..., :hk * dk].reshape(n, t, hk, dk)) * dk ** -0.5
    k = l2norm(qkv[..., hk * dk:2 * hk * dk].reshape(n, t, hk, dk))
    v = qkv[..., 2 * hk * dk:].reshape(n, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(ba[..., hv:]
                                                   + layer["dt_bias"])
    o = delta_rule(jnp.repeat(q, hv // hk, axis=2),
                   jnp.repeat(k, hv // hk, axis=2), v, g, beta, segment)
    y = rms(o, cfg["rms_norm_eps"]) * layer["out_norm"] * jax.nn.silu(z)
    return y.reshape(n, t, hv * dv) @ layer["w_out"]


def attention(h, layer, cfg, q_block=None, remat=False):
    """Gated grouped-query attention: query head a reads key/value head
    a // (heads / kv heads).  `q_block`: rows of the scores at a time."""
    n, t, _ = h.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    rotary = int(d * cfg["partial_rotary_factor"])
    q = zrms((h @ layer["wq"]).reshape(n, t, heads, d), layer["q_norm"], eps)
    k = zrms((h @ layer["wk"]).reshape(n, t, kv, d), layer["k_norm"], eps)
    v = (h @ layer["wv"]).reshape(n, t, kv, d)
    q, k = rope(q, cfg["rope_theta"], rotary), rope(k, cfg["rope_theta"],
                                                    rotary)
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    step = q_block or t
    if t % step:
        raise ValueError(f"{t} positions are not whole blocks of {step}")

    def block(lo):
        q_rows = jax.lax.dynamic_slice_in_dim(q, lo, step, axis=1)
        scores = jnp.einsum("nqhd,nkhd->nhqk", q_rows, k) / jnp.sqrt(float(d))
        seen = jnp.arange(t)[None, :] <= (lo + jnp.arange(step))[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    if remat:
        block = jax.checkpoint(block)
    outs = jax.lax.map(block, jnp.arange(0, t, step))
    ctx = jnp.moveaxis(outs, 0, 1).reshape(n, t, heads * d)
    return (ctx * jax.nn.sigmoid(h @ layer["w_gate"])) @ layer["wo"]


def experts(x, layer, cfg, router_gradient=True):
    """x (T, D) -> (y (T, D), counts of the held experts (G,), chosen
    experts (T, k)): the held experts' part and the shared expert's
    whole under its sigmoid gate."""
    k = cfg["num_experts_per_tok"]
    e = layer["router"].shape[1]
    held = layer["w1"].shape[0]
    first = cfg.get("expert_parallel_rank", 0) * held
    probs = jax.nn.softmax(x @ layer["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if not router_gradient:
        top_p = jax.lax.stop_gradient(top_p)
    gate = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32)
                   * top_p[..., None], axis=1)          # (T, E)
    y = jnp.zeros_like(x)
    for i in range(held):
        hidden = jax.nn.silu(x @ layer["w1"][i]) * (x @ layer["w3"][i])
        y = y + gate[:, first + i:first + i + 1] * (hidden @ layer["w2"][i])
    shared = (jax.nn.silu(x @ layer["shared_w1"])
              * (x @ layer["shared_w3"])) @ layer["shared_w2"]
    y = y + jax.nn.sigmoid(x @ layer["shared_gate"]) * shared
    counts = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32),
                     axis=(0, 1))[first:first + held]
    return y, counts, top_e


def decoder_layer(x, layer, kind, cfg, q_block=None, remat=False):
    """One layer: x (N, T, D) -> (x, counts (G,), experts (N*T, k))."""
    eps = cfg["rms_norm_eps"]
    n, t, d = x.shape
    h = zrms(x, layer["op_norm"], eps)
    if kind == "linear_attention":
        x = x + linear_attention(h, layer, cfg, q_block if remat else None)
    else:
        x = x + attention(h, layer, cfg, q_block, remat)
    h = zrms(x, layer["ffn_norm"], eps)
    y, counts, top_e = experts(
        h.reshape(n * t, d), layer, cfg,
        router_gradient=cfg.get("expert_parallel_size", 1) == 1)
    return x + y.reshape(n, t, d), counts, top_e


def forward(params, tokens, cfg, q_block=None, remat=False):
    """tokens (N, T) int -> dict(logits (N, T, V), counts [(G,) per
    layer], experts [(N*T, k) per layer]).  `remat`: a layer's (an
    attention block's, and a run of `q_block` positions of the
    recurrence's) intermediates are computed again in the backward pass
    and not kept, so that the gradients of 16384 positions fit one chip;
    the numbers are the same."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        counts, chosen = [], []
        for kind, layer in zip(layer_types(cfg), params["layers"]):
            def run(x, layer, kind=kind):
                return decoder_layer(x, layer, kind, cfg, q_block, remat)

            x, c, te = (jax.checkpoint(run) if remat else run)(x, layer)
            counts.append(c), chosen.append(te)
        x = zrms(x, params["final_norm"], cfg["rms_norm_eps"])
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
