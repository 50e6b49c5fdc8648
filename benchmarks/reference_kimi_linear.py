"""Plain float32 reference of the lane-decayed linear-attention MoE
decoder (`model_type` "kimi_linear": Kimi-Linear-48B-A3B's layer
equations, ISSUE 65) forward pass, loss and gradients: the benchmark's
own, so that the comparison that decides a cell's correctness does not
move when the program does.

Straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`.  No Program, no Executor, no
AMP, no kernel, no chunk, no sort.  Pre-norm residual stream, RMSNorm:

    layer l (1-based): KDA if l in kda_layers, latent attention if l in
    full_attn_layers;  x = x + mixer(rms(x));  x = x + ffn(rms(x))
    ffn: dense SwiGLU for l <= first_k_dense_replace, else routed

Kimi Delta Attention (Kimi Team, arXiv:2510.26692), the published
projections each a matrix of its own (q, k, v with a convolution each):

    q = l2norm(silu(conv(h W_q))) Dk^-1/2;  k = l2norm(silu(conv(h W_k)))
    v = silu(conv(h W_v))                      (l2norm a head, eps 1e-6)
    g = -exp(A_log[head]) softplus((h W_f1) W_f2 + dt_bias)   (a LANE)
    beta = sigmoid(h W_b)                                      (a head)
    a head, S_0 = 0 (Dk, Dv):
      S'_t = Diag(exp(g_t)) S_{t-1};  u_t = beta_t (v_t - S'_t^T k_t)
      S_t = S'_t + k_t u_t^T;         o_t = S_t^T q_t
    out = (rms(o) w_o sigmoid((h W_g1) W_g2)) W_out            (a head)

THE RECURRENCE IS A `lax.scan` OVER POSITIONS, as written: one decay of
the state's rows and one rank-one update a position.  Latent attention
with no positions (`mla_use_nope`, `q_lora_rank` null), the published
per-head column order ([nope | rope] a query head, [key | value] a
head of W_kvb):

    [q_nope | q_pe] = h W_q;  [c_kv | k_pe] = h W_kva;  c_kv = rms(c_kv)
    [k_nope | v] = c_kv W_kvb;   s = (q_nope . k_nope + q_pe . k_pe) / sqrt(192)
    out = causal_softmax(s) v W_o

an explicit causal mask (`q_block` rows of scores at a time where 8192
positions would not fit otherwise), NOTHING rotated.  Routed FFN:
sigmoid scores over ALL router outputs, the k largest of score + bias,
weights the unbiased scores over their sum + 1e-20, x
`routed_scaling_factor`, a python loop over the HELD experts, plus the
shared SwiGLU expert whole.

Where `expert_parallel_size` chips share each layer's experts the expert
layer is ONE rank's share, as `reference_lfm2.py` sets out: the router
is as wide as published, what the experts held elsewhere would have
added is LEFT OUT, the shared expert is whole, and `forward` holds the
routing weights constant in the backward pass as `models/decoder.py`
does.

Departures from the program, each a layout and no arithmetic: the
program holds ONE (D, 3 H Dk) matrix and one (3 H Dk, taps) filter for
q, k and v, and latent attention's W_q, W_kva and W_kvb as two column
blocks each; `params_from_list` / `grads_to_list` split and join them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the system's parameters of one layer, in the order `models/decoder.py`
# creates them
DELTA_KEYS = ("op_norm", "w_qkv", "conv", "w_f1", "w_f2", "w_b", "A_log",
              "dt_bias", "w_g1", "w_g2", "out_norm", "w_out")
LATENT_KEYS = ("op_norm", "wq.nope", "wq.rope", "wkv_a.latent", "kv_norm",
               "wkv_a.rope", "wkv_b.key", "wkv_b.value", "wo")
FFN_KEYS = {"dense": ("ffn_norm", "w1", "w3", "w2"),
            "experts": ("ffn_norm", "router", "w1", "w2", "w3",
                        "shared_w1", "shared_w3", "shared_w2")}
L2_EPS = 1e-6
NORM_TOPK_EPS = 1e-20
DELTA, FULL = "delta", "full"


def layer_types(cfg):
    group = cfg["linear_attn_config"]
    return [DELTA if i in group["kda_layers"] else FULL
            for i in range(1, cfg["num_hidden_layers"] + 1)]


def layer_keys(kind, dense):
    return (DELTA_KEYS if kind == DELTA else LATENT_KEYS) \
        + FFN_KEYS["dense" if dense else "experts"]


def system_names(cfg):
    """A name for every parameter of the system, in the builder's
    creation order: embedding, the layers, final norm, head."""
    names = ["embed"]
    for i, kind in enumerate(layer_types(cfg)):
        names += [f"layer{i}.{k}" for k in layer_keys(
            kind, i < cfg["first_k_dense_replace"])]
    return names + ["final_norm", "head"]


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


def _layer_from_system(flat, cfg):
    heads = cfg["num_attention_heads"]
    layer = {k: v for k, v in flat.items() if "." not in k}
    if "w_qkv" in layer:
        for name, part in zip("qkv", jnp.split(layer.pop("w_qkv"), 3, 1)):
            layer["w" + name] = part
        for name, part in zip("qkv", jnp.split(layer.pop("conv"), 3, 0)):
            layer["conv_" + name] = part
    else:
        layer["wq"] = _per_head(heads, flat["wq.nope"], flat["wq.rope"])
        layer["wkv_a"] = jnp.concatenate([flat["wkv_a.latent"],
                                          flat["wkv_a.rope"]], axis=1)
        layer["wkv_b"] = _per_head(heads, flat["wkv_b.key"],
                                   flat["wkv_b.value"])
    return layer


def _layer_to_system(layer, cfg, kind, dense):
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    flat = dict(layer)
    if kind == DELTA:
        flat["w_qkv"] = jnp.concatenate(
            [layer["w" + n] for n in "qkv"], axis=1)
        flat["conv"] = jnp.concatenate(
            [layer["conv_" + n] for n in "qkv"], axis=0)
    else:
        flat["wq.nope"], flat["wq.rope"] = _column_blocks(
            layer["wq"], heads, cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"])
        flat["wkv_a.latent"] = layer["wkv_a"][:, :rank]
        flat["wkv_a.rope"] = layer["wkv_a"][:, rank:]
        flat["wkv_b.key"], flat["wkv_b.value"] = _column_blocks(
            layer["wkv_b"], heads, cfg["qk_nope_head_dim"],
            cfg["v_head_dim"])
    return [flat[k] for k in layer_keys(kind, dense)]


def params_from_list(arrays, cfg, biases=None):
    """The reference's parameter tree (published layout) from the
    system's flat list in `system_names` order.  `biases`: the selection
    bias (E,) of each routed layer (not parameters: no gradient reaches
    them); None = zeros."""
    names = system_names(cfg)
    if len(arrays) != len(names):
        raise ValueError(f"{len(arrays)} arrays, {len(names)} expected")
    flat = {n: jnp.asarray(a, jnp.float32) for n, a in zip(names, arrays)}
    layers, routed = [], 0
    for i in range(cfg["num_hidden_layers"]):
        prefix = f"layer{i}."
        layer = _layer_from_system(
            {n[len(prefix):]: a for n, a in flat.items()
             if n.startswith(prefix)}, cfg)
        if "router" in layer:
            e = layer["router"].shape[1]
            layer["bias"] = (jnp.zeros((e,), jnp.float32) if biases is None
                             else jnp.asarray(biases[routed], jnp.float32))
            routed += 1
        layers.append(layer)
    return {"embed": flat["embed"], "layers": layers,
            "final_norm": flat["final_norm"], "head": flat["head"]}


def grads_to_list(grads, cfg):
    """A gradient tree shaped like `params_from_list`'s, as the flat
    list in `system_names` order (the selection biases left out)."""
    flat = [grads["embed"]]
    for i, (kind, layer) in enumerate(zip(layer_types(cfg),
                                          grads["layers"])):
        flat += _layer_to_system(layer, cfg, kind,
                                 i < cfg["first_k_dense_replace"])
    return flat + [grads["final_norm"], grads["head"]]


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x, w):
    """x (N, T, C), w (C, L): y[t] = sum_j w[:, j] x[t - (L-1) + j]."""
    taps, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[:, j] * padded[:, j:j + t] for j in range(taps))


def delta_rule(q, k, v, g, beta, segment=None):
    """The recurrence a position at a time.  q, k, g (N, T, H, Dk), v
    (N, T, H, Dv), beta (N, T, H); returns o (N, T, H, Dv).  `segment`:
    the same scan over positions written as a scan over runs of
    `segment` positions whose inner scan is recomputed in the backward
    pass, which then keeps one state a run and not one a position (2 MB
    each at 32 heads of 128 x 128)."""
    n, t, h, dk = k.shape
    dv = v.shape[-1]

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., :, None]              # a row a key lane
        u = b_t[..., None] * (v_t - jnp.einsum("nhkv,nhk->nhv", s, k_t))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("nhkv,nhk->nhv", s, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    s0 = jnp.zeros((n, h, dk, dv), jnp.float32)
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


def delta_attention(h, layer, cfg, segment=None):
    n, t, _ = h.shape
    group = cfg["linear_attn_config"]
    heads, d = group["num_heads"], group["head_dim"]

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + L2_EPS)

    def mixed(name):
        x = jax.nn.silu(causal_conv(h @ layer["w" + name],
                                    layer["conv_" + name]))
        return x.reshape(n, t, heads, d)

    q, k, v = l2norm(mixed("q")) * d ** -0.5, l2norm(mixed("k")), mixed("v")
    gate = ((h @ layer["w_f1"]) @ layer["w_f2"] + layer["dt_bias"])
    g = -jnp.exp(layer["A_log"])[:, None] * jax.nn.softplus(
        gate.reshape(n, t, heads, d))
    beta = jax.nn.sigmoid(h @ layer["w_b"])
    o = delta_rule(q, k, v, g, beta, segment)
    z = ((h @ layer["w_g1"]) @ layer["w_g2"]).reshape(n, t, heads, d)
    y = rms(o, cfg["rms_norm_eps"]) * layer["out_norm"] * jax.nn.sigmoid(z)
    return y.reshape(n, t, heads * d) @ layer["w_out"]


def latent_attention(h, layer, cfg, q_block=None, remat=False):
    """h (N, T, D) -> (N, T, D): queries out of ONE projection, keys and
    values out of their low-rank latent, one 64-lane key part for all
    heads, 192-wide scores, nothing rotated."""
    n, t, _ = h.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank = cfg["kv_lora_rank"]
    q = (h @ layer["wq"]).reshape(n, t, heads, nope + rope)
    ckv = h @ layer["wkv_a"]
    kv = (rms(ckv[..., :rank], eps) * layer["kv_norm"]) @ layer["wkv_b"]
    kv = kv.reshape(n, t, heads, nope + cfg["v_head_dim"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_pe = ckv[..., rank:]                              # (N, T, rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    step = q_block or t
    if t % step:
        raise ValueError(f"{t} positions are not whole blocks of {step}")

    def block(lo):
        rows = lambda x: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            x, lo, step, axis=1)
        scores = (jnp.einsum("nqhd,nkhd->nhqk", rows(q_nope), k_nope)
                  + jnp.einsum("nqhd,nkd->nhqk", rows(q_pe), k_pe)) \
            / jnp.sqrt(float(nope + rope))
        seen = jnp.arange(t)[None, :] <= (lo + jnp.arange(step))[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    if remat:
        block = jax.checkpoint(block)
    outs = jax.lax.map(block, jnp.arange(0, t, step))
    ctx = jnp.moveaxis(outs, 0, 1).reshape(n, t, heads * cfg["v_head_dim"])
    return ctx @ layer["wo"]


def swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def experts(x, layer, cfg, router_gradient=True):
    """x (T, D) -> (routed part y (T, D), counts of the held experts
    (G,), chosen experts (T, k)).  `router_gradient=False`: the weights
    are constants of the backward pass."""
    k = cfg["num_experts_per_token"]
    e = layer["router"].shape[1]
    held = layer["w1"].shape[0]
    first = cfg.get("expert_parallel_rank", 0) * held
    scores = jax.nn.sigmoid(x @ layer["router"])
    _, top_e = jax.lax.top_k(scores + layer["bias"], k)
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg["moe_renormalize"]:
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


def decoder_layer(x, layer, kind, cfg, q_block=None, remat=False):
    """One layer: x (N, T, D) -> (x, counts (G,) or None, experts
    (N*T, k) or None)."""
    eps = cfg["rms_norm_eps"]
    n, t, d = x.shape
    h = rms(x, eps) * layer["op_norm"]
    if kind == DELTA:
        x = x + delta_attention(h, layer, cfg, q_block if remat else None)
    else:
        x = x + latent_attention(h, layer, cfg, q_block, remat)
    h = rms(x, eps) * layer["ffn_norm"]
    if "router" not in layer:
        return x + swiglu(h, layer["w1"], layer["w3"], layer["w2"]), \
            None, None
    hf = h.reshape(n * t, d)
    y, counts, top_e = experts(
        hf, layer, cfg,
        router_gradient=cfg.get("expert_parallel_size", 1) == 1)
    y = y + swiglu(hf, layer["shared_w1"], layer["shared_w3"],
                   layer["shared_w2"])
    return x + y.reshape(n, t, d), counts, top_e


def forward(params, tokens, cfg, q_block=None, remat=False):
    """tokens (N, T) int -> dict(logits (N, T, V), counts [(G,) per
    routed layer], experts [(N*T, k) per routed layer]).  `remat`: a
    layer's (an attention block's, and a run of `q_block` positions of
    the recurrence's) intermediates are computed again in the backward
    pass and not kept, so that the gradients of 8192 positions fit one
    chip; the numbers are the same."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        counts, chosen = [], []
        for kind, layer in zip(layer_types(cfg), params["layers"]):
            def run(x, layer, kind=kind):
                return decoder_layer(x, layer, kind, cfg, q_block, remat)

            x, c, te = (jax.checkpoint(run) if remat else run)(x, layer)
            if c is not None:
                counts.append(c), chosen.append(te)
        x = rms(x, cfg["rms_norm_eps"]) * params["final_norm"]
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
    `q_block` the scores go `q_block` rows at a time, the recurrence in
    recomputed runs of `q_block` positions and every layer is recomputed
    in the backward pass (`remat`)."""
    return jax.value_and_grad(loss, has_aux=True)(
        params, tokens, labels, cfg, q_block, q_block is not None)
