"""The benchmark's plain float32 reference of a looped language model
(Ouro-2.6B; Zhu et al. 2025, "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741): ONE stack of layers applied
`total_ut_steps` times over shared weights, sandwich norms, and at the
end of every trip the final norm, the vocabulary head, a token
cross-entropy and a 1-wide exit gate.

Straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`.  No Program, no Executor, no
AMP, no kernel and NO SCAN: the stack is applied in a Python `for`
R times, the same parameter tree read at every trip, so a shared
leaf's gradient is `jax.grad`'s sum over the trips.  Attention
materialises its scores, `q_block` query rows at a time so that 4096
positions fit.

    x_0 = E[tokens]
    trip r = 1..R, layer l = 1..L (the SAME L parameter sets):
        h = rms_norm(x; g1);  q, k, v = h Wq, h Wk, h Wv   (no bias, no QK-norm)
        q, k = rope(q), rope(k)            (rotate-half, whole head, positions 0..T-1)
        x = x + rms_norm(causal_attention(q, k, v) Wo; g2)
        h = rms_norm(x; g3)
        x = x + rms_norm((silu(h W1) * (h W3)) W2; g4)
      s_r = rms_norm(x_r; g_final);  z_r = s_r W_head;  ce_r = token_ce(z_r, labels)
      lam_r = sigmoid(s_r w_gate + b_gate)
    p_1 = lam_1;  p_r = lam_r prod_{j<r}(1 - lam_j);  p_R = prod_{j<R}(1 - lam_j)
    loss = mean_tokens[ sum_r p_r ce_r - beta H(p) ],  H(p) = -sum_r p_r log p_r

The products are taken as they stand, and the system takes them so too
(the chip's float32 `log1p` is good to 1e-4 only, measured, PERF.md
PR 36: an exit distribution through `exp(-softplus)` carried that
error).  RoPE's
frequencies are a host constant (numpy float32), as on the system's
side (PERF.md, PR 26).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# parameters of one layer, in the order `models/decoder.py` creates them
LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "attn_post_norm",
              "ffn_norm", "w1", "w3", "w2", "ffn_post_norm")
TAIL_KEYS = ("final_norm", "head", "gate_w", "gate_b")


def params_from_list(arrays, num_hidden_layers):
    """The reference's parameter tree from a flat list in the builder's
    creation order: embedding, `LAYER_KEYS` per layer, `TAIL_KEYS`."""
    arrays = [jnp.asarray(a, jnp.float32) for a in arrays]
    n = len(LAYER_KEYS)
    if len(arrays) != 1 + n * num_hidden_layers + len(TAIL_KEYS):
        raise ValueError(f"{len(arrays)} arrays for "
                         f"{num_hidden_layers} layers")
    layers = [dict(zip(LAYER_KEYS, arrays[1 + i * n:1 + (i + 1) * n]))
              for i in range(num_hidden_layers)]
    return dict(zip(TAIL_KEYS, arrays[-len(TAIL_KEYS):]),
                embed=arrays[0], layers=layers)


def grads_to_list(grads):
    """A tree shaped like `params_from_list`'s, as the flat list."""
    flat = [grads["embed"]]
    for layer in grads["layers"]:
        flat += [layer[k] for k in LAYER_KEYS]
    return flat + [grads[k] for k in TAIL_KEYS]


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta):
    """x (N, T, H, D): rotate-half over the whole head, positions
    0..T-1 (the same at every trip)."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = (1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
                ).astype(np.float32)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def attention(h, layer, cfg, q_block=None):
    """h (N, T, D) -> (N, T, D): plain multi-head causal attention, the
    scores of `q_block` query rows at a time (each block recomputed in
    the backward pass, so that 4096 positions fit); no QK-norm."""
    n, t, _ = h.shape
    heads = cfg["num_attention_heads"]
    theta = float(cfg["rope_theta"])
    q = rope((h @ layer["wq"]).reshape(n, t, heads, -1), theta)
    k = rope((h @ layer["wk"]).reshape(n, t, heads, -1), theta)
    v = (h @ layer["wv"]).reshape(n, t, heads, -1)
    step = q_block or t

    def block(q_rows, k, v, lo):
        scores = jnp.einsum("nqhd,nkhd->nhqk", q_rows, k) \
            / jnp.sqrt(float(q.shape[-1]))
        seen = (jnp.arange(t)[None, :]
                <= (lo + jnp.arange(q_rows.shape[1]))[:, None])
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    if q_block:
        block = jax.checkpoint(block)
    outs = [block(q[:, lo:lo + step], k, v, lo) for lo in range(0, t, step)]
    return jnp.concatenate(outs, axis=1).reshape(n, t, -1) @ layer["wo"]


def decoder_layer(x, layer, cfg, q_block=None):
    eps = cfg["rms_norm_eps"]
    a = attention(rms_norm(x, layer["attn_norm"], eps), layer, cfg, q_block)
    x = x + rms_norm(a, layer["attn_post_norm"], eps)
    h = rms_norm(x, layer["ffn_norm"], eps)
    f = (jax.nn.silu(h @ layer["w1"]) * (h @ layer["w3"])) @ layer["w2"]
    return x + rms_norm(f, layer["ffn_post_norm"], eps)


def token_ce(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def exit_distribution(lam):
    """lam (R, N, T), the gates' probabilities -> p (R, N, T): the mass
    that leaves at each trip; the last trip takes what is left."""
    p, left = [], jnp.ones_like(lam[0])
    for r in range(lam.shape[0] - 1):
        p.append(lam[r] * left)
        left = left * (1.0 - lam[r])
    return jnp.stack(p + [left])


def forward(params, tokens, labels, cfg, q_block=None, keep_logits=True):
    """tokens, labels (N, T) int -> dict(`logits` [(N, T, V) per trip],
    `ce` (R, N, T), `lam` (R, N, T) the gates, `p` (R, N, T) the exit
    distribution).  `q_block`: every layer pass (and every trip's head)
    is recomputed in the backward pass, the numbers are the same.
    `keep_logits` False hands out no logits (four heads' do not fit
    beside the gradients at the cell's size)."""
    eps = cfg["rms_norm_eps"]
    remat = jax.checkpoint if q_block else (lambda f: f)

    def one(x, layer):
        return decoder_layer(x, layer, cfg, q_block)

    def exit_head(x, final_norm, head, gate_w, gate_b):
        s = rms_norm(x, final_norm, eps)
        logits = s @ head
        lam = jax.nn.sigmoid((s @ gate_w)[..., 0] + gate_b[0])
        return logits, token_ce(logits, labels), lam

    def exit_head_no_logits(*args):
        return exit_head(*args)[1:]

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        logits, ce, lam = [], [], []
        for _ in range(cfg["total_ut_steps"]):
            for layer in params["layers"]:          # the SAME layers
                x = remat(one)(x, layer)
            tail = [params[k] for k in TAIL_KEYS]
            if keep_logits:
                z, c, g = remat(exit_head)(x, *tail)
                logits.append(z)
            else:
                c, g = remat(exit_head_no_logits)(x, *tail)
            ce.append(c), lam.append(g)
        lam = jnp.stack(lam)
        return {"logits": logits, "ce": jnp.stack(ce), "lam": lam,
                "p": exit_distribution(lam)}


def loss(params, tokens, labels, cfg, beta, q_block=None,
         keep_logits=True):
    """(the exit-weighted objective, `forward`'s dict)."""
    out = forward(params, tokens, labels, cfg, q_block, keep_logits)
    p = out["p"]
    task = jnp.sum(p * out["ce"], axis=0)
    # p log p -> 0 as p -> 0
    neg_entropy = jnp.sum(jnp.where(p > 0, p * jnp.log(
        jnp.where(p > 0, p, 1.0)), 0.0), axis=0)
    return jnp.mean(task + beta * neg_entropy), out


def loss_and_grads(params, tokens, labels, cfg, beta, q_block=None,
                   keep_logits=True):
    """((loss, parts), gradient tree shaped like `params`): a shared
    leaf's gradient is the sum over the trips that read it."""
    return jax.value_and_grad(loss, has_aux=True)(
        params, tokens, labels, cfg, beta, q_block, keep_logits)
