"""The benchmark's own copy of the plain float32 reference of the
decoder-only MoE language model (OLMoE-1B-7B, Muennighoff et al. 2024,
arXiv:2409.02060; layer equations as the published `modeling_olmoe`).

A COPY of `paddle_tpu/models/decoder_reference.py`, kept here so that
the benchmark does not check the program with the program's own code:
`olmoe_parity.py` compares the system with THIS file.
`tests/benchmark/test_olmoe_cell.py` holds the two copies to the same
numbers.

Straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`.  No Program, no Executor,
no AMP, no kernel, no sort: attention materialises the (T, T) scores,
and the expert layer is a python loop over ALL experts, each a dense
SwiGLU FFN applied to every token and weighted by the router
probability where the expert is among the token's top k and by zero
where it is not.  Gradients are `jax.grad` of `loss`.

Departures from the published description, each deliberate:

- the load-balancing loss takes `f_e` as the share of the T*k
  (token, expert) assignments that went to expert e, as the paper's
  training code (megablocks) computes it; the `transformers` port
  divides by T alone and so reads k times larger;
- both auxiliary losses are averaged over layers (the training code's
  convention; the weights 0.01 and 0.001 apply to those means);
- no dropout (the model has none) and no `clip_qkv` (null in the
  published configuration);
- RoPE's frequencies `theta^(-2i/D)` are computed on the host (numpy
  float32), as a checkpoint's `inv_freq` buffer is, not with
  `jax.numpy`: run eagerly on a TPU, float32 `pow` is off by 3.6e-6,
  which at position 4095 turns a head by 1.5e-2 rad (measured, PERF.md
  PR 26).  Positions times frequencies, and the sines, stay float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# parameters of one layer, in the order `models/decoder.py` creates them
LAYER_KEYS = ("attn_norm", "wq", "q_norm", "wk", "k_norm", "wv", "wo",
              "ffn_norm", "router", "w1", "w2", "w3")


def params_from_list(arrays, num_hidden_layers):
    """The reference's parameter tree from a flat list in the
    builder's creation order: embedding, `LAYER_KEYS` per layer, final
    norm, head."""
    arrays = [jnp.asarray(a, jnp.float32) for a in arrays]
    n = len(LAYER_KEYS)
    if len(arrays) != 1 + n * num_hidden_layers + 2:
        raise ValueError(f"{len(arrays)} arrays for "
                         f"{num_hidden_layers} layers")
    layers = [dict(zip(LAYER_KEYS, arrays[1 + i * n:1 + (i + 1) * n]))
              for i in range(num_hidden_layers)]
    return {"embed": arrays[0], "layers": layers,
            "final_norm": arrays[-2], "head": arrays[-1]}


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


def attention(x, layer, cfg):
    n, t, _ = x.shape
    heads = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // heads
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = rms_norm(x @ layer["wq"], layer["q_norm"], eps)
    k = rms_norm(x @ layer["wk"], layer["k_norm"], eps)
    v = x @ layer["wv"]
    q = rope(q.reshape(n, t, heads, d), theta)
    k = rope(k.reshape(n, t, heads, d), theta)
    v = v.reshape(n, t, heads, d)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(float(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(n, t, heads * d) @ layer["wo"]


def experts(x, layer, cfg):
    """x (T, D) -> (y (T, D), load-balancing loss, z-loss, counts (E,),
    chosen experts (T, k))."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logits = x @ layer["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    chosen = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=1)
    gate = probs * chosen
    if cfg["norm_topk_prob"]:
        gate = gate / jnp.sum(top_p, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for i in range(e):
        hidden = jax.nn.silu(x @ layer["w1"][i]) * (x @ layer["w3"][i])
        y = y + gate[:, i:i + 1] * (hidden @ layer["w2"][i])
    counts = jnp.sum(chosen, axis=0)
    share = counts / (x.shape[0] * k)
    aux = e * jnp.sum(share * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, aux, z, counts, top_e


def forward(params, tokens, cfg):
    """tokens (N, T) int -> dict(logits (N, T, V), aux, z (means over
    layers), counts [(E,) per layer], experts [(N*T, k) per layer])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        n, t, d = x.shape
        aux, z, counts, chosen = [], [], [], []
        for layer in params["layers"]:
            x = x + attention(
                rms_norm(x, layer["attn_norm"], cfg["rms_norm_eps"]),
                layer, cfg)
            h = rms_norm(x, layer["ffn_norm"], cfg["rms_norm_eps"])
            y, a, zz, c, te = experts(h.reshape(n * t, d), layer, cfg)
            x = x + y.reshape(n, t, d)
            aux.append(a), z.append(zz), counts.append(c), chosen.append(te)
        x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        head = (params["embed"].T if cfg["tie_word_embeddings"]
                else params["head"])
        return {"logits": x @ head, "aux": sum(aux) / len(aux),
                "z": sum(z) / len(z), "counts": counts, "experts": chosen}


def loss(params, tokens, labels, cfg, aux_loss_weight=0.01,
         z_loss_weight=0.001):
    """(total, parts): mean token cross-entropy + the weighted
    auxiliary losses; `parts` is `forward`'s dict plus `ce`."""
    out = forward(params, tokens, cfg)
    logp = jax.nn.log_softmax(out["logits"], axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    total = ce + aux_loss_weight * out["aux"] + z_loss_weight * out["z"]
    return total, dict(out, ce=ce)


def loss_and_grads(params, tokens, labels, cfg, **weights):
    """((total, parts), gradient tree shaped like `params`)."""
    return jax.value_and_grad(loss, has_aux=True)(params, tokens, labels,
                                                  cfg, **weights)
