"""Plain float32 reference of the block-diffusion MoE decoder
(`model_type` "sdar_moe": SDAR-30B-A3B's layer equations under
block-diffusion training, Arriola et al., arXiv:2503.09573; ISSUE 47)
forward pass, loss and gradients: the benchmark's own, so that the
comparison that decides a cell's correctness does not move when the
program does.

Straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`.  No Program, no Executor, no
AMP, no kernel, no sort.  The input is ONE sequence of 2 L rows: the
clean document x_0 and after it its noised copy x_t.  A row is (half,
position): row r < L is (clean, r), row L + p is (noised, p); RoPE
turns it by its POSITION.  Attention materialises its scores
(`q_block` rows at a time where 16384 rows would not fit otherwise)
under an EXPLICIT mask built from (half, position), blk(p) = p // B:

    clean  -> clean :  blk(s) <= blk(r)
    noised -> clean :  blk(s) <  blk(r)
    noised -> noised:  blk(s) == blk(r)
    clean  -> noised:  never

    layer:  h = rms_norm(x);  q, k, v = h Wq, h Wk, h Wv
            q, k = rms_norm of each head over its D lanes
            q, k = rope(q), rope(k)           (rotate-half, whole head)
            x = x + masked_softmax(q k^T / sqrt(D)) v Wo
            h = rms_norm(x);  p = softmax(h Wr) over all E, float32
            S = the k largest;  w_e = p_e / sum_{S} p  (norm_topk_prob)
            x = x + sum_{e in S, held here} w_e (silu(h W1_e) * (h W3_e)) W2_e
    head:   logits = rms_norm(x[noised half]) W_head     (L rows)
    loss:   (1 / (N L)) sum_i w_i CE(logits_i, x_0[i])

with w_i the fed weights (1 / t_b on a masked position, 0 elsewhere).
The sparse block, the norms and the parameter tree are
`reference_mellum`'s (the same layer but for the mask and the RoPE's
positions): where `expert_parallel_size` chips share each layer's
experts it is ONE rank's share, what the experts held elsewhere would
have added LEFT OUT, the routing weights constants of the backward
pass, as `models/decoder.py` holds them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference_mellum import (LAYER_KEYS, experts, flat_leaves,  # noqa: F401
                              leaf_names, params_from_list, rms_norm,
                              rotate_half)


def rope(x, positions, theta):
    """x (N, T, H, D): rotate-half rotary embedding over the whole
    head, row r turned by `positions[r]`."""
    d = x.shape[-1]
    inv_freq = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    freqs = (positions.astype(jnp.float32)[:, None]
             * np.asarray(inv_freq, np.float32)[None, :])
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def allowed(r_noised, r_pos, s_noised, s_pos, block_length):
    """The mask: whether row r = (r_noised, r_pos) reads row s."""
    r_blk = (r_pos // block_length)[:, None]
    s_blk = (s_pos // block_length)[None, :]
    r_noised, s_noised = r_noised[:, None], s_noised[None, :]
    return jnp.where(
        s_noised, r_noised & (s_blk == r_blk),
        jnp.where(r_noised, s_blk < r_blk, s_blk <= r_blk))


def attention(h, layer, cfg, q_block=None, remat=False):
    """Grouped-query attention over the 2 L rows: query head a reads
    key/value head a // (heads / kv heads); q, k normalised per head;
    RoPE by position; the explicit mask.  `q_block`: rows of the scores
    computed at a time, one block after another."""
    n, t, _ = h.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    half = t // 2
    rows = jnp.arange(t)
    noised = rows >= half
    pos = rows - half * noised
    q = rms_norm((h @ layer["wq"]).reshape(n, t, heads, d),
                 layer["q_norm"], eps)
    k = rms_norm((h @ layer["wk"]).reshape(n, t, kv, d), layer["k_norm"],
                 eps)
    v = (h @ layer["wv"]).reshape(n, t, kv, d)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    step = q_block or t
    if t % step:
        raise ValueError(f"{t} rows are not whole blocks of {step}")

    def block(lo):
        q_rows = jax.lax.dynamic_slice_in_dim(q, lo, step, axis=1)
        at = lo + jnp.arange(step)
        scores = jnp.einsum("nqhd,nkhd->nhqk", q_rows, k) \
            / jnp.sqrt(float(d))
        seen = allowed(at >= half, at - half * (at >= half), noised, pos,
                       cfg["block_length"])
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    if remat:
        block = jax.checkpoint(block)
    outs = jax.lax.map(block, jnp.arange(0, t, step))     # (blocks, n, ..)
    return jnp.moveaxis(outs, 0, 1).reshape(n, t, heads * d) @ layer["wo"]


def decoder_layer(x, layer, cfg, q_block=None, remat=False):
    """One layer: x (N, 2L, D) -> (x, counts (G,), experts (N*2L, k))."""
    eps = cfg["rms_norm_eps"]
    n, t, d = x.shape
    x = x + attention(rms_norm(x, layer["op_norm"], eps), layer, cfg,
                      q_block, remat)
    h = rms_norm(x, layer["ffn_norm"], eps)
    y, counts, top_e = experts(
        h.reshape(n * t, d), layer, cfg,
        router_gradient=cfg.get("expert_parallel_size", 1) == 1)
    return x + y.reshape(n, t, d), counts, top_e


def forward(params, tokens, cfg, q_block=None, remat=False):
    """tokens (N, 2L) int, [x_0 ; x_t] -> dict(logits (N, L, V) over the
    noised half, counts [(G,) per layer], experts [(N*2L, k) per
    layer]).  `remat`: a layer's (and an attention block's)
    intermediates are computed again in the backward pass."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        counts, chosen = [], []
        for layer in params["layers"]:
            def run(x, layer):
                return decoder_layer(x, layer, cfg, q_block, remat)

            x, c, te = (jax.checkpoint(run) if remat else run)(x, layer)
            counts.append(c), chosen.append(te)
        x = rms_norm(x[:, x.shape[1] // 2:], params["final_norm"],
                     cfg["rms_norm_eps"])
        return {"logits": x @ params["head"], "counts": counts,
                "experts": chosen}


def loss(params, tokens, labels, weights, cfg, q_block=None, remat=False):
    """(the weighted masked cross-entropy over N L positions,
    `forward`'s dict plus `ce`)."""
    out = forward(params, tokens, cfg, q_block, remat)
    logp = jax.nn.log_softmax(out["logits"], axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    ce = jnp.sum(weights * nll) / nll.size
    return ce, dict(out, ce=ce)


def loss_and_grads(params, tokens, labels, weights, cfg, q_block=None):
    """((loss, parts), gradient tree shaped like `params`).  With
    `q_block` the scores go `q_block` rows at a time and every layer is
    recomputed in the backward pass (`remat`)."""
    return jax.value_and_grad(loss, has_aux=True)(
        params, tokens, labels, weights, cfg, q_block, q_block is not None)
