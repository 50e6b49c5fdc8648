"""Plain float32 reference of the hybrid state-space / attention decoder
family (`model_type` "granitemoehybrid": Granite-4.0-H-Micro's layer
equations, ISSUE 58; the mixer is Mamba-2, Dao & Gu, arXiv:2405.21060)
forward pass, loss and gradients: the benchmark's own, so that the
comparison that decides a cell's correctness does not move when the
program does.

Straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`.  No Program, no Executor, no
AMP, no kernel and no chunk.  It takes the cut configuration file as it
stands (`layer_types` with the published names "mamba" / "attention",
the `mamba_*` sizes, the four multipliers).

    x_0 = embedding_multiplier * E[tokens]
    every layer:  x = x + residual_multiplier * mixer(rms_norm(x))
                  x = x + residual_multiplier * mlp(rms_norm(x))
    mlp(h) = (silu(h W_gate) * (h W_up)) W_down
    then a final rms_norm and logits = (x E^T) / logits_scaling

    attention:  q, k, v = h W_q, h W_k, h W_v; query head j reads
            key/value head j // (H / Hkv); a DENSE soft-max under an
            explicit causal mask, softmax(q k^T * attention_multiplier);
            no positions, no QK-norm, no bias; out W_o
    mamba:  z = h W_z;  xBC = silu(conv4(h W_xBC) + b_conv);  dt = h W_dt
            [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
            S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] B_t^T
            y_t[h] = S_t[h] C_t + D[h] x_t[h]
            a `lax.scan` over SINGLE positions on the (heads, d_head,
            d_state) state (in blocks of `time_block` positions whose
            inside is recomputed in the backward pass, where 8192
            positions' states would not fit otherwise: the numbers are
            the same)
            out = (rms_norm(y * silu(z)) * w) W_out   (the gate BEFORE
            the norm, the norm over all d_inner lanes)

Departures from the published code, each with its reason: the fused
in-projection `[z | xBC | dt] = h W_in` and the MLP's fused `W_in` are
a matrix a part here (the same numbers, split where the published code
slices); the scan is the sequential recurrence itself, not the
published kernels' chunked form; the published clamp of dt to (0, inf)
does nothing and is left out; no dropout; a vocabulary slice is a
smaller vocabulary (the cut's).

`params_from_list` takes the parameter arrays in the builder's creation
order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MIXER_KEYS = {
    "mamba": ("w_z", "w_xbc", "conv_w", "conv_b", "w_dt", "a_log", "d",
              "dt_bias", "gate_norm_w", "w_out"),
    "attention": ("wq", "wk", "wv", "wo")}
MLP_KEYS = ("mlp_norm_w", "w_gate", "w_up", "w_down")


def layer_keys(cfg, i):
    return ("norm_w",) + MIXER_KEYS[cfg["layer_types"][i]] + MLP_KEYS


def params_from_list(arrays, cfg):
    """The parameter tree from a flat list in the builder's creation
    order: the table, `layer_keys` per layer, the final norm."""
    arrays = [jnp.asarray(a, jnp.float32) for a in arrays]
    layers, at = [], 1
    for i in range(cfg["num_hidden_layers"]):
        keys = layer_keys(cfg, i)
        layers.append(dict(zip(keys, arrays[at:at + len(keys)])))
        at += len(keys)
    if len(arrays) != at + 1:
        raise ValueError(f"{len(arrays)} arrays, {at + 1} expected")
    return {"embed": arrays[0], "layers": layers, "final_norm_w": arrays[-1]}


def leaf_names(cfg):
    names = ["embed"]
    for i in range(cfg["num_hidden_layers"]):
        names += [f"layer{i}.{k}" for k in layer_keys(cfg, i)]
    return names + ["final_norm_w"]


def flat_leaves(tree, cfg):
    """A tree shaped like `params_from_list`'s back into the builder's
    order."""
    flat = [tree["embed"]]
    for i, layer in enumerate(tree["layers"]):
        flat += [layer[k] for k in layer_keys(cfg, i)]
    return flat + [tree["final_norm_w"]]


def lowered(x, dtype):
    """x rounded to `dtype`'s exponent and mantissa and back (a
    stand-in precision; `reduce_precision`, which the compiler does not
    fold away as it does a pair of converts)."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def causal_conv(x, w, b):
    """x (N, T, D), w (D, L): y[t] = sum_j w[:, j] x[t - (L-1) + j] + b."""
    taps, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[:, j] * padded[:, j:j + t] for j in range(taps)) + b


def ssd_recurrence(x, dt, a, b, c, d, time_block=None, state_dtype=None,
                   decay_dtype=None):
    """y (N, T, H, P) of the recurrence, one position at a time.  x
    (N, T, H, P); dt (N, T, H); a (H,); b, c (N, T, S); d (H,).
    `state_dtype` / `decay_dtype`: a stand-in precision for the carried
    state / the decay exp(dt A) (scratch checks that the limits catch a
    bfloat16 state or decay)."""
    n, t, heads, p = x.shape

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        decay = jnp.exp(dt_t * a)
        if decay_dtype is not None:
            decay = lowered(decay, decay_dtype)
        s = decay[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        if state_dtype is not None:
            s = lowered(s, state_dtype)
        return s, jnp.einsum("nhps,ns->nhp", s, c_t)

    def positions(s, xs):
        return jax.lax.scan(step, s, xs)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    s0 = jnp.zeros((n, heads, p, b.shape[-1]), jnp.float32)
    if time_block is None:
        _, y = positions(s0, xs)
    else:
        if t % time_block:
            raise ValueError(f"{t} positions are not whole blocks of "
                             f"{time_block}")
        blocks = tuple(v.reshape((t // time_block, time_block)
                                 + v.shape[1:]) for v in xs)
        _, y = jax.lax.scan(jax.checkpoint(positions), s0, blocks)
        y = y.reshape((t,) + y.shape[2:])
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


def mamba(h, layer, cfg, time_block=None, stand_in=None):
    stand_in = stand_in or {}
    n, t, _ = h.shape
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    states = cfg["mamba_d_state"]
    z = h @ layer["w_z"]
    xbc = jax.nn.silu(causal_conv(h @ layer["w_xbc"], layer["conv_w"],
                                  layer["conv_b"]))
    x, b, c = (xbc[..., :heads * p], xbc[..., heads * p:heads * p + states],
               xbc[..., heads * p + states:])
    dt = jax.nn.softplus(h @ layer["w_dt"] + layer["dt_bias"])
    y = ssd_recurrence(x.reshape(n, t, heads, p), dt,
                       -jnp.exp(layer["a_log"]), b, c, layer["d"],
                       time_block, stand_in.get("state_dtype"),
                       stand_in.get("decay_dtype")).reshape(n, t, heads * p)
    gated = y * jax.nn.silu(z)
    norm_dtype = stand_in.get("norm_dtype")
    if norm_dtype is not None:      # the gated norm's operands, lowered
        gated = lowered(gated, norm_dtype)
        return lowered(rms_norm(gated, layer["gate_norm_w"],
                                cfg["rms_norm_eps"]),
                       norm_dtype) @ layer["w_out"]
    return rms_norm(gated, layer["gate_norm_w"],
                    cfg["rms_norm_eps"]) @ layer["w_out"]


def attention(h, layer, cfg, q_block=None, remat=False, scale=None):
    """Causal grouped-query attention over the whole prefix under the
    configuration's scale (`scale`: another, a scratch check).
    `q_block`: rows of the scores computed at a time."""
    n, t, _ = h.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    scale = cfg["attention_multiplier"] if scale is None else scale
    q = (h @ layer["wq"]).reshape(n, t, heads, d)
    k, v = (jnp.repeat((h @ layer[w]).reshape(n, t, kv, d), heads // kv,
                       axis=2) for w in ("wk", "wv"))
    step = q_block or t
    if t % step:
        raise ValueError(f"{t} positions are not whole blocks of {step}")

    def block(lo):
        rows = jax.lax.dynamic_slice_in_dim(q, lo, step, axis=1)
        seen = jnp.arange(t)[None, :] <= (lo + jnp.arange(step))[:, None]
        scores = jnp.einsum("nqhd,nkhd->nhqk", rows, k) * scale
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("nhqk,nkhd->nqhd", p, v)

    if remat:
        block = jax.checkpoint(block)
    ctx = jax.lax.map(block, jnp.arange(0, t, step))     # (t/step, n, step..)
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(n, t, heads * d)
    return ctx @ layer["wo"]


def mlp(h, layer):
    return (jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])) \
        @ layer["w_down"]


def decoder_layer(x, layer, i, cfg, q_block=None, remat=False,
                  time_block=None, stand_in=None):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = rms_norm(x, layer["norm_w"], eps)
    if cfg["layer_types"][i] == "mamba":
        out = mamba(h, layer, cfg, time_block, stand_in)
    else:
        out = attention(h, layer, cfg, q_block, remat,
                        (stand_in or {}).get("attention_scale"))
    x = x + r * out
    return x + r * mlp(rms_norm(x, layer["mlp_norm_w"], eps), layer)


def forward(params, tokens, cfg, q_block=None, remat=False, time_block=None,
            stand_in=None):
    """tokens (N, T) int -> dict(logits (N, T, V)).  `remat`: a layer's
    (and an attention block's) intermediates are computed again in the
    backward pass and not kept, so that 8192 positions' gradients fit
    one chip; the numbers are the same."""
    with jax.default_matmul_precision("highest"):
        x = cfg["embedding_multiplier"] * params["embed"][tokens]
        for i, layer in enumerate(params["layers"]):
            def run(x, layer, i=i):
                return decoder_layer(x, layer, i, cfg, q_block, remat,
                                     time_block, stand_in)

            x = (jax.checkpoint(run) if remat else run)(x, layer)
        x = rms_norm(x, params["final_norm_w"], cfg["rms_norm_eps"])
        return {"logits": (x @ params["embed"].T) / cfg["logits_scaling"]}


def loss(params, tokens, labels, cfg, **how):
    """(mean token cross-entropy, `forward`'s dict plus `ce`)."""
    out = forward(params, tokens, cfg, **how)
    logp = jax.nn.log_softmax(out["logits"], axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return ce, dict(out, ce=ce)


def loss_and_grads(params, tokens, labels, cfg, q_block=None,
                   time_block=None, stand_in=None):
    """((loss, parts), gradient tree shaped like `params`).  With
    `q_block` the scores go `q_block` rows at a time and every layer is
    recomputed in the backward pass (`remat`)."""
    def f(params):
        return loss(params, tokens, labels, cfg, q_block=q_block,
                    remat=q_block is not None, time_block=time_block,
                    stand_in=stand_in)

    return jax.value_and_grad(f, has_aux=True)(params)
