"""Plain float32 reference of the decoder-hybrid-decoder family
(`model_type` "phi4flash": Phi-4-mini-flash-reasoning's layer
equations, ISSUE 53; SambaY, Ren et al., arXiv:2507.06607) forward
pass, loss and gradients: the benchmark's own, so that the comparison
that decides a cell's correctness does not move when the program does.

Straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`.  No Program, no Executor, no
AMP, no kernel.  It takes the cut configuration file as it stands
(`layer_types`, `layer_indices`, `shared_memory_layer`,
`shared_kv_layer`, the `mamba_*` sizes).

    every layer:  x = x + mixer(LN(x));  x = x + mlp(LN(x))
    mlp(h) = (silu(h W_gate) * (h W_up)) W_down
    then a final LN and the head = the embedding table transposed

    mamba:  u = silu(conv4(h W_u) + b_conv);  z = h W_z
            [r | B | C] = u W_x;  dt = softplus(r W_dt + b_dt)
            s_t = exp(dt_t A) s_{t-1} + (dt_t u_t) B_t^T     A = -exp(A_log)
            y_t = s_t C_t + D u_t;   out = (y * silu(z)) W_out
            a `lax.scan` over SINGLE positions (in blocks of
            `time_block` positions whose inside is recomputed in the
            backward pass, where 8192 positions' states would not fit
            otherwise: the numbers are the same)
    gated memory unit:  out = (silu(h W_1) * y_m) W_2, y_m the scan
            output of layer `shared_memory_layer`
    differential attention, as the published code writes it: 40 heads
            and 20 key/value heads of D = 64 in the PUBLISHED order;
            query pair j = heads (2j, 2j+1) = (q1, q2), key/value pair
            i = (k1, k2), (v1, v2), pair j reads pair j // (pairs /
            kv pairs); FOUR dense soft-max products a pair,
            a1 = [P_1 v1 | P_1 v2],  a2 = [P_2 v1 | P_2 v2]
            ctx = rms_norm_2D(a1 - lam a2) * g * (1 - lam_init)
            lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
            lam_init = 0.8 - 0.6 exp(-0.3 l), l = layer_indices[i]
            P_c = softmax(q_c k_c^T / sqrt(D)) under an EXPLICIT mask,
            j <= i and under the window i - W < j
    cross-attention: the same with q of its own and the keys and
            values of layer `shared_kv_layer`

Departures from the published code, each with its reason: the fused
`W_qkv` and `W_gu` are a matrix a part here (the same numbers, split
where the published code slices); the scan is the sequential
recurrence itself, not the published CUDA kernel's chunked form; no
dropout (the config's are 0); a vocabulary slice is a smaller
vocabulary (the cut's).

`params_from_list` takes the parameter arrays in the builder's creation
order.  ONE leaf is laid out differently there and is permuted here, on
the way in and (its gradient) on the way out: the query projection's
columns, which `models/decoder.py` keeps in the order its one grouped
attention call reads them (for key/value pair i the FIRST heads of its
query pairs, then their second heads; its docstring states the order
as a loader's permutation).  `q_columns` is that permutation, written
from the docstring.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NORM = ("norm_w", "norm_b")
MIXER_KEYS = {
    "mamba": ("w_u", "conv_w", "conv_b", "w_z", "w_x", "w_dt", "a_log", "d",
              "dt_bias", "w_out"),
    "gated_memory": ("w1", "w2"),
    "sliding_attention": ("wq", "bq", "wk", "bk", "wv", "bv", "lq1", "lk1",
                          "lq2", "lk2", "subln", "wo", "bo"),
    "cross_attention": ("wq", "bq", "lq1", "lk1", "lq2", "lk2", "subln",
                        "wo", "bo")}
MIXER_KEYS["full_attention"] = MIXER_KEYS["sliding_attention"]
MLP_KEYS = ("mlp_norm_w", "mlp_norm_b", "w_gate", "w_up", "w_down")


def layer_keys(cfg, i):
    return NORM + MIXER_KEYS[cfg["layer_types"][i]] + MLP_KEYS


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def q_columns(cfg):
    """Published column -> the builder's column of the query
    projection: published head 2j + c (pair j, member c) is the
    builder's head (2i + c) g + e, with i = j // g the key/value pair
    it reads, e = j % g, g = query pairs a key/value pair."""
    heads, d = cfg["num_attention_heads"], head_dim(cfg)
    g = heads // cfg["num_key_value_heads"]
    cols = []
    for head in range(heads):
        j, c = divmod(head, 2)
        i, e = divmod(j, g)
        cols.extend(range(((2 * i + c) * g + e) * d,
                          ((2 * i + c) * g + e + 1) * d))
    return np.asarray(cols)


def params_from_list(arrays, cfg):
    """The parameter tree from a flat list in the builder's creation
    order: the table, `layer_keys` per layer, the final norm; the query
    columns into the published order."""
    arrays = [jnp.asarray(a, jnp.float32) for a in arrays]
    cols = q_columns(cfg)
    layers, at = [], 1
    for i in range(cfg["num_hidden_layers"]):
        keys = layer_keys(cfg, i)
        layer = dict(zip(keys, arrays[at:at + len(keys)]))
        if "wq" in layer:
            layer["wq"], layer["bq"] = layer["wq"][:, cols], layer["bq"][cols]
        layers.append(layer)
        at += len(keys)
    if len(arrays) != at + 2:
        raise ValueError(f"{len(arrays)} arrays, {at + 2} expected")
    return {"embed": arrays[0], "layers": layers,
            "final_norm_w": arrays[-2], "final_norm_b": arrays[-1]}


def leaf_names(cfg):
    names = ["embed"]
    for i in range(cfg["num_hidden_layers"]):
        names += [f"layer{i}.{k}" for k in layer_keys(cfg, i)]
    return names + ["final_norm_w", "final_norm_b"]


def flat_leaves(tree, cfg):
    """A tree shaped like `params_from_list`'s back into the builder's
    order and layout (the query columns permuted back)."""
    back = np.argsort(q_columns(cfg))
    flat = [tree["embed"]]
    for i, layer in enumerate(tree["layers"]):
        for k in layer_keys(cfg, i):
            leaf = layer[k]
            if k == "wq":
                leaf = leaf[:, back]
            elif k == "bq":
                leaf = leaf[back]
            flat.append(leaf)
    return flat + [tree["final_norm_w"], tree["final_norm_b"]]


def lowered(x, dtype):
    """x rounded to `dtype`'s exponent and mantissa and back (a
    stand-in precision; `reduce_precision`, which the compiler does not
    fold away as it does a pair of converts)."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def causal_conv(x, w, b):
    """x (N, T, D), w (D, L): y[t] = sum_j w[:, j] x[t - (L-1) + j] + b."""
    taps, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[:, j] * padded[:, j:j + t] for j in range(taps)) + b


def selective_scan(u, dt, a, b, c, d, time_block=None, state_dtype=None):
    """y (N, T, D) of the recurrence, one position at a time.  u, dt
    (N, T, D); a (D, S); b, c (N, T, S); d (D,).  `state_dtype`: a
    stand-in precision for the carried state (a scratch check that the
    limits catch a bfloat16 state)."""
    n, t, width = u.shape

    def step(s, xs):
        dt_t, u_t, b_t, c_t = xs
        s = jnp.exp(dt_t[..., None] * a) * s \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        if state_dtype is not None:
            s = lowered(s, state_dtype)
        return s, jnp.einsum("nds,ns->nd", s, c_t)

    def positions(s, xs):
        return jax.lax.scan(step, s, xs)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (dt, u, b, c))
    s0 = jnp.zeros((n, width, a.shape[1]), jnp.float32)
    if time_block is None:
        _, y = positions(s0, xs)
    else:
        if t % time_block:
            raise ValueError(f"{t} positions are not whole blocks of "
                             f"{time_block}")
        blocks = tuple(x.reshape((t // time_block, time_block)
                                 + x.shape[1:]) for x in xs)
        _, y = jax.lax.scan(jax.checkpoint(positions), s0, blocks)
        y = y.reshape((t,) + y.shape[2:])
    return jnp.moveaxis(y, 0, 1) + d * u


def mamba(h, layer, cfg, time_block=None, state_dtype=None):
    """-> (the mixer's output, the scan output y that a memory unit
    reads)."""
    rank, states = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    u = jax.nn.silu(causal_conv(h @ layer["w_u"], layer["conv_w"],
                                layer["conv_b"]))
    z = h @ layer["w_z"]
    low = u @ layer["w_x"]
    r, b, c = (low[..., :rank], low[..., rank:rank + states],
               low[..., rank + states:])
    dt = jax.nn.softplus(r @ layer["w_dt"] + layer["dt_bias"])
    y = selective_scan(u, dt, -jnp.exp(layer["a_log"]), b, c, layer["d"],
                       time_block, state_dtype)
    return (y * jax.nn.silu(z)) @ layer["w_out"], y


def gated_memory(h, layer, memory):
    return (jax.nn.silu(h @ layer["w1"]) * memory) @ layer["w2"]


def allowed(q_pos, k_pos, window):
    """The mask: key j is read by query i where j <= i and, under a
    window of W keys (the query's own included), i - W < j."""
    seen = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        seen = seen & (k_pos[None, :] > q_pos[:, None] - window)
    return seen


def keys_and_values(h, layer, cfg):
    """(k, v) (N, T, Hkv, D) of an attention layer's own input."""
    n, t, _ = h.shape
    kv, d = cfg["num_key_value_heads"], head_dim(cfg)
    return ((h @ layer["wk"] + layer["bk"]).reshape(n, t, kv, d),
            (h @ layer["wv"] + layer["bv"]).reshape(n, t, kv, d))


def differential_attention(h, layer, cfg, kind, index, kv, q_block=None,
                           remat=False, lam_dtype=None):
    """Differential attention of a layer of type `kind` at the
    published index `index`, over the keys and values `kv` (its own, or
    the exporting layer's).  `q_block`: rows of the scores computed at
    a time.  `lam_dtype`: a stand-in precision for lambda and the
    sub-layer norm (a scratch check)."""
    n, t, _ = h.shape
    heads, d = cfg["num_attention_heads"], head_dim(cfg)
    pairs, kv_pairs = heads // 2, cfg["num_key_value_heads"] // 2
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    q = (h @ layer["wq"] + layer["bq"]).reshape(n, t, pairs, 2, d)
    k, v = (jnp.repeat(x.reshape(n, t, kv_pairs, 2, d), pairs // kv_pairs,
                       axis=2) for x in kv)
    step = q_block or t
    if t % step:
        raise ValueError(f"{t} positions are not whole blocks of {step}")
    span = t if window is None else min(t, step + window - 1)

    def block(lo):
        k_lo = jnp.clip(lo + step - span, 0, t - span)
        q_rows = jax.lax.dynamic_slice_in_dim(q, lo, step, axis=1)
        keys = jax.lax.dynamic_slice_in_dim(k, k_lo, span, axis=1)
        values = jax.lax.dynamic_slice_in_dim(v, k_lo, span, axis=1)
        seen = allowed(lo + jnp.arange(step), k_lo + jnp.arange(span),
                       window)
        maps = []
        for c in range(2):          # P_1 and P_2, each on v1 and on v2
            scores = jnp.einsum("nqpd,nkpd->npqk", q_rows[:, :, :, c],
                                keys[:, :, :, c]) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            maps.append(jnp.concatenate(
                [jnp.einsum("npqk,nkpd->nqpd", p, values[:, :, :, m])
                 for m in range(2)], axis=-1))
        return jnp.stack(maps)                  # (2, n, step, pairs, 2d)

    if remat:
        block = jax.checkpoint(block)
    outs = jax.lax.map(block, jnp.arange(0, t, step))
    a1, a2 = (jnp.moveaxis(outs[:, c], 0, 1).reshape(n, t, pairs, 2 * d)
              for c in range(2))
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = (jnp.exp(jnp.sum(layer["lq1"] * layer["lk1"]))
           - jnp.exp(jnp.sum(layer["lq2"] * layer["lk2"])) + lam_init)
    if lam_dtype is not None:
        lam = lowered(lam, lam_dtype)
        a1, a2 = lowered(a1, lam_dtype), lowered(a2, lam_dtype)
    x = a1 - lam * a2
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                          + cfg["layer_norm_eps"])
    ctx = x * layer["subln"] * (1.0 - lam_init)
    return ctx.reshape(n, t, heads * d) @ layer["wo"] + layer["bo"]


def mlp(h, layer):
    return (jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])) \
        @ layer["w_down"]


def decoder_layer(x, layer, i, cfg, shared, q_block=None, remat=False,
                  time_block=None, stand_in=None):
    """Layer `i`: (x, what it reads of other layers) -> (x, what it
    exports: {"memory": y} / {"kv": (k, v)} / {})."""
    eps = cfg["layer_norm_eps"]
    kind = cfg["layer_types"][i]
    stand_in = stand_in or {}
    h = layer_norm(x, layer["norm_w"], layer["norm_b"], eps)
    exports = {}
    if kind == "mamba":
        out, y = mamba(h, layer, cfg, time_block,
                       stand_in.get("state_dtype"))
        if i == cfg.get("shared_memory_layer"):
            exports["memory"] = y
    elif kind == "gated_memory":
        out = gated_memory(h, layer, shared["memory"])
    else:
        kv = shared["kv"] if kind == "cross_attention" \
            else keys_and_values(h, layer, cfg)
        if i == cfg.get("shared_kv_layer"):
            exports["kv"] = kv
        out = differential_attention(
            h, layer, cfg, kind, cfg["layer_indices"][i], kv, q_block, remat,
            stand_in.get("lam_dtype"))
    x = x + out
    h = layer_norm(x, layer["mlp_norm_w"], layer["mlp_norm_b"], eps)
    return x + mlp(h, layer), exports


def forward(params, tokens, cfg, q_block=None, remat=False, time_block=None,
            stand_in=None):
    """tokens (N, T) int -> dict(logits (N, T, V), memory, kv: what
    crossed layers).  `remat`: a layer's (and an attention block's)
    intermediates are computed again in the backward pass and not kept,
    so that 8192 positions' gradients fit one chip; the numbers are the
    same."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        shared = {}
        for i, layer in enumerate(params["layers"]):
            reads = {k: v for k, v in shared.items()}

            def run(x, layer, reads, i=i):
                return decoder_layer(x, layer, i, cfg, reads, q_block,
                                     remat, time_block, stand_in)

            x, exports = (jax.checkpoint(run) if remat else run)(
                x, layer, reads)
            shared.update(exports)
        x = layer_norm(x, params["final_norm_w"], params["final_norm_b"],
                       cfg["layer_norm_eps"])
        return dict(shared, logits=x @ params["embed"].T)


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
