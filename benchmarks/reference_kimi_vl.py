"""Plain float32 reference of Kimi-VL-A3B's forward pass, loss and
gradients: a native-resolution vision tower (MoonViT: packed patches,
a learnt position table interpolated to each image's grid, rotary
positions over two axes, attention inside an image), a 2 x 2 patch
merger and projector, and a DeepSeek-V3-shaped decoder (direct-q latent
attention with rotary lanes, a leading dense FFN, sigmoid-routed
experts beside a shared one) whose embedding rows at the placeholder
positions are the projector's rows.  The benchmark's own, written from
the equations of ISSUE 73 / `configs/kimi-vl-a3b.json`, so that the
comparison that decides a cell's correctness does not move when the
program does.

Straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`.  No Program, no Executor, no
AMP, no kernel, no sort; nothing of `paddle_tpu` is imported.  The
tower runs ONE IMAGE AT A TIME in the published row-major patch order,
so no mask exists in it at all: an image's patches are cut out of the
packed axis, brought from the collator's merge order back to row-major
(`row_major_of_merge`), embedded, given the table interpolated to the
image's grid by bicubic interpolation WRITTEN OUT from its definition
(`bicubic_matrix`: PyTorch's rule, A = -0.75, half-pixel centres,
clamped taps, as two dense (out, in) matrices; not the collator's
taps), turned by the complex product over (column, row) pairs, attended
with a full soft-max, and merged by the published permutation (2 x 2
blocks, row-major inside a block).  The rows enter the stream by a
scatter at the placeholder positions (`x[mask] = rows`).  The tower
keeps the published fused `wqkv` (D, 3 D); latent attention the
published per-head layouts, as `reference_joyai.py`.

Departures from the published description, each deliberate:

- no auxiliary loss (`seq_aux` has no coefficient in the config);
- the selection bias is an input that nothing updates here;
- where `expert_parallel_size` chips share each layer's experts, the
  routed part is ONE rank's share (the router as wide as published, the
  held experts' weights, what the others would have added LEFT OUT; the
  shared expert whole); under a share the routing weights are constants
  of the backward pass, the builder's decision, made here as there;
- the vocabulary is the slice the configuration states, and the slice's
  id `media_placeholder_token_id` stands for the published one;
- the pixel values arrive as the collator packs them (a block's four
  patches consecutive, the images in the sequence's order); the image
  grids are an argument (`grids`), not read from the data;
- with `q_block` (the chip's sizes) a tower's layers run as one scanned
  body over the stacked weights, the same arithmetic in the same order;
- rotary frequencies are computed on the host (numpy float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NORM_TOPK_EPS = 1e-20
BICUBIC_A = -0.75
LN_EPS = 1e-5


# -- the tower ----------------------------------------------------------------

def layer_norm(x, scale, shift, eps=LN_EPS):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + shift


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))


def bicubic_matrix(out, size):
    """(out, size) float64: row o holds the weights with which output
    o of a bicubic resize of `size` entries to `out` reads each entry
    (PyTorch, align_corners False: source (o + 0.5) size / out - 0.5,
    four taps around it with the cubic convolution kernel A = -0.75,
    taps clamped to the ends, where their weights add up)."""
    a = BICUBIC_A
    m = np.zeros((out, size))
    for o in range(out):
        src = (o + 0.5) * size / out - 0.5
        low = int(np.floor(src))
        t = src - low
        for tap in range(-1, 3):
            x = abs(t - tap)
            if x <= 1:
                w = ((a + 2) * x - (a + 3)) * x * x + 1
            else:
                w = ((a * x - 5 * a) * x + 8 * a) * x - 4 * a
            m[o, min(max(low + tap, 0), size - 1)] += w
    return m


def interpolated_table(table, h, w):
    """(h, w, D): the learnt (H0, W0, D) table resized to (h, w)."""
    rows = jnp.asarray(bicubic_matrix(h, table.shape[0]), jnp.float32)
    cols = jnp.asarray(bicubic_matrix(w, table.shape[1]), jnp.float32)
    return jnp.einsum("ya,abd,xb->yxd", rows, table, cols)


def row_major_of_merge(h, w):
    """perm with row_major[i] = merge_order[perm[i]]: the collator packs
    an image's patches block by block (2 x 2, row-major inside)."""
    r, c, a, b = np.meshgrid(np.arange(h // 2), np.arange(w // 2),
                             np.arange(2), np.arange(2), indexing="ij")
    at = ((2 * r + a) * w + (2 * c + b)).reshape(-1)    # row-major index
    return np.argsort(at)


def rope_two_axes(x, h, w, theta=10000.0):
    """x (h w, H, d) in row-major order: the complex product; pair 2m
    turns by column f_m, pair 2m + 1 by row f_m, f_m = theta^(-4m/d)."""
    d = x.shape[-1]
    freq = (1.0 / theta ** (np.arange(0, d, 4, dtype=np.float32) / d)
            ).astype(np.float32)
    ys, xs = np.meshgrid(np.arange(h, dtype=np.float32),
                         np.arange(w, dtype=np.float32), indexing="ij")
    ang = np.stack([xs.reshape(-1, 1) * freq, ys.reshape(-1, 1) * freq],
                   axis=-1).reshape(h * w, 1, d // 2)
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    z = jax.lax.complex(pairs[..., 0], pairs[..., 1]) \
        * jax.lax.complex(jnp.cos(ang), jnp.sin(ang))
    return jnp.stack([jnp.real(z), jnp.imag(z)], axis=-1).reshape(x.shape)


def tower_layer(x, layer, h, w, heads):
    """x (h w, D), one image, row-major."""
    p, d = x.shape
    qkv = layer_norm(x, *layer["ln1"]) @ layer["wqkv"] + layer["bqkv"]
    q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(p, heads, d // heads)
               for i in range(3))
    q, k = rope_two_axes(q, h, w), rope_two_axes(k, h, w)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(float(d // heads))
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(p, d) @ layer["wo"] + layer["bo"]
    m = gelu_tanh(layer_norm(x, *layer["ln2"]) @ layer["w0"] + layer["b0"])
    return x + m @ layer["w1"] + layer["b1"]


def image_rows(vision, pixels, h, w, cfg, remat=False):
    """pixels (h w, 588) in MERGE order -> ((h w / 4, text D) rows in
    row-major order of the blocks, the tower's output (h w, D) in
    row-major order)."""
    heads = cfg["vision_config"]["num_attention_heads"]
    x = pixels[row_major_of_merge(h, w)] @ vision["patch_w"] \
        + vision["patch_b"]
    table = vision["table"].reshape(
        cfg["vision_config"]["init_pos_emb_height"],
        cfg["vision_config"]["init_pos_emb_width"], -1)
    x = x + interpolated_table(table, h, w).reshape(h * w, -1)
    one = functools.partial(tower_layer, h=h, w=w, heads=heads)
    if remat:
        # the same layers as ONE scanned body, recomputed in the backward
        # pass: at the published sizes sixteen images x eight layers
        # written out is more program than the host compiles in memory
        stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                               *vision["layers"])
        x, _ = jax.lax.scan(
            lambda x, layer: (jax.checkpoint(one)(x, layer), None), x,
            stacked)
    else:
        for layer in vision["layers"]:
            x = one(x, layer)
    x = layer_norm(x, *vision["final_norm"])
    z = layer_norm(x, *vision["merge_norm"])
    d = z.shape[-1]
    z = z.reshape(h // 2, 2, w // 2, 2, d).transpose(0, 2, 1, 3, 4) \
        .reshape(h * w // 4, 4 * d)
    e = gelu(z @ vision["wa"] + vision["ba"]) @ vision["wb"] + vision["bb"]
    return e, x


# -- the decoder --------------------------------------------------------------

def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rope_pairs(x, theta):
    """x (N, T, H, D): the pairs (2i, 2i + 1) turned by position x
    theta^(-2i/D), the complex product, positions 0 .. T-1."""
    n, t, h, d = x.shape
    inv_freq = (1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
                ).astype(np.float32)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * inv_freq[None, :])[None, :, None, :]
    pairs = x.reshape(n, t, h, d // 2, 2)
    z = jax.lax.complex(pairs[..., 0], pairs[..., 1]) \
        * jax.lax.complex(jnp.cos(ang), jnp.sin(ang))
    return jnp.stack([jnp.real(z), jnp.imag(z)], axis=-1).reshape(x.shape)


def latent_attention(h, layer, cfg, q_block=None, remat=False):
    """h (N, T, D) -> (N, T, D): ONE direct query projection, keys and
    values out of their low-rank latent, one rotary key for all heads,
    192-wide causal scores."""
    n, t, _ = h.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, theta = cfg["kv_lora_rank"], float(cfg["rope_theta"])
    q = (h @ layer["wq"]).reshape(n, t, heads, nope + rope)
    ckv = h @ layer["wkv_a"]
    kv = (rms_norm(ckv[..., :rank], layer["kv_norm"], eps) @ layer["wkv_b"]
          ).reshape(n, t, heads, nope + cfg["v_head_dim"])
    k_rope = rope_pairs(ckv[..., rank:][:, :, None, :], theta)
    q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], theta)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(k_rope, heads, axis=2)],
                        axis=-1)
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
    """x (T, D) -> (routed part (T, D), counts of the held experts (G,),
    chosen experts (T, k)): sigmoid scores, the k largest of score +
    bias, weights the unbiased scores over their sum + 1e-20 times the
    scaling factor, a python loop over the HELD experts."""
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
    eps = cfg["rms_norm_eps"]
    n, t, d = x.shape
    x = x + latent_attention(rms_norm(x, layer["op_norm"], eps), layer, cfg,
                             q_block, remat)
    h = rms_norm(x, layer["ffn_norm"], eps)
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


# -- the model ----------------------------------------------------------------

def forward(params, tokens, pixel_values, cfg, grids, q_block=None,
            remat=False):
    """tokens (N, T) int, pixel_values (N, P, 588) merge-ordered, the
    images in the sequence's order, `grids` a tuple a sequence of its
    images' (h, w) -> dict(logits (N, T, V),
    image_rows [(R_n, D) a sequence, in the sequence's order], tower_out
    [(P_n, D) a sequence, row-major an image, in the sequence's order],
    counts, experts)."""
    eps = cfg["rms_norm_eps"]
    placeholder = cfg["media_placeholder_token_id"]
    counts, chosen = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        all_rows, all_out = [], []
        for n, images in enumerate(grids):
            rows, outs, at = [], [], 0
            for h, w in images:
                r, o = image_rows(
                    params["vision"], pixel_values[n, at:at + h * w], h, w,
                    cfg, remat)
                rows.append(r), outs.append(o)
                at += h * w
            rows = jnp.concatenate(rows)
            # x[mask] = rows: the r-th placeholder takes the r-th row
            where, = jnp.nonzero(tokens[n] == placeholder,
                                 size=rows.shape[0])
            x = x.at[n, where].set(rows)
            all_rows.append(rows), all_out.append(jnp.concatenate(outs))

        def one(x, layer):
            return decoder_layer(x, layer, cfg, q_block, remat)

        for layer in params["layers"]:
            x, c, te = (jax.checkpoint(one) if remat else one)(x, layer)
            if c is not None:
                counts.append(c), chosen.append(te)
        logits = rms_norm(x, params["final_norm"], eps) @ params["head"]
        return {"logits": logits, "image_rows": all_rows,
                "tower_out": all_out, "counts": counts, "experts": chosen}


def loss(params, tokens, labels, loss_weights, pixel_values, cfg, grids,
         q_block=None, remat=False):
    """(the mean cross-entropy over the weighted positions, `forward`'s
    dict plus `ce`)."""
    out = forward(params, tokens, pixel_values, cfg, grids, q_block, remat)
    logp = jax.nn.log_softmax(out["logits"], axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    ce = jnp.sum(ce * loss_weights) / jnp.sum(loss_weights)
    return ce, dict(out, ce=ce)


def loss_and_grads(params, tokens, labels, loss_weights, pixel_values, cfg,
                   grids, q_block=None):
    """((loss, parts), gradient tree shaped like `params`).  With
    `q_block` the decoder's scores go `q_block` rows at a time and every
    block of tower and decoder is recomputed in the backward pass."""
    return jax.value_and_grad(loss, has_aux=True)(
        params, tokens, labels, loss_weights, pixel_values, cfg, grids,
        q_block, q_block is not None)


# -- the system's flat parameter list <-> this tree ---------------------------

TOWER_LAYER_KEYS = ("ln1.scale", "ln1.shift", "wq", "bq", "wk", "bk", "wv",
                    "bv", "wo", "bo", "ln2.scale", "ln2.shift", "w0", "b0",
                    "w1", "b1")
ATTENTION_KEYS = ("op_norm", "wq.nope", "wq.rope", "wkv_a.latent", "kv_norm",
                  "wkv_a.rope", "wkv_b.key", "wkv_b.value", "wo")
FFN_KEYS = {"dense": ("ffn_norm", "w1", "w3", "w2"),
            "experts": ("ffn_norm", "router", "w1", "w2", "w3",
                        "shared_w1", "shared_w3", "shared_w2")}


def block_keys(dense):
    return ATTENTION_KEYS + FFN_KEYS["dense" if dense else "experts"]


def system_names(cfg):
    """A name for every parameter of the system, in the builders'
    creation order: the tower (patch embedding, table, layers, last
    norm), the projector, then the decoder."""
    names = ["vision.patch_w", "vision.patch_b", "vision.table"]
    for i in range(cfg["vision_config"]["num_hidden_layers"]):
        names += [f"vision.layer{i}.{k}" for k in TOWER_LAYER_KEYS]
    names += ["vision.final_norm.scale", "vision.final_norm.shift",
              "vision.merge_norm.scale", "vision.merge_norm.shift",
              "vision.wa", "vision.ba", "vision.wb", "vision.bb", "embed"]
    for i in range(cfg["num_hidden_layers"]):
        names += [f"layer{i}.{k}"
                  for k in block_keys(i < cfg["first_k_dense_replace"])]
    return names + ["final_norm", "head"]


def _per_head(heads, *blocks):
    parts = [b.reshape(b.shape[0], heads, -1) for b in blocks]
    return jnp.concatenate(parts, axis=-1).reshape(blocks[0].shape[0], -1)


def _column_blocks(w, heads, *widths):
    parts = w.reshape(w.shape[0], heads, -1)
    out, at = [], 0
    for width in widths:
        out.append(parts[:, :, at:at + width].reshape(w.shape[0], -1))
        at += width
    return out


def params_from_list(arrays, cfg, biases=None):
    """The reference's parameter tree (published layouts: the tower's
    fused `wqkv`, latent attention's per-head `wq` and `wkv_b`) from the
    system's flat list in `system_names` order.  `biases`: the selection
    bias (E,) of each routed layer; None = zeros."""
    names = system_names(cfg)
    if len(arrays) != len(names):
        raise ValueError(f"{len(arrays)} arrays, {len(names)} expected")
    flat = {n: jnp.asarray(a, jnp.float32) for n, a in zip(names, arrays)}
    heads = cfg["num_attention_heads"]

    def tower(i):
        f = {k: flat[f"vision.layer{i}.{k}"] for k in TOWER_LAYER_KEYS}
        return {"ln1": (f["ln1.scale"], f["ln1.shift"]),
                "wqkv": jnp.concatenate([f["wq"], f["wk"], f["wv"]], axis=1),
                "bqkv": jnp.concatenate([f["bq"], f["bk"], f["bv"]]),
                "wo": f["wo"], "bo": f["bo"],
                "ln2": (f["ln2.scale"], f["ln2.shift"]),
                "w0": f["w0"], "b0": f["b0"], "w1": f["w1"], "b1": f["b1"]}

    routed = [0]

    def block(i):
        f = {k: flat[f"layer{i}.{k}"]
             for k in block_keys(i < cfg["first_k_dense_replace"])}
        layer = {k: v for k, v in f.items() if "." not in k}
        layer["wq"] = _per_head(heads, f["wq.nope"], f["wq.rope"])
        layer["wkv_a"] = jnp.concatenate([f["wkv_a.latent"],
                                          f["wkv_a.rope"]], axis=1)
        layer["wkv_b"] = _per_head(heads, f["wkv_b.key"], f["wkv_b.value"])
        if "router" in layer:
            e = layer["router"].shape[1]
            layer["bias"] = (jnp.zeros((e,), jnp.float32) if biases is None
                             else jnp.asarray(biases[routed[0]],
                                              jnp.float32))
            routed[0] += 1
        return layer

    vision = {"patch_w": flat["vision.patch_w"],
              "patch_b": flat["vision.patch_b"],
              "table": flat["vision.table"],
              "layers": [tower(i) for i in range(
                  cfg["vision_config"]["num_hidden_layers"])],
              "final_norm": (flat["vision.final_norm.scale"],
                             flat["vision.final_norm.shift"]),
              "merge_norm": (flat["vision.merge_norm.scale"],
                             flat["vision.merge_norm.shift"]),
              **{k: flat["vision." + k] for k in ("wa", "ba", "wb", "bb")}}
    return {"vision": vision, "embed": flat["embed"],
            "layers": [block(i) for i in range(cfg["num_hidden_layers"])],
            "final_norm": flat["final_norm"], "head": flat["head"]}


def grads_to_list(grads, cfg):
    """A gradient tree shaped like `params_from_list`'s, as the flat
    list in `system_names` order (the selection biases left out)."""
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    v = grads["vision"]
    flat = [v["patch_w"], v["patch_b"], v["table"]]
    for layer in v["layers"]:
        d = layer["wo"].shape[0]
        f = {"ln1.scale": layer["ln1"][0], "ln1.shift": layer["ln1"][1],
             "ln2.scale": layer["ln2"][0], "ln2.shift": layer["ln2"][1],
             **{k: layer[k] for k in ("wo", "bo", "w0", "b0", "w1", "b1")}}
        for i, part in enumerate("qkv"):
            f["w" + part] = layer["wqkv"][:, i * d:(i + 1) * d]
            f["b" + part] = layer["bqkv"][i * d:(i + 1) * d]
        flat += [f[k] for k in TOWER_LAYER_KEYS]
    flat += [*v["final_norm"], *v["merge_norm"], v["wa"], v["ba"], v["wb"],
             v["bb"], grads["embed"]]
    for i, layer in enumerate(grads["layers"]):
        f = dict(layer)
        f["wq.nope"], f["wq.rope"] = _column_blocks(
            layer["wq"], heads, cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"])
        f["wkv_a.latent"] = layer["wkv_a"][:, :rank]
        f["wkv_a.rope"] = layer["wkv_a"][:, rank:]
        f["wkv_b.key"], f["wkv_b.value"] = _column_blocks(
            layer["wkv_b"], heads, cfg["qk_nope_head_dim"],
            cfg["v_head_dim"])
        flat += [f[k] for k in block_keys(i < cfg["first_k_dense_replace"])]
    return flat + [grads["final_norm"], grads["head"]]
