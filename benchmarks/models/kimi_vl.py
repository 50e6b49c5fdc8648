"""The vision-language family (`paddle_tpu.models.vision_tower` feeding
`paddle_tpu.models.decoder`): configurations whose `model_type` is
"kimi_vl" (a native-resolution ViT over packed patches under 2-D rotary
positions, a 2 x 2 patch merger and projector, and a DeepSeek-V3-shaped
decoder: direct-q latent attention with rotary lanes, a leading dense
FFN, sigmoid-routed experts beside two shared ones).  ONE program and
one jitted step hold tower, projector, merge and decoder.

The two builders take the published configuration's own keys, so most
of the file is handed over as it stands (`PASSED`; `vision_config`'s
keys under `VISION`).  What this family spells otherwise is mapped
HERE, and the map is the whole of it:

    n_routed_experts       -> num_experts       (what THIS chip holds)
    first_k_dense_replace  -> num_dense_layers
    scoring_func "sigmoid" -> router="sigmoid"
    topk_method "noaux_tc" -> use_expert_bias=True
    the family's 1e-20 under norm_topk_prob -> norm_topk_eps
    vision_config.num_attention_heads / hidden_size ... -> the tower's
    vision_config.merge_kernel_size [2, 2] -> the merger's block
    media_placeholder_token_id -> where the tower's rows enter

A value the builders do not build raises (`ONLY`, and the builders' own
checks).  `seq_aux`, `ep_size`, `max_position_embeddings` and
`model_type` are not read.  `expert_parallel_size` /
`expert_parallel_rank` are the deployment's.  The counts are the
benchmark's own, from the configuration's shapes and the CELL's images
(`images`: the multiset of patch counts every batch holds, and the
grids each may take): they do not move when the program's HLO does.
"""

from __future__ import annotations

import numpy as np

PASSED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "intermediate_size",
          "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
          "routed_scaling_factor", "rms_norm_eps", "rope_theta",
          "rope_interleave", "rope_scaling", "vocab_size",
          "tie_word_embeddings", "kv_lora_rank", "q_lora_rank",
          "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
          "n_shared_experts", "expert_parallel_size", "expert_parallel_rank",
          "media_placeholder_token_id")
RENAMED = {"n_routed_experts": "num_experts",
           "first_k_dense_replace": "num_dense_layers"}
SPELT = {"scoring_func": {"sigmoid": {"router": "sigmoid"}},
         "topk_method": {"noaux_tc": {"use_expert_bias": True}}}
ONLY = {"n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
        "hidden_act": "silu", "attention_bias": False}
EQUATIONS = {"norm_topk_eps": 1e-20, "loss_weights": True}
VISION = ("hidden_size", "num_hidden_layers", "num_attention_heads",
          "intermediate_size", "patch_size", "init_pos_emb_height",
          "init_pos_emb_width", "merge_kernel_size")
MERGE = 4           # patches a row of the merger: a 2 x 2 block
BICUBIC_A = -0.75   # PyTorch's


def architecture(config):
    """The decoder builder's arguments for this family's configuration."""
    for key, built in ONLY.items():
        if config[key] != built:
            raise NotImplementedError(
                f"{key} = {config[key]!r} is not built (only {built!r})")
    args = {k: config[k] for k in PASSED}
    args.update({new: config[old] for old, new in RENAMED.items()})
    for key, values in SPELT.items():
        if config[key] not in values:
            raise NotImplementedError(
                f"{key} = {config[key]!r} is not built")
        args.update(values[config[key]])
    return dict(args, **EQUATIONS)


def tower_architecture(config):
    """The tower builder's arguments."""
    vision = config["vision_config"]
    return dict({k: vision[k] for k in VISION},
                text_hidden_size=config["hidden_size"],
                patch_rows=config["patch_rows"],
                in_token_limit=config["in_token_limit"])


def build_model(config, **over):
    """The training graph under the caller's program guard: the tower
    first, then the decoder that reads its rows at the placeholder ids,
    in ONE program.  Returns the decoder builder's dict (`loss`,
    `logits`, `counts`, `experts`, ..) with the tower's `image_rows` and
    `tower_out`.  `over`: the decoder builder's keywords beside the
    configuration's `training` (a parity script's `with_optimizer`)."""
    from paddle_tpu.models import decoder, vision_tower

    training = dict(config["training"], **over)
    tower = vision_tower.vision_tower(
        recompute=training.get("recompute"), **tower_architecture(config))
    model = decoder.build_model(
        max_length=config["sequence_length"], **training,
        image_rows=tower["image_rows"], **architecture(config))
    return dict(model, image_rows=tower["image_rows"],
                tower_out=tower["tower_out"])


def build(config):
    """Build the training graph under the caller's program guard and
    return the loss variable."""
    return build_model(config)["loss"]


# -- the collator -------------------------------------------------------------

def merge_order(h, w):
    """(h w, 2) int32 (row, column) of an (h, w) grid's patches in MERGE
    order: the four patches of a 2 x 2 block consecutive (row-major
    inside it), blocks row-major."""
    r, c, a, b = np.meshgrid(np.arange(h // 2), np.arange(w // 2),
                             np.arange(2), np.arange(2), indexing="ij")
    return np.stack([2 * r + a, 2 * c + b], axis=-1).reshape(-1, 2) \
        .astype(np.int32)


def _cubic(t):
    """The four bicubic weights (A = -0.75) of the taps at floor - 1,
    floor, floor + 1, floor + 2 for a fraction t."""
    a = BICUBIC_A

    def near(x):        # |x| <= 1
        return ((a + 2) * x - (a + 3)) * x * x + 1

    def far(x):         # 1 < |x| < 2
        return ((a * x - 5 * a) * x + 8 * a) * x - 4 * a

    return np.stack([far(t + 1), near(t), near(1 - t), far(2 - t)], axis=-1)


def _axis_taps(out, size, at):
    """Taps (len(at), 4) int and weights float64 along one axis: the
    table's `size` entries interpolated to `out`, half-pixel centres,
    taps clamped to the table."""
    src = (at + 0.5) * (size / out) - 0.5
    low = np.floor(src)
    taps = np.clip(low[:, None] + np.arange(-1, 3), 0, size - 1)
    return taps.astype(np.int64), _cubic(src - low)


def bicubic_taps(yx, h, w, table_h, table_w):
    """(taps (P, 16) int32 into the flattened (table_h table_w) table,
    weights (P, 16) float32) of the patches at `yx` of an (h, w) grid:
    PyTorch's bicubic interpolation of the table to (h, w), written as
    what it is for one patch, 4 row taps x 4 column taps.  The identity
    (one weight 1) at the table's own grid."""
    rows, wr = _axis_taps(h, table_h, yx[:, 0].astype(np.float64))
    cols, wc = _axis_taps(w, table_w, yx[:, 1].astype(np.float64))
    taps = rows[:, :, None] * table_w + cols[:, None, :]
    weights = wr[:, :, None] * wc[:, None, :]
    return (taps.reshape(len(yx), 16).astype(np.int32),
            weights.reshape(len(yx), 16).astype(np.float32))


def _token_probs(vocab):
    # ids 1..vocab-1 with Zipf-like frequencies, as
    # benchmarks/models/joyai_llm_flash.py draws them over its slice
    p = 1.0 / (np.arange(1, vocab) + 10.0)
    return p / p.sum()


def image_patch_counts(cell):
    """The patches of every image of a batch, the cell's multiset."""
    return [group["patches"] for group in cell["images"]
            for _ in range(group["count"])]


def draw_grids(cell, rng):
    """The (h, w) of a batch's images: each group's aspect drawn from
    its `grids`, the images' order drawn."""
    grids = [tuple(group["grids"][rng.integers(len(group["grids"]))])
             for group in cell["images"] for _ in range(group["count"])]
    return [grids[i] for i in rng.permutation(len(grids))]


def make_batch(config, cell, rng, grids=None):
    """One global batch as the numpy feed of `Executor.run`, what a
    multimodal collator hands a trainer, all static shapes.  A sequence
    is `length` positions: the cell's images, each as h w / 4
    consecutive placeholder ids, between runs of at least one text
    token (before each image and after the last); `labels` the next
    token; `loss_weights` 0 exactly where the label is the placeholder.
    The patches lie in merge order on ONE row axis of `patch_rows` rows,
    the images in the sequence's order (rows past a batch's patches,
    where it has fewer: segment -1, zero pixels), with their image,
    their (row, column) and their bicubic taps.  `grids`: the images as
    given (a test's), not drawn."""
    n = cell["batch_per_chip"] * cell["chips"]
    length, rows = cell["length"], config["patch_rows"]
    if length != config["sequence_length"]:
        raise ValueError(f"length {length} is not the sequence_length "
                         f"{config['sequence_length']} the program is "
                         f"built for")
    vision = config["vision_config"]
    table = vision["init_pos_emb_height"], vision["init_pos_emb_width"]
    lanes = 3 * vision["patch_size"] ** 2
    vocab, placeholder = (config["vocab_size"],
                          config["media_placeholder_token_id"])
    feed = {"tokens": np.empty((n, length), np.int64),
            "labels": np.empty((n, length), np.int64),
            "pixel_values": np.zeros((n, rows, lanes), np.float32),
            "patch_segments": np.full((n, rows), -1, np.int32),
            "patch_yx": np.zeros((n, rows, 2), np.int32),
            "pos_taps": np.zeros((n, rows, 16), np.int32),
            "pos_weights": np.zeros((n, rows, 16), np.float32)}
    for i in range(n):
        images = draw_grids(cell, rng) if grids is None else list(grids)
        patches = sum(h * w for h, w in images)
        if any(h % 2 or w % 2 or h * w > config["in_token_limit"]
               for h, w in images) or patches > rows:
            raise ValueError(f"images {images} are not even grids of at "
                             f"most {config['in_token_limit']} patches, "
                             f"{rows} in all")
        text = length - patches // MERGE
        runs = len(images) + 1
        if text < runs:
            raise ValueError(f"{patches // MERGE} image rows leave no "
                             f"text token around every image of {length}")
        # at least one text token a run, the rest where the seed puts it
        cuts = np.sort(rng.choice(text - 1, size=runs - 1, replace=False)) \
            + 1 if runs > 1 else np.zeros(0, np.int64)
        sizes = np.diff(np.concatenate([[0], cuts, [text]]))
        words = (rng.choice(vocab - 1, size=text + 1, p=_token_probs(vocab))
                 + 1).astype(np.int64)
        ids, at, row = [], 0, 0
        for image, (h, w) in enumerate(images):
            yx = merge_order(h, w)
            here = slice(row, row + h * w)
            feed["patch_segments"][i, here] = image
            feed["patch_yx"][i, here] = yx
            feed["pos_taps"][i, here], feed["pos_weights"][i, here] = \
                bicubic_taps(yx, h, w, *table)
            row += h * w
            ids += [words[at:at + sizes[image]],
                    np.full(h * w // MERGE, placeholder, np.int64)]
            at += sizes[image]
        ids.append(words[at:])          # the last run and one label more
        ids = np.concatenate(ids)
        feed["tokens"][i], feed["labels"][i] = ids[:-1], ids[1:]
        feed["pixel_values"][i, :row] = rng.standard_normal(
            (row, lanes), np.float32)
    feed["loss_weights"] = (feed["labels"] != placeholder) \
        .astype(np.float32)
    return feed


# -- the counts ---------------------------------------------------------------

def allowed_pairs(cell):
    """(query, key) pairs of one step's tower attention: every patch of
    an image reads every patch of the same image."""
    return sum(p * p for p in image_patch_counts(cell))


def forward_flops(config, cell):
    """Forward matmul FLOP of one STEP (2 per multiply-add), by part.
    The tower: its patch embedding; a layer's four projections and two
    MLP matrices over every patch; its attention from the cell's own
    images at the PUBLISHED head size, 2 x 2 x 72 lanes a pair a head
    (scores and values), whatever lanes a kernel pads a head to; the
    projector's two matrices over the merged rows.  The decoder a
    position, as `joyai_llm_flash.py` counts it with a DIRECT query
    projection: a block's four projections (q, kv down with the rotary
    key, kv up, out) and causal scores (192 lanes) and values (128) at
    half; the dense FFN; a routed FFN's router over ALL experts, the
    shared experts whole and the held experts at the uniform
    expectation; the head.  The position table's taps, norms, rotary
    products, GELU, the merge, embedding, soft-max and the sort count
    zero."""
    n = cell["batch_per_chip"] * cell["chips"]
    vision = config["vision_config"]
    vd, vlayers = vision["hidden_size"], vision["num_hidden_layers"]
    patches = sum(image_patch_counts(cell))
    merged = MERGE * vd
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v, kv_rank = config["v_head_dim"], config["kv_lora_rank"]
    width = config["moe_intermediate_size"]
    dense = config["first_k_dense_replace"]
    blocks = config["num_hidden_layers"]
    routed = blocks - dense
    length = cell["length"]
    projections = (d * heads * qk + d * (kv_rank + config["qk_rope_head_dim"])
                   + kv_rank * heads * (config["qk_nope_head_dim"] + v)
                   + heads * v * d)
    position = {
        "attention_projections": blocks * 2 * projections,
        "attention": blocks * 2 * length * heads * (qk + v) / 2,
        "dense_ffn": dense * 3 * 2 * d * config["intermediate_size"],
        "router": routed * 2 * d * (config["n_routed_experts"]
                                    * config["expert_parallel_size"]),
        "shared_experts": routed * config["n_shared_experts"]
        * 3 * 2 * d * width,
        "experts": routed * config["num_experts_per_tok"]
        / config["expert_parallel_size"] * 3 * 2 * d * width,
        "head": 2 * d * config["vocab_size"]}
    parts = {
        "patch_embedding": n * 2.0 * patches * 3 * vision["patch_size"] ** 2
        * vd,
        "tower_projections": n * vlayers * 2.0 * patches * (
            4 * vd * vd + 2 * vd * vision["intermediate_size"]),
        "tower_attention": n * vlayers * 4.0 * allowed_pairs(cell) * vd,
        "projector": n * 2.0 * (patches // MERGE) * (
            merged * merged + merged * d)}
    parts.update({k: n * length * float(f) for k, f in position.items()})
    return parts


def train_flops(config, cell):
    """Model FLOP of one training step over the global batch: forward
    and backward = 3 x the forward matmul FLOP."""
    return 3.0 * sum(forward_flops(config, cell).values())


def units(config, cell):
    """What one step completes: the sequence positions of the decoder's
    stream, image rows and text alike, summed over chips."""
    n = cell["batch_per_chip"] * cell["chips"]
    return {"tokens_per_s": {"per_step": n * cell["length"],
                             "unit": "tokens/s"}}
