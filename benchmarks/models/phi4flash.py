"""The decoder-hybrid-decoder family (`paddle_tpu.models.decoder`):
configurations whose `model_type` is "phi4flash" (SambaY, Ren et al.,
arXiv:2507.06607: Mamba-1 state-space mixers and differential attention
alternating, under a window and over the whole prefix, and a
cross-decoder whose layers READ another layer's work: gated memory
units on one mamba layer's scan output, cross-attention on one
whole-prefix layer's keys and values; LayerNorm, no positions, a tied
head, a dense SwiGLU MLP in every layer).

The builder takes the published configuration's own keys, so most of
the file is handed over as it stands (`PASSED`).  What no key spells is
stated in the configuration's `assumed`: the state-space sizes (the
published class's defaults) and the layer rule are keys of the file
beside the published ones (`mamba_*`, `layer_types`, `layer_indices`,
`shared_memory_layer`, `shared_kv_layer`) and are passed too; the
equations are builder arguments named for the mechanism (`EQUATIONS`).
Every layer's feed-forward is the dense MLP (`num_dense_layers` = the
depth; the routed experts' arguments are handed over empty).

A value the builder does not build raises (`ONLY`): another activation,
a bias on the MLP or the head, a dropout, another `mb_per_layer`.
`max_position_embeddings` is the deployed context and stays in the
file.  The counts are the benchmark's own, from the configuration's
shapes: they do not move when the program's HLO does.
"""

from __future__ import annotations

import numpy as np

PASSED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "intermediate_size", "layer_norm_eps",
          "sliding_window", "vocab_size", "tie_word_embeddings",
          "layer_types", "layer_indices", "shared_memory_layer",
          "shared_kv_layer", "mamba_d_state", "mamba_d_conv",
          "mamba_expand", "mamba_dt_rank", "attention_bias")
ONLY = {"hidden_act": "silu", "mlp_bias": False, "lm_head_bias": False,
        "embd_pdrop": 0, "resid_pdrop": 0, "mb_per_layer": 2}
EQUATIONS = {"norm": "layer_norm", "attention": "differential",
             "positions": "none", "qk_norm": None}
# no layer routes: the builder's expert arguments, empty
NO_EXPERTS = {"num_experts": 0, "num_experts_per_tok": 0,
              "norm_topk_prob": False}
ATTENTION = ("sliding_attention", "full_attention", "cross_attention")


def architecture(config):
    """The builder's arguments for this family's configuration."""
    for key, built in ONLY.items():
        if config.get(key, built) != built:
            raise NotImplementedError(
                f"{key} = {config[key]!r} is not built (only {built!r})")
    return dict({k: config[k] for k in PASSED},
                num_dense_layers=config["num_hidden_layers"],
                **NO_EXPERTS, **EQUATIONS)


def build(config):
    """Build the training graph under the caller's program guard and
    return the loss variable."""
    from paddle_tpu.models import decoder

    return decoder.build_model(
        max_length=config["sequence_length"], **config["training"],
        **architecture(config))["loss"]


def _token_probs(vocab):
    # ids 1..vocab-1 with Zipf-like frequencies, as
    # benchmarks/models/olmoe.py draws them: here over this chip's
    # slice of the vocabulary
    p = 1.0 / (np.arange(1, vocab) + 10.0)
    return p / p.sum()


def make_batch(config, cell, rng):
    """One global batch as the numpy feed of `Executor.run`: `length` +
    1 ids a sequence from the vocabulary slice, inputs and labels
    shifted by one, every position real."""
    n = cell["batch_per_chip"] * cell["chips"]
    length = cell["length"]
    if length != config["sequence_length"]:
        raise ValueError(f"length {length} is not the sequence_length "
                         f"{config['sequence_length']} the program is "
                         f"built for")
    vocab = config["vocab_size"]
    ids = rng.choice(vocab - 1, size=(n, length + 1),
                     p=_token_probs(vocab)) + 1
    return {"tokens": ids[:, :-1].astype(np.int64),
            "labels": ids[:, 1:].astype(np.int64)}


def score_pairs(length, window=None):
    """Score pairs a head that the mask allows over `length` positions:
    the causal half, or with a `window` W the band i - W < j <= i."""
    w = min(window or length, length)
    return w * length - w * (w - 1) // 2


def forward_flops_per_token(config, length):
    """Forward matmul FLOP of one token (2 per multiply-add), by part.
    A mamba layer: the in projection (hidden -> 2 d_inner), the
    step / B / C projection (d_inner -> dt_rank + 2 d_state), the step's
    (dt_rank -> d_inner) and the out projection.  A memory unit: in and
    out, hidden <-> d_inner.  An attention layer: q and the out
    projection at hidden x hidden, k and v at the key/value heads' width
    (a cross layer has neither).  Scores and values count the
    MATHEMATICS whatever runs: for each query head, scores over
    head_dim lanes and values over the pair's 2 x head_dim, over the
    pairs the MASK allows (the band under the window, the causal half
    over the whole prefix and in a cross layer).  The MLP: three
    matmuls at its width.  The scan, the convolution, norms, soft-max,
    the subtraction, embedding and recomputation count zero."""
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    head_dim = d // heads
    kv = config["num_key_value_heads"] * head_dim
    kinds = config["layer_types"]
    d_inner = config["mamba_expand"] * d
    low = config["mamba_dt_rank"] + 2 * config["mamba_d_state"]

    def scores(kind):
        window = config["sliding_window"] \
            if kind == "sliding_attention" else None
        return kinds.count(kind) * (
            2 * heads * (head_dim + 2 * head_dim)
            * score_pairs(length, window) / length)

    own_kv = kinds.count("sliding_attention") + kinds.count("full_attention")
    return {
        "state_space_projections": kinds.count("mamba") * 2 * (
            2 * d * d_inner + d_inner * low
            + config["mamba_dt_rank"] * d_inner + d_inner * d),
        "gated_memory": kinds.count("gated_memory") * 2 * 2 * d * d_inner,
        "attention_projections": 2 * (
            sum(kinds.count(k) for k in ATTENTION) * 2 * d * d
            + own_kv * 2 * d * kv),
        "sliding_attention": scores("sliding_attention"),
        "full_attention": scores("full_attention"),
        "cross_attention": scores("cross_attention"),
        "mlp": len(kinds) * 3 * 2 * d * config["intermediate_size"],
        "head": 2 * d * config["vocab_size"]}


def train_flops(config, cell):
    """Model FLOP of one training step over the global batch: forward
    and backward = 3 x the forward matmul FLOP."""
    tokens = cell["batch_per_chip"] * cell["chips"] * cell["length"]
    return 3.0 * sum(forward_flops_per_token(
        config, cell["length"]).values()) * tokens


def units(config, cell):
    """What one step completes: tokens that enter the loss (every
    position of every sequence), summed over chips."""
    n = cell["batch_per_chip"] * cell["chips"]
    return {"tokens_per_s": {"per_step": n * cell["length"],
                             "unit": "tokens/s"}}
