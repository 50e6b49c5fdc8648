"""The window / full attention MoE decoder family
(`paddle_tpu.models.decoder`): configurations whose `model_type` is
"mellum" (sliding-window and full attention layers mixed over
grouped-query heads whose `head_dim` is a key of its own, a RoPE of
its own a layer type, every layer's FFN routed experts).

The builder takes the published configuration's own keys, so most of
the file is handed over as it stands (`PASSED`).  What no key spells is
stated in the configuration's `assumed` and passed HERE as builder
arguments named for the mechanism (`EQUATIONS`): QK-norm a head, the
soft-max router.  Two keys are the deployment's and not the catalog's:
`expert_parallel_size` chips share each layer's experts and this chip
is `expert_parallel_rank`, so `num_experts` is what is HELD here and
the router is `num_experts * expert_parallel_size` wide.

A value the builder does not build raises (`ONLY`): another activation,
projection biases, a dense layer among the sparse ones.
`intermediate_size` is the dense width and no layer is dense;
`max_window_layers` says nothing beside an explicit `layer_types`:
both stay in the file and are not read.  The counts are the
benchmark's own, from the configuration's shapes: they do not move
when the program's HLO does.
"""

from __future__ import annotations

import numpy as np

PASSED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "head_dim", "layer_types",
          "sliding_window", "rope_parameters", "intermediate_size",
          "moe_intermediate_size", "num_experts", "num_experts_per_tok",
          "norm_topk_prob", "rms_norm_eps", "vocab_size",
          "tie_word_embeddings", "expert_parallel_size",
          "expert_parallel_rank")
ONLY = {"hidden_act": "silu", "attention_bias": False,
        "use_sliding_window": True}
EQUATIONS = {"qk_norm": "head", "router": "softmax"}


def architecture(config):
    """The builder's arguments for this family's configuration."""
    for key, built in ONLY.items():
        if config[key] != built:
            raise NotImplementedError(
                f"{key} = {config[key]!r} is not built (only {built!r})")
    if set(config["mlp_layer_types"]) != {"sparse"} \
            or len(config["mlp_layer_types"]) != config["num_hidden_layers"]:
        raise NotImplementedError("mlp_layer_types: only 'sparse', one a "
                                  "layer, is built")
    return dict({k: config[k] for k in PASSED}, **EQUATIONS)


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
    Projections: q, o at num_attention_heads x head_dim, k, v at the
    key/value heads'.  Scores and values: two matmuls over the pairs
    the MASK allows, the band in a sliding_attention layer and the
    causal half in a full_attention layer (a window layer counted as a
    full one would put `mfu` 1.8 x too high).  A routed FFN: the router
    over ALL experts, and the held experts at the uniform expectation,
    `num_experts_per_tok / expert_parallel_size` experts a token x 3
    matmuls.  Embedding, norms, RoPE, soft-max, the sort and
    recomputation count zero."""
    d, head_dim = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * head_dim
    kv = config["num_key_value_heads"] * head_dim
    kinds = config["layer_types"]
    layers = len(kinds)
    routed = config["num_experts"] * config["expert_parallel_size"]
    window = config["sliding_window"]

    def scores(kind):
        pairs = score_pairs(length, window if kind == "sliding_attention"
                            else None)
        return 2 * 2 * q * pairs / length

    return {
        "projections": layers * 2 * (2 * d * q + 2 * d * kv),
        "full_attention": sum(scores(k) for k in kinds
                              if k == "full_attention"),
        "sliding_attention": sum(scores(k) for k in kinds
                                 if k == "sliding_attention"),
        "router": layers * 2 * d * routed,
        "experts": layers * (config["num_experts_per_tok"]
                             / config["expert_parallel_size"]
                             * 3 * 2 * d * config["moe_intermediate_size"]),
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
