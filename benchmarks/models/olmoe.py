"""The decoder-only MoE family (`paddle_tpu.models.decoder`), OLMoE.

The builder takes the published configuration's own keys, so the
configuration file is handed over as it stands: the keys below and the
`training` group, nothing renamed.  The counts are the benchmark's
own, from the configuration's shapes: they do not move when the
program's HLO does.
"""

from __future__ import annotations

import numpy as np

ARCHITECTURE = ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "intermediate_size", "num_experts",
                "num_experts_per_tok", "norm_topk_prob", "rope_theta",
                "rms_norm_eps", "vocab_size", "tie_word_embeddings")


def build(config):
    """Build the training graph under the caller's program guard and
    return the loss variable."""
    from paddle_tpu.models import decoder

    return decoder.build_model(
        max_length=config["max_position_embeddings"],
        **config["training"], **{k: config[k] for k in ARCHITECTURE})["loss"]


def _token_probs(vocab):
    # ids 1..vocab-1 with Zipf-like frequencies, as
    # benchmarks/models/transformer.py draws them
    p = 1.0 / (np.arange(1, vocab) + 10.0)
    return p / p.sum()


def make_batch(config, cell, rng):
    """One global batch as the numpy feed of `Executor.run`: `length` +
    1 ids a sequence, inputs and labels shifted by one, every position
    real.  The program is built for the published context, so `length`
    is that context."""
    n = cell["batch_per_chip"] * cell["chips"]
    length, context = cell["length"], config["max_position_embeddings"]
    if length != context:
        raise ValueError(f"length {length} is not the context {context} "
                         f"the program is built for")
    vocab = config["vocab_size"]
    ids = rng.choice(vocab - 1, size=(n, length + 1),
                     p=_token_probs(vocab)) + 1
    return {"tokens": ids[:, :-1].astype(np.int64),
            "labels": ids[:, 1:].astype(np.int64)}


def forward_flops_per_token(config, length):
    """Forward matmul FLOP of one token (2 per multiply-add), by part:
    q, k, v, o projections; causal scores and values at half; the
    router; `num_experts_per_tok` active experts x 3 matmuls; the head.
    Embedding, norms, RoPE, soft-max, the sort and recomputation count
    zero."""
    d, dff = config["hidden_size"], config["intermediate_size"]
    layers = config["num_hidden_layers"]
    per_layer = {
        "projections": 4 * 2 * d * d,
        "attention": 2 * 2 * length * d / 2,
        "router": 2 * d * config["num_experts"],
        "experts": config["num_experts_per_tok"] * 3 * 2 * d * dff,
    }
    parts = {k: layers * v for k, v in per_layer.items()}
    parts["head"] = 2 * d * config["vocab_size"]
    return parts


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
