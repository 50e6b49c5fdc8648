"""The looped decoder family (`paddle_tpu.models.decoder`):
configurations whose `model_type` is "ouro" (LoopLM: one stack of
layers run `total_ut_steps` times over shared weights, sandwich norms,
an exit gate and the vocabulary head at every trip).

The builder takes the published configuration's own keys, so most of
the file is handed over as it stands (`PASSED`).  What no key spells is
stated in the configuration's `assumed` and passed HERE as builder
arguments named for the mechanism (`EQUATIONS`): a norm after each
sub-layer, no QK-norm, a sigmoid exit gate.  Projections carry no bias
(the config has no `attention_bias` key; the builder builds none).  The
model is dense: no experts, every layer's FFN at `intermediate_size`.

A value the builder does not build raises (`ONLY`): another
activation, a sliding window, scaled RoPE.  `early_exit_threshold` is
a SERVING-time key (leave at the first trip whose cumulative exit mass
reaches it; at 1 every trip runs): the training path does not read it.
`head_dim` is checked against hidden_size / num_attention_heads;
`max_window_layers` says nothing without a window.  The counts are the
benchmark's own, from the configuration's shapes: they do not move
when the program's HLO does.
"""

from __future__ import annotations

import numpy as np

PASSED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "intermediate_size", "layer_types",
          "rms_norm_eps", "rope_theta", "rope_scaling", "vocab_size",
          "tie_word_embeddings", "total_ut_steps")
ONLY = {"hidden_act": "silu", "use_sliding_window": False,
        "sliding_window": None, "rope_scaling": None}
EQUATIONS = {"sandwich_norm": True, "qk_norm": None, "exit_gate": "sigmoid"}


def architecture(config):
    """The builder's arguments for this family's configuration."""
    for key, built in ONLY.items():
        if config[key] != built:
            raise NotImplementedError(
                f"{key} = {config[key]!r} is not built (only {built!r})")
    if config["head_dim"] * config["num_attention_heads"] \
            != config["hidden_size"]:
        raise ValueError("head_dim is not hidden_size / num_attention_heads")
    args = {k: config[k] for k in PASSED}
    # dense: no experts, every layer's FFN at intermediate_size
    args.update(num_experts=0, num_experts_per_tok=0, norm_topk_prob=False,
                num_dense_layers=config["num_hidden_layers"])
    return dict(args, **EQUATIONS)


def build(config):
    """Build the training graph under the caller's program guard and
    return the loss variable."""
    from paddle_tpu.models import decoder

    return decoder.build_model(
        max_length=config["sequence_length"], **config["training"],
        **architecture(config))["loss"]


def _token_probs(vocab):
    # ids 1..vocab-1 with Zipf-like frequencies, as
    # benchmarks/models/olmoe.py draws them
    p = 1.0 / (np.arange(1, vocab) + 10.0)
    return p / p.sum()


def make_batch(config, cell, rng):
    """One global batch as the numpy feed of `Executor.run`: `length` +
    1 ids a sequence over the whole vocabulary, inputs and labels
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


def forward_flops_per_token(config, length):
    """Forward matmul FLOP of one token (2 per multiply-add), by part,
    over ALL trips: a layer pass is the q, k, v, o projections, causal
    scores and values at half, and the three FFN matmuls; the stack
    runs `total_ut_steps` times, and every trip ends in the vocabulary
    head and the 1-wide gate.  Embedding, norms, RoPE, soft-max and
    recomputation count zero."""
    d, dff = config["hidden_size"], config["intermediate_size"]
    kv = (config["num_key_value_heads"] * d
          // config["num_attention_heads"])
    trips = config["total_ut_steps"]
    passes = trips * config["num_hidden_layers"]
    return {
        "projections": passes * 2 * (2 * d * d + 2 * d * kv),
        "attention": passes * 2 * 2 * length * d / 2,
        "ffn": passes * 3 * 2 * d * dff,
        "head": trips * 2 * d * config["vocab_size"],
        "gate": trips * 2 * d}


def train_flops(config, cell):
    """Model FLOP of one training step over the global batch: forward
    and backward = 3 x the forward matmul FLOP."""
    tokens = cell["batch_per_chip"] * cell["chips"] * cell["length"]
    return 3.0 * sum(forward_flops_per_token(
        config, cell["length"]).values()) * tokens


def units(config, cell):
    """What one step completes: tokens that enter the loss (every
    position of every sequence, once, however many trips read it),
    summed over chips."""
    n = cell["batch_per_chip"] * cell["chips"]
    return {"tokens_per_s": {"per_step": n * cell["length"],
                             "unit": "tokens/s"}}
