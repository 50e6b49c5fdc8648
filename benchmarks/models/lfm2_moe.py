"""The hybrid conv/attention MoE decoder family
(`paddle_tpu.models.decoder`), LFM2: configurations whose `model_type`
is "lfm2_moe".

The builder takes the published configuration's own keys, so the
configuration file is handed over as it stands: the keys below and the
`training` group, nothing renamed.  What the family's name stands for
and no key spells, the builder takes by mechanism (`EQUATIONS`): QK-norm
a head, and the sigmoid router.  Two keys are the deployment's and
not the catalog's: `expert_parallel_size` chips share each layer's
experts and this chip is `expert_parallel_rank`, so `num_experts` is
what is HELD here and the router is `num_experts *
expert_parallel_size` wide.  The counts are the benchmark's own, from
the configuration's shapes: they do not move when the program's HLO
does.
"""

from __future__ import annotations

import numpy as np

ARCHITECTURE = ("hidden_size", "num_hidden_layers",
                "layer_types", "num_dense_layers", "num_attention_heads",
                "num_key_value_heads", "intermediate_size",
                "moe_intermediate_size", "num_experts",
                "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
                "routed_scaling_factor", "conv_L_cache", "conv_bias",
                "norm_eps", "rope_parameters", "vocab_size",
                "expert_parallel_size", "expert_parallel_rank")
EQUATIONS = {"qk_norm": "head", "router": "sigmoid"}


def build(config):
    """Build the training graph under the caller's program guard and
    return the loss variable."""
    from paddle_tpu.models import decoder

    return decoder.build_model(
        max_length=config["sequence_length"], **config["training"],
        **EQUATIONS, **{k: config[k] for k in ARCHITECTURE})["loss"]


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


def forward_flops_per_token(config, length):
    """Forward matmul FLOP of one token (2 per multiply-add), by part.
    A conv operator: the in-projection to B, C, u and the
    out-projection.  An attention operator: q, o at the query heads'
    width, k, v at the key/value heads', causal scores and values at
    half.  The dense FFN: three matmuls at `intermediate_size`.  A
    routed FFN: the router over ALL experts, and the held experts at
    the uniform expectation, `num_experts_per_tok / expert_parallel_size`
    experts a token x 3 matmuls (what the router really sends here is
    the per-layer metric `held_expert_row_share`).  Embedding, norms,
    RoPE, the convolution's taps and gates, soft-max and the sort count
    zero."""
    d = config["hidden_size"]
    kv = d * config["num_key_value_heads"] // config["num_attention_heads"]
    routed = config["num_experts"] * config["expert_parallel_size"]
    per_kind = {"conv": 2 * d * 3 * d + 2 * d * d,
                "full_attention": 2 * (2 * d * d) + 2 * (2 * d * kv)
                + 2 * 2 * length * d / 2}
    parts = {"conv": 0.0, "full_attention": 0.0, "dense_ffn": 0.0,
             "router": 0.0, "experts": 0.0}
    for i, kind in enumerate(config["layer_types"]):
        parts[kind] += per_kind[kind]
        if i < config["num_dense_layers"]:
            parts["dense_ffn"] += 3 * 2 * d * config["intermediate_size"]
        else:
            parts["router"] += 2 * d * routed
            parts["experts"] += (
                config["num_experts_per_tok"]
                / config["expert_parallel_size"]
                * 3 * 2 * d * config["moe_intermediate_size"])
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
