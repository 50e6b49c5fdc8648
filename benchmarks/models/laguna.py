"""The window / full attention MoE decoder family whose head count
follows the layer type (`paddle_tpu.models.decoder`): configurations
whose `model_type` is "laguna" (sliding-window and full attention
layers mixed over grouped-query heads, `num_attention_heads_per_layer`
query heads over the same key/value heads, a RoPE of its own a layer
type with its own share of the head rotated, a gate a head on the
context, a leading dense layer, a shared expert beside the routed
ones).

The builder takes the published configuration's own keys, so most of
the file is handed over as it stands (`PASSED`).  What no key spells is
stated in the configuration's `assumed` and passed HERE as builder
arguments named for the mechanism (`EQUATIONS`): QK-norm a head, the
soft-max router with its weights renormalised, the gate's form (a
head).  One key is renamed (`RENAMED`: the factor on the routed sum).
Two keys are the deployment's and not the catalog's:
`expert_parallel_size` chips share each layer's experts and this chip
is `expert_parallel_rank`, so `num_experts` is what is HELD here and
the router is `num_experts * expert_parallel_size` wide.

A value the builder does not build raises (`ONLY`): another activation,
projection biases, no gate, the router's weights on the experts'
inputs.  `num_attention_heads` is the full layers' count and is read
only where `num_attention_heads_per_layer` is absent;
`max_position_embeddings` is the deployed context: both stay in the
file.  The counts are the benchmark's own, from the configuration's
shapes: they do not move when the program's HLO does.
"""

from __future__ import annotations

import numpy as np

PASSED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
          "num_attention_heads_per_layer", "num_key_value_heads",
          "head_dim", "layer_types", "mlp_layer_types", "sliding_window",
          "rope_parameters", "partial_rotary_factor", "intermediate_size",
          "moe_intermediate_size", "shared_expert_intermediate_size",
          "num_experts", "num_experts_per_tok", "rms_norm_eps",
          "vocab_size", "tie_word_embeddings", "expert_parallel_size",
          "expert_parallel_rank")
RENAMED = {"moe_routed_scaling_factor": "routed_scaling_factor"}
ONLY = {"hidden_act": "silu", "attention_bias": False, "gating": True,
        "moe_apply_router_weight_on_input": False}
EQUATIONS = {"qk_norm": "head", "router": "softmax", "norm_topk_prob": True,
             "attention_gate": "head"}


def architecture(config):
    """The builder's arguments for this family's configuration."""
    for key, built in ONLY.items():
        if config.get(key, built) != built:
            raise NotImplementedError(
                f"{key} = {config[key]!r} is not built (only {built!r})")
    return dict({k: config[k] for k in PASSED},
                **{new: config[old] for old, new in RENAMED.items()},
                **EQUATIONS)


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
    Projections: q, o at the LAYER's own query heads x head_dim, k, v
    at the key/value heads'; the gate hidden -> the layer's heads.
    Scores and values: two matmuls over the pairs the MASK allows (the
    band in a sliding_attention layer, the causal half in a
    full_attention layer) at the layer's own head count.  A dense FFN
    and a shared expert: three matmuls at their width.  A routed FFN:
    the router over ALL experts, and the held experts at the uniform
    expectation, `num_experts_per_tok / expert_parallel_size` experts
    a token x 3 matmuls.  Embedding, norms, RoPE, soft-max, the sort
    and recomputation count zero."""
    d, head_dim = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"] * head_dim
    kinds = config["layer_types"]
    heads = config["num_attention_heads_per_layer"]
    sparse = config["mlp_layer_types"].count("sparse")
    dense = config["mlp_layer_types"].count("dense")
    routed = config["num_experts"] * config["expert_parallel_size"]
    window = config["sliding_window"]

    def scores(kind):
        return sum(2 * 2 * h * head_dim * score_pairs(
            length, window if kind == "sliding_attention" else None)
            / length for h, k in zip(heads, kinds) if k == kind)

    return {
        "projections": sum(2 * (2 * d * h * head_dim + 2 * d * kv)
                           for h in heads),
        "full_attention": scores("full_attention"),
        "sliding_attention": scores("sliding_attention"),
        "gates": sum(2 * d * h for h in heads),
        "dense_ffn": dense * 3 * 2 * d * config["intermediate_size"],
        "shared_experts": sparse * 3 * 2 * d
        * config["shared_expert_intermediate_size"],
        "router": sparse * 2 * d * routed,
        "experts": sparse * (config["num_experts_per_tok"]
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
