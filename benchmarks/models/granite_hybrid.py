"""The hybrid state-space / attention decoder family
(`paddle_tpu.models.decoder`): configurations whose `model_type` is
"granitemoehybrid" with no routed experts (Granite 4.0-H: Mamba-2
state-space mixers, Dao & Gu, arXiv:2405.21060, nine to one causal
grouped-query attention layer without positions under a scale of its
own; RMSNorm, a tied head, the shared SwiGLU MLP in every layer, and
four multipliers: on the embedding, on every residual branch, inside
the soft-max and under the logits).

The builder takes the published configuration's own keys, so most of
the file is handed over as it stands (`PASSED`).  Two keys are mapped:
`layer_types`' "attention" is the builder's "full_attention", and
`shared_intermediate_size` is the width of the dense feed-forward of
every layer (`num_local_experts` 0: nothing routes; `num_dense_layers`
= the depth and the routed experts' arguments are handed over empty).
What no key spells is a builder argument named for the mechanism
(`EQUATIONS`): no positions, no QK-norm.

A value the builder does not build raises (`ONLY`): routed experts,
another activation or norm, positions, a bias on the attention or the
mixer's projections, several groups of B and C, a convolution without
its bias.  `max_position_embeddings` is the deployed context and
`rope_theta` is unused (`position_embedding_type` "nope"): both stay in
the file.  The counts are the benchmark's own, from the configuration's
shapes: they do not move when the program's HLO does.
"""

from __future__ import annotations

import numpy as np

PASSED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "rms_norm_eps", "vocab_size",
          "tie_word_embeddings", "attention_bias", "mamba_d_state",
          "mamba_d_conv", "mamba_expand", "mamba_n_heads", "mamba_d_head",
          "mamba_n_groups", "mamba_chunk_size", "embedding_multiplier",
          "residual_multiplier", "attention_multiplier", "logits_scaling")
ONLY = {"num_local_experts": 0, "num_experts_per_tok": 0,
        "hidden_act": "silu", "normalization_function": "rmsnorm",
        "position_embedding_type": "nope", "attention_bias": False,
        "mamba_n_groups": 1, "mamba_proj_bias": False,
        "mamba_conv_bias": True, "rope_scaling": None}
EQUATIONS = {"positions": "none", "qk_norm": None}
# no layer routes: the builder's expert arguments, empty
NO_EXPERTS = {"num_experts": 0, "num_experts_per_tok": 0,
              "norm_topk_prob": False}
LAYER_TYPES = {"mamba": "mamba", "attention": "full_attention"}


def architecture(config):
    """The builder's arguments for this family's configuration."""
    for key, built in ONLY.items():
        if config.get(key, built) != built:
            raise NotImplementedError(
                f"{key} = {config[key]!r} is not built (only {built!r})")
    unknown = sorted(set(config["layer_types"]) - set(LAYER_TYPES))
    if unknown:
        raise NotImplementedError(f"layer types {unknown} are not built")
    if config.get("intermediate_size",
                  config["shared_intermediate_size"]) \
            != config["shared_intermediate_size"]:
        raise NotImplementedError(
            "intermediate_size beside another shared_intermediate_size: "
            "one dense MLP a layer is built")
    return dict({k: config[k] for k in PASSED},
                layer_types=[LAYER_TYPES[k] for k in config["layer_types"]],
                intermediate_size=config["shared_intermediate_size"],
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


def forward_flops_per_token(config, length):
    """Forward matmul FLOP of one token (2 per multiply-add), by part.
    A mamba layer: the in projection (hidden -> d_inner for z, d_inner +
    2 groups x d_state for x, B and C, one a head for the step) and the
    out projection; its recurrence in the SEQUENTIAL form, whatever
    chunks a kernel runs it in: a head's write dt x B^T and its read-out
    S C, 2 x d_head x d_state multiply-adds a head.  An attention layer:
    q and the out projection at hidden x hidden, k and v at the
    key/value heads' width; scores and values over the causal half.
    The MLP: three matmuls at its width.  The convolution, norms,
    soft-max, the multipliers, embedding and recomputation count
    zero."""
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    head_dim = d // heads
    kv = config["num_key_value_heads"] * head_dim
    kinds = config["layer_types"]
    mamba, attention = kinds.count("mamba"), kinds.count("attention")
    ssm_heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    states = config["mamba_n_groups"] * config["mamba_d_state"]
    d_inner = ssm_heads * p
    causal_pairs = length * (length + 1) // 2
    return {
        "state_space_projections": mamba * 2 * (
            d * (2 * d_inner + 2 * states + ssm_heads) + d_inner * d),
        "state_space_recurrence": mamba * 2 * (
            2 * ssm_heads * p * config["mamba_d_state"]),
        "attention_projections": attention * 2 * (2 * d * d + 2 * d * kv),
        "full_attention": attention * 2 * heads * 2 * head_dim
        * causal_pairs / length,
        "mlp": len(kinds) * 3 * 2 * d * config["shared_intermediate_size"],
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
