"""The hybrid linear-attention MoE decoder family
(`paddle_tpu.models.decoder`): configurations whose `model_type` is
"qwen3_next" (gated-delta-rule linear-attention layers and gated full
attention layers mixed by `full_attention_interval`, a quarter of each
attention head's lanes rotated, zero-centred norms, every layer's FFN
soft-max-routed experts beside one gated shared expert).

The builder takes the published configuration's own keys, so most of
the file is handed over as it stands (`PASSED`); `layer_types` is
spelt out here from `full_attention_interval` (layer i is
`full_attention` when (i + 1) % interval == 0, else
`linear_attention`).  What no key spells is stated in the
configuration's `assumed` and passed HERE as builder arguments named
for the mechanism (`EQUATIONS`): QK-norm a head, the soft-max router,
the zero-centred norm scale, the sigmoid gates on the attention context
and on the shared expert.  Two keys are the deployment's and not the
catalog's: `expert_parallel_size` chips share each layer's experts and
this chip is `expert_parallel_rank`, so `num_experts` is what is HELD
here and the router is `num_experts * expert_parallel_size` wide.

A value the builder does not build raises (`ONLY`): another
activation, a window, scaled RoPE, a dense layer among the sparse ones.
`intermediate_size` is the dense width and no layer is dense: it stays
in the file and is not read.  The counts are the benchmark's own, from
the configuration's shapes: they do not move when the program's HLO
does.
"""

from __future__ import annotations

import numpy as np

PASSED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "head_dim", "partial_rotary_factor",
          "rope_theta", "intermediate_size", "moe_intermediate_size",
          "shared_expert_intermediate_size", "num_experts",
          "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
          "vocab_size", "tie_word_embeddings", "linear_num_key_heads",
          "linear_num_value_heads", "linear_key_head_dim",
          "linear_value_head_dim", "linear_conv_kernel_dim",
          "expert_parallel_size", "expert_parallel_rank")
ONLY = {"hidden_act": "silu", "use_sliding_window": False,
        "rope_scaling": None, "decoder_sparse_step": 1,
        "mlp_only_layers": []}
EQUATIONS = {"qk_norm": "head", "router": "softmax",
             "zero_centered_norm": True, "attention_gate": "sigmoid",
             "shared_expert_gate": "sigmoid"}
LINEAR, FULL = "linear_attention", "full_attention"


def layer_types(config):
    every = config["full_attention_interval"]
    return [FULL if (i + 1) % every == 0 else LINEAR
            for i in range(config["num_hidden_layers"])]


def architecture(config):
    """The builder's arguments for this family's configuration."""
    for key, built in ONLY.items():
        if config[key] != built:
            raise NotImplementedError(
                f"{key} = {config[key]!r} is not built (only {built!r})")
    return dict({k: config[k] for k in PASSED},
                layer_types=layer_types(config), **EQUATIONS)


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
    A linear layer: its four projections (q, k, v, z as one count, b
    and a, out) and the recurrence IN ITS SEQUENTIAL FORM, three
    Dk x Dv products a value head a token (S^T k, k u^T, S^T q: 6 Dk Dv;
    the decay aside), whatever chunks a kernel runs it in.  A full
    layer: q and its gate, k, v, o, and two matmuls over the causal
    half's pairs (diagonal included).  A routed FFN: the router over
    ALL experts, the held experts at the uniform expectation
    (`num_experts_per_tok / expert_parallel_size` experts a token x 3
    matmuls), the shared expert whole and its 1-wide gate.  Embedding,
    norms, RoPE, the convolution, soft-max, the sort and recomputation
    count zero."""
    d, head_dim = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * head_dim
    kv = config["num_key_value_heads"] * head_dim
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    kinds = layer_types(config)
    linear, full, layers = kinds.count(LINEAR), kinds.count(FULL), len(kinds)
    routed = config["num_experts"] * config["expert_parallel_size"]
    pairs = length * (length + 1) // 2
    return {
        "linear_projections": linear * 2 * d * (
            2 * hk * dk + 2 * hv * dv + 2 * hv + hv * dv),
        "recurrence": linear * hv * 6 * dk * dv,
        "full_projections": full * 2 * d * (3 * q + 2 * kv),
        "full_attention": full * 2 * 2 * q * pairs / length,
        "router": layers * 2 * d * routed,
        "shared_expert": layers * 2 * d * (
            3 * config["shared_expert_intermediate_size"] + 1),
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
