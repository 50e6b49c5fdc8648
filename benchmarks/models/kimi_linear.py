"""The lane-decayed linear-attention MoE decoder family
(`paddle_tpu.models.decoder`): configurations whose `model_type` is
"kimi_linear" (Kimi Delta Attention mixers, a delta rule whose decay is
a key lane's own, three layers in four; latent attention with no
positions at all in the fourth; a leading dense FFN, then sigmoid-routed
experts beside one shared expert).

The builder takes the published configuration's own keys, so most of
the file is handed over as it stands (`PASSED`, `linear_attn_config`
whole).  What this family spells otherwise is mapped HERE, and the map
is the whole of it:

    num_experts_per_token  -> num_experts_per_tok
    moe_renormalize        -> norm_topk_prob
    num_shared_experts     -> n_shared_experts
    first_k_dense_replace  -> num_dense_layers
    moe_router_activation_func "sigmoid" -> router="sigmoid"
    linear_attn_config.kda_layers / full_attn_layers (1-based)
                           -> layer_types ("channel_delta_attention" /
                              "full_attention", every layer in one list)

What no key spells is stated in the configuration's `assumed` and
passed as builder arguments named for the mechanism (`EQUATIONS`): no
QK-norm, the selection bias on the sigmoid scores, the family's 1e-20
under the renormalised weights.  The low-rank gates' rank is the
builder's constant, the head size.  A value the builder does not build
raises (`ONLY`, and the builder's own checks: `num_expert_group` /
`topk_group` > 1, a prediction module beside the new mixer).
`head_dim` (72), `rope_theta`, `rope_scaling` and `model_max_length` are
not read: latent attention takes its head sizes from its own keys and
`mla_use_nope` leaves it without positions.  `expert_parallel_size` /
`expert_parallel_rank` are the deployment's (`num_experts` is what is
HELD here and the router is `num_experts * expert_parallel_size` wide).
The counts are the benchmark's own, from the configuration's shapes:
they do not move when the program's HLO does.
"""

from __future__ import annotations

import numpy as np

PASSED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "intermediate_size",
          "moe_intermediate_size", "num_experts", "routed_scaling_factor",
          "rms_norm_eps", "vocab_size", "tie_word_embeddings",
          "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "mla_use_nope",
          "linear_attn_config", "num_expert_group", "topk_group",
          "num_nextn_predict_layers", "expert_parallel_size",
          "expert_parallel_rank")
RENAMED = {"num_experts_per_token": "num_experts_per_tok",
           "moe_renormalize": "norm_topk_prob",
           "num_shared_experts": "n_shared_experts",
           "first_k_dense_replace": "num_dense_layers"}
SPELT = {"moe_router_activation_func": {"sigmoid": {"router": "sigmoid"}}}
ONLY = {"hidden_act": "silu", "moe_layer_freq": 1, "rope_scaling": None,
        "use_grouped_topk": True}
EQUATIONS = {"qk_norm": None, "use_expert_bias": True,
             "norm_topk_eps": 1e-20}
DELTA, FULL = "channel_delta_attention", "full_attention"


def layer_types(config):
    """One entry a layer from the two 1-based lists, which together
    name every layer once."""
    group = config["linear_attn_config"]
    delta, full = set(group["kda_layers"]), set(group["full_attn_layers"])
    layers = range(1, config["num_hidden_layers"] + 1)
    if delta & full or delta | full != set(layers):
        raise ValueError(
            f"kda_layers {sorted(delta)} and full_attn_layers "
            f"{sorted(full)} do not name layers 1 .. "
            f"{config['num_hidden_layers']} once each")
    return [DELTA if i in delta else FULL for i in layers]


def architecture(config):
    """The builder's arguments for this family's configuration."""
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
    return dict(args, layer_types=layer_types(config), **EQUATIONS)


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
    shifted by one, every position real, one unbroken document."""
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
    A delta layer: its projections (q, k, v as one, the two low-rank
    pairs at the head size's rank, beta, out) and the recurrence IN ITS
    SEQUENTIAL FORM, three Dk x Dv products a head a token (S^T k,
    k u^T, S^T q: 6 Dk Dv; the decay aside), whatever chunks a kernel
    runs it in.  A latent layer: its four projections (q direct, kv
    down with the 64 extra lanes, kv up, out) and causal scores (192
    lanes) and values (128) at half.  The dense FFN: three matmuls at
    `intermediate_size`.  A routed FFN: the router over ALL experts,
    the shared expert whole, the held experts at the uniform
    expectation (`num_experts_per_token / expert_parallel_size` experts
    a token x 3 matmuls).  Embedding, norms, the convolution, soft-max,
    the sort and recomputation count zero."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    group = config["linear_attn_config"]
    lanes = group["num_heads"] * group["head_dim"]
    rank = group["head_dim"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v, kv_rank = config["v_head_dim"], config["kv_lora_rank"]
    width = config["moe_intermediate_size"]
    kinds = layer_types(config)
    delta, full = kinds.count(DELTA), kinds.count(FULL)
    dense = config["first_k_dense_replace"]
    routed = len(kinds) - dense
    return {
        "delta_projections": delta * 2 * (
            d * 3 * lanes + 2 * (d * rank + rank * lanes)
            + d * group["num_heads"] + lanes * d),
        "recurrence": delta * group["num_heads"] * 6 * group["head_dim"] ** 2,
        "latent_projections": full * 2 * (
            d * heads * qk + d * (kv_rank + config["qk_rope_head_dim"])
            + kv_rank * heads * (config["qk_nope_head_dim"] + v)
            + heads * v * d),
        "latent_attention": full * 2 * (length + 1) * heads * (qk + v) / 2,
        "dense_ffn": dense * 3 * 2 * d * config["intermediate_size"],
        "router": routed * 2 * d * (config["num_experts"]
                                    * config["expert_parallel_size"]),
        "shared_expert": routed * config["num_shared_experts"]
        * 3 * 2 * d * width,
        "experts": routed * config["num_experts_per_token"]
        / config["expert_parallel_size"] * 3 * 2 * d * width,
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
