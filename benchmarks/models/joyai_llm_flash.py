"""The latent-attention MoE decoder family
(`paddle_tpu.models.decoder`): configurations whose `model_type` is
"joyai_llm_flash" (DeepSeek-V3-shaped: latent attention, sigmoid-routed
experts beside a shared one, a multi-token-prediction module).

The builder takes the published configuration's own keys, so most of
the file is handed over as it stands (`PASSED`).  What this family
spells otherwise is mapped HERE, and the map is the whole of it:

    n_routed_experts       -> num_experts       (what THIS chip holds)
    first_k_dense_replace  -> num_dense_layers
    scoring_func "sigmoid" -> router="sigmoid"
    topk_method "noaux_tc" -> use_expert_bias=True (the selection bias
                              no gradient reaches, moved against load)
    the family's 1e-20 under norm_topk_prob -> norm_topk_eps
    qk_head_dim            =  qk_nope_head_dim + qk_rope_head_dim (checked)

A value the builder does not build raises (`ONLY`): grouped top-k
(`n_group` / `topk_group` > 1), a dense layer between expert layers
(`moe_layer_freq`), another activation, attention biases.  `head_dim`
(64, the rotary width) and `ep_size` (the checkpoint's inference
setting) are not read: latent attention takes its head sizes from its
own keys, and `expert_parallel_size` / `expert_parallel_rank` are the
deployment's (`expert_parallel_size` chips share each layer's experts,
`n_routed_experts` is what is held here and the router is
`n_routed_experts * expert_parallel_size` wide).  The counts are the
benchmark's own, from the configuration's shapes: they do not move
when the program's HLO does.
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
          "n_shared_experts", "num_nextn_predict_layers",
          "expert_parallel_size", "expert_parallel_rank")
RENAMED = {"n_routed_experts": "num_experts",
           "first_k_dense_replace": "num_dense_layers"}
SPELT = {"scoring_func": {"sigmoid": {"router": "sigmoid"}},
         "topk_method": {"noaux_tc": {"use_expert_bias": True}}}
ONLY = {"n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
        "hidden_act": "silu", "attention_bias": False}
EQUATIONS = {"norm_topk_eps": 1e-20}


def architecture(config):
    """The builder's arguments for this family's configuration."""
    for key, built in ONLY.items():
        if config[key] != built:
            raise NotImplementedError(
                f"{key} = {config[key]!r} is not built (only {built!r})")
    if config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                 + config["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    args = {k: config[k] for k in PASSED}
    args.update({new: config[old] for old, new in RENAMED.items()})
    for key, values in SPELT.items():
        if config[key] not in values:
            raise NotImplementedError(
                f"{key} = {config[key]!r} is not built")
        args.update(values[config[key]])
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
    # benchmarks/models/lfm2_moe.py draws them over its slice
    p = 1.0 / (np.arange(1, vocab) + 10.0)
    return p / p.sum()


def make_batch(config, cell, rng):
    """One global batch as the numpy feed of `Executor.run`: `length` +
    2 ids a sequence from the vocabulary slice; `tokens`, their
    successors `labels` and the labels' own successors `next_labels`
    (the prediction module's targets), every position real."""
    n = cell["batch_per_chip"] * cell["chips"]
    length = cell["length"]
    if length != config["sequence_length"]:
        raise ValueError(f"length {length} is not the sequence_length "
                         f"{config['sequence_length']} the program is "
                         f"built for")
    vocab = config["vocab_size"]
    ids = (rng.choice(vocab - 1, size=(n, length + 2),
                      p=_token_probs(vocab)) + 1).astype(np.int64)
    return {"tokens": ids[:, :-2], "labels": ids[:, 1:-1],
            "next_labels": ids[:, 2:]}


def forward_flops_per_token(config, length):
    """Forward matmul FLOP of one token (2 per multiply-add), by part,
    of the main model AND the prediction module (the published
    objective runs both).  A block's latent attention: its five
    projections (q down and up, kv down with the rotary key, kv up,
    out) and causal scores (192 lanes) and values (128) at half.  The
    dense FFN: three matmuls at `intermediate_size`.  A routed FFN: the
    router over ALL experts, the shared experts whole, and the held
    experts at the uniform expectation, `num_experts_per_tok /
    expert_parallel_size` experts a token x 3 matmuls.  The module: its
    4096 -> 2048 projection, one block of the routed kind, and the head
    a second time.  Embedding, norms, RoPE, soft-max and the sort count
    zero."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v, q_rank = config["v_head_dim"], config["q_lora_rank"]
    kv_rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    width = config["moe_intermediate_size"]
    dense = config["first_k_dense_replace"]
    modules = config["num_nextn_predict_layers"]
    blocks = config["num_hidden_layers"] + modules
    routed = blocks - dense
    projections = (d * q_rank + q_rank * heads * qk + d * (kv_rank + rope)
                   + kv_rank * heads * (config["qk_nope_head_dim"] + v)
                   + heads * v * d)
    return {
        "attention_projections": blocks * 2 * projections,
        "attention": blocks * 2 * length * heads * (qk + v) / 2,
        "dense_ffn": dense * 3 * 2 * d * config["intermediate_size"],
        "router": routed * 2 * d * (config["n_routed_experts"]
                                    * config["expert_parallel_size"]),
        "shared_experts": routed * config["n_shared_experts"]
        * 3 * 2 * d * width,
        "experts": routed * config["num_experts_per_tok"]
        / config["expert_parallel_size"] * 3 * 2 * d * width,
        "mtp_projection": modules * 2 * 2 * d * d,
        "head": (1 + modules) * 2 * d * config["vocab_size"]}


def train_flops(config, cell):
    """Model FLOP of one training step over the global batch: forward
    and backward = 3 x the forward matmul FLOP."""
    tokens = cell["batch_per_chip"] * cell["chips"] * cell["length"]
    return 3.0 * sum(forward_flops_per_token(
        config, cell["length"]).values()) * tokens


def units(config, cell):
    """What one step completes: tokens that enter the MAIN loss (every
    position of every sequence), summed over chips; the module's second
    prediction of each is not a token more."""
    n = cell["batch_per_chip"] * cell["chips"]
    return {"tokens_per_s": {"per_step": n * cell["length"],
                             "unit": "tokens/s"}}
