"""The block-diffusion MoE decoder family
(`paddle_tpu.models.decoder`, `objective="block_diffusion"`):
configurations whose `model_type` is "sdar_moe" (grouped-query
attention at a `head_dim` of its own, every layer's FFN routed experts
under a soft-max router, trained by diffusion over blocks: the program
reads a document and its noised copy as ONE sequence of 2 L rows under
the block-diffusion mask; Arriola et al., arXiv:2503.09573).

The builder takes the published configuration's own keys, so most of
the file is handed over as it stands (`PASSED`).  What no key spells is
stated in the configuration's `assumed` and passed HERE as builder
arguments named for the mechanism (`EQUATIONS`, and the objective with
the configuration's `block_length`).  Two keys are the deployment's
and not the catalog's: `expert_parallel_size` chips share each layer's
experts and this chip is `expert_parallel_rank`, so `num_experts` is
what is HELD here and the router is `num_experts *
expert_parallel_size` wide.

The deployment also PLACES its experts (`place_experts`: each rank
one of the experts the mask id's rows take), in the start-up program.

A value the builder does not build raises (`ONLY`).  `intermediate_size`
is the dense width and no layer is dense; `max_window_layers` and
`sliding_window` say nothing under `use_sliding_window` false: they
stay in the file and are not read.  The counts are the benchmark's own,
from the configuration's shapes: they do not move when the program's
HLO does.
"""

from __future__ import annotations

import numpy as np

PASSED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
          "num_key_value_heads", "head_dim", "intermediate_size",
          "moe_intermediate_size", "num_experts", "num_experts_per_tok",
          "norm_topk_prob", "rms_norm_eps", "rope_theta", "vocab_size",
          "tie_word_embeddings", "expert_parallel_size",
          "expert_parallel_rank")
ONLY = {"hidden_act": "silu", "attention_bias": False,
        "use_sliding_window": False, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "rope_scaling": None}
EQUATIONS = {"qk_norm": "head", "router": "softmax",
             "objective": "block_diffusion"}


def architecture(config):
    """The builder's arguments for this family's configuration."""
    for key, built in ONLY.items():
        if config[key] != built:
            raise NotImplementedError(
                f"{key} = {config[key]!r} is not built (only {built!r})")
    return dict({k: config[k] for k in PASSED}, **EQUATIONS,
                block_length=config["block_length"])


def build(config):
    """Build the training graph under the caller's program guard and
    return the loss variable; the start-up program ends with the
    deployment's expert placement (`place_experts`)."""
    from paddle_tpu.models import decoder

    loss = decoder.build_model(
        max_length=config["sequence_length"], **config["training"],
        **architecture(config))["loss"]
    place_experts(config)
    return loss


def placement_order(config):
    """Where `place_experts` puts what: position i of the new router
    columns takes entry `order[i]` of [the mask id's chosen experts,
    best first ; the other experts by index].  Rank r's block starts
    with the r-th chosen expert and goes on with the next
    `num_experts - 1` of the others."""
    ranks, held = config["expert_parallel_size"], config["num_experts"]
    k = config["num_experts_per_tok"]
    if k != ranks:
        raise NotImplementedError(
            f"the placement gives each of the {ranks} ranks one of the "
            f"mask id's experts: {k} a token are not one a rank")
    order = np.empty((ranks, held), np.int64)
    order[:, 0] = np.arange(ranks)
    order[:, 1:] = k + np.arange(ranks * (held - 1)).reshape(ranks, held - 1)
    return order.reshape(-1)


def place_experts(config):
    """The deployment's expert placement, as ops appended to the
    caller's START-UP program (they run once, after the seed drew the
    weights; the step is the builder's, untouched).

    Under block diffusion about a quarter of a layer's rows hold ONE
    id, the mask's, and under these start-up weights they stay one
    vector through the stack (untrained attention averages), so in
    every layer they take the same `num_experts_per_tok` experts, each
    of which gets ~5 x an expert's mean rows.  Which ranks hold those
    experts is the deployment's to say, and no deployment leaves it to
    chance: with as many of them as ranks, each rank holds ONE.
    `experts_held` is a contiguous range of router columns, so the
    placement is a permutation of each router's columns (experts drawn
    alike from one seed are exchangeable: the expert weights stay):
    the router's choice for the mask id's embedding row (the row's
    norm and the norm's scale of 1 do not reorder logits), best first,
    then `placement_order`.  It holds for as long as the stream stays
    what start-up made it: at the configuration's rate a whole run
    (PERF.md section 6, PR 47: at twenty times the rate the late steps
    of a run route otherwise)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    ops = fluid.default_main_program().global_block().ops
    startup = fluid.default_startup_program()
    params = startup.global_block().vars
    table = params[next(op for op in ops
                        if op.type == "lookup_table").input("W")[0]]
    routers = [params[op.input("GateW")[0]] for op in ops
               if op.type == "moe_dropless"]
    k = config["num_experts_per_tok"]
    order = placement_order(config)
    experts = order.size
    with fluid.program_guard(startup, startup):
        mask_row = layers.gather(table, layers.assign(
            np.array([config["mask_token_id"]], np.int64)))
        order = layers.assign(order)
        index = layers.assign(np.arange(experts, dtype=np.float32))
        for router in routers:
            _, chosen = layers.topk(layers.matmul(mask_row, router), k)
            chosen = layers.reshape(chosen, [k, 1])
            taken = layers.reduce_sum(layers.one_hot(chosen, experts), dim=0)
            # the others by index: the chosen sort behind all of them
            _, others = layers.argsort(
                layers.elementwise_add(layers.scale(taken, float(experts)),
                                       index))
            columns = layers.gather(
                layers.concat([layers.reshape(chosen, [k]), others]), order)
            layers.assign(layers.transpose(layers.gather(
                layers.transpose(router, [1, 0]), columns), [1, 0]),
                output=router)


def _token_probs(ids):
    # Zipf-like frequencies, as benchmarks/models/olmoe.py draws them:
    # here over this chip's slice of the vocabulary less the mask id
    p = 1.0 / (np.arange(1, ids + 1) + 10.0)
    return p / p.sum()


def make_batch(config, cell, rng):
    """One global batch as the numpy feed of `Executor.run`: a document
    of `length` ids a sequence from the vocabulary slice (1 ..
    mask_token_id - 1), noised on the host by the package's own
    function: `tokens` (N, 2 x length), the document and its noised
    copy, `labels` the document, `loss_weights` 1 / t_b on the masked
    positions."""
    from paddle_tpu.data.diffusion import block_diffusion_feeds

    n = cell["batch_per_chip"] * cell["chips"]
    length = cell["length"]
    if length != config["sequence_length"]:
        raise ValueError(f"length {length} is not the sequence_length "
                         f"{config['sequence_length']} the program is "
                         f"built for")
    mask_id = config["mask_token_id"]
    if mask_id != config["vocab_size"] - 1:
        raise ValueError("the mask id is the vocabulary slice's last row")
    x0 = rng.choice(mask_id - 1, size=(n, length),
                    p=_token_probs(mask_id - 1)) + 1
    return block_diffusion_feeds(x0, config["block_length"], mask_id, rng,
                                 t_min=config["noise_t_min"])


def forward_flops(config, length):
    """Forward matmul FLOP of one document (2 per multiply-add), by
    part.  Projections (q, o at num_attention_heads x head_dim, k, v at
    the key/value heads'), the router over ALL experts and the held
    experts at the uniform expectation (`num_experts_per_tok /
    expert_parallel_size` experts a row x 3 matmuls) run over the 2 x
    `length` rows of every layer; scores and values are two matmuls
    over the pairs the MASK allows (`kernel_counts_sdar.allowed_pairs`,
    the readers' count); the head reads the noised half, `length` rows.
    Embedding, norms, RoPE, soft-max, the sort and recomputation count
    zero."""
    from kernel_counts_sdar import allowed_pairs

    d, head_dim = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * head_dim
    kv = config["num_key_value_heads"] * head_dim
    layers = config["num_hidden_layers"]
    routed = config["num_experts"] * config["expert_parallel_size"]
    rows = 2 * length
    return {
        "projections": layers * rows * 2 * (2 * d * q + 2 * d * kv),
        "block_diffusion_attention": layers * 2 * 2 * q * allowed_pairs(
            length, config["block_length"]),
        "router": layers * rows * 2 * d * routed,
        "experts": layers * rows * (
            config["num_experts_per_tok"] / config["expert_parallel_size"]
            * 3 * 2 * d * config["moe_intermediate_size"]),
        "head": length * 2 * d * config["vocab_size"]}


def train_flops(config, cell):
    """Model FLOP of one training step over the global batch: forward
    and backward = 3 x the forward matmul FLOP."""
    n = cell["batch_per_chip"] * cell["chips"]
    return 3.0 * n * sum(forward_flops(config, cell["length"]).values())


def units(config, cell):
    """What one step completes: the document's tokens (the target
    side: every position can enter the loss), not the 2 x length rows
    the program runs and not the half of them a draw masks; summed over
    chips."""
    n = cell["batch_per_chip"] * cell["chips"]
    return {"tokens_per_s": {"per_step": n * cell["length"],
                             "unit": "tokens/s"}}
