"""Decoder-only language model, built from a published `config.json`'s
own keys.

One builder for the decoder family of queue R: its arguments ARE the
catalog's keys (`hidden_size`, `num_hidden_layers`, ...), so a
configuration file is passed through unrenamed and the next decoder
configuration extends this builder instead of forking it.  What is
here is what OLMoE-1B-7B (Muennighoff et al. 2024, arXiv:2409.02060)
and LFM2-24B-A2B need; a key whose other values are not built yet
raises.

OLMoE's block, pre-norm:

    h  = rms_norm(x)
    q, k, v = h Wq, h Wk, h Wv                 (no bias)
    q, k = rms_norm(q), rms_norm(k)            (QK-norm over the whole
                                                projection, before the
                                                head split)
    q, k = rope(q), rope(k)                    (rotate-half, whole head)
    x  = x + flash_attention(q, k, v, causal) Wo
    x  = x + dropless_moe(rms_norm(x))         (top-k of E SwiGLU experts)

then a final rms_norm and the vocabulary head.  Activations stay
head-grouped (N, T, H*D) from the projections through the Pallas flash
kernels and back: no transpose exists in the program.

What the other keys add.  Two equations are spelt by no catalog key
and are arguments of their own, named for the mechanism and never for
a model (the family file that maps a configuration onto this builder
passes them): `qk_norm` ("projection": over the whole projection, one
(H*D,) scale; "head": each head alone under one shared (D,) scale) and
`router` ("softmax" | "sigmoid").

    layer i:   x = x + op_i(rms_norm(x));  x = x + ffn_i(rms_norm(x))
    op_i  = layer_types[i]: "full_attention" as above, with
            num_key_value_heads < num_attention_heads (query head j
            reads key/value head j // group) and q, k normalised as
            `qk_norm` says; or "conv":
            B, C, u = split3(h W_in);  out = (C * conv(B * u)) W_out,
            a causal depthwise convolution of conv_L_cache taps
            (`layers.short_conv`)
    ffn_i = i < num_dense_layers: (silu(h W1) * (h W3)) W2 at
            intermediate_size; otherwise the routed experts at
            moe_intermediate_size; with `router="sigmoid"` chosen by
            sigmoid score + use_expert_bias's bias and weighted by
            the score (`layers.dropless_moe(routing=)`); a training
            recipe's `expert_bias_update_rate` moves that bias against
            each expert's load every step (balancing without an
            auxiliary loss); 0, the default, leaves it alone

`expert_parallel_size` chips share each layer's experts: `num_experts`
is then what THIS chip (`expert_parallel_rank`) holds, the router is
`num_experts * expert_parallel_size` wide, and the expert layer gives
its share of the result (`experts_held`), nothing standing in for the
other chips.  No exchange runs here either, so nothing sums the ranks'
parts of the gradient that reaches the router and the layer's input
through the routing weights; this builder therefore asks the share for
`router_gradient=False` (the routing weights are constants of the
backward pass: the router of such a program is NOT trained, it only
decays).  The op's own backward is the true partial; a lowering that
all-reduces the parts asks for it.

The training objective is the paper's: token cross-entropy + `aux_loss_weight`
x the load-balancing loss + `z_loss_weight` x the router z-loss (both
averaged over layers), AdamW, global-norm gradient clipping, linear
warm-up into a cosine decay to `lr_floor` of the peak.
"""

from __future__ import annotations

from .. import layers, optimizer
from ..clip import GradientClipByGlobalNorm, set_gradient_clip
from ..initializer import Normal
from ..param_attr import ParamAttr


def decoder(hidden_size, num_hidden_layers, num_attention_heads,
            num_key_value_heads, intermediate_size, num_experts,
            num_experts_per_tok, norm_topk_prob, vocab_size, max_length,
            rope_theta=None, rms_norm_eps=None, tie_word_embeddings=False,
            initializer_range=0.02, qk_norm="projection", router="softmax",
            layer_types=None,
            num_dense_layers=0, moe_intermediate_size=None,
            conv_L_cache=None, conv_bias=False, use_expert_bias=False,
            routed_scaling_factor=1.0, norm_eps=None, rope_parameters=None,
            expert_parallel_size=1, expert_parallel_rank=0,
            expert_bias_update_rate=0.0):
    """Append the forward pass to the default program.  Feeds `tokens`
    and `labels`, both (N, max_length) int64.  Returns a dict: `logits`
    (N, T, vocab); `ce`, `aux`, `z`, each (1,): the mean token
    cross-entropy, the load-balancing loss and the router z-loss, the
    last two averaged over the layers that route (None where none
    does); `counts` and `experts`, per routed layer the rows per expert
    held and each token's experts (N*T, k)."""
    if qk_norm not in ("projection", "head"):
        raise NotImplementedError(f"qk_norm {qk_norm!r} is not built")
    if router not in ("softmax", "sigmoid"):
        raise NotImplementedError(f"router {router!r} is not built")
    if hidden_size % num_attention_heads:
        raise ValueError("hidden_size is not a whole number of heads")
    if num_attention_heads % num_key_value_heads:
        raise ValueError("num_attention_heads is not a multiple of "
                         "num_key_value_heads")
    if conv_bias:
        raise NotImplementedError("conv_bias is not built")
    eps = norm_eps if rms_norm_eps is None else rms_norm_eps
    theta = (rope_parameters or {}).get("rope_theta", rope_theta)
    if rope_parameters and rope_parameters.get("rope_type",
                                               "default") != "default":
        raise NotImplementedError(
            f"rope_type {rope_parameters['rope_type']!r} is not built")
    if eps is None or theta is None:
        raise ValueError("decoder needs rms_norm_eps or norm_eps, and "
                         "rope_theta or rope_parameters")
    layer_types = list(layer_types or
                       ["full_attention"] * num_hidden_layers)
    if len(layer_types) != num_hidden_layers:
        raise ValueError(f"{len(layer_types)} layer_types for "
                         f"{num_hidden_layers} layers")
    head_dim = hidden_size // num_attention_heads
    kv_size = num_key_value_heads * head_dim
    expert_width = moe_intermediate_size or intermediate_size
    held = None
    if expert_parallel_size != 1:
        held = (expert_parallel_rank * num_experts, num_experts)

    def weight():
        return ParamAttr(initializer=Normal(0.0, initializer_range))

    def proj(x, size, name):
        return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=False,
                         param_attr=weight(), name=name)

    def norm(x):
        return layers.rms_norm(x, epsilon=eps)

    def norm_qk(x):
        if qk_norm == "head":
            return layers.rms_norm(x, epsilon=eps, group_size=head_dim)
        return norm(x)

    def attention(h):
        q = layers.rope(norm_qk(proj(h, hidden_size, "attn_qkv")),
                        num_attention_heads, theta)
        k = layers.rope(norm_qk(proj(h, kv_size, "attn_qkv")),
                        num_key_value_heads, theta)
        v = proj(h, kv_size, "attn_qkv")
        ctx = layers.flash_attention(q, k, v, causal=True, use_pallas=True,
                                     layout="nthd",
                                     n_head=num_attention_heads,
                                     n_kv_head=num_key_value_heads)
        return proj(ctx, hidden_size, "attn_out")

    def conv(h):
        y = layers.short_conv(proj(h, 3 * hidden_size, "conv_in"),
                              conv_L_cache, param_attr=weight())
        return proj(y, hidden_size, "conv_out")

    def dense_ffn(h):
        gate = layers.swiglu(proj(h, intermediate_size, "ffn_in"),
                             proj(h, intermediate_size, "ffn_in"))
        return proj(gate, hidden_size, "ffn_out")

    tokens = layers.data(name="tokens", shape=[max_length], dtype="int64")
    labels = layers.data(name="labels", shape=[max_length], dtype="int64")
    embed = ParamAttr(name="tok_embedding.w",
                      initializer=Normal(0.0, initializer_range))
    x = layers.embedding(tokens, size=[vocab_size, hidden_size],
                         param_attr=embed)
    aux_losses, z_losses, counts, experts = [], [], [], []
    for i, kind in enumerate(layer_types):
        if kind == "full_attention":
            op = attention
        elif kind == "conv":
            op = conv
        else:
            raise NotImplementedError(f"layer type {kind!r} is not built")
        x = layers.elementwise_add(x, op(norm(x)))
        if i < num_dense_layers:
            x = layers.elementwise_add(x, dense_ffn(norm(x)))
            continue
        y, aux, z, count, chosen = layers.dropless_moe(
            norm(x), num_experts * expert_parallel_size, expert_width,
            num_experts_per_tok, norm_topk_prob=norm_topk_prob,
            param_attr=weight(), experts_held=held,
            # a share without the exchange that sums the ranks' parts
            router_gradient=held is None, routing=router,
            use_expert_bias=use_expert_bias,
            routed_scaling_factor=routed_scaling_factor,
            expert_bias_update_rate=expert_bias_update_rate)
        x = layers.elementwise_add(x, y)
        aux_losses.append(aux), z_losses.append(z)
        counts.append(count), experts.append(chosen)
    x = norm(x)
    if tie_word_embeddings:
        table = x.block.program.global_block().var(embed.name)
        logits = layers.matmul(x, table, transpose_y=True)
    else:
        logits = proj(x, vocab_size, "lm_head")
    ce = layers.mean(layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(labels, axes=[2])))

    def layer_mean(losses):
        if not losses:
            return None
        return layers.scale(layers.sums(losses), scale=1.0 / len(losses))

    return {"logits": logits, "ce": ce, "aux": layer_mean(aux_losses),
            "z": layer_mean(z_losses), "counts": counts,
            "experts": experts, "feeds": ["tokens", "labels"]}


def build_model(max_length, learning_rate=4e-4, beta1=0.9, beta2=0.95,
                epsilon=1e-8, weight_decay=0.1, warmup_steps=2000,
                decay_steps=1_000_000, lr_floor=0.1, clip_norm=1.0,
                aux_loss_weight=0.01, z_loss_weight=0.001, use_amp=True,
                with_optimizer=True, **architecture):
    """The training Program of `decoder(**architecture)`: loss, AdamW
    under bf16 AMP, clipping and the schedule.  The defaults are the
    OLMoE paper's settings; `architecture` holds the configuration's
    own keys and, where its equations need them, `qk_norm` / `router`."""
    model = decoder(max_length=max_length, **architecture)
    ce, aux, z = model["ce"], model["aux"], model["z"]
    # an auxiliary loss with no weight (or no routed layer) is no term
    # of the objective: LFM2's configuration has none
    terms = {"moe_aux_loss": (aux, aux_loss_weight),
             "moe_z_loss": (z, z_loss_weight)}
    terms = {name: (var, w) for name, (var, w) in terms.items()
             if var is not None and w}
    loss = layers.sums([ce] + [layers.scale(var, scale=w)
                               for var, w in terms.values()]) \
        if terms else ce
    if with_optimizer:
        program = loss.block.program
        set_gradient_clip(GradientClipByGlobalNorm(clip_norm),
                          param_list=program.all_parameters())
        # cosine from the first step (the paper's starts where the
        # warm-up ends: 2000 of a million steps apart)
        cosine = layers.cosine_decay(learning_rate * (1.0 - lr_floor), 1,
                                     decay_steps)
        lr = layers.linear_lr_warmup(
            layers.scale(cosine, bias=learning_rate * lr_floor),
            warmup_steps, 0.0, learning_rate)
        opt = optimizer.AdamOptimizer(
            learning_rate=lr, beta1=beta1, beta2=beta2, epsilon=epsilon,
            weight_decay=weight_decay)
        if use_amp:
            from .. import amp

            opt = amp.decorate(opt)
        opt.minimize(loss)
        from ..observe.metrics import track_scalars

        track_scalars(program, ce_loss=ce,
                      **{name: var for name, (var, _) in terms.items()})
    return dict(model, loss=loss)
