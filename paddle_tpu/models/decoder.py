"""Decoder-only language model, built from a published `config.json`'s
own keys.

One builder for the decoder family of queue R: its arguments ARE the
catalog's keys (`hidden_size`, `num_hidden_layers`, ...), so a
configuration file is passed through unrenamed and the next decoder
configuration extends this builder instead of forking it.  What is
here is what OLMoE-1B-7B (Muennighoff et al. 2024, arXiv:2409.02060)
needs; a key whose other values are not built yet raises.

The block, pre-norm:

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
            num_experts_per_tok, norm_topk_prob, rope_theta, rms_norm_eps,
            vocab_size, tie_word_embeddings, max_length,
            initializer_range=0.02):
    """Append the forward pass to the default program.  Feeds `tokens`
    and `labels`, both (N, max_length) int64.  Returns a dict: `logits`
    (N, T, vocab); `ce`, `aux`, `z`, each (1,): the mean token
    cross-entropy, the load-balancing loss and the router z-loss, the
    last two averaged over layers; `counts` and `experts`, per layer
    the rows per expert (E,) and each token's experts (N*T, k)."""
    if num_key_value_heads != num_attention_heads:
        raise NotImplementedError(
            "grouped-query attention (num_key_value_heads < "
            "num_attention_heads) is not built yet")
    if hidden_size % num_attention_heads:
        raise ValueError("hidden_size is not a whole number of heads")

    def weight():
        return ParamAttr(initializer=Normal(0.0, initializer_range))

    def proj(x, size, name):
        return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=False,
                         param_attr=weight(), name=name)

    def norm(x):
        return layers.rms_norm(x, epsilon=rms_norm_eps)

    tokens = layers.data(name="tokens", shape=[max_length], dtype="int64")
    labels = layers.data(name="labels", shape=[max_length], dtype="int64")
    embed = ParamAttr(name="tok_embedding.w",
                      initializer=Normal(0.0, initializer_range))
    x = layers.embedding(tokens, size=[vocab_size, hidden_size],
                         param_attr=embed)
    aux_losses, z_losses, counts, experts = [], [], [], []
    for _ in range(num_hidden_layers):
        h = norm(x)
        q = layers.rope(norm(proj(h, hidden_size, "attn_qkv")),
                        num_attention_heads, rope_theta)
        k = layers.rope(norm(proj(h, hidden_size, "attn_qkv")),
                        num_attention_heads, rope_theta)
        v = proj(h, hidden_size, "attn_qkv")
        ctx = layers.flash_attention(q, k, v, causal=True, use_pallas=True,
                                     layout="nthd",
                                     n_head=num_attention_heads)
        x = layers.elementwise_add(x, proj(ctx, hidden_size, "attn_out"))
        y, aux, z, count, chosen = layers.dropless_moe(
            norm(x), num_experts, intermediate_size, num_experts_per_tok,
            norm_topk_prob=norm_topk_prob, param_attr=weight())
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
        return layers.scale(layers.sums(losses),
                            scale=1.0 / num_hidden_layers)

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
    OLMoE paper's settings."""
    model = decoder(max_length=max_length, **architecture)
    ce, aux, z = model["ce"], model["aux"], model["z"]
    loss = layers.sums([ce, layers.scale(aux, scale=aux_loss_weight),
                        layers.scale(z, scale=z_loss_weight)])
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

        track_scalars(program, ce_loss=ce, moe_aux_loss=aux,
                      moe_z_loss=z)
    return dict(model, loss=loss)
