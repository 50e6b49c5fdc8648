"""Transformer NMT (encoder-decoder), the flagship benchmark model.

reference: benchmark/fluid's Transformer config (machine translation) and
the fluid Transformer implementation pattern (pre/post-process wrappers
around multi-head attention + FFN).  Attention is composed from
matmul/softmax layers — XLA fuses the chain onto the MXU; masks are
additive biases built in-graph from sequence lengths (segment-style
replacement for LoD, SURVEY.md §5.7).  A Pallas flash-attention kernel
(ops/pallas/flash_attention.py) can replace the composed attention via
use_flash=True.
"""

from __future__ import annotations

import numpy as np

from .. import layers, optimizer
from ..param_attr import ParamAttr
from ..initializer import Normal
from ..observe.monitoring import runtime_stats


def multi_head_attention(queries, keys, values, attn_bias, d_key, d_value,
                         d_model, n_head, dropout_rate=0.0,
                         use_flash=False, fused_qkv=False,
                         flash_pallas=None, causal=False,
                         head_major=False):
    if head_major:
        # Head-major end-to-end (ISSUE 8): the attn_qkv projections'
        # (N, T, H*d) head-grouped outputs feed the flash op's
        # layout="nthd" contract DIRECTLY and its (N, T, H*d) output
        # feeds attn_out — the (N,T,H*d)<->(N,H,T,d) transpose
        # round-trip at every kernel boundary (the r05 longctx profile:
        # ~15.9 s copy/transpose vs ~5.0 s kernel) ceases to exist.
        # Layer names are IDENTICAL to the baseline path, so the
        # Megatron column/row ShardingRules and the one-allreduce-per-
        # block property are untouched (asserted in
        # tests/test_head_major.py).
        if keys is None and fused_qkv:
            group = 2 * d_key + d_value
            qkv = layers.fc(queries, size=group * n_head,
                            num_flatten_dims=2, bias_attr=False,
                            name="attn_qkv")
            # head-grouped minor dim: [q_h|k_h|v_h] per head h — view
            # as (N, T, H, group), slice the minor axis, merge back.
            # reshape/slice only; no transpose.
            r = layers.reshape(qkv, shape=[0, 0, n_head, group])
            q = layers.reshape(
                layers.slice(r, axes=[3], starts=[0], ends=[d_key]),
                shape=[0, 0, n_head * d_key])
            k = layers.reshape(
                layers.slice(r, axes=[3], starts=[d_key],
                             ends=[2 * d_key]),
                shape=[0, 0, n_head * d_key])
            v = layers.reshape(
                layers.slice(r, axes=[3], starts=[2 * d_key],
                             ends=[group]),
                shape=[0, 0, n_head * d_value])
        else:
            if keys is None:  # self-attention
                keys, values = queries, queries
            q = layers.fc(queries, size=d_key * n_head,
                          num_flatten_dims=2, bias_attr=False,
                          name="attn_qkv")
            k = layers.fc(keys, size=d_key * n_head, num_flatten_dims=2,
                          bias_attr=False, name="attn_qkv")
            v = layers.fc(values, size=d_value * n_head,
                          num_flatten_dims=2, bias_attr=False,
                          name="attn_qkv")
        # NOTE: like the flash path below, head-major attention has no
        # dropout on the attention weights (the flash op's contract)
        ctx = layers.flash_attention(q, k, v, attn_bias,
                                     scale=d_key ** -0.5,
                                     causal=causal,
                                     use_pallas=flash_pallas,
                                     layout="nthd", n_head=n_head)
        return layers.fc(ctx, size=d_model, num_flatten_dims=2,
                         bias_attr=False, name="attn_out")
    if keys is None and fused_qkv:
        # Megatron-style fused QKV: ONE (D, (2dk+dv)·H) matmul instead
        # of three — a 3× wider MXU tile per layer.  The fused output
        # dim is HEAD-GROUPED ([q_h|k_h|v_h] per head h), so an mp
        # split of the fused dim lands on whole heads whenever mp
        # divides n_head — exactly the unfused column-parallel layout.
        # The reshape below then maps the mp shards onto the H axis and
        # the per-head q/k/v slices are shard-local: one allreduce per
        # attention block is preserved at any mp | n_head.  The layer
        # name keeps the attn_qkv prefix so the column-parallel rule
        # applies unchanged.
        group = 2 * d_key + d_value
        qkv = layers.fc(queries, size=group * n_head,
                        num_flatten_dims=2, bias_attr=False,
                        name="attn_qkv")
        r = layers.reshape(qkv, shape=[0, 0, n_head, group])
        r = layers.transpose(r, perm=[0, 2, 1, 3])  # (N, H, T, group)
        q = layers.slice(r, axes=[3], starts=[0], ends=[d_key])
        k = layers.slice(r, axes=[3], starts=[d_key], ends=[2 * d_key])
        v = layers.slice(r, axes=[3], starts=[2 * d_key],
                         ends=[group])
    else:
        if keys is None:  # self-attention
            keys, values = queries, queries
        # layer names drive the Megatron row/col sharding rules
        # (parallel/strategies.py): attn_qkv_* weights shard
        # column-parallel (output heads over mp), attn_out_*
        # row-parallel (input heads over mp) — one all-reduce per
        # attention block instead of three.
        q = layers.fc(queries, size=d_key * n_head, num_flatten_dims=2,
                      bias_attr=False, name="attn_qkv")
        k = layers.fc(keys, size=d_key * n_head, num_flatten_dims=2,
                      bias_attr=False, name="attn_qkv")
        v = layers.fc(values, size=d_value * n_head, num_flatten_dims=2,
                      bias_attr=False, name="attn_qkv")

        def split_heads(x, d):
            # (N, T, H*d) -> (N, H, T, d)
            rr = layers.reshape(x, shape=[0, 0, n_head, d])
            return layers.transpose(rr, perm=[0, 2, 1, 3])

        q = split_heads(q, d_key)
        k = split_heads(k, d_key)
        v = split_heads(v, d_value)

    if use_flash:
        # flash_pallas=True routes through the tiled Pallas kernel
        # (ops/pallas/flash_attention.py); default None/False keeps the
        # XLA composition inside the op — the historically-benched path.
        # causal=True (decoder self-attn under flash) uses the op's
        # in-kernel causal masking with a key-padding-only bias, the
        # form the Pallas kernel supports natively.
        ctx = layers.flash_attention(q, k, v, attn_bias,
                                     scale=d_key ** -0.5,
                                     causal=causal,
                                     use_pallas=flash_pallas)
    else:
        product = layers.matmul(q, k, transpose_y=True,
                                alpha=d_key ** -0.5)
        if attn_bias is not None:
            product = layers.elementwise_add(product, attn_bias)
        weights = layers.softmax(product)
        if dropout_rate:
            weights = layers.dropout(weights, dropout_prob=dropout_rate,
                                     dropout_implementation="upscale_in_train")
        ctx = layers.matmul(weights, v)

    ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = layers.reshape(ctx, shape=[0, 0, n_head * d_value])
    return layers.fc(ctx, size=d_model, num_flatten_dims=2,
                     bias_attr=False, name="attn_out")


def positionwise_feed_forward(x, d_inner, d_model, act="relu"):
    # ffn_in column-parallel, ffn_out row-parallel (classic Megatron MLP)
    hidden = layers.fc(x, size=d_inner, num_flatten_dims=2, act=act,
                       name="ffn_in")
    return layers.fc(hidden, size=d_model, num_flatten_dims=2,
                     name="ffn_out")


def pre_post_process(prev_out, out, process_cmd, dropout_rate=0.0):
    """'a' residual-add, 'n' layer-norm, 'd' dropout (reference
    pre_process_layer/post_process_layer convention)."""
    for cmd in process_cmd:
        if cmd == "a":
            out = layers.elementwise_add(out, prev_out) \
                if prev_out is not None else out
        elif cmd == "n":
            out = layers.layer_norm(out, begin_norm_axis=len(out.shape) - 1)
        elif cmd == "d":
            if dropout_rate:
                out = layers.dropout(
                    out, dropout_prob=dropout_rate,
                    dropout_implementation="upscale_in_train")
    return out


def _ffn_or_moe(x, d_inner, d_model, moe_experts, aux_list):
    """FFN sublayer: dense (default) or a switch-MoE block with the
    expert dim sharded over mp/ep (moe_experts > 0).  Aux load-balance
    losses accumulate into aux_list for the objective."""
    if not moe_experts:
        return positionwise_feed_forward(x, d_inner, d_model)
    out, aux, _frac = layers.switch_moe(x, num_experts=moe_experts,
                                        d_inner=d_inner)
    if aux_list is not None:
        aux_list.append(aux)
    return out


def encoder_layer(x, attn_bias, n_head, d_key, d_value, d_model, d_inner,
                  dropout, use_flash=False, fused_qkv=False,
                  moe_experts=0, aux_list=None, flash_pallas=None,
                  head_major=False):
    attn = multi_head_attention(
        pre_post_process(None, x, "n"), None, None, attn_bias, d_key,
        d_value, d_model, n_head, dropout, use_flash=use_flash,
        fused_qkv=fused_qkv, flash_pallas=flash_pallas,
        head_major=head_major)
    attn = pre_post_process(x, attn, "ad", dropout)
    ff = _ffn_or_moe(pre_post_process(None, attn, "n"), d_inner,
                     d_model, moe_experts, aux_list)
    return pre_post_process(attn, ff, "ad", dropout)


def decoder_layer(x, enc_out, self_bias, cross_bias, n_head, d_key, d_value,
                  d_model, d_inner, dropout, use_flash=False,
                  fused_qkv=False, moe_experts=0, aux_list=None,
                  flash_pallas=None, self_causal=False,
                  flash_cross=False, head_major=False):
    self_attn = multi_head_attention(
        pre_post_process(None, x, "n"), None, None, self_bias, d_key,
        d_value, d_model, n_head, dropout, use_flash=use_flash,
        fused_qkv=fused_qkv, flash_pallas=flash_pallas,
        causal=self_causal, head_major=head_major)
    self_attn = pre_post_process(x, self_attn, "ad", dropout)
    q = pre_post_process(None, self_attn, "n")
    # flash_cross routes CROSS attention through the flash op too
    # (key-padding bias, non-causal) — required at long sequence
    # lengths where the composed path would materialize the
    # (N, H, T, T) weight tensor; default off to keep the historically
    # benched short-sequence program unchanged.  head_major forces it:
    # a composed cross-attention would reintroduce the boundary
    # transposes the head-major layout exists to delete.
    cross = multi_head_attention(q, enc_out, enc_out, cross_bias, d_key,
                                 d_value, d_model, n_head, dropout,
                                 use_flash=flash_cross or head_major,
                                 flash_pallas=(flash_pallas
                                               if flash_cross else None),
                                 head_major=head_major)
    cross = pre_post_process(self_attn, cross, "ad", dropout)
    ff = _ffn_or_moe(pre_post_process(None, cross, "n"), d_inner,
                     d_model, moe_experts, aux_list)
    return pre_post_process(cross, ff, "ad", dropout)


def _fold_moe_aux(avg_cost, moe_aux, weight):
    """objective += weight * sum of per-layer load-balance losses."""
    if not moe_aux:
        return avg_cost
    total = moe_aux[0] if len(moe_aux) == 1 else layers.sums(moe_aux)
    return layers.elementwise_add(
        avg_cost, layers.scale(layers.reduce_sum(total),
                               scale=float(weight)))


def _word_embedding(ids, vocab_size, d_model, name):
    emb = layers.embedding(
        ids, size=[vocab_size, d_model],
        param_attr=ParamAttr(name=name,
                             initializer=Normal(0.0, d_model ** -0.5)))
    return layers.scale(emb, scale=d_model ** 0.5)


def _prepare_input(ids, vocab_size, d_model, max_len, dropout, name):
    emb = _word_embedding(ids, vocab_size, d_model, name)
    emb = layers.add_position_encoding(emb)
    if dropout:
        emb = layers.dropout(emb, dropout_prob=dropout,
                             dropout_implementation="upscale_in_train")
    return emb


def _padding_bias(seq_len, max_len):
    """(N,) lengths → additive attention bias (N, 1, 1, T): 0 valid,
    -1e9 padded."""
    m = layers.sequence_mask(seq_len, maxlen=max_len, dtype="float32")
    bias = layers.scale(m, scale=1e9, bias=-1e9)
    return layers.unsqueeze(layers.unsqueeze(bias, axes=[1]), axes=[1])


def _causal_bias(max_len):
    """(1, 1, T, T) additive bias: 0 where col <= row else -1e9."""
    r = layers.range(0, max_len, 1, "float32")
    row = layers.reshape(r, shape=[max_len, 1])
    col = layers.reshape(r, shape=[1, max_len])
    allowed = layers.cast(layers.less_equal(col, row), "float32")
    bias = layers.scale(allowed, scale=1e9, bias=-1e9)
    return layers.unsqueeze(layers.unsqueeze(bias, axes=[0]), axes=[0])


def transformer(src_vocab_size=10000, trg_vocab_size=10000, max_length=64,
                n_layer=6, n_head=8, d_key=64, d_value=64, d_model=512,
                d_inner_hid=2048, dropout=0.1, label_smooth_eps=0.1,
                use_flash=False, use_fused_ce=False, fused_qkv=False,
                moe_experts=0, moe_aux_weight=0.01, flash_pallas=None,
                recompute=False, pipeline=False, flash_cross=False,
                head_major=False):
    """Build the full training graph; returns (avg_cost, logits, feeds).
    moe_experts > 0 swaps every FFN sublayer for a switch-MoE block
    (experts sharded over mp/ep) and folds the load-balance aux losses
    into the objective with weight moe_aux_weight.  recompute=True
    wraps every encoder/decoder layer in fluid.recompute_scope
    (activations rematerialized in the backward — HBM for FLOPs; what a
    layer keeps is its input and, with flash_pallas, each attention
    kernel's output and logsumexp).
    pipeline=True tags the encoder and decoder stacks as two
    fluid.pipeline_scope groups: on a mesh with a "pp" axis each stack
    runs as a GPipe schedule over the pp stages
    (parallel/pipeline_engine.py); on other meshes the tags are inert.
    head_major=True keeps every attention activation in the flash
    kernels' head-major head-grouped layout end-to-end (no transpose at
    any kernel boundary, docs/LAYOUT.md); it requires the flash op
    (use_flash=True) and routes decoder CROSS attention through it
    too."""
    import contextlib

    from ..core.program import (pipeline_scope, pipeline_segment,
                                recompute_scope)

    def stack_scope():
        return pipeline_scope() if pipeline else contextlib.nullcontext()

    def layer_scope():
        ctx = contextlib.ExitStack()
        if pipeline:
            ctx.enter_context(pipeline_segment())
        if recompute:
            ctx.enter_context(recompute_scope())
        return ctx

    if head_major and not use_flash:
        raise ValueError(
            "head_major=True requires use_flash=True: the composed "
            "matmul+softmax attention path would reintroduce the "
            "boundary transposes the head-major layout deletes")

    moe_aux: list = []
    src_word = layers.data(name="src_word", shape=[max_length],
                           dtype="int64")
    trg_word = layers.data(name="trg_word", shape=[max_length],
                           dtype="int64")
    lbl_word = layers.data(name="lbl_word", shape=[max_length],
                           dtype="int64")
    src_len = layers.data(name="src_len", shape=[], dtype="int32")
    trg_len = layers.data(name="trg_len", shape=[], dtype="int32")

    src_bias = _padding_bias(src_len, max_length)
    trg_pad_bias = _padding_bias(trg_len, max_length)
    if use_flash:
        # flash path: decoder self-attn takes the key-padding bias +
        # the op's causal flag (the Pallas kernel's native form; the
        # XLA path inside the op applies the same mask)
        self_bias = trg_pad_bias
        self_causal = True
    else:
        causal = _causal_bias(max_length)
        self_bias = layers.elementwise_add(trg_pad_bias, causal)
        self_causal = False

    # encoder
    enc_in = _prepare_input(src_word, src_vocab_size, d_model, max_length,
                            dropout, "src_word_emb")
    x = enc_in
    with stack_scope():
        for _ in range(n_layer):
            with layer_scope():
                x = encoder_layer(x, src_bias, n_head, d_key, d_value,
                                  d_model, d_inner_hid, dropout,
                                  use_flash=use_flash,
                                  fused_qkv=fused_qkv,
                                  moe_experts=moe_experts,
                                  aux_list=moe_aux,
                                  flash_pallas=flash_pallas,
                                  head_major=head_major)
    enc_out = pre_post_process(None, x, "n")

    # decoder
    dec_in = _prepare_input(trg_word, trg_vocab_size, d_model, max_length,
                            dropout, "trg_word_emb")
    y = dec_in
    with stack_scope():
        for _ in range(n_layer):
            with layer_scope():
                y = decoder_layer(y, enc_out, self_bias, src_bias,
                                  n_head, d_key, d_value, d_model,
                                  d_inner_hid, dropout,
                                  use_flash=use_flash,
                                  fused_qkv=fused_qkv,
                                  moe_experts=moe_experts,
                                  aux_list=moe_aux,
                                  flash_pallas=flash_pallas,
                                  self_causal=self_causal,
                                  flash_cross=flash_cross,
                                  head_major=head_major)
    dec_out = pre_post_process(None, y, "n")

    if use_fused_ce:
        # fused projection+CE (ops/pallas/vocab_ce.py): the (tokens,
        # vocab) logits never hit HBM.  The weight is created directly
        # so the fused op owns the projection; a logits var is still
        # produced for the API (decode paths) via the same weight.
        from ..layer_helper import LayerHelper

        helper = LayerHelper("vocab_proj")
        proj_w = helper.create_parameter(
            None, shape=[d_model, trg_vocab_size], dtype="float32")
        cost_tok = layers.fused_vocab_softmax_ce(
            dec_out, proj_w, lbl_word, epsilon=label_smooth_eps,
            use_pallas=True)
        logits = layers.matmul(dec_out, proj_w)
        tmask = layers.sequence_mask(trg_len, maxlen=max_length,
                                     dtype="float32")
        cost = layers.elementwise_mul(cost_tok, tmask)
        sum_cost = layers.reduce_sum(cost)
        token_num = layers.reduce_sum(tmask)
        avg_cost = layers.elementwise_div(sum_cost, token_num)
        avg_cost = _fold_moe_aux(avg_cost, moe_aux, moe_aux_weight)
        feeds = ["src_word", "trg_word", "lbl_word", "src_len",
                 "trg_len"]
        return avg_cost, logits, feeds

    logits = layers.fc(dec_out, size=trg_vocab_size, num_flatten_dims=2,
                       bias_attr=False)

    if label_smooth_eps:
        # measured on v5e: XLA fuses this one_hot composition into MXU
        # contractions (~152k tok/s) and beats the gather-based fused
        # label_smooth_eps CE (~145k tok/s) — vocab-dim gathers are slow
        # on TPU, dense one_hot contractions are not
        label = layers.label_smooth(
            layers.one_hot(lbl_word, depth=trg_vocab_size),
            epsilon=label_smooth_eps)
        cost = layers.softmax_with_cross_entropy(logits, label,
                                                 soft_label=True)
    else:
        lbl3 = layers.unsqueeze(lbl_word, axes=[2])
        cost = layers.softmax_with_cross_entropy(logits, lbl3)

    # mask padded target positions out of the loss
    tmask = layers.sequence_mask(trg_len, maxlen=max_length,
                                 dtype="float32")
    cost = layers.elementwise_mul(layers.squeeze(cost, axes=[2]), tmask)
    sum_cost = layers.reduce_sum(cost)
    token_num = layers.reduce_sum(tmask)
    avg_cost = layers.elementwise_div(sum_cost, token_num)
    avg_cost = _fold_moe_aux(avg_cost, moe_aux, moe_aux_weight)
    feeds = ["src_word", "trg_word", "lbl_word", "src_len", "trg_len"]
    return avg_cost, logits, feeds


@runtime_stats.stage("build_program")
def build_model(src_vocab_size=10000, trg_vocab_size=10000, max_length=64,
                n_layer=6, n_head=8, d_model=512, d_inner_hid=2048,
                dropout=0.1, learning_rate=2.0, warmup_steps=4000,
                with_optimizer=True, label_smooth_eps=0.1, use_flash=False,
                use_amp=False, use_fused_ce=False, fused_qkv=False,
                moe_experts=0, flash_pallas=None, recompute=False,
                pipeline=False, flash_cross=False, head_major=False):
    avg_cost, logits, feeds = transformer(
        src_vocab_size, trg_vocab_size, max_length, n_layer, n_head,
        d_model // n_head, d_model // n_head, d_model, d_inner_hid,
        dropout, label_smooth_eps, use_flash=use_flash,
        use_fused_ce=use_fused_ce, fused_qkv=fused_qkv,
        moe_experts=moe_experts, flash_pallas=flash_pallas,
        recompute=recompute, pipeline=pipeline,
        flash_cross=flash_cross, head_major=head_major)
    if with_optimizer:
        lr = layers.noam_decay(d_model, warmup_steps)
        lr = layers.elementwise_mul(
            lr, layers.fill_constant([1], "float32", learning_rate))
        opt = optimizer.AdamOptimizer(learning_rate=lr, beta1=0.9,
                                      beta2=0.997, epsilon=1e-9)
        if use_amp:
            from .. import amp as amp_mod

            opt = amp_mod.decorate(opt)
        opt.minimize(avg_cost)
    return {"loss": avg_cost, "logits": logits, "feeds": feeds}


def make_fake_batch(batch_size, max_length=64, src_vocab=10000,
                    trg_vocab=10000, seed=0):
    """Synthetic NMT batch for benchmarking (reference --use_fake_data)."""
    rng = np.random.RandomState(seed)
    src = rng.randint(1, src_vocab, (batch_size, max_length)).astype(np.int64)
    trg = rng.randint(1, trg_vocab, (batch_size, max_length)).astype(np.int64)
    lbl = rng.randint(1, trg_vocab, (batch_size, max_length)).astype(np.int64)
    src_len = np.full((batch_size,), max_length, np.int32)
    trg_len = np.full((batch_size,), max_length, np.int32)
    return {"src_word": src, "trg_word": trg, "lbl_word": lbl,
            "src_len": src_len, "trg_len": trg_len}
