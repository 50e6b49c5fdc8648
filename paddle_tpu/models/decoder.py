"""Decoder-only language model, built from a published `config.json`'s
own keys.

One builder for the decoder family of queue R: its arguments ARE the
catalog's keys (`hidden_size`, `num_hidden_layers`, ...), so a
configuration file is passed through unrenamed and the next decoder
configuration extends this builder instead of forking it.  What it
builds, each chosen by the configuration's own keys (a key whose other
values are not built yet raises):

- operators: causal attention with grouped-query heads and QK-norm
  (flash kernels at d_head 256, 128 and 64), over the whole prefix or
  under a sliding window, its RoPE unscaled or YaRN's, over the whole
  head or its first lanes, by layer type, its query head count the
  model's or the layer's own, its context gated a lane, a head or not;
  the gated short convolution; the gated-delta-rule linear-attention
  mixer (a chunked scan, `ops/pallas/gated_delta.py`) and the same
  rule under a decay a key lane (`ops/pallas/channel_delta.py`); the
  Mamba-1
  state-space mixer (a selective scan, `ops/pallas/selective_scan.py`)
  and the Mamba-2 one (one decay a head over a matrix state, its
  chunked matrix-product form, `ops/pallas/ssd_scan.py`, under a gated
  RMSNorm);
  differential attention (two soft-max maps subtracted) on those flash
  kernels; layers that READ another layer's work (gated memory units
  on one layer's scan output, cross-attention on one layer's keys and
  values); RMSNorm or LayerNorm, projections with or without a bias,
  RoPE or no positions at all; attention under a scale of the
  configuration's own; multipliers on the embedding, on every residual
  branch and under the logits;
  latent attention (`kv_lora_rank` ...: queries, keys and values out
  of low-rank latents, or the queries out of one direct projection, a
  rotary part beside the unrotated one, turned or left as it is made,
  its own flash kernels);
- feed-forward layers: dense SwiGLU; dropless routed SwiGLU experts
  under a soft-max or a sigmoid router with a selection bias, whole or
  as one expert-parallel rank's share; shared experts beside the
  routed ones (`n_shared_experts`);
- heads: the vocabulary head and token cross-entropy, tied or not, over
  every position or under per-position `loss_weights`; a
  multi-token-prediction module (`num_nextn_predict_layers`) that
  re-enters the embedding table and the head;
- inputs: `tokens`, and optionally a SECOND input, the output rows of
  another network built before it (`image_rows`, e.g.
  `models/vision_tower.py`'s), which replace the embedding rows at the
  positions of `media_placeholder_token_id`.

The first two configurations it was written for are OLMoE-1B-7B
(Muennighoff et al. 2024, arXiv:2409.02060) and a hybrid
conv/attention expert model; their blocks are spelt out below.

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

`head_dim` is a key of its own where a configuration has it: the q
and o projections are `num_attention_heads * head_dim` wide and k, v
`num_key_value_heads * head_dim`, whatever `hidden_size` is (absent:
`hidden_size // num_attention_heads`).  `layer_types[i]` =
"sliding_attention" is "full_attention" in which query i reads the
`sliding_window` newest keys of its prefix, i - W < j <= i, its own
included (the flash kernels skip the key blocks behind the window).
`rope_parameters` is one flat group (every layer's) or one group a
layer type; a group's `rope_type` "default" is theta^(-2i/D) and
"yarn" the blend of kept and interpolated frequencies with
`attention_factor` on cos and sin (`ops/decoder.py rope_frequencies`,
on the host; the op takes them as attributes); any other raises.  In
a program that has a window layer the two kinds' attention operators
lower under the name scopes `sliding_attention` / `full_attention`.

`layer_types[i]` = "linear_attention" (the `linear_*` keys; Gated
DeltaNet, Yang, Kautz, Hatamizadeh, arXiv:2412.06464) is a mixer with a
matrix-valued state in place of a cache:

    [q | k | v] = silu(conv(h W_qkv));  z = h W_z;  [b | a] = h W_ba
    q, k = l2norm a head (q x Dk^-1/2);  beta = sigmoid(b)
    g = -exp(A_log) softplus(a + dt_bias)
    S_t = exp(g_t) S_{t-1} + k_t (beta_t (v_t - exp(g_t) S_{t-1}^T k_t))^T
    out = (rms_norm(S_t^T q_t) * w * silu(z)) W_out        (a head)

`linear_num_key_heads` key heads of `linear_key_head_dim` serve
`linear_num_value_heads` value heads of `linear_value_head_dim` (value
head h reads key head h // ratio); the convolution is causal,
depthwise, `linear_conv_kernel_dim` taps (`layers.short_conv(
activation="silu")`); the recurrence is ONE op, `gated_delta_rule`,
whose sequential part is a Pallas kernel at heads of 128 x 128.  Its
ops lower under the `linear_attention` name scope.

`layer_types[i]` = "channel_delta_attention" (`linear_attn_config`:
`num_heads` H heads of `head_dim` D, `short_conv_kernel_size` taps; Kimi
Delta Attention, Kimi Team, arXiv:2510.26692) is the same delta rule
under a decay that is each key LANE's own, its gates made by low-rank
pairs of projections (their rank is the head size: the paper's, no
key gives another):

    [q | k | v] = silu(conv(h W_qkv))                  (3 H D wide)
    q, k = l2norm a head (q x D^-1/2);  beta = sigmoid(h W_b)   (a head)
    g = -exp(A_log[head]) softplus((h W_f1) W_f2 + dt_bias)
                                         (float32, one a head AND lane)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    out = (rms_norm(S_t^T q_t) * w * sigmoid((h W_g1) W_g2)) W_out

the convolution as above; the recurrence is ONE op,
`channel_delta_rule` (`ops/pallas/channel_delta.py`: five Pallas
kernels at an even number of heads of 128 x 128), the norm a head under
the SIGMOID of its gate ONE fused op (`layers.rms_norm(gate=,
gate_activation="sigmoid")`).  Its ops lower under the
`channel_delta_attention` name scope.  Beside a looped stack, the
block-diffusion objective, a head count a layer or a prediction module
it raises.

`partial_rotary_factor` f < 1: RoPE turns the first f x head_dim lanes
of each head as a head of that size would, the rest pass through.  A
`rope_parameters` group may carry its own (a layer type's share of the
head: the whole head plainly in the window layers, half of it under
YaRN in the full ones); scaled frequencies are then those of a head of
f x head_dim lanes (`rope_frequencies(rotary_dim, ...)`: the
correction range found with that dim, as transformers'
`_compute_yarn_parameters` does).

`num_attention_heads_per_layer` (one count a layer): layer l's q and o
projections are `H_l * head_dim` wide and its attention runs `H_l`
query heads over the SAME `num_key_value_heads` (query head j reads
key/value head j // (H_l / Hkv); each `H_l` a multiple of it), so the
shape of a layer's projections follows its type.  Built for the causal
attention mixers only: beside latent or linear attention, a looped
stack, a prediction module or the block-diffusion objective it raises.
`mlp_layer_types` (one entry a layer) spells where the dense FFNs are:
only leading "dense" entries before "sparse" ones are built (it sets
`num_dense_layers`).
Three equations more that no key spells, arguments named for the
mechanism: `zero_centered_norm` (every norm of the decoder scales by
1 + w, w from 0, so that decay pulls the scale to 1; the linear mixer's
gated output norm keeps a plain scale from 1), `attention_gate`
("sigmoid": a second projection as wide as q, the context times its
sigmoid before the out projection; the operator then lowers under the
`gated_attention` name scope; "head": a projection hidden -> the
layer's heads, g = sigmoid(h W_g) from the layer's normed input, head
j's whole context times g_j before the out projection, Qiu et al.,
arXiv:2505.06708; under the name scope `attention_head_gate` inside the
layer type's own) and `shared_expert_gate` ("sigmoid": the
shared expert's result times sigmoid(h w), w (D, 1)).
`shared_expert_intermediate_size` gives the shared expert's width where
a configuration names it so (`n_shared_experts` x the routed width
where it counts experts).

`embedding_init_range` (a recipe's): the std of the embedding table
where it is not `initializer_range`, every matrix's.  A table of unit
variance under small matrices keeps the residual stream what the token
is; under one std for all, attention's averaging makes it ONE direction
by the second layer and an untrained router sends every token to the
same experts (PERF.md, PR 38).

Latent attention (`kv_lora_rank`, `q_lora_rank`, `qk_nope_head_dim`,
`qk_rope_head_dim`, `v_head_dim`; DeepSeek-V2, arXiv:2405.04434) takes
the place of `full_attention`'s operator:

    c_q = rms_norm(h W_qa);  q_nope, q_rope = c_q W_qb      (H heads)
    c_kv = rms_norm(h W_kva[:, :kv_lora_rank])
    k_rope = rope(h W_kva[:, kv_lora_rank:])               (ONE head)
    k_nope, v = c_kv W_kvb                                  (H heads)
    s = (q_nope . k_nope + rope(q_rope) . k_rope) / sqrt(Dn + Dr)
    out = causal_softmax(s) v W_o

`rope` is over the rotary lanes only, over pairs (2i, 2i+1) with
`rope_interleave`.  Every piece of W_qb, W_kva and W_kvb is a
projection of its own: all heads' unrotated parts side by side, all
heads' rotary parts side by side, all heads' keys, all heads' values
(a checkpoint's per-head column order is a loader's one-off
permutation), so nothing is sliced at a stride and the kernels
(`ops/pallas/flash_mla.py`) read each where the projection wrote it.
Its ops lower under the `latent_attention` name scope.  `q_lora_rank`
None beside `kv_lora_rank`: no query latent and no norm, [q_nope |
q_rope] = h W_q, ONE direct projection (two column blocks).
`mla_use_nope`: NOTHING is rotated, `rope` above is the identity on
both rotary parts and the layer carries no position of any kind (the
scans beside it do); no `rope_theta` is then read, the same kernels
take the 64 lanes as they are made, and the name scope stays
`latent_attention`.  `num_expert_group` / `topk_group` above 1 (a
top-k over groups of experts) raise.

`q_lora_rank` None WITHOUT `mla_use_nope` is the rotated direct-q
latent attention: the one direct projection's rotary block and the
one rotary key head turn under `rope_theta` (over pairs with
`rope_interleave`), the rest as above.

**A second input** (`image_rows` (N, R, hidden_size), a Variable made by
another builder in the same Program, and `media_placeholder_token_id`):

    x_0[n, t] = image_rows[n, r]   where tokens[n, t] is the r-th
                                   placeholder id of sequence n
              = Emb(tokens[n, t])  elsewhere

ONE op (`layers.image_merge`, name scope `image_merge`); its gradient
reaches the rows' maker (the matching scatter, read as a gather), and
the table takes none at the placeholders.  The decoder then runs on the
merged stream with its ordinary positions 0 .. T-1.  `loss_weights`
(True: feed float32 `loss_weights` (N, max_length)) under
`objective="next_token"`:

    loss = sum_i w_i CE(logits_i, labels_i) / sum_i w_i

(a collator gives weight 0 where the TARGET is a placeholder).  Beside
the block-diffusion objective, a looped stack or a prediction module
both raise; `image_rows` without its id, or the id without rows, raises.

`n_shared_experts` s > 0: beside the routed experts every token also
goes through ONE dense SwiGLU of width s x `moe_intermediate_size`
(name scope `shared_expert`); under `expert_parallel_size` it is whole
on every rank, so over the ranks it counts ONCE and its gradient flows
as ever.

`num_nextn_predict_layers` 1 (DeepSeek-V3, arXiv:2412.19437, 2.2): one
module predicts token i+2 from the main model's final normed state at
i and the embedding of token i+1,

    g = [rms_norm(Emb(t_{i+1})) ; rms_norm(h_i)] W_eh
    logits' = Head(rms_norm(block(g)))      a block of the routed kind

with the main model's own table and head, fed `next_labels` beside
`labels`; its cross-entropy joins the objective x `mtp_loss_weight`.
Its ops lower under the `mtp` name scope.

`sandwich_norm`: a second norm AFTER each sub-layer, before the
residual add, each with a scale of its own:

    x = x + rms_norm(op_i(rms_norm(x)));  x = x + rms_norm(ffn_i(rms_norm(x)))

`total_ut_steps` R > 1 (a looped language model, Zhu et al. 2025,
arXiv:2510.25741): the SAME stack of layers runs R times over shared
weights, and every trip ends in the final norm, the vocabulary head, a
token cross-entropy and a 1-wide exit gate (`exit_gate="sigmoid"`):

    x_0 = Emb(tokens);   trip r = 1..R:   x_r = stack(x_{r-1})
      s_r = rms_norm(x_r);  z_r = s_r W_head;  ce_r = token_ce(z_r)
      lam_r = sigmoid(s_r w_gate + b_gate)
    p_r = lam_r prod_{j<r}(1 - lam_j)  (r < R);   p_R = prod_{j<R}(1 - lam_j)
    loss = mean_tokens[ sum_r p_r ce_r - exit_entropy_weight * H(p) ]

The stack is built ONCE, in one sub-block that a counted
`layers.StaticRNN(trip_count=R)` runs R times (`lax.scan`): the hidden
state is its memory (beside the exit mass that has not left yet),
`ce_r`, the mass leaving at trip r and the logits are its step outputs,
stacked (R, N, T[, V]); the Program is no longer for a larger R.
The weights are created once in the global block and read at every
trip, so their gradients are the sums over the trips.  Head, gate and
cross-entropy run INSIDE the trip, so one head's logits are alive at a
time (fetch `logits` and all R are kept; the training step keeps none).
`recompute="layer"` wraps each layer pass and each trip's head in a
`recompute_scope`: the backward pass keeps their inputs and the
attention kernel's output and logsumexp, and recomputes the rest (the
projections, norms, RoPE and the feed-forward or expert layer).  The
loop lowers under the name scope `ut_loop`, a trip's head under
`ut_loop/exit_head`.  With `total_ut_steps` 1 and no gate the stack is
appended to the main block as ever.  (A serving-time key such as an
early-exit threshold is not the training path's to read.)

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

`objective` = "block_diffusion" (block-diffusion training, Arriola et
al., arXiv:2503.09573; `block_length` B): the program reads ONE
sequence of 2 x `max_length` rows, a document x_0 and after it its
noised copy x_t (`paddle_tpu.data.diffusion.block_diffusion_feeds`
makes both on the host, with the labels x_0 and the weights), row L + p
turning as position p in RoPE (`layers.rope(period=)`).  Every layer
runs all 2 L rows under the block-diffusion mask
(`layers.flash_attention(block_diffusion=B)`: a clean row reads the
clean rows of its own and earlier blocks; a noised row the clean rows
of strictly earlier blocks and the noised rows of its own block), under
the `block_diffusion_attention` name scope.  The final norm and the
head read the noised half only; position i predicts x_0[i], no shift:

    loss = (1 / (N L)) sum_i w_i CE(logits_i, x_0[i])

with w_i = 1 / t_b on a masked position and 0 elsewhere (feed
`loss_weights`).  Only `full_attention` layers are built under it: a
window, a convolution, latent or linear attention, a prediction module
and a loop raise.

`layer_types[i]` = "mamba" (the `mamba_*` sizes; a Mamba-1 state-space
mixer, Gu & Dao, arXiv:2312.00752) is a mixer whose state is a vector
of `mamba_d_state` numbers a channel under a decay of the (channel,
state) pair's own:

    u = silu(conv(h W_u) + b_conv);  z = h W_z          (d_inner wide)
    [r | B | C] = u W_x                  (mamba_dt_rank + 2 mamba_d_state)
    dt = softplus(r W_dt + b_dt);  A = -exp(A_log)
    s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] u_t[c] B_t[n]
    y_t[c] = sum_n C_t[n] s_t[c, n] + D[c] u_t[c]
    out = (y * silu(z)) W_out

d_inner = `mamba_expand` x hidden_size; the convolution is causal,
depthwise, `mamba_d_conv` taps with a bias (`layers.short_conv(
activation="silu", bias_attr=)`); the recurrence is ONE op,
`selective_scan` (`ops/pallas/selective_scan.py`: two Pallas kernels at
16 states, T whole chunks).  Its ops lower under the `state_space` name
scope.

`layer_types[i]` = "mamba" with `mamba_n_heads` / `mamba_d_head` /
`mamba_n_groups` / `mamba_chunk_size` in place of `mamba_dt_rank` (a
Mamba-2 state-space mixer, state-space duality, Dao & Gu,
arXiv:2405.21060; WHICH mixer a "mamba" layer is follows from the keys
a configuration has: both key sets, or neither, raises) is a mixer
whose state is a `mamba_d_head` x `mamba_d_state` MATRIX a head under
ONE decay a (position, head), B and C shared by all the heads of a
group:

    z = h W_z;  xBC = silu(conv(h W_xBC) + b_conv);  dt = h W_dt
    [x | B | C] = xBC       (d_inner + 2 mamba_n_groups mamba_d_state)
    dt = softplus(dt + dt_bias) a head;  A = -exp(A_log)      (heads,)
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] B_t^T
    y_t[h] = S_t[h] C_t + D[h] x_t[h]
    out = (rms_norm(y * silu(z)) * w) W_out

d_inner = `mamba_n_heads` x `mamba_d_head` (= `mamba_expand` x
hidden_size; a share of a mixer's heads raises).  The in projection is
three projections of the one input (a checkpoint's fused matrix splits
[z | xBC | dt] by columns); ONE convolution runs over x, B and C
together (`layers.short_conv(activation="silu", bias_attr=)`, causal,
depthwise, `mamba_d_conv` taps); the step needs no low-rank projection,
only its bias; the recurrence is ONE op, `ssd_scan`, whose input is
xBC WHOLE, as the convolution leaves it (`ops/pallas/ssd_scan.py`: the
chunked matrix-product form, chunks of `mamba_chunk_size`, two Pallas
kernels at heads of 64, 128 states, one group, chunks of 256, which
read x, B and C out of xBC's lanes and write d xBC as one array; a
layer's recompute segment keeps xBC for them, so it convolves once);
the gate comes BEFORE the norm, the norm runs
over all d_inner lanes (`layers.gated_rms_norm`, one fused op, under
the name scope `gated_rms_norm`).  `mamba_n_groups` > 1 raises.  Its
ops lower under the `state_space_duality` name scope (Mamba-1 keeps
`state_space`).

**Four multipliers**, each a key of the configurations that have them
and absent (None) elsewhere, where the step's ops are what they were:
`embedding_multiplier` (x_0 = m E[tokens]), `residual_multiplier`
(x = x + m op(norm(x)), every mixer's and every feed-forward's branch,
a `scale` op before the add), `attention_multiplier` (the soft-max's
scale in place of head_dim^-1/2: `layers.flash_attention(scale=)`; the
operator then lowers under the name scope `full_attention`) and
`logits_scaling` (logits = (x W_head) / m, a `scale` op on the logits,
which XLA fuses into the loss's first pass).

**Values that cross layers** (a decoder-hybrid-decoder, Ren et al.,
arXiv:2507.06607).  Two layers may EXPORT an intermediate that later
layers read, state of the layer loop and nothing else:
`shared_memory_layer` m names the "mamba" layer whose scan output y
(with the D u term, before the gate) every later "gated_memory" layer
reads, position by position:

    out = (silu(h W_1) * y_m) W_2                    (name scope `gated_memory`)

and `shared_kv_layer` a names the attention layer whose keys and values
every later "cross_attention" layer reads: such a layer projects
queries only and attends causally over layer a's K and V (name scope
`cross_attention`).  Under `recompute="layer"` the exported value
leaves its segment as an output and enters each reader's as an input;
the readers' gradients add up into the one cotangent that enters the
exporter's backward pass (autodiff's sum, nothing here).  A reader
before its exporter, or without one, raises at build time.

`attention` = "differential" (Ye et al., arXiv:2410.05258): adjacent
heads pair, query pair j = heads (2j, 2j + 1) reads key/value pair
j // (H / Hkv); with P_c = softmax(q_c k_c^T / sqrt(D) + mask) and
V = [v_1 | v_2] (2 D lanes):

    ctx_j = rms_norm_2D(P_1 V - lam P_2 V) * (1 - lam_init)
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
    lam_init = 0.8 - 0.6 exp(-0.3 l)          l = `layer_indices`[i]

lowered as ONE grouped flash call a layer at heads of 2 D lanes (a
query or key head [x | 0], a value head [v_1 | v_2] for both of its
pair's keys; on a 128 x 128 MXU a 64-lane contraction padded to 128
costs what 64 cost) and one fused `diff_combine` op; the projections'
columns are therefore in the order the call reads them: for key/value
pair i the first heads of its H / Hkv query pairs, then their second
heads (a checkpoint's published order, head 2j + c, is a loader's
one-off permutation).  Under the name scope `differential_attention`
(and the layer type's own inside it).  `attention_bias`: the q, k, v
and out projections carry a bias.  `positions` = "none": no positional
encoding of any kind (the state-space layers carry position); no
`rope_theta` is then read.  `norm` = "layer_norm": every norm of the
stream is a LayerNorm (mean and variance, scale and bias, eps
`layer_norm_eps`).

The training objective is the paper's: token cross-entropy + `aux_loss_weight`
x the load-balancing loss + `z_loss_weight` x the router z-loss (both
averaged over layers), or the exit-weighted loss above, AdamW,
global-norm gradient clipping, linear
warm-up into a cosine decay to `lr_floor` of the peak.
"""

from __future__ import annotations

import contextlib
import functools

import numpy as np

from .. import layers, optimizer
from ..core.program import name_scope, recompute_scope
from ..observe.monitoring import runtime_stats
from ..ops.decoder import rope_frequencies
from ..clip import GradientClipByGlobalNorm, set_gradient_clip
from ..initializer import Normal, Uniform
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
            expert_bias_update_rate=0.0, norm_topk_eps=None,
            kv_lora_rank=None, q_lora_rank=None, qk_nope_head_dim=None,
            qk_rope_head_dim=None, v_head_dim=None, rope_interleave=False,
            rope_scaling=None, n_shared_experts=0,
            num_nextn_predict_layers=0, total_ut_steps=1,
            sandwich_norm=False, exit_gate=None, exit_entropy_weight=0.0,
            recompute=None, head_dim=None, sliding_window=None,
            embedding_init_range=None, partial_rotary_factor=1.0,
            linear_num_key_heads=None, linear_num_value_heads=None,
            linear_key_head_dim=None, linear_value_head_dim=None,
            linear_conv_kernel_dim=None,
            shared_expert_intermediate_size=None, zero_centered_norm=False,
            attention_gate=None, shared_expert_gate=None,
            objective="next_token", block_length=None,
            num_attention_heads_per_layer=None, mlp_layer_types=None,
            norm="rms_norm", layer_norm_eps=None, attention="softmax",
            attention_bias=False, positions="rope", layer_indices=None,
            mamba_d_state=None, mamba_d_conv=None, mamba_expand=None,
            mamba_dt_rank=None, shared_memory_layer=None,
            shared_kv_layer=None, mamba_n_heads=None, mamba_d_head=None,
            mamba_n_groups=None, mamba_chunk_size=None,
            embedding_multiplier=None, residual_multiplier=None,
            attention_multiplier=None, logits_scaling=None,
            linear_attn_config=None, mla_use_nope=False,
            num_expert_group=1, topk_group=1, image_rows=None,
            media_placeholder_token_id=None, loss_weights=False):
    """Append the forward pass to the default program.  Feeds `tokens`
    and `labels`, both (N, max_length) int64 (and `next_labels`, the
    labels' own successors, with a prediction module; float32
    `loss_weights` (N, max_length) where `loss_weights` asks for the
    weighted next-token loss); under
    `objective="block_diffusion"` `tokens` (N, 2 * max_length), `labels`
    and float32 `loss_weights` (N, max_length), `logits` over the noised
    half, `ce` the weighted loss and `masked_share` (1,), the share of
    positions with a weight.  Returns a dict:
    `logits` (N, T, vocab); `ce`, `aux`, `z`, each (1,): the mean token
    cross-entropy, the load-balancing loss and the router z-loss, the
    last two averaged over the layers that route (None where none
    does); `counts` and `experts`, per routed layer the rows per expert
    held and each token's experts (N*T, k), the module's layer last;
    `mtp_logits` and `mtp_ce`, the module's (None without one).  A
    looped model (`total_ut_steps` > 1 or an `exit_gate`) gives `logits`
    (R, N, T, vocab), `ut_ce` and `ut_exit_p`, each (R,): the mean
    cross-entropy and the mean exit mass of each trip, `ut_exit_entropy`
    (1,), and `ce`, the exit-weighted objective."""
    if qk_norm not in (None, "projection", "head"):
        raise NotImplementedError(f"qk_norm {qk_norm!r} is not built")
    if router not in ("softmax", "sigmoid"):
        raise NotImplementedError(f"router {router!r} is not built")
    if head_dim is None and hidden_size % num_attention_heads:
        raise ValueError("hidden_size is not a whole number of heads")
    if num_attention_heads % num_key_value_heads:
        raise ValueError("num_attention_heads is not a multiple of "
                         "num_key_value_heads")
    if conv_bias:
        raise NotImplementedError("conv_bias is not built")
    if rope_scaling:
        raise NotImplementedError(f"rope_scaling {rope_scaling!r} is not "
                                  f"built")
    if num_nextn_predict_layers not in (0, 1):
        raise NotImplementedError(
            f"{num_nextn_predict_layers} chained prediction modules are "
            f"not built")
    if exit_gate not in (None, "sigmoid"):
        raise NotImplementedError(f"exit_gate {exit_gate!r} is not built")
    if recompute not in (None, "layer"):
        raise NotImplementedError(f"recompute {recompute!r} is not built")
    if attention_gate not in (None, "sigmoid", "head"):
        raise NotImplementedError(f"attention_gate {attention_gate!r} is "
                                  f"not built")
    if shared_expert_gate not in (None, "sigmoid"):
        raise NotImplementedError(f"shared_expert_gate "
                                  f"{shared_expert_gate!r} is not built")
    if shared_expert_intermediate_size and n_shared_experts:
        raise ValueError("the shared expert's width is given twice: "
                         "shared_expert_intermediate_size and "
                         "n_shared_experts")
    if not 0.0 < partial_rotary_factor <= 1.0:
        raise ValueError(f"partial_rotary_factor {partial_rotary_factor} is "
                         f"no share of a head")
    if attention_gate and kv_lora_rank is not None:
        raise NotImplementedError("a gate on latent attention is not built")
    if total_ut_steps < 1:
        raise ValueError(f"total_ut_steps {total_ut_steps} is not positive")
    if objective not in ("next_token", "block_diffusion"):
        raise NotImplementedError(f"objective {objective!r} is not built")
    if norm not in ("rms_norm", "layer_norm"):
        raise NotImplementedError(f"norm {norm!r} is not built")
    if attention not in ("softmax", "differential"):
        raise NotImplementedError(f"attention {attention!r} is not built")
    if positions not in ("rope", "none"):
        raise NotImplementedError(f"positions {positions!r} is not built")
    diffusion = objective == "block_diffusion"
    if (image_rows is None) != (media_placeholder_token_id is None):
        raise ValueError("a second input needs both image_rows (its rows) "
                         "and media_placeholder_token_id (where they go)")
    if image_rows is not None or loss_weights:
        # built straight, under the plain next-token objective
        unbuilt = [what for what, asked in [
            ("objective='block_diffusion'", diffusion),
            ("a looped stack", total_ut_steps > 1 or exit_gate),
            ("a prediction module", num_nextn_predict_layers),
            ("an embedding_multiplier", embedding_multiplier is not None
             and image_rows is not None)] if asked]
        if unbuilt:
            raise NotImplementedError(
                "a second input (image_rows) or loss_weights under "
                "objective='next_token' beside " + ", ".join(unbuilt)
                + " is not built")
        if image_rows is not None and (
                len(image_rows.shape) != 3
                or int(image_rows.shape[-1]) != hidden_size):
            raise ValueError(
                f"image_rows {tuple(image_rows.shape)} are not rows of the "
                f"stream's {hidden_size} lanes")
    if not diffusion and block_length is not None:
        raise ValueError("block_length without objective='block_diffusion'")
    if diffusion:
        if not block_length or block_length < 1 \
                or max_length % block_length:
            raise ValueError(
                f"block_length {block_length!r} does not cut max_length "
                f"{max_length} into whole blocks")
        kinds = set(layer_types or ["full_attention"])
        unbuilt = [what for what, asked in [
            (f"layer types {sorted(kinds - {'full_attention'})}",
             kinds != {"full_attention"}),
            ("latent attention", kv_lora_rank is not None),
            ("a prediction module", num_nextn_predict_layers),
            ("a looped stack", total_ut_steps > 1 or exit_gate),
            ("a tied head", tie_word_embeddings)] if asked]
        if unbuilt:
            raise NotImplementedError(
                "under objective='block_diffusion' only full_attention "
                "layers, straight, with an untied head are built; got "
                + ", ".join(unbuilt))
    if mlp_layer_types is not None:
        # a config that spells each layer's FFN: the dense ones lead
        dense = list(mlp_layer_types).count("dense")
        if list(mlp_layer_types) != (["dense"] * dense + ["sparse"] * (
                num_hidden_layers - dense)):
            raise NotImplementedError(
                f"mlp_layer_types {list(mlp_layer_types)}: only leading "
                f"'dense' layers before 'sparse' ones, one entry a layer, "
                f"are built")
        if num_dense_layers not in (0, dense):
            raise ValueError(f"num_dense_layers {num_dense_layers} beside "
                             f"{dense} leading dense mlp_layer_types")
        num_dense_layers = dense
    loops = total_ut_steps > 1 or exit_gate is not None
    if loops and exit_gate is None:
        raise ValueError("a stack run several times needs an exit_gate: "
                         "its objective weighs the trips by it")
    if loops and (num_dense_layers < num_hidden_layers
                   or num_nextn_predict_layers or tie_word_embeddings):
        raise NotImplementedError(
            "routed experts, a prediction module or a tied head inside "
            "the loop are not built")
    if num_expert_group != 1 or topk_group != 1:
        raise NotImplementedError(
            f"num_expert_group {num_expert_group} / topk_group {topk_group}: "
            f"a top-k over groups of experts is not built (the top "
            f"num_experts_per_tok are taken over all experts at once)")
    # q_lora_rank None beside kv_lora_rank: the queries are ONE direct
    # projection, no latent and no norm
    latent = (kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim)
    if kv_lora_rank is None:
        if any(v is not None for v in latent + (q_lora_rank,)):
            raise ValueError("latent attention's sizes without kv_lora_rank")
        if mla_use_nope:
            raise ValueError("mla_use_nope without kv_lora_rank: there is "
                             "no latent attention to leave unrotated")
    elif None in latent:
        raise ValueError("latent attention needs kv_lora_rank, "
                         "qk_nope_head_dim, qk_rope_head_dim and v_head_dim "
                         "(q_lora_rank or None beside them)")
    elif num_key_value_heads != num_attention_heads:
        raise ValueError("latent attention has one key and value a query "
                         "head: num_key_value_heads is num_attention_heads")
    eps = norm_eps if rms_norm_eps is None else rms_norm_eps
    if norm == "layer_norm":
        eps = layer_norm_eps if eps is None else eps
    layer_types = list(layer_types or
                       ["full_attention"] * num_hidden_layers)
    if len(layer_types) != num_hidden_layers:
        raise ValueError(f"{len(layer_types)} layer_types for "
                         f"{num_hidden_layers} layers")
    layer_indices = list(layer_indices or range(num_hidden_layers))
    if len(layer_indices) != num_hidden_layers:
        raise ValueError(f"{len(layer_indices)} layer_indices for "
                         f"{num_hidden_layers} layers")
    # which state-space mixer a "mamba" layer is follows from the keys
    # a configuration has: a low-rank step (a vector state a channel) or
    # heads (a matrix state a head under one decay)
    vector_keys = {"mamba_dt_rank": mamba_dt_rank}
    head_keys = {"mamba_n_heads": mamba_n_heads,
                 "mamba_d_head": mamba_d_head,
                 "mamba_n_groups": mamba_n_groups,
                 "mamba_chunk_size": mamba_chunk_size}
    by_head = any(v is not None for v in head_keys.values())
    if by_head and mamba_dt_rank is not None:
        raise ValueError(
            f"a mamba layer is given twice: {sorted(vector_keys)} (a step "
            f"of low rank: a vector state a channel) beside "
            f"{sorted(k for k, v in head_keys.items() if v is not None)} "
            f"(heads: a matrix state a head)")
    if "mamba" in layer_types:
        sizes = dict(mamba_d_state=mamba_d_state, mamba_d_conv=mamba_d_conv,
                     mamba_expand=mamba_expand,
                     **(head_keys if by_head else vector_keys))
        if None in sizes.values():
            raise ValueError(
                f"a mamba layer needs mamba_d_state, mamba_d_conv, "
                f"mamba_expand and either {sorted(vector_keys)} or "
                f"{sorted(head_keys)}; missing "
                f"{sorted(k for k, v in sizes.items() if v is None)}")
        if by_head:
            if mamba_n_groups != 1:
                raise NotImplementedError(
                    f"mamba_n_groups {mamba_n_groups}: several groups of B "
                    f"and C are not built")
            if mamba_n_heads * mamba_d_head != mamba_expand * hidden_size:
                raise NotImplementedError(
                    f"{mamba_n_heads} heads of {mamba_d_head} are not the "
                    f"mixer's {mamba_expand * hidden_size} lanes: a share "
                    f"of a mixer's heads is not built")
            if shared_memory_layer is not None:
                raise NotImplementedError(
                    "a memory unit on a scan of heads is not built")
    # what crosses layers: each reader's exporter is named, is of the
    # kind that makes the value, and comes before it
    for reader, key, at, makes in [
            ("gated_memory", "shared_memory_layer", shared_memory_layer,
             ("mamba",)),
            ("cross_attention", "shared_kv_layer", shared_kv_layer,
             ("full_attention", "sliding_attention"))]:
        first_reader = (layer_types.index(reader) if reader in layer_types
                        else num_hidden_layers)
        if at is None:
            if reader in layer_types:
                raise ValueError(f"a {reader} layer needs {key}: the layer "
                                 f"whose work it reads")
        elif not 0 <= at < num_hidden_layers \
                or layer_types[at] not in makes:
            raise ValueError(f"{key} {at} is no {' / '.join(makes)} layer "
                             f"of {layer_types}")
        elif at > first_reader:
            raise ValueError(f"layer {first_reader} ({reader}) reads layer "
                             f"{at}'s work before it is made")
    differential = attention == "differential"
    crossing = [kind for kind in ("mamba", "gated_memory", "cross_attention")
                if kind in layer_types]
    if differential or crossing or norm != "rms_norm" or attention_bias \
            or positions != "rope":
        # built straight, on the causal attention mixers' path only
        unbuilt = [what for what, asked in [
            ("latent attention", kv_lora_rank is not None),
            ("a looped stack", total_ut_steps > 1 or exit_gate),
            ("objective='block_diffusion'", diffusion),
            ("a prediction module", num_nextn_predict_layers),
            ("num_attention_heads_per_layer",
             num_attention_heads_per_layer is not None),
            ("an attention_gate", attention_gate),
            ("sandwich_norm", sandwich_norm),
            ("zero_centered_norm", zero_centered_norm)] if asked]
        if unbuilt:
            raise NotImplementedError(
                "differential attention, a LayerNorm stream, projection "
                "biases, no positions and the mamba / gated_memory / "
                "cross_attention layers beside " + ", ".join(unbuilt)
                + " are not built")
    if "cross_attention" in layer_types and not differential:
        raise NotImplementedError("a cross_attention layer under "
                                  "attention='softmax' is not built")
    if differential and (num_attention_heads % 2 or num_key_value_heads % 2
                         or positions != "none" or qk_norm is not None):
        raise NotImplementedError(
            "differential attention pairs adjacent heads (even head "
            "counts) and is built without positions and QK-norm")
    windowed = "sliding_attention" in layer_types
    if windowed and not sliding_window:
        raise ValueError("a sliding_attention layer needs sliding_window")
    linear = (linear_num_key_heads, linear_num_value_heads,
              linear_key_head_dim, linear_value_head_dim,
              linear_conv_kernel_dim)
    if "linear_attention" in layer_types:
        if None in linear:
            raise ValueError(
                "a linear_attention layer needs linear_num_key_heads, "
                "linear_num_value_heads, linear_key_head_dim, "
                "linear_value_head_dim and linear_conv_kernel_dim")
        if linear_num_value_heads % linear_num_key_heads:
            raise ValueError("linear_num_value_heads is not a multiple of "
                             "linear_num_key_heads")
    if "channel_delta_attention" in layer_types:
        sizes = ("num_heads", "head_dim", "short_conv_kernel_size")
        if not linear_attn_config or any(
                linear_attn_config.get(key) is None for key in sizes):
            raise ValueError(
                f"a channel_delta_attention layer needs linear_attn_config "
                f"with {', '.join(sizes)}")
        # built straight, under the next-token objective
        unbuilt = [what for what, asked in [
            ("a looped stack", loops),
            ("objective='block_diffusion'", diffusion),
            ("num_attention_heads_per_layer",
             num_attention_heads_per_layer is not None),
            ("a prediction module", num_nextn_predict_layers)] if asked]
        if unbuilt:
            raise NotImplementedError(
                "a channel_delta_attention layer beside "
                + ", ".join(unbuilt) + " is not built")
    if head_dim is None:
        head_dim = hidden_size // num_attention_heads
    heads_of = list(num_attention_heads_per_layer
                    or [num_attention_heads] * num_hidden_layers)
    if len(heads_of) != num_hidden_layers:
        raise ValueError(f"{len(heads_of)} num_attention_heads_per_layer "
                         f"for {num_hidden_layers} layers")
    if any(h < 1 or h % num_key_value_heads for h in heads_of):
        raise ValueError(f"num_attention_heads_per_layer {heads_of}: a "
                         f"layer's heads are not a multiple of "
                         f"num_key_value_heads {num_key_value_heads}")
    if num_attention_heads_per_layer is not None:
        # q and o take the layer's width; the mixers and objectives
        # whose head count means something else are not built with it
        unbuilt = [what for what, asked in [
            ("latent attention", kv_lora_rank is not None),
            ("linear_attention layers", "linear_attention" in layer_types),
            ("a looped stack", loops),
            ("objective='block_diffusion'", diffusion),
            ("a prediction module", num_nextn_predict_layers)] if asked]
        if unbuilt:
            raise NotImplementedError(
                "num_attention_heads_per_layer beside "
                + ", ".join(unbuilt) + " is not built")
    kv_size = num_key_value_heads * head_dim
    # `rope_parameters`: one flat group, or one a layer type (beside
    # which a config may repeat a number of its groups: not read)
    attention_kinds = ("full_attention", "sliding_attention")
    by_kind = rope_parameters and any(
        isinstance(rope_parameters.get(kind), dict)
        for kind in attention_kinds)
    rotary = {}
    for kind in attention_kinds if positions == "rope" else ():
        group = dict((rope_parameters.get(kind) if by_kind
                      else rope_parameters) or {})
        group.setdefault("rope_theta", rope_theta)
        if group["rope_theta"] is None:
            if kind in layer_types and not mla_use_nope:
                raise ValueError("decoder needs rope_theta or "
                                 "rope_parameters")
            continue
        # a layer type's own share of the head, or the model's
        share = group.pop("partial_rotary_factor", partial_rotary_factor)
        rotary_dim = int(head_dim * share)
        if not 0.0 < share <= 1.0 or rotary_dim % 2:
            raise ValueError(f"partial_rotary_factor {share} of a head of "
                             f"{head_dim} is no whole number of pairs")
        rotary[kind] = {"theta": group["rope_theta"]}
        if rotary_dim != head_dim:
            rotary[kind]["rotary_dim"] = rotary_dim
        if group.get("rope_type", "default") != "default":
            # scaled frequencies of a head of `rotary_dim` lanes
            inv_freq, factor = rope_frequencies(rotary_dim, **group)
            rotary[kind].update(inv_freq=inv_freq, attention_factor=factor)
    # (an unrotated latent attention is the only attention there is
    # beside it: nothing reads a rope_theta)
    if eps is None or not rotary and positions == "rope" \
            and not mla_use_nope:
        raise ValueError("decoder needs rms_norm_eps or norm_eps, and "
                         "rope_theta or rope_parameters")
    theta = rotary.get("full_attention", {}).get("theta")
    expert_width = moe_intermediate_size or intermediate_size
    held = None
    if expert_parallel_size != 1:
        held = (expert_parallel_rank * num_experts, num_experts)

    def weight():
        return ParamAttr(initializer=Normal(0.0, initializer_range))

    def proj(x, size, name, bias=False, param_attr=None):
        return layers.fc(x, size=size, num_flatten_dims=2,
                         bias_attr=None if bias else False,
                         param_attr=param_attr or weight(), name=name)

    def rms_norm(x):
        return layers.rms_norm(x, epsilon=eps,
                               zero_centered=zero_centered_norm)

    def layer_norm(x):
        return layers.layer_norm(x, begin_norm_axis=2, epsilon=eps)

    norm = rms_norm if norm == "rms_norm" else layer_norm

    def turn_qk(x, n_head, turn):
        """QK-norm, then RoPE; a norm a head rides in the `rope` op."""
        if turn is None:        # no positions of any kind
            if qk_norm == "head":
                raise NotImplementedError(
                    "qk_norm='head' without positions is not built")
            return x if qk_norm is None else norm(x)
        if qk_norm == "head":
            return layers.rope(x, n_head, norm=True, epsilon=eps,
                               zero_centered=zero_centered_norm, **turn)
        return layers.rope(x if qk_norm is None else norm(x), n_head, **turn)

    def attention(h, kind, heads=num_attention_heads):
        turn = rotary[kind] if positions == "rope" else None
        q_size = heads * head_dim
        scaled = {}
        if attention_multiplier is not None:
            # the soft-max's scale is the configuration's, not d_head^-1/2
            scaled["scale"] = float(attention_multiplier)
            runtime_stats.record_scaled_attention()
        # a program that mixes kinds of layer tells their rows apart
        scope = "gated_attention" if attention_gate == "sigmoid" else kind
        if diffusion:
            # both halves stand at positions 0 .. max_length - 1
            scope = "block_diffusion_attention"
            turn = dict(turn, period=max_length)
        with name_scope(scope) if windowed or attention_gate or diffusion \
                or scaled else contextlib.nullcontext():
            q = turn_qk(proj(h, q_size, "attn_qkv"), heads, turn)
            k = turn_qk(proj(h, kv_size, "attn_qkv"), num_key_value_heads,
                        turn)
            v = proj(h, kv_size, "attn_qkv")
            ctx = layers.flash_attention(
                q, k, v, causal=not diffusion, use_pallas=True,
                layout="nthd", n_head=heads,
                n_kv_head=num_key_value_heads,
                window=sliding_window if kind == "sliding_attention"
                else None, block_diffusion=block_length, **scaled)
            if attention_gate == "sigmoid":
                # a gate a lane of the context, from the layer's input
                ctx = layers.elementwise_mul(ctx, layers.sigmoid(
                    proj(h, q_size, "attn_gate")))
            elif attention_gate == "head":
                # one gate a HEAD: its lanes all take sigmoid(h w_head)
                with name_scope("attention_head_gate"):
                    gate = layers.sigmoid(proj(h, heads, "attn_gate"))
                    ctx = layers.reshape(layers.elementwise_mul(
                        layers.reshape(ctx, [0, 0, heads, head_dim]),
                        layers.unsqueeze(gate, axes=[3])), [0, 0, q_size])
                runtime_stats.record_attention_head_gate()
            return proj(ctx, hidden_size, "attn_out")

    def linear_attention(h):
        """The gated-delta-rule mixer: q, k, v out of ONE projection
        through a short causal convolution and a SiLU, the scan, and an
        output norm a head gated by a fourth projection z."""
        hk, hv = linear_num_key_heads, linear_num_value_heads
        dk, dv = linear_key_head_dim, linear_value_head_dim
        with name_scope("linear_attention"):
            qkv = layers.short_conv(
                proj(h, 2 * hk * dk + hv * dv, "linear_qkvz"),
                linear_conv_kernel_dim, param_attr=weight(),
                activation="silu")
            z = proj(h, hv * dv, "linear_qkvz")
            o = layers.gated_delta_rule(
                qkv, proj(h, 2 * hv, "linear_ba"), hk, hv, dk, dv)
            # this norm's scale starts at 1 whatever the others do
            return proj(layers.rms_norm(o, epsilon=eps, group_size=dv,
                                        gate=z), hidden_size, "linear_out")

    def channel_delta_attention(h):
        """The delta-rule mixer whose decay is a key lane's own: q, k, v
        out of ONE projection through a short causal convolution and a
        SiLU, the decay and the output gate each out of a low-rank pair
        of projections, the scan, and an output norm a head under the
        gate's sigmoid."""
        heads, dim = (linear_attn_config[key]
                      for key in ("num_heads", "head_dim"))
        rank = dim              # of the two low-rank pairs: the head size
        with name_scope("channel_delta_attention"):
            qkv = layers.short_conv(
                proj(h, 3 * heads * dim, "delta_qkv"),
                linear_attn_config["short_conv_kernel_size"],
                param_attr=weight(), activation="silu")
            decay = proj(proj(h, rank, "delta_decay_a"), heads * dim,
                         "delta_decay_b")
            o = layers.channel_delta_rule(
                qkv, decay, proj(h, heads, "delta_beta"), heads, dim, dim)
            z = proj(proj(h, rank, "delta_gate_a"), heads * dim,
                     "delta_gate_b")
            # this norm's scale starts at 1 whatever the others do
            return proj(layers.rms_norm(o, epsilon=eps, group_size=dim,
                                        gate=z, gate_activation="sigmoid"),
                        hidden_size, "delta_out")

    def latent_attention(h):
        heads = num_attention_heads

        def rotary(x, n_head):
            if mla_use_nope:        # no positions: the lanes stay as made
                return x
            return layers.rope(x, n_head, theta, interleave=rope_interleave)

        with name_scope("latent_attention"):
            # the queries' latent, or the layer's input itself
            c_q = h if q_lora_rank is None \
                else norm(proj(h, q_lora_rank, "attn_q_a"))
            q_nope = proj(c_q, heads * qk_nope_head_dim, "attn_q_b")
            q_rope = rotary(proj(c_q, heads * qk_rope_head_dim, "attn_q_b"),
                            heads)
            c_kv = norm(proj(h, kv_lora_rank, "attn_kv_a"))
            k_rope = rotary(proj(h, qk_rope_head_dim, "attn_kv_a"), 1)
            k_nope = proj(c_kv, heads * qk_nope_head_dim, "attn_kv_b")
            v = proj(c_kv, heads * v_head_dim, "attn_kv_b")
            ctx = layers.latent_attention(q_nope, q_rope, k_nope, k_rope, v,
                                          heads)
            return proj(ctx, hidden_size, "attn_out")

    # what crosses layers, by what is exported: the exporting mamba
    # layer's scan output, the exporting attention layer's (k, v) as the
    # attention call reads them
    shared = {}

    def heads_of_twice(x, n_head):
        """Heads of D lanes -> heads [x | 0] of 2 D lanes."""
        x = layers.pad(layers.reshape(x, [0, 0, n_head, head_dim]),
                       [0, 0, 0, 0, 0, 0, 0, head_dim])
        return layers.reshape(x, [0, 0, 2 * n_head * head_dim])

    def differential_attention(h, kind, at):
        """Layer `at`: two soft-max maps a head pair, subtracted: ONE
        grouped flash call at heads of 2 D lanes and `diff_combine`.  A
        "cross_attention" layer projects q only and reads `shared`'s
        keys and values; layer `shared_kv_layer` puts its own there."""
        heads, kv_heads = num_attention_heads, num_key_value_heads
        cross = kind == "cross_attention"
        with name_scope("cross_attention" if cross
                        else "differential_attention"), \
                contextlib.nullcontext() if cross else name_scope(kind):
            q = heads_of_twice(proj(h, heads * head_dim, "attn_qkv",
                                    attention_bias), heads)
            if cross:
                k, v = shared["kv"]
            else:
                k = heads_of_twice(proj(h, kv_size, "attn_qkv",
                                        attention_bias), kv_heads)
                # a pair's values, side by side, under both of its keys
                v = layers.reshape(layers.expand(layers.reshape(
                    proj(h, kv_size, "attn_qkv", attention_bias),
                    [0, 0, kv_heads // 2, 1, 2 * head_dim]),
                    [1, 1, 1, 2, 1]), [0, 0, 2 * kv_size])
                if at == shared_kv_layer:
                    shared["kv"] = (k, v)
            ctx = layers.flash_attention(
                q, k, v, causal=True, use_pallas=True, layout="nthd",
                n_head=heads, n_kv_head=kv_heads, scale=head_dim ** -0.5,
                window=sliding_window if kind == "sliding_attention"
                else None)
            with name_scope("diff_combine"):
                ctx = layers.diff_combine(
                    ctx, kv_heads // 2, 2 * head_dim,
                    0.8 - 0.6 * float(np.exp(-0.3 * layer_indices[at])),
                    epsilon=eps)
            runtime_stats.record_cross_layer(differential=1,
                                             kv_reads=int(cross))
            return proj(ctx, hidden_size, "attn_out", attention_bias)

    def state_space_duality(h, at):
        """A Mamba-2 mixer: z, xBC and the step a head out of the input
        (three projections of one input, as `dense_ffn` writes its two;
        a checkpoint's one fused matrix splits [z | xBC | dt] by
        columns), x, B and C TOGETHER through one short causal
        convolution and a SiLU, the scan of heads on xBC as it lies (no
        split: the scan's kernels block the three out of its lanes),
        the gated norm."""
        d_inner = mamba_n_heads * mamba_d_head
        with name_scope("state_space_duality"):
            z = proj(h, d_inner, "ssd_in")
            xbc = layers.short_conv(
                proj(h, d_inner + 2 * mamba_n_groups * mamba_d_state,
                     "ssd_in"), mamba_d_conv, param_attr=weight(),
                activation="silu", bias_attr=True)
            dt = proj(h, mamba_n_heads, "ssd_in")
            y = layers.ssd_scan(xbc, dt, mamba_n_heads, mamba_d_state,
                                n_groups=mamba_n_groups,
                                chunk_size=mamba_chunk_size)
            with name_scope("gated_rms_norm"):
                y = layers.gated_rms_norm(y, z, epsilon=eps)
            return proj(y, hidden_size, "ssd_out")

    def state_space(h, at):
        """Layer `at`, a Mamba-1 mixer: u and z out of the input, u
        through a short causal convolution and a SiLU, the step, B and C
        out of u, the selective scan, the gate; layer
        `shared_memory_layer` puts its scan output into `shared`."""
        d_inner = mamba_expand * hidden_size
        with name_scope("state_space"):
            u = layers.short_conv(
                proj(h, d_inner, "ssm_in"), mamba_d_conv,
                param_attr=weight(), activation="silu", bias_attr=True)
            z = proj(h, d_inner, "ssm_in")
            r, b, c = layers.split(
                proj(u, mamba_dt_rank + 2 * mamba_d_state, "ssm_x"),
                [mamba_dt_rank, mamba_d_state, mamba_d_state], dim=2)
            bound = mamba_dt_rank ** -0.5
            delta = proj(r, d_inner, "ssm_dt", param_attr=ParamAttr(
                initializer=Uniform(-bound, bound)))
            y = layers.selective_scan(u, delta, b, c)
            if at == shared_memory_layer:
                shared["memory"] = y
            return proj(layers.swiglu(z, y), hidden_size, "ssm_out")

    def gated_memory(h):
        with name_scope("gated_memory"):
            runtime_stats.record_cross_layer(memory_reads=1)
            m = shared["memory"]
            return proj(layers.swiglu(proj(h, int(m.shape[-1]), "gmu_in"),
                                      m), hidden_size, "gmu_out")

    def conv(h):
        y = layers.short_conv(proj(h, 3 * hidden_size, "conv_in"),
                              conv_L_cache, param_attr=weight())
        return proj(y, hidden_size, "conv_out")

    def dense_ffn(h, width=intermediate_size):
        gate = layers.swiglu(proj(h, width, "ffn_in"),
                             proj(h, width, "ffn_in"))
        return proj(gate, hidden_size, "ffn_out")

    aux_losses, z_losses, counts, experts = [], [], [], []

    def routed_ffn(h):
        y, aux, z, count, chosen = layers.dropless_moe(
            h, num_experts * expert_parallel_size, expert_width,
            num_experts_per_tok, norm_topk_prob=norm_topk_prob,
            param_attr=weight(), experts_held=held,
            # a share without the exchange that sums the ranks' parts
            router_gradient=held is None, routing=router,
            use_expert_bias=use_expert_bias,
            routed_scaling_factor=routed_scaling_factor,
            expert_bias_update_rate=expert_bias_update_rate,
            norm_topk_eps=norm_topk_eps)
        aux_losses.append(aux), z_losses.append(z)
        counts.append(count), experts.append(chosen)
        shared_width = (shared_expert_intermediate_size
                        or n_shared_experts * expert_width)
        if shared_width:
            # whole on every rank: over the ranks it counts once
            with name_scope("shared_expert"):
                shared = dense_ffn(h, shared_width)
                if shared_expert_gate:
                    shared = layers.elementwise_mul(shared, layers.sigmoid(
                        proj(h, 1, "shared_expert_gate")))
                y = layers.elementwise_add(y, shared)
        return y

    # layer type -> mixer; latent attention stands where full attention
    # does and is not built under a window
    mixers = {"conv": conv, "linear_attention": linear_attention,
              "channel_delta_attention": channel_delta_attention,
              "full_attention": functools.partial(attention,
                                                  kind="full_attention"),
              "sliding_attention": functools.partial(
                  attention, kind="sliding_attention")}
    if kv_lora_rank is not None:
        mixers["full_attention"] = latent_attention
        del mixers["sliding_attention"]
    mixers.update(mamba=state_space_duality if by_head else state_space,
                  gated_memory=gated_memory)
    placed = ("mamba",)     # the mixers that are told which layer they are
    if differential:
        placed += attention_kinds + ("cross_attention",)
        for kind in placed[1:]:
            mixers[kind] = functools.partial(differential_attention,
                                             kind=kind)

    def block(x, kind, dense, heads=num_attention_heads, at=None):
        op = mixers.get(kind)
        if op is None:
            raise NotImplementedError(
                f"layer type {kind!r} is not built"
                + (" beside latent attention" if kind == "sliding_attention"
                   else ""))
        if heads != num_attention_heads and kind in attention_kinds:
            op = functools.partial(op, heads=heads)
        if kind in placed:
            op = functools.partial(op, at=at)     # what it exports, by place
        def branch(y):
            """What a sub-layer adds to the stream: normed again under
            `sandwich_norm`, times `residual_multiplier` where given."""
            y = norm(y) if sandwich_norm else y
            if residual_multiplier is None:
                return y
            return layers.scale(y, scale=float(residual_multiplier))

        x = layers.elementwise_add(x, branch(op(norm(x))))
        ffn = dense_ffn if dense else routed_ffn
        return layers.elementwise_add(x, branch(ffn(norm(x))))

    def segment():
        return (recompute_scope() if recompute == "layer"
                else contextlib.nullcontext())

    def stack(x):
        for i, kind in enumerate(layer_types):
            with segment():
                x = block(x, kind, dense=i < num_dense_layers,
                          heads=heads_of[i], at=i)
        return x

    def head(x):
        if logits_scaling is not None:
            # the loss reads (x E^T) / logits_scaling: on the logits
            # themselves (XLA fuses it into the loss's first pass)
            return layers.scale(unscaled_head(x),
                                scale=1.0 / float(logits_scaling))
        return unscaled_head(x)

    def unscaled_head(x):
        if tie_word_embeddings:
            table = x.block.program.global_block().var(embed_name)
            return layers.matmul(x, table, transpose_y=True)
        if not num_nextn_predict_layers:
            return proj(x, vocab_size, "lm_head")
        # the module re-enters the head: declared once, shared by name
        shared = x.block.program.global_block().has_var("lm_head.w")
        return layers.fc(
            x, size=vocab_size, num_flatten_dims=2, bias_attr=False,
            param_attr=ParamAttr(
                name="lm_head.w", initializer=None if shared
                else Normal(0.0, initializer_range)), name="lm_head")

    def token_ce(logits, targets):
        return layers.mean(layers.softmax_with_cross_entropy(
            logits, layers.unsqueeze(targets, axes=[2])))

    def looped(x, trips=total_ut_steps):
        """`stack` run `trips` times over the memory `x` as ONE sub-block,
        each trip ending in the final norm, the head, the token
        cross-entropy and the exit gate; then the exit distribution and the
        exit-weighted objective over the stacked (trips, N, T) outputs.
        The op count does not depend on `trips`."""
        targets = layers.unsqueeze(labels, axes=[2])
        with name_scope("ut_loop"):
            loop = layers.StaticRNN(trip_count=trips)
            with loop.step():
                h = loop.memory(init=x)
                # the exit mass that has not left before this trip
                left = loop.memory(shape=[-1, x.shape[1], 1], batch_ref=x,
                                   init_value=1.0)
                y = stack(h)
                loop.update_memory(h, y)
                with name_scope("exit_head"), segment():
                    s = norm(y)
                    logits = head(s)
                    ce = layers.softmax_with_cross_entropy(logits, targets,
                                                           one_hot_pick=True)
                    gate = layers.fc(s, size=1, num_flatten_dims=2,
                                     param_attr=weight(), name="exit_gate")
                    lam = layers.sigmoid(layers.cast(gate, "float32"))
                    leaving = layers.elementwise_mul(left, lam)
                loop.update_memory(left, layers.elementwise_sub(left, leaving))
                loop.output(ce, leaving, left, logits)
            ce, leaving, left, logits = loop()
        with name_scope("exit_loss"):
            ce = layers.squeeze(ce, axes=[3])               # (R, N, T)
            # p_r = lam_r prod_{j<r}(1 - lam_j), plain products (the chip's
            # float32 log1p is good to 1e-4 only: no log space); the last
            # trip takes all the mass that is left
            last = np.array([0.0] * (trips - 1) + [1.0],
                            np.float32).reshape(trips, 1, 1, 1)
            p = layers.squeeze(layers.elementwise_add(
                layers.elementwise_mul(leaving, layers.assign(1.0 - last)),
                layers.elementwise_mul(left, layers.assign(last))), axes=[3])
            task = layers.reduce_sum(layers.elementwise_mul(p, ce), dim=0)
            # p log p -> 0 as p -> 0
            neg_entropy = layers.reduce_sum(layers.elementwise_mul(
                p, layers.log(layers.clip(p, 1e-30, 1.0))), dim=0)
            objective = layers.mean(layers.elementwise_add(task, layers.scale(
                neg_entropy, scale=float(exit_entropy_weight))))
            return {"logits": logits, "ce": objective, "exit_p": p,
                    "ut_ce": layers.reduce_mean(ce, dim=[1, 2]),
                    "ut_exit_p": layers.reduce_mean(p, dim=[1, 2]),
                    "ut_exit_entropy": layers.scale(layers.mean(neg_entropy),
                                                    scale=-1.0),
                    "total_ut_steps": trips}

    tokens = layers.data(name="tokens", dtype="int64",
                         shape=[2 * max_length if diffusion else max_length])
    labels = layers.data(name="labels", shape=[max_length], dtype="int64")
    embed_name = "tok_embedding.w"
    x = layers.embedding(
        tokens, size=[vocab_size, hidden_size],
        param_attr=ParamAttr(name=embed_name, initializer=Normal(
            0.0, initializer_range if embedding_init_range is None
            else embedding_init_range)))
    if embedding_multiplier is not None:
        x = layers.scale(x, scale=float(embedding_multiplier))
    if image_rows is not None:
        # the second input's rows stand where the placeholder ids are
        with name_scope("image_merge"):
            x = layers.image_merge(x, image_rows, tokens,
                                   media_placeholder_token_id)
    feeds = ["tokens", "labels"]
    if loops:
        return dict(looped(x), aux=None, z=None, counts=[], experts=[],
                    feeds=feeds, mtp_logits=None, mtp_ce=None)
    x = stack(x)
    masked_share = None
    if diffusion:
        # the head reads the noised half; position i predicts x_0[i]
        weights = layers.data(name="loss_weights", shape=[max_length],
                              dtype="float32")
        feeds.append("loss_weights")
        x = layers.slice(x, axes=[1], starts=[max_length],
                         ends=[2 * max_length])
    x = norm(x)
    logits = head(x)
    if diffusion:
        ce = layers.mean(layers.elementwise_mul(
            layers.softmax_with_cross_entropy(
                logits, layers.unsqueeze(labels, axes=[2])),
            layers.unsqueeze(weights, axes=[2])))
        masked_share = layers.mean(layers.sign(weights))
    elif loss_weights:
        # the mean over the weighted positions (a second input's
        # placeholders are no targets: their weight is 0)
        weights = layers.data(name="loss_weights", shape=[max_length],
                              dtype="float32")
        feeds.append("loss_weights")
        ce = layers.elementwise_div(
            layers.reduce_sum(layers.elementwise_mul(
                layers.softmax_with_cross_entropy(
                    logits, layers.unsqueeze(labels, axes=[2])),
                layers.unsqueeze(weights, axes=[2]))),
            layers.reduce_sum(weights))
    else:
        ce = token_ce(logits, labels)
    mtp_logits = mtp_ce = None
    if num_nextn_predict_layers:
        next_labels = layers.data(name="next_labels", shape=[max_length],
                                  dtype="int64")
        feeds.append("next_labels")
        with name_scope("mtp"):
            # token i+1's embedding beside the main model's state at i
            e = layers.embedding(labels, size=[vocab_size, hidden_size],
                                 param_attr=ParamAttr(name=embed_name))
            g = proj(layers.concat([norm(e), norm(x)], axis=2), hidden_size,
                     "mtp_eh")
            g = norm(block(g, layer_types[-1], dense=False))
            mtp_logits = head(g)
            mtp_ce = token_ce(mtp_logits, next_labels)

    def layer_mean(losses):
        if not losses:
            return None
        return layers.scale(layers.sums(losses), scale=1.0 / len(losses))

    return {"logits": logits, "ce": ce, "aux": layer_mean(aux_losses),
            "z": layer_mean(z_losses), "counts": counts,
            "experts": experts, "feeds": feeds, "mtp_logits": mtp_logits,
            "mtp_ce": mtp_ce, "masked_share": masked_share}


@runtime_stats.stage("build_program")
def build_model(max_length, learning_rate=4e-4, beta1=0.9, beta2=0.95,
                epsilon=1e-8, weight_decay=0.1, warmup_steps=2000,
                decay_steps=1_000_000, lr_floor=0.1, clip_norm=1.0,
                aux_loss_weight=0.01, z_loss_weight=0.001,
                mtp_loss_weight=0.3, use_amp=True, with_optimizer=True,
                **architecture):
    """The training Program of `decoder(**architecture)`: loss, AdamW
    under bf16 AMP, clipping and the schedule.  The defaults are the
    OLMoE paper's settings (`mtp_loss_weight` DeepSeek-V3's first);
    `architecture` holds the configuration's own keys and, where its
    equations need them, `qk_norm` / `router` / `norm_topk_eps` /
    `sandwich_norm` / `exit_gate` / `attention_gate` ("sigmoid" a lane,
    "head" a head) / `shared_expert_gate` / `zero_centered_norm`; the
    keys that size a layer by its type, `num_attention_heads_per_layer`,
    `mlp_layer_types` and a `rope_parameters` group's own
    `partial_rotary_factor`; a recipe's `exit_entropy_weight` and
    `recompute` travel with them."""
    model = decoder(max_length=max_length, **architecture)
    ce, aux, z = model["ce"], model["aux"], model["z"]
    # an auxiliary loss with no weight (or no routed layer, or no
    # module) is no term of the objective
    terms = {"moe_aux_loss": (aux, aux_loss_weight),
             "moe_z_loss": (z, z_loss_weight),
             "mtp_loss": (model["mtp_ce"], mtp_loss_weight)}
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
        if model.get("masked_share") is not None:
            track_scalars(program, diffusion_loss=ce,
                          masked_share=model["masked_share"])
        trips = model.get("total_ut_steps")
        if trips:
            # whether a further trip buys anything, and where the exit
            # mass goes
            def trip(var, r):
                return layers.slice(var, axes=[0], starts=[r], ends=[r + 1])

            track_scalars(
                program, ut_exit_entropy=model["ut_exit_entropy"],
                **{f"ut_ce_{r + 1}": trip(model["ut_ce"], r)
                   for r in range(trips)},
                **{f"ut_exit_p_{r + 1}": trip(model["ut_exit_p"], r)
                   for r in range(trips)})
    return dict(model, loss=loss)
