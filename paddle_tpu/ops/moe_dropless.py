"""Dropless routed-expert layer: top-k of E experts, SwiGLU experts,
no capacity and no dropped token.

`ops/moe.py moe_ffn` is the GShard lowering: a `(G, Bg, E, C)`
dispatch plan, tokens over an expert's capacity dropped, top-k 1 or 2.
At 64 experts top-8 that plan cannot be held, and a model trained
dropless (OLMoE, arXiv:2409.02060) is not the same model with a
capacity.  This op routes by sorting instead:

- router: `p = softmax_f32(X @ GateW)` over E; the k largest `p` of a
  token are its experts and (not renormalised unless `norm_topk_prob`)
  their weights;
- the `T*k` (token, expert) pairs are sorted by expert, stably, so
  each expert's rows are contiguous and in token order; rows are
  gathered into that order: `(T*k, D)`, a STATIC shape whatever the
  routing is, only the group sizes are data;
- the three expert matmuls run over the sorted rows as grouped matmuls
  with the per-expert row counts as group sizes
  (`ops/pallas/grouped_matmul.py`: Pallas kernels whose grid walks the
  row tiles group by group, the rows' count being data, so the work is
  the real rows', never `E x` dense and never the buffer's; a width
  that is no multiple of 128 keeps `jax.lax.ragged_dot`, which the TPU
  compiler lowers to a grouped-matmul kernel of its own);
- rows are gathered back to token order and combined with the router
  weights.

The gathers' gradients are written out (`_permute`): every token has
exactly k sorted rows, so the gradient of a gather is another gather
and a sum over k, never a scatter-add.

Outputs besides `Out`: `AuxLoss`, the load-balancing loss `E * sum_e
f_e P_e` with `f_e` the share of the `T*k` assignments that went to
expert e and `P_e` the mean router probability of e (Switch / the
OLMoE paper, as megablocks computes it; differentiable through `P_e`);
`ZLoss`, `mean_t logsumexp(logits_t)^2`; `Counts` (E,), this call's
rows per expert; `Experts` (T, k), each token's experts by falling
weight; and `TokenCountOut` = `TokenCount` + `Counts`, int32
state the step carries on the device (no fetch, no callback;
`observe/routing.py` reads it).

Two things are attributes of this ONE op, not ops of their own (and a
third, `router_gradient`, is the caller's say over the backward pass):

- `routing="sigmoid"`: the scores are `s = sigmoid_f32(X @ GateW)`; a
  token's experts are the k largest of `s + Bias` (`Bias` (E,), state
  that no gradient reaches: it steers the choice and never the
  weight), its weights the unbiased `s` of the chosen, over their sum
  + `norm_topk_eps` (1e-6 absent) with `norm_topk_prob`, times
  `routed_scaling_factor`.  With
  `bias_update_rate` u > 0 the step also balances the load the way the
  bias exists for (Wang et al. 2024, arXiv:2408.15664, no auxiliary
  loss): `BiasOut = Bias + u * sign(mean load - load)` over ALL E
  experts' rows of this call, used from the next step on;
- `experts_held=(first, count)`: the layer holds ONE expert-parallel
  rank's share.  `GateW` stays (D, E) and routes over all E; W1, W3, W2
  are (count, ...), experts first..first+count-1.  The pairs are sorted
  with the held experts first, their rows go through the grouped
  matmuls with the held experts' counts as group sizes, every other
  row comes out of them as zero, and `Out` is the PARTIAL sum: what
  the absent experts would
  have added is left out (the shares of all ranks add up to the whole
  layer: tests/test_expert_share.py).  The weights are normalised over
  all k chosen experts, held or not.  Shapes stay static, T*k rows
  whatever the routing, so a token routed to a held expert is never
  dropped; there is no exchange and nothing stands in for one.
  `Counts` and `TokenCount` are then (count,), the held experts' rows,
  and `OffShareCountOut` = `OffShareCount` (1,) + the rows that went
  to experts not held.  The backward pass is this rank's own part of
  every gradient, the one that reaches `GateW` and `X` through the
  routing weights included: summed over the ranks the parts are the
  whole layer's gradient (tests/test_expert_share.py).  Absent: all
  experts, the op as it was.

  A share's real rows are the HEAD of the sorted order, so its
  sorted-row section (gather, the grouped matmuls, gate, combine)
  runs on a buffer of the rows it got, not of T*k: the section is
  traced at up to three static sizes (`row_buffer_sizes`: 1.5 and 3
  times the expected `T*k*count/E` rows, in whole 512s, and T*k) and
  a `jax.lax.switch` on the device takes the smallest that holds this
  call's held rows.  T*k is always among them: there is still no
  capacity, and a routing that sends every row here costs what it did.
  The backward pass recomputes the section at the size taken and
  differentiates it there (`_switched`), so nothing of sorted-row size
  is kept from the forward pass.  The grouped matmuls are the same
  kernels in every branch, T*k included.  `RowBufferCountOut` =
  `RowBufferCount` (3,) + the one-hot of the size taken.

  What the section MOVES is the buffer's R rows, never T*k (PR 50).
  Tokens -> rows is a gather of R rows (the experts' input; the
  output's gradient for the rows' and, as a dot a row, for the
  weights' gradient, which `back` then places into (T, k): T*k
  scalars out of R + 1).  Rows -> tokens (the combine; the gradient of
  the gather) is `ops/pallas/rows_to_tokens.py`: the R rows in token
  order (`by_token`: one sort of the layer's T*k sorted rows' tokens
  serves every buffer, and carries the pairs' weights along, as the
  sort by expert does) and one kernel that sums each token tile's
  range of them on the MXU, float32 weights and a float32 sum.  No
  (T, k, D) array is built, in any branch.  A shape that kernel does
  not tile (a width that is no
  multiple of 128, tokens or rows in no whole 128s: the tests' toy
  shapes) keeps the composition it replaced, a (T, k, D) gather out of
  the buffer summed over k (`_pairs_rows`); the shape chooses, and
  `runtime_stats.share_rows_kernel` / `_xla` count the sections traced
  each way.

`router_gradient=False` (its own attribute, tied to neither of the
above) makes the routing weights constants of the backward pass:
nothing reaches `GateW`, or `X`, through them; the experts' inputs and
weights get their gradients as ever.  It is for the caller that runs a
share with no exchange to sum the ranks' parts (`models/decoder.py`
under `expert_parallel_size`): one rank's part knows only that ITS
experts help, and applied alone it teaches the router to prefer them,
which no deployment does.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import first, opt_in
from .decoder import silu_gate
from .pallas.grouped_matmul import grouped_matmul, row_visits
from .pallas.rows_to_tokens import (by_token, order_of, rows_to_tokens,
                                    rows_to_tokens_takes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _permute(x, index, back, group):
    """`x[index]`, for an `index` that holds every row of `x` exactly
    `group` times (`group` = 1: a permutation).  `back` lists, for row r
    of `x`, the `group` positions of the result that hold it, so the
    gradient is `g[back]` summed over each group."""
    return x[index]


def _permute_fwd(x, index, back, group):
    return x[index], back


def _permute_bwd(group, back, g):
    gx = g[back]
    if group > 1:
        gx = gx.reshape((-1, group) + g.shape[1:]).sum(axis=1)
    return gx, None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


_ROW_FACTORS = (1.5, 3.0)       # of the rows uniform routing would send
_ROW_TILE = 512
ROW_BUFFER_SIZES = len(_ROW_FACTORS) + 1        # and T*k


def row_buffer_sizes(t, k, e, count):
    """The static row counts a layer that holds `count` of `e` experts
    may run its sorted-row section at, rising, T*k last: 1.5 and 3
    times the rows uniform routing would send it, in whole 512s; a
    size that reaches T*k is T*k."""
    rows = t * k
    return tuple(sorted({
        min(rows, _ROW_TILE * math.ceil(f * rows * count / e / _ROW_TILE))
        for f in _ROW_FACTORS} | {rows}))


def _pairs_rows(ys, back, n):
    """(T, k, D): each pair's row of the R-row `ys`, zero for a pair
    whose row is not below `n`."""
    return jnp.where((back < n)[..., None],
                     ys[jnp.minimum(back, ys.shape[0] - 1)], 0)


@jax.custom_vjp
def _take_head(x, index, back, n):
    """`x[index]` for `index` (R,) = the tokens of the first R sorted
    rows.  `back` (T, k) is each pair's sorted row and only rows below
    `n` <= R count (the caller zeroes the others), so the gradient is
    a gather out of the R rows and a sum over k."""
    return x[index]


def _take_head_fwd(x, index, back, n):
    return x[index], (back, n)


def _take_head_bwd(res, g):
    return _pairs_rows(g, *res).sum(axis=1), None, None, None


_take_head.defvjp(_take_head_fwd, _take_head_bwd)


@jax.custom_vjp
def _combine(ys, weights, back, head, n):
    """`y[t] = sum_j weights[t, j] * ys[back[t, j]]` in float32 over
    the pairs whose sorted row is below `n`; `head` (R,) is the pair
    of each row of `ys`, so the gradient of `ys` is a gather of R rows
    of the output's."""
    yk = _pairs_rows(ys, back, n)
    return jnp.sum(yk.astype(jnp.float32) * weights[..., None], axis=1)


def _combine_fwd(ys, weights, back, head, n):
    return _combine(ys, weights, back, head, n), (ys, weights, back, head, n)


def _combine_bwd(res, g):
    ys, weights, back, head, n = res
    k = weights.shape[1]
    gys = (g[head // k] * weights.reshape(-1)[head][:, None]).astype(ys.dtype)
    yk = _pairs_rows(ys, back, n)
    gw = jnp.sum(g[:, None, :] * yk.astype(jnp.float32), axis=-1)
    return gys, gw, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


@jax.custom_vjp
def _take_rows(x, index, tokens):
    """`_take_head` where the kernel sums: `x[index]` for `index` (R,)
    = the tokens of the buffer's rows, `tokens` their `order_of`.
    The gradient sums a token's rows out of the R rows of `g`."""
    return x[index]


def _take_rows_fwd(x, index, tokens):
    # (a token's count and dtype: an empty array of X's kind)
    return x[index], (tokens, jnp.zeros((x.shape[0], 0), x.dtype))


def _take_rows_bwd(res, g):
    tokens, like = res
    return (rows_to_tokens(g, tokens, like.shape[0], out_dtype=like.dtype),
            None, None)


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def _combine_rows(ys, weights, of_row, back, index, n, tokens):
    """`_combine` where the kernel sums: each row of `ys` times its
    pair's weight, summed a token.  `index` (R,) is the rows' tokens,
    `of_row` (R,) float32 `weights` (T, k) in the rows' order, and
    `tokens` carries it in token order: the op's sort and `by_token`'s
    brought them along, so no R scalars are gathered."""
    return rows_to_tokens(ys, tokens, weights.shape[0], weighted=True)


def _combine_rows_fwd(ys, weights, of_row, back, index, n, tokens):
    return (_combine_rows(ys, weights, of_row, back, index, n, tokens),
            (ys, weights, of_row, back, index, n))


def _combine_rows_bwd(res, g):
    ys, weights, of_row, back, index, n = res
    rows = ys.shape[0]
    # tokens -> rows: ONE gather of R rows of the output's gradient,
    # for the rows' gradient and, a dot a row, for the weights'
    g = g[index]
    gys = (g * of_row[:, None]).astype(ys.dtype)
    dots = jnp.sum(g * ys.astype(jnp.float32), axis=-1)
    # placed into (T, k) by `back`: T x k scalars out of R + 1
    gw = jnp.concatenate([dots, jnp.zeros(1, dots.dtype)])[
        jnp.where(back < n, back, rows)]
    return gys, gw.astype(weights.dtype), None, None, None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _held_rows(rows, xf, w1, w3, w2, weights, order, back, counts, visits,
               sorted_weights, sorted_rows):
    """The sorted-row section of a layer that holds a share, on a
    buffer of `rows` rows (static; at least the held experts' rows):
    (T, D) float32, the partial sum.  `visits`: the grouped matmuls'
    tables over T*k rows, the same for every buffer; `sorted_weights`
    (T*k,): `weights` in the sorted rows' order, constants (the op's
    sort brought them along); `sorted_rows`: the T*k sorted rows
    `by_token`, or None where no buffer of this layer runs the kernel.

    Nothing here is T x k rows long where `rows_to_tokens_takes` the
    shape: tokens -> rows is a gather of `rows` rows, rows -> tokens
    the kernel of `ops/pallas/rows_to_tokens.py` over the rows in token
    order (one sort a layer, `by_token`, serves every buffer; `order_of`
    makes a buffer's table, for the combine and for the gradient of the
    gather).  A shape it does not take (a width that is no multiple of
    128, the tests' toy shapes) keeps the composition: a (T, k, D)
    array gathered out of the buffer and summed over k."""
    from ..observe.monitoring import runtime_stats

    k = weights.shape[1]
    n = jnp.sum(counts)
    head = order[:rows]
    kernel = sorted_rows is not None
    runtime_stats.record_share_rows(kernel)
    # rows past the held experts' belong to no group: a grouped matmul
    # writes them as zeros, forward and backward (the kernels as they
    # store, no pass over (rows, D))
    index = head // k
    if kernel:
        of_row = sorted_weights[:rows]
        tokens = order_of(sorted_rows, rows, xf.shape[0])
        xs = _take_rows(xf, index, tokens)
    else:
        xs = _take_head(xf, index, back, n)
    h = silu_gate(grouped_matmul(xs, w1, counts, visits),
                  grouped_matmul(xs, w3, counts, visits))
    ys = grouped_matmul(h, w2, counts, visits)
    if kernel:
        return _combine_rows(ys, weights, of_row, back, index, n, tokens)
    return _combine(ys, weights, back, head, n)


@functools.lru_cache(maxsize=None)
def _branch(rows):
    """`_held_rows` at `rows` rows and its gradient, as `switch`
    branches.  The same objects for every layer: a program's layers
    have one shape, and `switch` traces a branch it has seen at those
    shapes once, not once a layer."""
    section = functools.partial(_held_rows, rows)

    def grads(g, diff, index):
        return jax.vjp(lambda *d: section(*d, *index), *diff)[1](g)

    return section, grads


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _switched(sizes, taken, diff, index):
    """`_held_rows(sizes[taken], *diff, *index)`, the branch chosen on
    the device.  Differentiating a `switch` would have every branch
    write zeros for the other branches' residuals, T*k rows of them:
    the backward pass keeps the inputs instead and recomputes the
    section at the size taken."""
    return jax.lax.switch(taken, [_branch(r)[0] for r in sizes],
                          *diff, *index)


def _switched_fwd(sizes, taken, diff, index):
    return _switched(sizes, taken, diff, index), (taken, diff, index)


def _switched_bwd(sizes, res, g):
    taken, diff, index = res
    return None, jax.lax.switch(taken, [_branch(r)[1] for r in sizes],
                                g, diff, index), None


_switched.defvjp(_switched_fwd, _switched_bwd)


def route(logits, top_k, norm_topk_prob=False, scaling=1.0):
    """Router probabilities, the chosen experts and their weights from
    float32 `logits` (T, E): (probs (T, E), weights (T, k), experts
    (T, k) int32); the weights times `scaling`."""
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scaling != 1.0:
        weights = weights * scaling
    return probs, weights, experts.astype(jnp.int32)


SIGMOID_NORM_EPS = 1e-6


def route_sigmoid(logits, top_k, bias=None, norm_topk_prob=False,
                  scaling=1.0, norm_eps=SIGMOID_NORM_EPS):
    """The sigmoid router: scores (T, E), weights (T, k), experts
    (T, k) int32.  `bias` (E,) moves the choice only; `norm_eps` is
    what a family adds to the chosen scores' sum before it divides."""
    scores = jax.nn.sigmoid(logits)
    select = scores if bias is None else \
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, experts = jax.lax.top_k(select, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + norm_eps)
    if scaling != 1.0:
        weights = weights * scaling
    return scores, weights, experts.astype(jnp.int32)


def router_losses(logits, probs, counts, top_k):
    """(load-balancing loss, z-loss) of one layer, float32 scalars."""
    t, e = probs.shape
    share = counts.astype(jnp.float32) / (t * top_k)
    aux = e * jnp.sum(share * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return aux, z


@register_op("moe_dropless")
def moe_dropless(ctx, ins, attrs):
    """X (..., D); GateW (D, E); W1 (G, D, H) gate, W3 (G, D, H) up,
    W2 (G, H, D) down, G = E or the `experts_held` count; optional
    Bias (E,) (sigmoid routing), TokenCount (G,) int32, OffShareCount
    (1,) and RowBufferCount (3,) int32 (a share's)."""
    x = first(ins, "X")
    gate_w = first(ins, "GateW")
    w1, w3, w2 = first(ins, "W1"), first(ins, "W3"), first(ins, "W2")
    total = opt_in(ins, "TokenCount")
    off_share = opt_in(ins, "OffShareCount")
    row_buffers = opt_in(ins, "RowBufferCount")
    k = int(attrs.get("top_k", 1))
    e = gate_w.shape[1]
    if not 1 <= k <= e:
        raise ValueError(f"moe_dropless: top_k {k} outside 1..E={e}")
    held = attrs.get("experts_held")
    first_held, groups = (0, e) if held is None else map(int, held)
    if not (0 <= first_held and groups >= 1 and first_held + groups <= e):
        raise ValueError(f"moe_dropless: experts_held {held} outside "
                         f"the {e} experts")
    if w1.shape[0] != groups:
        raise ValueError(f"moe_dropless: weights of {w1.shape[0]} experts "
                         f"for {groups} held")
    if row_buffers is not None and held is None:
        raise ValueError("moe_dropless: RowBufferCount without "
                         "experts_held: only a share chooses a row buffer")

    d = x.shape[-1]
    xf = x.reshape(-1, d)
    t = xf.shape[0]
    logits = jnp.dot(xf, gate_w, preferred_element_type=jnp.float32)
    norm = bool(attrs.get("norm_topk_prob", False))
    routing = attrs.get("routing", "softmax")
    scaling = float(attrs.get("routed_scaling_factor", 1.0))
    if routing == "softmax":
        probs, weights, experts = route(logits, k, norm, scaling)
    elif routing == "sigmoid":
        probs, weights, experts = route_sigmoid(
            logits, k, opt_in(ins, "Bias"), norm, scaling,
            float(attrs.get("norm_topk_eps", SIGMOID_NORM_EPS)))
    else:
        raise ValueError(f"moe_dropless: routing {routing!r} is neither "
                         f"'softmax' nor 'sigmoid'")

    if not attrs.get("router_gradient", True):
        weights = jax.lax.stop_gradient(weights)
    flat = experts.reshape(-1)                       # (T*k,) expert ids
    # held experts sort first, as groups 0..count-1; the rest follow
    key = flat if held is None else (flat - first_held) % e
    if held is None:
        order = jnp.argsort(key, stable=True)        # sorted row -> pair
    else:
        # a share's sort brings the pairs' weights along, for the
        # section's combine: gathering R scalars costs more than this
        _, order, sorted_weights = jax.lax.sort(
            (key, jax.lax.iota(jnp.int32, t * k),
             jax.lax.stop_gradient(weights).reshape(-1)), num_keys=1)
    back = jnp.argsort(order).astype(jnp.int32)      # pair -> sorted row
    counts = jnp.sum(key[:, None] == jnp.arange(groups, dtype=jnp.int32),
                     axis=0, dtype=jnp.int32)       # no scatter

    # what the grouped matmuls' grids walk: once a layer, for the
    # three products, forward and backward, whatever the row buffer
    visits = row_visits(counts, t * k)
    if held is None:
        xs = _permute(xf, (order // k).astype(jnp.int32), back, k)
        h = silu_gate(grouped_matmul(xs, w1, counts, visits),
                      grouped_matmul(xs, w3, counts, visits))
        ys = grouped_matmul(h, w2, counts, visits)   # (T*k, D) sorted
        yk = _permute(ys, back, order.astype(jnp.int32), 1).reshape(t, k, d)
        y = jnp.sum(yk.astype(jnp.float32) * weights[..., None], axis=1)
    else:
        sizes = row_buffer_sizes(t, k, e, groups)
        taken = jnp.sum(jnp.sum(counts) > jnp.asarray(sizes[:-1], jnp.int32),
                        dtype=jnp.int32)
        diff = (xf, w1, w3, w2, weights)
        order = order.astype(jnp.int32)
        # the rows by token, once for whichever buffer is taken (T*k
        # keys; only a shape the kernel takes: every size is then one)
        sorted_rows = None
        if all(rows_to_tokens_takes(r, t, d) for r in sizes):
            sorted_rows = by_token(order // k, jnp.sum(counts), t,
                                   sorted_weights)
        index = (order, back.reshape(t, k), counts, visits, sorted_weights,
                 sorted_rows)
        y = (_held_rows(t * k, *diff, *index) if len(sizes) == 1
             else _switched(sizes, taken, diff, index))

    all_counts = counts if held is None else jnp.sum(
        flat[:, None] == jnp.arange(e, dtype=jnp.int32), axis=0,
        dtype=jnp.int32)
    aux, z = router_losses(logits, probs, all_counts, k)
    outs = {"Out": [y.reshape(x.shape).astype(x.dtype)],
            "AuxLoss": [aux.reshape(1)], "ZLoss": [z.reshape(1)],
            "Counts": [counts], "Experts": [experts]}
    if total is not None:
        outs["TokenCountOut"] = [total + counts]
    rate = float(attrs.get("bias_update_rate", 0.0))
    if rate:
        load = all_counts.astype(jnp.float32)
        outs["BiasOut"] = [first(ins, "Bias")
                           + rate * jnp.sign(jnp.mean(load) - load)]
    if off_share is not None:
        outs["OffShareCountOut"] = [
            off_share + (t * k - jnp.sum(counts)).astype(jnp.int32)]
    if row_buffers is not None:
        outs["RowBufferCountOut"] = [row_buffers + jax.nn.one_hot(
            taken, ROW_BUFFER_SIZES, dtype=jnp.int32)]
    return outs
