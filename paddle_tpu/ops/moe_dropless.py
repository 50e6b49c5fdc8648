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
- the three expert matmuls run over the sorted rows as
  `jax.lax.ragged_dot` with the per-expert row counts as group sizes
  (the TPU compiler lowers each to a grouped-matmul kernel of its own:
  `T*k` rows of work, never `E x` dense);
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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import first, opt_in
from .decoder import silu_gate


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


def route(logits, top_k, norm_topk_prob=False):
    """Router probabilities, the chosen experts and their weights from
    float32 `logits` (T, E): (probs (T, E), weights (T, k), experts
    (T, k) int32)."""
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return probs, weights, experts.astype(jnp.int32)


def router_losses(logits, probs, counts, top_k):
    """(load-balancing loss, z-loss) of one layer, float32 scalars."""
    t, e = probs.shape
    share = counts.astype(jnp.float32) / (t * top_k)
    aux = e * jnp.sum(share * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return aux, z


@register_op("moe_dropless")
def moe_dropless(ctx, ins, attrs):
    """X (..., D); GateW (D, E); W1 (E, D, H) gate, W3 (E, D, H) up,
    W2 (E, H, D) down; optional TokenCount (E,) int32."""
    x = first(ins, "X")
    gate_w = first(ins, "GateW")
    w1, w3, w2 = first(ins, "W1"), first(ins, "W3"), first(ins, "W2")
    total = opt_in(ins, "TokenCount")
    k = int(attrs.get("top_k", 1))
    e = gate_w.shape[1]
    if not 1 <= k <= e:
        raise ValueError(f"moe_dropless: top_k {k} outside 1..E={e}")

    d = x.shape[-1]
    xf = x.reshape(-1, d)
    t = xf.shape[0]
    logits = jnp.dot(xf, gate_w, preferred_element_type=jnp.float32)
    probs, weights, experts = route(
        logits, k, bool(attrs.get("norm_topk_prob", False)))

    flat = experts.reshape(-1)                       # (T*k,) expert ids
    order = jnp.argsort(flat, stable=True)           # sorted row -> pair
    back = jnp.argsort(order).astype(jnp.int32)      # pair -> sorted row
    counts = jnp.sum(flat[:, None] == jnp.arange(e, dtype=jnp.int32),
                     axis=0, dtype=jnp.int32)       # no scatter

    xs = _permute(xf, (order // k).astype(jnp.int32), back, k)
    h = silu_gate(jax.lax.ragged_dot(xs, w1, counts),
                  jax.lax.ragged_dot(xs, w3, counts))
    ys = jax.lax.ragged_dot(h, w2, counts)           # (T*k, D) sorted
    yk = _permute(ys, back, order.astype(jnp.int32), 1).reshape(t, k, d)
    y = jnp.sum(yk.astype(jnp.float32) * weights[..., None], axis=1)

    aux, z = router_losses(logits, probs, counts, k)
    outs = {"Out": [y.reshape(x.shape).astype(x.dtype)],
            "AuxLoss": [aux.reshape(1)], "ZLoss": [z.reshape(1)],
            "Counts": [counts], "Experts": [experts]}
    if total is not None:
        outs["TokenCountOut"] = [total + counts]
    return outs
