"""Mixture-of-Experts FFN with expert parallelism (GShard / Switch
Transformer routing).

The reference tree (Fluid 1.2) predates MoE; this op exists because
expert parallelism is a first-class scale axis on TPU meshes (ep in
dp/tp/pp/sp/ep).  TPU-first design, not a port: routing, dispatch and
combine are dense einsums over a static expert-capacity buffer — no
dynamic shapes, no scatter — so GSPMD shards the expert dimension over
the mesh's `ep`/`mp` axis and inserts the all-to-alls itself (the
standard GShard lowering; see PAPERS.md GShard/Switch entries for the
published formulation).

Routing (top-1 "switch" or top-2):
- gate logits (B, E) from X @ GateW; probs = softmax
- per-expert capacity C = ceil(B * top_k / E * capacity_factor);
  tokens beyond an expert's capacity are DROPPED (their combine weight
  is zero and the residual path carries them — the Switch convention);
  top-2 combine weights are the GShard normalization p_i / (p1 + p2)
- position of each token in its expert's buffer = exclusive cumsum of
  the dispatch mask (deterministic, order-preserving)
- dispatch: (B, E, C) one-hot plan; expert_in = dispatchᵀ @ X
- experts: per-expert 2-layer FFN as batched einsums (E in the batch
  dim -> one MXU matmul per projection across ALL experts)
- combine: out = Σ_ec gate_prob * dispatch * expert_out

AuxLoss is the Switch load-balancing loss: E * Σ_e (fraction of tokens
routed to e) * (mean router prob of e); add `aux_weight * AuxLoss` to
the training objective to keep routing balanced.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import first, opt_in, out


def _act(name):
    return {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
            "tanh": jnp.tanh, "identity": lambda v: v,
            None: jax.nn.relu}[name]


@register_op("moe_ffn")
def moe_ffn(ctx, ins, attrs):
    """X (..., D); GateW (D, E); W1 (E, D, H); B1 (E, H); W2 (E, H, D);
    B2 (E, D).  Outputs Out (..., D), AuxLoss (1,), plus router stats
    (Fraction (E,) tokens-per-expert) for observability."""
    x = first(ins, "X")
    gate_w = first(ins, "GateW")
    w1, b1 = first(ins, "W1"), opt_in(ins, "B1")
    w2, b2 = first(ins, "W2"), opt_in(ins, "B2")
    top_k = int(attrs.get("top_k", 1))
    cap_factor = float(attrs.get("capacity_factor", 1.25))
    act = _act(attrs.get("act", "relu"))
    if top_k not in (1, 2):
        raise ValueError(f"moe_ffn: top_k must be 1 or 2, got {top_k}")
    if top_k > gate_w.shape[1]:
        raise ValueError(
            f"moe_ffn: top_k={top_k} needs at least that many experts, "
            f"got E={gate_w.shape[1]} (the second pass would re-route "
            f"to the same expert)")

    lead = x.shape[:-1]
    d = x.shape[-1]
    e = gate_w.shape[1]
    xf = x.reshape(-1, d)
    b = xf.shape[0]

    # GShard GROUPED formulation: tokens split into G groups with
    # per-group capacity.  G=1 reproduces the ungrouped Switch layout;
    # on a mesh with an `ep` axis G = ep so the group dim shards over
    # ep and the dispatch/combine einsums lower to the GShard
    # all-to-alls (pinned by tests/test_moe.py HLO assertion) instead
    # of all-gathering the dispatch tensor.  Capacity is then per
    # GROUP (C = ceil(B/G * k / E * cf)) — the published GShard
    # semantics.
    from ..parallel.mesh import get_exec_context

    ectx = get_exec_context()
    g = 1
    ep_ax = mp_ax = batch_ax = None
    if ectx is not None:
        mesh = ectx.mesh
        if mesh.shape.get("ep", 1) > 1:
            g = mesh.shape["ep"]
            ep_ax = "ep"
            if mesh.shape.get("mp", 1) > 1:
                mp_ax = "mp"
            if mesh.shape.get(ectx.batch_axis, 1) > 1:
                batch_ax = ectx.batch_axis
    if b % g != 0:
        raise ValueError(
            f"moe_ffn on an ep={g} mesh needs the token count ({b}) "
            f"divisible by ep (per-group GShard capacity)")
    bg = b // g
    # C = ceil(B/G * top_k / E * capacity_factor)
    import math

    cap = max(1, int(math.ceil(bg * top_k / e * cap_factor)))

    def wsc(v, *spec):
        if ep_ax is None:
            return v
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.lax.with_sharding_constraint(
            v, NamedSharding(mesh, P(*spec)))

    xg = wsc(xf.reshape(g, bg, d), ep_ax, batch_ax, None)
    logits = jnp.einsum("gbd,de->gbe", xg, gate_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)          # (G, Bg, E)

    combine = jnp.zeros((g, bg, e, cap), xf.dtype)
    dispatch = jnp.zeros((g, bg, e, cap), xf.dtype)
    used = jnp.zeros((g, bg, e), bool)
    fill = jnp.zeros((g, e), jnp.float32)  # slots taken by earlier k's
    for k in range(top_k):
        masked = jnp.where(used, -jnp.inf, logits)
        idx = jnp.argmax(masked, axis=-1)            # (G, Bg)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)
        # deterministic position in the expert buffer (token order
        # WITHIN the group), offset by slots earlier k's already filled
        pos = (jnp.cumsum(onehot, axis=1) - onehot)  # exclusive
        pos = jnp.sum((pos + fill[:, None, :]) * onehot, axis=-1)
        fill = fill + jnp.sum(onehot, axis=1)
        fits = pos < cap                              # (G, Bg)
        gate = jnp.sum(probs * onehot, axis=-1)       # (G, Bg)
        pos_oh = jax.nn.one_hot(
            jnp.where(fits, pos, 0).astype(jnp.int32), cap,
            dtype=jnp.float32)
        # dispatch derives from the ROUTING plan (chosen expert & a
        # fitting slot), not from the gate-weighted combine tensor: a
        # token whose softmax prob underflows to exactly 0.0 still
        # occupies its slot (contributing 0 to the output) instead of
        # silently freeing capacity
        plan_mask = (onehot[..., None] * pos_oh[..., None, :]
                     * fits.astype(jnp.float32)[..., None, None])
        dispatch = dispatch + plan_mask.astype(xf.dtype)
        combine = combine + (plan_mask
                             * gate[..., None, None]).astype(xf.dtype)
        used = used | (onehot > 0)

    if top_k == 2:
        # GShard top-2 normalization: divide by the prob mass of the
        # CHOSEN experts (p1 + p2) so the pair's weights sum to 1; a
        # capacity-dropped choice simply vanishes, leaving the kept
        # expert at p_kept/(p1+p2) — never amplified
        chosen = jnp.sum(probs * used, axis=-1)[..., None, None]
        combine = combine / jnp.maximum(chosen, 1e-9).astype(
            combine.dtype)

    dispatch = wsc(dispatch, ep_ax, batch_ax, None, None)
    combine = wsc(combine, ep_ax, batch_ax, None, None)
    # dispatch all-to-all: (G over ep, ...) -> (E over ep, G, ...)
    expert_in = wsc(jnp.einsum("gbec,gbd->egcd", dispatch, xg),
                    ep_ax, None, None, None)
    h = act(jnp.einsum("egcd,edh->egch", expert_in, w1)
            + (b1[:, None, None, :] if b1 is not None else 0.0))
    h = wsc(h, ep_ax, None, None, mp_ax)
    expert_out = (jnp.einsum("egch,ehd->egcd", h, w2)
                  + (b2[:, None, None, :] if b2 is not None else 0.0))
    expert_out = wsc(expert_out, ep_ax, None, None, None)
    # combine all-to-all: back to (G over ep, Bg, D)
    yf = wsc(jnp.einsum("gbec,egcd->gbd", combine, expert_out),
             ep_ax, batch_ax, None)
    yf = yf.reshape(b, d)

    # Switch load-balancing loss on the top-1 assignment (global stats)
    top1 = jax.nn.one_hot(jnp.argmax(logits, axis=-1), e,
                          dtype=jnp.float32)
    fraction = jnp.mean(top1, axis=(0, 1))           # (E,)
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(fraction * mean_prob)

    return {"Out": [yf.reshape(lead + (d,))],
            "AuxLoss": [aux.reshape(1)],
            "Fraction": [fraction]}
