"""Optimizer update ops.

Covers the reference optimizer op corpus (SURVEY.md §2.2 "Optimizers";
reference: paddle/fluid/operators/optimizers/*_op.cc — sgd, momentum,
lars_momentum, adam, adamax, adagrad, decayed_adagrad, adadelta, rmsprop,
ftrl, proximal_gd, proximal_adagrad).  Each op consumes Param/Grad plus
accumulator state and emits the updated values; the Executor writes them
back to the persistable scope vars, so the whole update fuses into the
jitted train step.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..core.registry import register_op
from ..core.selected_rows import SparseGrad
from .common import first, opt_in, out


def _lr(ins):
    return first(ins, "LearningRate").reshape(())


@register_op("sgd")
def sgd(ctx, ins, attrs):
    p, g = first(ins, "Param"), first(ins, "Grad")
    if isinstance(g, SparseGrad):
        # SelectedRows path (reference: optimizers/sgd_op.h SelectedRows
        # kernel): scatter-add only the touched rows; duplicate ids sum
        # naturally.
        return {"ParamOut": [p.at[g.ids].add(-_lr(ins) * g.rows)]}
    return {"ParamOut": [p - _lr(ins) * g]}


@register_op("momentum")
def momentum(ctx, ins, attrs):
    p, g, v = first(ins, "Param"), first(ins, "Grad"), first(ins, "Velocity")
    mu = attrs["mu"]
    lr = _lr(ins)
    if isinstance(g, SparseGrad):
        # lazy rows-only update with merged duplicates (reference:
        # optimizers/momentum_op.h SparseMomentumFunctor)
        valid, ids, rows = g.merged()
        v_rows = mu * v[ids] + rows
        if attrs.get("use_nesterov", False):
            p_delta = -(rows + mu * v_rows) * lr
        else:
            p_delta = -lr * v_rows
        keep = valid[:, None]
        v_new = v.at[ids].add(jnp.where(keep, v_rows - v[ids], 0.0))
        p_new = p.at[ids].add(jnp.where(keep, p_delta, 0.0))
        return {"ParamOut": [p_new], "VelocityOut": [v_new]}
    v_new = mu * v + g
    if attrs.get("use_nesterov", False):
        p_new = p - (g + mu * v_new) * lr
    else:
        p_new = p - lr * v_new
    return {"ParamOut": [p_new], "VelocityOut": [v_new]}


@register_op("lars_momentum")
def lars_momentum(ctx, ins, attrs):
    p, g, v = first(ins, "Param"), first(ins, "Grad"), first(ins, "Velocity")
    mu = attrs["mu"]
    lars_coeff = attrs.get("lars_coeff", 0.001)
    lars_wd = attrs.get("lars_weight_decay", 0.0005)
    lr = _lr(ins)
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
    g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
    local_lr = lr * lars_coeff * p_norm / (
        g_norm + lars_wd * p_norm + 1e-12)
    v_new = mu * v + local_lr * (g + lars_wd * p)
    return {"ParamOut": [p - v_new], "VelocityOut": [v_new]}


@register_op("adam")
def adam(ctx, ins, attrs):
    p, g = first(ins, "Param"), first(ins, "Grad")
    m1, m2 = first(ins, "Moment1"), first(ins, "Moment2")
    b1p = first(ins, "Beta1Pow").reshape(())
    b2p = first(ins, "Beta2Pow").reshape(())
    beta1 = attrs.get("beta1", 0.9)
    beta2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    # decoupled weight decay (AdamW, Loshchilov & Hutter 2019): the
    # parameter shrinks by lr * weight_decay of itself, outside the
    # moments; 0 (the default) leaves the trace as it was
    decay = attrs.get("weight_decay", 0.0)
    lr = _lr(ins) * jnp.sqrt(1 - b2p) / (1 - b1p)
    beta_pows = {"Beta1PowOut": [(b1p * beta1).reshape((1,))],
                 "Beta2PowOut": [(b2p * beta2).reshape((1,))]}
    if isinstance(g, SparseGrad):
        # lazy sparse Adam with merged duplicate rows (reference:
        # optimizers/adam_op.h SparseAdamFunctor over merged SelectedRows
        # grad): moments and param update touch only the gradient's rows.
        valid, ids, rows = g.merged()
        m1r = beta1 * m1[ids] + (1 - beta1) * rows
        m2r = beta2 * m2[ids] + (1 - beta2) * jnp.square(rows)
        p_delta = -lr * m1r / (jnp.sqrt(m2r) + eps)
        if decay:
            p_delta = p_delta - _lr(ins) * decay * p[ids]   # touched rows
        keep = valid[:, None]
        m1n = m1.at[ids].add(jnp.where(keep, m1r - m1[ids], 0.0))
        m2n = m2.at[ids].add(jnp.where(keep, m2r - m2[ids], 0.0))
        p_new = p.at[ids].add(jnp.where(keep, p_delta, 0.0))
        return {"ParamOut": [p_new], "Moment1Out": [m1n],
                "Moment2Out": [m2n], **beta_pows}
    m1n = beta1 * m1 + (1 - beta1) * g
    m2n = beta2 * m2 + (1 - beta2) * jnp.square(g)
    p_new = p - lr * m1n / (jnp.sqrt(m2n) + eps)
    if decay:
        p_new = p_new - _lr(ins) * decay * p
    return {
        "ParamOut": [p_new], "Moment1Out": [m1n], "Moment2Out": [m2n],
        **beta_pows,
    }


@register_op("adamax")
def adamax(ctx, ins, attrs):
    p, g = first(ins, "Param"), first(ins, "Grad")
    m, inf = first(ins, "Moment"), first(ins, "InfNorm")
    b1p = first(ins, "Beta1Pow").reshape(())
    beta1 = attrs.get("beta1", 0.9)
    beta2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    lr = _lr(ins) / (1 - b1p)
    m_new = beta1 * m + (1 - beta1) * g
    inf_new = jnp.maximum(beta2 * inf, jnp.abs(g))
    p_new = p - lr * m_new / (inf_new + eps)
    return {"ParamOut": [p_new], "MomentOut": [m_new],
            "InfNormOut": [inf_new]}


@register_op("adagrad")
def adagrad(ctx, ins, attrs):
    p, g, m = first(ins, "Param"), first(ins, "Grad"), first(ins, "Moment")
    eps = attrs.get("epsilon", 1e-6)
    if isinstance(g, SparseGrad):
        # reference: optimizers/adagrad_op.h SparseAdagradFunctor (merged
        # duplicate rows, lazy row updates)
        valid, ids, rows = g.merged()
        m_rows = m[ids] + jnp.square(rows)
        p_delta = -_lr(ins) * rows / (jnp.sqrt(m_rows) + eps)
        keep = valid[:, None]
        m_new = m.at[ids].add(jnp.where(keep, jnp.square(rows), 0.0))
        p_new = p.at[ids].add(jnp.where(keep, p_delta, 0.0))
        return {"ParamOut": [p_new], "MomentOut": [m_new]}
    m_new = m + jnp.square(g)
    p_new = p - _lr(ins) * g / (jnp.sqrt(m_new) + eps)
    return {"ParamOut": [p_new], "MomentOut": [m_new]}


@register_op("decayed_adagrad")
def decayed_adagrad(ctx, ins, attrs):
    p, g, m = first(ins, "Param"), first(ins, "Grad"), first(ins, "Moment")
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    m_new = decay * m + (1 - decay) * jnp.square(g)
    p_new = p - _lr(ins) * g / (jnp.sqrt(m_new) + eps)
    return {"ParamOut": [p_new], "MomentOut": [m_new]}


@register_op("adadelta")
def adadelta(ctx, ins, attrs):
    p, g = first(ins, "Param"), first(ins, "Grad")
    avg_sq_g = first(ins, "AvgSquaredGrad")
    avg_sq_u = first(ins, "AvgSquaredUpdate")
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    g2 = rho * avg_sq_g + (1 - rho) * jnp.square(g)
    update = -jnp.sqrt((avg_sq_u + eps) / (g2 + eps)) * g
    u2 = rho * avg_sq_u + (1 - rho) * jnp.square(update)
    return {"ParamOut": [p + update], "AvgSquaredGradOut": [g2],
            "AvgSquaredUpdateOut": [u2]}


@register_op("rmsprop")
def rmsprop(ctx, ins, attrs):
    p, g = first(ins, "Param"), first(ins, "Grad")
    ms = first(ins, "MeanSquare")
    mom = first(ins, "Moment")
    eps = attrs.get("epsilon", 1e-10)
    decay = attrs.get("decay", 0.9)
    mu = attrs.get("momentum", 0.0)
    lr = _lr(ins)
    if attrs.get("centered", False):
        mg = first(ins, "MeanGrad")
        ms_new = decay * ms + (1 - decay) * jnp.square(g)
        mg_new = decay * mg + (1 - decay) * g
        mom_new = mu * mom + lr * g / jnp.sqrt(
            ms_new - jnp.square(mg_new) + eps)
        return {"ParamOut": [p - mom_new], "MeanSquareOut": [ms_new],
                "MomentOut": [mom_new], "MeanGradOut": [mg_new]}
    ms_new = decay * ms + (1 - decay) * jnp.square(g)
    mom_new = mu * mom + lr * g / jnp.sqrt(ms_new + eps)
    return {"ParamOut": [p - mom_new], "MeanSquareOut": [ms_new],
            "MomentOut": [mom_new]}


@register_op("ftrl")
def ftrl(ctx, ins, attrs):
    p, g = first(ins, "Param"), first(ins, "Grad")
    sq, lin = first(ins, "SquaredAccumulator"), first(ins, "LinearAccumulator")
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    lr_power = attrs.get("lr_power", -0.5)
    lr = _lr(ins)
    new_sq = sq + jnp.square(g)
    if lr_power == -0.5:
        sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq)) / lr
    else:
        sigma = (jnp.power(new_sq, -lr_power) - jnp.power(sq, -lr_power)) / lr
    new_lin = lin + g - sigma * p
    if lr_power == -0.5:
        denom = jnp.sqrt(new_sq) / lr + 2 * l2
    else:
        denom = jnp.power(new_sq, -lr_power) / lr + 2 * l2
    x = l1 * jnp.sign(new_lin) - new_lin
    p_new = jnp.where(jnp.abs(new_lin) > l1, x / denom, 0.0)
    return {"ParamOut": [p_new], "SquaredAccumOut": [new_sq],
            "LinearAccumOut": [new_lin]}


@register_op("proximal_gd")
def proximal_gd(ctx, ins, attrs):
    p, g = first(ins, "Param"), first(ins, "Grad")
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    lr = _lr(ins)
    prox = p - lr * g
    p_new = jnp.sign(prox) * jnp.maximum(
        jnp.abs(prox) - lr * l1, 0.0) / (1.0 + lr * l2)
    return {"ParamOut": [p_new]}


@register_op("proximal_adagrad")
def proximal_adagrad(ctx, ins, attrs):
    p, g, m = first(ins, "Param"), first(ins, "Grad"), first(ins, "Moment")
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    lr = _lr(ins)
    m_new = m + jnp.square(g)
    eff_lr = lr / jnp.sqrt(m_new)
    prox = p - eff_lr * g
    p_new = jnp.sign(prox) * jnp.maximum(
        jnp.abs(prox) - eff_lr * l1, 0.0) / (1.0 + eff_lr * l2)
    return {"ParamOut": [p_new], "MomentOut": [m_new]}


@register_op("average_accumulates")
def average_accumulates(ctx, ins, attrs):
    """Parameter-averaging window accumulators (reference:
    optimizers/average_accumulates_op.cc, driving ModelAverage):

      num_updates += 1;  num_accumulates += 1;  sum_1 += param
      if num_updates % max_acc == 0:  sum_2 += sum_1; sum_1 = 0
      if num_accumulates >= min_window and
         num_accumulates >= min(max_window, num_updates * window_rate):
          sum_3 = sum_1 + sum_2; sum_1 = sum_2 = 0
          old_num_accumulates = num_accumulates; num_accumulates = 0
    """
    p = first(ins, "Param")
    s1 = first(ins, "Sum1")
    s2 = first(ins, "Sum2")
    s3 = first(ins, "Sum3")
    num_acc = first(ins, "NumAccumulates").reshape(())
    old_num = first(ins, "OldNumAccumulates").reshape(())
    num_upd = first(ins, "NumUpdates").reshape(())
    rate = float(attrs.get("average_window", 0.0))
    max_acc = int(attrs.get("max_average_window", 10000))
    min_w = int(attrs.get("min_average_window", 10000))

    num_upd = num_upd + 1
    num_acc = num_acc + 1
    s1 = s1 + p
    roll = (num_upd % max(max_acc, 1)) == 0
    s2 = jnp.where(roll, s2 + s1, s2)
    s1 = jnp.where(roll, jnp.zeros_like(s1), s1)
    window = jnp.minimum(jnp.asarray(float(max_acc)),
                         num_upd.astype(jnp.float32) * rate)
    emit = (num_acc >= min_w) & (num_acc.astype(jnp.float32) >= window)
    s3 = jnp.where(emit, s1 + s2, s3)
    s1 = jnp.where(emit, jnp.zeros_like(s1), s1)
    s2 = jnp.where(emit, jnp.zeros_like(s2), s2)
    old_num = jnp.where(emit, num_acc, old_num)
    num_acc = jnp.where(emit, jnp.zeros_like(num_acc), num_acc)
    return {"Sum1Out": [s1], "Sum2Out": [s2], "Sum3Out": [s3],
            "NumAccumulatesOut": [num_acc.reshape((1,))],
            "OldNumAccumulatesOut": [old_num.reshape((1,))],
            "NumUpdatesOut": [num_upd.reshape((1,))]}


@register_op("ema_accumulate")
def ema_accumulate(ctx, ins, attrs):
    """Exponential moving average of a param (reference: fluid's
    ExponentialMovingAverage builds this from scale/sum ops;
    one fused op here): ema = decay * ema + (1 - decay) * param."""
    p = first(ins, "Param")
    ema = first(ins, "Ema")
    decay = float(attrs.get("decay", 0.999))
    return {"EmaOut": [decay * ema + (1.0 - decay) * p]}
