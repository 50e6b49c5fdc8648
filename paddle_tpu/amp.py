"""Automatic mixed precision (bf16) training.

Capability analog of the reference fp16 path
(reference: paddle/contrib/float16/float16_transpiler.py — a program
rewrite inserting cast ops; python/paddle/fluid/contrib was growing the
same op-list policy).  TPU-native design: instead of rewriting the
program with cast ops, the Executor applies a dtype policy at op dispatch
inside the single jit trace — white-list ops (MXU matmul/conv families)
consume bfloat16, black-list ops (softmax/loss/reductions) are forced to
float32, everything else runs in whichever dtype arrives.  Parameters
stay float32 master copies: the cast happens at the op boundary, so
jax AD accumulates gradients in float32 and optimizer updates are full
precision.  bf16 has the dynamic range of f32, so no loss scaling is
needed (the fp16 transpiler's scale machinery is unnecessary on TPU).

Usage (fluid style)::

    opt = fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
    opt = fluid.amp.decorate(opt)      # returns wrapped optimizer
    opt.minimize(avg_cost)             # marks the program as amp
"""

from __future__ import annotations

from typing import Optional, Set

# Ops whose FLOPs dominate and map onto the MXU: run in bf16.
DEFAULT_WHITE: Set[str] = {
    "mul", "matmul", "conv2d", "conv3d", "depthwise_conv2d",
    "conv2d_transpose", "conv3d_transpose", "flash_attention",
    "sequence_conv", "moe_dropless", "latent_attention",
    "segment_attention",
}

# Numerically sensitive ops: force f32 inputs.
DEFAULT_BLACK: Set[str] = {
    "softmax", "softmax_with_cross_entropy", "cross_entropy",
    "sigmoid_cross_entropy_with_logits", "mean", "reduce_mean",
    "reduce_sum", "sum", "exp", "log", "cos_sim", "kldiv_loss",
}


class AutoMixedPrecisionLists:
    """White/black op-type lists with user overrides (mirrors the list
    policy the reference fp16 transpiler hardcoded)."""

    def __init__(self, custom_white_list=None, custom_black_list=None):
        self.white_list = set(DEFAULT_WHITE) | set(custom_white_list or ())
        self.black_list = set(DEFAULT_BLACK) | set(custom_black_list or ())
        overlap = self.white_list & self.black_list
        if overlap:
            raise ValueError(
                f"ops in both white and black amp lists: {sorted(overlap)}")


class OptimizerWithMixedPrecision:
    """Optimizer wrapper: marks the program as amp at minimize() time.

    The wrapped optimizer is unchanged — master weights are the normal
    f32 params, so every optimizer composes with amp.  With
    `loss_scaling` set (a resilience.LossScaleConfig), minimize() also
    enables the in-step non-finite update guard with dynamic loss
    scaling (resilience/guard.py) — the fp16 transpiler's scale
    machinery, TPU-native.
    """

    def __init__(self, optimizer,
                 amp_lists: Optional[AutoMixedPrecisionLists],
                 loss_scaling=None):
        self._optimizer = optimizer
        self._amp_lists = amp_lists or AutoMixedPrecisionLists()
        self._loss_scaling = loss_scaling

    def __getattr__(self, name):
        return getattr(self._optimizer, name)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        program = loss.block.program
        program._amp_lists = self._amp_lists
        program._bump()
        result = self._optimizer.minimize(
            loss, startup_program=startup_program,
            parameter_list=parameter_list, no_grad_set=no_grad_set)
        if self._loss_scaling is not None:
            # after minimize: the guard must see the full op list
            # (backward marker + update ops are appended by now)
            from .resilience.guard import enable_update_guard

            enable_update_guard(program, loss_scaling=self._loss_scaling)
        return result


def decorate(optimizer, amp_lists: Optional[AutoMixedPrecisionLists] = None,
             use_dynamic_loss_scaling: bool = False,
             init_loss_scaling: float = 2.0 ** 15,
             incr_every_n_steps: int = 1000,
             decr_every_n_nan_or_inf: int = 1,
             incr_ratio: float = 2.0, decr_ratio: float = 0.5):
    """Wrap `optimizer` for bf16 mixed-precision training.

    use_dynamic_loss_scaling: enable the device-side loss-scale
        schedule + non-finite update guard (reference: fluid's
        decorate(init_loss_scaling=..., use_dynamic_loss_scaling=True)
        fp16 API).  bf16 usually needs no scaling (f32 dynamic range) —
        this is the fp16/overflow-hardening opt-in; the update guard it
        brings protects bf16 runs from NaN steps too.
    """
    loss_scaling = None
    if use_dynamic_loss_scaling:
        from .resilience.guard import LossScaleConfig

        loss_scaling = LossScaleConfig(
            init_loss_scaling=init_loss_scaling,
            incr_every_n_steps=incr_every_n_steps,
            decr_every_n_nan_or_inf=decr_every_n_nan_or_inf,
            incr_ratio=incr_ratio, decr_ratio=decr_ratio)
    return OptimizerWithMixedPrecision(optimizer, amp_lists,
                                       loss_scaling=loss_scaling)


def cast_ins_for_op(op_type: str, ins, amp_lists: AutoMixedPrecisionLists):
    """Apply the dtype policy to one op's input slots (called from the
    executor's trace loop)."""
    import jax.numpy as jnp

    if op_type in amp_lists.white_list:
        src, dst = jnp.float32, jnp.bfloat16
    elif op_type in amp_lists.black_list:
        src, dst = jnp.bfloat16, jnp.float32
    else:
        return ins

    def cast(v):
        if hasattr(v, "dtype") and v.dtype == src:
            return v.astype(dst)
        return v

    return {slot: [cast(v) for v in vals] for slot, vals in ins.items()}
