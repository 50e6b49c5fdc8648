"""OpTest-equivalent harness.

reference: python/paddle/fluid/tests/unittests/op_test.py:132 — per-op
forward check against a reference computation plus analytic-vs-numeric
gradient comparison (get_numeric_gradient:43, check_grad:414).  Here the
analytic grads come from jax AD over the registered op impl; the numeric
side is central finite differences.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpContext, get_op_impl


def run_op(op_type, ins_np, attrs=None, out_slot="Out", n_outs=None):
    """Execute one op impl on numpy inputs.  ins_np: {slot: array or
    [arrays]}.  As ONE compiled function, which is how the executor
    runs an op (inside its jitted step): op by op every primitive of
    the impl compiles by itself, three to four times as long over the
    registry's sweep."""
    impl = get_op_impl(op_type)
    ins = {}
    for slot, v in ins_np.items():
        vs = v if isinstance(v, (list, tuple)) else [v]
        ins[slot] = [jnp.asarray(a) for a in vs]
    attrs = dict(attrs or {})
    res = jax.jit(lambda ins: impl(
        OpContext(jax.random.PRNGKey(0), 0), ins, attrs)[out_slot])(ins)
    if n_outs is None:
        return np.asarray(res[0])
    return [np.asarray(r) for r in res[:n_outs]]


def with_pull_back(fn, ct):
    """`fn`'s result and the pull-back of `ct` through it, as ONE
    compiled function (which is how a step runs an op): op by op an
    XLA lowering or a written-out reference compiles every primitive
    alone.  Returns a function of `fn`'s arguments that gives (result,
    gradient of each argument)."""
    def both(*xs):
        y, vjp = jax.vjp(fn, *xs)
        return (y,) + tuple(vjp(ct))

    return jax.jit(both)


def check_output(op_type, ins_np, expected, attrs=None, out_slot="Out",
                 rtol=1e-5, atol=1e-6):
    got = run_op(op_type, ins_np, attrs, out_slot)
    np.testing.assert_allclose(got, expected, rtol=rtol, atol=atol,
                               err_msg=f"op {op_type} forward mismatch")


def check_grad(op_type, ins_np, grad_slot, attrs=None, out_slot="Out",
               eps=1e-3, max_relative_error=5e-3):
    """Compare jax.grad of sum(op(out_slot)) w.r.t. ins_np[grad_slot]
    against numeric central differences (reference check_grad semantics
    with sum-cotangent)."""
    impl = get_op_impl(op_type)
    attrs = dict(attrs or {})

    base = {s: (v if isinstance(v, (list, tuple)) else [v])
            for s, v in ins_np.items()}

    def f(x):
        ins = {s: [jnp.asarray(a) for a in vs] for s, vs in base.items()}
        ins[grad_slot] = [x] + [jnp.asarray(a)
                                for a in base[grad_slot][1:]]
        ctx = OpContext(jax.random.PRNGKey(0), 0)
        return jnp.sum(impl(ctx, ins, attrs)[out_slot][0])

    x0 = np.asarray(base[grad_slot][0], dtype=np.float64).astype(np.float32)

    # one vmapped evaluation over ALL 2*size perturbed inputs (per-element
    # eager loops retrace the op for every probe and made the
    # registry-wide sweep dominate CI time), compiled together with the
    # analytic gradient: one compile a check, not two
    flat0 = x0.reshape(-1)
    n = flat0.size
    probes = np.tile(flat0, (2 * n, 1))
    idx = np.arange(n)
    probes[idx, idx] += eps
    probes[n + idx, idx] -= eps

    analytic, vals = jax.jit(lambda x, xs: (
        jax.grad(f)(x), jax.vmap(lambda fx: f(fx.reshape(x0.shape)))(xs)))(
        jnp.asarray(x0), jnp.asarray(probes, jnp.float32))
    analytic, vals = np.asarray(analytic), np.asarray(vals, dtype=np.float64)
    numeric = ((vals[:n] - vals[n:]) / (2 * eps)).reshape(x0.shape)

    denom = np.maximum(np.abs(numeric), 1.0)
    rel = np.abs(analytic - numeric) / denom
    assert rel.max() <= max_relative_error, (
        f"op {op_type} grad mismatch: max rel err {rel.max():.4g}\n"
        f"analytic={analytic.reshape(-1)[:5]} numeric={numeric.reshape(-1)[:5]}")
