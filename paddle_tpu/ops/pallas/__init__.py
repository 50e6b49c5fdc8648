"""Pallas TPU kernels — the custom-kernel tier.

Analog of the reference's hand-written CUDA kernels and JIT codegen tier
(operators/math/*.cu, operators/jit/ xbyak codegen, SURVEY.md §2.2): ops
whose fusion XLA can't do on its own get tiled Pallas implementations.
"""

import contextlib

# tests/test_pallas_lowering.py exports these kernels with
# jax.export(platforms=["tpu"]) FROM a CPU host to validate the
# Pallas->Mosaic lowering without a chip.  The interpret gate resolves
# from the CURRENT backend at trace time, so without an override the
# export would serialize the interpreter path and the check would be
# vacuous.
_force_mosaic = [False]


@contextlib.contextmanager
def force_mosaic_lowering():
    """Force interpret=False regardless of backend, so a cross-platform
    jax.export actually runs the Mosaic lowering rules."""
    _force_mosaic[0] = True
    try:
        yield
    finally:
        _force_mosaic[0] = False


def interpret() -> bool:
    """Pallas kernels compile only on TPU; on the CPU backend (tests,
    virtual meshes) they run through the Pallas interpreter so the same
    code path is exercised everywhere.  force_mosaic_lowering()
    overrides for cross-platform jax.export TPU-lowering checks."""
    import jax

    if _force_mosaic[0]:
        return False
    return jax.default_backend() != "tpu"


# What a recompute segment keeps besides its inputs: the residuals whose
# cost to recompute is far above what they take to keep, by name.  The
# two an attention kernel's forward rule hands its backward kernel, the
# output and the logsumexp (recomputing them grows with the square of
# the length, or length x window, keeping them with the length), so a
# segment never runs a flash forward kernel a second time only to
# rebuild them; and the chunked delta rule's (I + A)^-1 (`gated_delta.py
# chunk_inverses`: a chain of dependent substitution steps a chunk, 6.6
# ms a layer at 16384 positions for 134 MB), so a segment's backward pass
# runs the chunk-operand forward kernel that READS the inverse and not
# the one that solves for it; and the selective scan's output and the
# states that enter its chunks (`selective_scan.py`: 84 + 10.5 MB a
# layer at 8192 x 5120 for a forward scan of the whole sequence), which
# are all its backward kernel reads besides the operands; and the same
# two of the scalar-a-head scan (`ssd_scan.py`: 67 + 67 MB a layer at
# 8192 positions x 64 heads of 64 x 128 states) with its operand xBC
# (71 MB: the convolution's output, which the scan's kernels read as it
# lies, so a segment that keeps it runs no convolution forward a second
# time only to hand the backward kernel its operand); and the lane-decayed
# delta rule's (I + A)^-1 and P (`channel_delta.py chunk_inverses`: 67 +
# 34 MB a layer at 8192 positions x 32 heads for the column loops of
# every chunk's diagonal sub-blocks and the substitution).  ONE
# mechanism in five places: whoever
# makes such a residual names it through `keep_residuals`, and the
# executor's `jax.checkpoint` saves exactly these names
# (`segment_policy`).  Names without that policy are inert (a `name`
# equation lowers to nothing); a residual nobody names is recomputed.
ATTENTION_RESIDUALS = ("attention_out", "attention_logsumexp")
INVERSE_RESIDUAL = ("gated_delta_inverse",)
SCAN_RESIDUALS = ("selective_scan_out", "selective_scan_states")
SSD_RESIDUALS = ("ssd_scan_out", "ssd_scan_states",
                 "ssd_scan_operand")
CHANNEL_DELTA_RESIDUALS = ("channel_delta_inverse", "channel_delta_scores")
KEPT_RESIDUALS = (ATTENTION_RESIDUALS + INVERSE_RESIDUAL + SCAN_RESIDUALS
                  + SSD_RESIDUALS + CHANNEL_DELTA_RESIDUALS)
_open_segments = [0]


def segment_policy():
    """The `jax.checkpoint` policy of a recompute segment."""
    import jax

    return jax.checkpoint_policies.save_only_these_names(*KEPT_RESIDUALS)


@contextlib.contextmanager
def tracing_segment():
    """Around the ops of a recompute segment WHILE `jax.checkpoint`
    traces them.  That trace runs a `custom_vjp`'s primal function; its
    forward rule is traced later, when the segment is differentiated
    (for a loop's body after the executor has left the loop), which is
    why the attention families' primal functions call their forward
    rules: the call below sees the segment it is in."""
    _open_segments[0] += 1
    try:
        yield
    finally:
        _open_segments[0] -= 1


def keep_residuals(*values, names=ATTENTION_RESIDUALS):
    """Name what a recompute segment is to keep, where it is made (an
    attention kernel's output and logsumexp where its forward rule
    returns them as residuals, unless `names` says otherwise); gives
    the values back.  Inside a segment's trace the call counts
    (`runtime_stats.recompute_kept_residuals` / `_bytes`: a loop's body
    once, as traced)."""
    from jax.ad_checkpoint import checkpoint_name

    if len(values) != len(names) or not set(names) <= set(KEPT_RESIDUALS):
        raise ValueError(f"keep_residuals: {len(values)} values for the "
                         f"names {names} of {KEPT_RESIDUALS}")
    if _open_segments[0]:
        from ...observe.monitoring import runtime_stats

        runtime_stats.record_kept_residuals(
            sum(x.size * x.dtype.itemsize for x in values))
    return tuple(checkpoint_name(x, name) for x, name in zip(values, names))


# kernel-name -> cost function registry (observe/cost.py injection
# point).  A cost fn maps the custom call's actual operand/result
# shapes to the kernel's DENSE-EQUIVALENT work:
#     fn(operand_shapes, result_shapes) -> (flops, bytes_or_None)
# where each shapes list holds (dims_tuple, element_bytes) pairs.
# "Dense-equivalent" is the standing MFU convention here: the flop
# count of the logical math (what the non-Pallas composition would
# compute ONCE) — skipped masked blocks are not credited and backward
# recompute is not double-counted.  bytes None = use the default
# materialized-buffers model (operands + outputs once), which already
# matches how these kernels stream HBM.  Each kernel module registers
# its entries next to its DEFAULT_BLOCK_* tuning constants.
KERNEL_COSTS = {}
# Registered in a cost function's place by a kernel whose work no
# operand's shape tells (a window's width): the call itself declares
# it, `pallas_call(cost_estimate=pl.CostEstimate(...))` in the same
# convention, and observe/cost.py reads it off the custom call.
DECLARED_AT_CALL = "cost_estimate"


def register_kernel_cost(name: str, fn):
    """Declare a Pallas kernel's analytic cost; `name` must match the
    `name=` the kernel passes to `pallas_call` (the jax.named_scope
    that reaches the custom call's HLO metadata)."""
    KERNEL_COSTS[name] = fn
    return fn


def pallas_call(*args, name=None, **kw):
    """pl.pallas_call with the shared interpret gate applied, and the
    invocation wrapped in a jax.named_scope carrying the kernel's name
    — device traces then attribute custom-call time to the specific
    Pallas kernel (custom calls are otherwise opaque blobs in profiles,
    the same blindness that makes them report zero flops to XLA's cost
    analysis).  `name` also keys the KERNEL_COSTS registry: observe.cost
    finds `pallas_<name>` in the custom call's op_name and injects the
    registered (flops, bytes) there."""
    import jax
    from jax.experimental import pallas as pl

    kernel = args[0] if args else kw.get("kernel")
    if name is None:
        name = getattr(kernel, "__name__", None) or getattr(
            getattr(kernel, "func", None), "__name__", "kernel")
    # (a jitted pass hands the gate on as it was when the pass was
    # called: it is part of what the pass is traced for)
    kw.setdefault("interpret", interpret())
    inner = pl.pallas_call(*args, **kw)

    def scoped(*call_args, **call_kw):
        with jax.named_scope(f"pallas_{name}"):
            return inner(*call_args, **call_kw)

    return scoped
