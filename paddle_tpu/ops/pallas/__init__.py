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


# kernel-name -> cost function registry (observe/cost.py injection
# point).  A cost fn maps the custom call's actual operand/result
# shapes to the kernel's DENSE-EQUIVALENT work:
#     fn(operand_shapes, result_shapes) -> (flops, bytes_or_None)
# where each shapes list holds (dims_tuple, element_bytes) pairs.
# "Dense-equivalent" is bench.py's standing MFU convention: the flop
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
    inner = pl.pallas_call(*args, interpret=interpret(), **kw)

    def scoped(*call_args, **call_kw):
        with jax.named_scope(f"pallas_{name}"):
            return inner(*call_args, **call_kw)

    return scoped
