"""Operator registry: op type name → JAX implementation.

TPU-native analog of the reference kernel registry
(reference: paddle/fluid/framework/op_registry.h:197,237,240 —
REGISTER_OPERATOR / REGISTER_OP_*_KERNEL).  There is no per-device kernel
dispatch: every op has one traceable JAX implementation and XLA lowers it to
the target backend.  Grad kernels don't exist either — autodiff is jax.grad
over the traced program (see core/backward.py) instead of grad-op makers.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List

# impl signature: impl(ctx, ins: Dict[slot, List[Array]], attrs: Dict) ->
#                 Dict[slot, List[Array]]
OpImpl = Callable[..., Dict[str, List[Any]]]

_REGISTRY: Dict[str, OpImpl] = {}

# Macro ops are interpreter-level: their impls receive the whole environment
# and the OpDesc (signature impl(ctx, env, desc) -> None, mutating env) so
# they can trace sub-blocks into lax control-flow primitives.  TPU-native
# analog of the reference's interpreter-level control-flow operators
# (reference: paddle/fluid/operators/controlflow/while_op.cc:50 — ops that
# run sub-blocks via a nested Executor).
_MACRO_OPS: Dict[str, Any] = {}


def register_op(op_type: str):
    """Decorator registering an implementation for `op_type`."""

    def deco(fn: OpImpl) -> OpImpl:
        if op_type in _REGISTRY:
            raise ValueError(f"op {op_type!r} registered twice")
        _REGISTRY[op_type] = fn
        return fn

    return deco


def register_macro_op(op_type: str):
    """Decorator registering an interpreter-level (env + sub-block) op."""

    def deco(fn):
        if op_type in _MACRO_OPS or op_type in _REGISTRY:
            raise ValueError(f"op {op_type!r} registered twice")
        _MACRO_OPS[op_type] = fn
        return fn

    return deco


def is_macro_op(op_type: str) -> bool:
    return op_type in _MACRO_OPS


def get_macro_op_impl(op_type: str):
    return _MACRO_OPS[op_type]


def get_op_impl(op_type: str) -> OpImpl:
    impl = _REGISTRY.get(op_type)
    if impl is None:
        raise NotImplementedError(
            f"no implementation registered for op {op_type!r}; "
            f"known ops: {sorted(_REGISTRY)[:20]}..."
        )
    return impl


def has_op(op_type: str) -> bool:
    return op_type in _REGISTRY


def registered_ops() -> List[str]:
    return sorted(_REGISTRY)


class OpContext:
    """Per-execution context handed to op impls.

    Provides deterministic per-op PRNG keys derived from the step key
    (replaces the reference's per-op curand/seed attrs) and scope-level
    flags such as nan-check (reference FLAGS_check_nan_inf,
    paddle/fluid/framework/operator.cc:943).
    """

    def __init__(self, rng_key, op_index: int = 0, is_test: bool = False,
                 program=None, amp_lists=None, sparse_rows=None):
        self._rng_key = rng_key
        self.op_index = op_index
        self.is_test = is_test
        # Set when executing inside a Program trace; macro (control-flow)
        # ops use these to locate and interpret their sub-blocks.
        self.program = program
        self.amp_lists = amp_lists
        # op_index → pre-gathered embedding rows for the SelectedRows-style
        # sparse grad path (core/executor.py, ops/sparse.py lookup_table)
        self.sparse_rows = sparse_rows

    def rng(self):
        """A PRNG key unique to this op within the step."""
        import jax

        if self._rng_key is None:
            raise RuntimeError(
                "op requested randomness but executor has no RNG state"
            )
        return jax.random.fold_in(self._rng_key, self.op_index)

    def run_block(self, block_idx: int, env, keep_names=None):
        """Trace a sub-block's ops over `env` (mutated in place).  Used by
        control-flow macro ops; the sub-block gets a distinct RNG stream so
        per-op keys don't collide with the parent block's.  `keep_names`:
        the names the caller reads from `env` afterwards; a recompute
        segment of the block then hands out only those and what later
        ops of the block read (None: everything it writes)."""
        import jax

        from .executor import run_ops

        if self.program is None:
            raise RuntimeError("OpContext has no program; sub-block "
                               "execution requires a program trace")
        # numerics provenance (observe pillar 6) attributes sub-block
        # ops to the OWNING macro op: sub-block op indices are
        # block-local and would corrupt the global per-op bitmap
        env.pop("__numerics_bits__", None)
        block = self.program.blocks[block_idx]
        sub_key = (None if self._rng_key is None
                   else jax.random.fold_in(self._rng_key, 7919 + block_idx))
        run_ops(block.ops, env, sub_key, amp_lists=self.amp_lists,
                program=self.program, keep_names=keep_names)
        return env
