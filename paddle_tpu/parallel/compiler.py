"""CompiledProgram: the placement of a Program's step on a mesh.

reference: python/paddle/fluid/compiler.py:33 CompiledProgram
.with_data_parallel (the forward-looking API wrapping ParallelExecutor,
parallel_executor.cc:191).  Instead of cloning per-device SSA graphs and
inserting NCCL all-reduce handles, the single traced program is jitted
with NamedShardings: feeds sharded over the batch ("dp") axis, params
replicated (AllReduce mode) or sharded (Reduce/FSDP mode, or tensor-
parallel rules) — XLA GSPMD partitions the computation and inserts the
ICI collectives, including the gradient all-reduce.

This module owns WHERE things lie: the mesh, the sharding rules, the
sharding of every state and feed array, the trace-time mesh context and
the step cache of its program.  `core/executor.py` owns the step: it
builds, caches, places, calls and writes back, and takes a
CompiledProgram as the optional placement of that one path.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from ..core.executor import RNG_STATE_VAR, global_scope
from ..core.program import Program
from .mesh import executing_mesh, get_default_mesh, make_mesh
from .strategies import ShardingRules


class ReduceStrategy:
    AllReduce = 0  # replicated params, grads all-reduced (GSPMD-implicit)
    Reduce = 1     # FSDP-style: params sharded over dp


class BuildStrategy:
    """reference: framework/details/build_strategy.h:55."""

    ReduceStrategy = ReduceStrategy

    def __init__(self):
        self.reduce_strategy = ReduceStrategy.AllReduce
        self.sharding_rules: Optional[ShardingRules] = None
        self.memory_optimize = False  # XLA buffer liveness subsumes this
        self.enable_inplace = True
        # multi-trainer (multi-host) topology; wired to jax.distributed by
        # parallel/dist.py init_distributed (reference: nccl2 mode,
        # parallel_executor.cc:254 num_trainers*ndev ranks)
        self.num_trainers = 1
        self.trainer_id = 0
        # K-micro-batch gradient accumulation (reference:
        # ir/multi_batch_merge_pass.cc)
        self.gradient_accumulation_steps = 1
        # GPipe microbatch count for programs built with
        # fluid.pipeline_scope() layer tagging, executed on a mesh with
        # a "pp" axis.  0 = auto (2x the pp degree when the batch
        # divides, else the pp degree).  Ignored when the program has no
        # pipeline tags or the mesh has no pp axis.
        self.pipeline_microbatches = 0
        # Opt-in explicit gradient synchronization for dp
        # (strategies.GradSyncConfig or a mode string): None keeps the
        # implicit GSPMD all-reduce; "int8" routes dense grads through
        # the blockwise-quantized two-phase exchange
        # (collectives.quantized_all_reduce, EQuARX), "bf16" the same
        # explicit path without quantization (the A/B control arm).
        self.grad_sync = None


class ExecutionStrategy:
    """reference: framework/details/execution_strategy.h (inert knobs kept
    for API parity; XLA owns scheduling)."""

    def __init__(self):
        self.num_threads = 0
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 1


class CompiledProgram:
    def __init__(self, program: Program):
        self._program = program
        self._mesh = None
        self._batch_axis = "dp"
        self._rules: Optional[ShardingRules] = None
        # the executor's step cache and AOT memo for this placement
        self._cache: Dict[Any, Any] = {}
        self._loss_name = None
        self._accum_steps = 1
        self._pp_microbatches = 0
        self._aot_cache: Dict[Any, Any] = {}
        self._opt_names = None  # lazy: optimizer-state var names

    def with_data_parallel(self, loss_name: Optional[str] = None,
                           build_strategy: Optional[BuildStrategy] = None,
                           exec_strategy: Optional[ExecutionStrategy] = None,
                           share_vars_from=None, places=None,
                           mesh=None, batch_axis: str = "dp"):
        self._loss_name = loss_name
        self._mesh = mesh or get_default_mesh()
        self._batch_axis = batch_axis
        bs = build_strategy or BuildStrategy()
        self._accum_steps = int(getattr(bs, "gradient_accumulation_steps",
                                        1) or 1)
        self._pp_microbatches = int(getattr(bs, "pipeline_microbatches",
                                            0) or 0)
        if bs.sharding_rules is not None:
            self._rules = bs.sharding_rules
        elif bs.reduce_strategy == ReduceStrategy.Reduce:
            self._rules = ShardingRules(default="fsdp",
                                        fsdp_axis=batch_axis)
        else:
            self._rules = ShardingRules()
        from .strategies import GradSyncConfig

        # explicit grad-sync mode rides the PROGRAM (the executor's
        # interpret_program hook reads it at trace time; the mesh/axis
        # come from the executing_mesh context this wrapper sets)
        self._program._grad_sync = GradSyncConfig.normalize(
            getattr(bs, "grad_sync", None))
        self._program._compiled_wrapper = self
        return self

    # -- shardings -------------------------------------------------------
    def _optimizer_state_names(self) -> set:
        """Names of the program's optimizer-state vars (accumulators,
        pow counters, the lr var) — the set the ZeRO axis shards.  Uses
        the same op-slot classification as observe.memory's buckets so
        the sharded bytes and the reported optimizer_state bucket are
        the SAME population."""
        if self._opt_names is None:
            from ..observe.memory import _program_var_buckets

            _params, opt = _program_var_buckets(self._program)
            self._opt_names = opt
        return self._opt_names

    def state_spec_for(self, name: str, shape) -> tuple:
        """The PartitionSpec dims this wrapper assigns to a STATE var:
        the rule spec, with the ZeRO axis composed in for
        optimizer-state vars (strategies.opt_state_spec_for).  Public
        because io.load_sharded reshards checkpoints into exactly these
        specs (mesh-shape-agnostic load)."""
        if name in self._optimizer_state_names():
            return self._rules.opt_state_spec_for(name, shape,
                                                  self._mesh)
        return self._rules.spec_for(name, shape, self._mesh)

    def data_axes(self) -> tuple:
        """Mesh axes the batch shards over (batch axis + fsdp/ZeRO)."""
        return self._rules.data_axes_for(self._mesh, self._batch_axis)

    def _state_sharding(self, name: str, value):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..observe import metrics as _obs_metrics

        if name == RNG_STATE_VAR or name == _obs_metrics.TELEMETRY_VAR:
            # the telemetry accumulator is a dict pytree of scalars: a
            # single replicated sharding acts as a pytree prefix
            return NamedSharding(self._mesh, P())
        spec = self.state_spec_for(name, np.shape(value))
        return NamedSharding(self._mesh, P(*spec))

    def _feed_sharding(self, name, value):
        from jax.sharding import NamedSharding, PartitionSpec as P

        # the data-axis rule lives on ShardingRules (feed_spec_for):
        # dim 0 over the batch axis when divisible, explicit rules win,
        # meshes without the batch axis (pure {"sp": N}) replicate
        spec = self._rules.feed_spec_for(name, np.shape(value),
                                         self._mesh,
                                         batch_axis=self._batch_axis)
        return NamedSharding(self._mesh, P(*spec))

    def _ensure_mesh(self):
        """The mesh; a bare CompiledProgram(program) compiles for one
        device, like fluid without with_data_parallel."""
        if self._mesh is None:
            self._mesh = make_mesh({"dp": 1})
            if self._rules is None:
                self._rules = ShardingRules()
        return self._mesh

    def _trace_context(self):
        """Entered around the step's trace: what mesh-aware op impls and
        the explicit grad_sync body read (parallel/mesh.py ExecContext)."""
        return executing_mesh(self._mesh, batch_axis=self._batch_axis,
                              pipeline_microbatches=self._pp_microbatches,
                              rules=self._rules)

    # -- execution: the executor's one path, with self as placement ------
    def run(self, executor, feed: Dict[str, Any], fetch_names, scope,
            return_numpy: bool = True, iterations: int = 1,
            accumulation_steps: int = 1):
        return executor.run(self, feed=feed, fetch_list=fetch_names,
                            scope=scope, return_numpy=return_numpy,
                            iterations=iterations,
                            accumulation_steps=accumulation_steps)

    def compiled_hlo_text(self, feed: Dict[str, Any], fetch_names,
                          scope, iterations: int = 1) -> str:
        """AOT-lower the sharded step and return the compiled
        (post-SPMD-partitioning) HLO text — for inspecting which
        collectives GSPMD inserted (e.g. asserting MoE dispatch lowers
        to all-to-all, tests/test_moe.py).  One extra XLA compile; the
        traced fn comes from the same cache as run()."""
        return self.compiled_step(feed, fetch_names, scope,
                                  iterations=iterations).as_text()

    def compiled_step(self, feed: Dict[str, Any], fetch_names=(),
                      scope=None, iterations: int = 1,
                      with_names: bool = False):
        """AOT-compile the SHARDED step and return the jax Compiled
        object — the multi-device analog of Executor.compiled_step.
        The post-SPMD module's collective instructions land in
        observe.cost's `comm` bucket (all-reduce/all-gather/
        reduce-scatter/all-to-all/collective-permute), so `comm_bytes`
        comes from the SAME analytic accounting as every other bucket.
        Memoized per (feed signature, fetches, iterations).

        with_names=True returns (compiled, arg_names) like
        Executor.compiled_step: the per-entry-parameter
        ("state"|"feed", var_name) labels observe.memory uses to
        attribute PER-DEVICE buffer bytes to named state vars."""
        from ..core.executor import Executor

        # the caches are this placement's, so any executor serves
        return Executor()._compiled_step(
            self._program, feed, list(fetch_names), scope or global_scope(),
            iterations, with_names, placement=self)
