"""CompiledProgram: multi-device compilation of a Program.

reference: python/paddle/fluid/compiler.py:33 CompiledProgram
.with_data_parallel (the forward-looking API wrapping ParallelExecutor,
parallel_executor.cc:191).  Instead of cloning per-device SSA graphs and
inserting NCCL all-reduce handles, the single traced program is jitted
with NamedShardings: feeds sharded over the batch ("dp") axis, params
replicated (AllReduce mode) or sharded (Reduce/FSDP mode, or tensor-
parallel rules) — XLA GSPMD partitions the computation and inserts the
ICI collectives, including the gradient all-reduce.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from ..core.executor import RNG_STATE_VAR, interpret_program
from ..core.program import Program
from .mesh import get_default_mesh
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

    # -- execution -------------------------------------------------------
    def run(self, executor, feed: Dict[str, Any], fetch_names, scope,
            return_numpy: bool = True, iterations: int = 1,
            accumulation_steps: int = 1):
        from ..core.executor import _debug_checks
        from ..observe.monitoring import runtime_stats

        fn, state, feed_arrays, _, _ = self._prepare_step(
            feed, fetch_names, scope, iterations, accumulation_steps)
        with runtime_stats.phase("call"):
            new_state, fetches = fn(state, feed_arrays)
        with runtime_stats.phase("writeback"):
            for name, val in new_state.items():
                scope.set_var(name, val)
            # the last references to the donated arrays: freeing some
            # 600 of them is host time of the step, so it is timed
            del state
            _debug_checks(fetch_names, fetches, new_state)
            if return_numpy:
                fetches = [np.asarray(f) for f in fetches]
        return fetches

    def compiled_hlo_text(self, feed: Dict[str, Any], fetch_names,
                          scope, iterations: int = 1) -> str:
        """AOT-lower the sharded step and return the compiled
        (post-SPMD-partitioning) HLO text — for inspecting which
        collectives GSPMD inserted (e.g. asserting MoE dispatch lowers
        to all-to-all, tests/test_moe.py) and for roofline tooling.
        One extra XLA compile; the traced fn comes from the same
        cache as run()."""
        return self.compiled_step(feed, fetch_names, scope,
                                  iterations=iterations).as_text()

    def compiled_step(self, feed: Dict[str, Any], fetch_names=(),
                      scope=None, iterations: int = 1,
                      with_names: bool = False):
        """AOT-compile the SHARDED step and return the jax Compiled
        object — the multi-device analog of Executor.compiled_step.
        This is what the dp bench's comm accounting reads: the
        post-SPMD module's collective instructions land in
        observe.cost's `comm` bucket (all-reduce/all-gather/
        reduce-scatter/all-to-all/collective-permute), so
        `comm_bytes` comes from the SAME analytic accounting as every
        other bucket.  Memoized per (feed signature, fetches,
        iterations) — bench's comm fields reuse one compile.

        with_names=True returns (compiled, arg_names) like
        Executor.compiled_step: the per-entry-parameter
        ("state"|"feed", var_name) labels observe.memory uses to
        attribute PER-DEVICE buffer bytes to named state vars — how
        the fsdp A/B proves opt-state bytes actually dropped on the
        sharded step."""
        from ..core.executor import global_scope

        fn, state, feed_arrays, _, _ = self._prepare_step(
            feed, list(fetch_names), scope or global_scope(),
            iterations, 1)
        key = (self._program._uid, self._program._version,
               tuple(sorted(feed)), tuple(fetch_names), iterations,
               tuple((n, tuple(getattr(v, "shape", ()) or ()),
                      str(getattr(v, "dtype", type(v).__name__)))
                     for n, v in sorted(feed_arrays.items())))
        entry = self._aot_cache.get(key)
        if entry is None:
            from ..observe.memory import _arg_labels

            compiled = fn.lower(state, feed_arrays).compile()
            entry = (compiled,
                     _arg_labels(state, feed_arrays, compiled=compiled))
            self._aot_cache[key] = entry
        return entry if with_names else entry[0]

    def _prepare_step(self, feed, fetch_names, scope, iterations,
                      accumulation_steps):
        """The step's first two host phases (observe.monitoring):
        `prepare` (`_lookup_step`) and `place` (every state array and
        every feed array through `jax.device_put`, a no-op for what is
        already placed; the two child spans say which of them costs)."""
        import jax
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        from ..observe.monitoring import SPAN_PREFIX, runtime_stats

        with runtime_stats.phase("prepare"):
            (fn, state_shardings, feed_shardings), state = \
                self._lookup_step(feed, fetch_names, scope, iterations,
                                  accumulation_steps)
        with runtime_stats.phase("place"):
            with TraceAnnotation(SPAN_PREFIX + "place_state"):
                state = {n: jax.device_put(v, state_shardings[n])
                         for n, v in state.items()}
            with TraceAnnotation(SPAN_PREFIX + "place_feed"):
                feed_arrays = {
                    n: jax.device_put(jnp.asarray(v), feed_shardings[n])
                    for n, v in feed.items()}
        return fn, state, feed_arrays, state_shardings, feed_shardings

    def _lookup_step(self, feed, fetch_names, scope, iterations,
                     accumulation_steps):
        """RNG and telemetry state, state names, feed shardings, cache
        key and look-up, and on a miss the step's build.  Returns
        ((fn, state_shardings, feed_shardings), state as the scope
        holds it)."""
        import jax

        # an explicit per-run override wins over the BuildStrategy knob
        accum = (accumulation_steps if accumulation_steps != 1
                 else self._accum_steps)

        if self._mesh is None:
            # bare CompiledProgram(program): single-device compilation,
            # like fluid without with_data_parallel
            from .mesh import make_mesh

            self._mesh = make_mesh({"dp": 1})
            if self._rules is None:
                self._rules = ShardingRules()

        program = self._program
        block = program.global_block()
        if RNG_STATE_VAR not in scope.vars:
            scope.set_var(RNG_STATE_VAR,
                          jax.random.PRNGKey(program.random_seed))
        state_names = tuple(sorted(
            v.name for v in block.vars.values()
            if v.persistable and scope.has_var(v.name)))
        from ..observe import metrics as _obs_metrics

        telemetry = getattr(program, "_telemetry_enabled", False)
        if telemetry:
            # mirror Executor._prepare: the device-side accumulator
            # rides the (donated) state pytree so enable_telemetry()
            # works identically under a mesh — bench dp entries carry
            # the same honesty counters as single-device ones (and the
            # same numerics fields when the program opted in)
            tel_cur = scope.find_var(_obs_metrics.TELEMETRY_VAR)
            if tel_cur is None:
                scope.set_var(_obs_metrics.TELEMETRY_VAR,
                              _obs_metrics.init_telemetry_for(program))
            else:
                patched = _obs_metrics.ensure_numerics_fields(
                    program, tel_cur)
                if patched is not tel_cur:
                    scope.set_var(_obs_metrics.TELEMETRY_VAR, patched)
            state_names = state_names + (_obs_metrics.TELEMETRY_VAR,)
        feed_shardings = {n: self._feed_sharding(n, v)
                          for n, v in feed.items()}
        # the chosen feed shardings are part of the key: a final partial
        # batch that is no longer dp-divisible must recompile with a
        # replicated layout rather than reuse the sharded executable
        feed_sig = tuple(sorted(
            (n, str(s.spec)) for n, s in feed_shardings.items()))
        key = (program._uid, program._version, feed_sig,
               tuple(fetch_names), state_names, id(self._mesh), iterations,
               accum)
        entry = self._cache.get(key)

        state = {n: scope.find_var(n) for n in state_names}
        state[RNG_STATE_VAR] = scope.find_var(RNG_STATE_VAR)

        if entry is None:
            state_shardings = {n: self._state_sharding(n, v)
                               for n, v in state.items()}
            persistable_names = tuple(sorted(
                v.name for v in block.vars.values() if v.persistable))

            feed_names = tuple(sorted(feed))

            def step(st, feeds):
                from .mesh import executing_mesh

                rng_key = st[RNG_STATE_VAR]
                env = {k: v for k, v in st.items() if k != RNG_STATE_VAR}
                env.update(feeds)
                with executing_mesh(
                        self._mesh, batch_axis=self._batch_axis,
                        pipeline_microbatches=self._pp_microbatches):
                    env = interpret_program(program, env, rng_key,
                                            fetch_names=fetch_names,
                                            accum_steps=accum,
                                            feed_names=feed_names)
                new_state = {n: env[n] for n in persistable_names
                             if n in env}
                from ..observe.metrics import TELEMETRY_VAR

                if TELEMETRY_VAR in env:
                    # executor-private state (not a block var): threads
                    # the step + chain_iterations carry, same as the
                    # single-device step fn
                    new_state[TELEMETRY_VAR] = env[TELEMETRY_VAR]
                new_state[RNG_STATE_VAR] = jax.random.split(rng_key, 1)[0]
                fetches = [env[n] for n in fetch_names]
                return new_state, fetches

            from ..core.executor import chain_iterations

            fn = jax.jit(
                chain_iterations(step, iterations),
                in_shardings=(state_shardings, feed_shardings),
                # pin the updated state to the SAME shardings it came
                # in with: without this XLA may infer a different
                # (replicated) output layout for ZeRO-sharded optimizer
                # state, which silently breaks donation — per-device
                # opt-state bytes then DOUBLE (input + undonated
                # output) and an all-gather sneaks into every step
                out_shardings=(state_shardings, None),
                donate_argnums=(0,),
            )
            entry = (fn, state_shardings, feed_shardings)
            self._cache[key] = entry

        return entry, state
