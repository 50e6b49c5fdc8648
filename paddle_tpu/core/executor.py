"""Executor: compile a Program to one XLA computation and run it.

TPU-native analog of the reference C++ Executor
(reference: paddle/fluid/framework/executor.cc — Run:299, Prepare:372, the
op-by-op hot loop at :448-455, program cache in python executor.py:222).
The key design change: instead of interpreting OpDescs one at a time on a
device stream, the whole program — forward ops, the autodiff boundary
(core/backward.py), and optimizer update ops — is traced ONCE into a single
`jax.jit` function of shape

    step(state: {persistable: Array}, feeds: {name: Array})
        -> (new_state, fetches)

with the state argument donated.  XLA then fuses/schedules everything; eager
per-op garbage collection (executor.cc:45-134) is unnecessary because XLA's
buffer liveness analysis subsumes it.

This module owns the step: its body, its jit, its cache, the host phases
`prepare` / `place` / `call` / `writeback` and the AOT memo, once.  A mesh
is an optional placement that one path takes (parallel/compiler.py
CompiledProgram: shardings, the trace-time mesh context, where the cache
lives); nothing here reads a placement except through that argument.
"""

from __future__ import annotations

import functools
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .desc import normalize_dtype
from .program import (GRAD_SUFFIX, Parameter, Program, Variable,
                      grad_var_name)
from .registry import OpContext, get_op_impl

RNG_STATE_VAR = "__rng_key__"


class Scope:
    """Name → value store for persistable state (reference: scope.h:48).

    Parent-chain lookup is kept for API parity; values are jax Arrays (on
    device) or numpy arrays.
    """

    # every Scope alive in the process: lets a reader that was handed
    # no scope (observe/routing.py) find device-side counters
    live: "weakref.WeakSet[Scope]" = weakref.WeakSet()

    def __init__(self, parent: Optional["Scope"] = None):
        self.parent = parent
        self.vars: Dict[str, Any] = {}
        self.kids: List["Scope"] = []
        Scope.live.add(self)

    def new_scope(self) -> "Scope":
        kid = Scope(self)
        self.kids.append(kid)
        return kid

    def var(self, name: str):
        """Find-or-create (reference scope.h:56 Var)."""
        if name not in self.vars:
            self.vars[name] = None
        return self.vars[name]

    def find_var(self, name: str):
        s: Optional[Scope] = self
        while s is not None:
            if name in s.vars:
                return s.vars[name]
            s = s.parent
        return None

    def set_var(self, name: str, value):
        self.vars[name] = value

    def has_var(self, name: str) -> bool:
        return self.find_var(name) is not None

    def local_var_names(self) -> List[str]:
        return list(self.vars)

    def drop_kids(self):
        self.kids = []


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


import contextlib


@contextlib.contextmanager
def scope_guard(scope: Scope):
    global _global_scope
    old = _global_scope
    _global_scope = scope
    try:
        yield
    finally:
        _global_scope = old


# ---------------------------------------------------------------------------
# Program interpretation (used inside jit traces)
# ---------------------------------------------------------------------------

# Optimizer ops with a SelectedRows-style sparse kernel (reference:
# optimizers/*_op.h SelectedRows paths); every other op sees densified
# gradients (reference analog: get_tensor_from_selected_rows).
SPARSE_AWARE_OPS = {"sgd", "momentum", "adam", "adagrad"}


def run_ops(ops, env: Dict[str, Any], rng_key, start_index: int = 0,
            amp_lists=None, program=None, sparse_rows=None,
            keep_names=None):
    """Interpret a straight-line op list over `env` (name → traced array).

    This runs under jax tracing: each op impl emits jaxpr; nothing executes
    eagerly.  Equivalent of the executor hot loop (executor.cc:448) but as a
    trace, compiled once.  With `amp_lists` set (paddle_tpu/amp.py), the
    bf16 dtype policy is applied at each op boundary inside the trace.
    Macro (control-flow) ops receive the whole env + their OpDesc and lower
    sub-blocks to lax primitives (ops/control_flow.py).
    """
    from .registry import get_macro_op_impl, is_macro_op
    from .selected_rows import densify

    # pipelining: maximal runs of consecutive ops sharing a
    # __pp_group__ tag (fluid.pipeline_scope) lift into the GPipe
    # schedule when the executing mesh has a pp axis
    # (parallel/pipeline_engine.py); on meshes without pp the tags are
    # inert and the ops run sequentially below.
    pp_ctx = None
    if program is not None and any(
            "__pp_group__" in op.desc.attrs for op in ops):
        from ..parallel.mesh import get_exec_context

        ectx = get_exec_context()
        if (ectx is not None
                and ectx.mesh.shape.get("pp", 1) > 1):
            pp_ctx = ectx

    # suffix read-sets: segment boundaries below need "names consumed
    # at or after op j" — precompute them in ONE backward walk
    # (snapshots only where a tagged run can end) instead of rescanning
    # ops[j:] per segment, which is quadratic on deep tagged stacks
    n_ops = len(ops)
    suffix_reads: Dict[int, set] = {}
    if keep_names is not None:
        def _tags(op):
            return (op.desc.attrs.get("__pp_group__"),
                    op.desc.attrs.get("__recompute__"))

        needed = {
            j for j in range(1, n_ops + 1)
            if _tags(ops[j - 1]) != (None, None)
            and (j == n_ops or _tags(ops[j]) != _tags(ops[j - 1]))
        }
        if needed:
            acc = set(keep_names)
            for j in range(n_ops, 0, -1):
                if j in needed:
                    suffix_reads[j] = set(acc)
                acc.update(ops[j - 1].desc.input_names())

    # rematerialization: maximal runs of consecutive ops sharing a
    # __recompute__ tag (fluid.recompute_scope) execute inside
    # jax.checkpoint — their activations are recomputed in the backward
    # instead of saved.  Macro (control-flow) ops never join a segment.
    i = 0
    while i < n_ops:
        gid = ops[i].desc.attrs.get("__pp_group__")
        if gid is not None and pp_ctx is not None:
            j = i
            while (j < n_ops
                   and ops[j].desc.attrs.get("__pp_group__") == gid):
                j += 1
            from ..parallel.pipeline_engine import run_pipelined_group

            # the numerics bitmap must not enter the gpipe shard_map
            # (stage-local envs would OR bits under a ppermute carry);
            # attribute the group's ops from their top-level outputs
            # after the schedule instead
            saved_bits = env.pop("__numerics_bits__", None)
            run_pipelined_group(
                ops[i:j], env, rng_key, start_index + i, program,
                pp_ctx.mesh, batch_axis=pp_ctx.batch_axis,
                n_micro_req=pp_ctx.pipeline_microbatches,
                amp_lists=amp_lists,
                downstream_reads=suffix_reads.get(j))
            if saved_bits is not None:
                from ..observe import numerics as _obs_num

                bits = saved_bits
                for off, gop in enumerate(ops[i:j]):
                    bits = _obs_num.update_bits(
                        bits, start_index + i + off,
                        [env[n] for n in gop.desc.output_names()
                         if n in env])
                env["__numerics_bits__"] = bits
            i = j
            continue
        tag = ops[i].desc.attrs.get("__recompute__")
        if tag is not None and not is_macro_op(ops[i].desc.type):
            j = i
            while (j < n_ops
                   and ops[j].desc.attrs.get("__recompute__") == tag
                   and not is_macro_op(ops[j].desc.type)):
                j += 1
            # a 1-op segment gains nothing from remat (inputs AND
            # outputs are saved regardless) and would break the
            # control-flow vjp replay, which re-traces ops one at a
            # time relying on CSE to merge with the forward
            # (ops/control_flow.py) — checkpoint only real runs
            if j - i >= 2:
                # restrict the checkpoint's outputs to names actually
                # consumed after the segment — the HBM saving must not
                # depend on JAX's remat DCE pruning unused outputs
                _run_checkpointed_segment(
                    ops[i:j], env, rng_key, start_index + i,
                    amp_lists=amp_lists, program=program,
                    sparse_rows=sparse_rows, keep=suffix_reads.get(j))
                i = j
                continue
        _run_one_op(ops[i], env, rng_key, start_index + i,
                    amp_lists=amp_lists, program=program,
                    sparse_rows=sparse_rows)
        i += 1
    return env


def _run_checkpointed_segment(seg_ops, env, rng_key, start_index,
                              amp_lists=None, program=None,
                              sparse_rows=None, keep=None):
    """Execute a recompute segment under jax.checkpoint.  All env names
    the segment reads enter as EXPLICIT arguments (closed-over tracers
    would be saved as residuals, defeating the remat); names it writes
    that someone downstream reads (`keep`; None = all) merge back into
    env.  The backward pass keeps the segment's inputs and what is
    named (ops/pallas `keep_residuals`: an attention kernel's output
    and logsumexp, the delta rule's inverses), and recomputes
    everything else."""
    import jax

    from ..ops.pallas import segment_policy, tracing_segment

    read, written = [], set()
    read_set = set()
    for op in seg_ops:
        for n in op.desc.input_names():
            if n not in written and n in env and n not in read_set:
                read.append(n)
                read_set.add(n)
        written.update(op.desc.output_names())
    out_names = sorted(written if keep is None else written & keep)
    if "__numerics_bits__" in env:
        # the per-op finite bitmap (observe pillar 6) must enter and
        # leave the checkpoint explicitly: bits set by remat-internal
        # ops would otherwise die inside the segment
        if "__numerics_bits__" not in read_set:
            read.append("__numerics_bits__")
        out_names.append("__numerics_bits__")

    # non-array env entries (host constants) can't cross the
    # checkpoint boundary as traced args; keep them closed-over
    import numpy as np

    def _is_arrayish(v):
        return hasattr(v, "dtype") or isinstance(
            v, (np.ndarray, float, int, bool))

    arr_in = [n for n in read if _is_arrayish(env[n])]
    arr_set = set(arr_in)
    other_in = {n: env[n] for n in read if n not in arr_set}

    @functools.partial(jax.checkpoint, policy=segment_policy())
    def seg_fn(rk, *vals):
        local = dict(other_in)
        local.update(zip(arr_in, vals))
        with tracing_segment():
            for k, op in enumerate(seg_ops):
                _run_one_op(op, local, rk, start_index + k,
                            amp_lists=amp_lists, program=program,
                            sparse_rows=sparse_rows)
        return tuple(local[n] for n in out_names)

    results = seg_fn(rng_key, *(env[n] for n in arr_in))
    env.update(zip(out_names, results))


def _run_one_op(op, env, rng_key, op_index, amp_lists=None,
                program=None, sparse_rows=None):
    import jax

    from .registry import get_macro_op_impl, is_macro_op
    from .selected_rows import densify

    desc = op.desc
    # fluid-op attribution (observe pillar 1): the scope name lands in
    # every emitted HLO instruction's metadata.op_name, so device
    # profiles and compiled-HLO dumps carry "<op_type>:<op_index>" —
    # trace-time only, zero runtime cost (observe/trace.py parses it
    # back out of captured profiles).  An op built under a
    # fluid.name_scope() lowers as "<path>/<op_type>:<op_index>".
    scope = f"{desc.type}:{op_index}"
    if "__name_scope__" in desc.attrs:
        scope = f"{desc.attrs['__name_scope__']}/{scope}"
    try:
        with jax.named_scope(scope):
            if is_macro_op(desc.type):
                ctx = OpContext(rng_key, op_index=op_index,
                                program=program, amp_lists=amp_lists)
                get_macro_op_impl(desc.type)(ctx, env, desc)
                outs = None  # macro impls write env themselves
            else:
                impl = get_op_impl(desc.type)
                ins = {
                    slot: [env[n] for n in names]
                    for slot, names in desc.inputs.items()
                }
                if desc.type not in SPARSE_AWARE_OPS:
                    ins = {slot: [densify(v) for v in vals]
                           for slot, vals in ins.items()}
                if amp_lists is not None:
                    from ..amp import cast_ins_for_op

                    ins = cast_ins_for_op(desc.type, ins, amp_lists)
                ctx = OpContext(rng_key, op_index=op_index,
                                program=program, amp_lists=amp_lists,
                                sparse_rows=sparse_rows)
                outs = impl(ctx, ins, desc.attrs)
    except Exception as exc:
        _reraise_with_op_context(exc, desc, op_index)
    if outs is not None:
        for slot, names in desc.outputs.items():
            values = outs.get(slot, [])
            if len(values) != len(names):
                raise RuntimeError(
                    f"op {desc.type}: output slot {slot!r} produced "
                    f"{len(values)} values for {len(names)} names"
                )
            for name, val in zip(names, values):
                env[name] = val
    if "__numerics_bits__" in env:
        # first-nonfinite op provenance (observe pillar 6): OR this
        # op's finite flag into the step bitmap — trace-time only, and
        # only when the program opted in (the bits var is absent
        # otherwise, so the disabled step is byte-identical)
        from ..observe import numerics as _obs_num

        env[_obs_num.NUMERICS_BITS_VAR] = _obs_num.update_bits(
            env[_obs_num.NUMERICS_BITS_VAR], op_index,
            [env[n] for n in desc.output_names() if n in env])
    return env


def _reraise_with_op_context(exc: Exception, desc, op_index: int):
    """Attach op type/index/io context to trace-time failures — the
    reference's PADDLE_ENFORCE discipline (platform/enforce.h) so a failing
    op inside a 500-op program is locatable.  The original traceback is
    preserved via exception chaining."""
    if isinstance(exc, (KeyboardInterrupt, SystemExit)):
        raise exc
    detail = (
        f"error while tracing op[{op_index}] {desc.type!r} "
        f"(inputs={desc.inputs}, outputs={desc.outputs}, "
        f"attrs={ {k: v for k, v in desc.attrs.items() if not str(k).startswith('_')} })"
    )
    try:
        new_exc = type(exc)(f"{detail}\n  caused by: {exc}")
    except Exception:
        new_exc = RuntimeError(f"{detail}\n  caused by: {exc!r}")
    raise new_exc from exc


def prune_ops(program: Program, fetch_names):
    """Dead-op elimination: keep ops contributing to fetches or writing
    persistable state (reference analog: Program pruning in
    framework/prune.cc + io.py save_inference_model's prune to targets).
    Training programs (with a backward boundary) are never pruned."""
    ops = program.global_block().ops
    if program._backward_info is not None:
        return ops
    block = program.global_block()

    def is_persistable(name: str) -> bool:
        return block.has_var(name) and block.var(name).persistable

    needed = set(fetch_names)
    keep = [False] * len(ops)
    for i in range(len(ops) - 1, -1, -1):
        desc = ops[i].desc
        outs = desc.output_names()
        if any(n in needed for n in outs) or any(
                is_persistable(n) for n in outs):
            keep[i] = True
            needed.update(desc.input_names())
    return [op for i, op in enumerate(ops) if keep[i]]


def _split_params(program: Program, env: Dict[str, Any]):
    info = program._backward_info
    trainable = {}
    for pname in info["params"]:
        if pname in env:
            trainable[pname] = env[pname]
    return trainable


def interpret_program(program: Program, env: Dict[str, Any], rng_key,
                      fetch_names=(), accum_steps: int = 1,
                      feed_names=()):
    """Run the full program (forward [+ backward + update ops]) over env.

    With accum_steps=K > 1, the feeds are split into K micro-batches along
    dim 0 and the forward+backward runs as a lax.scan accumulating
    (averaging) gradients before the optimizer ops execute once — the
    TPU-native equivalent of the reference's batch-merge pass
    (reference: paddle/fluid/framework/ir/multi_batch_merge_pass.cc:1,
    which cloned the fwd/bwd subgraph K times and summed gradients).
    """
    import jax

    info = program._backward_info
    amp_lists = getattr(program, "_amp_lists", None)
    block = program.global_block()
    persist = {v.name for v in block.vars.values() if v.persistable}
    if info is None:
        return run_ops(prune_ops(program, fetch_names), env, rng_key,
                       amp_lists=amp_lists, program=program,
                       keep_names=set(fetch_names) | persist)
    ops = block.ops

    k = info["index"]
    loss_name = info["loss"]
    fwd_ops, rest_ops = ops[:k], ops[k:]
    trainable = _split_params(program, env)
    # names someone reads after the forward section: the loss, fetches,
    # persistable state, and anything the post-marker (optimizer/metric)
    # ops consume — everything else a recompute segment writes is
    # internal and need not leave its jax.checkpoint
    fwd_keep = set(fetch_names) | persist | {loss_name}
    for op in rest_ops:
        fwd_keep.update(op.desc.input_names())
    tracked = getattr(program, "_tracked_scalars", None)
    if tracked and getattr(program, "_telemetry_enabled", False):
        fwd_keep.update(tracked.values())

    # numerics observability (observe pillar 6): seed the per-step
    # finite bitmap BEFORE the forward closure captures env — every
    # _run_one_op below then ORs its op's finite flag into it, and the
    # end of this function latches it into the telemetry accumulator.
    # Nothing here runs when the program did not opt in.
    from ..observe import metrics as _obs_metrics

    num_on = False
    if (getattr(program, "_numerics_enabled", False)
            and _obs_metrics.TELEMETRY_VAR in env):
        from ..observe import numerics as _obs_num

        if _obs_num.NONFINITE_WORDS in env[_obs_metrics.TELEMETRY_VAR]:
            env[_obs_num.NUMERICS_BITS_VAR] = _obs_num.init_step_bits(
                len(ops))
            num_on = True

    def fwd(params, base_env, key, sparse_rows=None):
        e = dict(base_env)
        e.update(params)
        run_ops(fwd_ops, e, key, amp_lists=amp_lists, program=program,
                sparse_rows=sparse_rows, keep_names=fwd_keep)
        loss = e[loss_name]
        if loss.ndim > 0:
            import jax.numpy as jnp

            loss = jnp.squeeze(loss)
        return loss, e

    # resilience update guard (resilience/guard.py): dynamic loss
    # scaling wraps the loss BEFORE autodiff; the all-finite check +
    # update select happen below.  All of it is pure jnp inside this
    # trace — the step remains ONE XLA computation.
    from ..observe import metrics as _obs_metrics

    guard_cfg = getattr(program, "_update_guard", None)
    scale = None
    if (guard_cfg is not None and guard_cfg.loss_scaling is not None
            and _obs_metrics.TELEMETRY_VAR in env):
        import jax.numpy as jnp

        scale = jnp.asarray(
            env[_obs_metrics.TELEMETRY_VAR]["loss_scale"], jnp.float32)

    grad_fwd = fwd
    if scale is not None:
        def grad_fwd(params, base_env, key, sparse_rows=None):
            loss, e = fwd(params, base_env, key,
                          sparse_rows=sparse_rows)
            return loss * scale, e

    sparse_lookups = _find_sparse_lookups(fwd_ops, trainable, env)
    # explicit dp gradient synchronization (ISSUE 10, docs/DIST.md):
    # with a GradSyncConfig on the program AND an executing mesh whose
    # batch axis is >1, the fwd+bwd runs inside a shard_map over that
    # axis and the gradient exchange becomes OURS — exact psum ("bf16")
    # or the EQuARX blockwise-int8 two-phase exchange ("int8") — instead
    # of the GSPMD-inserted all-reduce.  Everything stays inside the ONE
    # jitted step.
    gs_cfg = getattr(program, "_grad_sync", None)
    gs_ectx = None
    gs_axes: Tuple[str, ...] = ()
    if gs_cfg is not None:
        from ..parallel.mesh import get_exec_context

        _ectx = get_exec_context()
        if _ectx is not None:
            # the DATA axes of the mesh: the batch axis plus the
            # ZeRO/fsdp axis when the placement's rules name one
            # (strategies.data_axes_for) — fsdp is dp with sharded
            # optimizer state, so the explicit exchange spans both
            if _ectx.rules is not None:
                gs_axes = _ectx.rules.data_axes_for(
                    _ectx.mesh, _ectx.batch_axis)
            else:
                gs_axes = tuple(
                    a for a in (_ectx.batch_axis,)
                    if _ectx.mesh.shape.get(a, 1) > 1)
            if gs_axes:
                gs_ectx = _ectx
    if gs_ectx is not None:
        # a FINAL PARTIAL batch that no longer divides the data axes
        # falls back to the ordinary (replicated-feed) path — exact
        # grads, no dp speedup for that one step — mirroring
        # ShardingRules.feed_spec_for's replicate-on-indivisible rule
        # instead of crashing the epoch tail (found by driving the
        # surface; pinned in tests/test_grad_sync.py)
        _n_dp = 1
        for _a in gs_axes:
            _n_dp *= gs_ectx.mesh.shape[_a]
        if not any(
                hasattr(env.get(f), "ndim")
                and getattr(env.get(f), "ndim", 0) >= 1
                and env[f].shape[0] > 0 and env[f].shape[0] % _n_dp == 0
                for f in feed_names):
            gs_ectx = None
    if gs_ectx is not None:
        if accum_steps > 1:
            raise ValueError(
                "grad_sync cannot compose with gradient accumulation "
                "yet: the explicit exchange would run per micro-batch "
                "(K quantized all-reduces instead of one).  Use "
                "accumulation with the default GSPMD sync, or "
                "grad_sync without accumulation.")
        loss_val, grads, env = _dp_sync_value_and_grad(
            grad_fwd, fwd_ops, sparse_lookups, trainable, env, rng_key,
            gs_ectx, gs_cfg, feed_names, fwd_keep, gs_axes)
    elif accum_steps <= 1:
        if sparse_lookups:
            loss_val, grads, env = _sparse_value_and_grad(
                grad_fwd, fwd_ops, sparse_lookups, trainable, env,
                rng_key)
        else:
            (loss_val, env_after), grads = jax.value_and_grad(
                grad_fwd, has_aux=True)(trainable, env, rng_key)
            env = env_after
    else:
        # accumulation + sparse grads: dense fallback (SparseGrads don't
        # zeros_like/add in the scan carry); correctness is identical
        loss_val, grads, env = _accumulate_gradients(
            program, grad_fwd, fwd_ops, trainable, env, rng_key,
            accum_steps, feed_names, fetch_names, loss_name)
    if scale is not None:
        # unscale before the finite check and the update ops: the
        # optimizer must see master-scale gradients
        from ..resilience import guard as _guard

        inv = 1.0 / scale
        loss_val = loss_val * inv
        grads = _guard.scale_grads(grads, inv)
        if accum_steps > 1 and loss_name in env:
            # the accumulation scan surfaced the scaled loss
            env[loss_name] = env[loss_name] * inv
    finite = None
    pre_update: Dict[str, Any] = {}
    if guard_cfg is not None:
        from ..resilience import guard as _guard

        finite = _guard.all_finite(loss_val, grads)
        written = set()
        for op in rest_ops[1:]:
            written.update(op.desc.output_names())
        pre_update = _guard.snapshot_env(env, written)
    env[grad_var_name(loss_name)] = loss_val * 0 + 1.0
    for pname, g in grads.items():
        env[grad_var_name(pname)] = g
    # rest_ops[0] is the `backward_marker` op itself; skip it.
    run_ops(rest_ops[1:], env, rng_key, start_index=k + 1,
            amp_lists=amp_lists, program=program)
    if finite is not None:
        # a non-finite step becomes a full state no-op: every value the
        # update ops wrote selects back to its pre-update snapshot
        from ..resilience import guard as _guard

        _guard.select_updates(finite, env, pre_update)
    if getattr(program, "_telemetry_enabled", False):
        # device-side telemetry accumulation (observe pillar 2): pure
        # jnp over values already live in the trace — grads, loss, and
        # the pre/post-update params — so the step stays ONE fused XLA
        # computation with no callbacks/host syncs
        if _obs_metrics.TELEMETRY_VAR in env:
            env[_obs_metrics.TELEMETRY_VAR] = _obs_metrics.device_update(
                env[_obs_metrics.TELEMETRY_VAR], loss_val, grads,
                trainable, env, tracked=tracked)
            if finite is not None:
                from ..resilience import guard as _guard

                env[_obs_metrics.TELEMETRY_VAR] = \
                    _guard.guard_telemetry_update(
                        env[_obs_metrics.TELEMETRY_VAR], finite,
                        guard_cfg)
            if num_on:
                # observe pillar 6: per-group dynamics + the
                # first-nonfinite latch.  Still the same trace; the
                # bitmap is consumed here and never leaves the step.
                from ..observe import numerics as _obs_num

                bits = env.pop(_obs_num.NUMERICS_BITS_VAR)
                tel = _obs_num.device_group_update(
                    env[_obs_metrics.TELEMETRY_VAR], grads, trainable,
                    env, _obs_num.param_groups(trainable))
                env[_obs_metrics.TELEMETRY_VAR] = _obs_num.latch_step_bits(
                    tel, bits,
                    poisoned_extra=None if finite is None else ~finite)
    return env


def _find_sparse_lookups(fwd_ops, trainable, env):
    """(op_index, table, ids_name, padding_idx) for every lookup_table op
    eligible for the SelectedRows-style grad path: is_sparse=True, table
    trainable, ids already in env (a feed/state var — ids computed by
    earlier ops fall back to dense), and the table consumed by nothing
    else (another consumer needs the dense grad for its own path, e.g.
    weight-tied softmax)."""
    candidates = []
    table_lookup_ops = {}
    for idx, op in enumerate(fwd_ops):
        d = op.desc
        if d.type == "lookup_table" and d.attrs.get("is_sparse"):
            tbl = d.inputs["W"][0]
            ids_n = d.inputs["Ids"][0]
            if tbl in trainable and ids_n in env:
                candidates.append(
                    (idx, tbl, ids_n, d.attrs.get("padding_idx", -1)))
                table_lookup_ops.setdefault(tbl, set()).add(idx)
    if not candidates:
        return []
    ineligible = set()
    for idx, op in enumerate(fwd_ops):
        for tbl, own in table_lookup_ops.items():
            if idx not in own and tbl in op.desc.input_names():
                ineligible.add(tbl)
    return [c for c in candidates if c[1] not in ineligible]


def _sparse_value_and_grad(fwd, fwd_ops, sparse_lookups, trainable, env,
                           rng_key):
    """Differentiate w.r.t. gathered embedding rows instead of whole
    tables: the table grad materializes as SparseGrad (ids + rows),
    O(touched) instead of O(vocab) — the SelectedRows capability
    (reference: lookup_table_op.cc grad SelectedRows path)."""
    import jax
    import jax.numpy as jnp

    from ..ops.sparse import gather_rows
    from .selected_rows import SparseGrad

    sparse_tables = {tbl for _i, tbl, _n, _p in sparse_lookups}
    dense_trainable = {k: v for k, v in trainable.items()
                       if k not in sparse_tables}
    rows_init = {
        idx: gather_rows(trainable[tbl], env[ids_n], pad)
        for idx, tbl, ids_n, pad in sparse_lookups
    }

    def fwd_sparse(params_rows, base_env, key):
        params, rows = params_rows
        return fwd(params, base_env, key, sparse_rows=rows)

    (loss_val, env_after), (dense_grads, rows_grads) = jax.value_and_grad(
        fwd_sparse, has_aux=True)((dense_trainable, rows_init), env, rng_key)

    grads = dict(dense_grads)
    per_table = {}
    for idx, tbl, ids_n, _pad in sparse_lookups:
        d = trainable[tbl].shape[-1]
        rows_g = rows_grads[idx].reshape(-1, d)
        ids_flat = env[ids_n].reshape(-1).astype(jnp.int32)
        per_table.setdefault(tbl, []).append((ids_flat, rows_g))
    for tbl, pairs in per_table.items():
        ids_c = (pairs[0][0] if len(pairs) == 1
                 else jnp.concatenate([p[0] for p in pairs]))
        rows_c = (pairs[0][1] if len(pairs) == 1
                  else jnp.concatenate([p[1] for p in pairs]))
        grads[tbl] = SparseGrad(ids_c, rows_c, trainable[tbl].shape)
    return loss_val, grads, env_after


def _dp_sync_value_and_grad(fwd, fwd_ops, sparse_lookups, trainable, env,
                            rng_key, ectx, cfg, feed_names, keep_names,
                            data_axes=None):
    """Data-parallel fwd+bwd with an EXPLICIT gradient exchange
    (docs/DIST.md).  The forward/backward runs inside a shard_map over
    the mesh's DATA axes (the batch axis, plus the fsdp/ZeRO axis when
    present — ISSUE 13): every rank differentiates its local batch
    shard's mean loss, then

      - dense grads sync through `cfg.mode`: exact lax.pmean ("bf16")
        or the EQuARX blockwise-int8 two-phase exchange ("int8") —
        collectives.quantized_all_reduce_local on a single-axis
        fully-manual mesh, its psum-form twin
        (quantized_all_reduce_psum: same quantization, same error
        model, single-psum movement) on multi-axis data groups and
        under partial-auto, where all_to_all/all_gather cannot lower;
        tensors below cfg.min_quant_numel ride the exact psum either
        way (the bf16-fallback floor);
      - SparseGrad STAYS SPARSE: ids+rows gathered over the data axes
        (all_gather on the single-axis manual path, a
        dynamic_update_slice + psum concatenation elsewhere — same
        O(touched-rows) payload, never quantized);
      - the loss pmeans; forward-written values someone reads
        downstream (fetches, persistable BN stats, lr-schedule vars)
        leave the shard_map classified per name: batch-dim outputs
        reassemble to the global batch, replicated floats pmean
        (cross-replica-mean BN semantics), replicated ints pmax.

    Both sync modes produce BITWISE-identical results on every rank
    (fixed-order/all-reduce accumulation + shared bytes), so the
    replicated parameters can never drift apart across data ranks.

    Composition (ISSUE 13): non-data sharded axes (mp/ep/sp) stay
    GSPMD-owned via partial-auto shard_map — params enter with their
    mp shardings intact and the Megatron collectives are still
    GSPMD-inserted inside the body.  The one DESIGNED error left:
    params sharded over a data axis (ZeRO-3-style default="fsdp"
    rules) — the replicated param entry would silently all-gather the
    model every step.

    RNG: each rank folds its linearized data-rank index into the step
    key — dropout draws differ per rank like separate workers' would;
    exact-parity tests against single-device runs therefore pin
    dropout=0.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel.collectives import (compat_shard_map,
                                        quantized_all_reduce_local,
                                        quantized_all_reduce_psum)
    from .selected_rows import SparseGrad

    mesh = ectx.mesh
    axes = tuple(data_axes) if data_axes else (ectx.batch_axis,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    auto = tuple(sorted(a for a, s in mesh.shape.items()
                        if a not in axes and s > 1))
    # the one remaining designed restriction: a param sharded over a
    # DATA axis cannot enter the exchange replicated (it would
    # all-gather the model); mp/ep-sharded params are fine — they ride
    # the auto axes with their shardings intact
    if ectx.rules is not None:
        def _spec_axes(spec):
            for e in spec:
                if e is None:
                    continue
                yield from (e if isinstance(e, (tuple, list)) else (e,))

        bad = sorted(
            pname for pname, v in trainable.items()
            if any(ax in axes for ax in _spec_axes(
                ectx.rules.spec_for(pname, v.shape, mesh))))
        if bad:
            raise ValueError(
                f"grad_sync={cfg.mode!r} cannot run with params "
                f"sharded over the data axes {axes}: {bad[:4]}… enter "
                f"the exchange shard_map replicated, which would "
                f"silently all-gather them every step.  Keep param "
                f"sharding on non-data axes (mp), or use the default "
                f"GSPMD sync for ZeRO-3-style param sharding "
                f"(docs/DIST.md §hybrid).")
    # the collective axis argument: a bare name for single-axis data
    # groups, the tuple for composed dp×fsdp groups
    ax = axes[0] if len(axes) == 1 else axes
    # all_to_all/all_gather survive only the fully-manual single-axis
    # mesh; everything else uses the psum-form exchanges
    psum_only = bool(auto) or len(axes) > 1

    feeds = {}
    for name in feed_names:
        v = env.get(name)
        if (v is not None and hasattr(v, "ndim") and v.ndim >= 1
                and v.shape[0] > 0 and v.shape[0] % n == 0):
            feeds[name] = v
    if not feeds:
        raise ValueError(
            f"grad_sync needs at least one feed with a batch dim "
            f"divisible by {axes}={n}; got "
            f"{[(k, getattr(env.get(k), 'shape', None)) for k in feed_names]}")
    base_env = {k: v for k, v in env.items() if k not in feeds}

    def local_grads(params, feed_shards, key):
        e_in = dict(base_env)
        e_in.update(feed_shards)
        if sparse_lookups:
            return _sparse_value_and_grad(fwd, fwd_ops, sparse_lookups,
                                          params, e_in, key)
        (loss, e_after), grads = jax.value_and_grad(
            fwd, has_aux=True)(params, e_in, key)
        return loss, grads, e_after

    # names the rest of the program reads out of the forward section
    written = set()
    for op in fwd_ops:
        written.update(op.desc.output_names())
    out_names = sorted(written & set(keep_names))

    # classify each out name batch-sharded vs replicated by comparing
    # abstract shapes of a local-shard trace vs a global-batch trace —
    # a leading dim that scales with the feed batch reassembles over
    # the axis, everything else leaves replicated (no shape heuristics
    # that a (C,)-stat-with-C==local_batch coincidence could fool)
    def _shapes(feed_structs):
        out = jax.eval_shape(
            lambda p, f: local_grads(p, f, rng_key)[2],
            trainable, feed_structs)
        return {k: out[k] for k in out_names}

    local_structs = {
        k: jax.ShapeDtypeStruct((v.shape[0] // n,) + v.shape[1:],
                                v.dtype) for k, v in feeds.items()}
    global_structs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                      for k, v in feeds.items()}
    shp_local, shp_global = _shapes(local_structs), _shapes(global_structs)
    batchish = {}
    for name in out_names:
        sl, sg = shp_local[name].shape, shp_global[name].shape
        if sl == sg:
            batchish[name] = False
        elif (len(sl) == len(sg) and sl[1:] == sg[1:]
              and sg[0] == n * sl[0]):
            batchish[name] = True
        else:
            raise ValueError(
                f"grad_sync cannot classify forward output {name!r}: "
                f"local-shard shape {sl} vs global shape {sg} differ "
                f"beyond the leading batch dim")

    # the linearized data-rank index (RNG fold, sparse-concat offset)
    # enters as a SHARDED IOTA input rather than lax.axis_index:
    # axis_index of a manual axis lowers to stablehlo.partition_id,
    # which this XLA's SPMD partitioner rejects inside partial-auto
    # regions ("PartitionId instruction is not supported...") — found
    # the hard way benching dropout on dp×mp.  An arange split over the
    # data axes hands every rank its own index with plain math.
    _rank_holder = []

    def rank_index():
        return _rank_holder[0]

    def gather_concat(v, scale=None):
        """Concatenate per-rank arrays along dim 0 across the data
        group.  Single-axis manual meshes use all_gather; multi-axis /
        partial-auto groups emulate it with dynamic_update_slice +
        psum (all_gather hard-aborts the partitioner there)."""
        if scale is not None:
            v = v * jnp.asarray(scale, v.dtype)
        if not psum_only:
            return jax.lax.all_gather(v, ax, axis=0, tiled=True)
        full = jnp.zeros((n * v.shape[0],) + v.shape[1:], v.dtype)
        start = (rank_index() * v.shape[0],) + (0,) * (v.ndim - 1)
        return jax.lax.psum(jax.lax.dynamic_update_slice(full, v, start),
                            ax)

    def sync_grad(g):
        if isinstance(g, SparseGrad):
            # ids+rows concatenation over the data group: densifies to
            # the same scatter-add sum a global batch would produce —
            # O(touched rows), never quantized
            return SparseGrad(gather_concat(g.ids),
                              gather_concat(g.rows, scale=1.0 / n),
                              g.dense_shape)
        if cfg.mode == "int8":
            if psum_only:
                return quantized_all_reduce_psum(
                    g, ax, n, None, block_size=cfg.block_size,
                    min_quant_numel=cfg.min_quant_numel, op="mean")
            return quantized_all_reduce_local(
                g, ax, n, block_size=cfg.block_size,
                min_quant_numel=cfg.min_quant_numel, op="mean")
        return jax.lax.pmean(g, ax)

    # numerics bitmap (observe pillar 6): per-rank bitmaps differ (each
    # rank sees its own batch shard), so the step bitmap is the exact
    # bitwise OR across the data axes — provenance names the earliest
    # poisoned op on ANY rank
    track_bits = "__numerics_bits__" in base_env

    def body(params, feed_shards, ridx):
        _rank_holder.clear()
        _rank_holder.append(ridx[0])
        key = jax.random.fold_in(rng_key, rank_index())
        loss, grads, e_after = local_grads(params, feed_shards, key)
        loss = jax.lax.pmean(loss, ax)
        grads = {k: sync_grad(g) for k, g in grads.items()}
        outs = []
        for name in out_names:
            v = e_after[name]
            if batchish[name]:
                outs.append(v)
            elif jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating):
                outs.append(jax.lax.pmean(v, ax))
            elif jnp.asarray(v).dtype == jnp.bool_:
                outs.append(jax.lax.pmax(
                    jnp.asarray(v).astype(jnp.int32), ax) > 0)
            else:
                outs.append(jax.lax.pmax(v, ax))
        if track_bits:
            from ..observe import numerics as _obs_num

            outs.append(_obs_num.or_across_axis(
                e_after["__numerics_bits__"], ax))
        return loss, grads, tuple(outs)

    batch_entry = axes[0] if len(axes) == 1 else tuple(axes)
    out_specs = (P(), P(), tuple(
        P(batch_entry) if batchish[name] else P() for name in out_names)
        + ((P(),) if track_bits else ()))
    sm = compat_shard_map(
        body, mesh,
        in_specs=(P(), {k: P(batch_entry) for k in feeds},
                  P(batch_entry)),
        out_specs=out_specs, auto=frozenset(auto))
    loss_val, grads, outs = sm(trainable, feeds,
                               jnp.arange(n, dtype=jnp.int32))
    if track_bits:
        env["__numerics_bits__"] = outs[-1]
        outs = outs[:-1]
    for name, val in zip(out_names, outs):
        env[name] = val
    return loss_val, grads, env


def _accumulate_gradients(program, fwd, fwd_ops, trainable, env, rng_key,
                          accum_steps, feed_names, fetch_names, loss_name):
    """K-micro-batch gradient accumulation as a lax.scan.

    Feeds are reshaped (B, ...) → (K, B/K, ...); the scan body computes
    per-micro-batch grads (each micro-step gets its own RNG stream so
    dropout masks differ, like separate steps would).  Returns
    (mean loss, mean grads, env) where env holds: forward activations from
    a representative micro-batch for downstream ops, micro-averaged values
    for fetched forward vars (batch-mean metrics stay correct), and
    last-micro-batch values for persistable forward outputs (BN moving
    stats follow the same last-wins rule as sequential steps).
    """
    import jax
    import jax.numpy as jnp

    block = program.global_block()
    feeds = {}
    for n in feed_names:
        if n not in env:
            continue
        v = env[n]
        if v.ndim == 0 or v.shape[0] % accum_steps != 0:
            raise ValueError(
                f"gradient accumulation with {accum_steps} steps needs "
                f"feed {n!r} batch dim divisible; got shape {v.shape}")
        feeds[n] = v.reshape((accum_steps, v.shape[0] // accum_steps)
                             + v.shape[1:])
    if not feeds:
        raise ValueError("gradient accumulation requires batched feeds")
    base_env = {n: v for n, v in env.items() if n not in feeds}

    fwd_out_names = set()
    for op in fwd_ops:
        fwd_out_names.update(op.desc.output_names())
    # Vars the post-marker (optimizer/metric-update) ops read but the
    # forward section produces — e.g. the lr-schedule value — must survive
    # the scan; identical across micro-batches unless feed-dependent, so
    # last-wins matches sequential-step semantics.
    k = program._backward_info["index"]
    rest_reads = set()
    for op in block.ops[k + 1:]:
        rest_reads.update(op.desc.input_names())
    persist_written = sorted(
        n for n in fwd_out_names
        if (block.has_var(n) and block.var(n).persistable)
        or n in rest_reads)
    fetch_fwd = sorted(n for n in fetch_names
                       if n in fwd_out_names and n != loss_name
                       and n not in persist_written)

    grad_fn = jax.value_and_grad(fwd, has_aux=True)
    micro_b = next(iter(feeds.values())).shape[1]
    # State-like names that pre-exist in env (BN moving stats) thread
    # through the scan carry so K micro-batches compound K updates, exactly
    # like K sequential steps (and multi_batch_merge_pass's K clones);
    # names only computed inside the forward (the lr-schedule value) are
    # surfaced via the scan outputs instead (last value).
    carried = sorted(n for n in persist_written if n in env)
    computed = sorted(n for n in persist_written if n not in env)

    # numerics bitmap (observe pillar 6): each micro-batch starts from
    # the step's zeroed bitmap in base_env; the per-micro-batch results
    # are OR-merged below so the step-level bitmap covers all K
    track_bits = "__numerics_bits__" in base_env

    def body(carry, inp):
        gacc, persist = carry
        idx, mslice = inp
        e_in = dict(base_env)
        e_in.update(persist)
        e_in.update(mslice)
        key = jax.random.fold_in(rng_key, 31337 + idx)
        (loss, e_after), grads = grad_fn(trainable, e_in, key)
        gacc = jax.tree_util.tree_map(jnp.add, gacc, grads)
        new_persist = {n: e_after[n] for n in carried}
        ys = (loss, tuple(e_after[n] for n in fetch_fwd),
              tuple(e_after[n] for n in computed))
        if track_bits:
            ys = ys + (e_after["__numerics_bits__"],)
        return (gacc, new_persist), ys

    gzero = jax.tree_util.tree_map(jnp.zeros_like, trainable)
    idxs = jnp.arange(accum_steps)
    init_persist = {n: env[n] for n in carried}
    (gsum, final_persist), ys_out = \
        jax.lax.scan(body, (gzero, init_persist), (idxs, feeds))
    bits_stack = None
    if track_bits:
        losses, fetch_stacks, computed_stacks, bits_stack = ys_out
    else:
        losses, fetch_stacks, computed_stacks = ys_out
    inv = 1.0 / accum_steps
    grads = jax.tree_util.tree_map(lambda g: g * inv, gsum)
    loss_val = jnp.mean(losses)

    # Rebuild env for downstream (optimizer) ops: forward activations are
    # not needed by them, but fetches and persistable updates are.
    env = dict(base_env)
    loss_decl = block.var(loss_name).shape if block.has_var(loss_name) else ()
    env[loss_name] = (jnp.reshape(loss_val, loss_decl)
                      if all(d > 0 for d in loss_decl) else loss_val)
    for n, v in zip(fetch_fwd, fetch_stacks):
        # v: (K, ...) stacked micro-batch values.  Per-example outputs
        # (leading dim == micro batch) concatenate back to the full batch;
        # batch-aggregate values (scalars/means) average — correct for
        # equal-size micro-batches.
        if v.ndim >= 2 and v.shape[1] == micro_b:
            env[n] = v.reshape((-1,) + v.shape[2:])
        else:
            env[n] = jnp.mean(v, axis=0)
    env.update(final_persist)
    for n, v in zip(computed, computed_stacks):
        env[n] = v[-1]
    if bits_stack is not None:
        merged = bits_stack[0]
        for t in range(1, accum_steps):
            merged = merged | bits_stack[t]
        env["__numerics_bits__"] = merged
    # keep full-batch feeds visible for any fetch of a feed var
    for n in feeds:
        env[n] = feeds[n].reshape((-1,) + feeds[n].shape[2:])
    return loss_val, grads, env


def _debug_checks(fetch_names, fetches, new_state):
    """FLAGS.check_nan_inf: the reference's post-op NaN scan
    (operator.cc:943 under FLAGS_check_nan_inf), applied per run to
    fetches and updated state; FLAGS.benchmark forces a blocking sync
    (operator.cc:940)."""
    from ..flags import FLAGS

    if FLAGS.check_nan_inf:
        for n, f in zip(fetch_names, fetches):
            arr = np.asarray(f)
            if arr.dtype.kind == "f" and not np.isfinite(arr).all():
                raise FloatingPointError(
                    f"NaN/Inf detected in fetched var {n!r}")
        for n, v in new_state.items():
            arr = np.asarray(v)
            if arr.dtype.kind == "f" and not np.isfinite(arr).all():
                raise FloatingPointError(
                    f"NaN/Inf detected in persistable var {n!r}")
    elif FLAGS.benchmark:
        for f in fetches:
            getattr(f, "block_until_ready", lambda: None)()


def chain_iterations(base_step, iterations: int):
    """Iteration batching: chain K executions of the program over the
    SAME feeds in one compiled call, amortizing host dispatch.  Note the
    feeds are frozen for all K iterations — this accelerates fixed-input
    loops (synthetic-data benchmarks, lr-search sweeps, steady-state
    profiling), NOT epoch training; feeding fresh batches still requires
    one run() per batch (device-side input pipelines come with the data
    plane).  Valid because state shapes are step-invariant."""
    if iterations <= 1:
        return base_step
    import jax

    def step(state, feeds):
        st, fetches = base_step(state, feeds)

        def body(_, carry):
            st, _f = carry
            return base_step(st, feeds)

        return jax.lax.fori_loop(1, iterations, body, (st, fetches))

    return step


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

class Executor:
    """Compile-and-run engine (reference: python/paddle/fluid/executor.py:445
    Executor.run and paddle/fluid/framework/executor.cc).

    place is accepted for API parity; JAX device placement is controlled by
    the platform (real TPU) or by CompiledProgram shardings (parallel/).
    """

    def __init__(self, place=None):
        self.place = place
        # key -> (step fn, its state shardings under a placement, the
        # feed signatures it has been called with: a NEW shape/dtype
        # signature on an already-built step fn means jax will retrace
        # and recompile it — counted as a retrace, observe pillar 2)
        self._cache: Dict[Any, Any] = {}
        # AOT-compiled steps for cost analysis / optimized-HLO access
        # (compiled_step): memoized so cost_analysis + observe.cost on
        # the same program pay one extra compile, not two
        self._aot_cache: Dict[Any, Any] = {}
        from ..observe import monitoring as _obs_monitoring

        _obs_monitoring.install()

    # -- public API ------------------------------------------------------
    def run(self, program: Optional[Program] = None,
            feed: Optional[Dict[str, Any]] = None,
            fetch_list: Optional[Sequence[Any]] = None,
            scope: Optional[Scope] = None,
            return_numpy: bool = True,
            use_program_cache: bool = True,
            iterations: int = 1,
            accumulation_steps: int = 1):
        from .program import default_main_program
        from ..observe.monitoring import runtime_stats

        program, placement = _resolve_placement(
            program or default_main_program())
        scope = scope or global_scope()
        feed = dict(feed or {})
        fetch_names = _fetch_names(fetch_list)

        # replaced whenever a step fn is built, a feed signature is new
        # or jax traces, lowers, compiles or reads its cache: another
        # object after the call means this run was cold
        heard = runtime_stats.heard
        fn, state, feed_arrays = self._prepare(
            program, feed, fetch_names, scope, iterations,
            use_program_cache, accumulation_steps, placement)
        with runtime_stats.phase("call"):
            new_state, fetches = fn(state, feed_arrays)
        cold = runtime_stats.heard is not heard
        with runtime_stats.phase("writeback"):
            for name, val in new_state.items():
                scope.set_var(name, val)
            # the last references to the donated arrays: freeing some
            # 600 of them is host time of the step, so it is timed
            del state
            _debug_checks(fetch_names, fetches, new_state)
            if return_numpy:
                fetches = [np.asarray(f) for f in fetches]
        if cold:
            runtime_stats.record_cold_run(
                heard, program=program._uid,
                ops=len(program.global_block().ops),
                state_arrays=len(new_state), feed_arrays=len(feed),
                fetches=len(fetch_names),
                placement=placement is not None)
        return fetches

    def close(self):
        self._cache.clear()
        self._aot_cache.clear()

    def compiled_step(self, program: Program, feed=None, fetch_list=None,
                      scope: Optional[Scope] = None,
                      with_names: bool = False):
        """AOT-compile the one-iteration step and return the jax
        Compiled object (cost_analysis(), as_text(), the optimized HLO
        module via observe.cost.compiled_hlo_proto, memory_analysis via
        observe.memory).  One extra XLA compile beyond run()'s own jit
        cache (the jit-internal executable is not introspectable); the
        traced step fn itself is shared via the program cache, and the
        Compiled is memoized per (program, feed-signature) so
        cost_analysis + observe.cost/.memory on the same step compile
        once.  Always the ONE-DEVICE step, also of a Program that
        carries a placement (its unsharded twin); the sharded step is
        CompiledProgram.compiled_step.

        with_names=True returns (compiled, arg_names): one
        ("state"|"feed", var_name) label per flattened step argument in
        jax's pytree leaf order — the HLO entry parameter order —
        which is how observe.memory attributes entry-parameter buffers
        to named state vars (params vs optimizer accumulators)."""
        return self._compiled_step(program, dict(feed or {}),
                                   _fetch_names(fetch_list), scope, 1,
                                   with_names)

    def _compiled_step(self, program: Program, feed, fetch_names, scope,
                       iterations: int, with_names: bool, placement=None):
        fn, state, feed_arrays = self._prepare(
            program, feed, fetch_names, scope or global_scope(),
            iterations, True, 1, placement)
        key = (program._uid, program._version, tuple(sorted(feed)),
               tuple(fetch_names), iterations,
               _feed_signature(feed_arrays))
        aot_cache = (self._aot_cache if placement is None
                     else placement._aot_cache)
        entry = aot_cache.get(key)
        if entry is None:
            from ..observe.memory import _arg_labels

            compiled = fn.lower(state, feed_arrays).compile()
            entry = (compiled,
                     _arg_labels(state, feed_arrays, compiled=compiled))
            aot_cache[key] = entry
        return entry if with_names else entry[0]

    def cost_analysis(self, program: Program, feed=None, fetch_list=None,
                      scope: Optional[Scope] = None):
        """XLA cost analysis of the compiled one-iteration step (flops,
        bytes accessed).  TPU analog of the reference profiler's per-op
        accounting — here the unit is the whole fused step.  Returns the
        backend's dict (keys like 'flops', 'bytes accessed').  Note:
        XLA's aggregate 'bytes accessed' overcounts real HBM traffic
        and Pallas custom calls report zero flops — observe.cost holds
        the analytic per-op accounting built on the same compile."""
        compiled = self.compiled_step(program, feed=feed,
                                      fetch_list=fetch_list, scope=scope)
        analyses = compiled.cost_analysis()
        # PJRT returns one dict (or a list with one per executable)
        if isinstance(analyses, (list, tuple)):
            analyses = analyses[0]
        return dict(analyses)

    def _prepare(self, program: Program, feed, fetch_names, scope,
                 iterations: int, use_program_cache: bool,
                 accumulation_steps: int = 1, placement=None):
        """Shared run()/compiled_step() setup, as the step's first two
        host phases (observe.monitoring): `prepare` (`_lookup_step`)
        and `place` (feed conversion; under a placement `_place` for
        the state and for the feed, with a child span each, counted in
        `place_puts` / `place_skips`; and the retrace check, which reads
        the converted feed's dtypes)."""
        from ..observe.monitoring import SPAN_PREFIX, runtime_stats

        with runtime_stats.phase("prepare"):
            fn, state, shardings, seen = self._lookup_step(
                program, feed, fetch_names, scope, iterations,
                use_program_cache, accumulation_steps, placement)
        with runtime_stats.phase("place"):
            if placement is None:
                block = program.global_block()
                feed_arrays = {n: _to_array(v, block)
                               for n, v in feed.items()}
            else:
                from jax.profiler import TraceAnnotation

                state_shardings, feed_shardings = shardings
                with TraceAnnotation(SPAN_PREFIX + "place_state"):
                    state = _place(state, state_shardings)
                with TraceAnnotation(SPAN_PREFIX + "place_feed"):
                    feed_arrays = _place(
                        {n: _one_array(v) for n, v in feed.items()},
                        feed_shardings)
            sig = _feed_signature(feed_arrays)
            if seen and sig not in seen:
                runtime_stats.record_retrace()
            seen.add(sig)
        return fn, state, feed_arrays

    def _lookup_step(self, program: Program, feed, fetch_names, scope,
                     iterations: int, use_program_cache: bool,
                     accumulation_steps: int, placement=None):
        """RNG and telemetry state, state gathering, program-cache
        lookup and, on a miss, the step's build.  Returns (step fn,
        state as the scope holds it, under a placement the (state,
        feed) shardings `place` puts them to, the feed signatures the
        step fn has seen)."""
        import jax

        block = program.global_block()
        # Ensure RNG state exists whenever any op may need randomness.
        if RNG_STATE_VAR not in scope.vars:
            scope.set_var(RNG_STATE_VAR,
                          jax.random.PRNGKey(program.random_seed))
        state_names = tuple(sorted(
            v.name for v in block.vars.values()
            if v.persistable and scope.has_var(v.name)
        ))
        from ..observe import metrics as _obs_metrics
        from ..observe.monitoring import runtime_stats

        telemetry = getattr(program, "_telemetry_enabled", False)
        if telemetry:
            # the accumulator rides in the state pytree (donated,
            # carried through chain_iterations); creating it here keeps
            # enable_telemetry() a pure program-level flag flip.
            # init_telemetry_for sizes the numerics fields (per-group
            # vectors + per-op bitmap) when the program opted in
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
        state = {n: scope.find_var(n) for n in state_names}
        state[RNG_STATE_VAR] = scope.find_var(RNG_STATE_VAR)

        feed_names = tuple(sorted(feed))
        if placement is None:
            cache, feed_key, feed_shardings = self._cache, feed_names, None
        else:
            # the step cache lives on the placement: a second Executor
            # over one CompiledProgram does not recompile
            cache = placement._cache
            mesh = placement._ensure_mesh()
            if accumulation_steps == 1:
                # an explicit per-run override wins over the
                # BuildStrategy knob
                accumulation_steps = placement._accum_steps
            feed_shardings = {n: placement._feed_sharding(n, v)
                              for n, v in feed.items()}
            # the chosen feed shardings are part of the key: a final
            # partial batch that is no longer dp-divisible must
            # recompile with a replicated layout rather than reuse the
            # sharded executable
            feed_key = (id(mesh),) + tuple(sorted(
                (n, str(s.spec)) for n, s in feed_shardings.items()))
        key = (program._uid, program._version, feed_key,
               tuple(fetch_names), state_names, iterations,
               accumulation_steps)
        entry = cache.get(key) if use_program_cache else None
        if entry is None:
            trace_context, state_shardings = contextlib.nullcontext, None
            if placement is not None:
                trace_context = placement._trace_context
                state_shardings = {n: placement._state_sharding(n, v)
                                   for n, v in state.items()}
            fn = self._build_step_fn(
                program, feed_names, tuple(fetch_names), iterations,
                accumulation_steps, trace_context, state_shardings,
                feed_shardings)
            runtime_stats.record_build()
            entry = (fn, state_shardings, set())
            if use_program_cache:
                cache[key] = entry
        fn, state_shardings, seen = entry
        return fn, state, (state_shardings, feed_shardings), seen

    # -- compilation -----------------------------------------------------
    def _build_step_fn(self, program: Program, feed_names, fetch_names,
                       iterations: int = 1, accumulation_steps: int = 1,
                       trace_context=contextlib.nullcontext,
                       state_shardings=None, feed_shardings=None):
        """The jitted step.  A placement adds `trace_context` (the
        executing-mesh context mesh-aware op impls read, entered around
        the trace) and the shardings of the step's arguments."""
        import jax

        persistable_names = tuple(sorted(
            v.name for v in program.global_block().vars.values()
            if v.persistable
        ))

        def step(state, feeds):
            rng_key = state[RNG_STATE_VAR]
            env: Dict[str, Any] = {}
            env.update({k: v for k, v in state.items()
                        if k != RNG_STATE_VAR})
            env.update(feeds)
            with trace_context():
                env = interpret_program(program, env, rng_key,
                                        fetch_names=fetch_names,
                                        accum_steps=accumulation_steps,
                                        feed_names=feed_names)
            new_state = {
                n: env[n] for n in persistable_names if n in env
            }
            from ..observe.metrics import TELEMETRY_VAR

            if TELEMETRY_VAR in env:
                # not a block var; threads the step (and the
                # chain_iterations carry) as executor-private state
                new_state[TELEMETRY_VAR] = env[TELEMETRY_VAR]
            new_state[RNG_STATE_VAR] = jax.random.split(rng_key, 1)[0]
            fetches = [env[n] for n in fetch_names]
            return new_state, fetches

        placed = {}
        if state_shardings is not None:
            # pin the updated state to the SAME shardings it came in
            # with: without this XLA may infer a different (replicated)
            # output layout for ZeRO-sharded optimizer state, which
            # silently breaks donation — per-device opt-state bytes
            # then DOUBLE (input + undonated output) and an all-gather
            # sneaks into every step
            placed = dict(in_shardings=(state_shardings, feed_shardings),
                          out_shardings=(state_shardings, None))
        return jax.jit(chain_iterations(step, iterations),
                       donate_argnums=(0,), **placed)


def _resolve_placement(program):
    """(Program, placement): the placement is the CompiledProgram that
    was passed in the Program's place (fluid style) or that
    `with_data_parallel` hung on the Program; None on one device."""
    if hasattr(program, "_program") and hasattr(program, "_state_sharding"):
        return program._program, program
    return program, getattr(program, "_compiled_wrapper", None)


def _fetch_names(fetch_list) -> List[str]:
    return [f.name if isinstance(f, Variable) else str(f)
            for f in (fetch_list or [])]


def _feed_signature(feed_arrays) -> tuple:
    return tuple(
        (n, tuple(getattr(v, "shape", ()) or ()),
         str(getattr(v, "dtype", type(v).__name__)))
        for n, v in sorted(feed_arrays.items()))


def _place(values, shardings):
    """`values` laid out as `shardings` say, for the step's
    `in_shardings`: what already lies there is passed through as the
    object it is (after the first step the whole state: the step's
    `out_shardings` are these shardings); anything else goes to its
    sharding in ONE `jax.device_put`, which cuts a host value on the
    host and sends each chip its own piece while the previous step
    runs.  A host value must not pass through one chip on the way
    (`jnp.asarray`): resharding from there is a program on that chip's
    stream, behind the running step, and chip-to-chip copies the next
    step then waits for.  Counted in `runtime_stats.place_puts` /
    `place_skips`."""
    import jax

    from ..observe.monitoring import runtime_stats

    placed, puts = {}, 0
    for name, value in values.items():
        if not _lies_at(value, shardings[name]):
            value = jax.device_put(value, shardings[name])
            puts += 1
        placed[name] = value
    runtime_stats.record_place(puts, len(placed) - puts)
    return placed


def _lies_at(value, sharding) -> bool:
    """An array whose real sharding is `sharding`; of the telemetry
    accumulator (a dict of arrays under one sharding), every leaf."""
    import jax

    if isinstance(value, dict):
        return all(_lies_at(v, sharding) for v in value.values())
    return isinstance(value, jax.Array) and value.sharding == sharding


def _one_array(value):
    """A feed as `jax.device_put` reads ONE array from.  Arrays and
    scalars go as they are: `device_put` gives them the dtype
    `jnp.asarray` would (int64 -> int32, float64 -> float32, a Python
    scalar weakly typed).  A list it would read as a pytree of
    scalars: the numpy array of it."""
    return np.asarray(value) if isinstance(value, (list, tuple)) else value


def _to_array(value, block):
    import jax.numpy as jnp

    if isinstance(value, np.ndarray):
        return jnp.asarray(value)
    if isinstance(value, (int, float, list, tuple)):
        return jnp.asarray(value)
    return value  # already a jax Array
