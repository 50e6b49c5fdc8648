"""Pipeline parallelism: a GPipe microbatch scheduler over a mesh axis.

The 1.2 reference predates pipeline parallelism (Paddle's
PipelineOptimizer landed later); pp is first-class on TPU pods, so the
primitive lives here alongside dp/tp/fsdp/sp/ep.  TPU-first design:
stages are S copies of one stage function whose stacked parameters
(leading dim S) shard over the mesh's `pp` axis; the schedule is a
`lax.scan` over T = n_micro + S - 1 ticks inside `shard_map`, with
`lax.ppermute` handing each microbatch's activation to the next stage
every tick — the classic GPipe wavefront (bubble fraction
(S-1)/(n_micro + S - 1); raise n_micro to amortize).  Reverse-mode AD
flows through ppermute/scan (ppermute transposes to the reverse
permutation), so `jax.grad` of a loss on the pipeline output yields
per-stage parameter gradients without any hand-written backward
schedule.

Capabilities (round 5; the round-4 primitive took a single array):
- activations are PYTREES: stage_fn maps a pytree of arrays to a
  same-structure, same-shape pytree (room for (hidden, attention-bias,
  encoder-context, ...) bundles — invariant leaves just pass through),
- inputs can arrive SCATTERED over the pp axis (each rank holds
  n_micro/S microbatches; a one-slot-per-tick ppermute conveyor streams
  them to stage 0) so no rank ever materializes the full batch,
- a dp axis composes: `batch_axis=` keeps the per-microbatch batch dim
  sharded inside the shard_map (each dp group pipelines its own shard;
  stage-parameter gradients are psum'd over dp in the backward).

Constraints (documented, enforced):
- every stage maps activations of one fixed pytree-of-shapes to itself
  (transformer-block pipelines satisfy this; embed/head layers run
  outside the pipelined region),
- stage_params is a pytree whose every leaf has leading dim S.

Memory strategy: the schedule is GPipe (all-forward, then AD's
transpose runs all-backward), NOT 1F1B.  The TPU-first answer to
GPipe's activation footprint is REMAT, not schedule surgery: wrap
stage_fn in jax.checkpoint (the pipeline engine does this when the
layers carry fluid.recompute_scope tags) and the backward re-runs each
tick's forward from its input — per-rank live activations drop to the
O(n_micro) tick inputs, the same asymptotics 1F1B buys, traded for
one extra forward pass of FLOPs that XLA overlaps well on the MXU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def gpipe(stage_fn, mesh, axis: str = "pp", batch_axis=None,
          scatter_inputs=None):
    """Build a pipelined apply: `fn(stacked_params, micro_x) -> out`.

    stage_fn(params_s, x) -> y, x/y pytrees with identical structure
    and shapes (a single array works as a one-leaf pytree);
    stacked_params: pytree, leaves (S, ...) — stage s uses leaf[s];
    micro_x: pytree, every leaf (n_micro, B_micro, ...) microbatched.
    Returns out with micro_x's structure/shapes =
    stage_{S-1}(...stage_0(x)).

    batch_axis: mesh axis the per-microbatch batch dim (leaf dim 1) is
    sharded over (e.g. "dp" on a dp x pp mesh) — without it the
    shard_map boundary would all-gather dp-sharded activations and
    every dp group would redo the full compute.
    scatter_inputs: shard micro_x's microbatch dim over the pp axis
    (needs S | n_micro) and stream microbatches to stage 0 via a
    ppermute conveyor.  None = auto (on when S divides n_micro).
    """
    from jax.sharding import PartitionSpec as P

    s = mesh.shape[axis]
    perm_fwd = [(i, i + 1) for i in range(s - 1)]
    # input conveyor: a full ring rotated one slot toward rank 0 per
    # tick (rank r's head -> rank r-1; consumed items recirculate
    # through rank S-1's tail, so rank 0 sees microbatch t at tick t)
    perm_conv = [(i, (i - 1) % s) for i in range(s)]
    b_ax = (batch_axis if batch_axis
            and mesh.shape.get(batch_axis, 1) > 1 else None)
    dp = mesh.shape.get(b_ax, 1) if b_ax else 1

    def leaf_spec(l, scattered):
        dims = [axis if scattered else None]
        # a batch dim that doesn't divide dp degrades to replicated —
        # each dp rank then redundantly computes it (perf, not
        # correctness: shard_map's transpose psums per-shard cotangents
        # and passes replicated ones through correctly in either
        # layout; pinned by test_gpipe_dp_gradients_match including the
        # mb=1 indivisible case)
        if l.ndim >= 2 and l.shape[1] % dp == 0:
            dims.append(b_ax)
        dims += [None] * (l.ndim - len(dims))
        return P(*dims)

    def pipelined(stacked_params, micro_x):
        leaves = jax.tree.leaves(micro_x)
        if not leaves:
            raise ValueError("gpipe: micro_x has no array leaves")
        n_micro = leaves[0].shape[0]
        if any(l.shape[0] != n_micro for l in leaves):
            raise ValueError(
                "gpipe: every micro_x leaf needs the same leading "
                f"(n_micro) dim; got {[l.shape for l in leaves]}")
        scatter = (n_micro % s == 0 if scatter_inputs is None
                   else scatter_inputs)
        if scatter and n_micro % s != 0:
            raise ValueError(
                f"gpipe(scatter_inputs=True): n_micro ({n_micro}) must "
                f"be divisible by the {axis!r} axis size ({s})")
        ticks = n_micro + s - 1

        in_x_spec = jax.tree.map(lambda l: leaf_spec(l, scatter), micro_x)
        out_spec = jax.tree.map(lambda l: leaf_spec(l, False), micro_x)

        # On a MULTI-AXIS mesh (dp×pp), params enter the shard_map
        # fully replicated (P()) and each rank slices out its own stage
        # inside the body.  The obvious P(axis) stage-sliced entry is
        # WRONG on this jax/XLA version when the stacked array is a
        # jit-internal value (the engine stacks env params mid-program):
        # the SPMD partitioner delivers each rank's slice dp-SUMMED
        # instead of replicated — every layer's weights arrive
        # multiplied by the dp degree.  Caught by
        # tests/test_pipeline_engine.py::test_pipelined_transformer_dp_x_pp;
        # minimal repro in tests/test_gpipe.py::
        # test_gpipe_dp_x_pp_with_jit_internal_stacked_params.  Neither
        # with_sharding_constraint, optimization_barrier, nor
        # mentioning dp via a broadcast dim avoids it — only the
        # fully-replicated entry does.  Cost: inside the manual region
        # each device transiently holds all S stages' params instead of
        # 1/S, so pure-pp meshes (where the sliced entry is correct)
        # keep the memory-lean path.
        multi_axis = any(name != axis and size > 1
                         for name, size in mesh.shape.items())
        if multi_axis:
            param_spec = jax.tree.map(lambda _: P(), stacked_params)
        else:
            param_spec = jax.tree.map(lambda _: P(axis), stacked_params)

        @partial(
            jax.shard_map, mesh=mesh,
            in_specs=(param_spec, in_x_spec),
            out_specs=out_spec,
            check_vma=False)
        def run(params, xs):
            rank = lax.axis_index(axis)
            if multi_axis:
                # full (S, ...) leaves on every device: take this
                # rank's stage (transpose: scatter + psum over the
                # replicated-in axes = the correct dp grad sum, pinned
                # by tests/test_gpipe.py::test_gpipe_dp_gradients_match)
                params = jax.tree.map(
                    lambda l: lax.dynamic_index_in_dim(
                        l, rank, 0, keepdims=False), params)
            else:
                # stage-sliced entry: leaves are (1, ...) local shards
                params = jax.tree.map(lambda l: l[0], params)
            zero = jax.tree.map(lambda l: jnp.zeros(l.shape[1:], l.dtype),
                                xs)

            def where(pred, a, b):
                return jax.tree.map(partial(jnp.where, pred), a, b)

            def ppermute(t, perm):
                return jax.tree.map(
                    lambda l: lax.ppermute(l, axis, perm), t)

            def step(x_in, handoff, t):
                # stage index is data-dependent (one trace runs on every
                # pp rank), so the scope names the schedule phase; the
                # stage body's own op scopes nest inside it
                with jax.named_scope("gpipe_stage"):
                    y = stage_fn(params, x_in)
                mb = t - rank
                active = (mb >= 0) & (mb < n_micro)
                y = where(active, y, zero)
                with jax.named_scope("gpipe_handoff"):
                    return ppermute(y, perm_fwd), y

            if scatter:
                def tick(carry, t):
                    handoff, conv = carry
                    head = jax.tree.map(lambda c: c[0], conv)
                    x_in = where(rank == 0, head, handoff)
                    new_handoff, y = step(x_in, handoff, t)
                    with jax.named_scope("gpipe_conveyor"):
                        sent = ppermute(head, perm_conv)
                    conv = jax.tree.map(
                        lambda c, sv: jnp.concatenate(
                            [c[1:], sv[None]], axis=0), conv, sent)
                    return (new_handoff, conv), y

                (_, _), ys = lax.scan(tick, (zero, xs),
                                      jnp.arange(ticks))
            else:
                def tick(handoff, t):
                    x_t = jax.tree.map(
                        lambda l: l[jnp.clip(t, 0, n_micro - 1)], xs)
                    x_in = where(rank == 0, x_t, handoff)
                    new_handoff, y = step(x_in, handoff, t)
                    return new_handoff, y

                _, ys = lax.scan(tick, zero, jnp.arange(ticks))

            # microbatch m leaves the last stage at tick m + (S-1):
            # ys[s-1:] on the last rank is the pipeline output
            outs = jax.tree.map(
                lambda l: lax.dynamic_slice_in_dim(l, s - 1, n_micro, 0),
                ys)
            # broadcast the last stage's result to every pp rank so the
            # out_spec (replicated over pp) is truthful
            last = (rank == s - 1)
            return jax.tree.map(
                lambda l: lax.psum(l * last.astype(l.dtype), axis), outs)

        return run(stacked_params, micro_x)

    return pipelined


def gpipe_loss_and_grad(stage_fn, loss_fn, mesh, axis: str = "pp",
                        batch_axis=None, scatter_inputs=None):
    """Convenience: (stacked_params, micro_x, micro_y) ->
    (mean loss, grads w.r.t. stacked_params) through the pipeline."""
    fwd = gpipe(stage_fn, mesh, axis, batch_axis=batch_axis,
                scatter_inputs=scatter_inputs)

    def loss(params, micro_x, micro_y):
        out = fwd(params, micro_x)
        return jnp.mean(jax.vmap(loss_fn)(out, micro_y))

    return jax.value_and_grad(loss)
