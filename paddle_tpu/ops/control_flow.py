"""Control-flow operators: sub-blocks lowered to lax primitives.

TPU-native analog of the reference's interpreter-level control flow
(reference: paddle/fluid/operators/controlflow/while_op.cc:50,125 — runs a
sub-block via a nested Executor with StepScopes; recurrent_op.cc:222 —
dynamic RNN over time steps; conditional_block_op.cc; beam_search_op.cc;
tensor_array_read_write_op.cc).  Instead of a nested interpreter with step
scopes, each macro op traces its sub-block *inside* a `lax.while_loop` /
`lax.scan` / `lax.switch` body, so the whole loop compiles to one XLA
computation with static shapes:

- `while`      → lax.while_loop over the loop-carried write set
- `switch`     → lax.switch over case sub-blocks (scalar conditions)
- `static_rnn` → lax.scan over the time dimension (differentiable; this is
                 the training-time recurrence, replacing recurrent_op's
                 replay-based gradient)
- `dynamic_rnn`→ lax.scan with per-example seq_len masking (padded+seq_len
                 replaces LoD / lod_rank_table reordering machinery)
- tensor arrays→ fixed-capacity (buffer, length) pairs with dynamic
                 update/index (replaces LoDTensorArray, which grew
                 dynamically — XLA requires a static capacity)
- `beam_search`/`beam_search_decode` → dense (batch, beam) top-k step and
                 reverse-scan backtrace (replaces the LoD-linked
                 beam_search_op.cc contract)

Divergence notes: `lax.while_loop` is not reverse-differentiable, so
training-time recurrence must use static_rnn/dynamic_rnn (scan); While is
for inference/decoding loops — matching how the reference's own while_grad
was in practice exercised only through RNN-style patterns.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_macro_op, register_op
from .common import first, out


# ---------------------------------------------------------------------------
# while
# ---------------------------------------------------------------------------

@register_macro_op("while")
def while_op(ctx, env, desc):
    """inputs: Condition (scalar bool var), X (outer reads, for pruning);
    outputs: Out (loop-carried vars: every outer var written in the body);
    attrs: sub_block (block index).

    The loop carry is [condition] + Out; the body re-traces the sub-block
    with carry values spliced into a copy of the surrounding env.
    """
    cond_name = desc.inputs["Condition"][0]
    out_names = [n for n in desc.outputs.get("Out", []) if n != cond_name]
    carry_names = [cond_name] + out_names
    sub_block = desc.attrs["sub_block"]

    def cond_fn(carry):
        return jnp.reshape(carry[0], ()).astype(bool)

    def body_fn(carry):
        e = dict(env)
        e.update(zip(carry_names, carry))
        ctx.run_block(sub_block, e)
        new = []
        for name, old in zip(carry_names, carry):
            v = e[name]
            # Keep carry dtypes stable (weak-type drift from python scalars
            # would change the carry signature between iterations).
            if hasattr(old, "dtype") and hasattr(v, "dtype") \
                    and v.dtype != old.dtype:
                v = v.astype(old.dtype)
            new.append(v)
        return tuple(new)

    init = tuple(env[n] for n in carry_names)
    final = lax.while_loop(cond_fn, body_fn, init)
    env.update(zip(carry_names, final))


# ---------------------------------------------------------------------------
# switch (scalar multi-way conditional; fluid layers.Switch / conditional_block)
# ---------------------------------------------------------------------------

@register_macro_op("switch")
def switch_op(ctx, env, desc):
    """inputs: Conditions (list of scalar bool vars, checked in order);
    outputs: Out (vars any case may write; must pre-exist in env);
    attrs: case_blocks (list of block indices, one per condition),
           default_block (block index or -1).

    Lowered to lax.switch: the selected branch index is the first true
    condition (or the default).  Like fluid's Switch (built on
    conditional_block_op.cc), untaken branches are not executed.
    """
    conds = [jnp.reshape(env[n], ()).astype(bool)
             for n in desc.inputs.get("Conditions", [])]
    out_names = desc.outputs.get("Out", [])
    case_blocks = list(desc.attrs["case_blocks"])
    default_block = desc.attrs.get("default_block", -1)

    # index of first true condition; len(conds) = default
    idx = jnp.asarray(len(conds), jnp.int32)
    for i in range(len(conds) - 1, -1, -1):
        idx = jnp.where(conds[i], jnp.asarray(i, jnp.int32), idx)

    def make_branch(block_idx):
        def branch(operand):
            if block_idx < 0:
                return operand
            e = dict(env)
            e.update(zip(out_names, operand))
            ctx.run_block(block_idx, e)
            return tuple(
                jnp.asarray(e[n]).astype(o.dtype).reshape(o.shape)
                for n, o in zip(out_names, operand))
        return branch

    branches = [make_branch(b) for b in case_blocks]
    branches.append(make_branch(default_block))
    operand = tuple(jnp.asarray(env[n]) for n in out_names)
    result = lax.switch(idx, branches, operand)
    env.update(zip(out_names, result))


# ---------------------------------------------------------------------------
# static_rnn (lax.scan; fluid recurrent_op / StaticRNN)
# ---------------------------------------------------------------------------

@register_macro_op("static_rnn")
def static_rnn_op(ctx, env, desc):
    """attrs:
      sub_block:    block index of the step body
      step_inputs:  [[outer_name, inner_name]]  outer is time-major (T, ...)
      memories:     [[pre_name, post_name, init_name]]
      step_outputs: [[inner_name, outer_name]]  outer gets (T, ...) stacked
      final_states: [[post_name, outer_name]]   (optional)
      unroll:       lax.scan unroll factor (default 1) — the cheap
                    XLA-side scan-bound lever (fewer while iterations,
                    more work per iteration for the scheduler)
      trip_count:   (optional) the counted form: no step input, the
                    body runs this many times over its memories; what
                    the body reads from outside (weights) is the scan's
                    closure, so its gradient sums over the trips

    reference: paddle/fluid/operators/recurrent_op.cc:222 (step-scope
    iteration) — here one lax.scan, reverse-differentiable by jax AD, so
    recurrent gradients need no replay machinery (recurrent_op.cc:311).
    """
    sub_block = desc.attrs["sub_block"]
    step_inputs = desc.attrs.get("step_inputs", [])
    memories = desc.attrs.get("memories", [])
    step_outputs = desc.attrs.get("step_outputs", [])
    final_states = desc.attrs.get("final_states", [])
    unroll = int(desc.attrs.get("unroll", 1))
    trip_count = desc.attrs.get("trip_count")
    if trip_count is not None:
        if step_inputs:
            raise ValueError("static_rnn: a trip_count and step inputs")
        from ..observe.monitoring import runtime_stats

        runtime_stats.record_loop_trips(int(trip_count))

    init_carry = tuple(env[init] for _pre, _post, init in memories)
    xs = tuple(env[outer] for outer, _inner in step_inputs)
    # what leaves the body: a recompute segment inside it hands out
    # these names and keeps the rest to itself
    leaving = ({post for _pre, post, _init in memories}
               | {inner for inner, _outer in step_outputs})

    def body(carry, x_slices):
        e = dict(env)
        for (pre, _post, _init), c in zip(memories, carry):
            e[pre] = c
        for (_outer, inner), x in zip(step_inputs, x_slices):
            e[inner] = x
        ctx.run_block(sub_block, e, keep_names=leaving)
        new_carry = tuple(
            e[post].astype(c.dtype) if hasattr(c, "dtype") else e[post]
            for (_pre, post, _init), c in zip(memories, carry))
        ys = tuple(e[inner] for inner, _outer in step_outputs)
        return new_carry, ys

    final, ys = lax.scan(body, init_carry, xs, length=trip_count,
                         unroll=unroll)
    for (_inner, outer), y in zip(step_outputs, ys):
        env[outer] = y
    # final is ordered by memories; final_states maps post->outer
    post_to_final = {post: f for (_pre, post, _init), f in zip(memories, final)}
    for post, outer in final_states:
        env[outer] = post_to_final[post]


# ---------------------------------------------------------------------------
# dynamic_rnn (scan + seq_len masking; fluid DynamicRNN w/o lod_rank_table)
# ---------------------------------------------------------------------------

@register_macro_op("dynamic_rnn")
def dynamic_rnn_op(ctx, env, desc):
    """Like static_rnn but over padded batch-major sequences (B, T, ...)
    with a per-example length vector: steps past an example's length leave
    its memory unchanged and emit zeros.  Replaces the reference's
    lod_rank_table / shrink_rnn_memory reorder-by-length machinery
    (operators/lod_rank_table_op.cc, shrink_rnn_memory_op.cc) — masking
    costs a few flops but keeps one static-shape scan, which is the right
    trade on the MXU.

    attrs: sub_block, step_inputs [[outer, inner]], memories
    [[pre, post, init]], step_outputs [[inner, outer]], final_states
    [[post, outer]], seq_len (name of the (B,) length var), unroll
    (lax.scan unroll factor, default 1).
    """
    sub_block = desc.attrs["sub_block"]
    step_inputs = desc.attrs.get("step_inputs", [])
    memories = desc.attrs.get("memories", [])
    step_outputs = desc.attrs.get("step_outputs", [])
    final_states = desc.attrs.get("final_states", [])
    seq_len = env[desc.attrs["seq_len"]]  # (B,) int
    unroll = int(desc.attrs.get("unroll", 1))

    init_carry = tuple(env[init] for _pre, _post, init in memories)
    # batch-major (B, T, ...) → time-major (T, B, ...) for the scan
    xs = tuple(jnp.moveaxis(env[outer], 1, 0) for outer, _inner in step_inputs)
    t_max = xs[0].shape[0] if xs else int(jnp.max(seq_len))

    def mask_like(active, val):
        # active: (B,) bool; val: (B, ...) — broadcast mask over trailing dims
        m = active.reshape(active.shape + (1,) * (val.ndim - 1))
        return m

    def body(carry, inp):
        t, x_slices = inp
        active = t < seq_len  # (B,)
        e = dict(env)
        for (pre, _post, _init), c in zip(memories, carry):
            e[pre] = c
        for (_outer, inner), x in zip(step_inputs, x_slices):
            e[inner] = x
        ctx.run_block(sub_block, e)
        new_carry = tuple(
            jnp.where(mask_like(active, e[post]), e[post].astype(c.dtype), c)
            for (_pre, post, _init), c in zip(memories, carry))
        ys = tuple(
            jnp.where(mask_like(active, e[inner]), e[inner],
                      jnp.zeros_like(e[inner]))
            for inner, _outer in step_outputs)
        return new_carry, ys

    ts = jnp.arange(t_max)
    final, ys = lax.scan(body, init_carry, (ts, xs), unroll=unroll)
    for (_inner, outer), y in zip(step_outputs, ys):
        env[outer] = jnp.moveaxis(y, 0, 1)  # back to (B, T, ...)
    post_to_final = {post: f for (_pre, post, _init), f in zip(memories, final)}
    for post, outer in final_states:
        env[outer] = post_to_final[post]


# ---------------------------------------------------------------------------
# calc_gradient (fluid backward.py:613 gradients/calc_gradient)
# ---------------------------------------------------------------------------

@register_macro_op("calc_gradient")
def calc_gradient_op(ctx, env, desc):
    """Gradients of target vars w.r.t. arbitrary input vars.

    attrs: op_range [start, stop) — the block-0 op span whose recomputation
    expresses targets as a pure function of inputs.  The impl re-traces
    those ops with the inputs as function arguments and applies jax.vjp;
    XLA CSE dedups the recomputed subgraph against the original trace.

    inputs: TargetGradients (optional cotangents, one per target, or absent
    → ones).  Targets/Inputs are carried by name in attrs because their
    values are taken from / spliced into the live env.
    """
    target_names = desc.attrs["targets"]
    input_names = set(desc.attrs["inputs"])
    input_order = desc.attrs["inputs"]
    grad_names = desc.outputs["InputGrads"]
    start, stop = desc.attrs["op_range"]
    span = ctx.program.blocks[desc.attrs.get("block", 0)].ops[start:stop]

    # Prune the span to the inputs→targets path (fluid _find_op_path_,
    # backward.py:573).  Two correctness requirements: (a) ops *producing*
    # an input var must not run, or they would overwrite the vjp-traced
    # binding and the gradient would be silently zero; (b) ops off the
    # path (e.g. branches over unfed data vars that the main run pruned)
    # must not run, or they would KeyError on absent env names.
    needed = set(target_names)
    keep_rev = []
    for op in reversed(span):
        outs = op.desc.output_names()
        if any(n in needed and n not in input_names for n in outs):
            keep_rev.append(op)
            needed.update(op.desc.input_names())
    ops = list(reversed(keep_rev))
    op_offset = {id(op): start + i for i, op in enumerate(span)}

    tg = desc.inputs.get("TargetGradients", [])

    def f(xs):
        e = dict(env)
        # the vjp replay re-traces ops already bitmapped by the main
        # forward — numerics provenance must not double-scan them
        e.pop("__numerics_bits__", None)
        e.update(zip(input_order, xs))
        from ..core.executor import run_ops

        # Re-trace with the *same* per-op RNG keys as the original forward
        # (same base key + op indices) so stochastic ops (dropout) replay
        # the identical realization and XLA CSE can merge the subgraphs.
        for op in ops:
            run_ops([op], e, ctx._rng_key, start_index=op_offset[id(op)],
                    amp_lists=ctx.amp_lists, program=ctx.program)
        return tuple(e[t] for t in target_names)

    primal_in = tuple(env[n] for n in input_order)
    _primals, vjp_fn = jax.vjp(f, primal_in)
    if tg:
        cotangents = tuple(env[n] for n in tg)
    else:
        cotangents = tuple(jnp.ones_like(env[t]) for t in target_names)
    (grads,) = vjp_fn(cotangents)
    env.update(zip(grad_names, grads))


# ---------------------------------------------------------------------------
# Tensor arrays (fixed-capacity analog of LoDTensorArray)
# ---------------------------------------------------------------------------
# Representation in env: a 2-tuple (buffer, length) where buffer has shape
# (capacity, *elem_shape) and length is an int32 scalar tracking the
# high-water mark.  Tuples are jax pytrees, so arrays flow through while
# carries transparently.
# reference: paddle/fluid/operators/controlflow/tensor_array_read_write_op.cc

@register_op("create_array")
def create_array(ctx, ins, attrs):
    shape = tuple(attrs["element_shape"])
    cap = int(attrs["capacity"])
    # canonicalize (int64→int32 when x64 is off) without warning spam
    dtype = jax.dtypes.canonicalize_dtype(jnp.dtype(attrs.get("dtype",
                                                              "float32")))
    buf = jnp.zeros((cap,) + shape, dtype=dtype)
    return out(Out=(buf, jnp.asarray(0, jnp.int32)))


@register_op("array_write")
def array_write(ctx, ins, attrs):
    x = first(ins, "X")
    i = jnp.reshape(first(ins, "I"), ()).astype(jnp.int32)
    buf, length = first(ins, "Array")
    buf = lax.dynamic_update_index_in_dim(buf, x.astype(buf.dtype), i, 0)
    length = jnp.maximum(length, i + 1)
    return out(Out=(buf, length))


@register_op("array_read")
def array_read(ctx, ins, attrs):
    buf, _length = first(ins, "Array")
    i = jnp.reshape(first(ins, "I"), ()).astype(jnp.int32)
    return out(Out=lax.dynamic_index_in_dim(buf, i, 0, keepdims=False))


@register_op("array_length")
def array_length(ctx, ins, attrs):
    _buf, length = first(ins, "Array")
    return out(Out=length.reshape((1,)))


@register_op("array_to_tensor")
def array_to_tensor(ctx, ins, attrs):
    """Stack the written prefix (whole buffer; entries past `length` are
    zero).  Axis attr concatenates instead when axis >= 0 semantics of
    fluid's array_to_lod_tensor are not needed on padded tensors."""
    buf, length = first(ins, "Array")
    return out(Out=buf, OutIndex=length.reshape((1,)))


@register_op("max_sequence_len")
def max_sequence_len(ctx, ins, attrs):
    """Max over a (B,) length vector (reference: max_sequence_len_op from
    the lod_rank_table machinery; here lengths are explicit)."""
    sl = first(ins, "SeqLen")
    return out(Out=jnp.max(sl).reshape((1,)))


# ---------------------------------------------------------------------------
# Beam search (dense batch×beam formulation)
# ---------------------------------------------------------------------------

@register_op("beam_search")
def beam_search(ctx, ins, attrs):
    """One beam expansion step.

    inputs: PreIds (B, K) int32 — tokens chosen last step
            PreScores (B, K) f32 — cumulative log-probs
            Scores (B, K, V) f32 — log-probs of next-token candidates
    attrs:  beam_size K, end_id, is_first_step (bool: only beam 0 is live,
            others are -inf so the first expansion doesn't duplicate)
    outputs: SelectedIds (B, K), SelectedScores (B, K), ParentIdx (B, K)

    Finished beams (pre_id == end_id) are frozen: they propagate with
    unchanged score and re-emit end_id, so top-k naturally retires them.
    reference: paddle/fluid/operators/beam_search_op.cc:1 (LoD-linked
    variant); the dense (B, K) + parent-pointer formulation is the
    TPU-native equivalent (static shapes, one top_k per step).
    """
    pre_ids = first(ins, "PreIds")
    pre_scores = first(ins, "PreScores")
    scores = first(ins, "Scores")  # (B, K, V) log-probs
    B, K, V = scores.shape
    end_id = int(attrs.get("end_id", 1))
    neg_inf = jnp.asarray(-1e9, scores.dtype)

    finished = pre_ids == end_id  # (B, K)
    # Expansion scores: live beams add candidate log-probs; finished beams
    # keep exactly one candidate (end_id) at their frozen score.
    expand = pre_scores[:, :, None] + scores  # (B, K, V)
    onehot_end = jax.nn.one_hot(end_id, V, dtype=scores.dtype)  # (V,)
    frozen = pre_scores[:, :, None] + jnp.where(
        onehot_end.astype(bool), 0.0, neg_inf)  # (B, K, V)
    total = jnp.where(finished[:, :, None], frozen, expand)
    if attrs.get("is_first_step", False):
        # only beam 0 contributes candidates on the first step
        beam_mask = (jnp.arange(K) == 0)[None, :, None]
        total = jnp.where(beam_mask, total, neg_inf)

    flat = total.reshape(B, K * V)
    top_scores, top_idx = lax.top_k(flat, K)  # (B, K)
    parent = (top_idx // V).astype(jnp.int32)
    token = (top_idx % V).astype(pre_ids.dtype)
    return out(SelectedIds=token, SelectedScores=top_scores,
               ParentIdx=parent)


@register_op("beam_search_decode")
def beam_search_decode(ctx, ins, attrs):
    """Backtrace parent pointers into full sequences.

    inputs: Ids (T, B, K) int — tokens per step; Parents (T, B, K) int;
            NumSteps (scalar int, optional — entries past it are padding)
    attrs:  end_id
    outputs: SentenceIds (B, K, T) — right-padded with end_id;
             SentenceScores passthrough handled by caller.
    reference: beam_search_decode_op.cc (walks LoD links; here a reverse
    lax.scan over the parent-pointer arrays).
    """
    ids = first(ins, "Ids")  # (T, B, K)
    parents = first(ins, "Parents")
    T, B, K = ids.shape
    end_id = int(attrs.get("end_id", 1))
    num_steps = ins.get("NumSteps")
    n = (jnp.reshape(num_steps[0], ()).astype(jnp.int32)
         if num_steps else jnp.asarray(T, jnp.int32))

    batch_ix = jnp.arange(B)[:, None]  # (B, 1)

    def body(beam_ix, t):
        # beam_ix: (B, K) — which beam slot each final hypothesis occupied
        # at step t+1; gather token at t and hop to its parent.
        valid = t < n
        tok = jnp.where(valid, ids[t][batch_ix, beam_ix],
                        jnp.full((B, K), end_id, ids.dtype))
        prev = jnp.where(valid, parents[t][batch_ix, beam_ix], beam_ix)
        return prev, tok

    init = jnp.tile(jnp.arange(K, dtype=jnp.int32)[None, :], (B, 1))
    _final, toks = lax.scan(body, init, jnp.arange(T - 1, -1, -1))
    # toks: (T, B, K) in reverse time order → (B, K, T) forward
    seqs = jnp.moveaxis(toks[::-1], 0, 2)
    return out(SentenceIds=seqs)

@register_op("tensor_array_to_tensor")
def tensor_array_to_tensor(ctx, ins, attrs):
    """Concat (default) or stack the tensor-array buffer along `axis`
    (reference: operators/tensor_array_to_tensor_op.cc:154 concats a
    LoDTensorArray along axis, OutIndex recording each entry's size on
    that axis).  Fixed-capacity divergence: all T capacity slots
    participate (unwritten tail entries are zero) — the dense
    tensor-array protocol above."""
    buf, _length = first(ins, "X")
    use_stack = bool(attrs.get("use_stack", False))
    t = buf.shape[0]
    entry = buf.shape[1:]
    axis = _tat_axis(int(attrs.get("axis", 0)), len(entry), use_stack)
    moved = jnp.moveaxis(buf, 0, axis)
    if use_stack:
        o = moved
        index = jnp.ones((t,), jnp.int32)
    else:
        o = moved.reshape(entry[:axis] + (t * entry[axis],)
                          + entry[axis + 1:])
        index = jnp.full((t,), entry[axis], jnp.int32)
    return out(Out=o, OutIndex=index)


def _tat_axis(axis: int, rank: int, use_stack: bool) -> int:
    """Validate/normalize tensor_array_to_tensor's axis: stacking
    INSERTS a dim (valid positions 0..rank, like the reference
    StackOp); concatenation needs entries of rank >= 1 and a dim to
    concat on (0..rank-1)."""
    if not use_stack and rank == 0:
        raise ValueError(
            "tensor_array_to_tensor: cannot concat scalar entries — "
            "use use_stack=True to stack them into a vector")
    bound = rank + 1 if use_stack else rank
    if not -bound <= axis < bound:
        raise ValueError(
            f"tensor_array_to_tensor: axis {axis} out of range for "
            f"entry rank {rank} "
            f"({'stack inserts at 0..' + str(rank) if use_stack else 'concat needs 0..' + str(rank - 1)})")
    return axis % bound
