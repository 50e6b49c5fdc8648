"""The chip's compiler on what a cell's step hands it beside the attention
kernels, at the published widths: `olmoe-4k`, `lfm2-8k`, the looped step
of `ouro-4k`, `ops/pallas/grouped_matmul.py` at the four expert cells'
shapes, and the whole steps of `sdar-8k` and `laguna-16k` with the
plans their cut rules read and of `qwen3next-16k` with the inverses its
linear layers' segments keep (tests/chip_compile.py says why and how).
"""

from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp

from chip_compile import (BF16, EXPERT_CELLS, F32, I32, SHARE_CELLS,
                          _compile_args, _lower_args, _sites,
                          _state_by_shape)


def test_olmoe_step_kernels_at_the_published_shapes(one_chip):
    """What `olmoe-4k`'s step hands the chip's compiler that no other
    cell does, at OLMoE-1B-7B's widths (4 x 4096 tokens, 16 heads of
    128, 64 experts of 2048 x 1024, 8 a token): the causal head-major
    flash call with no bias, forward and backward, and the dropless
    expert op, whose nine grouped matmuls are the Pallas kernels of
    `ops/pallas/grouped_matmul.py` under the name `ragged_dot` (PR 40;
    the TPU compiler's own lowering of `jax.lax.ragged_dot` before),
    static shapes whatever the routing.  `observe.cost` must name every kernel and count T*k rows
    of work for a grouped matmul, never E x dense."""
    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.observe import cost
    from paddle_tpu.ops.pallas.flash_attention import \
        pallas_flash_attention

    n, t, heads, d, e, h, k = 4, 4096, 16, 128, 64, 1024, 8
    hidden = heads * d

    def attention(q, k_, v):
        with jax.named_scope("flash_attention:9"):
            o = pallas_flash_attention(q, k_, v, None, d ** -0.5, True,
                                       layout="nthd", n_head=heads)
        return jnp.sum(o.astype(F32))

    compiled = _compile_args(
        jax.jit(jax.grad(attention, argnums=(0, 1, 2))),
        *[jax.ShapeDtypeStruct((n, t, hidden), BF16, sharding=one_chip)] * 3)
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    # the backward pass is ONE kernel at this shape (PR 37;
    # tests/test_chip_compile_flash.py has its cases)
    assert sorted(r["kernel"] for r in rows if r["kernel"]) == [
        "flash_dkv", "flash_fwd"]
    assert {r["op_type"] for r in rows if r["kernel"]} == {
        "flash_attention"}

    impl = get_op_impl("moe_dropless")

    def experts(x, gate, w1, w3, w2):
        with jax.named_scope("moe_dropless:12"):
            o = impl(OpContext(jax.random.PRNGKey(0), 0),
                     {"X": [x], "GateW": [gate], "W1": [w1], "W3": [w3],
                      "W2": [w2]}, {"top_k": k})
        return (jnp.sum(o["Out"][0].astype(F32)) + o["AuxLoss"][0][0]
                + o["ZLoss"][0][0])

    shapes = [(n, t, hidden), (hidden, e), (e, hidden, h), (e, hidden, h),
              (e, h, hidden)]
    compiled = _compile_args(
        jax.jit(jax.grad(experts, argnums=(0, 1, 2, 3, 4))),
        *[jax.ShapeDtypeStruct(s, BF16, sharding=one_chip)
          for s in shapes])
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    matmuls = [r for r in rows if r["kernel"] == "ragged_dot"]
    assert len(matmuls) == 9            # 3 forward, 3 dX, 3 dW
    per_matmul = 2.0 * n * t * k * hidden * h
    assert {r["flops"] for r in matmuls} == {per_matmul}
    assert {r["bucket"] for r in matmuls} == {"custom_call"}
    assert not any(r["bucket"] == "matmul" and r["flops"] > per_matmul
                   for r in rows)       # nothing E x dense beside them
    totals = cost.total_costs(cost.compiled_hlo_proto(compiled))
    assert totals["custom_calls"] == totals["pallas_matched"] >= 9
    # the routing never reaches a shape: no dynamic dimension anywhere
    assert "<=" not in compiled.as_text().split("ENTRY")[1].split("\n")[0]


def _computation(text, name):
    """The lines of computation `name` in a compiled module's text."""
    body = text.split(f"\n%{name} (", 1)[1]
    return body[:body.index("\n}\n")].split("\n")[1:]


LFM2 = dict(t=8192, hidden=2048, e=64, held=8, h=1536, k=4)


def _lfm2_share_layer(one_chip):
    """The gradient of `lfm2-8k`'s share-holding expert op at
    LFM2-24B-A2B's widths (1 x 8192 tokens, a router over 64 experts, 8
    of them held at 2048 x 1536, 4 a token), jitted, and its described
    arguments."""
    from paddle_tpu.core.registry import OpContext, get_op_impl

    t, hidden, e, held, h, k = LFM2.values()
    impl = get_op_impl("moe_dropless")
    attrs = {"top_k": k, "routing": "sigmoid", "norm_topk_prob": True,
             "experts_held": [0, held], "router_gradient": False}

    def experts(x, gate, bias, w1, w3, w2):
        with jax.named_scope("moe_dropless:12"):
            o = impl(OpContext(jax.random.PRNGKey(0), 0),
                     {"X": [x], "GateW": [gate], "Bias": [bias],
                      "W1": [w1], "W3": [w3], "W2": [w2]}, attrs)
        # not linear in Out: a share's routing weights are constants of
        # the backward pass, and a linear loss would need no forward
        return jnp.sum(jnp.sin(o["Out"][0].astype(F32)))

    shapes = [((1, t, hidden), BF16), ((hidden, e), BF16), ((e,), F32),
              ((held, hidden, h), BF16), ((held, hidden, h), BF16),
              ((held, h, hidden), BF16)]
    return (jax.jit(jax.grad(experts, argnums=(0, 1, 3, 4, 5))),
            [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in shapes])


def _lfm2_short_conv(one_chip):
    """The gradient of `lfm2-8k`'s gated short convolution, jitted, and
    its described arguments."""
    from paddle_tpu.core.registry import OpContext, get_op_impl

    t, hidden = LFM2["t"], LFM2["hidden"]
    conv = get_op_impl("short_conv")

    def short_conv(bcu, w):
        with jax.named_scope("short_conv:7"):
            o = conv(OpContext(jax.random.PRNGKey(0), 0),
                     {"X": [bcu], "Filter": [w]}, {})
        return jnp.sum(o["Out"][0].astype(F32))

    return (jax.jit(jax.grad(short_conv, argnums=(0, 1))),
            [jax.ShapeDtypeStruct((1, t, 3 * hidden), BF16,
                                  sharding=one_chip),
             jax.ShapeDtypeStruct((hidden, 3), F32, sharding=one_chip)])


def test_lfm2_share_layer_and_short_conv_by_their_traces(one_chip):
    """Tier-1's stand-in for the slow test below, with no compile: the
    three row buffers the share rule takes, the layer lowered with
    Mosaic's kernels in it and the counters around its trace.  The
    expert op lowers to two `case`s (forward, backward) on the kernels
    of `grouped_matmul.py` and `rows_to_tokens.py`, no fall-back, no
    absent expert's weight; the gated convolution's gradient to ONE
    kernel and no dot."""
    from paddle_tpu.ops import moe_dropless

    t, hidden, e, held, h, k = LFM2.values()
    assert moe_dropless.row_buffer_sizes(t, k, e, held) == (
        6144, 12288, t * k)
    fn, args = _lfm2_share_layer(one_chip)
    lowered, took = _lower_args(fn, *args)
    # a branch's three forward products and three dX, at three sizes
    assert (took["grouped_matmuls_kernel"], took["grouped_matmuls_xla"]) \
        == (18, 0)
    assert (took["share_rows_kernel"], took["share_rows_xla"]) == (6, 0)
    assert set(_sites(lowered)) == {"ragged_dot", "rows_to_tokens"}
    text = lowered.as_text()
    assert text.count('"stablehlo.case"') == 2
    assert f"tensor<{e}x{hidden}x{h}x" not in text
    fn, args = _lfm2_short_conv(one_chip)
    lowered, took = _lower_args(fn, *args)
    assert (took["short_convs_kernel"], took["short_convs_xla"]) == (1, 0)
    assert _sites(lowered) == {"short_conv_bwd": 1}
    assert "dot_general" not in lowered.as_text()


# slow, 56 s: two compiles of the layer and one of the convolution.  The
# driver's chip run of `lfm2-8k` guards that Mosaic takes them and that
# the step fits (`hbm_peak_gb`); the branches' contents and the 710 MiB
# pin wait for this test (`-m slow -k lfm2`)
@pytest.mark.slow
def test_lfm2_share_layer_and_short_conv_at_the_published_shapes(
        one_chip, monkeypatch):
    """What `lfm2-8k`'s step hands the chip's compiler beside the
    attention kernels, at LFM2-24B-A2B's widths (1 x 8192 tokens, a
    router over 64 experts, 8 of them held at 2048 x 1536, 4 a token).
    The expert op that holds a share compiles to two `conditional`s,
    forward and backward, of three branches: its sorted rows at 6144,
    12288 and T*k = 32768 rows, eleven Mosaic grouped matmuls a size,
    the kernels of `ops/pallas/grouped_matmul.py` under the
    `moe_dropless` scope in every branch (PR 40; the compiler's own
    lowering of `jax.lax.ragged_dot` before)
    (three forward; backward the two up-projections again and six
    more: the down-projection's result would serve the router's
    gradient alone, which a program that runs a share holds back);
    static shapes whatever the routing, nothing 64 experts wide but
    the router; the smallest branch writes NOTHING T*k rows long since
    PR 50 (the way back to token order is `ops/pallas/
    rows_to_tokens.py` over the buffer's rows; one T*k-row gather each
    way before); and the plan needs less
    memory than the section differentiated on T*k rows (a quarter less
    before PR 40; an eighth since, the kernels having taken the masks'
    buffers out of the section differentiated as it stands).  The gated
    short convolution's gradient is one Mosaic kernel of
    `ops/pallas/short_conv.py` under the op's scope, and no dot (XLA
    fusions with no kernel before PR 46)."""
    from paddle_tpu.observe import cost
    from paddle_tpu.ops import moe_dropless

    t, hidden, e, held, h, k = LFM2.values()
    sizes = moe_dropless.row_buffer_sizes(t, k, e, held)
    assert sizes == (6144, 12288, t * k)

    def compile_layer():
        fn, args = _lfm2_share_layer(one_chip)
        return _compile_args(fn, *args)

    compiled = compile_layer()
    text = compiled.as_text()
    assert f"[{e},{hidden},{h}]" not in text     # no absent expert's weight
    assert "<=" not in text.split("ENTRY")[1].split("\n")[0]
    branches = [line.split("branch_computations={")[1].split("}")[0]
                .replace("%", "").split(", ")
                for line in text.split("\n") if " conditional(" in line]
    assert [len(b) for b in branches] == [3, 3]  # forward, backward
    for smallest, _, _ in branches:
        lines = _computation(text, smallest)
        assert not any(f"[{t * k},{h}]" in line.split(" = ")[1].split("(")[0]
                       for line in lines if " = " in line)
        # nothing T*k rows long, nor (T, k, D): the way back to token
        # order is the kernel over the buffer's rows (PR 50; one T*k-row
        # gather each way before)
        assert not any(
            f"[{t * k}," in line.split(" = ")[1].split("(")[0]
            or f"[{t},{k},{hidden}]" in line.split(" = ")[1].split("(")[0]
            for line in lines if " = " in line)

    proto = cost.compiled_hlo_proto(compiled)
    every = cost.instruction_costs(proto, every_branch=True)
    matmuls = [r for r in every if r["kernel"] == "ragged_dot"]
    assert {r["bucket"] for r in matmuls} == {"custom_call"}
    assert all(r["branch_of"] for r in matmuls)
    # every branch's are the Pallas kernels, under the op's scope: the
    # step holds no `ragged-dot` of the compiler's
    assert {r["pallas_kernel"] for r in matmuls} == {"ragged_dot"}
    assert {r["op_type"] for r in matmuls} == {"moe_dropless"}
    assert "ragged-dot" not in text
    by_size = {}
    for r in matmuls:
        by_size[r["flops"]] = by_size.get(r["flops"], 0) + 1
    assert by_size == {2.0 * rows * hidden * h: 11 for rows in sizes}
    # a table that sums to a step lists the heaviest branch alone
    rows = cost.instruction_costs(proto)
    assert [r["flops"] for r in rows if r["kernel"] == "ragged_dot"] == [
        2.0 * t * k * hidden * h] * 11
    inside = [r for r in every if r["branch_of"] and r["bucket"] in (
        "elementwise", "layout", "matmul")]
    assert inside and {r["op_type"] for r in inside
                       if r["op_type"]} == {"moe_dropless"}

    # the section on T*k rows, differentiated as it stands (the parent
    # of PR 31): what the switch is measured against
    monkeypatch.setattr(moe_dropless, "row_buffer_sizes",
                        lambda t, k, e, count: (t * k,))
    full = compile_layer()
    assert " conditional(" not in full.as_text()
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < 0.9 * full.memory_analysis().temp_size_in_bytes
    # and in bytes.  What plans the most is the catch-all's backward:
    # the T*k rows, the two up-projections and three gradients as wide
    # at once, which the compiler's own ragged dots took as fused
    # operands and a kernel takes from HBM (713 MiB; 556 at the parent,
    # whose section as it stands planned 994 to this one's 803).  No
    # step's peak is there: `lfm2-8k` reads `hbm_peak_gb` 4.07 for the
    # parent's 4.12 (PERF.md, PR 40).  PR 50 pins what it leaves: 708.5
    # MiB (742,929,408 bytes; the parent 712.6), the T*k branch on the
    # rows -> tokens kernel like the others: no (T, k, D) array, the
    # rows in token order in its place.  (A `vmem_limit_bytes` of 64
    # MiB on that kernel alone planned 900 MiB here.)
    assert temporaries <= 710 << 20

    fn, args = _lfm2_short_conv(one_chip)
    compiled = _compile_args(fn, *args)
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    # the shape rule takes the gated form at this width (PR 46): the
    # gradient is ONE kernel, which recomputes the convolution from
    # `BCu` and writes d(BCu) whole, under the op's scope; beside it
    # only the sums of the filter's gradient and of the loss
    assert [r["kernel"] for r in rows if r["kernel"]] == ["short_conv_bwd"]
    assert not any(r["bucket"] in ("matmul", "conv") for r in rows)
    assert {r["op_type"] for r in rows if r["op_type"]} == {"short_conv"}


def test_a_looped_step_with_flash_kernels_in_the_scans_body(one_chip):
    """What a looped decoder's step hands the chip's compiler that no
    other cell does, at the published widths (1 x 4096 tokens, 16 heads
    of 128, FFN 5632, the whole 49152-row head; depth cut to ONE layer
    for the test's time, 4 trips as published): the Mosaic flash
    kernels inside a `lax.scan`'s body and inside its transpose, each
    layer pass and each trip's head a recompute segment in the body,
    bf16 AMP.  The loops are counted (trip count 4), the body's
    instructions are cost rows of their own under their kernels' names
    and the `ut_loop` scope, the weights' bf16 copies are made outside
    the loops, and the forward loop hands its transpose the segments'
    inputs and the flash kernel's two residuals (PR 39), nothing else
    of a layer."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder
    from paddle_tpu.observe import cost, trace

    t, d, dff, vocab, trips = 4096, 2048, 5632, 49152, 4
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        model = decoder.build_model(
            max_length=t, hidden_size=d, num_hidden_layers=1,
            num_attention_heads=16, num_key_value_heads=16,
            intermediate_size=dff, num_experts=0, num_experts_per_tok=0,
            norm_topk_prob=False, num_dense_layers=1, vocab_size=vocab,
            rope_theta=1e6, rms_norm_eps=1e-6, total_ut_steps=trips,
            sandwich_norm=True, qk_norm=None, exit_gate="sigmoid",
            exit_entropy_weight=0.1, recompute="layer")
        _state_by_shape(main, scope)    # no start-up run at this size
        feed = {k: jnp.zeros((1, t), jnp.int64)
                for k in ("tokens", "labels")}
        exe = fluid.Executor()
        step, state, feeds = exe._prepare(
            main, feed, [model["loss"].name], scope, 1, True)

        def described(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

        compiled = _compile_args(step, jax.tree.map(described, state),
                                 jax.tree.map(described, feeds))
    assert [len(b.ops) for b in main.blocks][1] > 20     # ONE sub-block
    proto = cost.compiled_hlo_proto(compiled)
    rows = cost.instruction_costs(proto)
    loops = [r for r in rows if r["opcode"] == "while"]
    assert [r["trip_count"] for r in loops] == [trips, trips]
    assert all(r["bucket"] == "loop" and r["flops"] == 0 for r in loops)
    inside = [r for r in rows if r["loop_of"]]
    assert all(r["trips"] == trips for r in inside)
    # the forward kernel in the forward loop and NOT again in the
    # backward loop's recomputed layer pass (the segment keeps its
    # output and logsumexp); the single backward kernel once
    assert sorted(r["kernel"] for r in inside if r["kernel"]) == [
        "flash_dkv", "flash_fwd"]
    assert not [r for r in rows if r["kernel"] and not r["loop_of"]]
    pmap = trace.program_map(proto)
    for r in inside:
        if r["kernel"]:
            assert r["pallas_kernel"] and r["flops"] > 0
            assert "ut_loop" in trace.name_scope_of(
                pmap[r["name"]]["op_name"]).split("/")
    # the loop's matmuls carry their FLOPs per call: a layer pass's
    # seven products forward, again recomputed, twice that backward,
    # and the head's three; nothing of them outside the loops
    tokens = float(t)
    layer = 2 * tokens * (4 * d * d + 3 * d * dff)
    head = 2 * tokens * d * vocab
    matmul = sum(r["flops"] for r in inside if r["bucket"] == "matmul")
    assert matmul == pytest.approx(4 * layer + 4 * head, rel=0.02)
    assert sum(cost.per_step(r, "flops") for r in rows
               if r["bucket"] == "matmul") == pytest.approx(
        trips * (4 * layer + 4 * head), rel=0.02)
    totals = cost.total_costs(proto)
    assert totals["custom_calls"] == totals["pallas_matched"] == 2
    # the weights' bf16 copies are loop-invariant: no float32 weight
    # enters a loop's body to be cast there once a trip
    module = cost.HloModule(proto)
    bodies = [module.computations[c] for loop in module.entry.instructions
              if loop.opcode == "while" for c in loop.called_ids]
    weights = {(d, dff), (dff, d), (d, vocab)}
    for body in bodies:
        for instr in body.instructions:
            if instr.opcode == "parameter":
                continue
            assert not (instr.opcode == "convert"
                        and tuple(instr.shape.dims) in weights), instr.name
    # the compiler's own copies inside the loops (no scope of theirs)
    # are handed to the op they work for, and what they move of the
    # body's parameter reads `carry`: a weight that rides the carry is
    # no `state` in there, and nothing in a body is
    moved = [r for r in inside if r["bucket"] == "layout"]
    carried = [r for r in moved if r["source"] == "carry"]
    assert carried and not [r for r in moved if r["source"] == "state"]
    assert all(r["source_shape"] and r["source_parameter"] is None
               for r in carried)
    pairs = [r for r in carried if r["opcode"] in ("copy-start",
                                                   "copy-done")]
    assert pairs and all(r["op_type"] is None for r in pairs)
    # most feed a scoped instruction of the body; one that moves an
    # element of the carry and hands it straight back has nobody IN
    # the body (a walk stays inside its computation)
    fed = [r for r in pairs if r["owner_via"] == "consumer"]
    assert len(fed) > len(pairs) / 2
    assert all(r["owner_op_type"] and r["owner_consumers"] for r in fed)
    assert {r["source"] for r in rows if r["bucket"] == "layout"
            and not r["loop_of"]} == {"state", "activation"}
    text = compiled.as_text()
    forward = [ln for ln in text.splitlines() if " while(" in ln][0]
    # what the forward loop saves for its transpose: the float32 input
    # of the layer's segment and of the head's, stacked over the trips
    # (2 x 134 MB) and the flash kernel's output and logsumexp (67 +
    # 8.4 MB), not the layer's activations
    assert forward.count(f"f32[{trips},1,{t},{d}]") == 2
    assert forward.count(f"bf16[{trips},1,{t},{d}]") == 1
    assert forward.count(f"f32[{trips},16,8,{t}]") == 1
    assert f"[{trips},1,{t},{dff}]" not in forward
    assert f"{t},{vocab}]" not in forward


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_grouped_matmul_kernels_at_the_cells_shapes(one_chip, cell):
    """`ops/pallas/grouped_matmul.py`: forward, dX and dW at the tiles
    its rule takes for a cell's up- and down-projection, within
    Mosaic's default scoped VMEM (no `vmem_limit_bytes`): bf16 at the
    smallest and the largest row buffer, float32 at the smallest.  The cost table
    counts 2 x rows x K x N for each, dW by its result's rank."""
    from paddle_tpu.observe import cost
    from paddle_tpu.ops import moe_dropless
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul

    t, k, e, held, d, h = EXPERT_CELLS[cell]
    groups = e if held is None else held
    sizes = ((t * k,) if held is None
             else moe_dropless.row_buffer_sizes(t, k, e, held))

    def vjp(lhs, rhs, counts, ct):
        out, pull = jax.vjp(lambda l, r: grouped_matmul(l, r, counts),
                            lhs, rhs)
        return (out,) + pull(ct)

    cases = [(sizes[0], BF16), (sizes[-1], BF16), (sizes[0], F32)]
    for rows, dtype in dict.fromkeys(cases):
        for kk, nn in ((d, h), (h, d)):
            compiled = _compile_args(jax.jit(vjp), *[
                jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in (((rows, kk), dtype), ((groups, kk, nn), dtype),
                              ((groups,), I32), ((rows, nn), dtype))])
            assert "vmem_limit_bytes" not in compiled.as_text()
            table = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
            kernels = [r for r in table if r["kernel"] == "ragged_dot"]
            assert [r["pallas_kernel"] for r in kernels] == ["ragged_dot"] * 3
            assert {r["flops"] for r in kernels} == {2.0 * rows * kk * nn}


def test_grouped_matmul_in_float32_at_highest_stays_within_vmem(one_chip):
    """What a parity script hands the chip's compiler: float32 rows
    under "highest" products, whose bfloat16 parts Mosaic keeps beside
    the blocks.  At `kimilinear-8k`'s up-projection (2304 -> 1024, 8
    held) the rule's first choice, (128, 2304, 512), took 16.33 MiB of
    the 16 MiB a call may claim (PR 65); the rule now counts the row
    tile's parts and takes (128, 2304, 256).  The case above compiles
    float32 at the default precision, where that tiling fits."""
    from paddle_tpu.ops.pallas import force_mosaic_lowering
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul, tiles_for

    # the cell's first row buffer, its experts held, D and H
    rows, held, d, h = 3072, 8, 2304, 1024
    assert tiles_for(rows, d, h, held, 4)[0] == (128, 2304, 256)
    assert tiles_for(rows, d, h, held, 2)[0] == (128, 2304, 1024)

    def vjp(lhs, rhs, counts, ct):
        out, pull = jax.vjp(lambda l, r: grouped_matmul(l, r, counts),
                            lhs, rhs)
        return (out,) + pull(ct)

    for kk, nn in ((d, h), (h, d)):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in (((rows, kk), F32), ((held, kk, nn), F32),
                              ((held,), I32), ((rows, nn), F32))]
        with force_mosaic_lowering(), jax.default_matmul_precision("highest"):
            text = jax.jit(vjp).lower(*args).compile().as_text()
        assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("cell", sorted(SHARE_CELLS))
def test_rows_to_tokens_kernel_at_the_share_cells_shapes(one_chip, cell):
    """`ops/pallas/rows_to_tokens.py` at the five share cells' first
    row buffer: the shape rule takes it, and Mosaic compiles the kernel
    with the float32 routing weights (three exact bf16 passes) and
    without (one, its float32 sum in scratch and a bf16 result), on
    bf16 rows and on float32 rows
    (`Precision.HIGHEST`), and at the T x k rows of the last buffer;
    `token_order` compiles beside it.
    Nothing T x k rows long is planned at the first size: the
    temporaries are the R rows in token order and the (T, D) sums."""
    from paddle_tpu.observe import cost
    from paddle_tpu.ops.pallas import rows_to_tokens as rt

    rows, t, k, d = SHARE_CELLS[cell]
    assert rt.rows_to_tokens_takes(rows, t, d)
    assert rt.rows_to_tokens_takes(t * k, t, d)

    def sums(vals, *order):
        # the combine; the gradient of a gather, in the rows' dtype
        return (rt.rows_to_tokens(vals, order, t, weighted=True),
                rt.rows_to_tokens(vals, order, t, out_dtype=vals.dtype))

    # (the order is an argument: a sort of 24,576 keys or more compiles
    # for 17 s here, whatever it sorts, and the step has its like)
    chunk, tile = rt.ROW_CHUNK, rt.TOKEN_TILE
    for r, dtype in ((rows, BF16), (rows, F32), (t * k, BF16)):
        compiled = _compile_args(jax.jit(sums), *[
            jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in (((r, d), dtype), ((r,), I32),
                          ((r // chunk, 1, chunk), I32),
                          ((3, t // tile + r // chunk), I32), ((), I32),
                          ((r // chunk, 1, chunk), F32))])
        table = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
        kernels = [x for x in table if x["kernel"]]
        assert [x["pallas_kernel"] for x in kernels] == ["rows_to_tokens"] * 2
        assert {x["flops"] for x in kernels} == {0.0}
        if r == rows:
            itemsize = jnp.dtype(dtype).itemsize
            assert compiled.memory_analysis().temp_size_in_bytes <= \
                2 * r * d * itemsize + 2 * 4 * t * d
            assert f"[{t},{k},{d}]" not in compiled.as_text()
    if rows < 8192:
        order = _compile_args(
            jax.jit(lambda tokens, n, w: rt.token_order(tokens, n, t, w)),
            jax.ShapeDtypeStruct((rows,), I32, sharding=one_chip),
            jax.ShapeDtypeStruct((), I32, sharding=one_chip),
            jax.ShapeDtypeStruct((rows,), F32, sharding=one_chip))
        assert " sort(" in order.as_text()
        assert "scatter" not in order.as_text()


def _cell_lowered(cell_name, one_chip):
    """The whole training step of a cell as `benchmarks/run.py` builds
    it (the published widths, the cell's rows, bf16 AMP, every layer a
    recompute segment), traced and lowered for the described chip with
    Mosaic's kernels in it, nothing compiled, nothing run (seconds): (the
    Program's parameter count, the lowered step, the counters around its
    trace)."""
    import os
    import sys

    import numpy as np

    import paddle_tpu as fluid

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import run as bench_run

    cell, config, family = bench_run.load_cell(cell_name, (bench,))
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        loss = family.build(config)
        _state_by_shape(main, scope)
        batch = family.make_batch(config, cell, np.random.default_rng(0))
        step, state, feeds = fluid.Executor()._prepare(
            main, batch, [loss.name], scope, 1, True)

        def described(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

        lowered, took = _lower_args(step, jax.tree.map(described, state),
                                    jax.tree.map(described, feeds))
    return (sum(int(np.prod(p.shape)) for p in main.all_parameters()),
            lowered, took)


def _cell_step(cell_name, one_chip):
    """`_cell_lowered`'s step compiled for the described chip (a minute
    or two: the `slow` half): (the Program's parameter count, the
    compiled step, its plan in GB (`arguments`, aliased to the outputs,
    `temporaries`, `total`), its Mosaic calls by kernel name, the
    counters around its trace)."""
    import collections
    import re

    parameters, lowered, took = _cell_lowered(cell_name, one_chip)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    plan = {"arguments": memory.argument_size_in_bytes / 1e9,
            "temporaries": memory.temp_size_in_bytes / 1e9}
    plan["total"] = plan["arguments"] + plan["temporaries"]
    calls = " ".join(ln for ln in compiled.as_text().splitlines()
                     if "tpu_custom_call" in ln)
    return (parameters, compiled, plan,
            collections.Counter(re.findall(r"pallas_(\w+?)/", calls)), took)


# -- a whole step by its trace: what tier-1 holds of the seven slow tests ----
#
# A compile of a whole cell's step for the described chip is 50-200 s and
# `slow` (tests/chip_compile.py has the rule).  What such a test asserts
# WITHOUT the compiled text is here, a function a cell: the Program's
# parameter count at the published widths and the counters around the
# step's trace (which path every kernel family took, the grid steps, the
# kept residuals), with the names of the kernels the step lowers to.  The
# slow test calls the same function on its own build, so the two cannot
# drift; `test_a_cells_step_by_its_trace` runs it in tier-1 on
# `_cell_lowered` alone (5-9 s a cell).

def _sdar_traced(parameters, took):
    assert parameters == 645623296
    assert took["flash_attention_backward_split"] == 0
    assert took["flash_block_diffusion_grid_steps"] \
        == took["flash_block_diffusion_blocks_allowed"] \
        == 80 * took["flash_block_diffusion_calls"]
    assert took["flash_block_diffusion_entries_computed"] == (
        72 * 1024 * 1024 + 8 * 8 * 128 * 128) * took[
            "flash_block_diffusion_calls"]


def _laguna_traced(parameters, took):
    assert parameters == 691625216
    # no fall-back anywhere: by the step's trace
    assert (took["flash_window_calls"], took["flash_grouped_calls"]) == (6, 4)
    # every window forward (three layers, traced forward and for the
    # segment's backward pass) is the whole-band step (PR 60): 32 query
    # tiles x 2 key tiles of 512 x 512 a head, 49.2 % of them allowed
    assert (took["flash_window_forward_whole_band"],
            took["flash_window_forward_tiled"]) == (6, 0)
    assert took["flash_window_entries_computed"] == 6 * 64 * 512 * 512
    assert took["flash_window_pairs_allowed"] == 6 * 8257792
    assert (took["flash_attention_backward_fused"],
            took["flash_attention_backward_split"]) == (5, 0)
    assert took["recompute_kept_residuals"] == 5
    assert (took["ropes_kernel"], took["ropes_xla"]) == (10, 0)
    assert (took["share_rows_kernel"], took["share_rows_xla"]) == (9, 0)


def _phi4flash_traced(parameters, took):
    assert parameters == 697299072
    # no fall-back anywhere: by the step's trace (a mamba layer's
    # forward, its forward traced again for the segment's backward
    # pass, its backward: 32 chunks a call)
    assert (took["selective_scans_kernel"], took["selective_scans_xla"],
            took["selective_scan_chunks"]) == (6, 0, 6 * 32)
    assert (took["short_convs_kernel"], took["short_convs_xla"],
            took["short_conv_bias_calls"]) == (2, 0, 2)
    assert (took["flash_window_calls"], took["flash_grouped_calls"]) == (2, 4)
    # the window layer's forward, traced twice: the whole-band step at
    # a group of two heads (PR 60)
    assert (took["flash_window_forward_whole_band"],
            took["flash_window_forward_tiled"]) == (2, 0)
    assert (took["flash_attention_backward_fused"],
            took["flash_attention_backward_split"]) == (3, 0)
    # three attention calls' (o, logsumexp) and two scans' (y, states)
    assert took["recompute_kept_residuals"] == 5
    assert took["recompute_kept_bytes"] >= 2 * (8192 * 5120 * 2
                                                + 32 * 16 * 5120 * 4)


def _granite4h_traced(parameters, took):
    assert parameters == 772160448
    # no fall-back anywhere: by the step's trace (a mamba layer's
    # forward, its forward traced again for the segment's backward
    # pass, its backward: 32 chunks a call)
    assert (took["ssd_scans_kernel"], took["ssd_scans_xla"],
            took["ssd_scan_chunks"]) == (27, 0, 27 * 32)
    assert (took["short_convs_kernel"], took["short_convs_xla"],
            took["short_conv_bias_calls"]) == (9, 0, 9)
    assert (took["flash_gqa_backward_fused"],
            took["flash_gqa_backward_split"]) == (1, 0)
    assert took["gated_rms_norm_calls"] == 9
    assert took["selective_scans_kernel"] == took["selective_scans_xla"] == 0
    # nine scans' (y, states, xBC: the operand the kernels read as the
    # convolution leaves it, PR 70) and the attention call's (o,
    # logsumexp)
    assert took["recompute_kept_residuals"] == 10
    assert took["recompute_kept_bytes"] >= 9 * (8192 * 4096 * 2
                                                + 32 * 32 * 128 * 128 * 4
                                                + 8192 * 4352 * 2)


def _qwen3next_traced(parameters, took):
    assert parameters == 424340544
    assert (took["flash_attention_backward_fused"],
            took["flash_attention_backward_split"]) == (1, 0)
    assert (took["gated_delta_inverse_calls"],
            took["gated_delta_operand_calls"],
            took["gated_delta_operand_chunks"]) == (3, 9, 9 * 256 * 32)
    # every scan and chunk-operand call on the op's own arrays since PR
    # 72: o and dO by lane block, q, k, v and dQKV on QKV (a fall-back
    # to `scan_xla` / `chunk_operands` or operands apart would read less)
    assert (took["gated_delta_calls"], took["gated_delta_flat_calls"]) == (
        9, 18)
    # the full layer's (o, logsumexp) and three inverses
    assert took["recompute_kept_residuals"] == 4
    assert took["recompute_kept_bytes"] >= 3 * 134217728
    # a head's lane statistic (`ops/pallas/head_norm.py`): the
    # silu-gated norm a head alone since PR 69 (q's and k's l2norm is
    # taken inside the chunk-operand kernels: 27 calls, 442,368 rows
    # before): one pass a delta layer's forward, one its forward traced
    # again for the segment's backward pass, one its backward; three
    # layers of 16384 rows: 9 calls, 147,456 rows
    assert (took["head_norm_calls"], took["head_norm_rows"]) == (
        3 * 3, 3 * 3 * 16384)


def _kimilinear_traced(parameters, took):
    assert parameters == 602433408
    # a delta layer's forward, its forward traced again for the
    # segment's backward pass, its backward: 128 chunks x 32 heads a call
    assert (took["channel_delta_calls"], took["channel_delta_chunks"]) == (
        12, 12 * 128 * 32)
    # the inverse kernel once a layer, the operand kernels as the scan's
    assert (took["channel_delta_operand_calls"],
            took["channel_delta_operand_chunks"]) == (16, 16 * 128 * 32)
    assert (took["short_convs_kernel"], took["short_convs_xla"]) == (4, 0)
    assert (took["flash_mla_backward_fused"],
            took["flash_mla_backward_split"]) == (1, 0)
    assert took["gated_delta_calls"] == took["gated_delta_operand_calls"] == 0
    # four layers' (inverse, P) and the latent layer's (o, logsumexp)
    assert took["recompute_kept_residuals"] == 5
    assert took["recompute_kept_bytes"] >= 4 * (67108864 + 33554432)
    # a head's lane statistic (`ops/pallas/head_norm.py`): the
    # sigmoid-gated norm a head alone since PR 69 (q's and k's l2norm is
    # taken inside the chunk-local kernels: 36 calls, 294,912 rows
    # before): one pass a delta layer's forward, one its forward traced
    # again for the segment's backward pass, one its backward; four
    # layers of 8192 rows: 12 calls, 98,304 rows
    assert (took["head_norm_calls"], took["head_norm_rows"]) == (
        4 * 3, 4 * 3 * 8192)


def _kimivl_traced(parameters, took):
    assert parameters == 726479616
    # no fall-back anywhere: by the step's trace.  The segment-confined
    # attention of the eight tower layers (the op of a layer is traced
    # once; its recompute segment keeps the output and the logsumexp, so
    # the compiled step holds ONE forward and one backward kernel a
    # layer): 16 heads x 24 x 24 tiles of 1024 x 1024 a call
    assert (took["flash_segment_calls"], took["flash_segment_xla_calls"],
            took["flash_segment_tiles_total"]) == (8, 0, 8 * 16 * 24 * 24)
    # every call's 128-lane layout, and q's and k's turn inside it, is
    # `ops/pallas/head_lanes.py`'s kernels' (PR 74), none XLA's
    assert (took["flash_segment_lane_kernel_calls"],
            took["flash_segment_lane_xla_calls"]) == (8, 0)
    assert (took["image_patches"], took["image_rows"]) == (24576, 6144)
    assert (took["flash_mla_backward_fused"],
            took["flash_mla_backward_split"]) == (5, 0)
    # eight tower layers' and five decoder layers' (o, logsumexp); the
    # tower's o at the heads' own 72 lanes
    assert took["recompute_kept_residuals"] == 13
    assert took["recompute_kept_bytes"] == 8 * (
        24576 * 1152 * 2 + 16 * 8 * 24576 * 4) + 5 * (
        8192 * 2048 * 2 + 16 * 8 * 8192 * 4)
    # rotary turns as ops: the rotary lanes of q and the one key head of
    # a decoder layer, bare turns (the tower's sixteen over two axes are
    # the attention op's own since PR 74)
    assert (took["ropes_kernel"], took["ropes_xla"]) == (0, 10)
    assert (took["grouped_matmuls_kernel"],
            took["grouped_matmuls_xla"]) == (27, 0)
    assert (took["share_rows_kernel"], took["share_rows_xla"]) == (9, 0)


# cell -> (what its trace holds, the kernels its step lowers to and
# compiles to: the compiled step's by calls, the lowered step's by sites)
CELL_TRACES = {
    "sdar-8k": (_sdar_traced, {
        "flash_block_diffusion_fwd", "flash_block_diffusion_dkv",
        "ragged_dot", "rope_fwd", "rope_bwd", "rows_to_tokens"}),
    "laguna-16k": (_laguna_traced, {
        "flash_window_fwd", "flash_window_dkv", "flash_fwd", "flash_dkv",
        "ragged_dot", "rope_fwd", "rope_bwd", "rows_to_tokens"}),
    "phi4flash-8k": (_phi4flash_traced, {
        "selective_scan_fwd", "selective_scan_bwd", "short_conv_fwd",
        "short_conv_bwd", "flash_window_fwd", "flash_window_dkv",
        "flash_fwd", "flash_dkv"}),
    "granite4h-8k": (_granite4h_traced, {
        "ssd_scan_fwd", "ssd_scan_bwd", "short_conv_fwd", "short_conv_bwd",
        "flash_gqa_fwd", "flash_gqa_dkv"}),
    "qwen3next-16k": (_qwen3next_traced, {
        "gated_delta_inverse", "gated_delta_operands_fwd",
        "gated_delta_operands_bwd", "gated_delta_fwd", "gated_delta_bwd",
        "head_norm_fwd", "head_norm_bwd",
        "flash_fwd", "flash_dkv", "short_conv_fwd", "short_conv_bwd",
        "rope_fwd", "rope_bwd", "ragged_dot", "rows_to_tokens"}),
    "kimilinear-8k": (_kimilinear_traced, {
        "channel_delta_inverse", "channel_delta_operands_fwd",
        "channel_delta_operands_bwd", "channel_delta_fwd",
        "channel_delta_bwd", "head_norm_fwd", "head_norm_bwd",
        "flash_mla_fwd", "flash_mla_dkv",
        "short_conv_fwd", "short_conv_bwd", "ragged_dot",
        "rows_to_tokens"}),
    "kimivl-8k": (_kimivl_traced, {
        "flash_segment_fwd", "flash_segment_bwd", "head_lanes_to_tiles",
        "head_lanes_from_tiles", "flash_mla_fwd",
        "flash_mla_dkv", "ragged_dot", "rows_to_tokens"}),
}


def holds_its_trace(cell, parameters, took, kernels):
    check, names = CELL_TRACES[cell]
    check(parameters, took)
    assert set(kernels) == names


@pytest.mark.parametrize("cell", sorted(CELL_TRACES))
def test_a_cells_step_by_its_trace(one_chip, cell):
    """Tier-1's stand-in for a cell's slow whole-step compile (the six
    below and `kimilinear-8k`'s in tests/test_chip_compile_flash.py): the
    step built at the published widths, traced and lowered with Mosaic's
    kernels in it.  What it cannot see is the PLAN (the GB a depth, share
    or length rule read) and the compiled step's kernel COUNTS and cost
    rows: the slow test holds those, and between its runs the driver's
    chip run of the cell does (a step that does not fit or that Mosaic
    refuses is a failed cell there; `hbm_peak_gb` and the `*_calls`
    counters are its per-layer entries)."""
    parameters, lowered, took = _cell_lowered(cell, one_chip)
    holds_its_trace(cell, parameters, took, _sites(lowered))


# slow, 119 s.  Between its runs the driver's chip run of `sdar-8k`
# guards that the step compiles and fits (`hbm_peak_gb`, the kernels'
# `*_calls` counters), `test_a_cells_step_by_its_trace` the trace; the
# plan's side of the depth rule waits for this test
@pytest.mark.slow
def test_the_block_diffusion_cells_step_and_the_plan_its_depth_rule_read(
        one_chip):
    """The whole training step of `sdar-8k` as `benchmarks/run.py`
    builds it (6 layers at the published widths, 16384 rows, bf16 AMP,
    every layer a recompute segment), compiled for the described chip,
    nothing run.  The depth rule of the configuration (ISSUE 47: 6
    layers if the step's plan is 15.0 GB or less, else 4) read THIS
    plan: arguments (aliased to the outputs) 7.75 GB + temporaries 6.06
    GB = 13.81 GB; at 4 layers 5.48 + 5.41 = 10.89 GB (PERF.md, PR 47).
    A layer is ONE forward and one backward flash kernel under the
    block-diffusion mask: the segment keeps the forward's residuals.
    Since PR 59 both walk a list of visits
    (`ops/pallas/flash_block_diffusion.py`): 80 grid steps a head's
    pass where the rectangles took 144 and 256, and no `_dq` kernel."""
    parameters, compiled, plan, kernels, took = _cell_step("sdar-8k",
                                                           one_chip)
    holds_its_trace("sdar-8k", parameters, took, kernels)
    assert plan["arguments"] == pytest.approx(7.75, abs=0.01)
    assert 12.5 < plan["total"] <= 15.0, plan       # the rule's side: 6
    assert kernels["flash_block_diffusion_fwd"] == 6
    assert kernels["flash_block_diffusion_dkv"] == 6
    # q and k of six layers: normed and turned forward and recomputed,
    # one backward kernel each (PR 48)
    assert (kernels["rope_fwd"], kernels["rope_bwd"]) == (24, 12)
    # a layer's three row buffers sum their rows a token in one kernel
    # forward (the recomputed section's) and one backward (PR 50)
    assert kernels["rows_to_tokens"] == 36
    # the step was built through `Executor._prepare`, so under the
    # fluid scopes: the TPU compiler's own `copy-start` / `copy-done`
    # and `slice-start` pairs and relayout fusions carry none, and the
    # def-use map hands them to the op they work for (PR 49)
    from paddle_tpu.observe import cost

    module = cost.HloModule(cost.compiled_hlo_proto(compiled))
    rows = cost.instruction_costs(module, every_branch=True)
    moved = [r for r in rows if r["bucket"] == "layout"]
    opcodes = {r["opcode"] for r in moved if r["op_type"] is None}
    assert {"copy-start", "copy-done", "async-start", "async-done"} \
        <= opcodes
    owned = sum(r["shape_bytes"] * r["trips"] for r in moved
                if r["owner_via"] in cost.OWNER_VIAS)
    assert owned >= 0.99 * sum(r["shape_bytes"] * r["trips"]
                               for r in moved)
    assert all(r["owner_via"] == "scope" for r in rows if r["op_type"])
    # what re-lays or prefetches a step input says so, with the
    # parameter's number and shape: the placed experts' float32
    # weights, fetched four experts at a time
    parameters = {i.parameter_number: i for i in module.entry.instructions
                  if i.opcode == "parameter"}
    state = [r for r in moved if r["source"] == "state"]
    assert state and all(
        parameters[r["source_parameter"]].shape.text == r["source_shape"]
        for r in state)
    assert not [r for r in state if r["branch_of"] or r["loop_of"]]
    experts = [r for r in state if r["source_shape"] == "f32[16,768,2048]"
               and r["opcode"] == "async-start"]
    assert experts and {(r["owner_op_type"], r["shape"])
                        for r in experts} <= {
        ("moe_dropless", "f32[4,768,2048]"), ("adam", "f32[4,768,2048]"),
        # (since PR 50 one slice's first consumer is a cast the compiler
        # fused into the norm before the layer)
        ("rms_norm", "f32[4,768,2048]"),
        ("moe_dropless", "f32[16,768,2048]"),
        ("adam", "f32[16,768,2048]")}


# slow, 109 s.  Between its runs the driver's chip run of `laguna-16k`
# guards that the step compiles and fits (`hbm_peak_gb`, the kernels'
# `*_calls` counters), `test_a_cells_step_by_its_trace` the trace; the
# plan's side of the share rule waits for this test
@pytest.mark.slow
def test_the_head_count_a_layer_cells_step_and_the_plan_its_share_rule_read(
        one_chip):
    """The whole training step of `laguna-16k` as `benchmarks/run.py`
    builds it (5 layers at the published widths, 16384 rows, bf16 AMP,
    every layer a recompute segment), compiled for the described chip,
    nothing run.  The share rule of the configuration (ISSUE 51: 32 of
    256 experts held, one chip of 8, if the step's plan is 15.0 GB or
    less, else 16 of one chip of 16) read THIS plan: arguments (aliased
    to the outputs) 8.30 GB + temporaries 6.18 GB = 14.48 GB; at 16
    held 5.88 + 5.86 = 11.75 GB (PERF.md, PR 51).  A window layer is ONE
    `flash_window_fwd` and one `flash_window_dkv` at 64 / 8 heads, a
    full layer one `flash_fwd` and one `flash_dkv` at 48 / 8: the
    segments keep the forward's residuals."""
    parameters, _, plan, kernels, took = _cell_step("laguna-16k", one_chip)
    holds_its_trace("laguna-16k", parameters, took, kernels)
    assert plan["arguments"] == pytest.approx(8.30, abs=0.01)
    assert 13.5 < plan["total"] <= 15.0, plan   # the rule's side: 32 held
    assert (kernels["flash_window_fwd"], kernels["flash_window_dkv"]) == (3, 3)
    assert (kernels["flash_fwd"], kernels["flash_dkv"]) == (2, 2)
    # q and k of five layers: normed and turned forward and recomputed,
    # one backward kernel each; the full layers' turn half the head
    assert (kernels["rope_fwd"], kernels["rope_bwd"]) == (20, 10)
    assert kernels["rows_to_tokens"] == 24          # four sparse layers


# slow, 50 s.  Between its runs the driver's chip run of `phi4flash-8k`
# guards that the step compiles and fits (`hbm_peak_gb`, the kernels'
# `*_calls` counters), `test_a_cells_step_by_its_trace` the trace; the
# plan's side of the length it was cut to waits for this test
@pytest.mark.slow
def test_the_state_space_cells_step_and_the_plan_its_length_read(one_chip):
    """The whole training step of `phi4flash-8k` as `benchmarks/run.py`
    builds it (six layers at the published widths, 8192 rows, bf16 AMP,
    every layer a recompute segment), compiled for the described chip,
    nothing run.  The cell's length (ISSUE 53: 8192, because 16384 does
    not fit) read THIS plan: arguments (aliased to the outputs) 8.37 GB
    + temporaries 3.58 GB = 11.94 GB; at 16384 8.37 + 7.43 = 15.80 GB,
    over the chip's 15.75 (PERF.md, PR 53).  A mamba layer is ONE
    `selective_scan_fwd` and one `selective_scan_bwd` (its segment keeps
    the scan's output and entry states) and its biased convolution's
    kernels; every attention layer one forward and one backward flash
    kernel at 40 / 20 heads, the window layer's the band kernels'."""
    parameters, _, plan, kernels, took = _cell_step("phi4flash-8k", one_chip)
    holds_its_trace("phi4flash-8k", parameters, took, kernels)
    assert plan["arguments"] == pytest.approx(8.37, abs=0.01)
    assert 11.0 < plan["total"] <= 15.0, plan
    assert (kernels["selective_scan_fwd"],
            kernels["selective_scan_bwd"]) == (2, 2)
    assert (kernels["short_conv_fwd"], kernels["short_conv_bwd"]) == (4, 2)
    assert (kernels["flash_window_fwd"], kernels["flash_window_dkv"]) == (1, 1)
    assert (kernels["flash_fwd"], kernels["flash_dkv"]) == (2, 2)


# slow, 102 s.  Between its runs the driver's chip run of `granite4h-8k`
# guards that the step compiles and fits (`hbm_peak_gb`, the kernels'
# `*_calls` counters), `test_a_cells_step_by_its_trace` the trace; the
# plan's side of the length it was cut to waits for this test
@pytest.mark.slow
def test_the_state_space_duality_cells_step_and_the_plan_its_length_read(
        one_chip):
    """The whole training step of `granite4h-8k` as `benchmarks/run.py`
    builds it (ten layers at the published widths, 8192 rows, bf16 AMP,
    every layer a recompute segment), compiled for the described chip,
    nothing run.  The cell's length (ISSUE 58: 8192 if the plan reads
    15.0 GB or less) read THIS plan: arguments (aliased to the outputs)
    9.27 GB + temporaries 3.69 GB = 12.96 GB; at 16384 9.27 + 7.37 =
    16.64 GB, over the chip's 15.75 (PERF.md, PR 58).  A mamba layer is
    ONE `ssd_scan_fwd` and one `ssd_scan_bwd` (its segment keeps the
    scan's output, entry states and operand xBC, 67 + 67 + 71 MB: PR
    70) and ONE forward and one backward kernel of its biased
    convolution (the segment's backward pass re-made xBC by a second
    forward before the scan kept it: 18 / 9); the attention layer one
    forward and one backward `flash_gqa` kernel; no (chunks, heads,
    256, 256) float32 decay mask is a tensor of the step, and under the
    mixers' scope (the scan's with it) no x is cut out of xBC and no d
    xBC glued together: the kernels block the three out of its lanes
    and write its gradient as one array."""
    import re

    parameters, compiled, plan, kernels, took = _cell_step("granite4h-8k",
                                                           one_chip)
    holds_its_trace("granite4h-8k", parameters, took, kernels)
    assert plan["arguments"] == pytest.approx(9.27, abs=0.01)
    assert 12.0 < plan["total"] <= 15.0, plan
    assert (kernels["ssd_scan_fwd"], kernels["ssd_scan_bwd"]) == (9, 9)
    assert (kernels["short_conv_fwd"], kernels["short_conv_bwd"]) == (9, 9)
    assert (kernels["flash_gqa_fwd"], kernels["flash_gqa_dkv"]) == (1, 1)
    text = compiled.as_text()
    assert not re.search(r"f32\[[0-9,]*256,256\]", text)
    in_a_mixer = [line for line in text.splitlines()
                  if "state_space_duality/" in line]
    assert [line for line in in_a_mixer if "/ssd_scan" in line]
    assert not [line for line in in_a_mixer if re.search(
        r"= bf16\[1,8192,4096\]\S* slice\(|"
        r"= bf16\[1,8192,4352\]\S* concatenate\(", line)]


# slow, 84 s.  Between its runs the driver's chip run of `qwen3next-16k`
# guards that the step compiles and fits (`hbm_peak_gb`, the kernels'
# `*_calls` counters), `test_a_cells_step_by_its_trace` the trace; the
# plan's side of the 15.0 GB the cut rules use waits for this test
@pytest.mark.slow
def test_the_linear_attention_cells_step_keeps_its_inverses_under_the_plan(
        one_chip):
    """The whole training step of `qwen3next-16k` (one period of four
    layers: three linear, one full; 16384 rows), compiled for the
    described chip, nothing run.  A linear layer's segment keeps
    (I + A)^-1 (PR 52: 134,217,728 bytes a layer, float32, two heads a
    tile), so the step holds `gated_delta_inverse` THREE times, once a
    layer, and `gated_delta_operands_fwd` six (the forward pass's and
    the recomputed one that reads the kept inverse); the plan with the
    three inverses alive from forward to backward stays under the 15.0
    GB the cells' cut rules use (12.37 GB before, PERF.md, PR 50).  The
    full layer's backward pass is ONE kernel since PR 54 (16 / 2 heads
    of 256: 48 MiB of dq, dk and dv, the budget's edge): no `flash_dq`
    in the step, the counters 1 / 0."""
    parameters, compiled, plan, kernels, took = _cell_step("qwen3next-16k",
                                                           one_chip)
    holds_its_trace("qwen3next-16k", parameters, took, kernels)
    assert 12.0 < plan["total"] <= 15.0, plan
    # nothing of XLA's at the delta kernels' boundary since PR 72 (12.12
    # GB planned, 12.26 before): no head-major o copied to the op's
    # layout and back (9 `copy bf16[1,16384,32,128]` a step before), none
    # re-laid for the gated norm (6 `copy_bitcast_fusion` + 3 `copy
    # bf16[16384,4096]`), no v sliced out of QKV (6), no dq, dk, dv
    # padded to 8192 lanes and added (3 `pad_add_fusion` over 9 `pad`)
    made = [line.strip().split(" = ", 1)
            for line in compiled.as_text().splitlines()
            if " = " in line and "linear_attention/" in line]
    assert made
    for shape, ops in (("bf16[1,16384,32,128]", ("copy(", "transpose(")),
                       ("bf16[1,16384,4096]", ("slice(",)),
                       ("bf16[16384,4096]", ("copy(",)),
                       ("bf16[1,16384,8192]", ("pad(",))):
        assert not [name for name, what in made if what.startswith(shape)
                    and any(f" {op}" in what for op in ops)], shape
    assert not [name for name, _ in made
                if "copy_bitcast_fusion" in name or "pad_add_fusion" in name]
    assert kernels["flash_dq"] == 0
    assert (kernels["gated_delta_inverse"],
            kernels["gated_delta_operands_fwd"],
            kernels["gated_delta_operands_bwd"]) == (3, 6, 3)
    assert (kernels["gated_delta_fwd"], kernels["gated_delta_bwd"]) == (6, 3)
    assert (kernels["flash_fwd"], kernels["flash_dkv"]) == (1, 1)
    # the output norm's head statistic in a linear layer's forward and
    # recomputed forward, and its one backward pass (PR 68; q's and k's
    # are the chunk-operand kernels' own since PR 69: 18 / 9 before)
    assert (kernels["head_norm_fwd"], kernels["head_norm_bwd"]) == (6, 3)


# slow, 170 s.  Between its runs the driver's chip run of `kimivl-8k`
# guards that the step compiles and fits (`hbm_peak_gb`,
# `flash_segment_tile_visit_ratio`), `test_a_cells_step_by_its_trace`
# the trace; the plan against the 15.0 GB ISSUE 73 ruled waits for this
# test
@pytest.mark.slow
def test_the_vision_language_cells_step_holds_tower_and_decoder_under_the_plan(
        one_chip):
    """The whole training step of `kimivl-8k` as `benchmarks/run.py`
    builds it (ONE jitted step that holds a tower of 8 layers over 24576
    packed patches, the projector, the merge and a decoder of 5 layers
    over 8192 positions, bf16 AMP, every layer a recompute segment),
    compiled for the described chip, nothing run.  A tower layer is ONE
    `flash_segment_fwd` and one `flash_segment_bwd` (its segment keeps
    the forward's output, at the heads' own 72 lanes, and logsumexp), a
    decoder layer one `flash_mla_fwd` and one `flash_mla_dkv` at 16
    heads; no fall-back anywhere.  Around a tower layer's two kernels,
    since PR 74, five passes of `ops/pallas/head_lanes.py` and nothing
    of XLA's: q, k, v to the kernels' 128 lanes a head with q's and k's
    rotary turn (forward, and once more for the backward kernel; the
    segment's recomputed forward needs none), o back; do and o there,
    three gradients back through the turn's transpose; no view of a
    head's 72 lanes or of its 36 pairs is left in the step.  The plan
    ISSUE 73's rule read, set
    before the step existed ("under 15.0 GB, else give memory back
    inside the step, else 6 tower layers"): arguments (aliased to the
    outputs) 8.78 GB + temporaries 5.83 GB = 14.61 GB (14.97 with the
    kept outputs at the kernels' 128 lanes: PERF.md, PR 73)."""
    parameters, compiled, plan, kernels, took = _cell_step("kimivl-8k",
                                                           one_chip)
    holds_its_trace("kimivl-8k", parameters, took, kernels)
    assert plan["arguments"] == pytest.approx(8.78, abs=0.01)
    assert 12.5 < plan["total"] <= 15.0, plan
    assert (kernels["flash_segment_fwd"], kernels["flash_segment_bwd"]) \
        == (8, 8)
    assert (kernels["head_lanes_to_tiles"],
            kernels["head_lanes_from_tiles"]) == (8 * 3, 8 * 2)
    text = compiled.as_text()
    assert "[1,24576,16,72]" not in text and "[1,24576,16,36,2]" not in text
    assert (kernels["flash_mla_fwd"], kernels["flash_mla_dkv"],
            kernels["flash_mla_dq"]) == (5, 5, 0)
