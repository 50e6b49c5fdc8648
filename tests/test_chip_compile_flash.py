"""The chip's compiler on `ops/pallas/flash_attention.py`'s backward
pass, at the two cells' shapes and at the shape rule's edges.

`tests/chip_compile.py` says why such tests exist and how they work (a
v5e that is DESCRIBED, not attached; nothing runs) and holds the
helpers; the fixtures are `tests/conftest.py`'s.
"""

from __future__ import annotations

import functools
import re

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops.pallas import flash_attention as fa
from chip_compile import BF16, F32, _compile_args, _state_by_shape

D = 128


@functools.cache
def _compiled(one_chip, n, t, heads, dtype, d=D, layout="nthd", causal=True,
              kv_heads=None, **blocks):
    """Forward + backward of one call compiled for the described chip,
    once a module for each shape: (the compiled step, (fused, split)
    as counted around its trace)."""
    shape = (n, t, heads * d) if layout == "nthd" else (n, heads, t, d)
    kv_shape = shape if kv_heads is None else (n, t, kv_heads * d)

    def loss(q, k, v):
        with jax.named_scope("flash_attention:9"):
            o = fa.pallas_flash_attention(
                q, k, v, None, d ** -0.5, causal, layout=layout,
                n_head=heads if layout == "nthd" else None,
                n_kv_head=kv_heads, **blocks)
        return jnp.sum(o.astype(F32))

    before = runtime_stats.snapshot()
    compiled = _compile_args(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
        *[jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
          for s in (shape, kv_shape, kv_shape)])
    took = runtime_stats.delta(before)
    return compiled, (took["flash_attention_backward_fused"],
                      took["flash_attention_backward_split"])


def _backward(*args, **kwargs):
    """([kernel name, number of results] sorted by name, (fused,
    split)) of `_compiled`'s step."""
    from paddle_tpu.observe import cost

    compiled, took = _compiled(*args, **kwargs)
    rows = cost.instruction_costs(cost.compiled_hlo_proto(compiled))
    assert {r["op_type"] for r in rows if r["kernel"]} == {"flash_attention"}
    assert all(r["flops"] > 0 for r in rows if r["kernel"])
    results = {}
    for line in compiled.as_text().splitlines():
        name = re.search(r'op_name="[^"]*pallas_(\w+?)/', line)
        if "tpu_custom_call" in line and name:
            head = line.split(" custom-call(")[0].split(" = ", 1)[1]
            results[name.group(1)] = len(re.findall(r"\w+\[[\d,]*\]", head))
    assert sorted(results) == sorted(r["kernel"] for r in rows if r["kernel"])
    return sorted(results.items()), took


# (N, T, heads) at d_head 128, head-major, causal, no bias: what a
# decoder layer of `ouro-4k` (one packed sequence, 32 calls a step
# inside the loop's body) and of `olmoe-4k` (4 sequences, one call) asks
@pytest.mark.parametrize("n, dtype", [(1, BF16), (1, F32), (4, BF16)],
                         ids=["ouro_4k-bf16", "ouro_4k-f32",
                              "olmoe_4k-bf16"])
def test_the_cells_backward_pass_is_one_kernel(one_chip, n, dtype):
    """Two custom calls: the forward kernel (o, logsumexp) and ONE
    backward kernel, `flash_dkv` grown by dq's dot (dq, dk, dv); no
    `flash_dq`.  Its blocks are 1024 x 1024, whose float32 score blocks
    pass Mosaic's default scoped VMEM: the call names a limit."""
    kernels, took = _backward(one_chip, n, 4096, 16, dtype)
    assert kernels == [("flash_dkv", 3), ("flash_fwd", 2)]
    assert took == (1, 0)


@pytest.mark.parametrize("case", [
    dict(n=1, t=4096, heads=16, dtype=BF16, layout="nhtd"),
    dict(n=1, t=4096, heads=16, dtype=BF16, causal=False),
    # d_head 64: the dq accumulator's rows are half a lane tile
    dict(n=2, t=8192, heads=8, dtype=BF16, d=64, layout="nhtd"),
    # blocks whose working set fits the default: no limit is named
    dict(n=1, t=4096, heads=2, dtype=F32, block_q=512, block_k=1024,
         no_limit=True),
    dict(n=1, t=12288, heads=1, dtype=BF16, block_q=256, block_k=1024,
         no_limit=True),
    # the budget's edge: 48 MiB of dq beside 1024 x 1024 blocks
    dict(n=1, t=fa.FUSED_ACCUMULATOR_BUDGET // (4 * D), heads=1, dtype=BF16),
], ids=["folded_layout", "not_causal", "d_head_64", "512x1024_no_limit",
        "12288_no_limit", "budget_edge_98304"])
def test_the_single_backward_kernel_elsewhere_in_its_rule(one_chip, case):
    """Everything else the rule sends to the single kernel that Mosaic
    could refuse: the folded layout, no causal mask (every dq block
    completes in the last pass), a head narrower than a lane tile,
    the two sides of the VMEM-limit rule, the largest accumulator."""
    case = dict(case)
    no_limit = case.pop("no_limit", False)
    named = fa._vmem_params(case["t"] * case.get("d", D) * 4,
                            case.get("block_q", fa.DEFAULT_BWD_BLOCK_Q),
                            case.get("block_k", fa.DEFAULT_BWD_BLOCK_K))
    assert bool(named) != no_limit
    kernels, took = _backward(one_chip, **case)
    assert kernels == [("flash_dkv", 3), ("flash_fwd", 2)]
    assert took == (1, 0)


def test_a_sequence_past_the_budget_compiles_the_two_kernels(one_chip):
    """One block past the single kernel's budget: dk / dv and dq by a
    kernel each, blocks only in VMEM."""
    t = fa.FUSED_ACCUMULATOR_BUDGET // (4 * D) + 1024
    assert not fa.fused_backward_fits(t, t, D, 1024, 1024)
    kernels, took = _backward(one_chip, 1, t, 1, BF16)
    assert kernels == [("flash_dkv", 2), ("flash_dq", 1), ("flash_fwd", 2)]
    assert took == (0, 1)


# -- the band kernels: a window, grouped key/value heads --------------------
#
# (1, 16384) positions, 32 query heads over 4 key/value heads of 128:
# what a `sliding_attention` (window 1024) and a `full_attention` layer
# of `mellum2-16k` ask, 6 + 2 calls a step

@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("window", [1024, None], ids=["window", "full"])
def test_the_window_cells_backward_pass_is_one_kernel(one_chip, window,
                                                      dtype):
    """Two custom calls a layer: the forward kernel and ONE backward
    kernel (dq, dk, dv), which holds dq of a query head and dk, dv of
    its key/value head full-length: 24 MiB at 16384 x 128, inside the
    budget.  The window's kernels run under names of their own and
    carry the band's cost (`cost_estimate`), not the causal half's;
    dk and dv come out 4 heads wide."""
    assert fa.band_backward_fits(16384, D)
    kernels, took = _backward(one_chip, 1, 16384, 32, dtype, kv_heads=4,
                              **({"window": window} if window else {}))
    prefix = "flash_window_" if window else "flash_"
    assert kernels == [(prefix + "dkv", 3), (prefix + "fwd", 2)]
    assert took == (1, 0)
    if window:
        return
    # over the whole prefix the kernels walk a table of visits (PR 63),
    # the custom calls' first operand: the registry still reads q, k, v
    # and the logsumexp behind it, the dense-equivalent work of the
    # plain names
    from paddle_tpu.observe import cost

    compiled, _ = _compiled(one_chip, 1, 16384, 32, dtype, kv_heads=4)
    rows = {r["kernel"]: r for r in cost.instruction_costs(
        cost.compiled_hlo_proto(compiled)) if r["kernel"]}
    scores = 32 * 16384 * 16384
    assert rows["flash_fwd"]["flops"] == scores * (4 * D + 8)
    assert rows["flash_dkv"]["flops"] == scores * (8 * D + 8)
    size = dtype.dtype.itemsize
    assert rows["flash_fwd"]["bytes"] == 16384 * (
        D * size * 2 * (32 + 4) + 32 * 8 * 4)
    assert "s32[9,136]" in compiled.as_text()


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32_highest"])
@pytest.mark.parametrize("t, heads, kv_heads, window, whole", [
    (16384, 64, 8, 512, True), (16384, 32, 4, 1024, True),
    (8192, 40, 20, 512, True), (16384, 32, 4, 2048, False)],
    ids=["laguna_16k", "mellum2_16k", "phi4flash_8k", "window_2048_tiled"])
def test_the_window_forward_the_shape_chooses(one_chip, t, heads, kv_heads,
                                              window, whole, dtype):
    """The window forward alone at the three cells' shapes, in bfloat16
    as the cells run it and in float32 at "highest" as their parity
    scripts do: a query tile of 512 against its WHOLE band (two key
    tiles under 512 keys, three under 1024), a key/value head's group
    (8, 8, 2) a grid step, the soft-max in one pass (PR 60); a window
    of 2048 keys keeps the online soft-max over 1024 x 1024 tiles.
    Either is ONE custom call named `flash_window_fwd` that declares
    the band's pairs.  In bfloat16 no call names a VMEM limit (XLA
    plans a step differently around one that does); float32 operands
    at a group of 8 pass the default 16 MiB and name one."""
    from chip_compile import force_mosaic_lowering
    from paddle_tpu.observe import cost

    blocks, _ = fa._band_blocks(t, None, None, window)
    assert blocks == ((512, 512) if whole else (1024, 1024))
    assert fa.whole_band_forward_fits(window, *blocks) == whole

    def forward(q, k, v):
        return fa._flash_band_fwd(q, k, v, D ** -0.5, blocks, (512, 512),
                                  heads, heads // kv_heads, window)[0]

    before = runtime_stats.snapshot()
    args = [jax.ShapeDtypeStruct((1, t, h * D), dtype, sharding=one_chip)
            for h in (heads, kv_heads, kv_heads)]
    with force_mosaic_lowering(), jax.default_matmul_precision(
            "default" if dtype == BF16 else "highest"):
        compiled = jax.jit(forward).lower(*args).compile()
    took = runtime_stats.delta(before)
    assert (took["flash_window_forward_whole_band"],
            took["flash_window_forward_tiled"]) == (
        (1, 0) if whole else (0, 1))
    row, = [r for r in cost.instruction_costs(
        cost.compiled_hlo_proto(compiled)) if r["kernel"]]
    pairs = heads * (window * t - window * (window - 1) // 2)
    assert row["kernel"] == "flash_window_fwd"
    assert row["flops"] == pairs * (4 * D + 8)
    assert row["bytes"] == t * D * dtype.dtype.itemsize * 2 * (
        heads + kv_heads)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert (f'"size":"{fa._VMEM_LIMIT}"' in text) == (
        whole and dtype == F32 and heads // kv_heads == 8)
    # key tiles a head's grid holds and those with an allowed pair
    band = fa._Band(t, *blocks, window)
    assert took["flash_window_blocks_visited"] == band.nq * band.k_steps
    assert took["flash_window_blocks_allowed"] == band.blocks_allowed
    assert took["flash_window_entries_computed"] == (
        band.nq * band.k_steps if whole else band.blocks_allowed
    ) * blocks[0] * blocks[1]


def test_a_window_kernels_registered_cost_is_the_bands(one_chip):
    from paddle_tpu.observe import cost

    # the step `test_the_window_cells_backward_pass_is_one_kernel
    # [window-bf16]` compiles
    compiled, _ = _compiled(one_chip, 1, 16384, 32, BF16, kv_heads=4,
                            window=1024)
    rows = {r["kernel"]: r for r in cost.instruction_costs(
        cost.compiled_hlo_proto(compiled)) if r["kernel"]}
    pairs = 32 * (1024 * 16384 - 1024 * 1023 // 2)
    assert rows["flash_window_fwd"]["flops"] == pairs * (4 * D + 8)
    assert rows["flash_window_dkv"]["flops"] == pairs * (8 * D + 8)
    # q, o and k, v a forward; q, do, o, dq and k, v, dk, dv a backward
    assert rows["flash_window_fwd"]["bytes"] == 16384 * D * 2 * (
        2 * 32 + 2 * 4)
    assert rows["flash_window_dkv"]["bytes"] == 16384 * D * 2 * (
        4 * 32 + 4 * 4)
    # the causal half would be 8.3 x that
    full = 32 * (16384 * 16385 // 2)
    assert 8.2 < full / pairs < 8.3


@pytest.mark.parametrize("window", [1024, None], ids=["window", "full"])
@pytest.mark.parametrize("past", [False, True], ids=["edge", "past"])
def test_a_band_call_on_either_side_of_the_budget(one_chip, past, window):
    """32768 positions at d_head 128: 48 MiB of dq, dk and dv, the most
    the rule sends to the single kernel (at d_head 256 that is 16384
    positions, `qwen3next-16k`'s call: tests/test_chip_compile_kernels.py),
    under the 512 x 512 blocks of a window and the 1024 x 1024 without.
    One block past it: a kernel for dk / dv over the group's heads and
    one for dq, blocks only in VMEM."""
    t = fa.FUSED_ACCUMULATOR_BUDGET // (12 * D) + 1024 * past
    assert fa.band_backward_fits(t, D) != past
    kernels, took = _backward(one_chip, 1, t, 32, BF16, kv_heads=4,
                              **({"window": window} if window else {}))
    prefix = "flash_window_" if window else "flash_"
    backward = [("dkv", 2), ("dq", 1)] if past else [("dkv", 3)]
    assert kernels == [(prefix + k, n) for k, n in backward + [("fwd", 2)]]
    assert took == ((0, 1) if past else (1, 0))


# -- a recompute segment keeps the forward kernel's residuals ---------------
#
# What `mellum2-16k` (a layer a segment, straight stack) and `ouro-4k`
# (the segments inside the loop's body) hand the chip's compiler since
# PR 39: a segment's backward pass rebuilds q, k, v from the
# projections and reads the kernel's (o, logsumexp) where the forward
# pass left them; no second forward kernel.

def _segment_kernels(one_chip, geometry, t, trips=0):
    """Compile the step of ONE attention layer in a recompute segment
    (`tests/test_recompute.py attention_stack`, bf16 AMP, hidden 256)
    for the described chip: the compiled step's Pallas kernels by name,
    the counters around the build, the compiled text."""
    import paddle_tpu as fluid
    from test_recompute import attention_stack

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        loss = attention_stack(geometry, t, 256, 1, trips)
        main._amp_lists = fluid.amp.AutoMixedPrecisionLists()
        fetch = [loss.name] + [g.name for _, g in fluid.append_backward(loss)]
        _state_by_shape(main, scope)
        step, state, feeds = fluid.Executor()._prepare(
            main, {"x": jnp.zeros((1, t, 256), F32)}, fetch, scope, 1, True)

        def described(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

        before = runtime_stats.snapshot()
        compiled = _compile_args(step, jax.tree.map(described, state),
                                 jax.tree.map(described, feeds))
        took = runtime_stats.delta(before)
    text = compiled.as_text()
    kernels = sorted(
        re.search(r'op_name="[^"]*pallas_(\w+?)/', line).group(1)
        for line in text.splitlines() if "tpu_custom_call" in line
        and " custom-call(" in line and "pallas_" in line)
    return kernels, took, text


@pytest.mark.parametrize("window", [1024, None], ids=["window", "full"])
def test_a_layer_segments_gradient_is_one_forward_and_one_backward_kernel(
        one_chip, window):
    """A `sliding_attention` / `full_attention` layer of `mellum2-16k`
    as a segment: 32 / 4 heads of 128, 1 x 16384."""
    prefix = "flash_window_" if window else "flash_"
    kernels, took, _ = _segment_kernels(
        one_chip, (prefix + "fwd", D, 32, 4, window), 16384)
    assert kernels == [prefix + "dkv", prefix + "fwd"]
    assert took["recompute_kept_residuals"] == 1
    # o in bf16 + 8 float32 sublanes of logsumexp a head: 151.0 MB
    assert took["recompute_kept_bytes"] \
        == 16384 * 32 * D * 2 + 32 * 8 * 16384 * 4 == 150994944


def test_a_layer_segment_in_a_scans_body_is_one_forward_and_one_backward_kernel(
        one_chip):
    """A layer pass of `ouro-4k`: the plain kernel, 16 heads of 128,
    1 x 4096, the segment inside a 4-trip scan.  The forward loop
    stacks the kernel's residuals over the trips beside the segment's
    input; the backward loop's body holds the backward kernel alone."""
    kernels, took, text = _segment_kernels(
        one_chip, ("flash_fwd", D, 16, 16, None), 4096, trips=4)
    assert kernels == ["flash_dkv", "flash_fwd"]
    assert took["recompute_kept_residuals"] == 1      # the body, once
    assert took["recompute_kept_bytes"] \
        == 4096 * 16 * D * 2 + 16 * 8 * 4096 * 4 == 18874368     # 18.9 MB
    forward = [ln for ln in text.splitlines() if " while(" in ln][0]
    assert "bf16[4,1,4096,2048]" in forward         # o, a trip each
    assert "f32[4,16,8,4096]" in forward            # logsumexp


# -- the cells this file's kernels serve keep their steps -------------------
#
# sha256 of every cell's lowered step (`fn.lower(state, feeds).as_text()`,
# the state by its shapes) on the CPU under this suite's conftest (8
# virtual devices, "highest" matmuls), jax 0.9.0, with the running
# number jax appends to private functions taken off (`_NUMBERED`:
# `@argsort_115` -> `@argsort`; one more private function anywhere in
# the process shifts every later number, which is all that naming the
# flash residuals does to a step with no recompute segment).  The
# control cells' hashes are the PARENT's (PR 40: `tbase-256`,
# `resnet50-b128` and `ouro-4k`, which run no expert op), computed on
# its checkout with the same regex.  A PR that means to change a step
# updates its line.
_NUMBERED = re.compile(r"(@[A-Za-z_][\w.]*?)_\d+\b")
STEP_TEXT = {
    "tbase-256":
    "87120efa12b45c7d69f7684350139b982024676c0a63adb4374351fd02c5a473",
    "resnet50-b128":
    "0f48812e8db4cbbab451ea81efcf5d14ebe463e612fac1d4887697bd27719340",
    # the four cells with routed experts re-pinned, PR 40: their grouped
    # matmuls are the kernels of `ops/pallas/grouped_matmul.py`, here
    # through the interpreter (parents: 90e5dcc6.., bef93476..,
    # 5e45cfed.., e1f3219f..)
    "olmoe-4k":
    "edde7176fe33bb4d5429b1ced73b68db315c639ed19922b19640112a2b71fd8b",
    # the five cells that hold a share re-pinned, PR 50 (this one,
    # `joyai-8k`, `mellum2-16k`, `qwen3next-16k` and `sdar-8k` below):
    # their sorted-row section sums a token's rows with the kernel of
    # `ops/pallas/rows_to_tokens.py`, here through the interpreter, the
    # sort by expert carries the pairs' weights and one sort by token
    # stands in the op (parents: acccef44.., 46a623eb.., 7914857b..,
    # 5a9ae15a.., 44ab655f..); `olmoe-4k`, which holds every expert,
    # keeps its text.  (PR 48 before: QK-norm a head in the `rope` op.)
    # This one re-pinned again, PR 56: `flash_gqa_fwd` on 1024 x 1024
    # tiles over the grid (N*Hkv, q blocks, k blocks), here through the
    # interpreter (parent: 53baaa58..); no other cell reaches
    # `ops/pallas/flash_gqa.py`
    "lfm2-8k":
    "acebe18fff0e9f8427c8219f16235a4e792f0db56574bad723d8590b0d1188a7",
    "joyai-8k":
    "8dec48c80693b40a6ede0035f7ff2f7c4070bdd664975db9bbd7ab3caee346e2",
    # re-pinned, PR 39: the loop's segments keep (o, logsumexp), the
    # backward body holds no forward kernel (parent: 83ada587..)
    "ouro-4k":
    "feb20e0f77cac9b68fcfcbc630aa0a2d04a50249bddebc892a00584d8a915084",
    # re-pinned, PR 48, with `qwen3next-16k` and `sdar-8k` below: q and
    # k are normed and turned in the kernels of `ops/pallas/rope.py`,
    # here through the interpreter (parents: 692f6104.., acbeb427..,
    # PR 46's SiLU short convolution, eeed81fb.., new in PR 47); every
    # other cell turns bare or over pairs and keeps its parent's text
    # Re-pinned, PR 60, with `laguna-16k` and `phi4flash-8k` below: a
    # window forward's grid step is a query tile of 512 against its
    # whole band, here through the interpreter (parents: 0fa427d2..,
    # 71766418.., 8262a62f..); the ten other cells trace no window
    # kernel and keep their text
    # Re-pinned, PR 63, with `qwen3next-16k`, `laguna-16k`,
    # `phi4flash-8k` and `sdar-8k` below: a band call over the whole
    # causal prefix walks a scalar-prefetched list of visits, a jitted
    # pass the full layers share, here through the interpreter (parents:
    # 7c286e0c.., 3587790b.., 562da214.., b4728adc..); `sdar-8k` walks
    # the same shells under their new names (`_flash_fwd_visits`,
    # `_flash_bwd_visits`; parent: 691f6604..).  The eight other cells
    # trace no such call and keep their text
    # Re-pinned, PR 65, as PERF.md section 7 "From PR 64" asks: PR 64
    # changed this configuration's peak rate (4e-4 -> 2e-5), a constant
    # of the text, and could not edit this file (parent: 70ec8bb9..)
    "mellum2-16k":
    "95d77d9202f88ec87b6bca10681c330fee251618c28122d173992f3ee5654f5f",
    # re-pinned, PR 52: (I + A)^-1 is `gated_delta_inverse`'s, named for
    # the layers' segments to keep, and `gated_delta_operands_fwd` reads
    # it (parent: f591949a..); no other cell builds the op, and a name
    # in `KEPT_RESIDUALS` that a step never emits leaves its text alone.
    # Re-pinned again, PR 54: its `full_attention` layer's backward pass
    # (16 / 2 heads of 256, 48 MiB of dq, dk, dv) is inside the single
    # kernel's budget, one `flash_dkv` with three results and no
    # `flash_dq` (parent: cdf57c01..); the eleven other cells' calls
    # were inside the old budget and keep their text.
    # Re-pinned, PR 68, with `kimilinear-8k` below: q's and k's l2norm
    # and the gated norm a head are the kernels of
    # `ops/pallas/head_norm.py` on the flat tensor, here through the
    # interpreter, no float32 (.., H, 128) view and no product with a
    # 0 / 1 matrix (parents: 9df7a66e.., 3e0cc1d7..); the twelve other
    # cells build neither delta rule nor a grouped `rms_norm` and keep
    # their text.  Re-pinned, PR 69, with `kimilinear-8k` below: the
    # chunk-local kernels of both delta rules read q and k on QKV as it
    # lies and take the l2norm themselves (`gated_delta.py RawQK`; no
    # `head_norm_*` call before them; parents: 068ea25c.., 96c8ef2e..);
    # the twelve other cells build neither op and keep their text.
    # Re-pinned, PR 72, ALONE: the gated-delta kernels address the op's
    # own arrays, here through the interpreter (QKV the one operand of
    # the chunk-operand kernels and dQKV their one gradient, sent by the
    # backward kernel's own async copies; o and dO by lane block of
    # (N, T, Hv x 128): no slice, no transpose, no pad; parent:
    # 6377ad3f..); the thirteen other cells keep their text,
    # `kimilinear-8k` included: `RawQK`'s new fields default, and
    # `channel_delta.py` is not touched
    "qwen3next-16k":
    "667b63e5613e4b029514a078ba72ad8c09515a66265d520f70598b04992d7561",
    # re-pinned, PR 59: its block-diffusion flash kernels walk a
    # scalar-prefetched list of visits (`ops/pallas/
    # flash_block_diffusion.py`, here through the interpreter, a pass
    # jitted so that the six layers share one trace; parent:
    # b012ad2d..); the eleven other cells keep their text: the band and
    # plain kernels' bodies trace the same equations in the same order
    "sdar-8k":
    "af7a5ef6943bc8b15e626e9457d781d6b69cc71403dac83d046184ce45a5ce35",
    # new in PR 51 (a head count a layer type, the head gate, YaRN over
    # half a head, 512 x 512 forward tiles under 512 keys); every other
    # cell keeps its parent's text: `mellum2-16k`'s window of 1024 keeps
    # its 1024 x 1024 forward tile
    "laguna-16k":
    "b8675525a43fd1d91de22eeebe4ff38529017f56c7f87faa3c50ded1d643985b",
    # new in PR 53 (the selective scan and the biased convolution
    # through the interpreter, differential attention on the band and
    # grouped kernels, values that cross recompute segments); every
    # other cell keeps its parent's text: a bias that is absent and a
    # name in `KEPT_RESIDUALS` that a step never emits leave it alone
    "phi4flash-8k":
    "e1f37d39bd46c61613919b9ebbf19e7b476e8bb849ca43a062058ac03b61863d",
    # new in PR 58 (the scalar-a-head scan through the interpreter, the
    # gated norm, one biased convolution over x, B and C, grouped flash
    # attention under a scale of 2^-6, the four multipliers); every
    # other cell keeps its parent's text: a multiplier that is absent
    # appends no op, and the names `KEPT_RESIDUALS` gained are emitted
    # by no other step.  Re-pinned, PR 70: the scan's operand is the
    # convolution's xBC whole (no `split` op before `ssd_scan`; the
    # kernels, here through the interpreter, read x, B and C under three
    # block specs of the one array and write d xBC as one result; the
    # forward rule names xBC beside y and the entry states; parent:
    # d40ed03d..); the thirteen other cells build no such op and keep
    # their text: the name `KEPT_RESIDUALS` gained is emitted by no
    # other step
    "granite4h-8k":
    "184e1704c8ebe55cdeda156072317e49c68e6b9150890b761defdb85f1905b80",
    # new in PR 65 (the lane-decayed delta rule's five kernels through
    # the interpreter, the sigmoid-gated norm a head, latent attention
    # under one direct q projection with nothing rotated); every other
    # cell keeps its parent's text: an argument at its default appends no
    # op, and the two names `KEPT_RESIDUALS` gained are emitted by no
    # other step.  Re-pinned by PR 66 (`channel_delta.py`'s decayed
    # products take a chunk's pairs by levels on the MXU, the forward
    # down to sub-blocks of 4, the backward all the way; the kernels
    # lower through the interpreter into this text); every other cell
    # keeps its parent's text: none builds the op
    "kimilinear-8k":
    "30d48358de96dc053390f5736db72865f32816efab60ff0f0fa217bf459cf01c",
    # new in PR 73 (the vision tower's `flash_segment_*` kernels, the
    # position table's taps, the merge, rotary lanes beside a direct
    # query projection); every other cell keeps its parent's text: none
    # builds a second input, and an argument at its default appends no op.
    # Re-pinned, PR 74: the tower's q and k turn inside `segment_attention`
    # (its `Positions`; no `rope` op over two axes is built) and the
    # kernels' 128-lane layout is `ops/pallas/head_lanes.py`'s two kernels,
    # here through the interpreter (parent: 3e59c88d..); the fourteen
    # other cells build neither and keep their text
    "kimivl-8k":
    "15050e8b3ce5caac085adf9c3fe0b92d4804eafc0fcb9ae520392bdaa7f3d4c0",
}


def step_text(cell_name):
    """The lowered text of a cell's one jitted step at the cell's own
    sizes, built as `benchmarks/run.py` builds it, nothing run."""
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
        batch = family.make_batch(config, dict(cell, chips=1),
                                  np.random.default_rng(0))
        step, state, feeds = fluid.Executor()._prepare(
            main, batch, [loss.name], scope, 1, True)
        return step.lower(state, feeds).as_text()


# window forwards this lowering of a cell's step traces: three a window
# layer, a recompute segment (the AOT build of
# tests/test_chip_compile_cells.py counts two), all of them the
# whole-band step (PR 60); no other cell traces a window kernel
WINDOW_FORWARDS = {"laguna-16k": 9, "mellum2-16k": 18, "phi4flash-8k": 3}


@pytest.mark.parametrize("cell", sorted(STEP_TEXT))
def test_every_existing_cells_step_is_the_parents_text(cell):
    """`tbase-256-dp4` runs `tbase-256`'s Program under a mesh, which is
    a placement of the same step (PR 28)."""
    import hashlib

    before = runtime_stats.snapshot()
    text = _NUMBERED.sub(r"\1", step_text(cell))
    took = runtime_stats.delta(before)
    assert (took["flash_window_forward_whole_band"],
            took["flash_window_forward_tiled"]) == (
        WINDOW_FORWARDS.get(cell, 0), 0)
    assert hashlib.sha256(text.encode()).hexdigest() == STEP_TEXT[cell]


# slow, 197 s.  Between its runs the driver's chip run of `kimilinear-8k`
# guards that the step compiles and fits (`hbm_peak_gb`, the kernels'
# `*_calls` counters), tests/test_chip_compile_cells.py
# `test_a_cells_step_by_its_trace` the trace; the plan against the 15.0 GB
# the configuration states waits for this test
@pytest.mark.slow
def test_the_channel_delta_cells_step_holds_its_kernels_under_the_plan(
        one_chip):
    """The whole training step of `kimilinear-8k` (the published layers
    1-5: four delta layers whose decay is a key lane's own and one
    unrotated latent-attention layer; 8192 rows, bf16 AMP, every layer a
    recompute segment), compiled for the described chip, nothing run.  A
    delta layer's segment keeps (I + A)^-1 and P (67,108,864 +
    33,554,432 bytes a layer), so the step holds `channel_delta_inverse`
    FOUR times, once a layer, `channel_delta_operands_fwd` and
    `channel_delta_fwd` eight (the forward pass's and the recomputed one
    that reads what was kept) and the two backward kernels four; the
    latent layer runs `flash_mla`'s kernels at `joyai-8k`'s shape, its
    backward ONE kernel; no fall-back anywhere.  The plan the
    configuration states (ISSUE 65: under 15.0 GB, the length and the
    cut fixed before the step existed).  (Here and not beside the other
    cells' steps in tests/test_chip_compile_cells.py, whose helpers it
    borrows: before it was `slow` that file was the suite's longest and
    started late, tests/chip_compile.py.)"""
    from test_chip_compile_cells import _cell_step, holds_its_trace

    parameters, _, plan, kernels, took = _cell_step("kimilinear-8k", one_chip)
    holds_its_trace("kimilinear-8k", parameters, took, kernels)
    assert plan["arguments"] == pytest.approx(7.23, abs=0.01)
    assert 9.0 < plan["total"] <= 15.0, plan
    assert (kernels["channel_delta_inverse"],
            kernels["channel_delta_operands_fwd"],
            kernels["channel_delta_operands_bwd"]) == (4, 8, 4)
    assert (kernels["channel_delta_fwd"],
            kernels["channel_delta_bwd"]) == (8, 4)
    assert (kernels["flash_mla_fwd"], kernels["flash_mla_dkv"],
            kernels["flash_mla_dq"]) == (1, 1, 0)
    assert (kernels["short_conv_fwd"], kernels["short_conv_bwd"]) == (8, 4)
    # the output norm's head statistic in a delta layer's forward and
    # recomputed forward, and its one backward pass (PR 68; q's and k's
    # are the chunk-local kernels' own since PR 69: 24 / 12 before)
    assert (kernels["head_norm_fwd"], kernels["head_norm_bwd"]) == (8, 4)
