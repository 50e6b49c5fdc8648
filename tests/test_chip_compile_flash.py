"""The chip's compiler on `ops/pallas/flash_attention.py`'s backward
pass, at the two cells' shapes and at the shape rule's edges.

`tests/test_chip_compile.py` says why such tests exist and how they
work (a v5e that is DESCRIBED, not attached; nothing runs), and lends
its fixtures and helpers.  These cases are a file of their own because
`--dist loadfile` gives a file to ONE worker, and that file is already
the suite's longest.
"""

from __future__ import annotations

import re

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops.pallas import flash_attention as fa
from test_chip_compile import (BF16, F32, _compile_args,  # noqa: F401
                               one_chip, topology)

D = 128


def _backward(one_chip, n, t, heads, dtype, d=D, layout="nthd", causal=True,
              kv_heads=None, **blocks):
    """Compile forward + backward of one call for the described chip:
    ([kernel name, number of results] sorted by name, (fused, split))."""
    from paddle_tpu.observe import cost

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
    return sorted(results.items()), (took["flash_attention_backward_fused"],
                                     took["flash_attention_backward_split"])


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
    # the budget's edge: 32 MiB of dq beside 1024 x 1024 blocks
    dict(n=1, t=fa.FUSED_ACCUMULATOR_BUDGET // (4 * D), heads=1, dtype=BF16),
], ids=["folded_layout", "not_causal", "d_head_64", "512x1024_no_limit",
        "12288_no_limit", "budget_edge_65536"])
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


def test_a_window_kernels_registered_cost_is_the_bands(one_chip):
    from paddle_tpu.observe import cost

    def loss(q, k, v):
        with jax.named_scope("flash_attention:9"):
            o = fa.pallas_flash_attention(
                q, k, v, None, D ** -0.5, True, layout="nthd", n_head=32,
                n_kv_head=4, window=1024)
        return jnp.sum(o.astype(F32))

    compiled = _compile_args(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
        *[jax.ShapeDtypeStruct((1, 16384, h * D), BF16, sharding=one_chip)
          for h in (32, 4, 4)])
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
def test_a_band_call_past_the_budget_compiles_the_two_kernels(one_chip,
                                                              window):
    """32768 positions: 48 MiB of dq, dk and dv: a kernel for dk / dv
    over the group's heads and one for dq, blocks only in VMEM."""
    assert not fa.band_backward_fits(32768, D)
    kernels, took = _backward(one_chip, 1, 32768, 32, BF16, kv_heads=4,
                              **({"window": window} if window else {}))
    prefix = "flash_window_" if window else "flash_"
    assert kernels == [(prefix + "dkv", 2), (prefix + "dq", 1),
                       (prefix + "fwd", 2)]
    assert took == (0, 1)


# -- the cells this file's kernels serve keep their steps -------------------
#
# sha256 of every existing cell's lowered step (`fn.lower(state,
# feeds).as_text()`, the state by its shapes) on the CPU under this
# suite's conftest (8 virtual devices, "highest" matmuls), jax 0.9.0, as
# the commit before the band kernels gives it (PR 38: the kernels
# `olmoe-4k` and `ouro-4k` call grew two arguments, the decoder
# builder four; with `n_kv_head == n_head`, `window=None`, no
# `head_dim` and one flat `rope_parameters` every step is the
# parent's text).  A PR that means to change a step updates its line.
STEP_TEXT = {
    "tbase-256":
    "59a1ce76e7b7759970f178a35b4cde1478f17086a0768c5142a1e93f2c1efe07",
    "resnet50-b128":
    "2c02c843a21d4dc13cc7419ed42fac09fbe5417671d362434b8837957e9e9d4b",
    "olmoe-4k":
    "473330796808045c71562781385f331caaacdaaa3611bf06d631209d901e5148",
    "lfm2-8k":
    "07601de3300a1e3f526b5e32368123b01ae99e2bd07453ba56341b5191abc75b",
    "joyai-8k":
    "763a665ebaff790b785b98be78745a908131bfb1dc5e2ffa745091d66f02a53d",
    "ouro-4k":
    "3681e31fdf4a35fde50a5163cece591bc623b0aa78be55d43077619c84122e4c",
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
        for var in main.global_block().vars.values():
            if var.persistable and all(int(s) > 0 for s in var.shape):
                scope.set_var(var.name, jax.ShapeDtypeStruct(
                    tuple(int(s) for s in var.shape),
                    np.dtype(str(var.dtype))))
        batch = family.make_batch(config, dict(cell, chips=1),
                                  np.random.default_rng(0))
        step, state, feeds = fluid.Executor()._prepare(
            main, batch, [loss.name], scope, 1, True)
        return step.lower(state, feeds).as_text()


@pytest.mark.parametrize("cell", sorted(STEP_TEXT))
def test_every_existing_cells_step_is_the_parents_text(cell):
    """`tbase-256-dp4` runs `tbase-256`'s Program under a mesh, which is
    a placement of the same step (PR 28)."""
    import hashlib

    assert hashlib.sha256(step_text(cell).encode()).hexdigest() \
        == STEP_TEXT[cell]
