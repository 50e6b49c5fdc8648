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
              **blocks):
    """Compile forward + backward of one call for the described chip:
    ([kernel name, number of results] sorted by name, (fused, split))."""
    from paddle_tpu.observe import cost

    shape = (n, t, heads * d) if layout == "nthd" else (n, heads, t, d)

    def loss(q, k, v):
        with jax.named_scope("flash_attention:9"):
            o = fa.pallas_flash_attention(
                q, k, v, None, d ** -0.5, causal, layout=layout,
                n_head=heads if layout == "nthd" else None, **blocks)
        return jnp.sum(o.astype(F32))

    before = runtime_stats.snapshot()
    compiled = _compile_args(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
        *[jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)] * 3)
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
