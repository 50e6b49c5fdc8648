"""`ops/pallas/grouped_matmul.py` against `jax.lax.ragged_dot` and its
vjp, in interpret mode on the CPU: forward, dX and dW over group
patterns, widths and dtypes; the rows past the groups' sum; the tile
rule; the Mosaic lowering of each kernel at the four cells' shapes
(the chip's compiler has its say in tests/test_chip_compile_cells.py)."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops.pallas import grouped_matmul as gm
from test_pallas_lowering import _export_tpu

M, G = 512, 4           # four row tiles of 128
PATTERNS = {
    "uniform": (128, 128, 128, 128),
    "empty_first": (0, 200, 200, 112),
    "empty_middle": (200, 0, 0, 312),
    "empty_last": (300, 212, 0, 0),
    "one_group": (0, 512, 0, 0),
    "short_by_a_tile": (100, 100, 100, 84),
    "short_by_part_of_a_tile": (100, 150, 100, 100),
    "short_by_all": (0, 0, 0, 0),
    "edge_inside_a_tile": (130, 61, 190, 131),
}
WIDTHS = {"896x2304": (896, 2304), "1536x2048": (1536, 2048),
          "128x128": (128, 128)}
TOLERANCE = {"float32": 1e-4, "bfloat16": 2e-2}


def _operands(m, k, n, g, dtype, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(m, k), dtype),
            jnp.asarray(rng.randn(g, k, n) / np.sqrt(k), dtype),
            jnp.asarray(rng.randn(m, n), dtype))


def _reference(lhs, rhs, sizes, ct):
    """ragged_dot and its vjp, the rows of no group zero going in and
    coming out (what `_held_rows` did around it)."""
    mine = (np.arange(lhs.shape[0]) < int(np.sum(sizes)))[:, None]

    def f(l, r):
        return jnp.where(mine, jax.lax.ragged_dot(
            jnp.where(mine, l, 0), r, jnp.asarray(sizes, jnp.int32)), 0)

    out, vjp = jax.vjp(f, lhs, rhs)
    return (out,) + vjp(ct)


def _close(got, want, dtype, what):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    tol = TOLERANCE[jnp.dtype(dtype).name]
    np.testing.assert_allclose(
        got, want, rtol=tol, atol=tol * max(1.0, float(np.abs(want).max())),
        err_msg=what)


def _check(product, sizes, k, n, dtype):
    lhs, rhs, ct = _operands(M, k, n, len(sizes), dtype)
    out, vjp = jax.vjp(product, lhs, rhs)
    dlhs, drhs = vjp(ct)
    assert out.dtype == dlhs.dtype == drhs.dtype == jnp.dtype(dtype)
    for got, want, what in zip((out, dlhs, drhs),
                               _reference(lhs, rhs, sizes, ct),
                               ("forward", "dX", "dW")):
        _close(got, want, dtype, what)
    total = int(np.sum(sizes))
    # exact zeros past the groups' sum, forward and in the gradient
    assert not np.asarray(out, np.float32)[total:].any()
    assert not np.asarray(dlhs, np.float32)[total:].any()
    empty = [g for g, s in enumerate(sizes) if s == 0]
    assert not np.asarray(drhs, np.float32)[empty].any()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_the_three_kernels_are_ragged_dot_and_its_vjp(pattern, widths, dtype):
    sizes = PATTERNS[pattern]
    k, n = WIDTHS[widths]
    before = runtime_stats.snapshot()
    _check(lambda l, r: gm.grouped_matmul(l, r, jnp.asarray(sizes, jnp.int32)),
           sizes, k, n, dtype)
    took = runtime_stats.delta(before)
    assert took["grouped_matmuls_kernel"] == 1
    assert took["grouped_matmuls_xla"] == 0


@pytest.mark.parametrize("tilings", [
    # K cut: the float32 sum in VMEM; N cut: the rows read twice
    ((128, 128, 256), (128, 128, 256), (128, 128, 256)),
    ((64, 256, 128), (64, 256, 128), (64, 256, 128)),
    ((256, 128, 128), (256, 128, 128), (256, 128, 128)),
], ids=["k_cut", "n_cut", "both_cut"])
@pytest.mark.parametrize("pattern", ["edge_inside_a_tile", "empty_middle",
                                     "short_by_part_of_a_tile"])
def test_cut_widths_sum_and_revisit_like_whole_ones(pattern, tilings):
    sizes = PATTERNS[pattern]
    visits = gm._visits(jnp.asarray(sizes, jnp.int32), m=M, tm=tilings[0][0])
    _check(lambda l, r: gm._product(l, r, visits, tilings),
           sizes, 256, 256, "float32")


@pytest.mark.parametrize("rows", [M, 2 * M, 5 * M])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_a_longer_buffers_tables_serve_a_shorter_one(pattern, rows):
    """`row_visits` over `rows` rows, given to the products over M <=
    `rows` rows (a share's row buffers under the tables of T*k): the
    same three results; only the tail of zeros is the buffer's own, and
    an empty group that sits past the shorter buffer's last tile reads
    inside it."""
    sizes = PATTERNS[pattern]
    counts = jnp.asarray(sizes, jnp.int32)
    _check(lambda l, r: gm.grouped_matmul(
        l, r, counts, gm.row_visits(counts, rows)), sizes, 256, 128,
        "float32")
    assert gm.row_visits(counts, rows + 64) is None


def test_a_width_no_tile_divides_keeps_ragged_dot():
    sizes = (5, 0, 20, 3)
    lhs, rhs, ct = _operands(32, 24, 40, 4, "float32")
    before = runtime_stats.snapshot()
    out, vjp = jax.vjp(lambda l, r: gm.grouped_matmul(
        l, r, jnp.asarray(sizes, jnp.int32)), lhs, rhs)
    took = runtime_stats.delta(before)
    assert (took["grouped_matmuls_kernel"], took["grouped_matmuls_xla"]) \
        == (0, 1)
    for got, want in zip((out,) + vjp(ct), _reference(lhs, rhs, sizes, ct)):
        _close(got, want, "float32", "fallback")
    assert not np.asarray(out)[28:].any()
    assert gm.tiles_for(512, 100, 128, 4, 2) is None
    assert gm.tiles_for(512, 128, 200, 4, 2) is None
    assert gm.tiles_for(24, 128, 128, 4, 2) is None     # rows no tile divides


# the sorted-row buffers of the four cells: rows, groups, D, H
CELLS = {"mellum2-16k": (24576, 8, 2304, 896),
         "lfm2-8k": (6144, 8, 2048, 1536),
         "joyai-8k": (3072, 8, 2048, 768),
         "olmoe-4k": (131072, 64, 2048, 1024)}


# what the rule takes at the cells' smallest row buffers, bf16: forward,
# dX, dW of the up- and of the down-projection (timed alone on the
# chip, PERF.md PR 40)
TILINGS = {
    "mellum2-16k": (((128, 2304, 896), (128, 896, 2304), (128, 1152, 896)),
                    ((128, 896, 2304), (128, 2304, 896), (128, 896, 1152))),
    "lfm2-8k": (((128, 2048, 768), (128, 1536, 1024), (128, 1024, 768)),
                ((128, 1536, 1024), (128, 2048, 768), (128, 768, 1024))),
    "joyai-8k": (((128, 2048, 768), (128, 768, 2048), (128, 1024, 768)),
                 ((128, 768, 2048), (128, 2048, 768), (128, 768, 1024))),
    "olmoe-4k": (((128, 2048, 1024), (128, 1024, 2048), (128, 1024, 1024)),
                 ((128, 1024, 2048), (128, 2048, 1024), (128, 1024, 1024))),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_tile_rule_at_the_cells_shapes(cell):
    """A kernel that keeps the rows keeps K whole, so a group's weight
    block stays in VMEM while its row tiles pass (the whole weight
    where the budget holds it, N cut where not); dW cuts the wider of K
    and N first; 128 rows a tile; all within the budget, in float32
    too."""
    m, g, d, h = CELLS[cell]
    up, down = TILINGS[cell]
    assert gm.tiles_for(m, d, h, g, 2) == up
    assert gm.tiles_for(m, h, d, g, 2) == down
    for (k, n), (fwd, dx, dw) in (((d, h), up), ((h, d), down)):
        assert fwd[1] == k and dx[1] == n
        for itemsize in (2, 4):
            fwd, dx, dw = gm.tiles_for(m, k, n, g, itemsize)
            assert gm._kept_bytes(*fwd, k, itemsize) <= gm.VMEM_BUDGET
            assert gm._kept_bytes(*dx, n, itemsize) <= gm.VMEM_BUDGET
            assert gm._contracted_bytes(*dw, k, itemsize) <= gm.VMEM_BUDGET


@pytest.mark.parametrize("kernel", ["forward", "dx", "dw"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_each_kernel_lowers_for_tpu_at_the_cells_shapes(cell, kernel):
    m, g, d, h = CELLS[cell]
    fwd, dx, dw = gm.tiles_for(m, d, h, g, 2)
    bf16 = jnp.bfloat16
    lhs, rhs, ct, sizes = (jax.ShapeDtypeStruct(s, t) for s, t in (
        ((m, d), bf16), ((g, d, h), bf16), ((m, h), bf16),
        ((g,), jnp.int32)))
    def visits(s):
        return gm.row_visits(s, m)

    if kernel == "forward":
        exp = _export_tpu(lambda l, r, s: gm._rows_kept(l, r, visits(s), fwd),
                          lhs, rhs, sizes)
        assert exp.out_avals[0].shape == (m, h)
    elif kernel == "dx":
        exp = _export_tpu(lambda c, r, s: gm._rows_kept(
            c, r, visits(s), dx, transposed=True), ct, rhs, sizes)
        assert exp.out_avals[0].shape == (m, d)
    else:
        exp = _export_tpu(lambda l, c, s: gm._rows_contracted(
            l, c, visits(s), g, dw, bf16), lhs, ct, sizes)
        assert exp.out_avals[0].shape == (g, d, h)
    assert "pallas_ragged_dot" in exp.mlir_module()
    assert "vmem_limit_bytes" not in exp.mlir_module()


def test_a_shares_step_holds_no_ragged_dot_and_traces_each_kernel_once(
        monkeypatch):
    """The step of a layer that holds a share, at tileable widths: in
    every one of its three row buffers, T*k included, each grouped
    matmul is the kernel, and the step holds no `ragged_dot`; the
    counter reads what was traced.  A kernel is traced ONCE a shape:
    the backward pass recomputes the section, and its forward products
    meet the traces the forward pass left, and all of them walk ONE set
    of visit tables (`grouped_matmul` names the
    mesh context, which jax keys a jitted trace on; should a jax
    upgrade key it otherwise, the step would trace and lower 24 kernels
    in every warm start, and this count says so)."""
    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.ops import moe_dropless

    # (shapes no other test of this file leaves traced)
    t, d, h, e, held, k = 1024, 128, 256, 32, 4, 2
    sizes = moe_dropless.row_buffer_sizes(t, k, e, held)
    assert sizes == (512, 1024, t * k)
    impl = get_op_impl("moe_dropless")
    attrs = {"top_k": k, "experts_held": [4, held], "router_gradient": False}
    rng = np.random.RandomState(0)
    vals = [jnp.asarray(rng.randn(*s) * 0.1, jnp.float32) for s in (
        (t, d), (d, e), (held, d, h), (held, d, h), (held, h, d))]

    def loss(x, gate, w1, w3, w2):
        o = impl(OpContext(jax.random.PRNGKey(0), 0),
                 {"X": [x], "GateW": [gate], "W1": [w1], "W3": [w3],
                  "W2": [w2]}, attrs)
        return jnp.sum(jnp.sin(o["Out"][0]))

    traced = []
    pallas_call = gm._pallas_call

    def counted(kernel, **kw):
        traced.append((kernel.func.__name__, kw["out_shape"].shape))
        return pallas_call(kernel, **kw)

    tables = []
    visits = gm._visits
    monkeypatch.setattr(gm, "_pallas_call", counted)
    monkeypatch.setattr(gm, "_visits", lambda s, m, tm: (
        tables.append((m, tm)), visits(s, m=m, tm=tm))[1])
    moe_dropless._branch.cache_clear()
    before = runtime_stats.snapshot()
    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 2, 3, 4)))(*vals))
    took = runtime_stats.delta(before)
    # three products a branch, traced forward and again where the
    # backward pass recomputes the section: 3 row buffers x 2 x 3
    assert took["grouped_matmuls_kernel"] == 18
    assert took["grouped_matmuls_xla"] == 0
    assert "pallas_call" in jaxpr and "ragged_dot" not in jaxpr
    # one set of tables, over T*k rows, for all of them
    assert tables == [(t * k, 128)]
    # a row buffer's six kernels: forward and dX of the up- and of the
    # down-projection, and their two dW
    assert sorted(traced) == sorted(
        [("_kept_kernel", (rows, n)) for rows in sizes for n in (d, h)] * 2
        + [("_contracted_kernel", (held,) + kn) for _ in sizes
           for kn in ((d, h), (h, d))])
