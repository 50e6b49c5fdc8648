"""The backward pass of `ops/pallas/flash_gqa.py` on the CPU (interpret
mode): the single kernel and the two that hold blocks only, against
each other and against plain attention with the key/value heads
REPEATED over their groups (which the kernels never do); the shape rule
that chooses between them; the counters; the registered costs.  The
forward pass and the op are tests/test_expert_share.py's, Mosaic's own
checks tests/test_chip_compile_flash_attention.py's.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops.pallas import KERNEL_COSTS, flash_gqa

D = flash_gqa.HEAD_DIM
# float32 accumulator bytes a position of the single backward kernel
PER_POSITION = 3 * flash_gqa.LANES * 4


def operands(n, t, heads, kv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    widths = (heads, kv, kv, heads)
    return [jax.random.normal(k, (n, t, h * D)) for k, h in zip(ks, widths)]


def dense(q, k, v, heads, kv):
    n, t, _ = q.shape
    q4 = q.reshape(n, t, heads, D)
    k4 = jnp.repeat(k.reshape(n, t, kv, D), heads // kv, axis=2)
    v4 = jnp.repeat(v.reshape(n, t, kv, D), heads // kv, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q4, k4) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1),
                      v4).reshape(n, t, heads * D)


def _backward_path(monkeypatch, path):
    """Send the backward pass down `path` the only way there is: the
    shape rule's budget (no option chooses)."""
    monkeypatch.setattr(flash_gqa, "FUSED_ACCUMULATOR_BUDGET",
                        {"one_kernel": 1 << 40, "two_kernels": 0}[path])


def _grads(args, w, heads, kv, block_q, block_k):
    def loss(q, k, v):
        o = flash_gqa.flash_gqa(q, k, v, heads, kv, block_q=block_q,
                                block_k=block_k)
        return jnp.sum(o.astype(jnp.float32) * w)

    with jax.default_matmul_precision("highest"):
        return jax.grad(loss, argnums=(0, 1, 2))(*args)


def _took(before):
    took = runtime_stats.delta(before)
    return (took["flash_gqa_backward_fused"],
            took["flash_gqa_backward_split"])


@functools.cache
def _path_grads(path, blocks, block_q, block_k, heads, kv, dtype):
    """q, k, v and the weight of one geometry (two sequences, T of
    `blocks` of the larger block) in float32, and dq, dk, dv through
    the kernels on `path` with the operands in `dtype`, which the
    counters must say the traced backward took.  Once a module: the two
    tests below read the same calls."""
    t = blocks * max(block_q, block_k)
    *args, w = operands(2, t, heads, kv, seed=blocks + heads)
    with pytest.MonkeyPatch.context() as patch:
        _backward_path(patch, path)
        before = runtime_stats.snapshot()
        got = _grads([a.astype(dtype) for a in args], w, heads, kv, block_q,
                     block_k)
        assert _took(before) == ((1, 0) if path == "one_kernel" else (0, 1))
    return args, w, got


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads, kv", [(8, 2), (4, 4)], ids=["gqa", "mha"])
@pytest.mark.parametrize("blocks, block_q, block_k", [
    (1, 32, 32), (2, 32, 32), (4, 32, 32), (2, 32, 16), (2, 16, 32),
    (4, 16, 32)], ids=["1_block", "2_blocks", "4_blocks", "wide_q",
                       "wide_k", "4_blocks_wide_k"])
@pytest.mark.parametrize("path", ["one_kernel", "two_kernels"])
def test_both_backward_paths_give_the_dense_gradients(
        path, blocks, block_q, block_k, heads, kv, dtype):
    """dq, dk and dv, the single backward kernel and the two, over T of
    1, 2 and 4 blocks (diagonal, below-diagonal and skipped blocks) and
    block_q != block_k (a dq block then completes off the diagonal's
    corner, and a pass over the query blocks may complete two or none),
    at 4 query heads a key/value head (dk / dv sum over the group's
    query tiles, the outer axis of the single kernel) and at one."""
    args, w, got = _path_grads(path, blocks, block_q, block_k, heads, kv,
                               dtype)
    want = jax.grad(lambda *a: jnp.sum(dense(*a, heads, kv) * w),
                    argnums=(0, 1, 2))(*args)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == r.shape and g.dtype == dtype, name   # kv heads wide
        if dtype == jnp.float32:    # tests/test_expert_share.py's limits
            np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5,
                                       err_msg="d" + name)
        else:       # p and ds are cast to 8 bits of mantissa before a dot
            np.testing.assert_allclose(
                g.astype(jnp.float32), r, err_msg="d" + name,
                atol=4e-2 * float(jnp.abs(r).max()))


# T of 128 in four blocks of the larger block (three of them geometries
# of the test above: their calls are made once)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads, kv, block_q, block_k", [
    (8, 2, 32, 32), (8, 2, 16, 32), (8, 2, 32, 16), (4, 4, 16, 32),
    (16, 2, 32, 8)], ids=["gqa", "gqa_wide_k", "gqa_wide_q", "mha_wide_k",
                          "8_a_group_wide_q"])
def test_the_two_backward_paths_agree_to_the_bit(heads, kv, block_q, block_k,
                                                  dtype):
    """Same terms in the same order: the single kernel sums dq over the
    key blocks, and dk / dv over the group's query tiles and then the
    query blocks, as the two do."""
    *_, one = _path_grads("one_kernel", 4, block_q, block_k, heads, kv, dtype)
    *_, two = _path_grads("two_kernels", 4, block_q, block_k, heads, kv,
                          dtype)
    for a, b in zip(one, two):
        np.testing.assert_array_equal(a, b)


def test_the_shape_alone_chooses_the_backward_path():
    """The single kernel's accumulators are 1.5 KiB a position whatever
    the group: the cell's 8192 positions fit the budget, 32768 and the
    model's 128000 do not, whatever the operands' dtype; a traced
    backward says which it took, and `flash_gqa_dq` exists on the
    two-kernel path only."""
    edge = flash_gqa.FUSED_ACCUMULATOR_BUDGET // PER_POSITION
    assert flash_gqa.fused_backward_fits(8192)
    assert flash_gqa.fused_backward_fits(edge)
    assert not flash_gqa.fused_backward_fits(edge + 1)
    assert not flash_gqa.fused_backward_fits(32768)
    assert not flash_gqa.fused_backward_fits(128000)

    def kernels(t, heads=8, kv=2):
        args = [jax.ShapeDtypeStruct((1, t, h * D), jnp.bfloat16)
                for h in (heads, kv, kv)]
        before = runtime_stats.snapshot()
        text = jax.jit(jax.grad(
            lambda *a: jnp.sum(flash_gqa.flash_gqa(*a, heads, kv)
                               .astype(jnp.float32)),
            argnums=(0, 1, 2))).lower(*args).as_text(debug_info=True)
        return (sorted(n for n in ("flash_gqa_fwd", "flash_gqa_dkv",
                                   "flash_gqa_dq") if f"pallas_{n}" in text),
                *_took(before))

    assert kernels(8192) == (["flash_gqa_dkv", "flash_gqa_fwd"], 1, 0)
    assert kernels(8192, 4, 4) == (["flash_gqa_dkv", "flash_gqa_fwd"], 1, 0)
    assert kernels(32768) == (
        ["flash_gqa_dkv", "flash_gqa_dq", "flash_gqa_fwd"], 0, 1)


@pytest.mark.parametrize("path", ["one_kernel", "two_kernels"])
def test_key_value_gradients_leave_the_kernels_kv_heads_wide(monkeypatch,
                                                             path):
    """dk, dv are summed over the group in VMEM and dq over the key
    blocks: nothing the backward pass writes is wider than its operand,
    and no per-block partial of a gradient is in the program."""
    _backward_path(monkeypatch, path)
    heads, kv, t = 8, 2, 128
    args = [jax.ShapeDtypeStruct((1, t, h * D), jnp.float32)
            for h in (heads, kv, kv)]
    text = jax.jit(jax.grad(
        lambda *a: jnp.sum(flash_gqa.flash_gqa(
            *a, heads, kv, block_q=32, block_k=32)),
        argnums=(0, 1, 2))).lower(*args).as_text()
    assert f"tensor<1x{t}x{kv * D}xf32>" in text
    assert f"tensor<4x{t}x" not in text         # nk = 4 partials of dq
    assert f"x{t}x{heads * D}xf32>" in text and "x1024xf32>" not in text


def test_the_backward_pass_takes_its_own_blocks_where_they_divide_t():
    """The statistics are block-free, so the backward pass has blocks
    of its own (1024 x 1024); a sequence that is not a whole number of
    them keeps the forward's, as before, and a block size the caller
    gives holds for both passes."""
    def grid_of(t, **blocks):
        args = [jax.ShapeDtypeStruct((1, t, h * D), jnp.bfloat16)
                for h in (8, 2, 2)]
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(flash_gqa.flash_gqa(*a, 8, 2, **blocks)
                               .astype(jnp.float32)),
            argnums=(0, 1, 2)))(*args)
        grids = [e.params["grid_mapping"].grid for e in jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
        assert len(grids) == 2
        return grids

    # forward (N*H/2, nq, nk) at 512; backward (N*Hkv/2, G, nk, nq)
    assert grid_of(4096) == [(4, 8, 8), (1, 4, 4, 4)]
    assert grid_of(1536) == [(4, 3, 3), (1, 4, 3, 3)]
    assert grid_of(256) == [(4, 1, 1), (1, 4, 1, 1)]
    assert grid_of(4096, block_q=256) == [(4, 16, 8), (1, 4, 4, 16)]


def test_kernel_costs_are_registered_under_the_kernels_names():
    """`flash_gqa_dkv` names the two-kernel path's dk / dv kernel (two
    gradients out) and the single backward kernel (three): the second
    carries dq's dense-equivalent work too, so a step's total is the
    same on both paths, seven matmuls' worth with the forward."""
    t, heads, kv = 8192, 32, 8
    wide, narrow = ((1, t, heads * D), 2), ((1, t, kv * D), 2)
    stat = ((heads, 8, t), 4)
    scores = heads * t * t
    fwd_in = [wide, narrow, narrow]
    bwd_in = [wide, narrow, narrow, wide, wide, stat]
    for name, operands_, results, matmuls in (
            ("flash_gqa_fwd", fwd_in, [wide, stat], 2),
            ("flash_gqa_dkv", bwd_in, [narrow, narrow], 3),
            ("flash_gqa_dq", bwd_in, [wide], 1),
            ("flash_gqa_dkv", bwd_in, [wide, narrow, narrow], 4)):
        flops, nbytes = KERNEL_COSTS[name](operands_, results)
        # dense-equivalent: 2 x 64 a score and matmul, plus the soft-max
        assert 2 * D * matmuls * scores <= flops \
            <= (2 * D * matmuls + 8) * scores
        assert nbytes == sum(
            size * int(np.prod(dims)) for dims, size in operands_ + results)
    two = (KERNEL_COSTS["flash_gqa_dkv"](bwd_in, [narrow, narrow])[0]
           + KERNEL_COSTS["flash_gqa_dq"](bwd_in, [wide])[0])
    one, _ = KERNEL_COSTS["flash_gqa_dkv"](bwd_in, [wide, narrow, narrow])
    assert one == two
