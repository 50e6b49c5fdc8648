"""The kernels of `ops/pallas/flash_gqa.py` on the CPU (interpret mode),
against plain attention with the key/value heads REPEATED over their
groups (which the kernels never do).  The forward: its output AND its
logsumexp at the tiles the sequence's length chooses and at tiles given
by hand, and which tile a length chooses.  The backward: the single
kernel and the two that hold blocks only, against each other and the
reference; the shape rule that chooses between them; the counters; the
registered costs.  The op is tests/test_expert_share.py's, Mosaic's own
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


@functools.partial(jax.jit, static_argnums=(3, 4))
def dense(q, k, v, heads, kv):
    n, t, _ = q.shape
    q4 = q.reshape(n, t, heads, D)
    k4 = jnp.repeat(k.reshape(n, t, kv, D), heads // kv, axis=2)
    v4 = jnp.repeat(v.reshape(n, t, kv, D), heads // kv, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q4, k4) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1),
                      v4).reshape(n, t, heads * D)


@functools.partial(jax.jit, static_argnums=(4, 5))
def dense_grads(q, k, v, w, heads, kv):
    """dq, dk, dv of sum(dense * w): jitted, because an eager float32
    reference compiles every primitive of every shape by itself."""
    return jax.grad(lambda *a: jnp.sum(dense(*a, heads, kv) * w),
                    argnums=(0, 1, 2))(q, k, v)


@functools.partial(jax.jit, static_argnums=(2, 3))
def dense_lse(q, k, heads, kv):
    """(N*heads, T): the logsumexp of every query's allowed scores."""
    n, t, _ = q.shape
    q4 = q.reshape(n, t, heads, D)
    k4 = jnp.repeat(k.reshape(n, t, kv, D), heads // kv, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q4, k4) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jax.nn.logsumexp(s, -1).reshape(n * heads, t)


def _small_tiles(monkeypatch):
    """The shape rule at a size the interpreter runs: a tile of 32
    where T is a whole number of them, of 16 elsewhere."""
    monkeypatch.setattr(flash_gqa, "DEFAULT_BLOCK", 32)
    monkeypatch.setattr(flash_gqa, "FALLBACK_BLOCK", 16)


# T and the tile given by hand (None: the length chooses, under
# `_small_tiles`), and the tile that must come of it
FORWARD_TILES = {
    "1_tile": (32, None, (32, 32)), "2_tiles": (64, None, (32, 32)),
    "4_tiles": (128, None, (32, 32)),
    "fall_back_tile": (48, None, (16, 16)),
    "wide_q_by_hand": (64, (32, 16), (32, 16)),
    "wide_k_by_hand": (64, (16, 32), (16, 32))}


# every head geometry in float32; bfloat16 changes the casts, not the
# geometry, and runs at the first
@pytest.mark.parametrize("heads, kv, dtype", [
    (8, 2, jnp.float32), (8, 2, jnp.bfloat16), (32, 8, jnp.float32),
    (4, 4, jnp.float32)],
    ids=["gqa_8_over_2-f32", "gqa_8_over_2-bf16", "gqa_32_over_8-f32",
         "mha-f32"])
@pytest.mark.parametrize("tiles", sorted(FORWARD_TILES))
def test_the_forward_gives_the_dense_output_and_logsumexp(
        monkeypatch, tiles, heads, kv, dtype):
    """o and lse of the forward kernel, whose grid step is one key/value
    head and the query pairs that read it (two pairs at 8 / 2 and 32 /
    8, one pair of heads that read their own at MHA): over one tile
    (the diagonal's, masked), two and four (tiles below the diagonal
    run unmasked, tiles above it are skipped), a length only the
    fall-back tile divides, and oblong tiles, where the diagonal
    crosses two tiles of a row or a column.  The statistics keep the
    (N*H, 8, T) form the backward reads: head n*H + h, eight equal
    sublanes."""
    _small_tiles(monkeypatch)
    t, by_hand, want_tile = FORWARD_TILES[tiles]
    q, k, v, _ = operands(2, t, heads, kv, seed=t + heads)
    blocks = by_hand or flash_gqa.default_blocks(t)
    geo = flash_gqa._Geometry(q, k, heads, kv, *blocks)
    assert (geo.block_q, geo.block_k) == want_tile
    with jax.default_matmul_precision("highest"):
        o, lse8 = flash_gqa._flash_fwd(
            q.astype(dtype), k.astype(dtype), v.astype(dtype), D ** -0.5,
            geo)
        want_o, want_lse = dense(q, k, v, heads, kv), dense_lse(q, k, heads,
                                                                kv)
    assert o.dtype == dtype and lse8.shape == (2 * heads, 8, t)
    np.testing.assert_array_equal(lse8, jnp.broadcast_to(lse8[:, :1],
                                                         lse8.shape))
    if dtype == jnp.float32:        # tests/test_expert_share.py's limits
        np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(lse8[:, 0], want_lse, rtol=1e-5,
                                   atol=1e-5)
    else:       # operands and p at 8 bits of mantissa
        np.testing.assert_allclose(o.astype(jnp.float32), want_o,
                                   atol=4e-2 * float(jnp.abs(want_o).max()))
        np.testing.assert_allclose(lse8[:, 0], want_lse, atol=4e-2)


def test_a_scale_that_is_no_power_of_two_multiplies_the_scores():
    """0.125 rides on q, exactly; any other scale would round q's
    bfloat16 and stays on the float32 scores."""
    heads, kv, t = 8, 2, 32
    q, k, v, _ = operands(1, t, heads, kv, seed=5)
    with jax.default_matmul_precision("highest"):
        got = flash_gqa.flash_gqa(q, k, v, heads, kv, scale=0.1, block_q=16,
                                  block_k=16)
        want = dense(q * (0.1 * D ** 0.5), k, v, heads, kv)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _pallas_calls(t, heads=8, kv=2, **blocks):
    """(grid, q block, k block) of the forward and the backward calls a
    gradient of `t` positions traces."""
    args = [jax.ShapeDtypeStruct((1, t, h * D), jnp.bfloat16)
            for h in (heads, kv, kv)]
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(flash_gqa.flash_gqa(*a, heads, kv, **blocks)
                           .astype(jnp.float32)),
        argnums=(0, 1, 2)))(*args)
    maps = [e.params["grid_mapping"] for e in jaxpr.eqns
            if e.primitive.name == "pallas_call"]
    def rows_lanes(block):      # a side is an int or a Blocked(int)
        return tuple(getattr(b, "block_size", b) for b in block[1:])

    return [(m.grid, *(rows_lanes(m.block_mappings[i].block_shape)
                       for i in (0, 1))) for m in maps]


@pytest.mark.parametrize("t, tile, steps", [
    (512, 512, 1), (1024, 1024, 1), (1536, 512, 3), (8192, 1024, 8),
    (32768, 1024, 32)])
def test_the_sequence_length_alone_chooses_the_forwards_tile(t, tile, steps):
    """1024 x 1024 where T is a whole number of them (a shorter
    sequence is one tile), 512 x 512 elsewhere: read from the traced
    call, which no option reaches.  The grid is (N*Hkv, q blocks, k
    blocks) and a q block as wide as the G/2 = 2 pairs of a key/value
    head."""
    assert flash_gqa.default_blocks(t) == (
        (1024, 1024) if t % 1024 == 0 or t < 1024 else (512, 512))
    forward = _pallas_calls(t)[0]
    assert forward == ((2, steps, steps), (tile, 2 * 128), (tile, 128))
    # one pair a step where each head reads its own
    assert _pallas_calls(t, 4, 4)[0] == (
        (2, steps, steps), (tile, 128), (tile, 128))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("t", [32, 128, 48],
                         ids=["1_tile", "4_tiles", "fall_back_tile"])
def test_gradients_through_the_tiles_the_length_chooses(monkeypatch, t,
                                                        dtype):
    """The backward kernels on the new forward's residuals (o and lse
    at the tile the length chose, summed in another order than a 16 x 16
    forward's): dq, dk and dv against the dense reference, within the
    limits of the test below."""
    _small_tiles(monkeypatch)
    heads, kv = 8, 2
    *args, w = operands(2, t, heads, kv, seed=t)
    got = _grads([a.astype(dtype) for a in args], w, heads, kv, None, None)
    want = dense_grads(*args, w, heads, kv)
    for name, g, r in zip("qkv", got, want):
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5,
                                       err_msg="d" + name)
        else:
            np.testing.assert_allclose(
                g.astype(jnp.float32), r, err_msg="d" + name,
                atol=4e-2 * float(jnp.abs(r).max()))


def _backward_path(monkeypatch, path):
    """Send the backward pass down `path` the only way there is: the
    shape rule's budget (no option chooses)."""
    monkeypatch.setattr(flash_gqa, "FUSED_ACCUMULATOR_BUDGET",
                        {"one_kernel": 1 << 40, "two_kernels": 0}[path])


def _loss(w, heads, kv, block_q, block_k):
    def loss(q, k, v):
        o = flash_gqa.flash_gqa(q, k, v, heads, kv, block_q=block_q,
                                block_k=block_k)
        return jnp.sum(o.astype(jnp.float32) * w)

    return loss


def _grads(args, w, heads, kv, block_q, block_k):
    with jax.default_matmul_precision("highest"):
        return jax.grad(_loss(w, heads, kv, block_q, block_k),
                        argnums=(0, 1, 2))(*args)


def _took(before):
    took = runtime_stats.delta(before)
    return (took["flash_gqa_backward_fused"],
            took["flash_gqa_backward_split"])


@functools.cache
def _forward(blocks, block_q, block_k, heads, kv, dtype):
    """q, k, v and the weight of one geometry (two sequences, T of
    `blocks` of the larger block) in float32, and the pull-back of the
    weighted loss through the kernels with the operands in `dtype`: ONE
    forward pass a geometry, which both backward paths read (the shape
    rule is asked when the pull-back is called)."""
    t = blocks * max(block_q, block_k)
    *args, w = operands(2, t, heads, kv, seed=blocks + heads)

    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(_loss(w, heads, kv, block_q, block_k),
                          *(a.astype(dtype) for a in args))
    return args, w, pull


@functools.cache
def _path_grads(path, blocks, block_q, block_k, heads, kv, dtype):
    """`_forward`'s operands and weight, and dq, dk, dv through the
    kernels on `path`, which the counters must say the traced backward
    took.  Once a module: the two tests below read the same calls."""
    args, w, pull = _forward(blocks, block_q, block_k, heads, kv, dtype)
    with pytest.MonkeyPatch.context() as patch, \
            jax.default_matmul_precision("highest"):
        _backward_path(patch, path)
        before = runtime_stats.snapshot()
        got = pull(jnp.ones((), jnp.float32))
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
    want = dense_grads(*args, w, heads, kv)
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


def test_both_passes_take_the_tile_the_length_chooses():
    """The statistics are block-free, so the passes need not agree, but
    one rule serves both (1024 x 1024, or 512 x 512 where T is not a
    whole number of those), and a block size the caller gives holds for
    both passes."""
    def grids(t, **blocks):
        calls = _pallas_calls(t, **blocks)
        assert len(calls) == 2
        return [grid for grid, *_ in calls]

    # forward (N*Hkv, nq, nk); backward (N*Hkv/2, G, nk, nq)
    assert grids(4096) == [(2, 4, 4), (1, 4, 4, 4)]
    assert grids(1536) == [(2, 3, 3), (1, 4, 3, 3)]
    assert grids(256) == [(2, 1, 1), (1, 4, 1, 1)]
    assert grids(4096, block_q=256) == [(2, 16, 4), (1, 4, 4, 16)]


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
