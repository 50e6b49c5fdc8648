"""Latent attention's ops on the CPU: the Pallas kernels of
`ops/pallas/flash_mla.py` in interpret mode against plain attention on
the concatenated 192-wide queries and keys (forward and all five
gradients, the ONE rotary key's summed over the heads), the
`latent_attention` op on both paths, and `rope` over pairs against
`rope` over halves under the column permutation that maps one onto the
other.  Mosaic's own checks are tests/test_chip_compile_kernels.py's.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpContext, get_op_impl
from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops.pallas import KERNEL_COSTS, flash_mla
from op_test import with_pull_back

NOPE, ROPE = flash_mla.NOPE_DIM, flash_mla.ROPE_DIM


def operands(n, t, h, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = [(n, t, h * NOPE), (n, t, h * ROPE), (n, t, h * NOPE),
              (n, t, ROPE), (n, t, h * NOPE), (n, t, h * NOPE)]
    return [jax.random.normal(k, s, dtype) for k, s in zip(ks, shapes)]


def dense(q_nope, q_rope, k_nope, k_rope, v):
    """Plain causal attention on q, k of 192 lanes a head: the rotary
    key REPEATED over the heads, which is what the kernels never do."""
    n, t, _ = q_nope.shape
    h = q_nope.shape[-1] // NOPE
    q = jnp.concatenate([q_nope.reshape(n, t, h, NOPE),
                         q_rope.reshape(n, t, h, ROPE)], axis=-1)
    k = jnp.concatenate([k_nope.reshape(n, t, h, NOPE),
                         jnp.repeat(k_rope[:, :, None], h, axis=2)], axis=-1)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) * (NOPE + ROPE) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1),
                   v.reshape(n, t, h, -1))
    return o.reshape(n, t, -1)


# (N, T, H, block_q, block_k): square blocks, q blocks of two k blocks
# (the diagonal crosses a block off its corner), k blocks of two q
# blocks, and one block that is the whole sequence
@pytest.mark.parametrize("geometry", [
    (2, 256, 2, 128, 128), (1, 256, 2, 128, 64), (1, 256, 2, 64, 128),
    (1, 128, 4, 512, 512)], ids=["square", "wide_q", "wide_k", "one_block"])
def test_flash_mla_matches_dense_attention_forward_and_backward(geometry):
    n, t, h, bq, bk = geometry
    *args, w = operands(n, t, h)

    def kernel(*a):
        return flash_mla.flash_mla(*a, block_q=bq, block_k=bk)

    # one forward pass each, its pull-back called on the weight: the
    # gradients of sum(out * w)
    out, pull = jax.vjp(kernel, *args)
    ref, *want = with_pull_back(dense, w)(*args)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    got = pull(w)
    for name, g, r in zip(("q_nope", "q_rope", "k_nope", "k_rope", "v"),
                          got, want):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(g, r, atol=5e-5 * float(jnp.abs(r).max()),
                                   err_msg=name)
    # the one rotary key's gradient is the sum over the heads'
    assert got[3].shape == (n, t, ROPE)


def _backward_path(monkeypatch, path):
    """Send the backward pass down `path` the only way there is: the
    shape rule's budget (no option chooses)."""
    monkeypatch.setattr(flash_mla, "FUSED_ACCUMULATOR_BUDGET",
                        {"one_kernel": 1 << 40, "two_kernels": 0}[path])


@functools.cache
def _forward(blocks, block_q, block_k, heads, dtype):
    """The operands and the weight of one geometry (one sequence, T of
    `blocks` of the larger block) in float32, and the pull-back of the
    weighted loss through the kernels with the operands in `dtype`: ONE
    forward pass a geometry, which both backward paths read (the shape
    rule is asked when the pull-back is called)."""
    t = blocks * max(block_q, block_k)
    *args, w = operands(1, t, heads, seed=blocks + heads)
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(
            lambda *a: jnp.sum(flash_mla.flash_mla(
                *a, block_q=block_q, block_k=block_k).astype(jnp.float32) * w),
            *(a.astype(dtype) for a in args))
    return args, w, pull


@functools.cache
def _path_grads(path, blocks, block_q, block_k, heads, dtype):
    """`_forward`'s operands and weight, and all five gradients through
    the kernels on `path`, which the counters must say the traced
    backward took.  Once a module: the two tests below read the same
    calls."""
    args, w, pull = _forward(blocks, block_q, block_k, heads, dtype)
    with pytest.MonkeyPatch.context() as patch, \
            jax.default_matmul_precision("highest"):
        _backward_path(patch, path)
        before = runtime_stats.snapshot()
        got = pull(jnp.ones((), jnp.float32))
        took = runtime_stats.delta(before)
        assert (took["flash_mla_backward_fused"],
                took["flash_mla_backward_split"]) == (
                    (1, 0) if path == "one_kernel" else (0, 1))
    return args, w, got


@functools.cache
def _dense_grads(blocks, block_q, block_k, heads):
    """The reference's gradients on `_path_grads`' float32 operands:
    once a geometry, whatever the path and the kernels' dtype."""
    *args, w = operands(1, blocks * max(block_q, block_k), heads,
                        seed=blocks + heads)
    return with_pull_back(dense, w)(*args)[1:]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [2, 4])
@pytest.mark.parametrize("blocks, block_q, block_k", [
    (1, 128, 128), (2, 128, 128), (4, 128, 128), (2, 128, 64), (2, 64, 128),
    (4, 64, 128)], ids=["1_block", "2_blocks", "4_blocks", "wide_q",
                        "wide_k", "4_blocks_wide_k"])
@pytest.mark.parametrize("path", ["one_kernel", "two_kernels"])
def test_both_backward_paths_give_the_dense_gradients(
        path, blocks, block_q, block_k, heads, dtype):
    """All five gradients, the single backward kernel and the two, over
    T of 1, 2 and 4 blocks and block_q != block_k (a dq block then
    completes off the diagonal's corner, and a pass may complete two or
    none), 2 and 4 heads (the rotary key's gradient sums over the
    pairs, the outer axis of the single kernel)."""
    args, w, got = _path_grads(path, blocks, block_q, block_k, heads, dtype)
    want = _dense_grads(blocks, block_q, block_k, heads)
    # float32: today's limit; bfloat16 operands: p and ds are cast to
    # 8 bits of mantissa before their dots
    limit = 5e-5 if dtype == jnp.float32 else 4e-2
    for name, g, r in zip(("q_nope", "q_rope", "k_nope", "k_rope", "v"),
                          got, want):
        assert g.shape == r.shape and g.dtype == dtype, name
        np.testing.assert_allclose(g.astype(jnp.float32), r,
                                   atol=limit * float(jnp.abs(r).max()),
                                   err_msg=name)


def test_the_two_backward_paths_agree_to_the_bit():
    """Same arithmetic in the same order: the single kernel sums dq
    over the key blocks and dk over the query blocks as the two do (the
    `wide_k` geometry at four heads, whose calls the test above makes)."""
    *_, one = _path_grads("one_kernel", 2, 64, 128, 4, jnp.float32)
    *_, two = _path_grads("two_kernels", 2, 64, 128, 4, jnp.float32)
    for a, b in zip(one, two):
        np.testing.assert_array_equal(a, b)


def test_the_shape_alone_chooses_the_backward_path():
    """The accumulators of the single kernel are 2 KiB a position: the
    cell's 8192 positions fit the budget, 32768 and the model's 131072
    do not, whatever the operands' dtype; a traced backward says which
    it took."""
    assert flash_mla.fused_backward_fits(8192)
    assert flash_mla.fused_backward_fits(
        flash_mla.FUSED_ACCUMULATOR_BUDGET // 2048)
    assert not flash_mla.fused_backward_fits(
        flash_mla.FUSED_ACCUMULATOR_BUDGET // 2048 + 1024)
    assert not flash_mla.fused_backward_fits(32768)
    assert not flash_mla.fused_backward_fits(131072)

    def kernels(t):
        args = [jax.ShapeDtypeStruct((1, t, w), jnp.bfloat16)
                for w in (2 * NOPE, 2 * ROPE, 2 * NOPE, ROPE, 2 * NOPE)]
        before = runtime_stats.snapshot()
        text = jax.jit(jax.grad(
            lambda *a: jnp.sum(flash_mla.flash_mla(*a).astype(jnp.float32)),
            argnums=range(5))).lower(*args).as_text(debug_info=True)
        took = runtime_stats.delta(before)
        return (sorted(n for n in ("flash_mla_fwd", "flash_mla_dkv",
                                   "flash_mla_dq") if f"pallas_{n}" in text),
                took["flash_mla_backward_fused"],
                took["flash_mla_backward_split"])

    assert kernels(8192) == (["flash_mla_dkv", "flash_mla_fwd"], 1, 0)
    assert kernels(32768) == (
        ["flash_mla_dkv", "flash_mla_dq", "flash_mla_fwd"], 0, 1)


@pytest.mark.parametrize("path", ["one_kernel", "two_kernels"])
def test_the_rotary_key_is_one_head_at_the_kernel_boundary(monkeypatch, path):
    """The kernels take k_rope (N, T, 64) and v (N, T, H*128) as they
    lie: no operand of any of them is H x 192 wide, and the single
    backward kernel's dq accumulators never leave VMEM (no (T, H*192)
    or per-key-block partial of dq in the program)."""
    _backward_path(monkeypatch, path)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype)
            for a in operands(1, 256, 4)[:5]]
    text = jax.jit(jax.grad(
        lambda *a: jnp.sum(flash_mla.flash_mla(*a, block_q=128, block_k=128)),
        argnums=range(5))).lower(*args).as_text()
    assert "x768x" not in text and "768xf32" not in text      # 4 x 192
    assert "tensor<1x256x64xf32>" in text
    assert "tensor<2x256x" not in text          # nk = 2 partials of dq


@pytest.mark.parametrize("what, shapes", [
    ("odd", [(1, 128, 3 * NOPE), (1, 128, 3 * ROPE), (1, 128, 3 * NOPE),
             (1, 128, ROPE), (1, 128, 3 * NOPE)]),
    ("one rotary key head", [(1, 128, 2 * NOPE), (1, 128, 2 * ROPE),
                             (1, 128, 2 * NOPE), (1, 128, 2 * ROPE),
                             (1, 128, 2 * NOPE)]),
    ("whole number", [(1, 192, 2 * NOPE), (1, 192, 2 * ROPE),
                      (1, 192, 2 * NOPE), (1, 192, ROPE),
                      (1, 192, 2 * NOPE)])])
def test_a_geometry_the_kernels_do_not_block_is_refused(what, shapes):
    args = [jnp.zeros(s, jnp.float32) for s in shapes]
    with pytest.raises((ValueError, NotImplementedError), match=what):
        flash_mla.flash_mla(*args, block_q=128, block_k=128)


def test_kernel_costs_are_registered_under_the_kernels_names():
    """`flash_mla_dkv` names the two-kernel path's dk / dv kernel (three
    gradients out) and the single backward kernel (five): the second
    carries dq's dense-equivalent work too, so a step's total is the
    same on both paths."""
    shapes = [((1, 8192, 32 * NOPE), 2), ((1, 8192, 32 * ROPE), 2),
              ((1, 8192, 32 * NOPE), 2), ((1, 8192, ROPE), 2),
              ((1, 8192, 32 * NOPE), 2)]
    scores = 32 * 8192 * 8192
    wide, rotary, key = ((1, 8192, 4096), 2), ((1, 8192, 2048), 2), \
        ((1, 8192, 64), 2)
    for name, results, lanes in (
            ("flash_mla_fwd", [wide], 192 + 128),
            ("flash_mla_dkv", [wide, key, wide], 192 + 128 + 128),
            ("flash_mla_dq", [wide, rotary], 192),
            ("flash_mla_dkv", [wide, rotary, wide, key, wide],
             192 + 128 + 128 + 192)):
        flops, nbytes = KERNEL_COSTS[name](shapes, results)
        # dense-equivalent: 2 x lanes a score, plus the soft-max's few
        assert 2 * lanes * scores <= flops <= (2 * lanes + 8) * scores
        assert nbytes == 2 * 8192 * (3 * 4096 + 2048 + 64) + sum(
            2 * dims[1] * dims[2] for dims, _ in results)
    two = (KERNEL_COSTS["flash_mla_dkv"](shapes, [wide, key, wide])[0]
           + KERNEL_COSTS["flash_mla_dq"](shapes, [wide, rotary])[0])
    one, _ = KERNEL_COSTS["flash_mla_dkv"](
        shapes, [wide, rotary, wide, key, wide])
    assert one == two


@pytest.mark.parametrize("dims, kernel_calls", [
    ((NOPE, ROPE, NOPE), 1), ((NOPE, ROPE, 64), 0)],
    ids=["the_kernels_shape", "a_shape_they_do_not_take"])
def test_the_op_gives_dense_attention_on_both_paths(dims, kernel_calls,
                                                    monkeypatch):
    """The op chooses by the shape alone (`flash_mla_takes`): heads of
    128 + 64 score lanes and 128 value lanes go to the kernels, a value
    head of 64 to `plain_latent_attention`; both are dense attention."""
    *args, _ = operands(1, 128, 2, seed=3)
    args[4] = args[4][..., :2 * dims[2]]
    calls = []
    kernel = flash_mla.flash_mla
    monkeypatch.setattr(flash_mla, "flash_mla",
                        lambda *a, **kw: calls.append(1) or kernel(*a, **kw))
    impl = get_op_impl("latent_attention")
    out = impl(OpContext(jax.random.PRNGKey(0)),
               dict(zip(("QNope", "QRope", "KNope", "KRope", "V"),
                        ([a] for a in args))), {"n_head": 2})["Out"][0]
    assert len(calls) == kernel_calls
    np.testing.assert_allclose(out, dense(*args), atol=2e-5)


def test_the_plain_path_takes_any_head_sizes():
    """Dn 16, Dr 8, Dv 24: what the small-size parity tests run."""
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    n, t, h = 2, 12, 3
    shapes = [(n, t, h * 16), (n, t, h * 8), (n, t, h * 16), (n, t, 8),
              (n, t, h * 24)]
    qn, qr, kn, kr, v = (jax.random.normal(k, s) for k, s in zip(ks, shapes))
    impl = get_op_impl("latent_attention")
    out = impl(OpContext(jax.random.PRNGKey(0)),
               {"QNope": [qn], "QRope": [qr], "KNope": [kn], "KRope": [kr],
                "V": [v]}, {"n_head": h})["Out"][0]
    assert out.shape == (n, t, h * 24)
    q = jnp.concatenate([qn.reshape(n, t, h, 16), qr.reshape(n, t, h, 8)], -1)
    k = jnp.concatenate([kn.reshape(n, t, h, 16),
                         jnp.repeat(kr[:, :, None], h, axis=2)], -1)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(24.0)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    want = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1),
                      v.reshape(n, t, h, 24)).reshape(n, t, h * 24)
    np.testing.assert_allclose(out, want, atol=2e-6)


def test_rope_over_pairs_is_rope_over_halves_under_the_column_permutation():
    """Rotating the pairs (2i, 2i+1) in place is rotating the halves
    (i, i + D/2) of the head whose columns were gathered evens first:
    y_pairs[:, perm] == rope_halves(x[:, perm])."""
    impl = get_op_impl("rope")
    ctx = OpContext(jax.random.PRNGKey(0))
    n, t, h, d = 2, 9, 3, 8
    x = jax.random.normal(jax.random.PRNGKey(2), (n, t, h * d))
    perm = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    cols = (np.arange(h)[:, None] * d + perm[None, :]).reshape(-1)
    attrs = {"n_head": h, "theta": 32e6}
    pairs = impl(ctx, {"X": [x]}, dict(attrs, interleave=True))["Out"][0]
    halves = impl(ctx, {"X": [x[..., cols]]}, attrs)["Out"][0]
    np.testing.assert_allclose(pairs[..., cols], halves, atol=1e-6)
    assert float(jnp.abs(pairs - impl(ctx, {"X": [x]}, attrs)["Out"][0]
                         ).max()) > 0.1
    # position 0 is not rotated; a rotation keeps each pair's norm
    np.testing.assert_allclose(pairs[:, 0], x[:, 0], atol=1e-6)
    np.testing.assert_allclose(
        jnp.sum(pairs.reshape(n, t, h, d // 2, 2) ** 2, -1),
        jnp.sum(x.reshape(n, t, h, d // 2, 2) ** 2, -1), rtol=1e-5)
