"""`ops/pallas/flash_attention.py`'s band kernels (a `window`, grouped
key/value heads at any d_head the forward takes; interpret mode on the
CPU, the same kernels Mosaic compiles in tests/test_chip_compile_flash.py)
against the XLA composition under an EXPLICIT mask: forward and all
three gradients; the one-kernel and the two-kernel backward to the bit;
k, v, dk, dv never repeated; what the band kernels do not take raises.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops.pallas import flash_attention as fa

T, D, H = 64, 8, 8
BLOCK = 16


def _qkvw(hkv, seed=0, t=T, dtype=jnp.float32):
    rng = np.random.default_rng(seed)

    def draw(heads):
        return jnp.asarray(rng.normal(size=(2, t, heads * D)), dtype)

    return draw(H), draw(hkv), draw(hkv), draw(H)


def _dense(q, k, v, hkv, window):
    """Soft-max attention under the mask written out: key j is read by
    query i where j <= i and (under a window) i - window < j; key/value
    heads repeated."""
    n, t, _ = q.shape
    q4 = q.reshape(n, t, H, D)
    k4 = jnp.repeat(k.reshape(n, t, hkv, D), H // hkv, axis=2)
    v4 = jnp.repeat(v.reshape(n, t, hkv, D), H // hkv, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q4, k4) * D ** -0.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("nhqk,nkhd->nqhd", p, v4).reshape(n, t, H * D)


def _flash(q, k, v, hkv, window, **kw):
    return fa.pallas_flash_attention(
        q, k, v, None, None, True, layout="nthd", n_head=H,
        n_kv_head=None if hkv == H else hkv, window=window, **kw)


def _grads(fn, q, k, v, w):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(w * fn(q, k, v)), (0, 1, 2))(q, k, v)


# under a block, one block, one and a half, and every key (no window)
WINDOWS = [None, 5, BLOCK, 24, T, T + 9]


@pytest.mark.parametrize("blocks", [(16, 16), (16, 32), (32, 16)],
                         ids=["square", "wide_k", "wide_q"])
@pytest.mark.parametrize("hkv", [H, H // 8], ids=["mha", "gqa8"])
@pytest.mark.parametrize("window", WINDOWS)
def test_band_kernels_match_the_masked_composition(window, hkv, blocks,
                                                   monkeypatch):
    """Forward and dq, dk, dv; then the same call with the single
    kernel's budget at zero: the two kernels give the same bits."""
    q, k, v, w = _qkvw(hkv, seed=3)
    kw = dict(block_q=blocks[0], block_k=blocks[1])
    before = runtime_stats.snapshot()
    out, got = _grads(lambda *a: _flash(*a, hkv, window, **kw), q, k, v, w)
    took = runtime_stats.delta(before)
    want_out, want = _grads(lambda *a: _dense(*a, hkv, window), q, k, v, w)
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == r.shape           # dk, dv: key/value heads wide
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5,
                                   err_msg="d" + name)
    assert took["flash_attention_backward_fused"] == 1
    windowed = window is not None and window < T
    assert (took["flash_window_blocks_visited"] > 0) == windowed
    monkeypatch.setattr(fa, "FUSED_ACCUMULATOR_BUDGET", 0)
    before = runtime_stats.snapshot()
    _, split = _grads(lambda *a: _flash(*a, hkv, window, **kw), q, k, v, w)
    assert runtime_stats.delta(before)["flash_attention_backward_split"] == 1
    for g, s in zip(got, split):
        np.testing.assert_array_equal(g, s)


def test_a_window_that_holds_every_key_takes_the_kernels_without_one():
    """W >= T is no window: the call, its kernels' names and its bits
    are full attention's; one key fewer is another call."""
    q, k, v, w = _qkvw(H // 8, seed=5)
    full = _grads(lambda *a: _flash(*a, H // 8, None), q, k, v, w)
    same = _grads(lambda *a: _flash(*a, H // 8, T), q, k, v, w)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(same)):
        np.testing.assert_array_equal(a, b)

    def names(window):
        shape = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)]
        text = jax.jit(jax.grad(
            lambda *a: jnp.sum(_flash(*a, H // 8, window)),
            argnums=(0, 1, 2))).lower(*shape).as_text(debug_info=True)
        return sorted(n for n in ("flash_fwd", "flash_dkv", "flash_dq",
                                  "flash_window_fwd", "flash_window_dkv",
                                  "flash_window_dq")
                      if f"pallas_{n}" in text)

    assert names(T) == names(None) == ["flash_dkv", "flash_fwd"]
    assert names(T - 1) == ["flash_window_dkv", "flash_window_fwd"]


def test_grouped_keys_and_values_are_never_repeated():
    """In the traced call only the kernels read k and v and only the
    backward kernel writes dk and dv, each key/value heads wide: no
    `repeat`, broadcast or gather makes a query-heads-wide copy."""
    q, k, v, w = _qkvw(H // 8)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(w * _flash(*a, H // 8, 24, block_q=16,
                                      block_k=16)),
        argnums=(0, 1, 2)))(q, k, v)
    kernels = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == 2            # the forward, the single backward
    for eqn in jaxpr.jaxpr.eqns:
        narrow = [x for x in list(eqn.invars) + list(eqn.outvars)
                  if getattr(x.aval, "shape", None) == k.shape]
        if narrow:                      # only the kernels touch k, v, dk, dv
            assert eqn in kernels, eqn.primitive.name
    assert sum(x.aval.shape == k.shape for x in kernels[0].invars) == 2
    assert sum(x.aval.shape == k.shape for x in kernels[1].outvars) == 2
    dq, dk, dv = jaxpr.out_avals
    assert (dq.shape, dk.shape, dv.shape) == (q.shape, k.shape, v.shape)


def test_the_band_forward_skips_what_lies_outside_the_band():
    """The grid's last axis is as long as the widest band, not as the
    sequence; the counters say how many blocks a head's grid visits and
    how many of them hold an allowed pair."""
    band = fa._Band(16384, 512, 512, 1024)
    assert (band.nq, band.nk, band.k_steps, band.q_steps) == (32, 32, 3, 3)
    assert band.blocks_allowed == 1 + 2 + 30 * 3
    assert [band.first_k(qb) for qb in (0, 1, 2, 3, 31)] == [0, 0, 0, 1, 29]
    assert [band.last_q(kb) for kb in (0, 1, 30, 31)] == [2, 3, 31, 31]
    whole = fa._Band(16384, 256, 1024, None)
    assert (whole.k_steps, whole.q_steps) == (16, 64)
    assert whole.blocks_allowed == 4 * sum(range(1, 17))
    # a window of one block at the parent's blocks: two key blocks a
    # query block, half of what they hold masked
    wide = fa._Band(16384, 256, 1024, 1024)
    assert wide.k_steps == 2
    assert wide.pairs() == 1024 * 16384 - 1024 * 1023 // 2 == 16253440
    assert whole.pairs() == 16384 * 16385 // 2
    q, k, v, _ = _qkvw(H // 8, t=T)
    before = runtime_stats.snapshot()
    jax.eval_shape(lambda *a: _flash(*a, H // 8, 24, block_q=16,
                                     block_k=16), q, k, v)
    took = runtime_stats.delta(before)
    small = fa._Band(T, 16, 16, 24)
    assert took["flash_window_blocks_visited"] == small.nq * small.k_steps
    assert took["flash_window_blocks_allowed"] == small.blocks_allowed


def test_the_shape_alone_chooses_the_band_backward():
    assert fa.band_backward_fits(16384, 128)         # 24 MiB of 48
    assert fa.band_backward_fits(16384, 256)         # the edge (PR 54)
    assert fa.band_backward_fits(32768, 128)
    assert not fa.band_backward_fits(32768, 256)
    edge = fa.FUSED_ACCUMULATOR_BUDGET // (12 * 128)
    assert fa.band_backward_fits(edge, 128)
    assert not fa.band_backward_fits(edge + 1, 128)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_single_kernel_at_head_dim_256_gives_the_two_kernels_bits(
        dtype, monkeypatch):
    """`qwen3next-16k`'s geometry at a short length: 8 query heads of
    256 over ONE key/value head, several blocks a side, so dk and dv sum
    over the whole group inside the single kernel and over the group's
    axis of the dk / dv kernel; the same bits either way."""
    t, h, d = 128, 8, 256
    rng = np.random.default_rng(11)
    q, k, v, w = (jnp.asarray(rng.normal(size=(1, t, heads * d)), dtype)
                  for heads in (h, 1, 1, h))

    def grads():
        before = runtime_stats.snapshot()
        out = jax.grad(lambda q, k, v: jnp.sum((w * fa.pallas_flash_attention(
            q, k, v, None, None, True, layout="nthd", n_head=h, n_kv_head=1,
            block_q=32, block_k=64)).astype(jnp.float32)), (0, 1, 2))(q, k, v)
        took = runtime_stats.delta(before)
        return out, (took["flash_attention_backward_fused"],
                     took["flash_attention_backward_split"])

    fused, took = grads()
    assert took == (1, 0)
    monkeypatch.setattr(fa, "FUSED_ACCUMULATOR_BUDGET", 0)
    split, took = grads()
    assert took == (0, 1)
    for name, g, s in zip("qkv", fused, split):
        assert g.dtype == dtype and bool(jnp.any(g != 0)), name
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(s, np.float32),
                                      err_msg="d" + name)


@pytest.mark.parametrize("what, call", [
    ("a bias", dict(bias=jnp.zeros((2, 1, 1, T)))),
    ("position offsets", dict(q_offset=0, k_offset=0)),
    ("a returned logsumexp", dict(return_lse=True)),
    ("not causal", dict(causal=False)),
    ("a ragged block", dict(block_q=48)),
])
@pytest.mark.parametrize("hkv, window", [(H, 24), (H // 8, None)],
                         ids=["window", "grouped"])
def test_what_the_band_kernels_do_not_take_raises(what, call, hkv, window):
    q, k, v, _ = _qkvw(hkv)
    call = dict(call)
    args = (call.pop("bias", None), None, call.pop("causal", True))
    with pytest.raises(NotImplementedError, match="whole blocks"):
        fa.pallas_flash_attention(
            q, k, v, *args, layout="nthd", n_head=H,
            n_kv_head=None if hkv == H else hkv, window=window, **call)


def test_cross_lengths_the_other_layout_and_an_empty_window_raise():
    q, k, v, _ = _qkvw(H)
    with pytest.raises(NotImplementedError, match="whole blocks"):
        fa.pallas_flash_attention(q, k[:, :32], v[:, :32], None, None, True,
                                  layout="nthd", n_head=H, window=8)
    x = q.reshape(2, T, H, D).transpose(0, 2, 1, 3)
    with pytest.raises(NotImplementedError, match="head-major"):
        fa.pallas_flash_attention(x, x, x, None, None, True, window=8)
    with pytest.raises(ValueError, match="holds no key"):
        fa.pallas_flash_attention(q, k, v, None, None, True, layout="nthd",
                                  n_head=H, window=0)


@pytest.mark.parametrize("use_pallas", [True, False])
def test_the_flash_attention_op_takes_a_window_on_both_paths(use_pallas):
    from op_test import run_op

    q, k, v, _ = _qkvw(H // 8, seed=9)
    got = run_op("flash_attention", {"Q": q, "K": k, "V": v},
                 {"causal": True, "use_pallas": use_pallas,
                  "layout": "nthd", "n_head": H, "n_kv_head": H // 8,
                  "window": 24})
    np.testing.assert_allclose(got, _dense(q, k, v, H // 8, 24), rtol=1e-5,
                               atol=1e-5)
    for attrs, ins in [
            (dict(causal=False), {}),
            (dict(sequence_parallel="ring"), {}),
            (dict(), {"Bias": np.zeros((2, 1, 1, T), np.float32)})]:
        with pytest.raises(NotImplementedError, match="a window is causal"):
            run_op("flash_attention", {"Q": q, "K": k, "V": v, **ins},
                   {"causal": True, "layout": "nthd", "n_head": H,
                    "n_kv_head": H // 8, "window": 24, **attrs})
