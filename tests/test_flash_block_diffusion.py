"""`ops/pallas/flash_attention.py`'s band kernels under their THIRD
geometry, the block-diffusion training mask (`_DiffusionBand`;
interpret mode on the CPU, the same kernels Mosaic compiles in
tests/test_chip_compile_kernels.py), and the XLA lowering of
`ops/attention.py`, against a soft-max under the mask WRITTEN OUT from
(half, position): forward and the gradients of q, k, v; the one-kernel
and the two-kernel backward to the bit; the tiles the grids visit; what
the geometry does not take raises.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops.pallas import flash_attention as fa

D, H = 8, 4


def _qkvw(t, hkv, seed=0):
    rng = np.random.default_rng(seed)

    def draw(heads):
        return jnp.asarray(rng.normal(size=(2, t, heads * D)), jnp.float32)

    return draw(H), draw(hkv), draw(hkv), draw(H)


def _mask(length, block_length):
    """Row r = (half, position) reads row s, with loops and no
    arithmetic shared with the code under test."""
    t = 2 * length
    seen = np.zeros((t, t), bool)
    for r in range(t):
        for s in range(t):
            r_noised, s_noised = r >= length, s >= length
            r_blk = (r - length * r_noised) // block_length
            s_blk = (s - length * s_noised) // block_length
            if not r_noised and not s_noised:
                seen[r, s] = s_blk <= r_blk
            elif r_noised and not s_noised:
                seen[r, s] = s_blk < r_blk
            elif r_noised and s_noised:
                seen[r, s] = s_blk == r_blk
    return seen


def _dense(q, k, v, hkv, block_length):
    n, t, _ = q.shape
    q4 = q.reshape(n, t, H, D)
    k4 = jnp.repeat(k.reshape(n, t, hkv, D), H // hkv, axis=2)
    v4 = jnp.repeat(v.reshape(n, t, hkv, D), H // hkv, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q4, k4) * D ** -0.5
    p = jax.nn.softmax(jnp.where(_mask(t // 2, block_length), s, -jnp.inf),
                       axis=-1)
    return jnp.einsum("nhqk,nkhd->nqhd", p, v4).reshape(n, t, H * D)


def _flash(q, k, v, hkv, block_length, **kw):
    return fa.pallas_flash_attention(
        q, k, v, None, None, False, layout="nthd", n_head=H,
        n_kv_head=None if hkv == H else hkv, block_diffusion=block_length,
        **kw)


def _grads(fn, q, k, v, w):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(w * fn(q, k, v)), (0, 1, 2))(q, k, v)


# (L, B, tile): one tile a half and four; B = 4 and B = the tile
GEOMETRIES = {"one_tile-B4": (16, 4, 16), "four_tiles-B4": (64, 4, 16),
              "four_tiles-B_is_the_tile": (64, 16, 16),
              "one_tile-B_is_the_tile": (16, 16, 16),
              "two_tiles-B8": (64, 8, 32)}


@pytest.mark.parametrize("hkv", [H, H // 4], ids=["mha", "gqa4"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_the_kernels_match_the_mask_written_out(geometry, hkv, monkeypatch):
    """Forward and dq, dk, dv; then the same call with the single
    kernel's budget at zero: the two kernels give the same bits."""
    length, block_length, tile = GEOMETRIES[geometry]
    q, k, v, w = _qkvw(2 * length, hkv, seed=3)
    kw = dict(block_q=tile, block_k=tile)
    before = runtime_stats.snapshot()
    out, got = _grads(lambda *a: _flash(*a, hkv, block_length, **kw),
                      q, k, v, w)
    took = runtime_stats.delta(before)
    want_out, want = _grads(lambda *a: _dense(*a, hkv, block_length),
                            q, k, v, w)
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == r.shape           # dk, dv: key/value heads wide
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5,
                                   err_msg="d" + name)
    assert took["flash_attention_backward_fused"] == 1
    # forward and backward, a call each; what is visited is allowed
    assert took["flash_block_diffusion_calls"] == 2
    assert took["flash_block_diffusion_blocks_visited"] \
        == took["flash_block_diffusion_blocks_allowed"] > 0
    assert took["flash_window_blocks_visited"] == 0
    monkeypatch.setattr(fa, "FUSED_ACCUMULATOR_BUDGET", 0)
    before = runtime_stats.snapshot()
    _, split = _grads(lambda *a: _flash(*a, hkv, block_length, **kw),
                      q, k, v, w)
    assert runtime_stats.delta(before)["flash_attention_backward_split"] == 1
    for g, s in zip(got, split):
        np.testing.assert_array_equal(g, s)


@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("length, block_length, tile", [
    (16, 4, 16), (24, 4, 8), (20, 4, 4), (24, 6, None)],
    ids=["own_tile", "half_tile", "quarter_tile", "no_tile"])
def test_the_flash_attention_op_takes_the_mask_on_both_paths(
        use_pallas, length, block_length, tile, monkeypatch):
    """The op with the kernels, with the XLA lowering under the explicit
    mask (`use_pallas` off: the CPU's), and by the shape (tiles of 16
    here): a half that 16 does not cut into whole tiles runs at 8 or at
    4; one that none of the three cuts into whole tiles of whole blocks
    (24 rows in blocks of 6) the explicit mask again, under
    `use_pallas` too."""
    from op_test import run_op

    monkeypatch.setattr(fa, "DEFAULT_DIFFUSION_BLOCK", 16)
    monkeypatch.setattr(fa, "DEFAULT_DIFFUSION_BWD_BLOCK", 16)
    q, k, v, _ = _qkvw(2 * length, H // 4, seed=9)
    attrs = {"causal": False, "use_pallas": use_pallas, "layout": "nthd",
             "n_head": H, "n_kv_head": H // 4,
             "block_diffusion": block_length}
    before = runtime_stats.snapshot()
    got = run_op("flash_attention", {"Q": q, "K": k, "V": v}, attrs)
    calls = runtime_stats.delta(before)["flash_block_diffusion_calls"]
    assert (calls > 0) == (use_pallas and tile is not None)
    np.testing.assert_allclose(
        got, _dense(q, k, v, H // 4, block_length), rtol=1e-5, atol=1e-5)
    assert fa.block_diffusion_takes(2 * length, block_length) \
        == (tile is not None)
    if tile:
        assert fa._diffusion_blocks(2 * length, block_length) == (tile, tile)


def test_the_tile_is_the_largest_of_three_that_cuts_a_half():
    """At the kernels' own sizes: the cell's half at 1024, a half of
    8192 + 512 rows at 512, of 8192 + 256 at 256 (the three sizes
    timed on the chip); a half under one tile is one tile; a block that
    cuts no tile, an odd row count and a half no tile cuts are not
    taken."""
    assert fa._diffusion_blocks(2 * 8192, 4) == (1024, 1024)
    assert fa._diffusion_blocks(2 * 8704, 4) == (512, 512)
    assert fa._diffusion_blocks(2 * 8448, 4) == (256, 256)
    assert fa._diffusion_blocks(2 * 8192, 4, 512) == (512, 512)
    for rows, block_length, takes in [
            (2 * 8704, 4, True), (2 * 8448, 4, True), (2 * 8320, 4, False),
            (24, 4, True), (24, 5, False), (25, 5, False),
            (2 * 8192, 3, False)]:
        assert fa.block_diffusion_takes(rows, block_length) == takes


def test_the_xla_lowering_has_the_gradients_of_the_mask_written_out():
    from paddle_tpu.ops.attention import _xla_attention_nthd

    q, k, v, w = _qkvw(32, H // 4, seed=2)
    out, got = _grads(lambda *a: _xla_attention_nthd(
        *a, None, D ** -0.5, False, H, H // 4, None, 4), q, k, v, w)
    want_out, want = _grads(lambda *a: _dense(*a, H // 4, 4), q, k, v, w)
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5)


def test_the_grids_visit_the_tiles_that_hold_an_allowed_pair_and_no_other():
    """At the cell's shape, 2 x 8192 rows: in 512 x 512 tiles 288 of a
    head's 1024 tiles (causal over 2 L would visit 528), in the kernels'
    own 1024 x 1024 80 of 256; a query tile's two runs of key tiles and
    a key tile's two runs of query tiles."""
    own = fa._DiffusionBand(16384, fa.DEFAULT_DIFFUSION_BLOCK, 4)
    assert (own.block_q, own.n, own.blocks_allowed) == (1024, 8, 80)
    assert (own.k_steps, own.q_steps) == (9, 16)
    band = fa._DiffusionBand(16384, 512, 4)
    assert (band.n, band.nq, band.nk) == (16, 32, 32)
    assert (band.k_steps, band.q_steps) == (17, 32)
    assert band.blocks_allowed == 288
    assert band.pairs() == 67141632

    def keys(qb):
        return [kb for kb, run in (band.key_block(qb, s)
                                   for s in range(band.k_steps)) if run]

    def queries(kb):
        return [qb for qb, run in (band.query_block(kb, s)
                                   for s in range(band.q_steps)) if run]

    assert keys(0) == [0] and keys(3) == [0, 1, 2, 3]
    assert keys(16) == [0, 16]              # noised tile 0: clean 0, itself
    assert keys(19) == [0, 1, 2, 3, 19]
    assert queries(0) == list(range(32))
    assert queries(3) == list(range(3, 16)) + list(range(19, 32))
    assert queries(15) == [15, 31] and queries(16) == [16]
    pairs = {(qb, kb) for qb in range(32) for kb in keys(qb)}
    assert pairs == {(qb, kb) for kb in range(32) for qb in queries(kb)}
    assert len(pairs) == 288
    # against the mask itself, at a size where it can be written out
    small = fa._DiffusionBand(128, 16, 4)
    seen = _mask(64, 4).reshape(8, 16, 8, 16).any(axis=(1, 3))
    assert {(qb, kb) for qb in range(8)
            for kb, run in (small.key_block(qb, s)
                            for s in range(small.k_steps)) if run} \
        == set(zip(*np.nonzero(seen)))
    # a skipped step fetches nothing new: the index maps stay in place
    assert [band.k_time(19, s) for s in range(band.k_steps)][4:] == [19] * 13
    assert [band.q_time(15, s) for s in (0, 1, 2, 31)] == [15, 31, 31, 31]
    # a tile that is ONE block: the clean tile at the query's own
    # position holds no strictly earlier block, and is not visited
    whole = fa._DiffusionBand(128, 16, 16)
    assert whole.blocks_allowed == 4 * 5 // 2 + 4 * 3 // 2 + 4
    assert [kb for kb, run in (whole.key_block(4, s)
                               for s in range(whole.k_steps)) if run] == [4]
    assert whole.first_k(4) == 4 and whole.first_k(5) == 0
    assert whole.pairs() == 64 * 16 + 256 * (4 * 3 // 2 + 4 * 5 // 2)


def test_the_kernels_run_under_names_and_costs_of_their_own():
    """`flash_block_diffusion_fwd` / `_dkv` (and `_dq` past the budget),
    each declaring the cost of the pairs the mask allows."""
    q, k, v, _ = _qkvw(64, H // 4)
    shape = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)]

    def names():
        text = jax.jit(jax.grad(
            lambda *a: jnp.sum(_flash(*a, H // 4, 4, block_q=16,
                                      block_k=16)),
            argnums=(0, 1, 2))).lower(*shape).as_text(debug_info=True)
        return sorted(n for n in ("flash_fwd", "flash_dkv", "flash_window_fwd",
                                  "flash_block_diffusion_fwd",
                                  "flash_block_diffusion_dkv",
                                  "flash_block_diffusion_dq")
                      if f"pallas_{n}" in text)

    assert names() == ["flash_block_diffusion_dkv",
                       "flash_block_diffusion_fwd"]
    band = fa._DiffusionBand(64, 16, 4)
    cost = band.cost_estimate("fwd", 2 * H, D, 4, 4)["cost_estimate"]
    assert cost.flops == 2 * H * band.pairs() * (4 * D + 8)
    assert cost.bytes_accessed == 2 * H * 64 * D * 4 * (2 + 2 / 4)
    from paddle_tpu.ops.pallas import DECLARED_AT_CALL, KERNEL_COSTS

    for kernel in ("fwd", "dkv", "dq"):
        assert KERNEL_COSTS["flash_block_diffusion_" + kernel] \
            == DECLARED_AT_CALL


@pytest.mark.parametrize("what, call", [
    ("a bias", dict(bias=jnp.zeros((2, 1, 1, 64)))),
    ("position offsets", dict(q_offset=0, k_offset=0)),
    ("a returned logsumexp", dict(return_lse=True)),
    ("a causal mask beside it", dict(causal=True)),
    ("a window", dict(window=8)),
    ("a tile that is no whole number of blocks", dict(block_q=16, block_k=16,
                                                      block_diffusion=5)),
    ("a half that is no whole number of tiles", dict(block_q=24,
                                                     block_k=24)),
])
def test_what_the_geometry_does_not_take_raises(what, call):
    q, k, v, _ = _qkvw(64, H // 4)
    call = dict(dict(block_diffusion=4), **call)
    args = (call.pop("bias", None), None, call.pop("causal", False))
    with pytest.raises(NotImplementedError, match="whole\\s+blocks"):
        fa.pallas_flash_attention(q, k, v, *args, layout="nthd", n_head=H,
                                  n_kv_head=H // 4, **call)


def test_cross_lengths_oblong_tiles_and_the_other_layout_raise():
    q, k, v, _ = _qkvw(64, H)
    with pytest.raises(NotImplementedError, match="whole\\s+blocks"):
        fa.pallas_flash_attention(q, k[:, :32], v[:, :32], None, None, False,
                                  layout="nthd", n_head=H, block_diffusion=4)
    with pytest.raises(NotImplementedError, match="square"):
        fa.pallas_flash_attention(q, k, v, None, None, False, layout="nthd",
                                  n_head=H, block_diffusion=4, block_q=16,
                                  block_k=32)
    x = q.reshape(2, 64, H, D).transpose(0, 2, 1, 3)
    with pytest.raises(NotImplementedError, match="head-major"):
        fa.pallas_flash_attention(x, x, x, None, None, False,
                                  block_diffusion=4)
    with pytest.raises(ValueError, match="no block length"):
        fa.pallas_flash_attention(q, k, v, None, None, False, layout="nthd",
                                  n_head=H, block_diffusion=0)


def test_the_op_refuses_what_the_mask_does_not_go_with():
    from op_test import run_op

    q, k, v, _ = _qkvw(32, H // 4, seed=9)
    base = {"layout": "nthd", "n_head": H, "n_kv_head": H // 4,
            "block_diffusion": 4}
    for attrs, ins in [
            (dict(causal=True), {}),
            (dict(causal=True, window=8), {}),
            (dict(sequence_parallel="ring"), {}),
            (dict(), {"Bias": np.zeros((2, 1, 1, 32), np.float32)})]:
        with pytest.raises(NotImplementedError, match="block-diffusion mask"):
            run_op("flash_attention", {"Q": q, "K": k, "V": v, **ins},
                   {**base, **attrs})
    with pytest.raises(ValueError, match="whole blocks"):
        run_op("flash_attention", {"Q": q, "K": k, "V": v},
               dict(base, block_diffusion=3))
