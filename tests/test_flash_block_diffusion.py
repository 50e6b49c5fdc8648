"""`ops/pallas/flash_block_diffusion.py`: flash attention under the
block-diffusion training mask on a grid of VISITS (interpret mode on the
CPU, the same kernels Mosaic compiles in
tests/test_chip_compile_kernels.py), and the XLA lowering of
`ops/attention.py`, against a soft-max under the mask WRITTEN OUT from
(half, position): the visit table against that mask (every tile that
holds an allowed pair once in each order, the runs' brackets, the kinds,
the dq tile an output holds); forward and the gradients of q, k, v; the
one-kernel and the two-kernel backward to the bit; what the geometry
does not take raises.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import flash_block_diffusion as fbd

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


@functools.cache
def _dense_grads(length, block_length, hkv):
    """The weighted sum of the mask written out and its (dq, dk, dv) on
    `_qkvw(2 * length, hkv, seed=3)`: ONE compiled function, once a
    (length, block length, head geometry), whatever the tiles."""
    q, k, v, w = _qkvw(2 * length, hkv, seed=3)
    return jax.jit(lambda *a: _grads(
        lambda *b: _dense(*b, hkv, block_length), *a, w))(q, k, v)


# (L, B, tile, lanes): one tile a half and four; B = 4 and B = the tile.
# `lanes` is the quantum of an OWN_BLOCKS visit's squares (128 on the
# chip: no tile of 16 holds one, so the first five run no such visit);
# at 8 the small tiles do, and the last runs the real quantum
GEOMETRIES = {"one_tile-B4": (16, 4, 16, 128),
              "four_tiles-B4": (64, 4, 16, 128),
              "four_tiles-B_is_the_tile": (64, 16, 16, 128),
              "one_tile-B_is_the_tile": (16, 16, 16, 128),
              "two_tiles-B8": (64, 8, 32, 128),
              "own_blocks-one_tile-B4": (16, 4, 16, 8),
              "own_blocks-four_tiles-B4": (64, 4, 16, 8),
              "own_blocks-two_tiles-B8": (64, 8, 32, 8),
              "own_blocks-128_lanes": (512, 4, 256, 128)}


@pytest.mark.parametrize("hkv", [H, H // 4], ids=["mha", "gqa4"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_the_kernels_match_the_mask_written_out(geometry, hkv, monkeypatch):
    """Forward and dq, dk, dv; then the same call with the single
    kernel's budget at zero: the two kernels give the same bits."""
    length, block_length, tile, lanes = GEOMETRIES[geometry]
    monkeypatch.setattr(fbd, "LANES", lanes)
    band = fbd._DiffusionBand(2 * length, tile, block_length)
    kinds = {kind for *_, kind in band.tiles()}
    assert (fbd.OWN_BLOCKS in kinds) == geometry.startswith("own_blocks")
    q, k, v, w = _qkvw(2 * length, hkv, seed=3)
    kw = dict(block_q=tile, block_k=tile)
    before = runtime_stats.snapshot()
    # (the forward once: a backward rule runs whenever `pull` is called)
    out, pull = jax.vjp(lambda *a: _flash(*a, hkv, block_length, **kw),
                        q, k, v)
    got = pull(w)
    took = runtime_stats.delta(before)
    want_out, want = _dense_grads(length, block_length, hkv)
    np.testing.assert_allclose(jnp.sum(w * out), want_out, rtol=2e-5,
                               atol=2e-5)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == r.shape           # dk, dv: key/value heads wide
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5,
                                   err_msg="d" + name)
    assert took["flash_attention_backward_fused"] == 1
    # forward and backward, a call each; what is visited is allowed
    assert took["flash_block_diffusion_calls"] == 2
    assert took["flash_block_diffusion_blocks_visited"] \
        == took["flash_block_diffusion_blocks_allowed"] \
        == took["flash_block_diffusion_grid_steps"] > 0
    assert took["flash_window_blocks_visited"] == 0
    monkeypatch.setattr(fa, "FUSED_ACCUMULATOR_BUDGET", 0)
    before = runtime_stats.snapshot()
    split = pull(w)
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
    assert fbd.block_diffusion_takes(2 * length, block_length) \
        == (tile is not None)
    if tile:
        assert fbd._diffusion_blocks(2 * length, block_length) == (tile, tile)


def test_the_tile_is_the_largest_of_three_that_cuts_a_half():
    """At the kernels' own sizes: the cell's half at 1024, a half of
    8192 + 512 rows at 512, of 8192 + 256 at 256 (the three sizes
    timed on the chip); a half under one tile is one tile; a block that
    cuts no tile, an odd row count and a half no tile cuts are not
    taken."""
    assert fbd._diffusion_blocks(2 * 8192, 4) == (1024, 1024)
    assert fbd._diffusion_blocks(2 * 8704, 4) == (512, 512)
    assert fbd._diffusion_blocks(2 * 8448, 4) == (256, 256)
    assert fbd._diffusion_blocks(2 * 8192, 4, 512) == (512, 512)
    for rows, block_length, takes in [
            (2 * 8704, 4, True), (2 * 8448, 4, True), (2 * 8320, 4, False),
            (24, 4, True), (24, 5, False), (25, 5, False),
            (2 * 8192, 3, False)]:
        assert fbd.block_diffusion_takes(rows, block_length) == takes


def test_the_xla_lowering_has_the_gradients_of_the_mask_written_out():
    from paddle_tpu.ops.attention import _xla_attention_nthd

    q, k, v, w = _qkvw(32, H // 4, seed=2)
    out, got = _grads(lambda *a: _xla_attention_nthd(
        *a, None, D ** -0.5, False, H, H // 4, None, 4), q, k, v, w)
    want_out, want = _grads(lambda *a: _dense(*a, H // 4, 4), q, k, v, w)
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5)


ORDERS = {"query_major": dict(), "key_major": dict(key_major=True),
          "key_major-group4": dict(key_major=True, group=4)}


@pytest.mark.parametrize("order", sorted(ORDERS))
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES)[:-1])
def test_the_table_is_the_mask_written_out(geometry, order, monkeypatch):
    """Every visit's tile holds an allowed pair and every such tile is
    visited exactly once (a head), in the order's runs; `FULL` is a tile
    the mask leaves whole; FIRST / LAST bracket each run; the dq tile an
    output holds is complete, or being completed."""
    length, block_length, tile, lanes = GEOMETRIES[geometry]
    monkeypatch.setattr(fbd, "LANES", lanes)
    band = fbd._DiffusionBand(2 * length, tile, block_length)
    kw = ORDERS[order]
    table = band.visits(**kw)
    group, key_major = kw.get("group", 1), kw.get("key_major", False)
    assert table.dtype == np.int32 and table.shape[0] == 9
    q, k, head, kind, first, last, dq, dq_first, dq_last = table
    tiles = _mask(length, block_length).reshape(
        2 * length // tile, tile, 2 * length // tile, tile)
    holds = set(zip(*np.nonzero(tiles.any(axis=(1, 3)))))
    whole = set(zip(*np.nonzero(tiles.all(axis=(1, 3)))))
    for gi in range(group):
        mine = list(zip(q[head == gi], k[head == gi]))
        assert len(mine) == len(set(mine)) == band.blocks_allowed
        assert set(mine) == holds
    full = {(a, b) for a, b, c in zip(q, k, kind) if c == fbd.FULL}
    # (a tile of ONE block at the query tile's own position is whole
    # too, and masked all the same: no kind for a geometry no cell has)
    assert full == whole if block_length < tile else full < whole
    assert all(a % band.n == b % band.n for a, b in holds - full)
    assert set(kind) <= {fbd.FULL, fbd.DIAGONAL, fbd.OWN_BLOCKS}
    assert all(a == b >= band.n for a, b, c in zip(q, k, kind)
               if c == fbd.OWN_BLOCKS)
    from test_flash_band import assert_runs_and_dq_tiles

    assert_runs_and_dq_tiles(table, band.nq, group, key_major)
    if group == 1 and key_major:
        assert list(dq) == list(k)          # `dq_time`'s answer


@pytest.mark.parametrize("tile, visits, kinds", [
    (1024, 80, (56, 16, 8)), (512, 288, (240, 32, 16))])
def test_the_table_at_the_cells_shape(tile, visits, kinds):
    """2 x 8192 rows in blocks of 4: 80 of a head's 256 tiles at the
    kernels' own 1024 x 1024 (causal over 2 L would visit 136), 288 of
    1024 at 512 x 512; the rectangles they replace took 144 + 256 steps
    for the 80 + 80."""
    assert fbd._diffusion_blocks(16384, 4) == (1024, 1024)
    band = fbd._DiffusionBand(16384, tile, 4)
    assert (band.n, band.nq, band.nk) == (8192 // tile, 16384 // tile,
                                          16384 // tile)
    assert band.blocks_allowed == visits and band.pairs() == 67141632
    assert band.sub == 128
    for kw in ORDERS.values():
        table = band.visits(**kw)
        assert table.shape == (9, visits * kw.get("group", 1))
        assert tuple(np.bincount(table[fbd.V_KIND])) \
            == tuple(n * kw.get("group", 1) for n in kinds)
    before = runtime_stats.snapshot()
    band.record_blocks()
    took = runtime_stats.delta(before)
    assert took["flash_block_diffusion_calls"] == 1
    assert took["flash_block_diffusion_grid_steps"] \
        == took["flash_block_diffusion_blocks_visited"] \
        == took["flash_block_diffusion_blocks_allowed"] == visits
    assert took["flash_block_diffusion_pairs_allowed"] == band.pairs()
    entries = (kinds[0] + kinds[1]) * tile * tile + kinds[2] * tile * 128
    assert took["flash_block_diffusion_entries_computed"] == entries
    if tile == 1024:        # whole tiles read 80.04
        assert 100 * band.pairs() / entries == pytest.approx(87.71, abs=0.01)


@pytest.mark.parametrize("block_length, sub", [
    (4, 128), (16, 128), (128, 128), (256, 256), (1024, None)])
def test_an_own_blocks_visit_covers_the_tiles_allowed_pairs(block_length,
                                                            sub):
    """A noised tile of 1024 against itself allows blk(s) == blk(r)
    alone: the `sub` x `sub` squares on its diagonal hold every such
    pair, and each square's mask is the tile's mask there.  A tile of
    ONE block has no smaller square and stays `DIAGONAL`."""
    band = fbd._DiffusionBand(16384, 1024, block_length)
    own = [kind for qb, kb, kind in band.tiles() if qb == kb >= band.n]
    assert len(own) == band.n
    if sub is None:
        assert band.sub == 1024 and band.own == 0
        assert set(own) == {fbd.DIAGONAL}
        assert fbd.OWN_BLOCKS not in {kind for *_, kind in band.tiles()}
        return
    assert band.sub == sub and set(own) == {fbd.OWN_BLOCKS}
    blk = np.arange(1024) // block_length
    allowed = blk[:, None] == blk[None, :]
    covered = np.zeros_like(allowed)
    square = np.asarray(band._ahead(sub, 0) == 0)
    np.testing.assert_array_equal(square, np.asarray(band._ahead(sub, 1) == 0))
    for i in range(1024 // sub):
        at = slice(i * sub, (i + 1) * sub)
        covered[at, at] = True
        np.testing.assert_array_equal(allowed[at, at], square)
    assert not (allowed & ~covered).any()
    # the tile's own mask (a `DIAGONAL` visit's) is the same pairs
    np.testing.assert_array_equal(
        np.asarray(band.allowed(band.n, band.n, 0)), allowed)


def test_the_diagonal_masks_by_half():
    """A tile at the query tile's own position: clean -> clean sees its
    own block and earlier ones, noised -> clean strictly earlier ones;
    a tile that is one block is not met by the noised half at all."""
    band = fbd._DiffusionBand(128, 16, 4)
    blk = np.arange(16) // 4
    ahead = blk[:, None] - blk[None, :]             # blk(r) - blk(s)
    np.testing.assert_array_equal(band.allowed(1, 1, 0), ahead >= 0)
    np.testing.assert_array_equal(band.allowed(5, 1, 0), ahead >= 1)
    np.testing.assert_array_equal(band.allowed(5, 1, 1), (ahead >= 1).T)
    assert band.interior(5, 0) and not band.interior(5, 1)
    whole = fbd._DiffusionBand(128, 16, 16)
    assert whole.blocks_allowed == 4 * 5 // 2 + 4 * 3 // 2 + 4
    assert [kb for qb, kb, _ in whole.tiles() if qb == 4] == [4]
    assert [kb for qb, kb, _ in whole.tiles() if qb == 5] == [0, 5]
    assert whole.pairs() == 64 * 16 + 256 * (4 * 3 // 2 + 4 * 5 // 2)
    for name in ("key_at", "key_runs", "query_at", "query_runs", "dq_time",
                 "k_time", "q_time", "first_k"):
        assert name not in vars(fbd._DiffusionBand)     # `_Band`'s, unused


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
    band = fbd._DiffusionBand(64, 16, 4)
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
