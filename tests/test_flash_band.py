"""`ops/pallas/flash_attention.py`'s band kernels (a `window`, grouped
key/value heads at any d_head the forward takes; interpret mode on the
CPU, the same kernels Mosaic compiles in tests/test_chip_compile_flash.py)
against the XLA composition under an EXPLICIT mask: forward and all
three gradients; the one-kernel and the two-kernel backward to the bit;
the window forward's two paths (a query tile against its whole band,
the soft-max in one pass; the online soft-max over the band's key
tiles) and what chooses between them; the call without a window on its
list of visits (PR 63) against the rectangle grids it left, to the bit,
and the table itself on the host; k, v, dk, dv never repeated; what the
band kernels do not take raises.
"""

import functools
import itertools

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
        lambda q, k, v: jnp.sum((w * fn(q, k, v)).astype(jnp.float32)),
        (0, 1, 2))(q, k, v)


@functools.cache
def _dense_grads(window, hkv, dtype):
    """o and (dq, dk, dv) against the weight of the masked composition
    in float32, on `_qkvw(hkv, seed=3, dtype=dtype)`: once a module,
    whatever the tiles."""
    q, k, v, w = (x.astype(F32) for x in _qkvw(hkv, seed=3, dtype=dtype))
    out, pull = jax.vjp(lambda *a: _dense(*a, hkv, window), q, k, v)
    return out, pull(w)


# under a block, one block, one and a half, and every key (no window)
WINDOWS = [None, 5, BLOCK, 24, T, T + 9]
SQUARE, WIDE_K, WIDE_Q = (16, 16), (16, 32), (32, 16)
F32, BF16 = jnp.float32, jnp.bfloat16
# (window, key/value heads, blocks, dtype).  Square tiles under a window
# take the whole-band forward (W < b, W = b, W = 1.5 b, W = 2 b: two and
# three key tiles a step, the first query tiles' clamped), the others
# the online soft-max; groups of 1, 2 and 8 query heads a step
BAND_CASES = [
    case + (F32,) for case in itertools.product(
        WINDOWS, [H, H // 8], [SQUARE, WIDE_K, WIDE_Q])
] + [(32, hkv, SQUARE, F32) for hkv in (H, H // 8)] + [
    (window, H // 2, SQUARE, F32) for window in (5, BLOCK, 32)
] + [(5, H, SQUARE, BF16), (BLOCK, H // 2, SQUARE, BF16),
     (32, H // 8, SQUARE, BF16)]


def _case_id(case):
    window, hkv, blocks, dtype = case
    return (f"w{window}-group{H // hkv}-{blocks[0]}x{blocks[1]}-"
            f"{jnp.dtype(dtype).name}")


@pytest.mark.parametrize("window, hkv, blocks, dtype", BAND_CASES,
                         ids=[_case_id(c) for c in BAND_CASES])
def test_band_kernels_match_the_masked_composition(window, hkv, blocks,
                                                   dtype, monkeypatch):
    """Forward and dq, dk, dv; then the same call with the single
    kernel's budget at zero: the two kernels give the same bits.  The
    backward kernels are the same behind either forward."""
    q, k, v, w = _qkvw(hkv, seed=3, dtype=dtype)
    kw = dict(block_q=blocks[0], block_k=blocks[1])
    before = runtime_stats.snapshot()
    # (the forward once: a backward rule runs whenever `pull` is called)
    out, pull = jax.vjp(lambda *a: _flash(*a, hkv, window, **kw), q, k, v)
    got = pull(w)
    took = runtime_stats.delta(before)
    want_out, want = _dense_grads(window, hkv, dtype)
    # bfloat16: p rounded to 8 bits before each product, as o and the
    # gradients are
    tol = 2e-5 if dtype == F32 else 6e-2
    np.testing.assert_allclose(out.astype(F32), want_out, rtol=tol, atol=tol)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == r.shape           # dk, dv: key/value heads wide
        assert g.dtype == dtype
        np.testing.assert_allclose(g.astype(F32), r, rtol=tol, atol=tol,
                                   err_msg="d" + name)
    assert took["flash_attention_backward_fused"] == 1
    windowed = window is not None and window < T
    assert (took["flash_window_blocks_visited"] > 0) == windowed
    assert (took["flash_window_forward_whole_band"],
            took["flash_window_forward_tiled"]) == (
        (0, 0) if not windowed else (1, 0) if blocks == SQUARE else (0, 1))
    monkeypatch.setattr(fa, "FUSED_ACCUMULATOR_BUDGET", 0)
    before = runtime_stats.snapshot()
    split = pull(w)
    assert runtime_stats.delta(before)["flash_attention_backward_split"] == 1
    for g, s in zip(got, split):
        np.testing.assert_array_equal(g.astype(F32), s.astype(F32))


# (key/value heads, tiles, rows, dtype): groups of 1, 2 and 8 query
# heads; 2, 3 and 4 tiles a side, square and not
RECTANGLE_CASES = [
    (H, SQUARE, 32, F32), (H // 2, SQUARE, 48, BF16), (H // 8, SQUARE, 64, F32),
    (H // 8, WIDE_K, 64, BF16), (H // 2, WIDE_Q, 64, F32)]


@pytest.mark.parametrize(
    "hkv, blocks, t, dtype", RECTANGLE_CASES,
    ids=[f"group{H // c[0]}-{c[1][0]}x{c[1][1]}-t{c[2]}-"
         f"{jnp.dtype(c[3]).name}" for c in RECTANGLE_CASES])
def test_the_list_of_visits_gives_the_rectangles_bits(hkv, blocks, t, dtype,
                                                      monkeypatch):
    """The call over the whole causal prefix walks a list of visits, its
    tiles under the diagonal with no mask (PR 63); the rectangle grids
    it left (query tiles x the longest run, every tile masked: they
    still serve a window) give the same o, logsumexp, dq, dk and dv to
    the bit, by the single backward kernel and by the two.  The scale
    is a power of two: the CPU's compiler, which runs the interpreter's
    steps, contracts `s * scale - m` where no select stands between the
    two, and only such a product rounds the same either way."""
    q, k, v, do = (x[:1] for x in _qkvw(hkv, seed=13, t=t, dtype=dtype))
    group, scale = H // hkv, 0.25
    band = fa._Band(t, *blocks, None)
    o, lse = fa._flash_fwd(q, k, v, None, None, scale, True, *blocks, "nthd",
                           H, band, group)
    before = runtime_stats.snapshot()
    res = fa._flash_band_fwd(q, k, v, scale, blocks, blocks, H, group,
                             None)[1]
    took = runtime_stats.delta(before)
    steps = len(band.tiles())
    assert took["flash_prefix_grid_steps"] == steps == band.blocks_allowed
    assert took["flash_prefix_visits_full"] \
        + took["flash_prefix_visits_diagonal"] == steps
    assert took["flash_grouped_calls"] == (group > 1)
    np.testing.assert_array_equal(res[3].astype(F32), o.astype(F32))
    np.testing.assert_array_equal(res[4], lse)
    for budget, fused in ((fa.FUSED_ACCUMULATOR_BUDGET, 1), (0, 0)):
        monkeypatch.setattr(fa, "FUSED_ACCUMULATOR_BUDGET", budget)
        want = fa._flash_bwd_band(q, k, v, o, lse, do, scale, band, H, group)
        before = runtime_stats.snapshot()
        got = fa._flash_band_bwd(scale, blocks, blocks, H, group, None, res,
                                 do)
        took = runtime_stats.delta(before)
        assert (took["flash_attention_backward_fused"],
                took["flash_attention_backward_split"]) == (fused, 1 - fused)
        assert took["flash_prefix_grid_steps"] == steps
        for name, g, w in zip("qkv", got, want):
            assert g.dtype == dtype and bool(jnp.any(g != 0)), name
            np.testing.assert_array_equal(g.astype(F32), w.astype(F32),
                                          err_msg="d" + name)


ORDERS = {"query-major": {}, "key-major": {"key_major": True},
          "key-major-heads": {"key_major": True, "group": 2}}
# (rows, query tile, key tile, window): the causal prefix in 2, 3 and 4
# tiles, square and not; and under a window, whose tiles the same
# method lists (no kernel walks them: a window's grids have no empty run)
GEOMETRIES = [(32, 16, 16, None), (48, 16, 16, None), (64, 16, 16, None),
              (64, 16, 32, None), (64, 32, 16, None), (64, 16, 16, 24),
              (64, 16, 16, 5), (64, 32, 16, 33)]


@pytest.mark.parametrize("order", sorted(ORDERS))
@pytest.mark.parametrize("t, bq, bk, window", GEOMETRIES)
def test_the_table_of_visits_is_the_mask_written_out(t, bq, bk, window,
                                                     order):
    """No kernel: every tile that holds an allowed pair is visited
    exactly once (a head), `FULL` exactly where the mask leaves the tile
    whole (square tiles without a window: the key tile before the query
    tile's), a major tile's visits in a row with FIRST / LAST around
    them, and the dq tile an output holds is complete or being
    completed: never half-summed."""
    band = fa._Band(t, bq, bk, window)
    kw = ORDERS[order]
    group, key_major = kw.get("group", 1), kw.get("key_major", False)
    table = band.visits(**kw)
    assert table.dtype == np.int32 and table.shape[0] == 9
    q, k, head, kind, first, last, dq, dq_first, dq_last = table
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (j <= i) & (True if window is None else j > i - window)
    tiles = seen.reshape(t // bq, bq, t // bk, bk)
    holds = set(zip(*np.nonzero(tiles.any(axis=(1, 3)))))
    whole = set(zip(*np.nonzero(tiles.all(axis=(1, 3)))))
    for gi in range(group):
        mine = list(zip(q[head == gi], k[head == gi]))
        assert len(mine) == len(set(mine)) == band.blocks_allowed
        assert set(mine) == holds
    assert {(a, b) for a, b, c in zip(q, k, kind) if c == fa.FULL} == whole
    assert set(kind) <= {fa.FULL, fa.DIAGONAL}
    if window is None and bq == bk:
        assert all((c == fa.FULL) == (b < a) for a, b, c in zip(q, k, kind))
    # the mask of a tile the diagonal or the window's edge crosses, by
    # position, in both orientations
    for a, b in sorted(holds - whole)[:3]:
        np.testing.assert_array_equal(band.allowed(a, b, 0), tiles[a, :, b])
        np.testing.assert_array_equal(band.allowed(a, b, 1), tiles[a, :, b].T)
    assert_runs_and_dq_tiles(table, band.nq, group, key_major)


def assert_runs_and_dq_tiles(table, nq, group, key_major):
    """What every table of visits holds, whatever its geometry
    (tests/test_flash_block_diffusion.py reads this too): a major tile's
    visits in a row, a head after a head inside a key tile's, the other
    side ascending, FIRST / LAST around each run; a query tile's first
    and last visit; and the dq tile an output's index map holds is
    complete or being completed."""
    q, k, head, _, first, last, dq, dq_first, dq_last = table
    major, minor = (k, q) if key_major else (q, k)
    keys = list(zip(major, head, minor))
    assert keys == sorted(keys)
    run = list(zip(major, head))
    for v in range(len(run)):
        assert first[v] == (v == 0 or run[v] != run[v - 1])
        assert last[v] == (v == len(run) - 1 or run[v] != run[v + 1])
    if group > 1:
        return
    for qb in range(nq):
        met = np.flatnonzero(q == qb)
        assert list(np.flatnonzero(dq_first & (q == qb))) == [met[0]]
        assert list(np.flatnonzero(dq_last & (q == qb))) == [met[-1]]
    for v in range(len(q)):
        # written by now: it may leave whenever the index moves on
        assert np.flatnonzero(dq_last & (q == dq[v]))[0] <= max(
            v, np.flatnonzero(dq_last)[0])
        if v and dq[v] != dq[v - 1]:
            assert dq_last[v] and q[v] == dq[v]


@pytest.mark.parametrize("t, heads, visits, full", [
    (16384, (48, 8), 136, 120), (16384, (32, 4), 136, 120),
    (8192, (40, 20), 36, 28)])
def test_the_counters_say_what_a_call_over_the_whole_prefix_walks(
        t, heads, visits, full):
    """`laguna-16k`'s, `mellum2-16k`'s and `phi4flash-8k`'s full layers
    in the kernels' own 1024 x 1024 tiles, traced and not run: a grid
    step a tile that holds a score (the rectangle took 256 for 136, 64
    for 36), 88 % and 78 % of them computed with no mask; forward and
    backward walk the same tiles."""
    h, hkv = heads
    q, k, v = (jax.ShapeDtypeStruct((1, t, n * 128), BF16)
               for n in (h, hkv, hkv))
    assert fa._band_blocks(t, None, None, None) == ((1024, 1024),) * 2
    band = fa._Band(t, 1024, 1024, None)
    assert (band.nq * band.k_steps, band.blocks_allowed) == (
        (t // 1024) ** 2, visits)
    for kw in ORDERS.values():
        table = band.visits(**kw)
        assert table.shape == (9, visits * kw.get("group", 1))
        assert tuple(np.bincount(table[fa.V_KIND])) == tuple(
            n * kw.get("group", 1) for n in (full, visits - full))
    before = runtime_stats.snapshot()
    jax.eval_shape(jax.grad(lambda *a: jnp.sum(fa.pallas_flash_attention(
        *a, None, None, True, layout="nthd", n_head=h,
        n_kv_head=hkv).astype(F32)), (0, 1, 2)), q, k, v)
    took = runtime_stats.delta(before)
    assert took["flash_grouped_calls"] == 1
    assert took["flash_attention_backward_fused"] == 1
    steps = took["flash_prefix_grid_steps"]
    tiles = took["flash_prefix_visits_full"] \
        + took["flash_prefix_visits_diagonal"]
    assert steps == tiles == 2 * visits         # steps over tiles: 1.0
    assert took["flash_prefix_visits_full"] == 2 * full
    assert round(100 * full / visits) == (88 if t == 16384 else 78)


@pytest.mark.parametrize("window, hkv, dtype", [
    (5, H, F32), (BLOCK, H // 2, F32), (24, H // 8, F32), (32, H // 8, F32),
    (BLOCK, H // 8, BF16)])
def test_the_whole_band_forward_gives_the_tiled_forwards_o_and_lse(
        window, hkv, dtype):
    """The two forwards on the same operands: the same `o` and
    logsumexp to float32 rounding (a bfloat16 `o` to its last bit), the
    first query tiles (their clamped key tiles masked whole) included;
    the same declared cost (the kernel's name is read off
    the lowered text below and in tests/test_chip_compile_flash.py)."""
    q, k, v, _ = _qkvw(hkv, seed=7, dtype=dtype)
    band = fa._Band(T, BLOCK, BLOCK, window)
    o, lse = fa._flash_fwd(q, k, v, None, None, D ** -0.5, True, BLOCK,
                           BLOCK, "nthd", H, band, H // hkv)
    o2, lse2 = fa._flash_fwd_whole_band(q, k, v, D ** -0.5, BLOCK, H,
                                        H // hkv, window)
    assert (o2.shape, o2.dtype, lse2.shape) == (o.shape, o.dtype, lse.shape)
    tol = 2e-6 if dtype == F32 else 2 ** -7
    np.testing.assert_allclose(o2.astype(F32), o.astype(F32), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(lse2, lse, rtol=2e-6, atol=2e-6)
    np.testing.assert_array_equal(lse2[:, 0], lse2[:, 7])    # 8 sublanes

    def declared(fn, *args):
        eqn, = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
                if e.primitive.name == "pallas_call"]
        return eqn.params["cost_estimate"]

    cost = declared(lambda q, k, v: fa._flash_fwd_whole_band(
        q, k, v, 1.0, BLOCK, H, H // hkv, window), q, k, v)
    assert cost == declared(lambda q, k, v: fa._flash_fwd(
        q, k, v, None, None, 1.0, True, BLOCK, BLOCK, "nthd", H, band,
        H // hkv), q, k, v)
    assert cost.flops == 2 * H * band.pairs() * (4 * D + 8)


@pytest.mark.parametrize("window, blocks, whole", [
    (1, (256, 256), True), (100, (256, 256), True), (300, (256, 256), True),
    (512, (512, 512), True), (700, (512, 512), True),
    (1024, (512, 512), True), (1025, (512, 512), True),
    (1026, (1024, 1024), False), (2048, (1024, 1024), False),
    (4096, (1024, 1024), False), (None, (1024, 1024), False)])
def test_the_shape_alone_chooses_the_window_forward(window, blocks, whole):
    """The forward tile and the forward's path from the window alone: up
    to 1025 keys a tile of 512 (256 under 512 keys) against its whole
    band, whose float32 scores stay within the budget (512 x 1536 at the
    most); a wider window, and a call without one, the online soft-max
    over 1024 x 1024 tiles.  A tile given holds, and chooses: square and
    within the budget, the whole band."""
    fwd, bwd = fa._band_blocks(16384, None, None, window)
    assert fwd == blocks
    assert bwd == ((512, 512) if window else (1024, 1024))
    assert fa.whole_band_forward_fits(window, *fwd) == whole
    assert fa._band_blocks(16384, 128, 256, window) == ((128, 256),) * 2
    assert not fa.whole_band_forward_fits(window, 128, 256)
    if window:
        tiles = -(-(window - 1) // fwd[0]) + 1
        assert fa._Band(16384, *fwd, window).k_steps == tiles
        assert (4 * fwd[0] * tiles * fwd[1]
                <= fa.WHOLE_BAND_SCORE_BUDGET) == whole
    # 1024 x 1024 given: 4 MiB of scores a key tile, the online soft-max
    # whatever the window
    assert not fa.whole_band_forward_fits(window, 1024, 1024)


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
    # the whole-band forward reads k and v under one BlockSpec a key
    # tile of the band: the same two arrays, three times each
    narrow = [x for x in kernels[0].invars if x.aval.shape == k.shape]
    assert len(narrow) == 6 and len(set(narrow)) == 2
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
