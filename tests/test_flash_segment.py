"""Attention confined to the segments of a packed row axis
(`ops/pallas/flash_segment.py`, the `segment_attention` op) and the
other ops a second tower brings (`ops/vision.py`: `table_interp`,
`image_merge`; `rope` over two axes), on the CPU at small sizes.

The kernels run through the Pallas interpreter against the masked XLA
lowering, forward and every gradient, for segments that end on a tile
edge, straddle tiles, are shorter than a tile, and a padding tail, in
tiles of one sub-block and of four.  The LIST OF VISITS the kernels walk
and the TABLE OF SUB-BLOCKS that says which products a visit makes
(both made on the device from the rows' segment ids) are held to a
plain numpy enumeration as tables, not as more interpreted calls: every
tile pair that shares a segment once, in order, FIRST / LAST of each
run, the dq tile an output holds, the static bound; a pair of
sub-blocks runs exactly where it shares a segment, unmasked exactly
where no boundary crosses it, and the pairs a call runs are the same
in every order of the cell's sixteen images.

The layout the kernels read and the rotary turn inside it
(`ops/pallas/head_lanes.py`; `segment_attention`'s `Positions`): the
two lane kernels alone against the pad, the slice and `_rope` (there
and back, the zeros behind a head, the turn's transpose), and the
attention that turns its own q and k against `_rope` followed by the
XLA lowering and by the kernels on XLA's layout, every gradient, in
float32 and bfloat16, heads of 72, 32 and 128 lanes.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from models import kimi_vl as family  # noqa: E402
import reference_kimi_vl as ref  # noqa: E402

from paddle_tpu.core.registry import OpContext, get_op_impl  # noqa: E402
from paddle_tpu.observe.monitoring import runtime_stats  # noqa: E402
from paddle_tpu.ops import decoder  # noqa: E402
from paddle_tpu.ops.pallas import flash_segment as fs  # noqa: E402
from paddle_tpu.ops.pallas import head_lanes  # noqa: E402

BLOCK = 128


def segments(lengths, rows):
    """Ids 0, 1, .. over runs of `lengths` rows, then a padding tail."""
    seg = np.concatenate([np.full(n, i) for i, n in enumerate(lengths)]
                         + [np.full(rows - sum(lengths), -1)])
    return seg.astype(np.int32)


CASES = {
    # name: (segment lengths, rows)
    "tile_edges": ([128, 256, 128], 512),
    "straddling": ([100, 190, 60, 162], 512),
    "short_and_padded": ([20, 7, 130, 60, 150], 512),
    "one_segment": ([384], 384),
}
# tiles of four sub-blocks: pairs of sub-blocks skipped, masked and whole
# inside one visit (name: lengths, rows, tile, sub-block)
SUB_CASES = {
    "sub_edges": ([64, 192, 128, 128], 512, 256, 64),
    "sub_padded": ([20, 7, 130, 60, 150], 512, 256, 64),
}


@pytest.mark.parametrize("case", sorted(CASES) + sorted(SUB_CASES))
def test_the_kernels_are_the_masked_attention(case):
    """`flash_segment` (interpret mode, tiles of 128 that are one
    sub-block each, and tiles of 256 in sub-blocks of 64) against
    `segment_attention_xla` at 2 heads of 72 lanes, float32: the result
    and the gradients of q, k and v under one cotangent; a padding row's
    output and gradients are exactly 0."""
    lengths, rows, *tiles = (*CASES.get(case, ()), *SUB_CASES.get(case, ()))
    block, sub = tiles or (BLOCK, None)
    seg = jnp.asarray(segments(lengths, rows))[None]
    rng = np.random.default_rng(len(lengths))
    heads, d = 2, 72
    q, k, v, ct = (jnp.asarray(rng.normal(size=(1, rows, heads * d)),
                               jnp.float32) for _ in range(4))
    with jax.default_matmul_precision("highest"):
        got, back = jax.vjp(lambda *x: fs.flash_segment(
            *x, seg, heads, block=block, sub_block=sub,
            max_segment_rows=256)[0], q, k, v)
        want, back_xla = jax.vjp(lambda *x: fs.segment_attention_xla(
            *x, seg, heads, block=BLOCK), q, k, v)
        grads, grads_xla = back(ct), back_xla(ct)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    for name, g, w in zip("qkv", grads, grads_xla):
        np.testing.assert_allclose(g, w, atol=5e-6, rtol=5e-6,
                                   err_msg="d" + name)
    pad = np.asarray(seg[0]) < 0
    assert not np.asarray(got)[0, pad].any()
    assert not any(np.asarray(g)[0, pad].any() for g in grads)


def test_bfloat16_operands_miss_the_float32_tolerance():
    """The control: the same call on bfloat16 operands (AMP's) stays
    within bfloat16's rounding of the float32 result and misses the
    float32 tolerance by far."""
    lengths, rows = CASES["straddling"]
    seg = jnp.asarray(segments(lengths, rows))[None]
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(1, rows, 144)), jnp.float32)
               for _ in range(3))
    want = fs.segment_attention_xla(q, k, v, seg, 2, block=BLOCK)
    got = fs.flash_segment(*(x.astype(jnp.bfloat16) for x in (q, k, v)),
                           seg, 2, block=BLOCK)[0]
    err = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert 100 * 2e-6 < err < 0.05, err


# -- the list of visits, as a table -------------------------------------------

def expected_visits(seg, block):
    """[(outer tile, inner tile)] in the list's order, and per pair
    whether it lies wholly inside one segment, by enumeration: tile a's
    run is every tile that shares a segment with it (a tile of padding
    rows: itself)."""
    tiles = len(seg) // block
    ids = [set(seg[t * block:(t + 1) * block][
        seg[t * block:(t + 1) * block] >= 0].tolist()) for t in range(tiles)]
    pairs = []
    for a in range(tiles):
        run = [b for b in range(tiles) if ids[a] & ids[b]] or [a]
        assert run == list(range(run[0], run[-1] + 1))
        pairs += [(a, b) for b in run]
    whole = [len(ids[a]) == 1 and ids[a] == ids[b]
             and (seg[a * block:(a + 1) * block] >= 0).all()
             and (seg[b * block:(b + 1) * block] >= 0).all()
             for a, b in pairs]
    return pairs, whole


TABLES = dict(CASES, **{
    "padding_only_tiles": ([100], 512),
    "many_short": ([30] * 16, 512),
    "cell_like": ([256, 1024, 384, 128, 256], 2048),
})


@pytest.mark.parametrize("case", sorted(TABLES))
def test_the_list_of_visits_is_every_pair_that_shares_a_segment(case):
    lengths, rows = TABLES[case]
    seg = segments(lengths, rows)
    bound = fs.visit_bound(rows, BLOCK, max(lengths))
    table, visits, cut = fs.visit_table(jnp.asarray(seg)[None], BLOCK, bound)
    table, real = np.asarray(table), int(visits[0])
    assert not cut[0]
    pairs, whole = expected_visits(seg, BLOCK)
    assert real == len(pairs) <= bound
    assert list(zip(table[fs.V_A, :real], table[fs.V_B, :real])) == pairs
    assert (table[fs.V_REAL, :real] == 1).all()
    # the tail names the last real visit again and does nothing
    assert (table[fs.V_REAL, real:] == 0).all()
    assert (table[fs.V_A, real:] == pairs[-1][0]).all()
    assert (table[fs.V_B, real:] == pairs[-1][1]).all()
    assert not table[[fs.V_FIRST, fs.V_LAST, fs.V_B_FIRST, fs.V_B_LAST],
                     real:].any()
    a, b = np.array(pairs).T
    first = np.r_[True, a[1:] != a[:-1]]
    last = np.r_[a[1:] != a[:-1], True]
    np.testing.assert_array_equal(table[fs.V_FIRST, :real], first)
    np.testing.assert_array_equal(table[fs.V_LAST, :real], last)
    # the backward pass: an inner tile's sum opens at its first outer
    # tile and leaves at its last, and the output holds the tile that is
    # next to leave, so each leaves once, in order, and is never revisited
    opened, left = set(), []
    for v in range(real):
        if table[fs.V_B_FIRST, v]:
            assert b[v] not in opened
            opened.add(b[v])
        assert b[v] in opened and b[v] not in left
        if table[fs.V_B_LAST, v]:
            assert table[fs.V_HELD, v] == b[v]
            left.append(b[v])
        assert table[fs.V_HELD, v] == (len(left) - bool(
            table[fs.V_B_LAST, v]) if len(left) - bool(
            table[fs.V_B_LAST, v]) < rows // BLOCK else rows // BLOCK - 1)
    assert left == list(range(rows // BLOCK))
    held = table[fs.V_HELD]
    assert (np.diff(held) >= 0).all()


def pairs_run(seg, block, sub):
    """(pairs of sub-blocks a pass runs masked, unmasked) over the real
    visits of `seg`'s list, by the kernels' own rule on the two tables."""
    seg = np.asarray(fs.segment_runs(jnp.asarray(seg)[None]))
    low, high, one = np.asarray(fs.sub_table(jnp.asarray(seg), sub))
    table, visits, _ = fs.visit_table(
        jnp.asarray(seg), block, fs.visit_bound(len(seg[0]), block))
    per = block // sub
    masked = whole = 0
    for a, b in np.asarray(table)[[fs.V_A, fs.V_B], :int(visits[0])].T:
        for i in range(a * per, (a + 1) * per):
            for j in range(b * per, (b + 1) * per):
                shares = low[i] <= high[j] and low[j] <= high[i]
                is_whole = one[i] >= 0 and one[i] == one[j]
                masked += shares and not is_whole
                whole += is_whole
    return masked, whole


@pytest.mark.parametrize("case", sorted(TABLES))
def test_a_pair_of_sub_blocks_runs_where_it_shares_a_segment(case):
    """`sub_table` against enumeration: by the kernels' rule a pair of
    sub-blocks (32 rows here) runs exactly where some segment has rows
    in both, and unmasked exactly where every row of both is that one
    segment's; every such pair lies in a tile pair the list visits."""
    lengths, rows = TABLES[case]
    seg, sub = segments(lengths, rows), 32
    low, high, one = np.asarray(fs.sub_table(
        fs.segment_runs(jnp.asarray(seg)[None]), sub))
    blocks = seg.reshape(-1, sub)
    ids = [set(b[b >= 0].tolist()) for b in blocks]
    visited = set(expected_visits(seg, BLOCK)[0])
    masked = unmasked = 0
    for i, a in enumerate(ids):
        for j, b in enumerate(ids):
            shares = low[i] <= high[j] and low[j] <= high[i]
            whole = one[i] >= 0 and one[i] == one[j]
            assert shares == bool(a & b), (i, j)
            assert whole == (a == b and len(a) == 1 and (blocks[i] >= 0).all()
                             and (blocks[j] >= 0).all()), (i, j)
            if shares:
                assert (i * sub // BLOCK, j * sub // BLOCK) in visited
            masked += shares and not whole
            unmasked += whole
    # and the list's visits hold each such pair once
    assert pairs_run(seg, BLOCK, sub) == (masked, unmasked)


def test_the_cells_images_cost_the_same_in_every_order():
    """`kimivl-8k`'s sixteen images (every row count a multiple of the
    256-row sub-block) in drawn orders on the 24576-row axis: the tiles
    of 1024 a head visits differ with the order, the pairs of sub-blocks
    it runs do not, 936 unmasked (the allowed pairs, 61,341,696, in
    units of 256 x 256) and none masked."""
    images = (4096,) * 2 + (2304,) * 4 + (1024,) * 6 + (256,) * 4
    rng = np.random.default_rng(0)
    visits = set()
    for _ in range(4):
        seg = segments(list(rng.permutation(images)), 24576)
        assert pairs_run(seg, fs.LARGE_BLOCK, fs.SUB_BLOCK) == (0, 936)
        visits.add(int(fs.visit_table(jnp.asarray(seg)[None], fs.LARGE_BLOCK,
                                      fs.visit_bound(24576, 1024, 4096))[1][0]))
    assert len(visits) > 1
    assert 936 * 256 * 256 == sum(n * n for n in images) == 61_341_696


@pytest.mark.parametrize("lengths, most, cut", [
    ([128] * 8, 128, False),            # the bound's own segments
    ([300, 200, 524], 128, False),      # longer, and the list still holds
    ([1024], 128, True),                # 64 visits, a list of 40
    ([700, 324], 128, True)])
def test_the_table_says_when_its_list_is_cut(lengths, most, cut):
    """Segments longer than `max_segment_rows` may need more visits than
    the list's static length: `visit_table` says so, by the count."""
    seg = segments(lengths, 1024)
    bound = fs.visit_bound(1024, BLOCK, most)
    assert bound == 8 * 5
    pairs, _ = expected_visits(seg, BLOCK)
    _, visits, said = fs.visit_table(jnp.asarray(seg)[None], BLOCK, bound)
    assert bool(said[0]) == cut == (len(pairs) > bound)
    assert int(visits[0]) == min(len(pairs), bound)


def test_a_cut_list_is_nan_and_not_what_memory_held():
    """Two row axes in one call, the first a segment eight times
    `max_segment_rows`: its output is NaN, every row, and the second's
    is the masked attention."""
    seg = jnp.asarray(np.stack([segments([1024], 1024),
                                segments([128, 100, 60, 128, 90], 1024)]))
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 1024, 144)), jnp.float32)
               for _ in range(3))
    with jax.default_matmul_precision("highest"):
        got = fs.flash_segment(q, k, v, seg, 2, block=BLOCK,
                               max_segment_rows=128)[0]
        want = fs.segment_attention_xla(q, k, v, seg, 2, block=BLOCK)
    assert np.isnan(np.asarray(got[0])).all()
    np.testing.assert_allclose(got[1], want[1], atol=2e-6, rtol=2e-6)


def test_an_id_that_comes_again_is_another_segment():
    """A segment is a RUN of one id: rows of id 0 after rows of id 1 do
    not read the first rows of id 0, in the kernels and in the XLA
    lowering alike (a tile that both runs touch is visited, one that
    only the id joins is not: the runs decide, not the visits)."""
    lengths = [100, 190, 60, 162]
    again = np.repeat([0, 1, 0, 1], lengths).astype(np.int32)[None]
    np.testing.assert_array_equal(
        fs.segment_runs(jnp.asarray(again))[0], segments(lengths, 512))
    np.testing.assert_array_equal(
        fs.segment_runs(jnp.asarray([[-1, 4, 4, -1, -1, 4, 2]]))[0],
        [-1, 1, 1, -1, -1, 3, 4])
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 512, 144)), jnp.float32)
               for _ in range(3))
    with jax.default_matmul_precision("highest"):
        want = fs.segment_attention_xla(
            q, k, v, jnp.asarray(segments(lengths, 512))[None], 2,
            block=BLOCK)
        got = fs.flash_segment(q, k, v, jnp.asarray(again), 2, block=BLOCK,
                               max_segment_rows=256)[0]
        xla = fs.segment_attention_xla(q, k, v, jnp.asarray(again), 2,
                                       block=BLOCK)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    np.testing.assert_array_equal(xla, want)


def test_the_bound_holds_every_layout_of_bounded_segments():
    """`visit_bound` against random layouts of segments of at most M
    rows (and the rectangle where no M is given)."""
    rng = np.random.default_rng(0)
    rows, most = 4096, 1024
    bound = fs.visit_bound(rows, BLOCK, most)
    assert bound == 32 * (8 + 4)
    assert fs.visit_bound(rows, BLOCK) == 32 * 32
    assert fs.visit_bound(24576, 512, 4096) == 48 * 12
    for _ in range(40):
        lengths = []
        while sum(lengths) < rows:
            lengths.append(int(rng.choice(
                [rng.integers(1, most + 1), most, most - 1, 1, 127, 129])))
        lengths[-1] -= sum(lengths) - rows
        pairs, _ = expected_visits(segments(lengths, rows), BLOCK)
        assert len(pairs) <= bound, lengths


def test_the_shape_alone_chooses_the_lowering():
    takes = fs.segment_attention_takes
    assert takes(24576, 16, 72) and takes(512, 2, 72) and takes(1024, 4, 128)
    assert not takes(500, 2, 72) and not takes(128, 2, 72)
    assert (fs.default_block(24576), fs.default_block(1536)) == (1024, 512)
    with pytest.raises(ValueError, match="whole sub-blocks"):
        fs.flash_segment(*[jnp.ones((1, 512, 144))] * 3,
                         jnp.zeros((1, 512), jnp.int32), 2, block=256,
                         sub_block=96)
    assert fs.tiles_total(1, 24576, 16) == 16 * 24 * 24
    assert not takes(131072, 16, 72)        # dq of a head past the budget
    op = get_op_impl("segment_attention")
    x = jnp.ones((1, 64, 144), jnp.float32)
    seg = jnp.zeros((1, 64), jnp.int32)
    before = runtime_stats.snapshot()
    outs = op(OpContext(jax.random.PRNGKey(0), 0),
              {"Q": [x], "K": [x], "V": [x], "SegmentIds": [seg],
               "TilesVisited": [jnp.zeros((1,), jnp.int32)],
               "TilesTotal": [jnp.zeros((1,), jnp.int32)]}, {"n_head": 2})
    took = runtime_stats.delta(before)
    assert (took["flash_segment_calls"], took["flash_segment_xla_calls"],
            took["flash_segment_tiles_total"]) == (0, 1, 0)
    assert int(outs["TilesVisitedOut"][0][0]) == 0
    np.testing.assert_allclose(outs["Out"][0], x, atol=1e-6)
    with pytest.raises(ValueError, match="not 5 heads"):
        op(OpContext(jax.random.PRNGKey(0), 0),
           {"Q": [x], "K": [x], "V": [x], "SegmentIds": [seg]},
           {"n_head": 5})


# -- the kernels' layout and the turn inside it ------------------------------

def rotary_of(rng, n, rows, d):
    """cos, sin of drawn (row, column) positions, and the positions."""
    yx = jnp.asarray(rng.integers(0, 48, (n, rows, 2)), jnp.int32)
    return decoder._cos_sin_two_axes(yx, d, 10000.0), yx


def turned(x, rotary, heads, sign=1.0):
    cos, sin = rotary
    return decoder._rope(x, None, cos, sign * sin, heads, pairs=True)


# heads, lanes a head: a head in two of the row's tiles at sixteen
# offsets, four heads a tile, heads that are whole tiles
LANE_SHAPES = [(16, 72), (4, 32), (2, 128), (1, 256)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads, d", LANE_SHAPES)
def test_the_lane_kernels_are_the_pad_the_slice_and_the_turn(heads, d, dtype):
    """`head_lanes.to_tiles` / `from_tiles` (interpret mode) against
    `_to_lane_tiles`, `_from_lane_tiles` and `_rope` over pairs: there
    and back is the identity to the bit, an array that does not turn is
    moved to the bit, the lanes behind a head are exactly 0 turned or
    not, and `from_tiles` turns by the negated angle (the turn's
    transpose)."""
    rng = np.random.default_rng(heads)
    n, rows = 2, 128
    x, y, z = (jnp.asarray(rng.normal(size=(n, rows, heads * d)), dtype)
               for _ in range(3))
    rotary, _ = rotary_of(rng, n, rows, d)
    tables = head_lanes.tables(*rotary, d)
    tol = dict(atol=2e-6, rtol=2e-6) if dtype == jnp.float32 else dict(
        atol=0.02, rtol=0.02)
    wide = head_lanes.lane_tiles(d)
    assert head_lanes.head_lanes_take(rows, heads, d)

    plain = head_lanes.to_tiles((x, y), heads)
    for got, a in zip(plain, (x, y)):
        np.testing.assert_array_equal(got, fs._to_lane_tiles(a, heads))
    for got, a in zip(head_lanes.from_tiles(plain, heads, d), (x, y)):
        np.testing.assert_array_equal(got, a)

    moved = head_lanes.to_tiles((x, y, z), heads, tables, 2)
    for got, a in zip(moved, (x, y)):
        np.testing.assert_allclose(
            got.astype(jnp.float32),
            fs._to_lane_tiles(turned(a, rotary, heads), heads)
            .astype(jnp.float32), **tol)
    np.testing.assert_array_equal(moved[2], fs._to_lane_tiles(z, heads))
    for got in moved:
        behind = np.asarray(got.astype(jnp.float32)).reshape(
            n, rows, heads, wide)[..., d:]
        assert not behind.any()

    back = head_lanes.from_tiles(plain + (moved[2],), heads, d, tables, 2)
    for got, a in zip(back, (x, y)):
        np.testing.assert_allclose(
            got.astype(jnp.float32),
            turned(a, rotary, heads, -1.0).astype(jnp.float32), **tol)
    np.testing.assert_array_equal(back[2], z)
    if dtype == jnp.float32:
        # <to_tiles(x), w> = <x, from_tiles(w)>: the pair is a transpose
        w = jnp.asarray(rng.normal(size=moved[0].shape), dtype)
        there = float(jnp.vdot(moved[0], w))
        here = float(jnp.vdot(x, head_lanes.from_tiles(
            (w,), heads, d, tables, 1)[0]))
        np.testing.assert_allclose(there, here, rtol=1e-4)


def test_the_shape_alone_chooses_who_lays_the_lanes_out():
    takes = head_lanes.head_lanes_take
    assert takes(24576, 16, 72) and takes(512, 4, 32) and takes(512, 2, 128)
    # a row of 2 x 72 lanes is no whole tile; a head of 192 lies in two
    # of its own; 100 rows are no row tile
    assert not takes(512, 2, 72) and not takes(512, 2, 192)
    assert not takes(100, 16, 72)
    assert fs.lane_kernels_take(512, 16, 72)
    assert not fs.lane_kernels_take(256, 16, 72)    # XLA's attention
    # whole tiles that do not turn enter no kernel
    x = jnp.ones((1, 128, 256))
    assert head_lanes.to_tiles((x,), 2)[0] is x
    with pytest.raises(ValueError, match="no kernel turns"):
        fs.flash_segment(*[jnp.ones((1, 512, 144))] * 3,
                         jnp.zeros((1, 512), jnp.int32), 2,
                         rotary=rotary_of(np.random.default_rng(0), 1, 512,
                                          72)[0])


# name: (heads, lanes, dtype, segment lengths, rows, tile, sub-block)
TURNS = {
    "72_lanes_f32": (16, 72, jnp.float32, [100, 190, 60], 512, 128, None),
    "72_lanes_bf16": (16, 72, jnp.bfloat16, [100, 190, 60], 512, 128, None),
    "32_lanes_four_sub_blocks": (4, 32, jnp.float32, [20, 7, 130, 60, 150],
                                 512, 256, 64),
    "128_lanes_f32": (2, 128, jnp.float32, [128, 256, 100], 512, 128, None),
    "128_lanes_bf16": (2, 128, jnp.bfloat16, [128, 256, 100], 512, 128,
                       None),
}


@pytest.mark.parametrize("case", sorted(TURNS))
def test_the_attention_that_turns_is_the_turn_then_the_attention(
        case, monkeypatch):
    """`flash_segment(rotary=)` on UNTURNED q and k (the lane kernels
    lay the heads out and turn, interpret mode) against `_rope` followed
    by `segment_attention_xla`, and against `_rope` followed by the
    kernels on XLA's pad and slice (the path before PR 74): the result
    and the gradients of the unturned q, k and v, a padding tail's
    output and gradients exactly 0."""
    heads, d, dtype, lengths, rows, block, sub = TURNS[case]
    seg = jnp.asarray(segments(lengths, rows))[None]
    rng = np.random.default_rng(len(case))
    q, k, v, ct = (jnp.asarray(rng.normal(size=(1, rows, heads * d)), dtype)
                   for _ in range(4))
    rotary, _ = rotary_of(rng, 1, rows, d)

    def by_the_kernels(q, k, v):
        return fs.flash_segment(q, k, v, seg, heads, block=block,
                                sub_block=sub, max_segment_rows=256,
                                rotary=rotary)[0]

    def turn_then(attend):
        return lambda q, k, v: attend(turned(q, rotary, heads),
                                      turned(k, rotary, heads), v)

    with jax.default_matmul_precision("highest"):
        got, back = jax.vjp(by_the_kernels, q, k, v)
        grads = back(ct)
        want, back_xla = jax.vjp(turn_then(
            lambda *x: fs.segment_attention_xla(*x, seg, heads, block=128)),
            *(x.astype(jnp.float32) for x in (q, k, v)))
        grads_xla = back_xla(ct.astype(jnp.float32))
        monkeypatch.setattr(head_lanes, "head_lanes_take",
                            lambda *shape: False)
        before, back_before = jax.vjp(turn_then(
            lambda *x: fs.flash_segment(*x, seg, heads, block=block,
                                        sub_block=sub,
                                        max_segment_rows=256)[0]), q, k, v)
        grads_before = back_before(ct)
    f32 = dtype == jnp.float32
    tol = dict(atol=5e-6, rtol=5e-6) if f32 else dict(atol=0.06, rtol=0.06)
    np.testing.assert_allclose(got.astype(jnp.float32), want, **tol)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               before.astype(jnp.float32), **tol)
    for name, g, w, b in zip("qkv", grads, grads_xla, grads_before):
        scale = float(jnp.abs(w).max())
        for other in (w, b.astype(jnp.float32)):
            np.testing.assert_allclose(
                g.astype(jnp.float32) / scale, other / scale,
                atol=5e-6 if f32 else 0.03, err_msg="d" + name)
    pad = np.asarray(seg[0]) < 0
    assert pad.any()
    assert not np.asarray(got.astype(jnp.float32))[0, pad].any()
    assert not any(np.asarray(g.astype(jnp.float32))[0, pad].any()
                   for g in grads)


@pytest.mark.parametrize("rows, heads, d, counted", [
    (512, 16, 72, (1, 0, 1, 0)),    # the kernels, the lane kernels
    (512, 2, 72, (1, 0, 0, 1)),     # the kernels on XLA's pad and `_rope`
    (512, 2, 128, (1, 0, 1, 0)),    # whole tiles: the kernel only turns
    (64, 2, 72, (0, 1, 0, 0))])     # the XLA lowering after `_rope`
def test_the_op_with_positions_turns_its_own_q_and_k(rows, heads, d, counted):
    """The `segment_attention` op with `Positions` on unturned Q and K
    against the same op without it on the `rope` op's output (the
    Program before PR 74), on every path the shape may choose; where
    XLA turns, to the bit.  The counters say who laid the lanes out."""
    op, rope = get_op_impl("segment_attention"), get_op_impl("rope")
    rng = np.random.default_rng(rows + heads)
    lengths = [rows // 4, rows // 8, rows // 2]
    seg = jnp.asarray(segments(lengths, rows))[None]
    q, k, v = (jnp.asarray(rng.normal(size=(1, rows, heads * d)),
                           jnp.float32) for _ in range(3))
    _, yx = rotary_of(rng, 1, rows, d)
    ctx = OpContext(jax.random.PRNGKey(0), 0)

    def attend(q, k, **positions):
        return op(ctx, {"Q": [q], "K": [k], "V": [v], "SegmentIds": [seg],
                        **positions}, {"n_head": heads, "theta": 100.0,
                                       "max_segment_rows": rows})["Out"][0]

    def turn(x):
        return rope(ctx, {"X": [x], "Positions": [yx]},
                    {"n_head": heads, "theta": 100.0,
                     "interleave": True})["Out"][0]

    with jax.default_matmul_precision("highest"):
        before = runtime_stats.snapshot()
        got = attend(q, k, Positions=[yx])
        took = runtime_stats.delta(before)
        want = attend(turn(q), turn(k))
    assert tuple(took["flash_segment" + name] for name in (
        "_calls", "_xla_calls", "_lane_kernel_calls",
        "_lane_xla_calls")) == counted
    if counted[2]:
        np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6)
    else:
        np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="a \\(row, column\\) a row"):
        attend(q, k, Positions=[yx[:, :-1]])


def test_a_cut_list_is_nan_through_the_lane_kernels_too():
    """The cut list's NaN with the layout made by the lane kernels (4
    heads of 32 lanes): every row of the cut row axis, none of the
    other's."""
    seg = jnp.asarray(np.stack([segments([1024], 1024),
                                segments([128, 100, 60, 128, 90], 1024)]))
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 1024, 128)), jnp.float32)
               for _ in range(3))
    rotary, _ = rotary_of(rng, 2, 1024, 32)
    with jax.default_matmul_precision("highest"):
        got = fs.flash_segment(q, k, v, seg, 4, block=BLOCK,
                               max_segment_rows=128, rotary=rotary)[0]
        want = fs.segment_attention_xla(
            turned(q, rotary, 4), turned(k, rotary, 4), v, seg, 4,
            block=BLOCK)
    assert np.isnan(np.asarray(got[0])).all()
    np.testing.assert_allclose(got[1], want[1], atol=2e-6, rtol=2e-6)


# -- rope over two axes -------------------------------------------------------

def test_rope_over_two_axes_is_the_complex_product():
    """The op on (row, column) positions of merge-ordered patches
    against the reference's complex product on the row-major grid."""
    h, w, heads, d = 6, 10, 2, 72
    rng = np.random.default_rng(0)
    yx = family.merge_order(h, w)
    x = jnp.asarray(rng.normal(size=(h * w, heads, d)), jnp.float32)
    want = ref.rope_two_axes(x, h, w)                  # row-major rows
    order = yx[:, 0] * w + yx[:, 1]
    got = get_op_impl("rope")(
        OpContext(jax.random.PRNGKey(0), 0),
        {"X": [x[order].reshape(1, h * w, heads * d)],
         "Positions": [jnp.asarray(yx)[None]]},
        {"n_head": heads, "theta": 10000.0, "interleave": True})["Out"][0]
    np.testing.assert_allclose(got[0].reshape(h * w, heads, d),
                               want[order], atol=2e-6, rtol=2e-6)
    with pytest.raises(NotImplementedError, match="two axes"):
        get_op_impl("rope")(
            OpContext(jax.random.PRNGKey(0), 0),
            {"X": [x.reshape(1, h * w, heads * d)],
             "Positions": [jnp.asarray(yx)[None]]},
            {"n_head": heads, "theta": 10000.0, "period": 4})


# -- the position table's taps ------------------------------------------------

@pytest.mark.parametrize("grid", [(8, 8), (4, 6), (2, 10), (16, 12)])
def test_the_taps_are_bicubic_interpolation_an_image_at_a_time(grid):
    """The collator's 16 taps a patch through the `table_interp` op
    against the reference's bicubic resize of the (8, 8, D) table to the
    image's grid; the identity at the table's own grid; and the
    gradient of the table against autodiff of the resize."""
    h, w = grid
    rng = np.random.default_rng(h)
    table = jnp.asarray(rng.normal(size=(8, 8, 24)), jnp.float32)
    yx = family.merge_order(h, w)
    taps, weights = family.bicubic_taps(yx, h, w, 8, 8)
    op = get_op_impl("table_interp")

    def system(table):
        return op(OpContext(jax.random.PRNGKey(0), 0),
                  {"Table": [table.reshape(64, 24)],
                   "Taps": [jnp.asarray(taps)[None]],
                   "Weights": [jnp.asarray(weights)[None]]}, {})["Out"][0][0]

    def reference(table):
        return ref.interpolated_table(table, h, w)[yx[:, 0], yx[:, 1]]

    ct = jnp.asarray(rng.normal(size=(h * w, 24)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, back = jax.vjp(system, table)
        want, back_ref = jax.vjp(reference, table)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
        np.testing.assert_allclose(back(ct)[0], back_ref(ct)[0], atol=5e-6,
                                   rtol=5e-6)
    if grid == (8, 8):
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(table)[yx[:, 0], yx[:, 1]])
        assert ((weights == 1).sum(axis=1) == 1).all()
        assert ((weights != 0).sum(axis=1) == 1).all()


# -- the merge ----------------------------------------------------------------

def test_the_merge_puts_the_rows_at_the_placeholders_and_scatters_back():
    rng = np.random.default_rng(0)
    tokens = np.array([[5, 0, 0, 7, 0, 9, 3, 0], [0, 4, 4, 0, 0, 0, 2, 1]])
    x = jnp.asarray(rng.normal(size=(2, 8, 6)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(2, 5, 6)), jnp.float32)
    op = get_op_impl("image_merge")

    def system(x, rows):
        return op(OpContext(jax.random.PRNGKey(0), 0),
                  {"X": [x], "Rows": [rows], "Tokens": [jnp.asarray(tokens)]},
                  {"placeholder": 0})["Out"][0]

    def scatter(x, rows):       # x[mask] = rows, a sequence at a time
        for n in range(2):
            where, = np.nonzero(tokens[n] == 0)
            x = x.at[n, where].set(rows[n, :len(where)])
        return x

    ct = jnp.asarray(rng.normal(size=(2, 8, 6)), jnp.float32)
    got, back = jax.vjp(system, x, rows)
    want, back_ref = jax.vjp(scatter, x, rows)
    np.testing.assert_array_equal(got, want)
    for g, w in zip(back(ct), back_ref(ct)):
        np.testing.assert_array_equal(g, w)
    # the fifth row of the first sequence has no placeholder: no gradient
    assert not np.asarray(back(ct)[1])[0, 4].any()
