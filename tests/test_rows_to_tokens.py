"""Rows -> tokens (`ops/pallas/rows_to_tokens.py`, interpret mode): the
one new primitive of a share-holding expert layer's sorted-row section,
against the composition it replaces (`ops/moe_dropless.py _pairs_rows`:
a (T, k, D) array gathered out of the R-row buffer and summed over k),
forward and gradient, and the section's two `custom_vjp`s on it
against the composition's.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops import moe_dropless
from paddle_tpu.ops.pallas import rows_to_tokens as rt

T, K, E, HELD = 256, 4, 16, 4
BF16, F32 = jnp.bfloat16, jnp.float32


def routing(experts, rows, held=HELD, e=E):
    """What the op hands its sorted-row section for `experts` (T, k):
    (head (rows,), back (T, k), n), the held experts 0..held-1."""
    flat = np.asarray(experts, np.int32).reshape(-1)
    order = np.argsort(flat % e, kind="stable").astype(np.int32)
    back = np.argsort(order).astype(np.int32).reshape(experts.shape)
    n = int((flat < held).sum())
    assert n <= rows
    return jnp.asarray(order[:rows]), jnp.asarray(back), n


def uniform(seed, t=T, k=K, e=E):
    r = np.random.default_rng(seed)
    return np.stack([r.permutation(e)[:k] for _ in range(t)])


def steered(seed):
    """Token 0 holds no row, token 1 one, token 2 all k; the tokens of
    the second tile all meet held expert 0 (one range as long as the
    tile) and tile 1's rows are nobody else's."""
    experts = uniform(seed)
    experts[0] = np.arange(HELD, HELD + K)
    experts[1] = [0] + list(range(HELD + 1, HELD + K))
    experts[2] = np.arange(K)
    experts[128:, 0] = 0
    experts[128:, 1:] = np.arange(HELD, HELD + K - 1)
    return experts


def rows_of(seed, rows, d, dtype, garbage_from=None):
    r = np.random.default_rng(seed)
    vals = r.normal(size=(rows, d)).astype(np.float32)
    if garbage_from is not None:
        vals[garbage_from:] = 1e4 * r.normal(size=vals[garbage_from:].shape)
    return jnp.asarray(vals, dtype)


def both(vals, weights, head, back, n, **tiles):
    """(the kernel's, the composition's) weighted sum a token."""
    t, k = back.shape
    c = None if weights is None else weights.reshape(-1)[head]
    order = rt.token_order(head // k, n, t, c, **tiles)
    got = rt.rows_to_tokens(vals, order, t, weighted=c is not None,
                            token_tile=tiles.get("token_tile",
                                                 rt.TOKEN_TILE))
    yk = moe_dropless._pairs_rows(vals, back, n).astype(F32)
    if weights is not None:
        yk = yk * weights[..., None]
    return got, jnp.sum(yk, axis=1)


@pytest.mark.parametrize("weighted", [True, False], ids=["weights", "plain"])
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("d", [2048, 2304])
def test_the_kernel_sums_what_the_composition_gathers(d, dtype, weighted):
    """At both cells' widths, in both dtypes, with the float32 routing
    weights and without: a float32 sum of the same pairs (bf16 rows meet
    an exact three-way split of the weights, so the products are not
    even rounded)."""
    head, back, n = routing(uniform(d), 512)
    vals = rows_of(1, 512, d, dtype)
    w = jnp.asarray(np.random.default_rng(2).uniform(0.01, 1, (T, K)), F32)
    got, want = both(vals, w if weighted else None, head, back, n)
    assert got.shape == (T, d) and got.dtype == F32
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("case", ["none", "every", "garbage", "all_pairs"])
def test_only_the_rows_below_n_count(case):
    """n = 0 (zeros), n = R (every row of the buffer real), n < R with
    garbage past n (what no group's matmul wrote), and the buffer of
    all T x k pairs."""
    d = 256
    if case == "none":
        experts, rows = uniform(3) % (E - HELD) + HELD, 512
    elif case == "every":
        experts, rows = uniform(4), None
    elif case == "garbage":
        experts, rows = uniform(5), 512
    else:
        experts, rows = uniform(6), T * K
    n = int((experts < HELD).sum())
    rows = rows or n // 128 * 128
    if case == "every":         # as many held pairs as the buffer has rows
        flat = experts.reshape(-1)
        flat[np.flatnonzero(flat < HELD)[rows:]] = E - 1
    head, back, n = routing(experts, rows)
    assert {"none": n == 0, "every": n == rows}.get(case, 0 < n < rows)
    vals = rows_of(7, rows, d, BF16, n if case == "garbage" else None)
    w = jnp.asarray(np.random.default_rng(8).uniform(0.01, 1, (T, K)), F32)
    got, want = both(vals, w, head, back, n)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    assert (np.abs(np.asarray(got)).max() > 0) == (n > 0)


@pytest.mark.parametrize("tiles", [(128, 128), (256, 128), (256, 256)])
def test_a_token_with_no_row_one_row_and_k_rows_and_the_longest_range(tiles):
    """Tokens that hold nothing are zeros, one that holds k rows is
    their sum, and a tile whose every token meets one expert reads one
    range as long as itself: at every tiling the tool times."""
    tile, chunk = tiles
    experts = steered(9)
    head, back, n = routing(experts, 512)
    vals = rows_of(10, 512, 256, F32)
    w = jnp.asarray(np.random.default_rng(11).uniform(0.01, 1, (T, K)), F32)
    got, want = both(vals, w, head, back, n, token_tile=tile,
                     row_chunk=chunk)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    got = np.asarray(got)
    assert not got[0].any() and got[1].any() and got[2].any()
    held = np.asarray(vals)[np.asarray(back)[2]] * np.asarray(w)[2][:, None]
    np.testing.assert_allclose(got[2], held.sum(0), rtol=2e-6, atol=2e-6)


def test_the_visits_are_every_tile_once_a_chunk_it_touches():
    """`token_order`: the rows by token, T for a row that does not
    count, and a table whose visits walk the tiles in order, each at
    least once, the first of a tile marked; at most T / tile + R / chunk
    of them."""
    experts = steered(12)
    head, back, n = routing(experts, 512)
    c = jnp.asarray(np.random.default_rng(19).uniform(size=512), F32)
    perm, keys, visits, count, c_t = rt.token_order(head // K, n, T, c)
    keys, visits, count = (np.asarray(keys).reshape(-1), np.asarray(visits),
                           int(count))
    assert sorted(np.asarray(perm).tolist()) == list(range(512))
    # the weights rode along with their rows; without them, None
    np.testing.assert_array_equal(np.asarray(c_t).reshape(-1),
                                  np.asarray(c)[np.asarray(perm)])
    assert rt.token_order(head // K, n, T)[4] is None
    assert (np.diff(keys) >= 0).all() and (keys[n:] == T).all()
    np.testing.assert_array_equal(
        keys[:n], np.sort(np.asarray(head)[:n] // K))
    tiles, chunks = T // rt.TOKEN_TILE, 512 // rt.ROW_CHUNK
    assert visits.shape == (3, tiles + chunks) and tiles <= count <= \
        tiles + chunks
    tile, chunk, first = visits[:, :count]
    assert sorted(set(tile.tolist())) == list(range(tiles))
    assert (np.diff(tile) >= 0).all()
    assert first.tolist() == [1] + (np.diff(tile) > 0).astype(int).tolist()
    for t in range(tiles):      # the chunks of a tile hold all its rows
        mine = np.flatnonzero(keys // rt.TOKEN_TILE == t) // rt.ROW_CHUNK
        assert set(mine.tolist()) <= set(chunk[tile == t].tolist())


def test_one_sort_of_all_the_rows_serves_every_shorter_buffer():
    """`by_token` over the T x k sorted rows, cut to a buffer by
    `order_of`, is `token_order` of that buffer: the rows that do not
    count keep their places past the ones that do."""
    experts = uniform(20)
    head, back, n = routing(experts, T * K)
    c = jnp.asarray(np.random.default_rng(21).uniform(size=T * K), F32)
    whole = rt.by_token(head // K, n, T, c)
    for rows in (512, 768, T * K):
        cut = rt.order_of(whole, rows, T)
        own = rt.token_order(head[:rows] // K, n, T, c[:rows])
        for a, b in zip(cut, own):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_the_sections_gradients_are_the_compositions(dtype):
    """`_take_rows` and `_combine_rows` against `_take_head` and
    `_combine`: the gather's gradient (a sum of a token's rows), the
    rows' gradient, and the weights' (a dot a ROW placed into (T, k))."""
    d, rows = 256, 512
    head, back, n = routing(steered(13), rows)
    r = np.random.default_rng(14)
    x = jnp.asarray(r.normal(size=(T, d)), dtype)
    ys = rows_of(15, rows, d, dtype).at[n:].set(0)
    w = jnp.asarray(r.uniform(0.01, 1, (T, K)), F32)
    ct_rows = rows_of(16, rows, d, dtype).at[n:].set(0)
    ct_out = jnp.asarray(r.normal(size=(T, d)), F32)
    of_row = w.reshape(-1)[head]
    order = rt.token_order(head // K, n, T, of_row)

    xs, pull = jax.vjp(lambda x: moe_dropless._take_rows(
        x, head // K, order), x)
    want_xs, want_pull = jax.vjp(lambda x: moe_dropless._take_head(
        x, head // K, back, n), x)
    np.testing.assert_array_equal(xs, want_xs)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == BF16 else dict(
        rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(pull(ct_rows)[0].astype(F32),
                               want_pull(ct_rows)[0].astype(F32), **tol)

    y, pull = jax.vjp(lambda ys, w: moe_dropless._combine_rows(
        ys, w, of_row, back, head // K, n, order), ys, w)
    want_y, want_pull = jax.vjp(lambda ys, w: moe_dropless._combine(
        ys, w, back, head, n), ys, w)
    np.testing.assert_allclose(y, want_y, rtol=2e-6, atol=2e-6)
    (gys, gw), (want_gys, want_gw) = pull(ct_out), want_pull(ct_out)
    assert gys.dtype == ys.dtype and gw.dtype == F32
    np.testing.assert_array_equal(gys[:n], want_gys[:n])
    np.testing.assert_allclose(gw, want_gw, rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(want_gw)).max() > 0


@pytest.mark.parametrize("d, rows, kernel", [
    (256, 512, True), (200, 512, False), (256, 500, False)],
    ids=["whole-tiles", "no-lane-group", "no-row-chunk"])
def test_the_shape_alone_chooses_and_the_counter_says_which(d, rows, kernel):
    """No flag: a width of whole 128-lane groups over whole tiles runs
    the kernel (the op sorts its rows by token for it), anything else
    keeps the composition, and `runtime_stats.share_rows_kernel` /
    `_xla` count the sections traced each way."""
    assert rt.rows_to_tokens_takes(rows, T, d) == kernel
    experts = uniform(17)
    flat = experts.reshape(-1)
    order = jnp.asarray(np.argsort(flat % E, kind="stable"), jnp.int32)
    back = jnp.argsort(order).astype(jnp.int32).reshape(T, K)
    counts = jnp.asarray(np.bincount(flat, minlength=E)[:HELD], jnp.int32)
    r = np.random.default_rng(18)
    x = jnp.asarray(r.normal(size=(T, d)), F32)
    w1, w3 = (jnp.asarray(r.normal(size=(HELD, d, 128)) * 0.1, F32)
              for _ in range(2))
    w2 = jnp.asarray(r.normal(size=(HELD, 128, d)) * 0.1, F32)
    w = jnp.asarray(r.uniform(0.01, 1, (T, K)), F32)
    before = runtime_stats.snapshot()
    ws = w.reshape(-1)[order]
    out = moe_dropless._held_rows(
        rows, x, w1, w3, w2, w, order, back, counts, None, ws,
        rt.by_token(order // K, jnp.sum(counts), T, ws) if kernel else None)
    took = runtime_stats.delta(before)
    assert (took["share_rows_kernel"], took["share_rows_xla"]) == (
        int(kernel), int(not kernel))
    assert out.shape == (T, d) and np.isfinite(np.asarray(out)).all()
