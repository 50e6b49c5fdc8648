"""Flash attention under the block-diffusion training mask (Pallas, TPU):
`pallas_flash_attention(block_diffusion=B)`'s kernels, on a grid whose
steps are a scalar-prefetched LIST OF VISITS.

The rows are a clean half and a noised half of T / 2 positions each, cut
into blocks of B (`_DiffusionBand`).  Of a head's (2 n)^2 square tiles
few hold a pair the mask allows (80 of 256 at 2 x 8192 rows in 1024 x
1024 tiles), in two runs a query tile and two a key tile, and what the
mask leaves of a tile differs: a rectangle of (tiles) x (the longest
run) takes 2.5 grid steps for every tile it computes, and a noised tile
against itself is computed whole for its diagonal of B x B blocks.  So
the three kernels (`flash_block_diffusion_fwd`, `_dkv`, and `_dq` past
`flash_attention.band_backward_fits`; the names are the benchmark's
closed list) walk ONE int32 table a pass, made on the host from the
shape when the call is traced (`_DiffusionBand.visits`): a column a
visit, with its query tile, its key tile, FIRST / LAST of its run, the
dq tile the output holds and a KIND that says how much of the tile to
compute.  No option chooses a grid or a kind.  A pass is jitted on its
shapes and its geometry, so a program's layers share one trace and one
lowering of it (three branches a kernel, one unrolled eight times).

The tile arithmetic is `flash_attention.py`'s (`_softmax_step`,
`_bwd_p_ds`, `_add_dk_dv`, `_add_dq`), which also serves the plain,
grouped and window calls of every other cell: their grids are rectangles
with no empty run worth a table, so the shells and the `pallas_call`s
here are this geometry's own and their steps are not touched.  The
declared cost stays the band's (`_Band.cost_estimate` over the pairs the
MASK allows), the VMEM rules `_vmem_params` / `_fwd_vmem_params`, the
tile sizes `flash_attention.DEFAULT_DIFFUSION_BLOCK` / `_BWD_BLOCK`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import flash_attention as fa, keep_residuals
from .flash_attention import (
    NEG_INF, _Band, _add_dk_dv, _add_dq, _bwd_p_ds, _dot, _fwd_vmem_params,
    _init_softmax, _pallas_call, _softmax_step, _vmem_params, _write_o_lse)

# Rows of a visit table (scalar prefetch, a column a grid step), and
# what a visit computes of its tile: all of it, all of it under the mask
# by block id, or the squares on a noised tile's diagonal alone
V_Q, V_K, V_HEAD, V_KIND, V_FIRST, V_LAST, V_DQ, V_DQ_FIRST, V_DQ_LAST = \
    range(9)
FULL, DIAGONAL, OWN_BLOCKS = range(3)
LANES = 128


class _DiffusionBand(_Band):
    """The block geometry of block-diffusion training (Arriola et al.,
    arXiv:2503.09573) over `t` = 2 L rows, the clean half x_0 FIRST and
    the noised half x_t after it, row L + p standing at position p.
    With blk(p) = p // block_length, a row reads

        clean  -> clean :  blk(s) <= blk(r)   (block-causal, a block whole)
        noised -> clean :  blk(s) <  blk(r)   (the clean prefix)
        noised -> noised:  blk(s) == blk(r)   (its own block, both ways)
        clean  -> noised:  never

    Tiles are square, a whole number of blocks, `n` a half.  A clean
    query tile meets clean tiles 0 .. its own; a noised one (position
    tile qp) clean tiles 0 .. qp (the last holds its strictly earlier
    blocks: none where a tile is ONE block, `own` = 0, and it is then
    not met) and its own noised tile.  The kernels' grids walk a LIST
    of these tiles (`visits`), and a visit says what it computes: a
    tile before the query tile's position whole (`FULL`), one at its
    position under the mask by block id (`DIAGONAL`), a noised tile
    against itself the `sub` x `sub` squares on its diagonal alone
    (`OWN_BLOCKS`: they hold every allowed pair; an eighth of the tile
    at 1024 / 128).  `sub` is the smallest multiple of 128 lanes that
    whole blocks fill and that cuts the tile; where that is the tile
    itself the visit is `DIAGONAL`.  From the shape alone."""

    window = None
    prefix = "flash_block_diffusion_"

    def __init__(self, t, block, block_length):
        self.t, self.block_length = t, block_length
        self.block_q = self.block_k = block
        self.n = n = t // 2 // block
        self.nq = self.nk = 2 * n
        self.own = int(block_length < block)
        side = math.lcm(LANES, block_length)
        self.sub = side if block % side == 0 else block
        self.blocks_allowed = n * (n + 1) // 2 + n * (n - 1) // 2 \
            + n * self.own + n

    def _shape(self):       # a static argument of the jitted passes
        return self.t, self.block_q, self.block_length, self.sub

    def __eq__(self, other):
        return self._shape() == other._shape()

    def __hash__(self):
        return hash(self._shape())

    def tiles(self):
        """(query tile, key tile, kind) of every tile that holds an
        allowed pair, a query tile's in a row."""
        n, found = self.n, []
        for qb in range(2 * n):
            clean = qb + 1 if qb < n else qb - n + self.own
            found += [(qb, kb, FULL if self.interior(qb, kb) else DIAGONAL)
                      for kb in range(clean)]
            if qb >= n:
                found.append((qb, qb, OWN_BLOCKS if self.sub < self.block_q
                              else DIAGONAL))
        return found

    def visits(self, key_major=False, group=1):
        """The int32 (9, V) table a grid's last axis walks, from the
        shape, on the host: query-major (the forward, `_dq`) or
        key-major (the backward; with `group` each key tile meets its
        query tiles once a head of the group, `V_HEAD`: the kernel that
        holds tiles only).  `V_FIRST` / `V_LAST` bracket the run of one
        (major tile, head), `V_DQ_FIRST` / `V_DQ_LAST` a query tile's
        visits; `V_DQ` is the dq tile the output's index map holds, the
        last one completed (before any is, the first to be): it moves
        on only on the step that writes the next tile, so no half-summed
        tile is ever what Pallas writes back."""
        rows = sorted(
            ((qb, kb, head, kind) for head in range(group)
             for qb, kb, kind in self.tiles()),
            key=lambda r: (r[1], r[2], r[0]) if key_major else r[:2])
        q, k, head, kind = np.array(rows, np.int32).T
        run = (k if key_major else q) * group + head
        first = np.r_[True, run[1:] != run[:-1]]
        at = np.arange(q.size)
        met = [np.flatnonzero(q == qb) for qb in range(self.nq)]
        dq_first = np.isin(at, [m[0] for m in met])
        dq_last = np.isin(at, [m[-1] for m in met])
        done = np.maximum.accumulate(np.where(dq_last, at, -1))
        dq = q[np.where(done < 0, np.flatnonzero(dq_last)[0], done)]
        return np.stack([q, k, head, kind, first, np.r_[first[1:], True],
                         dq, dq_first, dq_last]).astype(np.int32)

    def record_blocks(self):
        """One traced pass, a head's: grid steps, tiles computed, tiles
        that hold an allowed pair; allowed pairs, entries computed."""
        from ...observe.monitoring import runtime_stats

        kinds = [kind for _, _, kind in self.tiles()]
        entries = sum(self.block_q * (self.sub if kind == OWN_BLOCKS
                                      else self.block_q) for kind in kinds)
        runtime_stats.record_flash_block_diffusion(
            len(kinds), self.blocks_allowed, steps=len(kinds),
            pairs=self.pairs(), entries=entries)

    def interior(self, qb, kb):
        """Whether every pair of tile (qb, kb) is allowed: a key tile
        before the query tile's own position."""
        return kb % self.n < qb % self.n

    def _ahead(self, side, q_axis):
        """blk(r) - blk(s) over a square of `side` rows from a block's
        edge, queries along `q_axis`."""
        def blk(axis):
            i = jax.lax.broadcasted_iota(jnp.int32, (side, side), axis)
            b = self.block_length
            if b & (b - 1) == 0:
                return jax.lax.shift_right_logical(i, b.bit_length() - 1)
            return jax.lax.div(i, b)

        return blk(q_axis) - blk(1 - q_axis)

    def allowed(self, qb, kb, q_axis):
        """The mask of a tile at the query tile's own position, from
        local block ids; queries along `q_axis` of the square tile."""
        n, ahead = self.n, self._ahead(self.block_q, q_axis)
        # clean -> clean: >= 0; noised -> clean: >= 1; noised -> noised: 0
        least = jnp.where((qb >= n) & (kb < n), 1, 0)
        return (ahead >= least) & ((kb < n) | (ahead == 0))

    def visit(self, visits, v, q_axis, compute):
        """Run `compute(mask, at)` as visit `v`'s kind says: rows `at`
        of the tile's two sides, under the masks in `mask`."""
        from jax.experimental import pallas as pl

        kind, sub = visits[V_KIND, v], self.sub
        pl.when(kind == FULL)(lambda: compute([], slice(None)))
        pl.when(kind == DIAGONAL)(lambda: compute(
            [self.allowed(visits[V_Q, v], visits[V_K, v], q_axis)],
            slice(None)))
        if sub == self.block_q:
            return

        @pl.when(kind == OWN_BLOCKS)
        def _own_blocks():
            mask = [self._ahead(sub, q_axis) == 0]
            # unrolled: the squares share nothing, and a rolled loop
            # runs their short chains one after the other (3.1 us a
            # visit forward, a whole tile's, for 1.1: PERF.md, PR 59)
            jax.lax.fori_loop(
                0, self.block_q // sub,
                lambda i, _: compute(mask, pl.ds(pl.multiple_of(i * sub, sub),
                                                 sub)), None, unroll=True)

    def pairs(self):
        half, b = self.t // 2, self.block_length
        blocks = half // b
        return half * b + b * b * (blocks * (blocks - 1) // 2
                                   + blocks * (blocks + 1) // 2)


def _diffusion_fwd_kernel(visits, q_ref, k_ref, v_ref, o_ref, lse_ref,
                          m_scr, l_scr, acc_scr, *, scale, band):
    """The forward pass under the block-diffusion mask, query-major:
    `_fwd_kernel`'s online soft-max, a visit a grid step."""
    from jax.experimental import pallas as pl

    v = pl.program_id(2)
    pl.when(visits[V_FIRST, v] == 1)(functools.partial(
        _init_softmax, m_scr, l_scr, acc_scr))

    def _compute(mask, at):
        s = _dot(q_ref[0, at], k_ref[0, at], ((1,), (1,))) * scale
        for allowed in mask:
            s = jnp.where(allowed, s, NEG_INF)
        _softmax_step(s, lambda: v_ref[0, at], m_scr, l_scr, acc_scr, at)

    band.visit(visits, v, 0, _compute)
    pl.when(visits[V_LAST, v] == 1)(functools.partial(
        _write_o_lse, o_ref, lse_ref, m_scr, l_scr, acc_scr))


def _diffusion_bwd_kernel(visits, q_ref, k_ref, v_ref, do_ref, o_ref,
                          lse_ref, *, band, group, fused, dq_ref=None,
                          dk_ref=None, dv_ref=None, dq_acc=None, dk_acc=None,
                          dv_acc=None, **dims):
    """The backward pass under the block-diffusion mask: p and ds once
    a visit and, of dq, dk and dv, the sums it was given.  `fused`
    (`_bwd_band_kernel`'s layout, key-major): all three, whole sequences
    in VMEM, a dq tile leaving on the visit that completes it, dk and dv
    during the group's last head.  Past that budget a kernel holds ONE
    tile of each: dk and dv key-major, the group's heads inside a key
    tile's run (`V_HEAD`), and dq query-major."""
    from jax.experimental import pallas as pl

    v = pl.program_id(2)
    gi = pl.program_id(1) + visits[V_HEAD, v]
    qb, kb = (visits[V_Q, v], visits[V_K, v]) if fused else (0, 0)

    def zero(acc, at):
        acc[at] = jnp.zeros(acc.shape[1:], acc.dtype)

    if dk_acc is not None:
        @pl.when((gi == 0) & (visits[V_FIRST, v] == 1))
        def _init():
            zero(dk_acc, kb)
            zero(dv_acc, kb)

    if dq_acc is not None:
        pl.when(visits[V_DQ_FIRST, v] == 1)(lambda: zero(dq_acc, qb))

    def _compute(mask, at):
        refs = [r.at[:, at] for r in (q_ref, k_ref, v_ref, do_ref, o_ref)]
        q, k, do, p, ds = _bwd_p_ds(
            *refs, lse_ref.at[:, :, at], None, None, None, kb, qb, mask=mask,
            **dims)
        if dk_acc is not None:
            _add_dk_dv(p, ds, q, do, dk_acc, dv_acc, dims["scale"],
                       at=(kb, at))
        if dq_acc is not None:
            _add_dq(ds, k, dq_acc, dims["scale"], at=(qb, at))

    band.visit(visits, v, 1, _compute)

    if dk_acc is not None:
        @pl.when((gi == group - 1) & (visits[V_LAST, v] == 1))
        def _finalize():
            dk_ref[0] = dk_acc[kb].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[kb].astype(dv_ref.dtype)

    if dq_acc is not None:
        @pl.when(visits[V_DQ_LAST, v] == 1)
        def _finalize_dq():
            dq_ref[0] = dq_acc[qb].astype(dq_ref.dtype)


def _diffusion_call(kernel, name, band, table, hkv, group, outs, scratch,
                    operands, **params):
    """One kernel over the grid (N*Hkv, the group's heads, the visits of
    `table`; a table with a `V_HEAD` holds the heads itself).  `outs`:
    (what, shape) pairs, `what` a tile at the table's `V_Q` ("q"), at
    `V_DQ` ("dq"), at `V_K` ("kv"; "kv_last": during the group's last
    head alone, when dk and dv of the single kernel leave, tile by tile)
    or the statistic ("stat")."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q = operands[0]
    b, d = band.block_q, q.shape[2] // (hkv * group)

    def head(g, a, v, visits):
        return (g % hkv) * group + a + visits[V_HEAD, v]

    def q_tile(row):
        return pl.BlockSpec((1, b, d), lambda g, a, v, visits: (
            g // hkv, visits[row, v], head(g, a, v, visits)))

    def kv_tile(last):
        return pl.BlockSpec((1, b, d), lambda g, a, v, visits: (
            g // hkv, jnp.where(a == group - 1, visits[V_K, v], 0) if last
            else visits[V_K, v], g % hkv))

    spec = {"q": q_tile(V_Q), "dq": q_tile(V_DQ), "kv": kv_tile(False),
            "kv_last": kv_tile(True),
            "stat": pl.BlockSpec((1, 8, b), lambda g, a, v, visits: (
                g * group + a + visits[V_HEAD, v], 0, visits[V_Q, v]))}
    ins = ["q", "kv", "kv", "q", "q", "stat"][:len(operands)]
    return _pallas_call(
        kernel, name=band.prefix + name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(q.shape[0] * hkv, group // (int(table[V_HEAD].max()) + 1),
                  table.shape[1]),
            in_specs=[spec[what] for what in ins],
            out_specs=[spec[what] for what, _ in outs],
            scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                            for shape in scratch]),
        out_shape=[shape for _, shape in outs], **params,
    )(table, *operands)


# (jitted, as `grouped_matmul.py`'s kernels are: a program's layers
# share their shapes, so a pass is traced and lowered once a shape and
# called from every layer)
@functools.partial(jax.jit, static_argnames=("scale", "band", "n_head",
                                             "group"))
def _flash_fwd_diffusion(q, k, v, *, scale, band, n_head, group):
    n, t, hd = q.shape
    b, d = band.block_q, hd // n_head
    return _diffusion_call(
        functools.partial(_diffusion_fwd_kernel, scale=scale, band=band),
        "fwd", band, band.visits(), n_head // group, group,
        [("q", jax.ShapeDtypeStruct(q.shape, q.dtype)),
         ("stat", jax.ShapeDtypeStruct((n * n_head, 8, t), jnp.float32))],
        [(b, 1), (b, 1), (b, d)], (q, k, v),
        **band.cost_estimate("fwd", n * n_head, d, q.dtype.itemsize, group),
        **_fwd_vmem_params(b, b, d, q.dtype.itemsize))


@functools.partial(jax.jit, static_argnames=("scale", "band", "n_head",
                                             "group", "fused"))
def _flash_bwd_diffusion(q, k, v, o, lse8, do, *, scale, band, n_head, group,
                         fused):
    """(dq, dk, dv) under the block-diffusion mask: one kernel where
    `fused` (`band_backward_fits`), `_dkv` and `_dq` that hold tiles
    only beyond; every term from `_bwd_p_ds`, added in the same order."""
    n, t, hd = q.shape
    b, d = band.block_q, hd // n_head
    dk_shape = jax.ShapeDtypeStruct(k.shape, q.dtype)
    shape = {"dq": jax.ShapeDtypeStruct(q.shape, q.dtype), "dk": dk_shape,
             "dv": dk_shape}

    def call(name, cost, parts, table, accumulators):
        names = [part + kind for kind in ("_ref", "_acc") for part in parts]

        def kern(visits, *refs):
            _diffusion_bwd_kernel(
                visits, *refs[:6], band=band, group=group, fused=fused,
                scale=scale, causal=False, block_q=b, block_k=b, t_q=t,
                t_k=t, **dict(zip(names, refs[6:])))

        return _diffusion_call(
            kern, name, band, table, n_head // group, group,
            [("dq" if part == "dq" else "kv_last" if fused else "kv",
              shape[part]) for part in parts],
            [(band.nq if fused else 1, b, d)] * len(parts),
            (q, k, v, do, o, lse8),
            **band.cost_estimate(cost, n * n_head, d, q.dtype.itemsize,
                                 group),
            **_vmem_params(accumulators, b, b))

    if fused:
        return tuple(call("dkv", "bwd", ("dq", "dk", "dv"),
                          band.visits(key_major=True), 3 * t * d * 4))
    dk, dv = call("dkv", "dkv", ("dk", "dv"),
                  band.visits(key_major=True, group=group), 0)
    dq, = call("dq", "dq", ("dq",), band.visits(), 0)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, tiles, n_head, group, length):
    return _flash_fwd(q, k, v, scale, tiles, n_head, group, length)[0]


def _flash_fwd(q, k, v, scale, tiles, n_head, group, length):
    # the counters count the calls traced, a layer's each, whichever of
    # them the jitted pass is traced for
    band = _DiffusionBand(q.shape[1], tiles[0], length)
    band.record_blocks()
    o, lse8 = keep_residuals(*_flash_fwd_diffusion(
        q, k, v, scale=scale, band=band, n_head=n_head, group=group))
    return o, (q, k, v, o, lse8)


def _flash_bwd(scale, tiles, n_head, group, length, res, do):
    from ...observe.monitoring import runtime_stats

    q, k, v, o, lse8 = res
    fused = fa.band_backward_fits(q.shape[1], q.shape[2] // n_head)
    runtime_stats.record_flash_backward("flash_attention", fused)
    band = _DiffusionBand(q.shape[1], tiles[1], length)
    band.record_blocks()
    dq, dk, dv = _flash_bwd_diffusion(
        q, k, v, o, lse8, do, scale=scale, band=band, n_head=n_head,
        group=group, fused=fused)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _diffusion_blocks(t, length, block=None):
    """(forward tile, backward tile) of a call under the block-diffusion
    mask over `t` rows in blocks of `length`: a size given holds for
    both passes; else each pass's own, or its half or its quarter where
    that is the largest that cuts a half into whole tiles of whole
    blocks (a half under a tile is one tile)."""
    half = t // 2

    def tile(own):
        sizes = [min(own >> halvings, half) for halvings in range(3)]
        return next((b for b in sizes
                     if b and half % b == 0 and b % length == 0), sizes[0])

    return tuple(min(int(block), half) if block else tile(own) for own in
                 (fa.DEFAULT_DIFFUSION_BLOCK, fa.DEFAULT_DIFFUSION_BWD_BLOCK))


def block_diffusion_takes(t, length):
    """Whether the kernels run `t` rows under the block-diffusion mask
    at one of their own tiles: each half a whole number of tiles, a
    tile a whole number of blocks of `length`.  From the shape alone;
    another shape runs the XLA lowering under the explicit mask."""
    return t % 2 == 0 and length >= 1 and all(
        b and (t // 2) % b == 0 and b % length == 0
        for b in _diffusion_blocks(t, length))


def flash_block_diffusion(q, k, v, scale, h, hkv, length, block_q, block_k,
                          bare):
    """The head-major call under the block-diffusion mask, or the
    reason it is not one.  `bare`: nothing beside q, k, v came with it
    (a bias, offsets, a returned logsumexp, a causal mask or a window)."""
    n, t, hd = q.shape
    d = hd // h
    if length < 1:
        raise ValueError(f"block_diffusion {length} is no block length")
    if block_q != block_k:
        raise NotImplementedError(
            f"the block-diffusion mask's tiles are square; got blocks "
            f"{block_q} x {block_k}")
    blocks = _diffusion_blocks(t, length, block_q)
    if (not bare or k.shape[1] != t or t % 2
            or any(b < 1 or (t // 2) % b or b % length for b in blocks)):
        raise NotImplementedError(
            f"flash attention under the block-diffusion mask is "
            f"self-attention over a clean and a noised half of whole "
            f"blocks each, a tile a whole number of blocks of "
            f"{length}, with no bias, offsets, returned logsumexp, "
            f"window or causal mask beside its own; got T_q {t}, T_k "
            f"{k.shape[1]}, tiles {blocks}")
    return _flash(q, k, v, float(d ** -0.5 if scale is None else scale),
                  blocks, int(h), int(h // hkv), length)
