"""Flash attention under the block-diffusion training mask (Pallas, TPU):
`pallas_flash_attention(block_diffusion=B)`'s geometry, on a grid whose
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
shape when the call is traced (`_Band.visits` over this geometry's
`tiles`): a column a visit, with its query tile, its key tile, FIRST /
LAST of its run, the dq tile the output holds and a KIND that says how
much of the tile to compute.  No option chooses a grid or a kind.

The shells that walk the table, their `pallas_call`s and the jitted
passes are `flash_attention.py`'s (`_visits_fwd_kernel`,
`_visits_bwd_kernel`, `_visits_call`, `_flash_fwd_visits`,
`_flash_bwd_visits`), shared since PR 63 with the band call over the
whole causal prefix, whose rectangle left 120 of a head's 256 steps
empty at 16384 rows; here live the geometry (which tiles, of which
kind, under which mask; the third kind, `OWN_BLOCKS`), the counters and
the call.  The tile arithmetic is that file's too (`_softmax_step`,
`_bwd_p_ds`, `_add_dk_dv`, `_add_dq`), as for the plain and window
calls, whose grids have no empty run worth a table.  The declared cost
stays the band's (`_Band.cost_estimate` over the pairs the MASK
allows), the VMEM rules `_vmem_params` / `_fwd_vmem_params`, the tile
sizes `flash_attention.DEFAULT_DIFFUSION_BLOCK` / `_BWD_BLOCK`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import flash_attention as fa, interpret, keep_residuals
from .flash_attention import (
    DIAGONAL, FULL, V_KIND, _Band, _flash_bwd_visits, _flash_fwd_visits)

# the third kind of visit: the squares on a noised tile's diagonal alone
OWN_BLOCKS = 2
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
        return type(self), self.t, self.block_q, self.block_length, self.sub

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
        """The band's two kinds, and the third where a side cuts the
        tile: the `sub` x `sub` squares on its diagonal, one after the
        other."""
        from jax.experimental import pallas as pl

        super().visit(visits, v, q_axis, compute)
        sub = self.sub
        if sub == self.block_q:
            return

        @pl.when(visits[V_KIND, v] == OWN_BLOCKS)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, tiles, n_head, group, length):
    return _flash_fwd(q, k, v, scale, tiles, n_head, group, length)[0]


def _flash_fwd(q, k, v, scale, tiles, n_head, group, length):
    # the counters count the calls traced, a layer's each, whichever of
    # them the jitted pass is traced for
    band = _DiffusionBand(q.shape[1], tiles[0], length)
    band.record_blocks()
    o, lse8 = keep_residuals(*_flash_fwd_visits(
        q, k, v, scale=scale, band=band, n_head=n_head, group=group,
        interpret=interpret()))
    return o, (q, k, v, o, lse8)


def _flash_bwd(scale, tiles, n_head, group, length, res, do):
    from ...observe.monitoring import runtime_stats

    q, k, v, o, lse8 = res
    fused = fa.band_backward_fits(q.shape[1], q.shape[2] // n_head)
    runtime_stats.record_flash_backward("flash_attention", fused)
    band = _DiffusionBand(q.shape[1], tiles[1], length)
    band.record_blocks()
    dq, dk, dv = _flash_bwd_visits(
        q, k, v, o, lse8, do, scale=scale, band=band, n_head=n_head,
        group=group, fused=fused, interpret=interpret())
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
