"""The kernel families `tools/time_kernel.py` times: one `Family` a
kernel file of `paddle_tpu/ops/pallas/`, found by name in `FAMILIES`.

A family says at which shape each cell of `BENCHMARK.json` runs it
(`cells`; the first is the tool's default), how to draw its operands
(`operands(shape, seed)` -> the differentiable ones as a tuple, and a
dict of whatever else the ways read; `parts` where one of them is
several side by side), and the WAYS one call can be
made: builders `way(mod, shape, aux, **keywords)` -> a function of the
operands, where `mod` is the kernel file's module (this tree's, or a
parent checkout's under `--parent`).  `ways` all compute the same
thing: `kernel` first, then the composition the module keeps as its
fall-back (`xla` / `view`), which the others are held against;
`composites` are a cell's layer around the kernel, timed and compared
with nothing.  `sweepable` lists what `--sweep` may set: a module
constant of the kernel file (UPPER CASE) or a keyword of the `kernel`
builder.  A kernel PR that changes an entry's signature repairs its
builder here; `tests/test_time_kernel.py` fails until it does.
"""

from __future__ import annotations

import functools
import inspect
import os
import sys
from typing import Callable, NamedTuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

BF16, F32 = jnp.bfloat16, jnp.float32


class Family(NamedTuple):
    module: str                 # paddle_tpu.ops.pallas.<module>
    cells: dict                 # workload -> the shape it runs the family at
    operands: Callable          # (shape, seed) -> (xs, aux)
    names: tuple                # the operands' names: xs, in order
    ways: dict                  # name -> builder; kernel, the fall-back, ...
    composites: dict = {}       # name -> builder: a layer around the kernel
    sweepable: tuple = ()       # constants and keywords `--sweep` may set
    # shape -> {operand: {label: (first lane, end)}}: an operand that is
    # several side by side, whose gradient is held to the fall-back's a
    # part at a time
    parts: Callable = lambda shape: {}


def _keys(seed, n):
    return jax.random.split(jax.random.PRNGKey(seed), n)


def _normal(key, shape, dtype=BF16):
    return jax.random.normal(key, shape, F32).astype(dtype)


def _log_uniform(key, shape, low, high):
    return jnp.exp(jax.random.uniform(key, shape, F32, np.log(low),
                                      np.log(high)))


def _with(entry, **fixed):
    """A builder that calls `mod.<entry>(*xs, **fixed)`."""
    return lambda mod, shape, aux: functools.partial(
        getattr(mod, entry), **fixed)


# -- the delta rules --------------------------------------------------------

def _unit(key, shape):
    x = jax.random.normal(key, shape, F32)
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def _channel_delta_operands(shape, seed):
    t, heads, d = shape["rows"], shape["heads"], 128
    ks = _keys(seed, 5)
    q, k = (_unit(key, (1, t, heads, d)).reshape(1, t, heads * d)
            for key in ks[:2])
    # a decay a lane between ~1e-3 and ~3 a position
    g = -_log_uniform(ks[3], (1, t, heads * d), 1e-3, 3.0)
    beta = jax.nn.sigmoid(_normal(ks[4], (1, t, heads), F32))
    return ((q * d ** -0.5).astype(BF16), k.astype(BF16),
            _normal(ks[2], (1, t, heads * d)), g, beta), {}


def _gated_delta_operands(shape, seed):
    """QKV as the convolved projection writes it (rows of any norm: the
    rule takes q's and k's l2norm), the log decay and beta."""
    t, hk, d = shape["rows"], shape["key_heads"], 128
    ks = _keys(seed, 3)
    g = -jnp.abs(_normal(ks[1], (1, t, 2 * hk), F32)) * 0.05
    beta = jax.nn.sigmoid(_normal(ks[2], (1, t, 2 * hk), F32))
    return (_normal(ks[0], (1, t, 4 * hk * d)), g, beta), {}


def _gated_delta_parts(shape):
    lanes = shape["key_heads"] * 128
    return {"qkv": {"q": (0, lanes), "k": (lanes, 2 * lanes),
                    "v": (2 * lanes, 4 * lanes)}}


def _gated_delta(use_kernel):
    """The rule as `ops/decoder.py gated_delta_rule` calls it, QKV in
    and Out (N, T, Hv x 128) out: the kernels and whatever XLA runs
    between them.  A checkout from before PR 72 has v cut out of QKV
    for it and hands back o a head at a time."""
    def build(mod, shape, aux):
        hk, d = shape["key_heads"], 128
        raw = mod.RawQK(q=0, k=hk * d, heads=hk, dim=d)

        def fn(qkv, g, beta):
            n, t, _ = qkv.shape
            if "v" in mod.RawQK._fields:
                k, v, at = None, None, raw._replace(v=2 * hk * d)
            else:
                k, at = qkv, raw
                v = qkv[..., 2 * hk * d:].reshape(n, t, 2 * hk, d)
            return mod.gated_delta_rule(
                qkv, k, v, g, beta, use_kernel=use_kernel,
                raw=at).reshape(n, t, 2 * hk * d)
        return fn
    return build


def _channel_delta_xla(mod, shape, aux):
    """The module's XLA lowering a PAIR of heads at a time (heads are
    independent): whole, at 8192 rows x 32 heads, its own dk and dg come
    out wrong on the chip (PERF.md section 7, "From PR 71"), and a pair
    at a time it agrees with the scan over positions."""
    def fn(q, k, v, g, beta):
        @jax.checkpoint
        def pair(p):
            cut = lambda x, lanes: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                x, p * lanes, lanes, axis=2)
            return mod.channel_delta_rule(
                cut(q, 256), cut(k, 256), cut(v, 256), cut(g, 256),
                cut(beta, 2), use_kernel=False)

        o = jax.lax.map(pair, jnp.arange(shape["heads"] // 2))
        return jnp.moveaxis(o, 0, 2).reshape(q.shape)   # (P, N, T, 256)
    return fn


# -- the flash kernels ------------------------------------------------------

def _attention_operands(shape, seed):
    t, d = shape["rows"], shape["head_dim"]
    return tuple(_normal(key, (1, t, heads * d)) for key, heads in zip(
        _keys(seed, 3),
        (shape["heads"], shape["kv_heads"], shape["kv_heads"]))), {}


def _attention_xla(mask):
    """The op's XLA composition (`ops/attention.py`), a query head at a
    time under `jax.checkpoint`: a head's (T, T) scores are all the chip
    holds of them.  `mask(shape)`: the composition's mask keywords."""
    def build(mod, shape, aux):
        from paddle_tpu.ops.attention import _xla_attention_nthd

        h, d = shape["heads"], shape["head_dim"]
        group = h // shape["kv_heads"]
        kw = mask(shape)

        def fn(q, k, v):
            cut = lambda x, j: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                x, j * d, d, axis=2)

            @jax.checkpoint
            def head(j):
                return _xla_attention_nthd(
                    cut(q, j), cut(k, j // group), cut(v, j // group), None,
                    shape.get("scale", d ** -0.5), n_head=1, **kw)

            o = jax.lax.map(head, jnp.arange(h))        # (H, N, T, D)
            return jnp.moveaxis(o, 0, 2).reshape(q.shape)
        return fn
    return build


def _sides(block):
    """(query side, key side) of a swept tile: a side, or the pair."""
    return block if isinstance(block, tuple) else (block, block)


def _flash_window(mod, shape, aux, block=None):
    block_q, block_k = _sides(block)
    return functools.partial(
        mod.pallas_flash_attention, causal=True, layout="nthd",
        n_head=shape["heads"], n_kv_head=shape["kv_heads"],
        window=shape["window"], block_q=block_q, block_k=block_k)


def _flash_gqa(mod, shape, aux, block=None):
    return lambda q, k, v: mod.flash_gqa(
        q, k, v, shape["heads"], shape["kv_heads"], shape.get("scale"),
        *_sides(block))


def _flash_block_diffusion(mod, shape, aux, block=None):
    return lambda q, k, v: mod.flash_block_diffusion(
        q, k, v, None, shape["heads"], shape["kv_heads"],
        shape["block_length"], block, block, bare=True)


def _segment_operands(shape, seed):
    """q, k, v (1, P, heads x head_dim) as the projections write them
    (q and k UNTURNED), the rows' segment ids (the cell's multiset of
    images on the packed axis, in an order drawn) and cos, sin of a
    (row, column) a row: what the `segment_attention` op has when it
    turns, lays out and attends."""
    from paddle_tpu.ops import decoder

    t, lanes = shape["rows"], shape["heads"] * shape["head_dim"]
    rng = np.random.default_rng(seed)
    sizes = rng.permutation(shape["images"])
    seg = np.repeat(np.arange(len(sizes)), sizes).astype(np.int32)[None]
    yx = jnp.asarray(rng.integers(0, 64, (1, t, 2)), jnp.int32)
    return (tuple(_normal(key, (1, t, lanes)) for key in _keys(seed, 3)),
            {"segment_ids": jnp.asarray(seg),
             "rotary": decoder._cos_sin_two_axes(yx, shape["head_dim"],
                                                 10000.0)})


def _turned(shape, aux, *xs):
    """The turn as XLA makes it (`ops/decoder.py _rope` over pairs)."""
    from paddle_tpu.ops.decoder import _rope

    return tuple(_rope(x, None, *aux["rotary"], shape["heads"], pairs=True)
                 for x in xs)


def _flash_segment(mod, shape, aux, block=None, sub_block=None,
                   max_segment_rows=None):
    """The op's kernel path from unturned q and k: the turn and the
    128-lane layout with the attention.  A checkout from before PR 74
    is handed q and k turned by XLA, as its op was.
    `max_segment_rows` swept: the list's static length, M / block + 4
    visits a tile (7168 at tiles of 512: the 18 a tile that a
    rectangle of every tile's longest possible run would take);
    `sub_block` at the tile's own size: a product a whole tile."""
    turns = "rotary" in inspect.signature(mod.flash_segment).parameters

    def fn(q, k, v):
        if not turns:
            q, k = _turned(shape, aux, q, k)
        return mod.flash_segment(
            q, k, v, aux["segment_ids"], shape["heads"],
            max_segment_rows=max_segment_rows or shape["max_segment_rows"],
            block=block, sub_block=sub_block,
            **({"rotary": aux["rotary"]} if turns else {}))[0]
    return fn


def _segment_xla(mod, shape, aux):
    return lambda q, k, v: mod.segment_attention_xla(
        *_turned(shape, aux, q, k), v, aux["segment_ids"], shape["heads"])


def _segment_rectangle(mod, shape, aux):
    """The whole rectangle: `flash_attention` over all P x P pairs with a
    key bias (a mask's worth of it), heads laid out at 128 lanes."""
    from paddle_tpu.ops.pallas.flash_attention import pallas_flash_attention

    def fn(q, k, v):
        bias = jnp.zeros((1, 1, 1, q.shape[1]), F32)
        o = pallas_flash_attention(
            *(mod._to_lane_tiles(x, shape["heads"])
              for x in (*_turned(shape, aux, q, k), v)),
            bias=bias, scale=shape["head_dim"] ** -0.5, causal=False,
            layout="nthd", n_head=shape["heads"])
        return o.reshape(q.shape[:2] + (shape["heads"], -1))[
            ..., :shape["head_dim"]].reshape(q.shape)
    return fn


def _head_lanes(mod, shape, aux):
    """The lane kernels alone, as the attention's forward and backward
    rules call them: q, k, v to the kernels' layout with q's and k's
    turn, and three gradients back through the turn's transpose (the
    backward pass needs nothing of the forward's, so `fwd_bwd` times
    the way back alone)."""
    heads, d = shape["heads"], shape["head_dim"]
    rotary = mod.tables(*aux["rotary"], d)

    @jax.custom_vjp
    def fn(q, k, v):
        return mod.to_tiles((q, k, v), heads, rotary, 2)

    fn.defvjp(lambda *xs: (fn(*xs), None),
              lambda _, cts: mod.from_tiles(cts, heads, d, rotary, 2))
    return fn


def _head_lanes_xla(mod, shape, aux):
    """The passes they replace: `_rope` x 2 and the pad of a (.., H, d)
    view x 3, and what XLA differentiates them to."""
    from paddle_tpu.ops.pallas.flash_segment import _to_lane_tiles

    return lambda q, k, v: tuple(
        _to_lane_tiles(x, shape["heads"])
        for x in (*_turned(shape, aux, q, k), v))


# -- the row-wise kernels ---------------------------------------------------

def _head_norm_operands(shape, seed):
    ks = _keys(seed, 3)
    rows = (1, shape["rows"], shape["heads"] * 128)
    return (_normal(ks[0], rows), 1 + 0.3 * _normal(ks[1], (128,), F32),
            _normal(ks[2], rows)), {}


def _head_norm(mod, shape, aux):
    return functools.partial(mod.head_norm, denom=128.0, eps=1e-6,
                             gate_activation=shape["gate"])


def _head_norm_view(mod, shape, aux):
    form = mod.Form(0, shape["heads"] * 128, denom=128.0, eps=1e-6,
                    gate_activation=shape["gate"])
    return lambda x, scale, gate: mod.head_norm_xla(x, scale, gate, form)


_ROPE_EPS = 1e-6


def _rope_operands(shape, seed):
    from paddle_tpu.ops import decoder

    ks = _keys(seed, 2)
    heads, d, centred = shape["heads"], shape["head_dim"], shape["centred"]
    x = _normal(ks[0], (1, shape["rows"], heads * d))
    w = _normal(ks[1], (d,), F32) * 0.1 + (not centred)
    cos, sin = decoder._cos_sin(
        shape["rows"], shape["rotary"],
        {"n_head": heads, "theta": 1e6, "rotary_dim": shape["rotary"]}, None)
    return (x, w), {"cos": cos, "sin": sin}


def _rope_scale(shape, w):
    return 1.0 + w if shape["centred"] else w


def _rope(mod, shape, aux, row_tile=None):
    return lambda x, w: mod.rope_kernel(
        x, _rope_scale(shape, w), aux["cos"][0, :, 0], aux["sin"][0, :, 0],
        shape["heads"], _ROPE_EPS, row_tile)


def _rope_xla(mod, shape, aux):
    from paddle_tpu.ops import decoder

    return jax.checkpoint(lambda x, w: decoder._rope(
        x, _rope_scale(shape, w), aux["cos"], aux["sin"], shape["heads"],
        _ROPE_EPS))


def _short_conv_operands(shape, seed):
    ks = _keys(seed, 2)
    d = shape["channels"]
    x = _normal(ks[0], (1, shape["rows"], (3 if shape["gated"] else 1) * d))
    return (x, _normal(ks[1], (d, shape["taps"]), F32) * 0.5), {}


def _short_conv(mod, shape, aux, row_tile=None, channel_tile=None):
    return lambda x, w: mod.short_conv_kernel(
        x, w, bool(shape["gated"]), row_tile, channel_tile)


def _short_conv_xla(mod, shape, aux):
    from paddle_tpu.ops import decoder

    return jax.checkpoint(
        decoder._short_conv if shape["gated"] else decoder._silu_conv)


def _share_rows_operands(shape, seed):
    """The first row buffer of a layer that holds `held` of `experts`
    experts, under a router that picks k distinct experts a token
    uniformly, as the op sorts them."""
    from paddle_tpu.ops import moe_dropless as md

    t, k, e, held = (shape[x] for x in ("tokens", "k", "experts", "held"))
    ks = _keys(seed, 3)
    experts = jnp.argsort(jax.random.uniform(ks[0], (t, e)), axis=1)[:, :k]
    flat = experts.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    back = jnp.argsort(order).astype(jnp.int32).reshape(t, k)
    n = jnp.sum(flat < held)
    rows = md.row_buffer_sizes(t, k, e, held)[0]
    head = order[:rows]
    vals = jnp.where(jnp.arange(rows)[:, None] < n,
                     _normal(ks[1], (rows, shape["width"])), 0)
    w = jax.random.uniform(ks[2], (t, k), F32, 0.01, 1.0)
    return (vals, w), {"back": back, "head": head, "tokens": head // k,
                       "n": n, "c": w.reshape(-1)[head]}


def _share_rows(mod, shape, aux, token_tile=None, row_chunk=None):
    """The section's combine (`ops/moe_dropless.py _combine_rows`: the
    kernel forward, a gather of R rows backward) with `mod`'s kernel at
    the tiles given in it while it is traced."""
    from paddle_tpu.ops import moe_dropless as md

    tile = token_tile or mod.TOKEN_TILE
    plan = mod.token_order(aux["tokens"], aux["n"], shape["tokens"], aux["c"],
                           tile, row_chunk or mod.ROW_CHUNK)

    def fn(ys, w):
        kept, md.rows_to_tokens = md.rows_to_tokens, functools.partial(
            mod.rows_to_tokens, token_tile=tile)
        try:
            return md._combine_rows(ys, w, aux["c"], aux["back"],
                                    aux["tokens"], aux["n"], plan)
        finally:
            md.rows_to_tokens = kept
    return fn


def _share_rows_xla(mod, shape, aux):
    from paddle_tpu.ops import moe_dropless as md

    return lambda ys, w: md._combine(ys, w, aux["back"], aux["head"],
                                     aux["n"])


# -- the state-space scans --------------------------------------------------

def _selective_scan_operands(shape, seed):
    t, d, states = shape["rows"], shape["channels"], 16
    ks = _keys(seed, 5)
    rates = -jnp.tile(jnp.arange(1, states + 1, dtype=F32), (d, 1))
    step = _log_uniform(ks[4], (d,), 1e-3, 1e-1)
    return (_normal(ks[0], (1, t, d)), _normal(ks[1], (1, t, d)) * 0.5,
            rates, _normal(ks[2], (1, t, states)),
            _normal(ks[3], (1, t, states)), jnp.ones((d,), F32),
            jnp.log(jnp.expm1(step))), {}


_SSD_STATE, _SSD_TAPS = 128, 4


def _ssd_scan_operands(shape, seed):
    """xBC as the convolution leaves it, the step, A and D; then the
    convolution's filter and bias, which the `composites` read."""
    t, heads = shape["rows"], shape["heads"]
    ks = _keys(seed, 4)
    width = heads * 64 + 2 * _SSD_STATE
    return (_normal(ks[0], (1, t, width)),
            _log_uniform(ks[1], (1, t, heads), 1e-3, 1e-1),
            -jnp.arange(1, heads + 1, dtype=F32), jnp.ones((heads,), F32),
            _normal(ks[2], (width, _SSD_TAPS), F32) / _SSD_TAPS,
            _normal(ks[3], (width,), F32)), {}


def _ssd_scan(mod, shape, aux):
    return lambda xbc, dt, a, d, w, bias: mod.scan_kernel(xbc, dt, a, d)


def _ssd_scan_xla(mod, shape, aux):
    def fn(xbc, dt, a, d, w, bias):
        x = xbc.shape[2] - 2 * _SSD_STATE
        return mod.scan_xla(xbc[..., :x], dt, a, xbc[..., x:x + _SSD_STATE],
                            xbc[..., x + _SSD_STATE:], d)
    return fn


def _ssd_layer(mod, shape, aux):
    """A mixer's biased SiLU convolution and the scan behind it."""
    from paddle_tpu.ops.pallas.short_conv import biased_conv_kernel

    return lambda u, dt, a, d, w, bias: mod.scan_kernel(
        biased_conv_kernel(u, w, bias), dt, a, d)


def _ssd_layer_segment(mod, shape, aux):
    """The same under a recompute segment: what a layer of the cell runs."""
    from paddle_tpu.ops.pallas import segment_policy

    return jax.checkpoint(_ssd_layer(mod, shape, aux),
                          policy=segment_policy())


KIMIVL_TOWER = dict(
    rows=24576, heads=16, head_dim=72, max_segment_rows=4096,
    images=(4096,) * 2 + (2304,) * 4 + (1024,) * 6 + (256,) * 4)

FAMILIES = {
    "channel_delta": Family(
        "channel_delta", {"kimilinear-8k": dict(rows=8192, heads=32)},
        _channel_delta_operands, ("q", "k", "v", "g", "beta"),
        {"kernel": _with("channel_delta_rule", use_kernel=True),
         "xla": _channel_delta_xla}),
    "flash_block_diffusion": Family(
        "flash_block_diffusion",
        {"sdar-8k": dict(rows=16384, heads=32, kv_heads=4, head_dim=128,
                         block_length=4)},
        _attention_operands, ("q", "k", "v"),
        {"kernel": _flash_block_diffusion,
         "xla": _attention_xla(lambda s: dict(
             causal=False, block_diffusion=s["block_length"]))},
        sweepable=("block", "OWN_BLOCKS")),
    "flash_gqa": Family(
        "flash_gqa",
        {"lfm2-8k": dict(rows=8192, heads=32, kv_heads=8, head_dim=64),
         "granite4h-8k": dict(rows=8192, heads=32, kv_heads=8, head_dim=64,
                              scale=2.0 ** -6)},
        _attention_operands, ("q", "k", "v"),
        {"kernel": _flash_gqa,
         "xla": _attention_xla(lambda s: dict(causal=True))},
        sweepable=("block", "FUSED_ACCUMULATOR_BUDGET")),
    "flash_segment": Family(
        "flash_segment", {"kimivl-8k": KIMIVL_TOWER},
        _segment_operands, ("q", "k", "v"),
        {"kernel": _flash_segment, "xla": _segment_xla},
        composites={"whole_rectangle": _segment_rectangle},
        sweepable=("block", "sub_block", "max_segment_rows")),
    "flash_window": Family(
        "flash_attention",
        {"laguna-16k": dict(rows=16384, heads=64, kv_heads=8, head_dim=128,
                            window=512),
         "mellum2-16k": dict(rows=16384, heads=32, kv_heads=4, head_dim=128,
                             window=1024),
         # no window: the whole causal prefix over grouped heads
         "qwen3next-16k": dict(rows=16384, heads=16, kv_heads=2,
                               head_dim=256, window=None)},
        _attention_operands, ("q", "k", "v"),
        {"kernel": _flash_window, "xla": _attention_xla(
            lambda s: dict(causal=True, window=s["window"]))},
        sweepable=("block", "FUSED_ACCUMULATOR_BUDGET",
                   "WHOLE_BAND_SCORE_BUDGET", "WHOLE_BAND_FWD_BLOCK")),
    "gated_delta": Family(
        "gated_delta", {"qwen3next-16k": dict(rows=16384, key_heads=16)},
        _gated_delta_operands, ("qkv", "g", "beta"),
        {"kernel": _gated_delta(True), "xla": _gated_delta(False)},
        sweepable=("DIAGONAL_BLOCK", "DEFAULT_BLOCK_CHUNKS"),
        parts=_gated_delta_parts),
    "head_lanes": Family(
        "head_lanes", {"kimivl-8k": KIMIVL_TOWER},
        _segment_operands, ("q", "k", "v"),
        {"kernel": _head_lanes, "xla": _head_lanes_xla},
        sweepable=("ROW_TILE", "ROW_CHUNK")),
    "head_norm": Family(
        "head_norm",
        {"kimilinear-8k": dict(rows=8192, heads=32, gate="sigmoid"),
         "qwen3next-16k": dict(rows=16384, heads=32, gate="silu")},
        _head_norm_operands, ("x", "scale", "gate"),
        {"kernel": _head_norm, "view": _head_norm_view},
        sweepable=("ROW_TILE", "LANE_TILE")),
    "rope": Family(
        "rope",
        {"mellum2-16k": dict(rows=16384, heads=32, head_dim=128, rotary=128,
                             centred=False),
         "sdar-8k": dict(rows=16384, heads=32, head_dim=128, rotary=128,
                         centred=False),
         "qwen3next-16k": dict(rows=16384, heads=16, head_dim=256, rotary=64,
                               centred=True)},
        _rope_operands, ("x", "scale"),
        {"kernel": _rope, "xla": _rope_xla}, sweepable=("row_tile",)),
    "selective_scan": Family(
        "selective_scan", {"phi4flash-8k": dict(rows=8192, channels=5120)},
        _selective_scan_operands,
        ("u", "delta", "a", "b", "c", "d", "delta_bias"),
        {"kernel": _with("scan_kernel"), "xla": _with("scan_xla")},
        sweepable=("CHANNEL_TILE", "BWD_CHANNEL_TILE")),
    "share_rows": Family(
        "rows_to_tokens",
        {"mellum2-16k": dict(tokens=16384, k=8, experts=64, held=8,
                             width=2304),
         "sdar-8k": dict(tokens=16384, k=8, experts=128, held=16, width=2048),
         "qwen3next-16k": dict(tokens=16384, k=10, experts=512, held=16,
                               width=2048),
         "lfm2-8k": dict(tokens=8192, k=4, experts=64, held=8, width=2048),
         "joyai-8k": dict(tokens=8192, k=8, experts=256, held=8, width=2048)},
        _share_rows_operands, ("rows", "weights"),
        {"kernel": _share_rows, "xla": _share_rows_xla},
        sweepable=("token_tile", "row_chunk")),
    "short_conv": Family(
        "short_conv",
        {"qwen3next-16k": dict(rows=16384, channels=8192, taps=4, gated=False),
         "lfm2-8k": dict(rows=8192, channels=2048, taps=3, gated=True)},
        _short_conv_operands, ("x", "filter"),
        {"kernel": _short_conv, "xla": _short_conv_xla},
        sweepable=("row_tile", "channel_tile", "ROW_CHUNK", "LANE_GROUP")),
    "ssd_scan": Family(
        "ssd_scan", {"granite4h-8k": dict(rows=8192, heads=64)},
        _ssd_scan_operands, ("xbc", "dt", "a", "d", "filter", "bias"),
        {"kernel": _ssd_scan, "xla": _ssd_scan_xla},
        composites={"layer": _ssd_layer, "layer_segment": _ssd_layer_segment},
        sweepable=("HEAD_BLOCK",)),
}
