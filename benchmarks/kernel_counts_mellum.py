"""The benchmark's own counts for what the `mellum2-16k` cell adds to
a step: causal flash attention over grouped key/value heads at d_head
128, under a window in the `sliding_attention` layers and over the
whole prefix in the `full_attention` layers.  For the readers in
`layer_metrics/` that share them, beside `kernel_counts.py` (whose
`kernel_of`, `peaks`, `roofline_ms` and `roofline_share` they use) and
`kernel_counts_joyai.py` (whose `scope_ms_per_step` reads a name
scope's rows).

Operations and bytes are what the ALGORITHM needs for the call, from
the cell's shapes: the pairs the MASK allows (the band i - W < j <= i,
or the causal half with its diagonal), seven score-sized matmuls of
d_head a pair (scores and values forward; scores again, dP, dV, dK, dQ
backward: the scores are recomputed once because that IS the
algorithm), whatever implements it and however many blocks its grid
visits.  Bytes: q, o forward and q, o, do, dq backward at the QUERY
heads' width; k, v forward and k, v, dk, dv backward at the KEY/VALUE
heads' width (they are never repeated); bfloat16, once each.  They do
not move when the program's HLO or its cost registry does.  A share of
a roofline cannot pass 100%.

It sits beside `run.py`, not in `layer_metrics/`, where `run.py` takes
every `*.py` for a reader.
"""

from __future__ import annotations

WINDOW_KERNELS = ("flash_window_fwd", "flash_window_dkv", "flash_window_dq")
# by prefix (`kernel_counts.kernel_ms_per_step`): no window kernel's
# name starts with one of these
GROUPED_KERNELS = ("flash_fwd", "flash_dkv", "flash_dq")
SLIDING, FULL = "sliding_attention", "full_attention"   # name scopes
BF16 = 2


def causal_pairs(t):
    """Pairs (i, j) with j <= i over t positions."""
    return t * (t + 1) // 2


def band_pairs(t, window):
    """Pairs with i - window < j <= i: `window` keys a query, its own
    included, but for the first window - 1 queries."""
    w = min(window, t)
    return w * t - w * (w - 1) // 2


def layers_of(config, kind):
    return sum(1 for k in config["layer_types"] if k == kind)


def _cost(config, cell, kind, pairs):
    n, t = cell["batch_per_chip"], cell["length"]
    heads, head_dim = config["num_attention_heads"], config["head_dim"]
    q = heads * head_dim
    kv = config["num_key_value_heads"] * head_dim
    flops = 7 * 2.0 * n * heads * pairs * head_dim
    nbytes = 6.0 * n * t * (q + kv) * BF16
    layers = layers_of(config, kind)
    return layers * flops, layers * nbytes


def flash_window_cost(config, cell):
    """(FLOP, bytes) of one step's window flash attention, forward and
    backward (the recomputed forward not counted), over the
    `sliding_attention` layers."""
    return _cost(config, cell, SLIDING,
                 band_pairs(cell["length"], config["sliding_window"]))


def flash_grouped_cost(config, cell):
    """The same over the `full_attention` layers: the causal half."""
    return _cost(config, cell, FULL, causal_pairs(cell["length"]))


def window_blocks():
    """(key blocks the window kernel's forward grid visited, those of
    them that hold an allowed pair), the program's two counters over
    every call traced in the process; None where the program keeps no
    such counters or no window kernel was traced."""
    try:
        from paddle_tpu.observe.monitoring import runtime_stats
    except ImportError:
        return None
    snap = runtime_stats.snapshot()
    visited = snap.get("flash_window_blocks_visited")
    allowed = snap.get("flash_window_blocks_allowed")
    if not visited or not allowed:
        return None
    return visited, allowed
