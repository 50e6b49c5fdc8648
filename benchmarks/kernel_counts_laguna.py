"""The benchmark's own counts for what the `laguna-16k` cell adds to a
step: causal flash attention at d_head 128 whose QUERY head count
follows the layer type (64 over 8 key/value heads under a window of 512
keys in the `sliding_attention` layers, 48 over 8 over the whole prefix
in the `full_attention` layers), and the grouped matmuls of the held
experts at 2048 <-> 512.  For the readers in `layer_metrics/` that
share them, beside `kernel_counts.py` (whose `kernel_of`, `peaks`,
`roofline_ms` and `roofline_share` they use), `kernel_counts_mellum.py`
(the pairs of a band and of the causal half, the kernels' names, the
name scopes), `kernel_counts_joyai.py` (whose `scope_ms_per_step` reads
a name scope's rows) and `kernel_counts_lfm2.py` (the held rows'
share, the expert op's rows).

Operations and bytes are what the ALGORITHM needs for the call, from
the cell's shapes: the pairs the MASK allows, seven score-sized matmuls
of d_head a pair at the LAYER's own query heads (scores and values
forward; scores again, dP, dV, dK, dQ backward: the scores are
recomputed once because that IS the algorithm), whatever implements it
and however many tiles its grid visits or how full they are.  Bytes: q,
o forward and q, o, do, dq backward at the layer's QUERY heads' width;
k, v forward and k, v, dk, dv backward at the 8 KEY/VALUE heads' width
(they are never repeated); bfloat16, once each.  They do not move when
the program's HLO or its cost registry does.  A share of a roofline
cannot pass 100%.

It sits beside `run.py`, not in `layer_metrics/`, where `run.py` takes
every `*.py` for a reader.
"""

from __future__ import annotations

import kernel_counts_mellum as bands

HEAD_GATE = "attention_head_gate"       # name scope of the builder
BF16 = 2


def heads_of(config, kind):
    """The query head counts of the layers of type `kind`."""
    return [h for h, k in zip(config["num_attention_heads_per_layer"],
                              config["layer_types"]) if k == kind]


def sparse_layers(config):
    return config["mlp_layer_types"].count("sparse")


def _cost(config, cell, kind, pairs):
    n, t = cell["batch_per_chip"], cell["length"]
    head_dim = config["head_dim"]
    kv = config["num_key_value_heads"] * head_dim
    flops = nbytes = 0.0
    for heads in heads_of(config, kind):
        flops += 7 * 2.0 * n * heads * pairs * head_dim
        nbytes += 6.0 * n * t * (heads * head_dim + kv) * BF16
    return flops, nbytes


def window_pairs(config, cell):
    """Score pairs a head that a window layer's mask allows."""
    return bands.band_pairs(cell["length"], config["sliding_window"])


def flash_window_cost(config, cell):
    """(FLOP, bytes) of one step's window flash attention, forward and
    backward (the recomputed forward not counted), over the
    `sliding_attention` layers at their own head count."""
    return _cost(config, cell, bands.SLIDING, window_pairs(config, cell))


def flash_grouped_cost(config, cell):
    """The same over the `full_attention` layers: the causal half."""
    return _cost(config, cell, bands.FULL,
                 bands.causal_pairs(cell["length"]))


def window_fill():
    """(score pairs a head that the window's mask allows, score entries
    its forward grid's visited tiles compute), the program's two
    counters over every window call traced in the process; None where
    the program keeps no such counters or no window kernel was
    traced."""
    try:
        from paddle_tpu.observe.monitoring import runtime_stats
    except ImportError:
        return None
    snap = runtime_stats.snapshot()
    pairs = snap.get("flash_window_pairs_allowed")
    entries = snap.get("flash_window_entries_computed")
    if not pairs or not entries:
        return None
    return pairs, entries


def expert_matmul_cost(config, cell, rows):
    """(FLOP, bytes) of one step's grouped expert matmuls over the
    sparse layers, for `rows` real rows a layer:
    `kernel_counts_lfm2.held_expert_matmul_cost`'s count (three matmuls
    forward and six backward, 2 * rows * D * H each; each reads its two
    operands and writes its result once, bfloat16, the weights the held
    experts'; the forward that a recompute segment runs again is not
    counted) over the layers `mlp_layer_types` calls sparse."""
    import kernel_counts_lfm2

    dense = config["num_hidden_layers"] - sparse_layers(config)
    return kernel_counts_lfm2.held_expert_matmul_cost(
        dict(config, num_dense_layers=dense), cell, rows)
