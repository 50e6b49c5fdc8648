"""The benchmark's own counts for what the `sdar-8k` cell adds to a
step: flash attention under the block-diffusion training mask over
grouped key/value heads at d_head 128, a document of L positions fed
as 2 L rows (clean, then noised), blocks of B; and its PLACED share of
the experts (`models/sdar_moe.py place_experts`), all 2 L rows routed
in every layer.  For the readers in `layer_metrics/` that share them,
beside `kernel_counts.py` (whose `kernel_of`, `peaks`, `roofline_ms`
and `roofline_share` they use), `kernel_counts_joyai.py` (whose
`scope_ms_per_step` reads a name scope's rows) and
`kernel_counts_lfm2.py` (whose `op_ms_per_step` reads a fluid op's rows
and whose `held_row_share` the expert op's two counters).

Operations and bytes are what the ALGORITHM needs for the call, from
the cell's shapes: the pairs the MASK allows (with n = L / B blocks:
noised -> noised L B, noised -> clean B^2 n (n - 1) / 2, clean -> clean
B^2 n (n + 1) / 2: 67,141,632 a head at L 8192, B 4), seven score-sized
matmuls of d_head a pair (scores and values forward; scores again, dP,
dV, dK, dQ backward: the scores are recomputed once because that IS the
algorithm), whatever implements it and however many tiles its grid
visits.  Bytes: q, o forward and q, o, do, dq backward at the QUERY
heads' width over the 2 L rows; k, v forward and k, v, dk, dv backward
at the KEY/VALUE heads' width (they are never repeated); bfloat16, once
each.  They do not move when the program's HLO or its cost registry
does.  A share of a roofline cannot pass 100%.

It sits beside `run.py`, not in `layer_metrics/`, where `run.py` takes
every `*.py` for a reader.
"""

from __future__ import annotations

KERNELS = ("flash_block_diffusion_fwd", "flash_block_diffusion_dkv",
           "flash_block_diffusion_dq")
SCOPE = "block_diffusion_attention"     # the operator's name scope
EXPERT_OP = "moe_dropless"
BF16 = 2


def allowed_pairs(length, block_length):
    """Score pairs a head that the mask allows over a document of
    `length` positions in blocks of `block_length`."""
    b, n = block_length, length // block_length
    return length * b + b * b * (n * (n - 1) // 2 + n * (n + 1) // 2)


def flash_block_diffusion_cost(config, cell):
    """(FLOP, bytes) of one step's flash attention under the mask,
    forward and backward (a recomputed forward not counted), over all
    layers."""
    n, length = cell["batch_per_chip"], cell["length"]
    heads, head_dim = config["num_attention_heads"], config["head_dim"]
    q = heads * head_dim
    kv = config["num_key_value_heads"] * head_dim
    pairs = allowed_pairs(length, config["block_length"])
    flops = 7 * 2.0 * n * heads * pairs * head_dim
    nbytes = 6.0 * n * 2 * length * (q + kv) * BF16
    layers = config["num_hidden_layers"]
    return layers * flops, layers * nbytes


def visited_blocks():
    """(tiles the kernels' grids computed, those of them that hold an
    allowed pair, calls traced), the program's three counters over every
    call traced in the process, forward and backward; None where the
    program keeps no such counters or no such kernel was traced (the
    XLA lowering under an explicit mask counts nothing)."""
    try:
        from paddle_tpu.observe.monitoring import runtime_stats
    except ImportError:
        return None
    snap = runtime_stats.snapshot()
    visited = snap.get("flash_block_diffusion_blocks_visited")
    allowed = snap.get("flash_block_diffusion_blocks_allowed")
    if not visited or not allowed:
        return None
    return visited, allowed, snap.get("flash_block_diffusion_calls", 0)


def placed_rows_per_layer_step(config, cell):
    """Mean rows (token, expert) a step that ONE layer's held experts
    got: the held share (the expert op's device-side counters, over
    every step of the process) of the 2 L x k rows a layer routes.
    None without the counters."""
    import kernel_counts_lfm2

    share = kernel_counts_lfm2.held_row_share()
    if share is None:
        return None
    return (share * cell["batch_per_chip"] * 2 * cell["length"]
            * config["num_experts_per_tok"])


def placed_expert_matmul_cost(config, cell, rows):
    """(FLOP, bytes) of one step's grouped expert matmuls over all
    layers, for `rows` real rows a layer:
    `kernel_counts_lfm2.held_expert_matmul_cost`'s count (three matmuls
    forward and six backward, 2 * rows * D * H each; each reads its two
    operands and writes its result once, bfloat16, the weights the held
    experts'; the forward that a recompute segment runs again is not
    counted) with every layer routed."""
    import kernel_counts_lfm2

    return kernel_counts_lfm2.held_expert_matmul_cost(
        dict(config, num_dense_layers=0), cell, rows)
