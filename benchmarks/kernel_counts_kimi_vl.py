"""The benchmark's own counts for what the `kimivl-8k` cell adds to a
step: attention confined to the images of a packed row axis (the
`flash_segment_*` kernels of `paddle_tpu/ops/pallas/flash_segment.py`)
in the vision tower, and which rows of a traced step belong to the
tower, its attention and the projector.  For the readers in
`layer_metrics/` that share them, beside `kernel_counts.py` (whose
`kernel_of`, `peaks`, `roofline_ms` and `roofline_share` they use),
`kernel_counts_joyai.py` (whose `scope_ms_per_step` reads a name
scope's rows and whose `flash_mla_cost` counts latent attention) and
`kernel_counts_lfm2.py` (the expert op's counters and its grouped
matmuls' cost).

The attention's cost is counted from the CELL's images (`images`: the
multiset every batch holds) at the PUBLISHED head size, as any
implementation must pay it: the allowed (query, key) pairs, every patch
of an image against every patch of the same image, and nothing of the
tiles a kernel visits besides, the lanes it pads a head to, or a pass
it recomputes.  The share therefore reads the same work whatever
implements it and cannot pass 100.

It sits beside `run.py`, not in `layer_metrics/`, where `run.py` takes
every `*.py` for a reader.
"""

from __future__ import annotations

import kernel_counts_joyai

# by prefix (`kernel_counts.kernel_ms_per_step`): both kernels
SEGMENT_KERNELS = ("flash_segment_",)
# by name (`kernel_counts_joyai.scope_ms_per_step`)
SEGMENT_KERNEL_NAMES = ("flash_segment_fwd", "flash_segment_bwd")
TOWER, ATTENTION, PROJECTOR = ("vision_tower", "vision_attention",
                               "vision_projector")    # name scopes
BF16 = 2
# score-sized products a pair: scores and values forward; scores again,
# dP, dV, dK, dQ backward (`kernel_counts.flash_attention_cost`)
PRODUCTS = 7


def allowed_pairs(cell):
    return sum(group["count"] * group["patches"] ** 2
               for group in cell["images"])


def patches(cell):
    return sum(group["count"] * group["patches"] for group in cell["images"])


def flash_segment_cost(config, cell):
    """(FLOP, bytes) of one step's segment-confined attention, forward
    and backward once each, over the tower's layers.  FLOP: 2 x the
    head's 72 lanes x `PRODUCTS` an allowed pair a head.  Bytes,
    bfloat16, once each at 72 lanes a head: q, k, v, o forward and q, k,
    v, o, do, dq, dk, dv backward; the soft-max statistics and the
    segment ids are left out."""
    vision = config["vision_config"]
    n = cell["batch_per_chip"]
    flops = PRODUCTS * 2.0 * n * allowed_pairs(cell) * vision["hidden_size"]
    nbytes = 12.0 * n * patches(cell) * vision["hidden_size"] * BF16
    return (vision["num_hidden_layers"] * flops,
            vision["num_hidden_layers"] * nbytes)


def flash_mla_cost(config, cell):
    """`kernel_counts_joyai.flash_mla_cost` at this configuration's 16
    heads and no prediction module."""
    return kernel_counts_joyai.flash_mla_cost(
        dict(config, num_nextn_predict_layers=0), cell)


def expert_matmul_cost(config, cell, rows):
    """`kernel_counts_lfm2.held_expert_matmul_cost` (three grouped
    matmuls forward and six backward, 2 * rows * D * H each, the weights
    the held experts') under this family's keys: `n_routed_experts` is
    what the chip holds, `first_k_dense_replace` the dense layers."""
    import kernel_counts_lfm2

    return kernel_counts_lfm2.held_expert_matmul_cost(
        dict(config, num_experts=config["n_routed_experts"],
             num_dense_layers=config["first_k_dense_replace"]), cell, rows)


def tile_visits():
    """(tiles the forward passes visited, tiles of their whole
    rectangles), the attention layers' device counters over every step
    of the process; None where the program keeps none or no call ran on
    the kernels."""
    try:
        from paddle_tpu.observe.routing import segment_tile_visits
    except ImportError:
        return None
    return segment_tile_visits()
