"""The benchmark's own counts for what the `lfm2-8k` cell adds to a
step: the gated short convolution, grouped-query flash attention at
d_head 64 and the expert layer that holds a share.  For the readers in
`layer_metrics/` that share them, beside `kernel_counts.py` (whose
`kernel_of`, `kernel_ms_per_step`, `peaks` and `roofline_ms` they use).

Operations and bytes are what the ALGORITHM needs, from the cell's
shapes (and, for the held experts, from the rows the router really
sent them); they do not move when the program's HLO or its cost
registry does.  A share of a roofline cannot pass 100%.

It sits beside `run.py`, not in `layer_metrics/`, where `run.py` takes
every `*.py` for a reader.
"""

from __future__ import annotations

import kernel_counts
import step_anatomy

FLASH_GQA_KERNELS = ("flash_gqa_fwd", "flash_gqa_dkv", "flash_gqa_dq")
SHORT_CONV = "short_conv"
EXPERT_OP = "moe_dropless"
BF16 = 2


def _tokens(cell):
    return cell["batch_per_chip"] * cell["length"]


def layers_of(config, kind):
    return sum(1 for k in config["layer_types"] if k == kind)


def routed_layers(config):
    return config["num_hidden_layers"] - config["num_dense_layers"]


def op_ms_per_step(run, op_type, kernels=()):
    """Self time per step on chip 0 of the step program's rows under
    the fluid op `op_type`'s scope (forward and backward), plus the
    kernels named `kernels` wherever they are scoped; None without
    the program's join."""
    a = step_anatomy.anatomy(run)
    if a is None:
        return None
    seconds = sum(
        r["self_s"] for r in a["step_rows"]
        if r["op_type"] == op_type
        or (kernels and kernel_counts.kernel_of(r) in kernels))
    return 1e3 * seconds / a["steps"]


def short_conv_bytes(config, cell):
    """Bytes one step's short convolutions must move, all conv layers,
    bfloat16, once each: forward reads `BCu` (T, 3D) and writes the
    output (T, D); backward reads `BCu` and the output's gradient and
    writes `BCu`'s gradient.  The filter (D, L) and its gradient are
    left out (L / T of an operand)."""
    t, d = _tokens(cell), config["hidden_size"]
    per_layer = (3 + 1 + 3 + 1 + 3) * t * d * BF16
    return float(layers_of(config, "conv") * per_layer)


def flash_gqa_cost(config, cell):
    """(FLOP, bytes) of one step's causal grouped-query flash
    attention, forward and backward, over the attention layers.  Seven
    matmuls of T x T x d_head a QUERY head, at half for the causal
    mask (`kernel_counts.flash_attention_cost`: the scores are
    recomputed once because that is the algorithm; the two-kernel
    backward's second recomputation is not counted).  Bytes: q, o
    forward and q, o, do, dq backward at the query heads' width; k, v
    forward and k, v, dk, dv backward at the KEY/VALUE heads' width
    (they are never repeated); bfloat16, once each."""
    n, t = cell["batch_per_chip"], cell["length"]
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    head_dim = d // heads
    kv = config["num_key_value_heads"] * head_dim
    layers = layers_of(config, "full_attention")
    flops = 7.0 * n * heads * t * t * head_dim
    nbytes = 6.0 * n * t * (d + kv) * BF16
    return layers * flops, layers * nbytes


def held_row_share():
    """Of the rows the share-holding layers routed, the fraction that
    went to experts they hold, from the two counters the expert op
    keeps on the device (`observe/routing.py`, over every step of the
    process).  None where the program keeps no such counters."""
    try:
        from paddle_tpu.observe import routing
    except ImportError:        # a program from before the counters
        return None
    return getattr(routing, "held_row_share", lambda: None)()


def held_rows_per_layer_step(config, cell):
    """Mean rows (token, expert) a step that ONE routed layer's held
    experts got: the held share of the T x k rows a layer routes.  None
    without the counters."""
    share = held_row_share()
    if share is None:
        return None
    return share * _tokens(cell) * config["num_experts_per_tok"]


def held_expert_matmul_cost(config, cell, rows):
    """(FLOP, bytes) of one step's grouped expert matmuls over the
    routed layers, for `rows` real rows a layer: three matmuls forward
    and six backward (dX and dW of each), 2 * rows * D * H each: a row
    meets ONE expert's weights.  Bytes: each matmul reads its two
    operands and writes its result once, bfloat16; the weights are the
    held experts'."""
    d, h = config["hidden_size"], config["moe_intermediate_size"]
    held = config["num_experts"]
    flops = 9 * 2.0 * rows * d * h
    nbytes = 9 * float(BF16) * (rows * d + rows * h + held * d * h)
    return routed_layers(config) * flops, routed_layers(config) * nbytes
