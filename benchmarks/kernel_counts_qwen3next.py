"""The benchmark's own counts for what the `qwen3next-16k` cell adds to
a step: the sequential part of the chunked gated delta rule (the
`gated_delta_fwd` / `gated_delta_bwd` kernels of
`paddle_tpu/ops/pallas/gated_delta.py`) in the `linear_attention`
layers, and causal flash attention at d_head 256 over grouped key/value
heads (16 / 2) in the `full_attention` layers.  For the readers in
`layer_metrics/` that share them, beside `kernel_counts.py` (whose
`kernel_of`, `peaks`, `roofline_ms` and `roofline_share` they use) and
`kernel_counts_joyai.py` (whose `scope_ms_per_step` reads a name
scope's rows).

The scan.  A chunk of C = 64 positions of one value head, state S
(Dk, Dv): what the kernels EXECUTE once, forward W S, (Q exp gamma) S,
P V' and (K exp ..)^T V' (three products of 2 C Dk Dv and one of
2 C C Dv), backward dV' (two), dP, dQ, dK, dW and dS (two): six of
2 C Dk Dv and two of 2 C C Dv.  The V' the backward kernel rebuilds
and the forward kernel's second run in a recompute segment are not
counted; the time the share divides by holds both.  (The model count,
`models/qwen3_next.py train_flops`, is the SEQUENTIAL form's 6 Dk Dv a
head a token, whatever the chunk; K K^T, the inverse, W and U are
XLA's batch part and no kernel's.)  Bytes, bfloat16, once each: forward
W, U, Q', K', O at 128 lanes and P at 64 a position a head; backward
those five operands and P again, dO, the four gradients and dP; the
state that enters each chunk (Dk x Dv a chunk a head) written once and
read once.

Flash at d_head 256: `kernel_counts_mellum.py`'s count of the same
kernels (the pairs the causal mask allows, seven score-sized matmuls of
d_head a pair, q, o, do, dq at the query heads' width and k, v, dk, dv
at the key/value heads'), at this configuration's heads.

They do not move when the program's HLO or its cost registry does.  A
share of a roofline cannot pass 100%.  It sits beside `run.py`, not in
`layer_metrics/`, where `run.py` takes every `*.py` for a reader.
"""

from __future__ import annotations

import kernel_counts_mellum as mellum

SCAN_KERNELS = ("gated_delta_fwd", "gated_delta_bwd")
# by prefix (`kernel_counts.kernel_ms_per_step`); no scan kernel's name
# starts with one of these
FLASH_KERNELS = mellum.GROUPED_KERNELS
LINEAR, GATED = "linear_attention", "gated_attention"   # name scopes
CHUNK = 64
BF16 = 2


def layer_types(config):
    every = config["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(config["num_hidden_layers"])]


def chunks_per_call(config, cell):
    """Chunks x value heads of one kernel call."""
    return (cell["batch_per_chip"] * config["linear_num_value_heads"]
            * -(-cell["length"] // CHUNK))


def gated_delta_cost(config, cell):
    """(FLOP, bytes) of one step's scan kernels, forward and backward
    once each, over the `linear_attention` layers."""
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    chunks = chunks_per_call(config, cell)
    wide, narrow = 2.0 * CHUNK * dk * dv, 2.0 * CHUNK * CHUNK * dv
    flops = chunks * ((3 + 6) * wide + (1 + 2) * narrow)
    rows = chunks * CHUNK
    # forward 3 x Dk + 2 x Dv lanes and P; backward 6 x Dk + 4 x Dv
    # (the five operands, dO, four gradients) and P, dP
    nbytes = BF16 * (rows * (9 * dk + 6 * dv + 3 * CHUNK)
                     + 2 * chunks * dk * dv)
    layers = layer_types(config).count("linear_attention")
    return layers * flops, layers * nbytes


def flash_d256_cost(config, cell):
    """(FLOP, bytes) of one step's causal flash attention over grouped
    heads at the configuration's head_dim, forward and backward (the
    kept residuals mean no recomputed forward), over the
    `full_attention` layers: `kernel_counts_mellum.flash_grouped_cost`,
    the same kernels' count, given this family's layer pattern."""
    return mellum.flash_grouped_cost(
        dict(config, layer_types=layer_types(config)), cell)


def scan_chunks():
    """(kernel calls traced, their chunks x heads), the program's two
    counters over every call traced in the process; None where the
    program keeps no such counters or no scan kernel was traced (a step
    on the XLA lowering)."""
    try:
        from paddle_tpu.observe.monitoring import runtime_stats
    except ImportError:
        return None
    snap = runtime_stats.snapshot()
    calls, chunks = (snap.get("gated_delta_calls"),
                     snap.get("gated_delta_chunks"))
    if not calls or not chunks:
        return None
    return calls, chunks
