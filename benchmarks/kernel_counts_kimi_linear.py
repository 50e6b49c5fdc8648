"""The benchmark's own counts for what the `kimilinear-8k` cell adds to
a step: the delta rule whose decay is a key lane's own (the five
`channel_delta_*` kernels of `paddle_tpu/ops/pallas/channel_delta.py`)
in the `channel_delta_attention` layers.  For the readers in
`layer_metrics/` that share them, beside `kernel_counts.py` (whose
`kernel_of`, `peaks`, `roofline_ms` and `roofline_share` they use),
`kernel_counts_joyai.py` (whose `scope_ms_per_step` reads a name
scope's rows) and `kernel_counts_lfm2.py` (the expert op's counters).

The recurrence's cost is counted as ANY implementation must pay it, not
as the chunks run it, so that the share holds whatever implements the
scan.  Operations: the sequential form's three Dk x Dv products a head
a token (S^T k, k u^T, S^T q: 6 Dk Dv), forward once and backward twice
(`models/kimi_linear.py train_flops` counts the same).  Bytes, once
each: forward q, k, v (bfloat16) and g (float32) read and o written, a
lane each, beta (float32) a head; backward the same operands and do
read again, dq, dk, dv and dg written, dbeta a head.  What the chunked
kernels move besides (kb, vb, W, U, the scaled q and k, P, the inverse,
the chunk-entry states, the recomputed forward) is in the measured
time and not in the count: the share says how far the whole
implementation is from the mechanism's floor, which is why it reads
far under 100 and cannot pass it.

It sits beside `run.py`, not in `layer_metrics/`, where `run.py` takes
every `*.py` for a reader.
"""

from __future__ import annotations

# by prefix (`kernel_counts.kernel_ms_per_step`): all five kernels
DELTA_KERNELS = ("channel_delta_",)
# by name (`kernel_counts_joyai.scope_ms_per_step`)
DELTA_KERNEL_NAMES = ("channel_delta_inverse", "channel_delta_operands_fwd",
                      "channel_delta_operands_bwd", "channel_delta_fwd",
                      "channel_delta_bwd")
DELTA, LATENT = "channel_delta_attention", "latent_attention"  # name scopes
CHUNK = 64
BF16, F32 = 2, 4


def delta_layers(config):
    return len(config["linear_attn_config"]["kda_layers"])


def chunks_per_call(config, cell):
    """Chunks x heads of one scan kernel call."""
    return (cell["batch_per_chip"] * config["linear_attn_config"]["num_heads"]
            * -(-cell["length"] // CHUNK))


def channel_delta_cost(config, cell):
    """(FLOP, bytes) of one step's lane-decayed delta rule, forward and
    backward once each, over the `channel_delta_attention` layers."""
    group = config["linear_attn_config"]
    heads, d = group["num_heads"], group["head_dim"]
    tokens = cell["batch_per_chip"] * cell["length"]
    flops = 3 * tokens * heads * 6.0 * d * d
    lanes = tokens * heads * d
    forward = lanes * (3 * BF16 + F32 + BF16) + tokens * heads * F32
    backward = forward + lanes * (BF16 + 3 * BF16 + F32) \
        + tokens * heads * F32
    layers = delta_layers(config)
    return layers * flops, layers * float(forward + backward)


def scan_chunks():
    """(scan kernel calls traced, their chunks x heads), the program's
    two counters over every call traced in the process; None where the
    program keeps no such counters or no scan kernel was traced (a step
    on the XLA lowering)."""
    try:
        from paddle_tpu.observe.monitoring import runtime_stats
    except ImportError:
        return None
    snap = runtime_stats.snapshot()
    calls, chunks = (snap.get("channel_delta_calls"),
                     snap.get("channel_delta_chunks"))
    if not calls or not chunks:
        return None
    return calls, chunks
