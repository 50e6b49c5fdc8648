"""The benchmark's own counts for what the `phi4flash-8k` cell adds to
a step: the selective scan of the state-space layers (the
`selective_scan_fwd` / `selective_scan_bwd` kernels of
`paddle_tpu/ops/pallas/selective_scan.py`) and differential attention
at 40 query heads over 20 key/value heads of 64 (under a window of 512,
over the whole prefix, and as cross-attention on another layer's keys
and values), whatever flash kernels run it.  For the readers in
`layer_metrics/` that share them, beside `kernel_counts.py` (whose
`kernel_of`, `peaks`, `roofline_ms` and `roofline_share` they use),
`kernel_counts_mellum.py` (the pairs of a band and of the causal half)
and `kernel_counts_joyai.py` (whose `scope_ms_per_step` reads a name
scope's rows).

The scan multiplies nothing on the MXU: an exponential and some seven
vector operations a (position, channel, state), 671 M of them a layer a
pass.  `peaks.json` has no row for the vector or the transcendental
unit, so its roofline is reckoned against its BYTES alone (as
`short_conv_roofline_share` is) and reads LOW: the share says how far
the kernels are from moving their operands at HBM speed, which is not
what bounds them.  Bytes, once each, what the ALGORITHM moves: forward
u, Delta and y at the channels' width in bfloat16, B and C at 16, the
state that enters each chunk of 256 positions in float32; backward
those operands and dy read, dU and dDelta written at the channels'
width, dB and dC at 16, dA, dD and the step bias's gradient a channel in
float32.  (The kernels read B and C as lane-broadcast tiles and write
dB and dC a lane, 8 x and 64 x these; the time the share divides by
holds that.)

Differential attention, the MATHEMATICS whatever runs: for each of the
query heads, scores over head_dim lanes and values over the pair's
2 x head_dim, over the pairs the MASK allows; forward scores and
values, backward scores again, dP, dV (2 x head_dim each) and dK, dQ
(head_dim each).  Bytes, bfloat16, once each: q and dq at the query
heads' width, twice q; the two maps' contexts (heads x 2 head_dim) and
their gradient, twice the contexts; k, v forward and k, v, dk, dv
backward at the KEY/VALUE heads' width.  The kernels that run it
contract 128 lanes where the mathematics has 64 (a head padded with
zeros) and read each value pair twice: the time the share divides by
holds both.

They do not move when the program's HLO or its cost registry does.  A
share of a roofline cannot pass 100%.  It sits beside `run.py`, not in
`layer_metrics/`, where `run.py` takes every `*.py` for a reader.
"""

from __future__ import annotations

import kernel_counts_mellum as bands

SCAN_KERNELS = ("selective_scan",)      # by prefix: _fwd and _bwd
FLASH_KERNELS = ("flash",)              # every flash kernel of the cell
STATE_SPACE, GATED_MEMORY = "state_space", "gated_memory"   # name scopes
DIFFERENTIAL, CROSS = "differential_attention", "cross_attention"
CHUNK = 256
BF16, F32 = 2, 4
ATTENTION = ("sliding_attention", "full_attention", "cross_attention")


def channels(config):
    return config["mamba_expand"] * config["hidden_size"]


def scan_layers(config):
    return config["layer_types"].count("mamba")


def selective_scan_cost(config, cell):
    """(0 FLOP, bytes) of one step's scan kernels, forward and backward
    once each, over the `mamba` layers."""
    n, t = cell["batch_per_chip"], cell["length"]
    d, s = channels(config), config["mamba_d_state"]
    wide, narrow = n * t * d * BF16, n * t * s * BF16
    entry = n * -(-t // CHUNK) * d * s * F32
    forward = 3 * wide + 2 * narrow + entry
    backward = (3 + 2) * wide + (2 + 2) * narrow + entry \
        + d * s * F32 + 2 * d * F32
    return 0.0, float(scan_layers(config) * (forward + backward))


def pairs_of(config, cell, kind):
    """Score pairs a head that a layer of type `kind` allows."""
    if kind == "sliding_attention":
        return bands.band_pairs(cell["length"], config["sliding_window"])
    return bands.causal_pairs(cell["length"])


def flash_diff_cost(config, cell):
    """(FLOP, bytes) of one step's differential attention, forward and
    backward (a recomputed forward not counted), over the window, the
    whole-prefix and the cross layers."""
    n, t = cell["batch_per_chip"], cell["length"]
    heads = config["num_attention_heads"]
    d = config["hidden_size"] // heads
    kv = config["num_key_value_heads"] * d
    # lanes a (query head, pair): scores d and values 2 d forward;
    # scores again d, dP 2 d, dV 2 d, dK d, dQ d backward
    lanes = (d + 2 * d) + (d + 2 * d + 2 * d + d + d)
    flops = nbytes = 0.0
    for kind in ATTENTION:
        layers = config["layer_types"].count(kind)
        flops += layers * 2.0 * n * heads * lanes * pairs_of(config, cell,
                                                             kind)
        nbytes += layers * float(n * t * BF16 * (
            3 * heads * d + 3 * heads * 2 * d + 6 * kv))
    return flops, nbytes


def _counters(*names):
    """The program's counters `names` over every call traced in the
    process, or None where the program keeps none of that name."""
    try:
        from paddle_tpu.observe.monitoring import runtime_stats
    except ImportError:
        return None
    snap = runtime_stats.snapshot()
    if any(name not in snap for name in names):
        return None
    return tuple(snap[name] for name in names)


def scan_chunks():
    """Chunks x batch the scan's kernels walk, summed over the calls
    traced in the process (a layer's forward, its forward traced again
    for a recompute segment's backward pass, its backward); None where
    the program keeps no such counter or no kernel call was traced."""
    counted = _counters("selective_scans_kernel", "selective_scan_chunks")
    return counted[1] if counted and counted[0] else None


def scans_on_xla():
    """Scans traced on the XLA lowering (0 where every one took the
    kernels); None on a program without the counter."""
    counted = _counters("selective_scans_xla")
    return None if counted is None else counted[0]
