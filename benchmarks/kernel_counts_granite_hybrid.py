"""The benchmark's own counts for what the `granite4h-8k` cell adds to
a step: the scan of the Mamba-2 state-space layers (the `ssd_scan_fwd`
/ `ssd_scan_bwd` kernels of `paddle_tpu/ops/pallas/ssd_scan.py`), their
one biased SiLU convolution over x, B and C together (the `short_conv_fwd`
/ `short_conv_bwd` kernels at 4352 channels: bytes alone) and
causal grouped-query attention at 32 query heads over 8 key/value heads
of 64 under a scale that is not d_head^-1/2, whatever flash kernels run
it.  For the readers in `layer_metrics/` that share them, beside
`kernel_counts.py` (whose `roofline_share` they use),
`kernel_counts_lfm2.py` (whose `flash_gqa_cost` counts this geometry:
imported, not edited), `kernel_counts_joyai.py` (whose
`scope_ms_per_step` reads a name scope's rows) and
`kernel_counts_phi4flash.py` (whose `_counters` reads the program's).

The scan's roofline is reckoned against the MATHEMATICS in its
sequential form, whatever chunks a kernel runs it in: a head's write
dt x B^T and its read-out S C, two d_head x d_state products a head a
token, 4 N P H FLOP a token a layer forward (2.10 M at 64 heads of 64 x
128 states) and twice that backward (the adjoint recurrence: dS's
update and its read-outs into dx and dB, dC), against the bf16 peak; or
its bytes once each against HBM bandwidth, whichever takes longer:
forward x and y at the heads' lanes in bfloat16, B and C at d_state,
the step a head in float32, the state that enters each chunk of 256
positions in float32; backward those operands and dy read, dx written
at the heads' lanes, dB and dC at d_state and the step's gradient a
head in float32, dA and dD a head.  The kernels execute about twice
that FLOP on the MXU (the chunked form: 4.26 M a token a layer forward,
at a contraction or an output of 64 lanes) and, a head a chunk, a 256 x
256 decay mask on the vector and transcendental units (an exponential,
a select, two multiplies and a cast an entry: 134 M entries a layer a
pass), for which `peaks.json` has no row: the share reads LOW by
construction and cannot pass 100.

They do not move when the program's HLO or its cost registry does.  It
sits beside `run.py`, not in `layer_metrics/`, where `run.py` takes
every `*.py` for a reader.
"""

from __future__ import annotations

import kernel_counts_lfm2 as lfm2
import kernel_counts_phi4flash as phi4flash

SSD_KERNELS = ("ssd_scan",)             # by prefix: _fwd and _bwd
FLASH_GQA_KERNELS = ("flash_gqa",)      # by prefix: _fwd, _dkv, _dq
SHORT_CONV_KERNELS = ("short_conv",)    # by prefix: _fwd and _bwd
STATE_SPACE_DUALITY, FULL_ATTENTION = "state_space_duality", "full_attention"
CHUNK = 256
BF16, F32 = 2, 4


def scan_layers(config):
    return config["layer_types"].count("mamba")


def ssd_scan_cost(config, cell):
    """(FLOP, bytes) of one step's scans, forward and backward once
    each, over the `mamba` layers: the sequential form's FLOP and every
    operand and result once."""
    n, t = cell["batch_per_chip"], cell["length"]
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    states = config["mamba_n_groups"] * config["mamba_d_state"]
    wide, narrow = n * t * heads * p * BF16, n * t * states * BF16
    step = n * t * heads * F32
    entry = n * -(-t // CHUNK) * heads * p * config["mamba_d_state"] * F32
    forward = 2 * wide + 2 * narrow + step + entry
    backward = (2 + 2) * wide + (2 + 2) * narrow + 2 * step + entry \
        + 2 * heads * F32
    flops = (1 + 2) * 4.0 * n * t * heads * p * config["mamba_d_state"]
    return (scan_layers(config) * flops,
            float(scan_layers(config) * (forward + backward)))


def short_conv_cost(config, cell):
    """(FLOP, bytes) of one step's joint convolutions over x, B and C,
    forward and backward once each, over the `mamba` layers: nothing
    for the MXU; forward reads xBC (T, d_inner + 2 G N) and writes the
    output, backward reads xBC and the output's gradient and writes
    xBC's gradient; bfloat16, once each.  The filter, the bias and
    their gradients are left out (taps / T of an operand)."""
    n, t = cell["batch_per_chip"], cell["length"]
    channels = config["mamba_n_heads"] * config["mamba_d_head"] \
        + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    return 0.0, float(scan_layers(config) * (2 + 3) * n * t * channels * BF16)


def flash_gqa_scaled_cost(config, cell):
    """(FLOP, bytes) of one step's causal grouped-query attention,
    forward and backward, over the `attention` layers:
    `kernel_counts_lfm2.flash_gqa_cost` of the same geometry (seven
    matmuls of T x T x d_head a query head at half for the causal mask;
    q, o, do, dq at the query heads' width and k, v, dk, dv at the
    key/value heads', bfloat16, once each).  The scale costs nothing:
    a power of two rides on q exactly."""
    kinds = ["full_attention" if k == "attention" else k
             for k in config["layer_types"]]
    return lfm2.flash_gqa_cost(dict(config, layer_types=kinds), cell)


def scan_chunks():
    """Chunks x batch the scan's kernels walk, summed over the calls
    traced in the process (a layer's forward, its forward traced again
    for a recompute segment's backward pass, its backward); None where
    the program keeps no such counter or no kernel call was traced."""
    counted = phi4flash._counters("ssd_scans_kernel", "ssd_scan_chunks")
    return counted[1] if counted and counted[0] else None


def scans_on_xla():
    """Scans traced on the XLA lowering (0 where every one took the
    kernels); None on a program without the counter."""
    counted = phi4flash._counters("ssd_scans_xla")
    return None if counted is None else counted[0]
