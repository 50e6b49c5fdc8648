"""Self time on chip 0, in the traced window, of the gated-delta-rule
mixer, forward, recomputed and backward, per step: the step program's
rows built under the `linear_attention` name scope (the q, k, v, z and
b, a projections, the short convolution, the batch part of the chunked
scan, the `gated_delta_fwd` / `gated_delta_bwd` kernels, the gated
output norm and the out projection), over all three linear layers."""

import kernel_counts_joyai as scopes
import kernel_counts_qwen3next as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["qwen3next-16k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.LINEAR)
