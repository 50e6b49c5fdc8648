"""Self time on chip 0, in the traced window, of the delta-rule mixer
whose decay is a key lane's own, forward, recomputed and backward, per
step: the step program's rows built under the `channel_delta_attention`
name scope (the q, k, v projection and its short convolution, the two
low-rank gate pairs, beta, l2norm, softplus, kb / vb, the five
`channel_delta_*` kernels, the sigmoid-gated output norm and the out
projection), over all four such layers."""

import kernel_counts_joyai as scopes
import kernel_counts_kimi_linear as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["kimilinear-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.DELTA, (),
                                    counts.DELTA_KERNEL_NAMES)
