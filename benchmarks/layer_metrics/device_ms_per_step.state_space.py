"""Self time on chip 0, in the traced window, of the Mamba-1 state-space
mixers, forward, recomputed and backward, per step: the step program's
rows built under the `state_space` name scope (the in, step / B / C,
step and out projections, the biased short convolution's kernels, the
`selective_scan_fwd` / `_bwd` kernels and the lane-broadcast copies of
B and C they read, the gate), over the two mamba layers."""

import kernel_counts_joyai as scopes
import kernel_counts_phi4flash as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["phi4flash-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.STATE_SPACE)
