"""Self time on chip 0, in the traced window, of the Mamba-2 state-space
mixers, forward, recomputed and backward, per step: the step program's
rows built under the `state_space_duality` name scope (the three in
projections and the out projection, the biased short convolution's
kernels over x, B and C together, the step's softplus and the
cumulative decays XLA makes of it, the `ssd_scan_fwd` / `_bwd` kernels,
the gated norm), over the nine mamba layers.  None (left out) on a
program whose rows carry no such scope."""

import kernel_counts_granite_hybrid as counts
import kernel_counts_joyai as scopes

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["granite4h-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.STATE_SPACE_DUALITY)
