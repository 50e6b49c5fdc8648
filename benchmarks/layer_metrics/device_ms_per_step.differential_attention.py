"""Self time on chip 0, in the traced window, of the differential
attention layers that have their own keys and values, forward,
recomputed and backward, per step: the step program's rows built under
the `differential_attention` name scope (the biased q, k, v and out
projections, the heads padded to 128 lanes and the value pairs
repeated, the band kernels under the window of 512 and the grouped
kernels over the whole prefix at 40 / 20 heads, `diff_combine`), over
the window layer and the whole-prefix layer."""

import kernel_counts_joyai as scopes
import kernel_counts_phi4flash as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["phi4flash-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.DIFFERENTIAL)
