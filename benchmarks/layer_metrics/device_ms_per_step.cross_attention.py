"""Self time on chip 0, in the traced window, of the differential
cross-attention layer, forward, recomputed and backward, per step: the
step program's rows built under the `cross_attention` name scope (the
q and out projections, the grouped flash kernels over ANOTHER layer's
keys and values, whose dK and dV add into that layer's, `diff_combine`),
over the one such layer."""

import kernel_counts_joyai as scopes
import kernel_counts_phi4flash as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["phi4flash-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.CROSS)
