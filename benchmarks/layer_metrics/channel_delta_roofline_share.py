"""100 x the roofline time of one step's lane-decayed delta rule at 32
heads of 128 x 128 over 8192 positions, four layers
(`kernel_counts_kimi_linear.channel_delta_cost`: the sequential form's
products against the bf16 peak, or the bytes ANY implementation reads
and writes, q, k, v, g, beta, o and their gradients, against HBM
bandwidth, whichever is larger: the bytes) over the measured self time
of the five `channel_delta_*` kernels per step on chip 0.  The measured
time holds the chunks' operands, the inverse, the recomputed forward
and the rebuilt V', the roofline none of them: it reads far under 100
whatever implements the scan."""

import kernel_counts
import kernel_counts_kimi_linear as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["kimilinear-8k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.DELTA_KERNELS,
                                        counts.channel_delta_cost)
