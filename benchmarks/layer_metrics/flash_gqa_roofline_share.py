"""100 x the roofline time of one step's causal grouped-query flash
attention at 32 query heads of 64 over 8 key/value heads
(`kernel_counts_lfm2.flash_gqa_cost`: seven half-masked matmuls a
query head against the bf16 peak, or its bytes with K, V, dK, dV at the
key/value heads' width against HBM bandwidth, whichever is larger) over
the measured self time of the `flash_gqa_fwd`, `flash_gqa_dkv` and
`flash_gqa_dq` kernels per step on chip 0.  A 64-deep contraction
fills half the MXU: 50% is what the geometry allows."""

import kernel_counts
import kernel_counts_lfm2 as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["lfm2-8k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.FLASH_GQA_KERNELS,
                                        counts.flash_gqa_cost)
