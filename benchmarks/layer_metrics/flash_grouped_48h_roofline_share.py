"""100 x the roofline time of one step's causal flash attention over
the whole prefix at 48 query heads of 128 over 8 key/value heads, groups
of SIX (`kernel_counts_laguna.flash_grouped_cost`: seven matmuls over
the causal half's 134,225,920 pairs a head against the bf16 peak, or its
bytes with K, V, dK, dV at the key/value heads' width against HBM
bandwidth, whichever is larger) over the measured self time of the
`flash_fwd`, `flash_dkv` and `flash_dq` kernels per step on chip 0 (the
band kernels without a window keep those names).  The measured time
holds the masked half of every diagonal tile, the roofline neither."""

import kernel_counts
import kernel_counts_laguna as counts
import kernel_counts_mellum as bands

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["laguna-16k"]}


def compute(run):
    return kernel_counts.roofline_share(run, bands.GROUPED_KERNELS,
                                        counts.flash_grouped_cost)
