"""100 x the roofline time of one step's full causal flash attention
at 32 query heads of 128 over 4 key/value heads
(`kernel_counts_mellum.flash_grouped_cost`: seven matmuls over the
causal half against the bf16 peak, or its bytes with K, V, dK, dV at
the key/value heads' width against HBM bandwidth, whichever is larger)
over the measured self time of the `flash_fwd`, `flash_dkv` and
`flash_dq` kernels per step on chip 0 (the window kernels run under
other names).  The measured time holds the recomputed forward, the
roofline does not."""

import kernel_counts
import kernel_counts_mellum as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["mellum2-16k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.GROUPED_KERNELS,
                                        counts.flash_grouped_cost)
