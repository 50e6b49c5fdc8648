"""100 x the roofline time of one step's causal flash attention at 16
query heads of 256 over 2 key/value heads
(`kernel_counts_qwen3next.flash_d256_cost`: seven matmuls over the
causal half against the bf16 peak, or its bytes with K, V, dK, dV at
the key/value heads' width against HBM bandwidth, whichever is larger)
over the measured self time of the `flash_fwd`, `flash_dkv` and
`flash_dq` kernels per step on chip 0.  At this head size a sequence of
16384 is past the single backward kernel's budget: `flash_dkv` and
`flash_dq` each recompute the scores, the roofline counts them once."""

import kernel_counts
import kernel_counts_qwen3next as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["qwen3next-16k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.FLASH_KERNELS,
                                        counts.flash_d256_cost)
