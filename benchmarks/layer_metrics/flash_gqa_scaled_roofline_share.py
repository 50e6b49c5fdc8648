"""100 x the roofline time of one step's causal grouped-query attention
at 32 query heads over 8 key/value heads of 64
(`kernel_counts_granite_hybrid.flash_gqa_scaled_cost`: the mathematics
of `lfm2-8k`'s geometry, seven matmuls of T x T x 64 a query head over
the causal half, forward and backward, one layer, against the bf16
peak, or its bytes against HBM bandwidth, whichever is larger) over the
measured self time of the `flash_gqa` kernels per step on chip 0, in
THIS cell: the same kernels and tiles as `lfm2-8k`'s, under the scale
1/64 (a power of two, on q) in place of 64^-1/2 (on the scores), with
no RoPE and no QK-norm before them."""

import kernel_counts
import kernel_counts_granite_hybrid as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["granite4h-8k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.FLASH_GQA_KERNELS,
                                        counts.flash_gqa_scaled_cost)
