"""100 x the roofline time of one step's flash attention under the
block-diffusion mask at 32 query heads of 128 over 4 key/value heads
(`kernel_counts_sdar.flash_block_diffusion_cost`: seven matmuls over
the pairs the mask allows, 67,141,632 a head a layer at L 8192 and B 4,
against the bf16 peak, or its bytes with K, V, dK, dV at the key/value
heads' width against HBM bandwidth, whichever is larger) over the
measured self time of the `flash_block_diffusion_fwd`, `_dkv` and `_dq`
kernels per step on chip 0.  The measured time holds every masked part
of a diagonal tile and every grid step that computes nothing, the
roofline neither: the share says what the mask costs against what it
must."""

import kernel_counts
import kernel_counts_sdar as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["sdar-8k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.KERNELS,
                                        counts.flash_block_diffusion_cost)
