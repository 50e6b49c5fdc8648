"""100 x the roofline time of one step's causal flash attention
(`kernel_counts.flash_attention_cost`: seven half-masked matmuls a
head against the bf16 peak, or its bytes against HBM bandwidth,
whichever is larger) over the measured self time of the `flash_fwd`,
`flash_dkv` and `flash_dq` kernels per step on chip 0.  Compute-bound
at these shapes: the share says how close the kernels run to the
MXU."""

import kernel_counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["olmoe-4k"]}


def compute(run):
    return kernel_counts.roofline_share(
        run, kernel_counts.FLASH_KERNELS,
        kernel_counts.flash_attention_cost)
