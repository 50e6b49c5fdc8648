"""100 x the roofline time of one step's differential attention at 40
query heads over 20 key/value heads of 64
(`kernel_counts_phi4flash.flash_diff_cost`: the MATHEMATICS, scores
over 64 lanes and values over 128 a head over the pairs each layer's
mask allows, 4,063,488 under the window of 512 and 33,558,528 over the
whole prefix and in the cross layer, forward and backward, against the
bf16 peak, or its bytes against HBM bandwidth, whichever is larger)
over the measured self time of every flash kernel of the step
(`flash_window_*` in the window layer, `flash_fwd` / `flash_dkv` /
`flash_dq` in the whole-prefix and the cross layer) per step on chip
0.  The kernels contract 128 lanes where the mathematics has 64 (a
head padded with zeros) and hold every masked part of a tile the grid
visits; the roofline neither."""

import kernel_counts
import kernel_counts_phi4flash as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["phi4flash-8k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.FLASH_KERNELS,
                                        counts.flash_diff_cost)
