"""100 x the roofline time of one step's segment-confined attention
(`kernel_counts_kimi_vl.flash_segment_cost`: the ALLOWED pairs of the
cell's images, every patch of an image against every patch of the same
image, seven score-sized products of 2 x 72 lanes a pair a head against
the bf16 peak, or q, k, v, o and their gradients once each at 72 lanes
a head against HBM bandwidth, whichever is larger: the products) over
the measured self time of the `flash_segment_fwd` and `flash_segment_bwd`
kernels per step on chip 0.  A head's 72 lanes fill 72 of a 128-deep
MXU pass: 56 % is what the geometry allows before a tile is visited
that a boundary crosses."""

import kernel_counts
import kernel_counts_kimi_vl as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["kimivl-8k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.SEGMENT_KERNELS,
                                        counts.flash_segment_cost)
