"""100 x the roofline time of one step's state-space scans
(`kernel_counts_granite_hybrid.ssd_scan_cost`: the MATHEMATICS in its
sequential form, two 64 x 128 products a head a token forward and four
backward, nine layers, against the bf16 peak, or x, y, B, C, the step,
the chunks' entry states and every gradient once each against HBM
bandwidth, whichever is larger) over the measured self time of the
`ssd_scan_fwd` and `ssd_scan_bwd` kernels per step on chip 0.  LOW by
construction: the kernels run the chunked matrix form, about twice that
FLOP at a contraction or an output of 64 lanes of the MXU's 128, and a
256 x 256 decay mask a head a chunk on the vector and transcendental
units, for which `peaks.json` has no row.  Read it as the distance from
the sequential form's arithmetic at the MXU's peak, not as the kernels'
room; a vector-unit peak is a `benchmark` issue's to add.  It cannot
pass 100."""

import kernel_counts
import kernel_counts_granite_hybrid as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["granite4h-8k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.SSD_KERNELS,
                                        counts.ssd_scan_cost)
