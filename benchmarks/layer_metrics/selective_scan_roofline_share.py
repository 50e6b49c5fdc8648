"""100 x the time the selective scan's bytes take at the chip's HBM
bandwidth (`kernel_counts_phi4flash.selective_scan_cost`: u, Delta, B,
C, y, the chunks' entry states and every gradient, once each, two
layers, forward and backward) over the measured self time of the
`selective_scan_fwd` and `selective_scan_bwd` kernels per step on chip
0.  BY BYTES, and so LOW by construction: the kernels multiply nothing
on the MXU and move little; what bounds them is the vector and the
transcendental units (an exponential and some seven vector operations
a (position, channel, state)), for which `peaks.json` has no row.  Read
it as the distance from a pass at HBM speed, not as the kernels' room;
a vector-unit peak is a `benchmark` issue's to add."""

import kernel_counts
import kernel_counts_phi4flash as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["phi4flash-8k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.SCAN_KERNELS,
                                        counts.selective_scan_cost)
