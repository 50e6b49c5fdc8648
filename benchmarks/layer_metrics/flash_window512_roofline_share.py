"""100 x the roofline time of one step's window flash attention at 64
query heads of 128 over 8 key/value heads, 512 keys a query
(`kernel_counts_laguna.flash_window_cost`: seven matmuls over the
band's 8,257,792 pairs a head against the bf16 peak, or its bytes with
K, V, dK, dV at the 8 key/value heads' width against HBM bandwidth,
whichever is larger) over the measured self time of the
`flash_window_fwd`, `_dkv` and `_dq` kernels per step on chip 0.  The
measured time holds every masked part of a tile the grid visits (half
of a 512 x 512 tile under 512 keys), the roofline neither: the share
says what the band costs against what it must."""

import kernel_counts
import kernel_counts_laguna as counts
import kernel_counts_mellum as bands

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["laguna-16k"]}


def compute(run):
    return kernel_counts.roofline_share(run, bands.WINDOW_KERNELS,
                                        counts.flash_window_cost)
