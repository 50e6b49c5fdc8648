"""100 x the roofline time of one step's window flash attention at 32
query heads of 128 over 4 key/value heads, 1024 keys a query
(`kernel_counts_mellum.flash_window_cost`: seven matmuls over the
band's pairs against the bf16 peak, or its bytes with K, V, dK, dV at
the key/value heads' width against HBM bandwidth, whichever is larger)
over the measured self time of the `flash_window_fwd`, `_dkv` and
`_dq` kernels per step on chip 0.  The measured time holds the
recomputed forward and every masked half block the grid visits, the
roofline neither: the share says what the band costs against what it
must."""

import kernel_counts
import kernel_counts_mellum as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["mellum2-16k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.WINDOW_KERNELS,
                                        counts.flash_window_cost)
