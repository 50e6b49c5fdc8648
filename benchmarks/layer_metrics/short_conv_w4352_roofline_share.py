"""100 x the time the joint convolutions' bytes take at the chip's HBM
bandwidth (`kernel_counts_granite_hybrid.short_conv_cost`: xBC and the
output forward; xBC, the output's gradient and xBC's gradient backward;
4352 channels, bfloat16, once each, nine layers) over the measured self
time of the `short_conv_fwd` and `short_conv_bwd` kernels per step on
chip 0.  The kernels multiply nothing on the MXU: bandwidth is their
roofline.  A `recompute: layer` segment runs each layer's forward
kernel a second time, which the count leaves out (the mathematics needs
it once), so the share reads lower than the kernels' own 81 % in
`lfm2-8k`; it cannot pass 100."""

import kernel_counts
import kernel_counts_granite_hybrid as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["granite4h-8k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.SHORT_CONV_KERNELS,
                                        counts.short_conv_cost)
