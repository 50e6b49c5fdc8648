"""Self time on chip 0, in the traced window, of the `short_conv_fwd`
and `short_conv_bwd` kernels per step: the ONE causal convolution of a
Mamba-2 mixer over x, B and C together (4352 channels = 34 lane tiles,
4 taps, a bias, SiLU), nine layers, each layer's forward twice (a
`recompute: layer` segment runs it again) and its backward once.  The
splits around it ([z | xBC | dt], [x | B | C]) are XLA copies and are
not in it; `device_ms_per_step.state_space_duality` holds both.  No
other cell runs these kernels with a bias or at this width."""

import kernel_counts
import kernel_counts_granite_hybrid as counts

META = {"layer": "Pallas tier", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["granite4h-8k"]}


def compute(run):
    return kernel_counts.kernel_ms_per_step(run, counts.SHORT_CONV_KERNELS) \
        or None
