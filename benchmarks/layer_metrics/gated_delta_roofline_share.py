"""100 x the roofline time of one step's chunked delta-rule scan at 32
value heads of 128 x 128, 256 chunks of 64 positions a layer
(`kernel_counts_qwen3next.gated_delta_cost`: the twelve products a chunk
the two kernels execute against the bf16 peak, or their operands',
gradients' and saved states' bytes against HBM bandwidth, whichever is
larger: the bytes) over the measured self time of the `gated_delta_fwd`
and `gated_delta_bwd` kernels per step on chip 0.  The measured time
holds the recomputed forward and the rebuilt V', the roofline
neither."""

import kernel_counts
import kernel_counts_qwen3next as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["qwen3next-16k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.SCAN_KERNELS,
                                        counts.gated_delta_cost)
