"""100 x the time the gated short convolutions' bytes take at the
chip's HBM bandwidth (`kernel_counts_lfm2.short_conv_bytes`: `BCu` and
the output forward; `BCu`, the output's gradient and `BCu`'s gradient
backward; bfloat16, once each, all conv layers) over the measured self
time per step on chip 0 of the rows under the `short_conv` scope.  The
op multiplies nothing on the MXU: bandwidth is its roofline."""

import kernel_counts
import kernel_counts_lfm2 as counts

META = {"layer": "ops", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["lfm2-8k"]}


def compute(run):
    ms = counts.op_ms_per_step(run, counts.SHORT_CONV)
    peak = kernel_counts.peaks()
    if not ms or not peak:
        return None
    nbytes = counts.short_conv_bytes(run["config"], run["cell"])
    return 100.0 * kernel_counts.roofline_ms(0.0, nbytes, peak) / ms
