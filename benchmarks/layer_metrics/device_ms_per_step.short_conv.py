"""Self time on chip 0, in the traced window, of the gated short
convolutions, forward and backward, per step: the step program's rows
under the `short_conv` op's scope (the products B * u and C * conv, the
taps, the backward's recomputation and the filter gradient's
reduction), all conv layers.  The projections on either side are
matmuls and are not in it.  The op lowers to XLA fusions: it has no
kernel of its own to add."""

import kernel_counts_lfm2 as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["lfm2-8k"]}


def compute(run):
    return counts.op_ms_per_step(run, counts.SHORT_CONV)
