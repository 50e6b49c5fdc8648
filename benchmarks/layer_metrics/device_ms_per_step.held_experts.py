"""Self time on chip 0, in the traced window, of the expert layers
that hold a share, forward and backward, per step: the step program's
rows under the `moe_dropless` op's scope (router, sigmoid, top-k, sort,
gathers, the masks of the rows held elsewhere, gate, combine) plus its
grouped-matmul kernels, as `device_ms_per_step.moe` reads `olmoe-4k`.
The buffers are T x k rows whatever the routing; an eighth of them are
real here."""

import kernel_counts
import kernel_counts_lfm2 as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["lfm2-8k"]}


def compute(run):
    return counts.op_ms_per_step(run, counts.EXPERT_OP,
                                 (kernel_counts.RAGGED_DOT,))
