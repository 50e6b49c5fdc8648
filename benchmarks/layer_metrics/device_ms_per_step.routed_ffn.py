"""Self time on chip 0, in the traced window, of the routed
feed-forward layers, forward and backward, per step: the step
program's rows under the `moe_dropless` op's scope (router, sigmoid,
top-k, sorts, the held section at the row buffer taken, gate, combine),
its grouped-matmul kernels, and the rows built under the
`shared_expert` name scope (the dense SwiGLU every token goes through),
over the expert layers and the prediction module's block."""

import kernel_counts
import kernel_counts_joyai as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["joyai-8k"]}


def compute(run):
    return counts.scope_ms_per_step(run, counts.SHARED_EXPERT,
                                    (counts.EXPERT_OP,),
                                    (kernel_counts.RAGGED_DOT,))
