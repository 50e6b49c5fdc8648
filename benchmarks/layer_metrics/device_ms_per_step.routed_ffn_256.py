"""Self time on chip 0, in the traced window, of `laguna-16k`'s routed
expert layers, forward, recomputed and backward, per step: the step
program's rows under the `moe_dropless` op's scope (the 256-wide
router, soft-max, top-8, sort, gathers, the masks of the rows held
elsewhere, gate, combine; the `conditional` that takes a row buffer and
what its branches leave it) plus its grouped-matmul kernels, over the
four sparse layers, as `device_ms_per_step.held_experts` reads
`lfm2-8k`.  The shared expert beside them is not in it (its rows carry
the `shared_expert` scope)."""

import kernel_counts
import kernel_counts_lfm2

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["laguna-16k"]}


def compute(run):
    return kernel_counts_lfm2.op_ms_per_step(
        run, kernel_counts_lfm2.EXPERT_OP, (kernel_counts.RAGGED_DOT,))
