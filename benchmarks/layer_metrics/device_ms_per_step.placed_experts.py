"""Self time on chip 0, in the traced window, of `sdar-8k`'s expert
layers, forward, recomputed and backward, per step: the step program's
rows under the `moe_dropless` op's scope (router, soft-max, top-k,
sort, gathers, the masks of the rows held elsewhere, gate, combine; the
`conditional` that takes a row buffer and what its branches leave it)
plus its grouped-matmul kernels, as `device_ms_per_step.held_experts`
reads `lfm2-8k`.  All 2 L rows are routed in every layer; an eighth of
the 2 L x k (token, expert) rows are real on this chip under the
placement."""

import kernel_counts
import kernel_counts_lfm2
import kernel_counts_sdar as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["sdar-8k"]}


def compute(run):
    return kernel_counts_lfm2.op_ms_per_step(
        run, counts.EXPERT_OP, (kernel_counts.RAGGED_DOT,))
