"""Self time on chip 0, in the traced window, of the attention layer's
operator, forward, recomputed and backward, per step: the step
program's rows built under the `full_attention` name scope in this cell
(the q, k, v and out projections at 32 / 8 heads of 64, the `flash_gqa`
kernels under the scale 1/64, with no RoPE and no QK-norm before them),
one layer of ten."""

import kernel_counts_granite_hybrid as counts
import kernel_counts_joyai as scopes

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["granite4h-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.FULL_ATTENTION)
