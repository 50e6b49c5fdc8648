"""Self time on chip 0, in the traced window, of the window attention
operator, forward, recomputed and backward, per step: the step
program's rows built under the `sliding_attention` name scope (the four
projections, QK-norm a head, RoPE, the `flash_window_fwd` / `_dkv` /
`_dq` kernels), over all six window layers."""

import kernel_counts_joyai as scopes
import kernel_counts_mellum as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["mellum2-16k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.SLIDING)
