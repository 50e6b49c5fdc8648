"""Self time on chip 0, in the traced window, of the window attention
operator at 64 query heads over 8 key/value heads, forward, recomputed
and backward, per step: the step program's rows built under the
`sliding_attention` name scope (the four projections, 8192 wide in q
and o, QK-norm a head and RoPE over the whole head, the
`flash_window_fwd` / `_dkv` kernels under 512 keys, the head gate), over
the three window layers."""

import kernel_counts_joyai as scopes
import kernel_counts_mellum as bands

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["laguna-16k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, bands.SLIDING)
