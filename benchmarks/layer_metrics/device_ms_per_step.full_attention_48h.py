"""Self time on chip 0, in the traced window, of the full attention
operator at 48 query heads over 8 key/value heads, forward, recomputed
and backward, per step: the step program's rows built under the
`full_attention` name scope (the four projections, 6144 wide in q and
o, QK-norm a head and YaRN's RoPE over the first 64 lanes, the
`flash_fwd` / `flash_dkv` kernels over the whole prefix, the head
gate), over the two full layers."""

import kernel_counts_joyai as scopes
import kernel_counts_mellum as bands

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["laguna-16k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, bands.FULL)
