"""Self time on chip 0, in the traced window, of the full attention
operator, forward, recomputed and backward, per step: the step
program's rows built under the `full_attention` name scope (the four
projections, QK-norm a head, RoPE under YaRN's frequencies, the
`flash_fwd` / `flash_dkv` kernels over grouped key/value heads), over
both full layers.  The builder opens the scope only in a program that
also has window layers."""

import kernel_counts_joyai as scopes
import kernel_counts_mellum as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["mellum2-16k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.FULL)
