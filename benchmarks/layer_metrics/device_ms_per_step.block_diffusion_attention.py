"""Self time on chip 0, in the traced window, of the attention operator
under the block-diffusion mask, forward, recomputed and backward, per
step: the step program's rows built under the
`block_diffusion_attention` name scope (the four projections, QK-norm a
head, RoPE with restarting positions, the `flash_block_diffusion_fwd` /
`_dkv` kernels), over all layers and the 2 L rows of each."""

import kernel_counts_joyai as scopes
import kernel_counts_sdar as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["sdar-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.SCOPE)
