"""Self time on chip 0, in the traced window, of latent attention,
forward and backward, per step: the step program's rows built under
the `latent_attention` name scope (the five projections, the two
latents' norms, RoPE over pairs, and the `flash_mla_fwd` / `_dkv` /
`_dq` kernels), over the layers and the prediction module's block."""

import kernel_counts_joyai as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["joyai-8k"]}


def compute(run):
    return counts.scope_ms_per_step(run, counts.LATENT_ATTENTION)
