"""Self time on chip 0, in the traced window, of `kimivl-8k`'s five
latent-attention blocks, forward and backward, per step: the step
program's rows built under the `latent_attention` name scope (the ONE
direct query projection, the key/value latent's down projection, its
norm and up projections, RoPE over pairs on the rotary lanes, the out
projection, and the `flash_mla_fwd` / `_dkv` / `_dq` kernels at 16
heads), as `device_ms_per_step.latent_attention` reads `joyai-8k`'s
six blocks at 32."""

import kernel_counts_joyai as scopes

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["kimivl-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, scopes.LATENT_ATTENTION)
