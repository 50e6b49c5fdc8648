"""Self time on chip 0, in the traced window, of `kimilinear-8k`'s one
latent-attention layer, forward and backward, per step: the step
program's rows built under the `latent_attention` name scope (the ONE
direct query projection's two column blocks, the key/value latent's
down projection, its norm and up projections, no RoPE of any kind, and
the `flash_mla_fwd` / `_dkv` kernels at `joyai-8k`'s call shape), as
`device_ms_per_step.latent_attention` reads `joyai-8k`'s six blocks."""

import kernel_counts_joyai as scopes
import kernel_counts_kimi_linear as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["kimilinear-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.LATENT)
