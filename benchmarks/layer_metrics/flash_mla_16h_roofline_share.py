"""100 x the roofline time of one step's latent-attention kernels at
`kimivl-8k`'s call shape, the THIRD of those kernels: 16 heads (not
`joyai-8k`'s and `kimilinear-8k`'s 32) over 8192 positions, five layers
(`kernel_counts_kimi_vl.flash_mla_cost`: `kernel_counts_joyai`'s count,
320 + 640 + 512 matmul lanes a causal score pair a head against the
bf16 peak, or the bytes with the rotary key at ONE head's width) over
the measured self time of the `flash_mla_fwd`, `flash_mla_dkv` and
`flash_mla_dq` kernels per step on chip 0."""

import kernel_counts
import kernel_counts_joyai
import kernel_counts_kimi_vl as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["kimivl-8k"]}


def compute(run):
    return kernel_counts.roofline_share(
        run, kernel_counts_joyai.FLASH_MLA_KERNELS, counts.flash_mla_cost)
