"""100 x the roofline time of one step's latent-attention kernels
(`kernel_counts_joyai.flash_mla_cost`: 320 + 640 + 512 matmul lanes a
causal score pair of each of the 32 heads against the bf16 peak, or
their bytes with the rotary key at ONE head's width against HBM
bandwidth, whichever is larger) over the measured self time of the
`flash_mla_fwd`, `flash_mla_dkv` and `flash_mla_dq` kernels per step on
chip 0.  The rotary 64 lanes contract half the MXU's depth: of the
1472 lanes a pair, 384 run at half rate, so 87% is what the geometry
allows."""

import kernel_counts
import kernel_counts_joyai as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["joyai-8k"]}


def compute(run):
    return kernel_counts.roofline_share(run, counts.FLASH_MLA_KERNELS,
                                        counts.flash_mla_cost)
