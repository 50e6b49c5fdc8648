"""Self time on chip 0, in the traced window, of the gated full
attention operator, forward, recomputed and backward, per step: the
step program's rows built under the `gated_attention` name scope (the
q, gate, k, v and o projections, QK-norm a head, RoPE over a quarter of
the lanes, the `flash_fwd` / `flash_dkv` / `flash_dq` kernels at d_head
256, the sigmoid gate), the one full layer."""

import kernel_counts_joyai as scopes
import kernel_counts_qwen3next as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["qwen3next-16k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.GATED)
