"""Self time on chip 0, in the traced window, of the attention
operators of `kimivl-8k`'s vision tower, forward and backward, per
step: the step program's rows built under the `vision_attention` name
scope (q, k, v and the out projection with their biases, the rotary
turn over (row, column), the heads' layout at 128 lanes and back) and
the `flash_segment_fwd` / `_bwd` kernels, over the eight layers."""

import kernel_counts_joyai as scopes
import kernel_counts_kimi_vl as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["kimivl-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.ATTENTION,
                                    kernels=counts.SEGMENT_KERNEL_NAMES)
