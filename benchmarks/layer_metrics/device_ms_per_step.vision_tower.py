"""Self time on chip 0, in the traced window, of `kimivl-8k`'s vision
tower, forward and backward, per step: the step program's rows built
under the `vision_tower` name scope (the patch embedding, the position
table's taps, eight layers of LayerNorm, q / k / v, the rotary turn
over two axes, the lane layout around the kernels, the out projection
and the tanh-GELU MLP, the last norm) and the `flash_segment_fwd` /
`_bwd` kernels, which lie inside it."""

import kernel_counts_joyai as scopes
import kernel_counts_kimi_vl as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["kimivl-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.TOWER,
                                    kernels=counts.SEGMENT_KERNEL_NAMES)
