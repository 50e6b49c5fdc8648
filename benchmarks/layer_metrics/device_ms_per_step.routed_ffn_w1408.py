"""Self time on chip 0, in the traced window, of `kimivl-8k`'s routed
feed-forward layers, forward, recomputed and backward, per step: the
step program's rows under the `moe_dropless` op's scope (the 64-wide
router, sigmoid, the selection bias, top-6, sorts, the held section at
the row buffer taken, gate, combine), its grouped-matmul kernels at
expert width 1408, and the rows built under the `shared_expert` name
scope (the dense SwiGLU of width 2816 every token goes through), over
the four sparse layers, as `device_ms_per_step.routed_ffn` reads
`joyai-8k`."""

import kernel_counts
import kernel_counts_joyai as scopes

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["kimivl-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, scopes.SHARED_EXPERT,
                                    (scopes.EXPERT_OP,),
                                    (kernel_counts.RAGGED_DOT,))
