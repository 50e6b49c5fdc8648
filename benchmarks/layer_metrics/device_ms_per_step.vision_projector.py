"""Self time on chip 0, in the traced window, of `kimivl-8k`'s patch
merger and projector, forward, recomputed and backward, per step: the
step program's rows built under the `vision_projector` name scope (the
merger's LayerNorm, the 4608 -> 4608 and 4608 -> 2048 matrices with
their biases, the exact GELU)."""

import kernel_counts_joyai as scopes
import kernel_counts_kimi_vl as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["kimivl-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.PROJECTOR)
