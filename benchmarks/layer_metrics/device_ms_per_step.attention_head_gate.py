"""Self time on chip 0, in the traced window, of the per-head output
gate, forward, recomputed and backward, per step: the step program's
rows built under the `attention_head_gate` name scope (the gate's
projection hidden -> the layer's heads, its sigmoid, and the product of
each head's 128-lane context with its gate), over all five layers.  A
LOWER bound: XLA fuses across name scopes, so the product may ride in a
fusion that carries the scope of the cast or the out projection beside
it and is then not counted here."""

import kernel_counts_joyai as scopes
import kernel_counts_laguna as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["laguna-16k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.HEAD_GATE)
