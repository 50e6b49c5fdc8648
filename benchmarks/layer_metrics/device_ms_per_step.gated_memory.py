"""Self time on chip 0, in the traced window, of the gated memory unit,
forward, recomputed and backward, per step: the step program's rows
built under the `gated_memory` name scope (the in projection, the gate
against ANOTHER layer's scan output, the out projection), over the one
such layer."""

import kernel_counts_joyai as scopes
import kernel_counts_phi4flash as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["phi4flash-8k"]}


def compute(run):
    return scopes.scope_ms_per_step(run, counts.GATED_MEMORY)
