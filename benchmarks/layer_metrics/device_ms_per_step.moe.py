"""Self time on chip 0, in the traced window, of the routed-expert
layer, forward and backward, per step: the step program's rows under
the `moe_dropless` op's scope (router, top-k, sort, gathers, gate,
combine) plus its grouped-matmul kernels.  The TPU compiler replaces
a ragged dot's `op_name` with its own (`ragged-dot-none`), so those
kernels carry no fluid scope and are found by kernel name; the expert
op is the only one in the program that lowers to them."""

import kernel_counts
import step_anatomy

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["olmoe-4k"]}


def compute(run):
    a = step_anatomy.anatomy(run)
    if a is None:
        return None
    seconds = sum(
        r["self_s"] for r in a["step_rows"]
        if r["op_type"] == "moe_dropless"
        or kernel_counts.kernel_of(r) == kernel_counts.RAGGED_DOT)
    return 1e3 * seconds / a["steps"]
