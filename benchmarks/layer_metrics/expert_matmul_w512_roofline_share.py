"""100 x the roofline time of one step's nine grouped expert matmuls a
sparse layer at 2048 <-> 512 over the rows the 32 held experts REALLY
got (`kernel_counts_laguna.expert_matmul_cost`, rows from the
device-side counters, `kernel_counts_lfm2.held_rows_per_layer_step`:
mean a step and a layer) over the measured self
time per step on chip 0 of the `ragged_dot` kernels.  The measured time
holds the forward products a second time (every layer is a recompute
segment) and whatever the row buffer's tiles past the held rows cost;
the roofline neither."""

import kernel_counts
import kernel_counts_laguna as counts
import kernel_counts_lfm2

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["laguna-16k"]}


def compute(run):
    rows = kernel_counts_lfm2.held_rows_per_layer_step(run["config"],
                                                       run["cell"])
    if rows is None:
        return None
    return kernel_counts.roofline_share(
        run, (kernel_counts.RAGGED_DOT,),
        lambda config, cell: counts.expert_matmul_cost(config, cell, rows))
