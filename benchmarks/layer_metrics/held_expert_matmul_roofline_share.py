"""100 x the roofline time of one step's nine grouped expert matmuls
over the rows the HELD experts really got
(`kernel_counts_lfm2.held_expert_matmul_cost`, rows from the
device-side counters: mean a step and a layer) over the measured self
time per step on chip 0 of the kernels the TPU compiler lowers
`jax.lax.ragged_dot` to.  The kernels are handed T x k rows of buffer,
an eighth of them real: the share says what the static shape costs."""

import kernel_counts
import kernel_counts_lfm2 as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["lfm2-8k"]}


def compute(run):
    rows = counts.held_rows_per_layer_step(run["config"], run["cell"])
    if rows is None:
        return None
    return kernel_counts.roofline_share(
        run, (kernel_counts.RAGGED_DOT,),
        lambda config, cell: counts.held_expert_matmul_cost(
            config, cell, rows))
