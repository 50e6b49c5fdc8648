"""100 x the roofline time of one step's nine grouped expert matmuls a
sparse layer at 2048 <-> 1408 over the rows the 8 held experts REALLY
got (`kernel_counts_kimi_vl.expert_matmul_cost`, rows from the
device-side counters, `kernel_counts_lfm2.held_rows_per_layer_step`:
mean a step and a layer) over the measured self time per step on chip 0
of the `ragged_dot` kernels.  The kernels are handed T x 6 rows of
buffer, an eighth of them real, and the measured time holds the forward
products a second time (every layer is a recompute segment): the share
says what the static shape and the recompute cost, the roofline
neither."""

import kernel_counts
import kernel_counts_kimi_vl as counts
import kernel_counts_lfm2

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["kimivl-8k"]}


def compute(run):
    rows = kernel_counts_lfm2.held_rows_per_layer_step(run["config"],
                                                       run["cell"])
    if rows is None:
        return None
    return kernel_counts.roofline_share(
        run, (kernel_counts.RAGGED_DOT,),
        lambda config, cell: counts.expert_matmul_cost(config, cell, rows))
