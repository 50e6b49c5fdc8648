"""100 x the roofline time of one step's nine grouped expert matmuls
(`kernel_counts.expert_matmul_cost`: T*k rows, never E x dense) over
the measured self time per step on chip 0 of the kernels the TPU
compiler lowers `jax.lax.ragged_dot` to."""

import kernel_counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["olmoe-4k"]}


def compute(run):
    return kernel_counts.roofline_share(
        run, (kernel_counts.RAGGED_DOT,),
        kernel_counts.expert_matmul_cost)
