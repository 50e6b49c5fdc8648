"""Self time on chip 0, in the traced window, of the step program's
Mosaic kernels (`tpu_custom_call`: the Pallas kernels of `ops/pallas`
and the TPU compiler's own grouped matmul), per step.  The compiler's
zero-work custom calls (ConcatBitcast, ...) and the grouped matmul's
metadata helper are not kernels and do not count.  0 in a cell whose
step runs no kernel."""

import kernel_counts

META = {"layer": "Pallas tier", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": None}


def compute(run):
    return kernel_counts.kernel_ms_per_step(run)
