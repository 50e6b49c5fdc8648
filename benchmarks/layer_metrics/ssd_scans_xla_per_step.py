"""State-space scans that a step build traced on the XLA lowering (the
chunks as einsums under a `lax.scan`) because the kernels do not tile
their shape, from the program's counter
(`paddle_tpu/observe/monitoring.py ssd_scans_xla`; over every call
traced in the process): 0 in the cell, whose 8192 positions x 64 heads
of 64 x 128 states in chunks of 256 the kernels take.  None (left out)
on a program from before the counter."""

import kernel_counts_granite_hybrid as counts

META = {"layer": "Pallas tier", "unit": "count", "moves": "mfu",
        "source": "program_counter", "cells": ["granite4h-8k"]}


def compute(run):
    return counts.scans_on_xla()
