"""Selective scans that a step build traced on the XLA lowering (a
`lax.scan` over chunks with an `associative_scan` inside) because the
kernels do not tile their shape, from the program's counter
(`paddle_tpu/observe/monitoring.py selective_scans_xla`; over every
call traced in the process): 0 in the cell, whose 8192 positions x 5120
channels x 16 states the kernels take.  None (left out) on a program
from before the counter."""

import kernel_counts_phi4flash as counts

META = {"layer": "Pallas tier", "unit": "count", "moves": "mfu",
        "source": "program_counter", "cells": ["phi4flash-8k"]}


def compute(run):
    return counts.scans_on_xla()
