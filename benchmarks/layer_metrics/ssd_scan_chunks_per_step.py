"""Chunks of 256 positions x batch that the state-space scan's Pallas
kernels walk, from the counter the program keeps when a step build
traces a kernel call (`paddle_tpu/observe/monitoring.py
ssd_scan_chunks`; over every call traced in the process): 32 a call,
the calls being each mamba layer's forward, its forward as the
recompute segment's backward pass traces it again (the segment keeps
its results: that one does not run) and its backward.  None (left out)
where no kernel call was traced, or on a program from before the
counter."""

import kernel_counts_granite_hybrid as counts

META = {"layer": "Pallas tier", "unit": "count", "moves": "mfu",
        "source": "program_counter", "cells": ["granite4h-8k"]}


def compute(run):
    return counts.scan_chunks()
