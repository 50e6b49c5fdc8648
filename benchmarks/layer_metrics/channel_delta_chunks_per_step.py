"""Chunks x heads that the lane-decayed scan's Pallas kernels run a
step, from the counter the program keeps when a step build traces a
kernel call (`paddle_tpu/observe/monitoring.py channel_delta_chunks`;
over every call traced in the process): `channel_delta_calls` x 128
chunks x 32 heads, the calls being each delta layer's forward, its
recomputed forward and its backward.  None (left out) where a step fell
back to the XLA lowering of the scan, or on a program from before the
counters."""

import kernel_counts_kimi_linear as counts

META = {"layer": "Pallas tier", "unit": "count", "moves": "mfu",
        "source": "program_counter", "cells": ["kimilinear-8k"]}


def compute(run):
    traced = counts.scan_chunks()
    return None if traced is None else traced[1]
