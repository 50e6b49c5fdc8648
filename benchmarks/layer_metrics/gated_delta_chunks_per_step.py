"""Chunks x value heads that the scan's Pallas kernels run a step, from
the counter the program keeps when a step build traces a kernel call
(`paddle_tpu/observe/monitoring.py gated_delta_chunks`; over every call
traced in the process): `gated_delta_calls` x 256 chunks x 32 heads,
the calls being each linear layer's forward, its recomputed forward and
its backward.  None (left out) where a step fell back to the XLA
lowering of the scan, or on a program from before the counters."""

import kernel_counts_qwen3next as counts

META = {"layer": "Pallas tier", "unit": "count", "moves": "mfu",
        "source": "program_counter", "cells": ["qwen3next-16k"]}


def compute(run):
    traced = counts.scan_chunks()
    return None if traced is None else traced[1]
