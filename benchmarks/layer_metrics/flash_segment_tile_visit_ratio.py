"""Tiles the segment-confined attention's forward passes VISITED over
the tiles of their whole rectangles (every head's 24 x 24 tiles of 1024
x 1024 at 24576 rows), from the two counters each attention layer keeps
on the device (`paddle_tpu/observe/routing.py segment_tile_visits`:
int32 state the step adds to, read here once, after the window; every
step of the process).  The visited tiles are DATA, where the images'
bounds fell: 0.14-0.18 at the cell's images (10.2 % of the pairs are
allowed, and inside a visit the kernels run only the 256-row
sub-blocks that share a segment: the tiles FETCHED are counted here);
1.0 would mean the mask is applied and nothing skipped.  None
(left out) where a step fell back to the XLA lowering, or on a program
from before the counters."""

import kernel_counts_kimi_vl as counts

META = {"layer": "Pallas tier", "unit": "ratio", "moves": "mfu",
        "source": "program_counter", "cells": ["kimivl-8k"]}


def compute(run):
    visits = counts.tile_visits()
    return None if visits is None else visits[0] / visits[1]
