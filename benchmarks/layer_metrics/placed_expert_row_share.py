"""100 x the rows (token, expert) routed to the experts this chip
holds over all the rows its layers routed (2 L x k a layer), from the
two counters the expert op keeps on the device
(`paddle_tpu/observe/routing.py`: int32 state the step adds to, read
here once, after the window; every step of the process, the warm-up
steps included, all layers).  `train_flops` counts the held experts at
the uniform expectation, 12.5 (16 of 128); the placement
(`models/sdar_moe.py place_experts`: each rank one of the eight experts
that the mask id's rows, a quarter of all, take) is what keeps a seed's
share there: left as drawn the share read 10.6 .. 13.4 by the seed and
single layers 8.9 .. 19.2 (PERF.md, PR 47).

A diagnostic, with no good direction of its own: BENCHMARK.json has to
give one and says "lower", the direction in which it moves `mfu` up
(fewer held rows are a shorter step against a fixed FLOP count).  Read
it as the distance from 12.5: a reading far from it says the placement
no longer holds (the mask rows' choice moved off what start-up
placed)."""

import kernel_counts_lfm2

META = {"layer": "ops", "unit": "%", "moves": "mfu",
        "source": "program_counter", "cells": ["sdar-8k"]}


def compute(run):
    share = kernel_counts_lfm2.held_row_share()
    return None if share is None else 100.0 * share
