"""100 x the rows (token, expert) routed to the experts this chip
holds over all the rows its sparse layers routed (T x 6 a layer), from
the two counters the expert op keeps on the device
(`paddle_tpu/observe/routing.py`: int32 state the step adds to, read
here once, after the window; every step of the process, the warm-up
steps included, all four sparse layers).  `train_flops` counts the held
experts at the uniform expectation, 12.5 (8 of 64).

A diagnostic, with no good direction of its own: BENCHMARK.json has to
give one and says "lower", the direction in which it moves `mfu` up
(fewer held rows are a shorter step against a fixed FLOP count).  Read
it as the distance from 12.5: a reading far from it says the untrained
router no longer spreads its rows (PERF.md section 6, PRs 38, 51 and
64)."""

import kernel_counts_lfm2

META = {"layer": "ops", "unit": "%", "moves": "mfu",
        "source": "program_counter", "cells": ["kimivl-8k"]}


def compute(run):
    share = kernel_counts_lfm2.held_row_share()
    return None if share is None else 100.0 * share
