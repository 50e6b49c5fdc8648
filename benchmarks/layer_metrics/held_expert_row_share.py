"""100 x the rows (token, expert) routed to experts this chip holds
over all the rows its routed layers routed (tokens x k), from the two
counters the expert op keeps on the device
(`paddle_tpu/observe/routing.py`: int32 state the step adds to, read
here once, after the window).  Over every step of the process, the
warm-up steps included, and all routed layers: 12.5 under uniform
routing over 64 experts of which 8 are held.  `train_flops` counts the
held experts at that expectation; this is what the router really
sent.

A diagnostic, with no good direction of its own: BENCHMARK.json has to
give one and says "lower", the direction in which it moves `mfu` up
(fewer held rows are a shorter step against a fixed FLOP count), but a
reading under 12.5 only means that the other seven ranks carry more.
Read it as the distance from 12.5."""

import kernel_counts_lfm2 as counts

META = {"layer": "ops", "unit": "%", "moves": "mfu",
        "source": "program_counter", "cells": ["lfm2-8k"]}


def compute(run):
    share = counts.held_row_share()
    return None if share is None else 100.0 * share
