"""Self time on chip 0, in the traced window, of the loop over shared
weights without its exit heads, forward and backward, per step: the
step program's rows built under the `ut_loop` name scope and not under
`exit_head` (every layer pass of every trip, the recomputed ones too,
the flash kernels, and the `while` instructions' own time between
their bodies' events)."""

import loop_rows

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["ouro-4k"]}


def compute(run):
    return loop_rows.scope_ms_per_step(run, loop_rows.LOOP,
                                       without=loop_rows.EXIT_HEAD)
