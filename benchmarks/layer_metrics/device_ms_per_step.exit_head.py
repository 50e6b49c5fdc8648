"""Self time on chip 0, in the traced window, of the exit heads,
forward and backward, per step: the step program's rows built under
the `exit_head` name scope (each trip's final norm, vocabulary head,
token cross-entropy and 1-wide gate, and their recomputation)."""

import loop_rows

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["ouro-4k"]}


def compute(run):
    return loop_rows.scope_ms_per_step(run, loop_rows.EXIT_HEAD)
