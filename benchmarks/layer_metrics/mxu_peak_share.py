"""100 x the FLOPs of the step program's `matmul` and `conv`
instructions (`observe/cost.py instruction_costs`, per step) over
their self seconds per step on chip 0 and the chip's bf16 peak: how
close the MXU work itself runs to peak.  With
`device_ms_per_step.matmul` it splits the gap to the north star into
"matmuls too slow" and "too much else"."""

import step_anatomy

META = {"layer": "ops", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": None}


def compute(run):
    a = step_anatomy.anatomy(run)
    if a is None:
        return None
    rows = [r for r in a["step_rows"]
            if r["bucket"] in step_anatomy.MXU_BUCKETS]
    seconds = sum(r["self_s"] for r in rows)
    peak = step_anatomy.peak_flops()
    if not seconds or not peak:
        return None
    flops = sum((r["flops"] or 0.0) * r["calls"] for r in rows)
    return 100.0 * flops / seconds / peak
