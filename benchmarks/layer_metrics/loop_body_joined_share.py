"""100 x the device self time on chip 0, in the traced window, of the
rows under the `ut_loop` name scope that can be READ, over all self
time under it: a row counts when it has a bucket of its own (not
`loop`, the bucket of a `while` instruction's own time and of a body
instruction without a cost row, nor `unknown`), FLOPs where the bucket
is `matmul`, and a kernel's name where it is a Mosaic call.  The
health of the tracing inside the loop: if a later change leaves the
body's instructions without cost rows, nearly all of the step falls
into one unreadable bucket, and this falls to nothing."""

import loop_rows

META = {"layer": "ops", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": ["ouro-4k"]}


def compute(run):
    found = loop_rows.scoped_rows(run, loop_rows.LOOP)
    if found is None:
        return None
    rows, _ = found
    total = sum(r["self_s"] for r in rows)
    if not total:
        return None
    return 100.0 * sum(r["self_s"] for r in rows
                       if loop_rows.lit(r)) / total
