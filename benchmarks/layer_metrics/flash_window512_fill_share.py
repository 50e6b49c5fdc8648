"""100 x the score pairs a head that the window's mask allows over the
score entries the forward grid's visited tiles compute, from the
program's two counters
(`paddle_tpu/observe/monitoring.py flash_window_pairs_allowed` /
`flash_window_entries_computed`, trace time, summed over the window
calls traced): how full the tiles are that the window kernel computes.
What `flash_window_block_visit_ratio` cannot see: a grid that skips
every tile outside the band reads 1.0 there while each tile it visits
is a quarter full.  Under 512 keys 1024 x 1024 tiles read 25, 512 x 512
tiles 49-50, 256 x 256 tiles 66; the forward tile follows the window
(`ops/pallas/flash_attention.py _window_fwd_blocks`).  None where the
program keeps no such counters (a program from before them)."""

import kernel_counts_laguna as counts

META = {"layer": "Pallas tier", "unit": "%", "moves": "mfu",
        "source": "program_counter", "cells": ["laguna-16k"]}


def compute(run):
    fill = counts.window_fill()
    if fill is None:
        return None
    pairs, entries = fill
    return 100.0 * pairs / entries
