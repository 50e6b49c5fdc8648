"""Key blocks the window kernel's forward grid visits over those of
them that hold a pair the mask allows, from the two counters the
program keeps when a step build traces a call with a window
(`paddle_tpu/observe/monitoring.py flash_window_blocks_visited` /
`_allowed`; over every call traced in the process, a head's grid
each).  1.0 is a grid that visits only blocks holding an allowed pair;
T / window (16 here) is skipping lost.  It counts BLOCKS: how much of a
visited block the mask leaves is the block sizes' matter
(`flash_attention.py DEFAULT_WINDOW_BLOCK_*`, PERF.md)."""

import kernel_counts_mellum as counts

META = {"layer": "Pallas tier", "unit": "ratio", "moves": "mfu",
        "source": "program_counter", "cells": ["mellum2-16k"]}


def compute(run):
    blocks = counts.window_blocks()
    return None if blocks is None else blocks[0] / blocks[1]
