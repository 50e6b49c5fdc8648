"""Tiles the block-diffusion kernels' grids compute over those of them
that hold a pair the mask allows, from the counters the program keeps
when a step build traces such a call
(`paddle_tpu/observe/monitoring.py flash_block_diffusion_blocks_visited`
/ `_allowed`; over every call traced in the process, forward and
backward, a head's grid each).  1.0 is a grid that computes only tiles
holding an allowed pair (80 of a head's 256 at 1024 x 1024 over 2 x 8192
rows); 1.7 is the causal half of 2 L (136), 3.2 every tile.  It counts
TILES: how much of a diagonal tile the mask leaves is the tile size's
matter (`flash_attention.py DEFAULT_DIFFUSION_BLOCK`, PERF.md).  None
where no such kernel was traced: a step that fell back to the XLA
lowering under an explicit mask reads nothing here."""

import kernel_counts_sdar as counts

META = {"layer": "Pallas tier", "unit": "ratio", "moves": "mfu",
        "source": "program_counter", "cells": ["sdar-8k"]}


def compute(run):
    blocks = counts.visited_blocks()
    return None if blocks is None else blocks[0] / blocks[1]
