"""Self time on chip 0, in the traced window, of the multi-token
prediction module, forward and backward, per step: the step program's
rows built under the `mtp` name scope (the second embedding lookup,
the module's two norms and 4096 -> 2048 projection, its block, its
final norm, the head a second time and its loss).  Its block's latent
attention and routed FFN are also in `device_ms_per_step.
latent_attention` and `.routed_ffn`: the three are cuts of one step,
not parts of a sum.  The module's grouped expert matmuls carry no
scope (the TPU compiler renames them) and are not in it."""

import kernel_counts_joyai as counts

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["joyai-8k"]}


def compute(run):
    return counts.scope_ms_per_step(run, counts.MTP)
