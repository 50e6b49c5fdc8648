"""The fullest expert's routed rows over the mean expert's, from the
counts the routed-expert op keeps on the device
(`paddle_tpu/observe/routing.py`: int32 state the step adds to, read
here once, after the window): 1.0 is an even router, 64 everything to
one expert.  Over every step of the process, the 16 warm-up steps
included; averaged over layers.  With dropless routing the ratio is
the longest expert's share of the grouped matmuls' rows."""

META = {"layer": "ops", "unit": "ratio", "moves": "mfu",
        "source": "program_counter", "cells": ["olmoe-4k"]}


def compute(run):
    try:
        from paddle_tpu.observe import routing
    except ImportError:        # a program from before the counters
        return None
    return routing.load_max_over_mean(routing.expert_token_counts())
