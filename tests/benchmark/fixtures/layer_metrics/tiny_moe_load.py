"""Test only: `moe_expert_load_max_over_mean` of benchmarks/, listed
for the toy cell so the harness test drives it inside `run_cell`, while
the cell's scope is alive."""

import os

import run as bench_run

META = {"layer": "ops", "unit": "ratio", "moves": "mfu",
        "source": "program_counter", "cells": ["tiny-olmoe-host"]}


def compute(run):
    return bench_run.load_module(os.path.join(
        bench_run.HERE, "layer_metrics",
        "moe_expert_load_max_over_mean.py")).compute(run)
