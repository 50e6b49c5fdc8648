"""Test only: `held_expert_row_share` of benchmarks/, listed for the
toy cell so the harness test drives it inside `run_cell`, while the
cell's scope is alive."""

import os

import run as bench_run

META = {"layer": "ops", "unit": "%", "moves": "mfu",
        "source": "program_counter", "cells": ["tiny-lfm2-host"]}


def compute(run):
    return bench_run.load_module(os.path.join(
        bench_run.HERE, "layer_metrics",
        "held_expert_row_share.py")).compute(run)
