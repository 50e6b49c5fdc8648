"""XLA compiles counted by `runtime_stats` between the window's first
and last stamp.  Must be 0: `correct` is false otherwise."""

META = {"layer": "program -> one jitted step", "unit": "count",
        "moves": "step_ms_p95", "source": "program_counter",
        "cells": None}


def compute(run):
    return run["compiles_in_window"]
