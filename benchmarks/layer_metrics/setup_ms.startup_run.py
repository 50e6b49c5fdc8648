"""The four host phases of the start-up program's cold run(s), summed:
its trace, compile or cache read, and the weights drawn on the device.
From `runtime_stats.cold_runs()`, the records without feed or fetch."""

import setup_anatomy

META = {"layer": "program -> one jitted step", "unit": "ms",
        "moves": "setup_s", "source": "program_span", "cells": None}


def compute(run):
    return setup_anatomy.startup_run_ms(run)
