"""Cold runs of `Executor.run` in the process: 2 on one chip (start-up,
step) unless something recompiled.  From `runtime_stats.snapshot()`.
Also prints the records on a line of their own."""

import json

import setup_anatomy

META = {"layer": "program -> one jitted step", "unit": "count",
        "moves": "setup_s", "source": "program_counter", "cells": None}


def compute(run):
    records = setup_anatomy.cold_runs(run)
    if records is None:
        return None
    print(json.dumps({"cold_runs": records}), flush=True)
    return setup_anatomy.counter(run, "cold_runs")
