"""Persistent-cache misses heard during the process's cold runs of
`Executor.run`: 0 on a warm run, so a line says which kind of `setup_s`
it sits beside.  From `cache_misses` of `runtime_stats.cold_runs()`."""

import setup_anatomy

META = {"layer": "program -> one jitted step", "unit": "count",
        "moves": "setup_s", "source": "program_counter", "cells": None}


def compute(run):
    records = setup_anatomy.cold_runs(run)
    if not records:
        return None
    return sum(r["cache_misses"] for r in records)
