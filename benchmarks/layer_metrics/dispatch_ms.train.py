"""Mean host wall time of the harness's span around `Executor.run`,
per step of the window dispatched while the profiler was off."""

META = {"layer": "program -> one jitted step", "unit": "ms",
        "moves": "mfu", "source": "program_span", "cells": None}


def compute(run):
    d = run["dispatch_s"]
    return 1e3 * sum(d) / len(d) if d else None
