"""Union of the device-op intervals on chip 0 in the traced window,
per step in it."""

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": None}


def compute(run):
    if not run["trace"]:
        return None
    c = run["trace"]["chip0"]
    return 1e3 * c["busy_s"] / c["steps"]
