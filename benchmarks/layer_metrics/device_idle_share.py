"""100 x (1 - busy union / traced window) on chip 0."""

META = {"layer": "device", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": None}


def compute(run):
    if not run["trace"]:
        return None
    c = run["trace"]["chip0"]
    return 100.0 * (1.0 - c["busy_s"] / (c["hi"] - c["lo"]))
