"""Summed device duration of the collective ops (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all) on chip 0
in the traced window, per step.  Overlap with compute is not taken
out: this is the collectives' time, not their exposed time."""

META = {"layer": "collectives", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": ["tbase-256-dp4"]}


def compute(run):
    if not run["trace"]:
        return None
    c = run["trace"]["chip0"]
    return 1e3 * c["collective_s"] / c["steps"]
