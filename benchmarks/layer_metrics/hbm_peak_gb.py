"""The fullest chip's `peak_bytes_reserved` after the window, in GB:
live arrays plus what running programs set aside for temporaries."""

META = {"layer": "device", "unit": "GB", "moves": "mfu",
        "source": "program_counter", "cells": None}


def compute(run):
    peaks = [s["peak_bytes_reserved"] for s in run["memory_stats"]
             if "peak_bytes_reserved" in s]
    return max(peaks) / 1e9 if peaks else None
