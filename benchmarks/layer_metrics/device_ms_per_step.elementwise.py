"""Self time on chip 0, in the traced window, of the step program's
instructions in the `elementwise` bucket of `observe/cost.py`, per step."""

import step_anatomy

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": None}


def compute(run):
    return step_anatomy.bucket_ms_per_step(run, "elementwise")
