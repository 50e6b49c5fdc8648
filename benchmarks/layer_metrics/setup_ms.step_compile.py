"""`backend_compile_duration` heard during the step's first cold run:
the XLA compile on a cache miss, the cache read on a hit
(`setup_cache_misses` says which).  From `backend_compile_s` of that
record."""

import setup_anatomy

META = {"layer": "program -> one jitted step", "unit": "ms",
        "moves": "setup_s", "source": "program_span", "cells": None}


def compute(run):
    return setup_anatomy.step_ms(run, lambda r: r["backend_compile_s"])
