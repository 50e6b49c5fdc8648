"""The four host phases of the step's first cold run, summed:
`prepare` holds the step fn's build, `place` the first placing of the
whole state on a mesh, `call` jax's trace, lowering, compile or cache
read, and the executable's load.  From `runtime_stats.cold_runs()`."""

import setup_anatomy

META = {"layer": "program -> one jitted step", "unit": "ms",
        "moves": "setup_s", "source": "program_span", "cells": None}


def compute(run):
    return setup_anatomy.step_ms(run, setup_anatomy.phases_s)
