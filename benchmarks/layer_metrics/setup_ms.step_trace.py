"""jaxpr tracing plus MLIR lowering heard during the step's first cold
run: our op impls and jax's own Python, warm or cold.  Nested jitted
functions report their own tracing too, so it runs a few per cent over
the wall time.  From `trace_s + lower_s` of that record."""

import setup_anatomy

META = {"layer": "program -> one jitted step", "unit": "ms",
        "moves": "setup_s", "source": "program_span", "cells": None}


def compute(run):
    return setup_anatomy.step_ms(run, lambda r: r["trace_s"] + r["lower_s"])
