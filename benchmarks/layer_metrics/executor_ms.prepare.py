"""Median host time of the `prepare` phase of `Executor.run` over the
process's runs: RNG and telemetry state, state names, feed shardings, the step
cache's key and look-up (a miss's build is inside it).  Read from
`runtime_stats.recent("prepare")`; the four phases sum to
`dispatch_ms.train` seen from inside."""

import step_anatomy

META = {"layer": "program -> one jitted step", "unit": "ms",
        "moves": "mfu", "source": "program_span", "cells": None}


def compute(run):
    return step_anatomy.executor_ms(run, "prepare")
