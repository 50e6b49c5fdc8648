"""Median host time of the `place` phase of `Executor.run` over the
process's runs: the feed turned into device arrays; on a mesh every state and
feed array through `jax.device_put`.  Read from
`runtime_stats.recent("place")`; the four phases sum to
`dispatch_ms.train` seen from inside."""

import step_anatomy

META = {"layer": "program -> one jitted step", "unit": "ms",
        "moves": "mfu", "source": "program_span", "cells": None}


def compute(run):
    return step_anatomy.executor_ms(run, "place")
