"""Median host time of the `writeback` phase of `Executor.run` over the
process's runs: the new state set in the scope, the debug checks, the
fetches turned into numpy where asked.  Read from
`runtime_stats.recent("writeback")`; the four phases sum to
`dispatch_ms.train` seen from inside."""

import step_anatomy

META = {"layer": "program -> one jitted step", "unit": "ms",
        "moves": "mfu", "source": "program_span", "cells": None}


def compute(run):
    return step_anatomy.executor_ms(run, "writeback")
