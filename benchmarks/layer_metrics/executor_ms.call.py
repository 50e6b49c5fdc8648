"""Median host time of the `call` phase of `Executor.run` over the
process's runs: the jitted call, which is the pjit dispatch (and trace and
compile on the first call).  Read from
`runtime_stats.recent("call")`; the four phases sum to
`dispatch_ms.train` seen from inside."""

import step_anatomy

META = {"layer": "program -> one jitted step", "unit": "ms",
        "moves": "mfu", "source": "program_span", "cells": None}


def compute(run):
    return step_anatomy.executor_ms(run, "call")
