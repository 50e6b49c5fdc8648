"""Host time inside the model builder (`models/*.build_model`: forward,
backward and optimizer ops appended to the Program), from
`runtime_stats.build_program_time_s`, which the
`paddle_tpu.setup.build_program` span also shows.  0 for a Program
built by anything else."""

import setup_anatomy

META = {"layer": "program -> one jitted step", "unit": "ms",
        "moves": "setup_s", "source": "program_span", "cells": None}


def compute(run):
    seconds = setup_anatomy.counter(run, "build_program_time_s")
    return None if seconds is None else 1e3 * seconds
