"""100 x the op self time on chip 0, in the traced window, whose
instruction was found in its program's map AND carries a fluid
`<op_type>:<op_index>` scope, over all op self time there: the health
of the tracing itself.  A JAX or libtpu upgrade that renames events
shows here first.  Also prints the step's time by bucket and its 20
longest (fluid op type, phase) pairs on lines of their own."""

import json

import step_anatomy

META = {"layer": "ops", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": None}


def compute(run):
    a = step_anatomy.anatomy(run)
    if a is None:
        return None
    print(json.dumps({
        "step_program": a["step_module"],
        "device_ms_per_step_by_bucket":
            step_anatomy.buckets_ms_per_step(a)}), flush=True)
    print(json.dumps({"fluid_op_table": step_anatomy.fluid_op_table(a)}),
          flush=True)
    total = sum(r["self_s"] for r in a["rows"])
    joined = sum(r["self_s"] for r in a["rows"]
                 if r["joined"] and r["op_type"])
    return 100.0 * joined / total if total else None
