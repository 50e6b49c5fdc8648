"""100 x the step program's op self time on chip 0, in the traced
window, whose row has an OWNER, over all of it: a fluid op by the row's
own `<op_type>:<op_index>` scope, or, for an instruction without one
(the compiler's copies, slices and prefetches), the op it feeds or
comes from (`paddle_tpu/observe/cost.py DefUse`).  It is
`op_time_joined_share` after the hand-off and the health of the
hand-off itself.  Also prints, on lines of their own, the per-op table
by owner (`[no scope]` dissolved) and the `layout` bucket by owner,
source, opcode and shape."""

import json

import layout_owner

META = {"layer": "ops", "unit": "%", "moves": "mfu",
        "source": "device_trace", "cells": None}


def compute(run):
    a = layout_owner.owned_anatomy(run)
    if a is None:
        return None
    print(json.dumps({"fluid_op_table_owned":
                      layout_owner.fluid_op_table_owned(a)}), flush=True)
    print(json.dumps({"layout_table": layout_owner.layout_table(a)}),
          flush=True)
    return layout_owner.owned_share(a)
