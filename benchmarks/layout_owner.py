"""Whose work the compiler's own copies are, and which of them re-lay
a step input, for the readers in `layer_metrics/` that share it.

The program (PR 49 on) hands every device op that carries no fluid
`<op_type>:<index>` scope (the TPU compiler's copies, `copy-start` /
`copy-done` and `slice-start` pairs, its relayout fusions) to the op
it works for, on every row of its trace join
(`paddle_tpu/observe/cost.py DefUse`, `observe/trace.py join_events`):
`owner_op_type`, `owner_phase` and `owner_via` ("scope": the row's own
scope; "consumer": the first scoped instruction it feeds; "producer":
the nearest scoped one behind it; "none": nobody), and on the rows of
the `layout` bucket `source`: "state" where what the row moves is a
parameter of the step (a weight, a moment, a feed: paid EVERY step for
an array that does not change between one step's end and the next
one's start), "carry" for a loop's carry, "activation" otherwise.  A
program from before that gives no such key, and the readers then read
nothing.

It sits beside `run.py`, not in `layer_metrics/`, where `run.py` takes
every `*.py` for a reader.
"""

from __future__ import annotations

import step_anatomy

OWNED = ("scope", "consumer", "producer")


def owned_anatomy(run):
    """`step_anatomy.anatomy(run)` where the step program's rows carry
    an owner; None without a trace, without the program's join, or
    where its rows carry none."""
    a = step_anatomy.anatomy(run)
    if a is None or not any("owner_via" in r for r in a["step_rows"]):
        return None
    return a


def owned_share(a):
    """100 x the step program's op self time whose row has an owner
    over all of it; None where there is no time."""
    total = sum(r["self_s"] for r in a["step_rows"])
    owned = sum(r["self_s"] for r in a["step_rows"]
                if r.get("owner_via") in OWNED)
    return 100.0 * owned / total if total else None


def fluid_op_table_owned(a, top=20):
    """`step_anatomy.fluid_op_table` by the OWNER: `[op type, phase, ms
    per step, share of the step program's op time]` for its `top`
    longest (owner op type, owner phase) pairs; `[no scope]` is what
    nobody owns."""
    by_owner = [dict(r, op_type=r.get("owner_op_type"),
                     phase=r.get("owner_phase") or "other")
                for r in a["step_rows"]]
    return step_anatomy.fluid_op_table(dict(a, step_rows=by_owner), top)


def layout_rows(a):
    return [r for r in a["step_rows"] if r["bucket"] == "layout"]


def layout_table(a, top=25):
    """The `layout` bucket by (owner op type, owner phase, `owner_via`,
    `source`, opcode, result shape): `[..., ms per step, calls per
    step, bytes of the result per call]` for its `top` longest groups,
    then one row `["[rest]", ...]` for the others, so that the rows
    sum to `device_ms_per_step.layout`."""
    groups = {}
    for r in layout_rows(a):
        opcode = r["instruction"].rstrip("0123456789").rstrip(".")
        key = (r.get("owner_op_type") or "[no scope]",
               r.get("owner_phase") or "other", r.get("owner_via"),
               r.get("source"), opcode, r.get("shape"))
        g = groups.setdefault(key, [0.0, 0, r.get("shape_bytes")])
        g[0] += r["self_s"]
        g[1] += r["calls"]
    ranked = sorted(groups.items(), key=lambda kv: -kv[1][0])
    steps = a["steps"]
    table = [[*key, 1e3 * s / steps, calls / steps, nbytes]
             for key, (s, calls, nbytes) in ranked[:top]]
    rest = ranked[top:]
    if rest:
        table.append(["[rest]", None, None, None, None, None,
                      1e3 * sum(g[0] for _, g in rest) / steps,
                      sum(g[1] for _, g in rest) / steps, None])
    return table


def layout_state_ms_per_step(a):
    """Self time per step of the step program's `layout` rows whose
    `source` is "state"."""
    return 1e3 * sum(r["self_s"] for r in layout_rows(a)
                     if r.get("source") == "state") / a["steps"]
