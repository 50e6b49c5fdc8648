"""Which rows of a traced step belong to a counted loop over shared
weights and to the exit head that ends each of its trips, for the
readers in `layer_metrics/` that share them.

The program builds the loop's sub-block under the name scope `ut_loop`
and a trip's final norm, head, cross-entropy and gate under
`ut_loop/exit_head`; its trace join gives every row the path its op
was built under (`name_scope`), the `while` instructions themselves
included.  A program from before that gives no such key, and the
readers then read nothing.

It sits beside `run.py`, not in `layer_metrics/`, where `run.py` takes
every `*.py` for a reader.
"""

from __future__ import annotations

import step_anatomy

LOOP = "ut_loop"            # name scopes of the builder
EXIT_HEAD = "exit_head"
DARK = ("loop", "[loop?]", "unknown")   # buckets of a row with no cost row


def _segments(row):
    return (row.get("name_scope") or "").split("/")


def scoped_rows(run, segment, without=None):
    """The step program's rows built under the name scope `segment`
    (anywhere in the path, forward and backward) and not under
    `without`, with the traced steps; None without the program's join
    or where its rows carry no name scope."""
    a = step_anatomy.anatomy(run)
    if a is None or not any("name_scope" in r for r in a["step_rows"]):
        return None
    rows = [r for r in a["step_rows"] if segment in _segments(r)
            and (without is None or without not in _segments(r))]
    return rows, a["steps"]


def scope_ms_per_step(run, segment, without=None):
    """Self time per step on chip 0 of `scoped_rows`; None where they
    cannot be read."""
    found = scoped_rows(run, segment, without)
    if found is None:
        return None
    rows, steps = found
    return 1e3 * sum(r["self_s"] for r in rows) / steps


def lit(row):
    """Whether a row of the loop can be read: it has a bucket of its
    own, FLOPs where it multiplies, and a kernel's name where it is a
    Mosaic call."""
    if row["bucket"] in DARK or not row["joined"]:
        return False
    if row["bucket"] == "matmul" and not row["flops"]:
        return False
    if row["bucket"] == "custom_call" \
            and "pallas_" in (row["op_name"] or "") \
            and not row.get("kernel"):
        return False
    return True
