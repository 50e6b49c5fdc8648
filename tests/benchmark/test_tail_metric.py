"""What ISSUE 64's step 2 chose for the tail (PR 64): nothing in
`BENCHMARK.json`.  `step_ms_p95` stays ONE entry at a 1 % bound with no
`workloads` key, so every cell, those later PRs add too, is held to it
and none to a looser sibling; `mellum2-16k`'s tail was steadied by its
configuration's stated rate (`test_mellum2_cell.py` pins it; PERF.md
section 2).  Looked up by name: a later PR may add entries beside it.
"""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_one_tail_entry_at_one_per_cent_holds_every_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bj = json.load(f)
    tails = [m for m in bj["end_to_end"] if m["name"] == "step_ms_p95"]
    assert len(tails) == 1
    tail = tails[0]
    assert (tail["unit"], tail["better"], tail["bound"],
            tail["source"]) == ("ms", "lower", 0.01, "host_clock")
    assert "workloads" not in tail
