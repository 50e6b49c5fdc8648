"""Expert routing counters: what the dropless routed-expert op
(`ops/moe_dropless.py`) counts on the device.

Each `layers.dropless_moe` keeps `<moe_expert...>.token_count`, an
int32 (E,) persistable variable that its op adds the step's rows per
expert to INSIDE the jitted step: no fetch, no callback, nothing on
the host until someone reads the scope.  Reading is one device-to-host
copy of E numbers per layer, made when the reader chooses (after a
benchmark window, every N steps of a trainer), never by the step.
The sum wraps after 2^31 rows to one expert: a trainer that runs that
long reads and resets.

A layer that holds one expert-parallel rank's share
(`experts_held=(first, count)`) keeps its counts (count,), the HELD
experts' rows, and beside them `<moe_expert...>.off_share_count`, one
int32 of the rows that went to experts it does not hold: the two add
up to tokens x k.  Such a layer runs its sorted rows on the smallest
of up to three static buffer sizes that holds the rows it got
(`ops/moe_dropless.py row_buffer_sizes`), and
`<moe_expert...>.row_buffer_count`, int32 (3,), counts the calls that
took the smallest, the next and the last (tokens x k rows).
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

TOKEN_COUNT_SUFFIX = ".token_count"
OFF_SHARE_COUNT_SUFFIX = ".off_share_count"
ROW_BUFFER_COUNT_SUFFIX = ".row_buffer_count"
TILES_VISITED_SUFFIX = ".tiles_visited"
TILES_TOTAL_SUFFIX = ".tiles_total"


def _counters(suffix, scope, reset) -> Dict[str, np.ndarray]:
    from ..core.executor import Scope

    out: Dict[str, np.ndarray] = {}
    for s in ([scope] if scope is not None else list(Scope.live)):
        for name in s.local_var_names():
            value = s.vars[name]
            if name.endswith(suffix) and value is not None:
                out[name] = np.asarray(value).astype(np.int64)
                if reset:
                    s.set_var(name, np.zeros(out[name].shape, np.int32))
    return out


def expert_token_counts(scope=None, reset: bool = False
                        ) -> Dict[str, np.ndarray]:
    """`{variable name: (E,) int64 rows routed to each expert so far}`
    for every routed-expert layer whose state lives in `scope` (the
    held experts only, for a layer that holds a share); with no scope,
    in any Scope alive in the process (a benchmark reader is handed
    none).  `reset` zeroes what was read."""
    return _counters(TOKEN_COUNT_SUFFIX, scope, reset)


def off_share_counts(scope=None, reset: bool = False
                     ) -> Dict[str, np.ndarray]:
    """`{variable name: (1,) int64 rows routed to experts the layer
    does not hold}`, one entry for each layer that holds a share."""
    return _counters(OFF_SHARE_COUNT_SUFFIX, scope, reset)


def row_buffer_counts(scope=None, reset: bool = False
                      ) -> Dict[str, np.ndarray]:
    """`{variable name: (3,) int64 calls that ran at each row-buffer
    size, smallest first}`, one entry for each layer that holds a
    share.  Where tokens x k is no more than a smaller size would be,
    the layer has fewer sizes and the later slots stay zero."""
    return _counters(ROW_BUFFER_COUNT_SUFFIX, scope, reset)


def held_row_share(scope=None) -> Optional[float]:
    """Of the rows (token, expert) that share-holding layers routed,
    the fraction that went to experts they hold, over all such layers:
    count / E under uniform routing.  None where no layer holds a
    share or nothing was routed yet."""
    off = off_share_counts(scope)
    if not off:
        return None
    counts = expert_token_counts(scope)
    held = sum(int(counts[name[:-len(OFF_SHARE_COUNT_SUFFIX)]
                          + TOKEN_COUNT_SUFFIX].sum()) for name in off)
    gone = sum(int(c.sum()) for c in off.values())
    return held / (held + gone) if held + gone else None


def load_max_over_mean(counts) -> Optional[float]:
    """The fullest expert's rows over the mean expert's, over all the
    layers of `counts` (as `expert_token_counts` gives them): 1.0 is a
    perfectly even router, E everything to one expert.  None before
    any token was routed."""
    ratios = [c.max() / c.mean() for c in counts.values() if c.sum() > 0]
    return float(np.mean(ratios)) if ratios else None


def segment_tile_visits(scope=None):
    """(tiles visited, tiles of the whole rectangle) of every
    `layers.segment_attention` whose state lives in `scope` (any live
    Scope with none), summed over the layers and over every forward
    pass of the process: each layer keeps `<name>.tiles_visited` and
    `<name>.tiles_total`, int32 (1,) state its op adds to INSIDE the
    step, what its list of visits held (data: where the segments'
    bounds fell) and what a kernel that skipped nothing would have
    run.  None where no layer keeps them or none ran on the kernels
    (the XLA lowering adds 0 to both)."""
    visited = _counters(TILES_VISITED_SUFFIX, scope, False)
    total = _counters(TILES_TOTAL_SUFFIX, scope, False)
    if not total or not sum(int(c.sum()) for c in total.values()):
        return None
    return (sum(int(c.sum()) for c in visited.values()),
            sum(int(c.sum()) for c in total.values()))
