"""What the program itself says about set-up, for the `setup_*` readers
in `layer_metrics/`.

The program (PR 34 on) keeps a record of every cold `Executor.run`
(`runtime_stats.cold_runs()`: a run during which a step fn was built, a
feed signature was new, or jax traced, lowered, compiled or read its
cache) and times the model builder (`build_program_time_s`).  This file
picks the records the readers share: the STEP's is the first cold run of
the program whose cold run with a feed is the newest (so a later AOT
compile, which leaves no record, a mesh cell's second lowering of the
same step or a retrace do not move it), the START-UP's are the cold
runs without feed or fetch that led up to it.  A program that keeps no
records (any commit before PR 34) gives `None` everywhere, and the
readers leave their metric out.

What stays outside the program: backend start-up (`jax.devices()`, before
any program code), making the pool, and the warm-up steps.  `run.py`
prints those as `setup_marks_s`.

It sits beside `run.py`, not in `layer_metrics/`, where `run.py` takes
every `*.py` for a reader.
"""

from __future__ import annotations

PHASES = ("prepare_s", "place_s", "call_s", "writeback_s")


def _stats(run):
    """The program's `runtime_stats`; None, as for every reader of the
    program's own timing, on a run without a reduced trace
    (`step_anatomy.executor_ms`)."""
    if not run["trace"]:
        return None
    from paddle_tpu.observe.monitoring import runtime_stats

    return runtime_stats


def cold_runs(run):
    """The program's records, oldest first, or None where it keeps
    none."""
    records = getattr(_stats(run), "cold_runs", None)
    return records() if records else None


def counter(run, name):
    """One counter of `runtime_stats.snapshot()`, or None where the
    program has no such counter."""
    stats = _stats(run)
    return stats.snapshot().get(name) if stats else None


def pick(records):
    """`(start-up records, the step's first record)`; `([], None)`
    without a cold run that had a feed."""
    fed = [r for r in records or () if r["feed_arrays"]]
    if not fed:
        return [], None
    step = next(r for r in fed if r["program"] == fed[-1]["program"])
    startup = []
    for r in records:
        if r is step:
            break
        if r["feed_arrays"]:
            startup = []        # another cell of this process: not ours
        elif not r["fetches"]:
            startup.append(r)
    return startup, step


def phases_s(record):
    """The four host phases of one record's run, summed."""
    return sum(record[p] for p in PHASES)


def startup_run_ms(run):
    startup, _ = pick(cold_runs(run))
    return 1e3 * sum(map(phases_s, startup)) if startup else None


def step_ms(run, of):
    """`of(record)`, seconds, in ms for the step's first cold run, or
    None."""
    _, step = pick(cold_runs(run))
    return None if step is None else 1e3 * of(step)
