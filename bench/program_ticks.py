"""The program's own records of the window's ticks (``repro.obs.spans``).

The platform keeps its last ticks in memory, each with every span's total
and self time and the tick's counters. No tick runs after the window, so
the window's ticks are the last ``len(run["timers"]["ticks"])`` records.
Each record's ``learner.steps`` has to equal the steps the benchmark
counted in that tick, or the records are not the window's and the reader
raises. Nothing here reads the profiler's trace.
"""

from __future__ import annotations


def window_ticks(run):
    """The window's tick records, oldest first; None where the program
    keeps none (a checkout without ``repro.obs.spans``) or the run has no
    ticks."""
    try:
        from repro.obs.spans import recent_ticks
    except ImportError:
        return None
    ticks = run["timers"].get("ticks")
    if not ticks:
        return None
    records = recent_ticks(len(ticks))
    got = [r.counters.get("learner.steps", 0) for r in records]
    want = [t["steps"] for t in ticks]
    if got != want:
        raise RuntimeError(f"the program's last {len(ticks)} tick records "
                           f"ran steps {got}, the window's ticks {want}")
    return records
