"""Time Python's garbage collector paused the tick thread over the
window: the window's summed ``gc.pause_s`` counter, in ms, from the
program's tick records (bench/program_ticks.py)."""

from bench.program_ticks import window_ticks


def read(run):
    records = window_ticks(run)
    if records is None:
        return None
    return 1e3 * sum(r.counters.get("gc.pause_s", 0.0) for r in records)
