"""The learner's host time per step: the window's summed
``ffdl.learner.tick`` time less its ``ffdl.learner.wait`` time (the host
blocked on the device), over the window's steps, in ms, from the
program's spans (bench/program_ticks.py)."""

from bench.program_ticks import window_ticks


def read(run):
    records = window_ticks(run)
    if records is None:
        return None
    host = sum(r.total_s("ffdl.learner.tick") - r.total_s("ffdl.learner.wait")
               for r in records)
    steps = sum(r.counters["learner.steps"] for r in records)
    return 1e3 * host / steps
