"""Host time of a platform tick outside its learner: the median over the
window's ticks of ``ffdl.tick``'s duration less its ``ffdl.learner.tick``
time, in ms, from the program's spans (bench/program_ticks.py). The
device waits through all of it."""

import statistics

from bench.program_ticks import window_ticks


def read(run):
    records = window_ticks(run)
    if records is None:
        return None
    return 1e3 * statistics.median(
        r.duration_s - r.total_s("ffdl.learner.tick") for r in records)
