"""Share of the training window in which no operation ran on the device:
1 minus the union of the device's operation intervals over the window,
from the profiler's trace (bench/trace.py)."""


def read(run):
    tr = run.get("trace")
    return None if tr is None else 100.0 * tr["idle_share"]
