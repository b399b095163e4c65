"""Device time a step of the MoE layers' routing, dispatch and combine in
the MoE cell's traced window, forward, recomputed and backward: the
operations whose ``op_name`` lies under the program's named scopes
``moe.route``, ``moe.dispatch`` or ``moe.combine`` (not ``moe.experts``,
the grouped matmuls). The trace's events carry no scope, so each is
joined by its instruction name with the compiled step's text that the
run wrote beside the trace (bench/device_ops.py ``op_scopes``). A fusion
counts under the scope of its own ``op_name``. None where there is no
such text or no operation under these scopes."""

import bench.device_ops as ops

SCOPES = ("moe.route", "moe.dispatch", "moe.combine")


def read(run):
    scopes = ops.op_scopes(run.get("trace_dir"))
    if not scopes:
        return None
    seconds = 0.0
    for name, s in ops.window_ops(run.get("trace_dir")):
        path = scopes.get(name.lstrip("%"), "")
        if any(sc in path for sc in SCOPES):
            seconds += s
    steps = sum(t["steps"] for t in run["timers"]["ticks"])
    if seconds <= 0 or steps <= 0:
        return None
    return 1e3 * seconds / steps
