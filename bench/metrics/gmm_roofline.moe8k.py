"""Share of its roofline that the grouped-matmul kernels (megablox
``gmm`` and ``tgmm``) reach in the MoE cell's traced window.

Their FLOPs and bytes over the window come from the rows the program
routed to the held experts (its ``moe.rows_held`` counter, in the
window's tick records) and the expert shapes (bench/flops_moe.py
``gmm_work``); the least time the chip could take for them is the larger
of FLOPs over the bf16 peak and bytes over HBM bandwidth; the share is
that over the kernels' summed device time in the trace. None where the
trace has no such kernel or the program no such counter."""

import re

import bench.flops_moe as flops
from bench.device_ops import window_ops
from bench.program_ticks import window_ticks

KERNEL = re.compile(r"^%t?gmm(\.\d+)?$")


def read(run):
    records = window_ticks(run)
    if not records or "moe.rows_held" not in records[-1].counters:
        return None
    seconds = sum(s for name, s in window_ops(run.get("trace_dir"))
                  if KERNEL.match(name))
    if seconds <= 0:
        return None
    m = run["model"]
    steps = sum(x["steps"] for x in run["timers"]["ticks"])
    rows = sum(r.counters["moe.rows_held"] for r in records)
    work, moved = flops.gmm_work(m, rows, steps * flops.moe_layers(m))
    peak = run["peak"]
    least = max(work / peak["bf16_flops_per_s"],
                moved / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
