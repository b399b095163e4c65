"""Model FLOP/s utilization of the MoE cell's training step in the window.

The model's FLOPs for the chip's share (bench/flops_moe.py: latent
attention, the dense layer, the shared experts, the router, the held
experts at the rows the program routed to them, the head over the
vocabulary slice and causal attention, forward and backward, no
recomputation) over the window, the chips and the chip's bf16 peak. The
rows come from the program's ``moe.rows_held`` counter in the window's
tick records (bench/program_ticks.py); None where the program keeps no
such counter."""

import bench.flops_moe as flops
from bench.program_ticks import window_ticks


def read(run):
    records = window_ticks(run)
    if not records or "moe.rows_held" not in records[-1].counters:
        return None
    t = run["timers"]
    steps = sum(x["steps"] for x in t["ticks"])
    rows = sum(r.counters["moe.rows_held"] for r in records)
    work = flops.train_flops(run["model"], steps * t["tokens_per_step"],
                             t["seq"], rows)
    peak = run["chips"] * run["peak"]["bf16_flops_per_s"]
    return 100.0 * work / run["window_s"] / peak
