"""Model FLOP/s utilization of the training step in the window.

The operations the model needs per token (bench/flops.py: forward and
backward, causal attention, no recomputation) times the tokens of the
steps completed in the window, over the window, the chips and the chip's
bf16 peak."""

import bench.flops as flops


def read(run):
    ticks = run["timers"].get("ticks")
    if not ticks:
        return None
    t = run["timers"]
    tokens = sum(x["steps"] for x in ticks) * t["tokens_per_step"]
    per_token = flops.train_flops_per_token(run["model"], t["seq"])
    peak = run["chips"] * run["peak"]["bf16_flops_per_s"]
    return 100.0 * tokens * per_token / run["window_s"] / peak
