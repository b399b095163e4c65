"""The readings that the limits of ``correct`` are set from, over many
seeds in one process: the program's, the control's (the reference at
float8, bench/reference.py) and a planted fault's.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3

A training cell reads, for each seed, the numbers a run compares: for the
program, for the control, and for the reference with half of each batch
left out (the mean taken over the rest). A state left unchanged reads 1
by construction and needs no run. Each reading is one JSON line on
standard output, with whether the harness's own comparison
(``bench/run.py`` ``judge``) finds it correct under the cell's limits
(``bench/limits/<cell>.json``), and the raw readings (losses and per-leaf
norms) from which any number compared can be worked out again; the last
line gives, for each number, the
largest reading of the program and the smallest of the control and of
the fault.

Not run by the benchmark's runs; it needs the chip, like bench/run.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
NUMBERS = ("loss_gap", "grad_gap", "change_gap", "change_gap_median")


def _emit(**kv):
    print(json.dumps(kv), flush=True)


def train_cell(cfg, traffic, seeds, limits):
    import bench.train as T
    from bench.host import free
    from bench.run import judge

    seen = {"program": [], "control": [], "half_batch": []}
    for seed in seeds:
        t = time.perf_counter()
        objs = T.build(cfg, traffic, seed)
        prog = T.warm(objs, cfg, traffic, seed)
        objs["learner"].kill()
        free(objs)
        refr = T.reference_readings(cfg, traffic, seed)
        ctrl = T.reference_readings(cfg, traffic, seed, lowp=True)
        half = T.reference_readings(cfg, traffic, seed, rows=0.5)
        out = {"program": T.compare(prog, refr),
               "control": T.compare(ctrl, refr),
               "half_batch": T.compare(half, refr)}
        for k, v in out.items():
            seen[k].append(v)
        _emit(seed=seed, wall_s=time.perf_counter() - t, **out,
              correct={k: judge(v, limits)[1] for k, v in out.items()},
              losses=prog["losses"], ref_losses=refr["losses"],
              readings={"program": prog, "reference": refr,
                        "control": ctrl, "half_batch": half})
    _emit(summary={n: {"program_max": max(r[n] for r in seen["program"]),
                       "control_min": min(r[n] for r in seen["control"]),
                       "half_batch_min": min(r[n]
                                             for r in seen["half_batch"])}
                   for n in NUMBERS}, seeds=len(seeds), limits=limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    import bench.generator as gen
    from bench.run import CACHE_DIR, find, load_spec

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU; not running", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    spec = load_spec()
    w = find(spec["workloads"], args.workload, "workload")
    c = find(spec["configs"], w["config"], "config")
    cfg = json.loads((ROOT / c["file"]).read_text())
    traffic = gen.load(w["traffic"])
    seeds = [int(s) for s in args.seeds.split(",")]
    if traffic["kind"] != "train":
        print(f"calibrate: no calibration for {traffic['kind']!r} mixes",
              file=sys.stderr)
        return 2
    limits = json.loads((ROOT / "bench" / "limits" / f"{args.workload}.json")
                        .read_text())["limits"]
    train_cell(cfg, traffic, seeds, limits)
    return 0


if __name__ == "__main__":
    sys.exit(main())
