"""The readings that the MoE cells' limits of ``correct`` are set from,
over many seeds in one process: as ``bench/calibrate.py`` reads them for
the training cells, for a cell whose traffic ``kind`` is ``train_moe``
(bench/train_moe.py), with its ``bias_gap`` besides.

    python bench/calibrate_moe.py --workload <cell> --seeds 1,2,3 \
        [--parts program,control,half_batch]

For each seed one JSON line: the numbers of the program, of the control
(the reference at float8) and of the reference with half of each batch
left out (those of them that ``--parts`` names, all by default), each
judged by ``bench/run.py`` ``judge`` under the cell's limits, with the
leaf that reads each worst gap; the last line gives, for each number,
the program's largest reading and the smallest of the control and of the
fault. A state left unchanged reads 1 by construction and needs no run.

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
NUMBERS = ("loss_gap", "grad_gap", "change_gap", "change_gap_median",
           "bias_gap")
PARTS = ("program", "control", "half_batch")


def _emit(**kv):
    print(json.dumps(kv), flush=True)


def moe_cell(cfg, traffic, seeds, limits, parts=PARTS):
    import bench.train_moe as TM
    from bench.host import free
    from bench.run import judge

    seen = {k: [] for k in parts}
    for seed in seeds:
        t = time.perf_counter()
        if "program" in parts:
            objs = TM.build(cfg, traffic, seed)
            prog = TM.warm(objs, cfg, traffic, seed)
            objs["learner"].kill()
            bias = objs["bias"]
            free(objs)
        else:
            bias = TM.start_bias(cfg["model"], traffic, seed)
        refr = TM.reference_readings(cfg, traffic, seed, bias)
        out = {}
        if "program" in parts:
            out["program"] = TM.compare(prog, refr)
        if "control" in parts:
            out["control"] = TM.compare(TM.reference_readings(
                cfg, traffic, seed, bias, lowp=True), refr)
        if "half_batch" in parts:
            out["half_batch"] = TM.compare(TM.reference_readings(
                cfg, traffic, seed, bias, rows=0.5), refr)
        for k, v in out.items():
            seen[k].append(v)
        _emit(seed=seed, wall_s=time.perf_counter() - t, **out,
              correct={k: judge(v, limits)[1] for k, v in out.items()},
              ref_losses=refr["losses"])
    _emit(summary={n: {f"{k}_{'max' if k == 'program' else 'min'}":
                       (max if k == "program" else min)(r[n] for r in v)
                       for k, v in seen.items()}
                   for n in NUMBERS}, seeds=len(seeds), limits=limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--parts", default=",".join(PARTS),
                    help="comma-separated, of " + ", ".join(PARTS))
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
    if traffic["kind"] != "train_moe":
        print(f"calibrate: {traffic['kind']!r} mixes are bench/calibrate.py's",
              file=sys.stderr)
        return 2
    limits = json.loads((ROOT / "bench" / "limits" / f"{args.workload}.json")
                        .read_text())["limits"]
    parts = tuple(args.parts.split(","))
    if set(parts) - set(PARTS):
        print(f"calibrate: --parts takes {', '.join(PARTS)}", file=sys.stderr)
        return 2
    moe_cell(cfg, traffic, [int(s) for s in args.seeds.split(",")], limits,
             parts)
    return 0


if __name__ == "__main__":
    sys.exit(main())
