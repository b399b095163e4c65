#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout, in one process that holds the chip.
It refuses to run, and prints no result, without a TPU or with fewer
chips than the cell asks for; there is no CPU fallback.

Everything the cell is made of is data found by name: the cell in
``BENCHMARK.json``, its configuration file, its traffic file under
``bench/traffic/``, its limits under ``bench/limits/``, and one reader
per per-layer metric under ``bench/metrics/``. The traffic file's
``kind`` picks the module that runs it: ``bench/<kind>.py``, which is
``bench/train.py`` for the training mixes.

Set-up (imports, platform, weights, compiles or cache loads, warm-up)
counts from process start to the start of the window. With ``--trace 0``
the result holds the cell's end-to-end metrics; with ``--trace 1`` the
window is profiled and the result holds its per-layer metrics and a
breakdown. Earlier lines of standard output carry what the run saw
(compiles inside the window among them); the last line is one JSON
object. The numbers that decide ``correct`` are the last lines of
standard error, each beside its limit, and the last key of the result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
OUT = ROOT / "bench_out"
CACHE_DIR = ROOT / ".jax_cache"  # the directory the program's helper uses


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find(items, name, what):
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def metrics_of(spec: dict, cell: str, table: str) -> list:
    """The metrics of ``table`` that ``cell`` reports."""
    out = []
    for mt in spec[table]:
        if "workloads" in mt:
            if cell in mt["workloads"]:
                out.append(mt)
        elif table == "end_to_end" or any(
                e["name"] == mt["moves"] and cell in e.get(
                    "workloads", [cell]) for e in spec["end_to_end"]):
            out.append(mt)
    return out


def read_metric(name: str, run: dict):
    """Call ``read(run)`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(run)


def judge(numbers: dict, limits: dict, failed: int = 0) -> tuple:
    """Each compared number beside its limit, and whether the run is
    correct: nothing failed and no number is above its limit."""
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in limits.items()}
    correct = failed == 0 and all(ch["value"] <= ch["limit"]
                                  for ch in checks.values())
    return checks, correct


def execute(spec: dict, cell: str, seed: int, seconds: float, trace: bool,
            clock, t_start: float, device_kind: str, limits=None) -> tuple:
    """Run the cell; return (result object, lines of what was compared).
    Makes no check of the device: ``main`` does."""
    import bench.generator as gen
    import bench.peaks as peaks
    import bench.trace as tr

    w = find(spec["workloads"], cell, "workload")
    c = find(spec["configs"], w["config"], "config")
    cfg = json.loads((ROOT / c["file"]).read_text())
    traffic = gen.load(w["traffic"])
    if limits is None:
        limits = json.loads((BENCH / "limits" / f"{cell}.json")
                            .read_text())["limits"]
    runner = importlib.import_module(f"bench.{traffic['kind']}")
    trace_dir = OUT / "trace" / cell if trace else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = runner.run(cfg, traffic, seed, seconds, trace_dir, clock, t_start)

    cw = run["compiles_in_window"]
    print(f"[window] compiles={cw['compiles']} compile_s={cw['compile_s']} "
          f"cache_hits={cw['cache_hits']} window_s={run['window_s']} "
          f"setup_s={run['setup_s']}", flush=True)
    for k, v in run.get("notes", {}).items():
        print(f"[note] {k}={v}", flush=True)

    numbers = run["numbers"]
    print(f"[readings] {json.dumps(numbers)}", flush=True)
    checks, correct = judge(numbers, limits, run["failed"])

    import jax
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": run["attempted"],
              "failed": run["failed"]}
    if trace:
        run["trace"] = tr.reduce(*tr.load(trace_dir))
        run.update(model=cfg["model"], traffic=traffic, chips=w["chips"],
                   peak=peaks.peak(device_kind))
        metrics = {}
        for mt in metrics_of(spec, cell, "per_layer"):
            value = read_metric(mt["name"], run)
            if value is not None:
                metrics[mt["name"]] = {"value": value, "unit": mt["unit"]}
        device.update(busy_s=run["trace"]["busy_s"],
                      window_s=run["trace"]["window_s"])
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"]}
    else:
        values = dict(run["e2e"], setup_s=run["setup_s"])
        result["metrics"] = {
            mt["name"]: {"value": values[mt["name"]], "unit": mt["unit"]}
            for mt in metrics_of(spec, cell, "end_to_end")}
        result["device"] = device
    result["checks"] = checks
    lines = [f"check {k} {ch['value']!r} limit {ch['limit']!r}"
             for k, ch in checks.items()]
    return result, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        spec = load_spec()
        w = find(spec["workloads"], args.workload, "workload")
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program's sources (src/repro) are missing",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform}); "
              f"not running", file=sys.stderr)
        return 2
    if len(devices) < w["chips"]:
        print(f"bench: the cell needs {w['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    import bench.peaks as peaks

    try:
        peaks.peak(devices[0].device_kind)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import os

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench.compile_clock import CompileClock

    clock = CompileClock()
    print(f"[device] platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)} "
          f"jax={jax.__version__}", flush=True)
    try:
        result, lines = execute(spec, args.workload, args.seed,
                                args.seconds, bool(args.trace), clock,
                                T_START, devices[0].device_kind)
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps(result), flush=True)
    for ln in lines:
        print(ln, file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
