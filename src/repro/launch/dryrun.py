"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: every cell must
``.lower().compile()`` on the 16x16 single-pod mesh AND the 2x16x16
multi-pod mesh, and the compiled artifact yields the roofline terms
(cost_analysis + HLO collective parse) recorded in EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""

# The VERY FIRST lines, before any other import: jax locks the device count
# at first init, and the dry-run (and ONLY the dry-run) needs 512 host
# devices for the production meshes.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, all_cells, get_config
from repro.launch import hlo_cost
from repro.launch.mesh import (
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS_BF16,
    make_env,
    make_production_mesh,
)
from repro.models import encdec, steps
from repro.models.steps import TrainState
from repro.nn import params as prm
from repro.nn.blocks import stack_state_axes
from repro.optim import adamw
from repro.parallel import logical_to_spec, param_shardings, use_env
from repro.parallel.zero import opt_state_shardings
from repro.utils.trees import tree_bytes

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_TYPE_RE = re.compile(r"(bf16|f64|f32|f16|f8e4m3|f8e5m2|s64|s32|s16|s8|u64"
                      r"|u32|u16|u8|pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective byte totals parsed from post-SPMD HLO (per device).

    For each collective instruction, counts max(result bytes, operand bytes)
    — all-gather moves ~result bytes, reduce-scatter ~operand bytes.
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$", line)
        if not m:
            continue
        rhs = m.group(1)
        opm = re.search(r"\b(all-reduce|all-gather|reduce-scatter|all-to-all"
                        r"|collective-permute)(?:-start)?\(", rhs)
        if not opm:
            continue
        op = opm.group(1)
        result_part = rhs[:opm.start()]
        operand_part = rhs[opm.start():]
        res_b = sum(_shape_bytes(t) for t in _TYPE_RE.finditer(result_part))
        opd_b = sum(_shape_bytes(t) for t in _TYPE_RE.finditer(operand_part))
        out[op] += max(res_b, opd_b)
        counts[op] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts
    return out


# --------------------------------------------------------------------------
# cell construction: step fn + abstract inputs + shardings
# --------------------------------------------------------------------------

def build_cell(arch: str, shape_name: str, env, remat=None, overrides=None):
    """Returns (fn, example_kwargs, in_shardings, out_shardings)."""
    cfg = get_config(arch)
    if remat:
        cfg = cfg.replace(remat=remat)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    mesh = env.mesh

    ns = lambda spec: NamedSharding(mesh, spec)
    aparams = steps.abstract_params(cfg)
    paxes = steps.param_axes(cfg)
    pshard = param_shardings(paxes, aparams, env)

    def batch_shardings(batch):
        out = {}
        for k, v in batch.items():
            if k in ("tokens", "labels"):
                out[k] = ns(logical_to_spec(("batch", None), env, v.shape))
            elif k == "frames":
                out[k] = ns(logical_to_spec(("batch", None, None), env,
                                            v.shape))
        return out

    specs = steps.input_specs(cfg, shape)

    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig(total_steps=10000)
        fn = steps.make_train_step(cfg, opt_cfg)
        astate = steps.abstract_train_state(cfg)
        oshard = opt_state_shardings(paxes, aparams, env)
        st_shard = TrainState(step=ns(P()), params=pshard, opt=oshard)
        in_sh = (st_shard, batch_shardings(specs["batch"]))
        out_sh = (st_shard, None)
        return fn, (astate, specs["batch"]), in_sh, out_sh, cfg

    if shape.kind == "prefill":
        fn = steps.make_prefill_step(cfg)
        in_sh = (pshard, batch_shardings(specs["batch"]))
        return fn, (aparams, specs["batch"]), in_sh, None, cfg

    # decode
    fn = steps.make_decode_step(cfg)
    if cfg.is_encoder_decoder:
        saxes = encdec.decode_state_axes(cfg)
    else:
        saxes = stack_state_axes(cfg)
    sshard = jax.tree.map(
        lambda axes, arr: ns(logical_to_spec(axes, env, arr.shape)),
        saxes, specs["states"],
        is_leaf=lambda l: isinstance(l, tuple) and
        all(isinstance(x, (str, type(None))) for x in l))
    tok_sh = ns(logical_to_spec(("batch", None), env, (shape.global_batch, 1)))
    in_sh = (pshard, tok_sh, sshard, ns(P()))
    out_sh = (None, sshard)
    return fn, (aparams, specs["token"], specs["states"],
                specs["cache_len"]), in_sh, out_sh, cfg


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             remat=None, overrides=None, rule_overrides=None,
             bf16_interior: bool = False, keep_hlo: bool = False) -> dict:
    from repro.nn import policy

    mesh = make_production_mesh(multi_pod=multi_pod)
    env = make_env(mesh, overrides=rule_overrides)
    n_chips = mesh.size
    shape = SHAPES[shape_name]
    t0 = time.time()
    with use_env(env), policy.bf16_interior(bf16_interior):
        fn, args, in_sh, out_sh, cfg = build_cell(arch, shape_name, env,
                                                  remat=remat,
                                                  overrides=overrides)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    # Loop-aware accounting (XLA's cost_analysis counts while bodies once —
    # see hlo_cost.py). Raw XLA numbers kept alongside for reference.
    la = hlo_cost.analyze(hlo)

    flops_pd = float(la["flops"])
    bytes_pd = float(la["bytes"])
    coll_pd = float(la["collective_bytes"])

    # model "useful" flops: 6ND train / 2ND per generated token (global)
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * tokens
    else:
        tokens = shape.global_batch  # one token per sequence
        model_flops = 2 * n_active * tokens

    compute_s = flops_pd / PEAK_FLOPS_BF16
    memory_s = bytes_pd / HBM_BW
    collective_s = coll_pd / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    bottleneck = max(terms, key=terms.get)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "kind": shape.kind,
        "compile_s": round(t_compile, 1),
        "flops_per_device": flops_pd,
        "bytes_per_device": bytes_pd,
        "collective_bytes_per_device": coll_pd,
        "collectives": la["collectives"],
        "collective_counts": la["collective_counts"],
        "xla_flops_raw": float(cost.get("flops", 0.0)),
        "xla_bytes_raw": float(cost.get("bytes accessed", 0.0)),
        "param_bytes_global": tree_bytes(steps.abstract_params(cfg)),
        "n_params": cfg.param_count(),
        "n_active_params": n_active,
        "model_flops_global": model_flops,
        "useful_flops_ratio": model_flops / max(flops_pd * n_chips, 1),
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": bottleneck.replace("_s", ""),
        "arg_bytes": mem.argument_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
    }
    if keep_hlo:
        result["hlo"] = hlo
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--bf16-interior", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
            if args.tag:
                tag += f"__{args.tag}"
            try:
                res = run_cell(arch, shape, multi_pod=mp, remat=args.remat,
                               bf16_interior=args.bf16_interior)
                with open(f"{args.out}/{tag}.json", "w") as f:
                    json.dump(res, f, indent=1)
                print(f"OK   {tag:60s} compile={res['compile_s']:6.1f}s "
                      f"bottleneck={res['bottleneck']:10s} "
                      f"compute={res['compute_s']*1e3:9.2f}ms "
                      f"mem={res['memory_s']*1e3:9.2f}ms "
                      f"coll={res['collective_s']*1e3:9.2f}ms", flush=True)
            except Exception as e:
                failures.append(tag)
                print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc(limit=4)
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("\nall cells passed")


if __name__ == "__main__":
    main()
