"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --tiny \\
        --steps 100 --batch 8 --seq 128 --mesh 2x2 --ckpt-dir /tmp/run1

Builds the mesh (+ logical rules), shards the train state (params by
TP/DP rules, optimizer by ZeRO-1), restores from the newest valid
checkpoint if one exists, then runs the step loop with async checkpointing
and metrics logging. The same code path the platform executor uses, exposed
as a standalone CLI for single-job runs (and the template for a real
multi-host deployment: swap `make_mesh` for `jax.distributed`-initialized
devices).

Optimized-rules flags expose the EXPERIMENTS.md §Perf winners:
  --sp           sequence-parallel residuals (seq → model)
  --batch-tp     batch-TP attention (for TP-indivisible head counts)
"""

from __future__ import annotations

import argparse
import time


def parse_mesh(spec: str):
    parts = [int(x) for x in spec.split("x")]
    if len(parts) == 1:
        return None  # single device
    return tuple(parts)


def jit_train_step(cfg, opt_cfg, env, batch: int, seq: int):
    """The launcher's jitted train step, donating the state.

    Without a mesh the step runs on the default device. With one, the
    params are placed by the logical rules, the optimizer state by
    ZeRO-1 and the batch over ``data``. Returns ``(step, state_shardings,
    batch_shardings)``; both shardings are ``None`` without a mesh. Call
    it, and the step, inside ``use_env(env)``.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import steps
    from repro.models.steps import TrainState
    from repro.parallel import logical_to_spec, param_shardings
    from repro.parallel.zero import opt_state_shardings

    train_step = steps.make_train_step(cfg, opt_cfg)
    if env.mesh is None:
        return jax.jit(train_step, donate_argnums=(0,)), None, None
    mesh = env.mesh
    aparams = steps.abstract_params(cfg)
    axes = steps.param_axes(cfg)
    st_sh = TrainState(
        step=NamedSharding(mesh, P()),
        params=param_shardings(axes, aparams, env),
        opt=opt_state_shardings(axes, aparams, env))
    tok_sh = NamedSharding(mesh, logical_to_spec(("batch", None), env,
                                                 (batch, seq)))
    b_sh = {"tokens": tok_sh, "labels": tok_sh}
    step = jax.jit(train_step, in_shardings=(st_sh, b_sh),
                   out_shardings=(st_sh, None), donate_argnums=(0,))
    return step, st_sh, b_sh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true",
                    help="use the reduced smoke config of the family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--mesh", default="1", help="e.g. 2x2 = data x model")
    ap.add_argument("--sp", action="store_true")
    ap.add_argument("--batch-tp", action="store_true")
    ap.add_argument("--remat", default="full", choices=["none", "dots", "full"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    from jax.sharding import AxisType

    from repro.ckpt import checkpoint as ckpt
    from repro.ckpt.checkpoint import AsyncCheckpointer
    from repro.configs import get_config, get_tiny_config
    from repro.data.objectstore import DirBucket
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_env
    from repro.models import steps
    from repro.optim import adamw
    from repro.parallel import use_env

    enable_compile_cache()
    cfg = get_tiny_config(args.arch) if args.tiny else get_config(args.arch)
    cfg = cfg.replace(remat=args.remat)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=args.warmup,
                                total_steps=args.steps)

    mesh_shape = parse_mesh(args.mesh)
    if mesh_shape is not None:
        if len(mesh_shape) != 2:
            raise SystemExit("--mesh must be DxM (e.g. 2x2)")
        need = mesh_shape[0] * mesh_shape[1]
        if jax.device_count() < need:
            raise SystemExit(
                f"mesh {args.mesh} needs {need} devices, have "
                f"{jax.device_count()} (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need} for CPU)")
        mesh = jax.make_mesh(mesh_shape, ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        overrides = {}
        if args.sp:
            overrides["seq"] = "model"
        if args.batch_tp:
            overrides["batch_attn"] = ("data", "model")
        env = make_env(mesh, overrides=overrides)
    else:
        from repro.parallel import null_env
        env = null_env()
        mesh = None

    data = SyntheticLM(DataConfig(cfg.vocab_size, args.seq, args.batch,
                                  seed=args.seed))
    bucket = DirBucket(args.ckpt_dir) if args.ckpt_dir else None
    acp = AsyncCheckpointer(bucket, "ckpt") if bucket else None

    with use_env(env):
        train_step, st_sh, b_sh = jit_train_step(cfg, opt_cfg, env,
                                                 args.batch, args.seq)

        # resume from the newest valid checkpoint (same contract the
        # platform's RealLearner uses)
        start = 0
        if bucket is not None:
            latest = ckpt.latest_step(bucket, "ckpt")
            if latest is not None:
                abstract = steps.abstract_train_state(cfg)
                state, _ = ckpt.restore(bucket, "ckpt", latest,
                                        like=abstract, shardings=st_sh)
                state = jax.tree.map(jax.numpy.asarray, state) \
                    if mesh is None else state
                start = latest
                print(f"resumed from checkpoint step {latest}")
        if start == 0:
            state = steps.init_train_state(cfg, jax.random.key(args.seed))
            if mesh is not None:
                state = jax.device_put(state, st_sh)

        from repro.utils import tree_count
        print(f"arch={cfg.name} params={tree_count(state.params)/1e6:.1f}M "
              f"mesh={args.mesh} devices={jax.device_count()}")

        t0 = time.perf_counter()
        tokens_done = 0
        for step in range(start, args.steps):
            batch = data.batch_at(step)
            if mesh is not None:
                batch = jax.device_put(batch, b_sh)
            state, metrics = train_step(state, batch)
            tokens_done += args.batch * args.seq
            if (step + 1) % args.log_every == 0:
                dt = time.perf_counter() - t0
                print(f"step {step+1:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"{tokens_done/dt:,.0f} tok/s")
            if acp is not None and (step + 1) % args.ckpt_every == 0:
                acp.save(step + 1, state,
                         {"loss": float(metrics["loss"])})
        if acp is not None:
            acp.save(args.steps, state, {"final": True})
            acp.wait()
            print(f"checkpoints: {ckpt.steps_available(bucket, 'ckpt')}")


if __name__ == "__main__":
    main()
