"""Training cells: a job submitted through ``ApiClient`` to an
``FfDLPlatform`` and ticked, as a tenant's job runs.

Set-up submits the job, lets the platform build its learner, gives the
learner the seed's weights and rows, and drives it through its first
steps one tick per step. Those steps go through the same
``platform.tick()`` as the window, and their readings (each step's loss,
the first gradient as the optimizer holds it, the weights' change after
three steps) are compared with the plain reference once the window has
closed and the program's state is freed.

The window is whole ticks: it ends with the first tick that ends at or
past ``--seconds``.

The learner has no public way to take weights or a data feed, so the
benchmark sets them through its private attributes (``_state``,
``_data``, ``steps_per_tick``). ``check_hooks`` fails the run where one
of them is missing or has changed its form, and each run checks that the
program drew every step's rows from the benchmark's feed: a changed path
fails loudly, and is never measured unknowingly.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

import bench.generator as gen
import bench.reference as ref
import bench.weights as W
from bench.host import Trace, bytes_in_use, free, memory_peak_bytes, span
from bench.norms import leaf_norms, median_gap, worst_gap

READ_STEPS = 3  # the steps the reference follows
WARM_STEPS = 5  # one tick per step; the window's ticks then align to 5
# leaves whose reference gradient is under this share of the median
# leaf's are nought to rounding, and move under Adam by round-off alone
ZERO_GRAD_SHARE = 1e-3


def _learner(platform, job_id):
    g = platform.guardians.get(job_id)
    return g.runtimes.get(0) if g is not None else None


def check_hooks(learner) -> None:
    """Fail where the learner's private attributes that the benchmark
    sets or reads are missing or have changed their form."""
    for name in ("_state", "_data", "steps_per_tick", "loss_history",
                 "phase"):
        if not hasattr(learner, name):
            raise RuntimeError(f"the learner has no {name!r}: the "
                               f"benchmark's hooks into it have changed")
    state = learner._state
    opt = getattr(state, "opt", None)
    for obj, names in ((state, ("_replace", "params", "opt", "step")),
                       (opt, ("_replace", "master", "m"))):
        for name in names:
            if not hasattr(obj, name):
                raise RuntimeError(f"the learner's state has no {name!r}: "
                                   f"its layout has changed")
    if not callable(getattr(learner._data, "batch_at", None)):
        raise RuntimeError("the learner's feed has no batch_at(step)")
    if not isinstance(learner.steps_per_tick, int):
        raise RuntimeError("the learner's steps_per_tick is not an int")
    tree = jax.tree.structure(state.params)
    if (jax.tree.structure(opt.master) != tree
            or jax.tree.structure(opt.m) != tree):
        raise RuntimeError("the optimizer's master or first moment is not "
                           "laid out as the parameters are")
    for leaf in jax.tree.leaves((opt.master, opt.m)):
        if leaf.dtype != jnp.float32:
            raise RuntimeError(f"an optimizer leaf is {leaf.dtype}, "
                               f"not float32")


def _check_drawn(feed, steps: int) -> None:
    if feed.drawn != list(range(steps)):
        raise RuntimeError(f"the learner ran {steps} steps but drew rows "
                           f"{feed.drawn[:8]}... from the benchmark's feed")


def _same_layout(got, want):
    if jax.tree.structure(got) != jax.tree.structure(want):
        raise RuntimeError("the program's parameter tree is not the "
                           "layout bench/weights.py makes")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise RuntimeError(f"leaf {b.shape} {b.dtype} made as "
                               f"{a.shape} {a.dtype}")


def build(cfg: dict, traffic: dict, seed: int) -> dict:
    """Submit the job, tick until its learner is built, and give the
    learner the seed's weights and rows. Returns the objects of the run."""
    from repro.api import ApiClient
    from repro.core import FfDLPlatform, JobManifest

    m = cfg["model"]
    platform = FfDLPlatform(n_hosts=1, chips_per_host=1)
    client = ApiClient.for_platform(platform, tenant="bench")
    train = {"tiny": cfg.get("tiny", False), "steps": traffic["steps"],
             "batch": traffic["batch"], "seq": traffic["seq"],
             "lr": traffic["optimizer"]["lr"],
             "warmup": traffic["optimizer"]["warmup"],
             "seed": gen.sub_seed(seed, "job")}
    if cfg.get("overrides"):
        train["overrides"] = dict(cfg["overrides"])
    job = client.submit(JobManifest(
        name=f"bench-{cfg['name']}", tenant="bench", n_learners=1,
        chips_per_learner=1, arch=cfg["arch"],
        checkpoint_interval=traffic["checkpoint_interval"], train=train))
    for _ in range(1000):
        platform.tick()
        learner = _learner(platform, job)
        if learner is not None and learner.phase == "PROCESSING":
            break
    else:
        raise RuntimeError(f"the job never started processing: "
                           f"{client.status(job).value}")
    check_hooks(learner)
    state = learner._state
    params = W.make(m, seed)
    _same_layout(params, state.params)
    master = W.make(m, seed, "float32")
    _same_layout(master, state.opt.master)
    learner._state = state._replace(
        params=params, opt=state.opt._replace(master=master))
    del state
    learner._data = gen.TrainFeed(traffic, seed, m["vocab_size"])
    return {"platform": platform, "client": client, "job": job,
            "learner": learner}


def warm(run: dict, cfg: dict, traffic: dict, seed: int) -> dict:
    """The first steps, one tick each; returns the program's readings."""
    platform, learner = run["platform"], run["learner"]
    beta1 = traffic["optimizer"]["beta1"]
    per_tick = learner.steps_per_tick
    learner.steps_per_tick = 1
    out = {}
    for step in range(1, WARM_STEPS + 1):
        with span("bench.warm"):
            platform.tick()
        if len(learner.loss_history) != step:
            raise RuntimeError(f"step {step} did not run: "
                               f"{run['client'].status(run['job']).value}")
        if step == 1:
            out["first_grad"] = leaf_norms(learner._state.opt.m,
                                           1 / (1 - beta1))
        if step == READ_STEPS:
            p0 = W.make(cfg["model"], seed, "float32")
            out["change"] = leaf_norms(jax.tree.map(
                jnp.subtract, learner._state.opt.master, p0))
            del p0
    learner.steps_per_tick = per_tick
    _check_drawn(learner._data, WARM_STEPS)
    out["losses"] = [loss for _, loss in learner.loss_history[:READ_STEPS]]
    return out


def window(run: dict, traffic: dict, seconds: float) -> dict:
    """Tick the platform through the window; time every tick."""
    platform, learner = run["platform"], run["learner"]
    ticks = []
    t0 = time.perf_counter()
    with span("bench.window"):
        while True:
            s0 = len(learner.loss_history)
            t = time.perf_counter()
            with span("bench.tick"):
                platform.tick()
            now = time.perf_counter()
            steps = len(learner.loss_history) - s0
            ticks.append({"wall_s": now - t, "steps": steps})
            if steps == 0:
                raise RuntimeError(f"a tick ran no step: "
                                   f"{run['client'].status(run['job']).value}")
            if now - t0 >= seconds:
                break
    _check_drawn(learner._data, len(learner.loss_history))
    return {"window_s": time.perf_counter() - t0, "ticks": ticks}


def reference_readings(cfg: dict, traffic: dict, seed: int,
                       lowp: bool = False, rows: float = 1.0) -> dict:
    """The reference's readings of the first steps on the same rows;
    ``rows`` < 1 keeps only that share of each batch (a planted fault)."""
    m = dict(cfg["model"], scan_layers=True)
    opt = dict(traffic["optimizer"], total_steps=traffic["steps"])
    keep = max(1, int(traffic["batch"] * rows))
    batches = [{k: v[:keep] for k, v in
                gen.train_rows(traffic, seed, m["vocab_size"], s).items()}
               for s in range(READ_STEPS)]
    losses, first, change = ref.train(m, opt, seed, batches, lowp,
                                      reduce=leaf_norms)
    return {"losses": losses, "first_grad": first, "change": change}


def compare(prog: dict, refr: dict) -> dict:
    """The numbers of the first steps: the worst leaf's gap of the first
    gradient's norm and of the change's, the median leaf's gap of the
    change's, and the worst step's relative loss gap. Which of them are
    held to a limit is the cell's limits file's to say (PERF.md)."""
    vals = sorted(refr["first_grad"].values())
    floor = ZERO_GRAD_SHARE * vals[len(vals) // 2]
    keep = [n for n, v in refr["first_grad"].items() if v >= floor]
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], refr["losses"]))
    grad, grad_leaf = worst_gap(prog["first_grad"], refr["first_grad"], keep)
    change, change_leaf = worst_gap(prog["change"], refr["change"], keep)
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change,
            "change_gap_median": median_gap(prog["change"], refr["change"],
                                            keep),
            "_leaves": {"grad": grad_leaf, "change": change_leaf,
                        "kept": len(keep),
                        "left_out": len(refr["first_grad"]) - len(keep)}}


def run(cfg: dict, traffic: dict, seed: int, seconds: float,
        trace_dir, clock, t_start: float) -> dict:
    """One run of a training cell; see bench/run.py for what it returns."""
    objs = build(cfg, traffic, seed)
    prog = warm(objs, cfg, traffic, seed)
    before = clock.reading()
    setup_s = time.perf_counter() - t_start
    with Trace(trace_dir, trace_dir is not None):
        timed = window(objs, traffic, seconds)
    in_window = clock.since(before)
    status = objs["client"].status(objs["job"]).value
    memory = memory_peak_bytes()
    objs["learner"].kill()  # drops the learner's state from the device
    free(objs)
    in_use = bytes_in_use()
    refr = reference_readings(cfg, traffic, seed)
    numbers = compare(prog, refr)
    steps = sum(t["steps"] for t in timed["ticks"])
    tokens = traffic["batch"] * traffic["seq"]
    return {
        "setup_s": setup_s,
        "window_s": timed["window_s"],
        "attempted": steps,
        "failed": 0 if status == "PROCESSING" else 1,
        "e2e": {"train_tokens_per_s": steps * tokens / timed["window_s"]},
        "timers": {"ticks": timed["ticks"], "tokens_per_step": tokens,
                   "seq": traffic["seq"]},
        "memory_peak_bytes": memory,
        "compiles_in_window": in_window,
        "numbers": numbers,
        "notes": {"job_status": status, "losses": prog["losses"],
                  "tick_walls_s": [round(t["wall_s"], 4)
                                   for t in timed["ticks"]],
                  "ref_losses": refr["losses"],
                  "bytes_in_use_after_free": in_use},
    }
