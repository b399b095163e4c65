"""MoE training cells (traffic ``kind: "train_moe"``): a job submitted
through ``ApiClient`` to an ``FfDLPlatform`` and ticked, as in
``bench/train.py``, for a model of DeepSeek-V3 blocks on one chip's share
of the experts.

It reuses ``bench/train.py``'s window, comparison and checks as they are,
and brings its own set-up: the weights and the routers' correction
biases come from ``bench/weights_moe.py`` and are given to the learner
through its state, as ``bench/train.py`` gives it weights, and the
reference is ``bench/reference_moe.py``. The biases start balanced, as
a deployed model's bias update keeps them: on the seed's weights, each
router sends every expert the same rows of a calibration batch of the
traffic's shape, drawn from the seed apart from the training rows, so
the experts held here start with their share of rows whatever the seed.
In a share the routers learn from the balance loss alone
(``repro.nn.moe``), so the held experts keep about their share through
the window. Besides
``bench/train.py``'s numbers, a run reads ``bias_gap``: how far the
program's biases after the reference's steps lie from the reference's,
over how far the reference's moved (an unchanged state reads 1).

A traced run returns its ``trace_dir`` for the readers that read the
device trace by name, and writes there the compiled train step's text
(``bench/device_ops.py`` ``STEP_HLO``), whose ``op_name`` metadata names
the program's scope of each operation in the trace. Its notes name the
kernel paths that the tick which traced the step counted
(``step_paths``).
"""

from __future__ import annotations

import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import bench.generator as gen
import bench.reference_moe as ref
import bench.train as T
import bench.weights_moe as W
from bench.device_ops import STEP_HLO
from bench.host import Trace, bytes_in_use, free, memory_peak_bytes, span
from bench.norms import leaf_norms
from bench.program_ticks import window_ticks


def check_buffers(learner, m: dict) -> None:
    """Fail where the learner's state has no router biases of the
    model's shape."""
    buffers = getattr(learner._state, "buffers", None)
    bias = (buffers or {}).get("router_bias")
    want = (W.n_moe(m), m["n_experts"])
    if bias is None or bias.shape != want or bias.dtype != jnp.float32:
        raise RuntimeError(f"the learner's state holds no float32 router "
                           f"biases of shape {want}: its layout has changed")


def start_bias(m: dict, traffic: dict, seed: int) -> np.ndarray:
    """The routers' biases a run starts from, (MoE layers, experts):
    balanced by the reference on the seed's weights (float32) over a
    calibration batch of the traffic's shape, tokens uniform over the
    vocabulary."""
    rng = np.random.default_rng(gen.sub_seed(seed, "router_bias"))
    tokens = rng.integers(0, m["vocab_size"],
                          (traffic["batch"], traffic["seq"]))
    params = W.make(m, seed, "float32")
    bias = ref.balanced_biases(params, tokens.astype(np.int32), m)
    del params
    return np.asarray(jax.device_get(bias))


def build(cfg: dict, traffic: dict, seed: int) -> dict:
    """Submit the job, tick until its learner is built, and give the
    learner the seed's weights, biases (``start_bias``, kept as
    ``"bias"``) and rows."""
    from repro.api import ApiClient
    from repro.core import FfDLPlatform, JobManifest

    m = cfg["model"]
    bias = start_bias(m, traffic, seed)  # before the learner takes memory
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
        learner = T._learner(platform, job)
        if learner is not None and learner.phase == "PROCESSING":
            break
    else:
        raise RuntimeError(f"the job never started processing: "
                           f"{client.status(job).value}")
    T.check_hooks(learner)
    check_buffers(learner, m)
    state = learner._state
    params = W.make(m, seed)
    T._same_layout(params, state.params)
    master = W.make(m, seed, "float32")
    T._same_layout(master, state.opt.master)
    learner._state = state._replace(
        params=params, opt=state.opt._replace(master=master),
        buffers={"router_bias": jnp.asarray(bias)})
    del state
    learner._data = gen.TrainFeed(traffic, seed, m["vocab_size"])
    return {"platform": platform, "client": client, "job": job,
            "learner": learner, "bias": bias}




def _step_paths() -> dict:
    """The kernel paths that the newest tick, the one that traced the
    step, counted (``attention.*``, ``moe.*``); {} where the program
    keeps no such record."""
    try:
        from repro.obs.spans import recent_ticks
    except ImportError:
        return {}
    ticks = recent_ticks(1)
    return {k: v for k, v in (ticks[-1].counters if ticks else {}).items()
            if k.startswith(("attention.", "moe.")) and "rows" not in k}


def warm(run: dict, cfg: dict, traffic: dict, seed: int) -> dict:
    """The first steps, one tick each; returns the program's readings."""
    platform, learner = run["platform"], run["learner"]
    m = cfg["model"]
    beta1 = traffic["optimizer"]["beta1"]
    per_tick = learner.steps_per_tick
    learner.steps_per_tick = 1
    out = {}
    for step in range(1, T.WARM_STEPS + 1):
        with span("bench.warm"):
            platform.tick()
        if len(learner.loss_history) != step:
            raise RuntimeError(f"step {step} did not run: "
                               f"{run['client'].status(run['job']).value}")
        if step == 1:
            out["paths"] = _step_paths()
            out["first_grad"] = leaf_norms(learner._state.opt.m,
                                           1 / (1 - beta1))
        if step == T.READ_STEPS:
            p0 = W.make(m, seed, "float32")
            out["change"] = leaf_norms(jax.tree.map(
                jnp.subtract, learner._state.opt.master, p0))
            del p0
            out["bias_change"] = np.asarray(jax.device_get(
                learner._state.buffers["router_bias"])) - run["bias"]
    learner.steps_per_tick = per_tick
    T._check_drawn(learner._data, T.WARM_STEPS)
    out["losses"] = [loss for _, loss in learner.loss_history[:T.READ_STEPS]]
    return out


def held_rows(timed: dict, m: dict) -> dict:
    """Rows routed a step to a held expert in the window, on average
    and for the busiest, from the program's ``moe.rows_held`` and
    ``moe.rows_busiest`` counters; {} where it keeps none."""
    records = window_ticks({"timers": timed})
    if not records or "moe.rows_held" not in records[-1].counters:
        return {}
    per = sum(t["steps"] for t in timed["ticks"]) * W.n_moe(m)
    return {"per_expert": sum(r.counters["moe.rows_held"] for r in records)
            / (per * m["e_local"]),
            "busiest": sum(r.counters["moe.rows_busiest"] for r in records)
            / per}


def write_step_hlo(learner, traffic: dict, trace_dir) -> None:
    """Write the text of the learner's compiled train step to
    ``trace_dir``. The step is lowered on the shapes it ran on, which
    finds the executable it ran, so the text's instruction names are the
    trace's."""
    rows = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq"]),
                                jnp.int32)
    text = learner._train_step.lower(
        learner._state, {"tokens": rows, "labels": rows}).compile().as_text()
    Path(trace_dir).mkdir(parents=True, exist_ok=True)
    (Path(trace_dir) / STEP_HLO).write_text(text)


def reference_readings(cfg: dict, traffic: dict, seed: int, bias,
                       lowp: bool = False, rows: float = 1.0) -> dict:
    """The reference's readings of the first steps on the same rows,
    from the routers' biases ``bias`` (``start_bias``); ``rows`` < 1
    keeps only that share of each batch (a planted fault)."""
    m = cfg["model"]
    opt = dict(traffic["optimizer"], total_steps=traffic["steps"])
    keep = max(1, int(traffic["batch"] * rows))
    batches = [{k: v[:keep] for k, v in
                gen.train_rows(traffic, seed, m["vocab_size"], s).items()}
               for s in range(T.READ_STEPS)]
    losses, first, change, end = ref.train(m, opt, seed, bias, batches,
                                           lowp, reduce=leaf_norms)
    return {"losses": losses, "first_grad": first, "change": change,
            "bias_change": np.asarray(jax.device_get(end)) - bias}


def compare(prog: dict, refr: dict) -> dict:
    """``bench/train.py``'s numbers, and ``bias_gap``."""
    out = T.compare(prog, refr)
    want = refr["bias_change"]
    out["bias_gap"] = float(np.linalg.norm(prog["bias_change"] - want)
                            / max(np.linalg.norm(want), 1e-30))
    return out


def run(cfg: dict, traffic: dict, seed: int, seconds: float,
        trace_dir, clock, t_start: float) -> dict:
    """One run of an MoE training cell; see bench/run.py for what it
    returns."""
    objs = build(cfg, traffic, seed)
    prog = warm(objs, cfg, traffic, seed)
    before = clock.reading()
    setup_s = time.perf_counter() - t_start
    with Trace(trace_dir, trace_dir is not None):
        timed = T.window(objs, traffic, seconds)
    in_window = clock.since(before)
    m = cfg["model"]
    if trace_dir is not None:
        write_step_hlo(objs["learner"], traffic, trace_dir)
    status = objs["client"].status(objs["job"]).value
    memory = memory_peak_bytes()
    objs["learner"].kill()  # drops the learner's state from the device
    bias = objs["bias"]
    free(objs)
    in_use = bytes_in_use()
    refr = reference_readings(cfg, traffic, seed, bias)
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
        "trace_dir": trace_dir,
        "memory_peak_bytes": memory,
        "compiles_in_window": in_window,
        "numbers": numbers,
        "notes": {"job_status": status, "step_paths": prog.get("paths"),
                  "held_rows_a_step": held_rows(timed, m),
                  "losses": prog["losses"],
                  "tick_walls_s": [round(t["wall_s"], 4)
                                   for t in timed["ticks"]],
                  "ref_losses": refr["losses"],
                  "bytes_in_use_after_free": in_use},
    }
