#!/usr/bin/env python3
"""Bring-up smoke run of the platform on a TPU, through its own entry points.

    python chip_smoke.py               # one chip: a training job, then a Service
    python chip_smoke.py --four-chips  # four chips: the sharded train step only

One process does all of it, and it refuses to run anywhere but on a TPU:
there is no CPU fallback. The model is ``smollm-360m`` at its published
widths with random weights from a fixed seed.

* Training: a ``JobManifest`` is submitted through ``ApiClient`` to an
  ``FfDLPlatform`` and ticked until it is terminal. It must end COMPLETED
  with finite losses, a first loss near ln(vocab), and its checkpoints in
  the object store. Timings come from the host clock around whole platform
  ticks; each stepping tick ends in the learner's ``device_get`` of its
  losses, which waits for the device.
* Serving: a ``Service`` is applied through ``WorkloadClient`` to a
  one-shard ``Federation``, a ``ServeEngine`` is attached, and three
  requests are invoked. Every answer must carry engine tokens inside the
  vocabulary, the same for the same prompt. Decoding through the cache
  must agree with a teacher-forced forward pass of the same weights
  (``lm_apply``) on a small input: the family's tiny preset. At full depth
  the agreement is only printed, because the randomly initialized 32-layer
  model turns a rounding difference into an O(1) change of its logits.
* Four chips: the launcher's step (``repro.launch.train.jit_train_step``)
  on a 2x2 data x model mesh against the same step on one device, with the
  same seed and batches; every parameter and optimizer leaf must live on
  all four devices.

Numbers go to earlier lines of standard output. The last line is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every phase
passed. The exit code is 0 then, and non-zero otherwise.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "smollm-360m"
TRAIN_SPEC = {"tiny": False, "steps": 15, "batch": 4, "seq": 2048,
              "lr": 3e-4, "warmup": 10, "seed": 0}
CHECKPOINT_INTERVAL = 10
SERVE_PAYLOAD = {"prompt_len": 512, "gen": 32}
SMALL_PAYLOAD = {"prompt_len": 16, "gen": 8}
N_REQUESTS = 3
# the first loss of a freshly initialized model sits near ln(vocab)
FIRST_LOSS_TOL = 1.0
# mean greedy margin over mean logit spread of the reference (see
# _reference_margin); a broken decode cache gives a ratio near 1
MAX_MARGIN_RATIO = 0.2
# as in tests/test_sharding.py: first loss, then after one optimizer step
SHARDED_RTOL = (2e-4, 4e-3)


class CompileClock:
    """Sums the backend compile time JAX reports, and counts cache hits."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reading(self):
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits}

    def close(self):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def _memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def _report(phase: str, **values):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in values.items()),
          flush=True)


def train_phase(clock: CompileClock, arch: str = ARCH,
                train: dict = TRAIN_SPEC,
                checkpoint_interval: int = CHECKPOINT_INTERVAL) -> dict:
    """Submit one training job and tick the platform until it ends."""
    import jax

    from repro.api import ApiClient
    from repro.configs import get_config, get_tiny_config
    from repro.core import FfDLPlatform, JobManifest, JobStatus

    platform = FfDLPlatform(n_hosts=1, chips_per_host=1)
    client = ApiClient.for_platform(platform, tenant="smoke")
    job = client.submit(JobManifest(
        name=f"{arch}-smoke", tenant="smoke", n_learners=1,
        chips_per_learner=1, arch=arch,
        checkpoint_interval=checkpoint_interval, train=dict(train)))
    terminal = (JobStatus.COMPLETED, JobStatus.FAILED, JobStatus.HALTED)

    lines: list[str] = []
    stepping_ticks = []  # (wall seconds, steps run in the tick, ckpt saved)
    slow_ticks = []  # (wall seconds, job status after it) of ticks over 1 s
    compile_before = clock.seconds
    t_start = time.perf_counter()
    for _ in range(10_000):
        t0 = time.perf_counter()
        platform.tick()
        dt = time.perf_counter() - t0
        new = client.logs(job)[len(lines):]
        lines += new
        n_steps = sum(1 for ln in new if ln.startswith("step "))
        if n_steps:
            saved = any(int(ln.split()[1]) % checkpoint_interval == 0
                        for ln in new if ln.startswith("step "))
            stepping_ticks.append((dt, n_steps, saved))
        status = client.status(job)
        if dt > 1.0:
            slow_ticks.append((dt, status.value))
        if status in terminal:
            break
    wall = time.perf_counter() - t_start
    status = client.status(job)
    for ln in lines:
        print(f"  [job log] {ln}")

    losses = [float(ln.split()[3]) for ln in lines if ln.startswith("step ")]
    n_params = [int(ln.split()[2]) for ln in lines if ln.startswith("model ")]
    cfg = (get_tiny_config(arch) if train.get("tiny", True)
           else get_config(arch))
    ln_v = math.log(cfg.vocab_size)
    results = platform.objstore.list("results", job)
    ckpt_steps = sorted({int(k.split("/step_")[1].split("/")[0])
                         for k in results if "/ckpt/step_" in k})
    want = sorted(set(range(checkpoint_interval, train["steps"] + 1,
                            checkpoint_interval)) | {train["steps"]})
    # the steadiest ticks: not the first (it compiles), no checkpoint save
    steady = [(dt, n) for dt, n, saved in stepping_ticks[1:] if not saved]
    step_s = (sum(dt for dt, _ in steady) / sum(n for _, n in steady)
              if steady else float("nan"))
    tokens = train["batch"] * train["seq"]
    out = {
        "status": status.value, "params": n_params, "steps": len(losses),
        "losses": losses, "ln_vocab": ln_v,
        "compile_s": clock.seconds - compile_before,
        "stepping_ticks_s_steps_saved": stepping_ticks,
        "slow_ticks_s_status": slow_ticks,
        "steady_step_s": step_s, "tokens_per_step": tokens,
        "steady_tokens_per_s": tokens / step_s,
        "job_wall_s": wall, "checkpoints": ckpt_steps,
        "memory": _memory(jax.devices()[0]),
    }
    _report("train", **out)

    if status != JobStatus.COMPLETED:
        raise RuntimeError(f"training job ended {status.value}, "
                           f"not COMPLETED")
    if len(losses) != train["steps"]:
        raise RuntimeError(f"{len(losses)} losses for {train['steps']} steps")
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"non-finite loss: {losses}")
    if abs(losses[0] - ln_v) > FIRST_LOSS_TOL:
        raise RuntimeError(f"first loss {losses[0]} is not near "
                           f"ln(vocab) = {ln_v:.3f}")
    if ckpt_steps != want:
        raise RuntimeError(f"checkpoints at steps {ckpt_steps}, "
                           f"expected {want}")
    return out


def _reference_margin(engine, payload: dict, tokens) -> tuple:
    """Teacher-forced reference for one greedy answer of ``engine``.

    One forward pass of the engine's own weights (``lm_apply``) over
    prompt + generated tokens. Returns the mean margin by which the
    reference prefers its best token over the engine's, divided by the
    mean spread (max - mean) of the reference logits, and the share of
    positions where both pick the same token. Decoding through a correct
    cache gives a ratio near 0; one through a broken cache picks tokens
    the reference ranks at random, a ratio near 1.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm

    toks = np.asarray(tokens)
    s, gen = payload["prompt_len"], len(toks)
    prompt = jax.random.randint(engine._key, (1, s), 0,
                                engine.cfg.vocab_size)
    seq = jnp.concatenate([prompt, jnp.asarray(toks[None, :-1], jnp.int32)],
                          axis=1)
    logits, _, _ = jax.jit(
        lambda p, t: lm.lm_apply(p, t, engine.cfg, mode="train"))(
            engine.params, seq)
    ref = np.asarray(logits[0, s - 1:], np.float32)
    if not np.all(np.isfinite(ref)):
        raise RuntimeError("non-finite reference logits")
    margin = ref.max(-1) - ref[np.arange(gen), toks]
    spread = ref.max(-1) - ref.mean(-1)
    return (float(margin.mean() / spread.mean()),
            float((ref.argmax(-1) == toks).mean()))


def serve_phase(clock: CompileClock, arch: str = ARCH, tiny: bool = False,
                payload: dict = SERVE_PAYLOAD,
                n_requests: int = N_REQUESTS) -> dict:
    """Apply a Service, attach a real engine, and invoke it."""
    import jax

    from repro.api import Federation
    from repro.api.client import WorkloadClient
    from repro.launch.serve import ServeEngine

    fed = Federation(n_shards=1)
    client = WorkloadClient.for_platform(fed, tenant="smoke")
    client.apply({"kind": "Service", "name": "lm", "tenant": "smoke",
                  "replicas": 1, "engine": "real", "arch": arch})
    compile_before = clock.seconds
    t0 = time.perf_counter()
    engine = ServeEngine(arch, tiny=tiny)
    warm = engine.infer(payload)  # set-up: compiles prefill and decode
    setup_s = time.perf_counter() - t0
    setup_compile_s = clock.seconds - compile_before
    fed.workloads.attach_engine("smoke", "lm", engine)
    for _ in range(100):
        fed.tick()
        if client.get("lm")["status"]["phase"] == "RUNNING":
            break
    else:
        raise RuntimeError("the Service never reached RUNNING")

    answers, latencies = [], []
    for _ in range(n_requests):
        t0 = time.perf_counter()
        answers.append(client.invoke("lm", payload=payload)["output"])
        latencies.append(time.perf_counter() - t0)
    invoke_compile_s = clock.seconds - compile_before - setup_compile_s
    full_ratio, full_agree = _reference_margin(engine, payload,
                                               warm["tokens"])
    # the small-input reference: the family's tiny preset, same engine
    small = ServeEngine(arch, tiny=True)
    small_tokens = small.infer(SMALL_PAYLOAD)["tokens"]
    small_ratio, small_agree = _reference_margin(small, SMALL_PAYLOAD,
                                                 small_tokens)
    out = {
        "setup_s": setup_s, "setup_compile_s": setup_compile_s,
        "compile_s_during_invokes": invoke_compile_s,
        "requests": n_requests, "prompt_len": payload["prompt_len"],
        "gen": payload["gen"], "invoke_s": latencies,
        "decode_ms_per_token": [a.get("decode_ms_per_token")
                                for a in answers],
        "first_tokens": [a.get("tokens", [])[:8] for a in answers],
        "full_depth_reference_margin_ratio": full_ratio,
        "full_depth_reference_argmax_agreement": full_agree,
        "small_reference_margin_ratio": small_ratio,
        "small_reference_argmax_agreement": small_agree,
        "memory": _memory(jax.devices()[0]),
    }
    _report("serve", **out)

    vocab = engine.cfg.vocab_size
    for a in answers:
        toks = a.get("tokens")
        if toks is None:
            raise RuntimeError(f"invoke answered without engine tokens: {a}")
        if len(toks) != payload["gen"] or not all(
                isinstance(t, int) and 0 <= t < vocab for t in toks):
            raise RuntimeError(f"tokens outside the vocabulary or of the "
                               f"wrong length: {toks}")
        if toks != warm["tokens"]:
            raise RuntimeError("greedy answers to the same prompt differ")
    if small_ratio > MAX_MARGIN_RATIO:
        raise RuntimeError(f"the small engine's tokens disagree with the "
                           f"reference forward: margin ratio "
                           f"{small_ratio:.4f}")
    return out


def four_chip_phase(clock: CompileClock, arch: str = ARCH,
                    tiny: bool = False, batch: int = 4, seq: int = 2048,
                    n_steps: int = 2) -> dict:
    """The launcher's step on a 2x2 mesh against one device."""
    import jax
    import numpy as np
    from jax.sharding import AxisType

    from repro.configs import get_config, get_tiny_config
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.mesh import make_env
    from repro.launch.train import jit_train_step
    from repro.models import steps
    from repro.optim import adamw
    from repro.parallel import null_env, use_env

    cfg = get_tiny_config(arch) if tiny else get_config(arch)
    opt = adamw.AdamWConfig(total_steps=10, warmup_steps=0)
    key = jax.random.key(0)
    data = SyntheticLM(DataConfig(cfg.vocab_size, seq, batch, seed=0))
    batches = [data.batch_at(i) for i in range(n_steps)]

    def run(env):
        with use_env(env):
            step, st_sh, b_sh = jit_train_step(cfg, opt, env, batch, seq)
            state = steps.init_train_state(cfg, key)
            if st_sh is not None:
                state = jax.device_put(state, st_sh)
            losses, times = [], []
            for b in batches:
                if b_sh is not None:
                    b = jax.device_put(b, b_sh)
                t0 = time.perf_counter()
                state, metrics = step(state, b)
                losses.append(float(metrics["loss"]))
                times.append(time.perf_counter() - t0)
        return state, losses, times

    compile_before = clock.seconds
    state, single, single_times = run(null_env())
    del state
    gc.collect()

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    state, sharded, sharded_times = run(make_env(mesh))

    leaves = jax.tree.leaves((state.params, state.opt))
    devices = set(mesh.devices.flat)
    spread = [len({s.device for s in leaf.addressable_shards})
              for leaf in leaves]
    full_bytes = sum(leaf.nbytes for leaf in leaves)
    per_device = {str(d.id): 0 for d in devices}
    for leaf in leaves:
        for s in leaf.addressable_shards:
            per_device[str(s.device.id)] += s.data.nbytes
    memory = {str(d.id): _memory(d) for d in devices}
    out = {
        "mesh": "2x2 data x model", "batch": batch, "seq": seq,
        "single_losses": single, "sharded_losses": sharded,
        "rel_diff": [abs(a - b) / abs(b) for a, b in zip(sharded, single)],
        "rtol": list(SHARDED_RTOL),
        "single_step_s": single_times, "sharded_step_s": sharded_times,
        "compile_s": clock.seconds - compile_before,
        "leaves": len(leaves),
        "partitioned_leaves": sum(not leaf.sharding.is_fully_replicated
                                  for leaf in leaves),
        "state_bytes": full_bytes, "state_bytes_per_device": per_device,
        "memory": memory,
    }
    _report("four_chips", **out)

    for got, want, rtol in zip(sharded, single, SHARDED_RTOL):
        np.testing.assert_allclose(got, want, rtol=rtol)
    if min(spread) < len(devices):
        raise RuntimeError(f"a leaf sits on {min(spread)} of "
                           f"{len(devices)} devices")
    if max(per_device.values()) >= full_bytes:
        raise RuntimeError("no device holds less than the whole state")
    if not all((m["bytes_in_use"] or 0) > 0 for m in memory.values()):
        raise RuntimeError(f"a device shows no memory in use: {memory}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh train step against one "
                         "device (needs four chips)")
    args = ap.parse_args(argv)

    try:
        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's sources are missing: {e}",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); not running",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    _report("device", platform=dev.platform, kind=dev.device_kind,
            count=len(devices), jax=jax.__version__, cache_dir=cache_dir)

    phases = ([four_chip_phase] if args.four_chips
              else [train_phase, serve_phase])
    failed = []
    for phase in phases:
        t0 = time.perf_counter()
        try:
            phase(clock)
        except Exception:
            traceback.print_exc()
            failed.append(phase.__name__)
        gc.collect()  # release the phase's platform and device state
        _report(phase.__name__, wall_s=time.perf_counter() - t0,
                ok=phase.__name__ not in failed, **clock.reading())
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
