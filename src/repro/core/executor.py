"""Learner runtime: what actually runs inside learner pods.

FfDL treats the framework as opaque — learners communicate with the platform
only through "lowest common denominator" channels (§7): a shared filesystem
(exit-code and status files on the job's NFS volume), environment-style
config, and logs to stdout. We reproduce that contract:

  * ``JobVolume`` — the shared NFS volume: plain key→bytes files, persistent
    across pod crashes (it's a PVC), deleted at job GC.
  * ``SimLearner`` — workload model for scheduler-scale benchmarks: runs for
    ``sim_duration`` clock-seconds, optionally writing checkpoints.
  * ``RealLearner`` — an actual JAX training loop (model from configs/,
    optimizer, data pipeline, checkpoint/restore through the object store):
    the platform path used by examples/ and the overhead benchmark. On
    restart it searches the bucket for the latest valid checkpoint and
    resumes — the paper's recovery contract.

Learners never talk to the Guardian directly: they write
``status/learner-<k>`` and ``exit/learner-<k>`` files; the controller helper
(controller.py) relays them to etcd.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Optional

from repro.ckpt import checkpoint as ckpt
from repro.core.types import EventLog, JobManifest
from repro.obs.spans import count, span


class JobVolume:
    """Shared NFS volume (PVC): survives pod crashes, deleted at job GC."""

    def __init__(self, job_id: str):
        self.job_id = job_id
        self.files: dict[str, str] = {}
        self.provisioned = True

    def write(self, path: str, content: str):
        if not self.provisioned:
            raise IOError(f"volume for {self.job_id} not provisioned")
        self.files[path] = content

    def read(self, path: str) -> Optional[str]:
        if not self.provisioned:
            raise IOError(f"volume for {self.job_id} not provisioned")
        return self.files.get(path)

    def list(self, prefix: str = "") -> list[str]:
        return sorted(k for k in self.files if k.startswith(prefix))


@dataclass
class LearnerContext:
    job_id: str
    learner_idx: int
    manifest: JobManifest
    volume: JobVolume
    clock: Any
    events: EventLog
    objstore: Any  # ObjectStore (checkpoints + results)

    @property
    def pod_name(self) -> str:
        return f"{self.job_id}-l{self.learner_idx}"

    def set_status(self, status: str, extra: Optional[dict] = None):
        payload = {"status": status, "ts": self.clock.now(),
                   "step": (extra or {}).get("step", 0)}
        payload.update(extra or {})
        self.volume.write(f"status/learner-{self.learner_idx}",
                          json.dumps(payload))

    def write_exit(self, code: int, msg: str = ""):
        self.volume.write(f"exit/learner-{self.learner_idx}",
                          json.dumps({"code": code, "msg": msg,
                                      "ts": self.clock.now()}))

    def log(self, line: str):
        prev = self.volume.files.get(f"logs/learner-{self.learner_idx}", "")
        self.volume.write(f"logs/learner-{self.learner_idx}",
                          prev + line + "\n")


class SimLearner:
    """Clock-driven workload model (used by scale/scheduling benchmarks).

    Phases: DOWNLOADING (data_latency) → PROCESSING (sim_duration) →
    STORING (store_latency) → exit 0. ``kill()`` models a process crash;
    progress resumes from the last checkpoint boundary.
    """

    DATA_LATENCY = 30.0
    STORE_LATENCY = 10.0
    CKPT_PERIOD = 120.0  # sim-seconds of work per checkpoint

    def __init__(self, ctx: LearnerContext, slowdown: float = 1.0):
        self.ctx = ctx
        self.slowdown = slowdown
        self.phase = "INIT"
        self.progress = 0.0  # seconds of work completed
        self.checkpointed = 0.0  # durable progress
        self._phase_started = None
        self.done = False
        self.stalled = False  # chaos: silent straggler (alive, no progress)

    def stall(self):
        self.stalled = True

    def start(self, resume: bool = False):
        self.phase = "DOWNLOADING"
        self._phase_started = self.ctx.clock.now()
        if resume:
            # durable progress lives on the volume (survives process death)
            raw = self.ctx.volume.read(f"ckpt/learner-{self.ctx.learner_idx}")
            self.checkpointed = float(raw) if raw else 0.0
            self.progress = self.checkpointed
        self.ctx.set_status("DOWNLOADING")

    def kill(self):
        self.phase = "DEAD"

    def tick(self):
        if self.phase in ("INIT", "DEAD") or self.done:
            return
        now = self.ctx.clock.now()
        dur = self.ctx.manifest.sim_duration or 60.0
        if self.phase == "DOWNLOADING":
            if now - self._phase_started >= self.DATA_LATENCY:
                self.phase = "PROCESSING"
                self._phase_started = now
                self._last = now
                self.ctx.set_status("PROCESSING")
                # learners log to stdout; the LogCollector tails it into
                # the searchable index (§3.2) — and `logs --follow` streams
                # it live over the wire
                self.ctx.log(f"processing started "
                             f"(target {dur:.0f} sim-seconds)")
            return
        if self.phase == "PROCESSING":
            if not self.stalled:
                self.progress += (now - self._last) / self.slowdown
            self._last = now
            self.ctx.set_status("PROCESSING", {"progress": self.progress})
            if self.progress - self.checkpointed >= self.CKPT_PERIOD:
                self.checkpointed = self.progress
                self.ctx.volume.write(
                    f"ckpt/learner-{self.ctx.learner_idx}",
                    str(self.checkpointed))
                self.ctx.log(f"checkpointed at progress "
                             f"{self.checkpointed:.0f}/{dur:.0f}")
            if self.progress >= dur:
                self.phase = "STORING"
                self._phase_started = now
                self.ctx.set_status("STORING")
                self.ctx.log("storing results")
            return
        if self.phase == "STORING":
            if now - self._phase_started >= self.STORE_LATENCY:
                self.done = True
                self.ctx.set_status("COMPLETED", {"progress": self.progress})
                self.ctx.log("completed")
                self.ctx.write_exit(0)


class RealLearner:
    """An actual JAX training job driven through the platform.

    Runs ``steps_per_tick`` real optimizer steps per platform tick;
    checkpoints every ``manifest.checkpoint_interval`` steps to the object
    store; on (re)start, resumes from the newest valid checkpoint.
    """

    def __init__(self, ctx: LearnerContext, steps_per_tick: int = 5):
        self.ctx = ctx
        self.steps_per_tick = steps_per_tick
        self.phase = "INIT"
        self.done = False
        self._state = None
        self._train_step = None
        self._data = None
        self._bucket = None
        self.loss_history: list[tuple[int, float]] = []

    # -- setup ----------------------------------------------------------
    def _build(self):
        import jax
        from repro.configs import get_tiny_config, get_config
        from repro.data.objectstore import MountedBucket
        from repro.data.pipeline import DataConfig, SyntheticLM
        from repro.models import steps as msteps
        from repro.optim import adamw
        from repro.utils import tree_count

        m = self.ctx.manifest
        t = m.train
        cfg = (get_tiny_config(m.arch) if t.get("tiny", True)
               else get_config(m.arch))
        for k, v in t.get("overrides", {}).items():
            cfg = cfg.replace(**{k: v})
        self.cfg = cfg
        self.total_steps = int(t.get("steps", 100))
        opt_cfg = adamw.AdamWConfig(
            lr=t.get("lr", 3e-4), warmup_steps=t.get("warmup", 10),
            total_steps=self.total_steps)
        self._train_step = jax.jit(msteps.make_train_step(cfg, opt_cfg))
        self._data = SyntheticLM(DataConfig(
            vocab_size=cfg.vocab_size, seq_len=t.get("seq", 128),
            global_batch=t.get("batch", 8), seed=t.get("seed", 0)))
        self._bucket = MountedBucket(self.ctx.objstore,
                                     self.ctx.manifest.results_bucket)
        self.ctx.objstore.create_bucket(self.ctx.manifest.results_bucket)
        self._ckpt_prefix = f"{self.ctx.job_id}/ckpt"

        # Resume from the latest valid checkpoint if one exists (§3.8).
        latest = ckpt.latest_step(self._bucket, self._ckpt_prefix)
        if latest is not None:
            abstract = jax.eval_shape(
                lambda: msteps.init_train_state(cfg, jax.random.key(0)))
            self._state, meta = ckpt.restore(self._bucket, self._ckpt_prefix,
                                             latest, like=abstract)
            self._state = jax.tree.map(jax.numpy.asarray, self._state)
            self.ctx.log(f"resumed from checkpoint step {latest}")
            self.ctx.events.emit("learner", "resume_from_checkpoint",
                                 job=self.ctx.job_id, step=latest)
        else:
            self._state = msteps.init_train_state(
                cfg, jax.random.key(int(t.get("seed", 0))))
        self.ctx.log(f"model {cfg.name}: "
                     f"{tree_count(self._state.params)} parameters")

    def start(self, resume: bool = False):
        self.phase = "DOWNLOADING"
        self.ctx.set_status("DOWNLOADING")

    def kill(self):
        self.phase = "DEAD"
        self._state = None  # lose in-memory state, like a real process crash
        self._train_step = None

    @property
    def step(self) -> int:
        return int(self._state.step) if self._state is not None else 0

    def tick(self):
        # Spans split the learner's host time for operators and the
        # profiler: waiting on the device, building batches, enqueueing
        # steps, reporting, checkpoint stalls.
        with span("ffdl.learner.tick"):
            self._tick()

    def _tick(self):
        if self.phase in ("INIT", "DEAD") or self.done:
            return
        if self.phase == "DOWNLOADING":
            try:
                self._build()
            except Exception as e:  # surfaces as learner failure
                self.ctx.log(f"fatal: {e}")
                self.ctx.set_status("FAILED", {"error": str(e)})
                self.ctx.write_exit(1, str(e))
                self.done = True
                return
            self.phase = "PROCESSING"
            self.ctx.set_status("PROCESSING", {"step": self.step})
            return
        if self.phase == "PROCESSING":
            import jax
            import numpy as np
            m = self.ctx.manifest
            losses = []
            for _ in range(self.steps_per_tick):
                with span("ffdl.learner.wait"):
                    step = self.step  # reads the previous step's output
                if step >= self.total_steps:
                    break
                with span("ffdl.learner.feed"):
                    batch = self._data.batch_at(step)
                with span("ffdl.learner.dispatch"):
                    self._state, metrics = self._train_step(self._state,
                                                            batch)
                count("learner.steps")
                losses.append((step, metrics["loss"]))
                if (step + 1) % m.checkpoint_interval == 0:
                    with span("ffdl.learner.wait"):
                        loss = float(metrics["loss"])
                    with span("ffdl.ckpt.save"):
                        ckpt.save(self._bucket, self._ckpt_prefix, step + 1,
                                  self._state, {"loss": loss})
                    self.ctx.events.emit("learner", "checkpoint",
                                         job=self.ctx.job_id, step=step + 1)
            # status/metric sync once per tick (periodic updates, §2) — not
            # per step, so the platform never serializes the device queue.
            with span("ffdl.learner.wait"):
                values = jax.device_get([loss for _, loss in losses])
            with span("ffdl.learner.report"):
                for (step, _), loss in zip(losses, values):
                    loss = float(loss)
                    self.loss_history.append((step, loss))
                    self.ctx.log(f"step {step + 1} loss {loss!r}")
                    if not np.isfinite(loss):
                        self.ctx.set_status("FAILED", {"error": "nan loss"})
                        self.ctx.write_exit(2, "non-finite loss")
                        self.done = True
                        return
                self.ctx.set_status("PROCESSING", {"step": self.step})
            if self.step >= self.total_steps:
                self.phase = "STORING"
                self.ctx.set_status("STORING", {"step": self.step})
            return
        if self.phase == "STORING":
            with span("ffdl.ckpt.save"):
                ckpt.save(self._bucket, self._ckpt_prefix, self.step,
                          self._state, {"final": True})
            self._bucket.write(f"{self.ctx.job_id}/model/DONE",
                               json.dumps({"steps": self.step}))
            self.done = True
            self.ctx.set_status("COMPLETED", {"step": self.step})
            self.ctx.write_exit(0)


def make_learner(ctx: LearnerContext):
    if ctx.manifest.arch is not None:
        return RealLearner(ctx)
    return SimLearner(ctx)
