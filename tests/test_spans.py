"""Program spans and counters (repro.obs.spans): the recorder, the spans
inside a platform tick as the profiler records them, and /metrics."""

import gc
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

import pytest

from repro.api import ApiClient, Federation
from repro.api.http import ApiHttpServer
from repro.core import FfDLPlatform, JobManifest
from repro.obs import (
    METRIC_NAMES,
    RING_TICKS,
    count,
    gc_pause_totals,
    phase_histograms,
    recent_ticks,
    span,
)
from repro.obs.spans import ROOT

SRC = Path(__file__).resolve().parent.parent / "src"
PHASES = ("timers", "chaos", "cluster", "lcm", "guardians", "admission",
          "scheduler", "wal_flush", "accounting")


def _tick(shard="t-shard", body=None):
    with span(ROOT, shard=shard):
        if body is not None:
            body()
    return recent_ticks(1)[0]


# -- the recorder ---------------------------------------------------------

def test_nesting_and_self_time():
    def body():
        with span("a"):
            with span("b"):
                pass
            with span("b"):
                pass
        with span("c"):
            pass

    rec = _tick(body=body)
    names = [s.name for s in rec.spans]
    assert names == [ROOT, "a", "b", "b", "c"]
    assert [s.parent for s in rec.spans] == [-1, 0, 1, 1, 0]
    for s in rec.spans[1:]:
        parent = rec.spans[s.parent]
        assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns
    assert rec.totals["b"].count == 2
    assert rec.self_s("a") == pytest.approx(
        rec.total_s("a") - rec.total_s("b"), abs=1e-9)
    assert rec.self_s(ROOT) == pytest.approx(
        rec.duration_s - rec.total_s("a") - rec.total_s("c"), abs=1e-9)
    assert rec.self_s("b") == rec.total_s("b")
    assert rec.total_s("absent") == 0.0 and rec.shard == "t-shard"


def test_span_closes_and_records_when_its_body_raises():
    def body():
        with span("inner"):
            raise TimeoutError("deadline")

    with pytest.raises(TimeoutError):
        _tick(shard="t-raise", body=body)
    rec = recent_ticks(1)[0]
    assert rec.shard == "t-raise"
    assert [s.name for s in rec.spans] == [ROOT, "inner"]
    assert all(s.end_ns >= s.start_ns for s in rec.spans)
    # the thread's stack unwound: the next span is a root again
    assert _tick(shard="t-after").spans[0].parent == -1


def test_a_tick_cut_by_its_deadline_is_recorded():
    """A wedged shard's tick raises DeadlineExceeded out of its spans;
    its record still shows where the time went, and the next shard's
    tick is recorded after it."""
    fed = Federation(n_shards=2, n_api_replicas=1, seed=0, tick_budget_s=0.2)
    fed.faults.install("shard.tick", key="shard-0", hang=True)
    try:
        fed.tick()
    finally:
        fed.faults.clear()
    wedged, healthy = recent_ticks(2)
    assert (wedged.shard, healthy.shard) == ("shard-0", "shard-1")
    assert fed.backends[0].breaker.deadline_exceeded_total == 1
    assert wedged.duration_s >= 0.2
    assert wedged.self_s(ROOT) == pytest.approx(wedged.duration_s, rel=1e-6)
    assert [s.name for s in wedged.spans] == [ROOT]
    assert "ffdl.tick.scheduler" in healthy.totals


def test_ring_keeps_the_last_ticks_only():
    for i in range(RING_TICKS + 7):
        _tick(shard=f"t-ring-{i}")
    recs = recent_ticks()
    assert len(recs) == RING_TICKS
    assert recs[-1].shard == f"t-ring-{RING_TICKS + 6}"
    assert recs[0].shard == "t-ring-7"
    assert [r.shard for r in recent_ticks(2)] == [
        f"t-ring-{RING_TICKS + 5}", f"t-ring-{RING_TICKS + 6}"]
    assert recent_ticks(0) == []


def test_counters_belong_to_their_root():
    count("learner.steps", 99)  # no span open: dropped

    def body(n):
        def f():
            count("learner.steps")
            with span("ffdl.learner.tick"):
                count("learner.steps", n)
        return f

    a = _tick(body=body(2))
    b = _tick(body=body(4))
    assert a.counters["learner.steps"] == 3
    assert b.counters["learner.steps"] == 5


def test_only_tick_roots_are_kept():
    before = recent_ticks(1)
    with span("not.a.tick"):
        count("x")
    assert recent_ticks(1) == before


def test_parents_are_per_thread():
    barrier = threading.Barrier(2, timeout=10)
    errors = []

    def worker(shard):
        try:
            with span(ROOT, shard=shard):
                barrier.wait()  # both ticks open at once
                with span(f"child.{shard}"):
                    count("learner.steps")
                    barrier.wait()
        except Exception as e:  # pragma: no cover - reported below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(f"t-thr-{i}",))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=20)
    assert not errors and not any(t.is_alive() for t in threads)
    recs = {r.shard: r for r in recent_ticks(2)}
    assert set(recs) == {"t-thr-0", "t-thr-1"}
    for shard, rec in recs.items():
        assert [s.name for s in rec.spans] == [ROOT, f"child.{shard}"]
        assert rec.counters == {"learner.steps": 1}


def test_gc_pauses_are_counted_in_the_tick():
    before = gc_pause_totals().get(2, 0.0)
    rec = _tick(body=lambda: gc.collect())
    assert rec.counters["gc.pause_s"] > 0
    assert gc_pause_totals()[2] > before


def test_phase_histograms_take_each_phase_time():
    def body():
        with span("ffdl.tick.guardians"):
            with span("ffdl.learner.tick"):
                time.sleep(0.01)
        with span("ffdl.tick.guardians"):
            pass

    rec = _tick(shard="t-hist", body=body)
    hists = phase_histograms()
    assert ("t-hist", "guardians") in hists
    assert ("t-hist", "ffdl.learner.tick") not in hists
    # one observation per tick, holding the learner's time it called
    _, total, n = hists[("t-hist", "guardians")].snapshot()
    assert n == 1
    assert total == pytest.approx(rec.total_s("ffdl.tick.guardians"),
                                  rel=1e-6)
    assert total >= rec.total_s("ffdl.learner.tick") >= 0.01


def test_spans_do_not_import_jax():
    code = ("import sys\n"
            "from repro.obs.spans import span, count, recent_ticks\n"
            "with span('ffdl.tick', shard='s'):\n"
            "    with span('ffdl.tick.scheduler'):\n"
            "        count('learner.steps')\n"
            "assert recent_ticks(1)[0].counters == {'learner.steps': 1}\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60,
                         env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin"})
    assert out.returncode == 0, out.stderr


# -- a platform tick, as the profiler records it ---------------------------

def _platform_with_learner(shard_id, seq=32):
    p = FfDLPlatform(n_hosts=1, chips_per_host=1, shard_id=shard_id)
    c = ApiClient.for_platform(p, tenant="spans")
    job = c.submit(JobManifest(name="spans", tenant="spans",
                               arch="smollm-360m", n_learners=1,
                               chips_per_learner=1,
                               train={"steps": 40, "batch": 2, "seq": seq}))
    for _ in range(100):
        p.tick()
        g = p.guardians.get(job)
        learner = g.runtimes.get(0) if g is not None else None
        if learner is not None and learner.loss_history:
            return p, learner
    raise AssertionError("the job never stepped")


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_tick_spans_land_in_the_profilers_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    p, learner = _platform_with_learner("t-prof")
    steps0 = len(learner.loss_history)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            p.tick()
    finally:
        jax.profiler.stop_trace()
    steps = len(learner.loss_history) - steps0
    assert steps == 2 * learner.steps_per_tick

    files = list(tmp_path.rglob("*.xplane.pb"))
    assert files
    data = ProfileData.from_file(str(files[0]))
    events = [(ev.name, int(ev.start_ns), int(ev.end_ns))
              for plane in data.planes if plane.name.startswith("/host:CPU")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("ffdl.")]
    ticks = [e for e in events if e[0] == ROOT]
    assert len(ticks) == 2
    for tick in ticks:
        inside = [e for e in events if e is not tick and _inside(e, tick)]
        names = {e[0] for e in inside}
        assert {f"{ROOT}.{ph}" for ph in PHASES} <= names
        guardians = [e for e in inside if e[0] == "ffdl.tick.guardians"]
        assert len(guardians) == 1
        for kind in ("wait", "feed", "dispatch", "report", "tick"):
            marks = [e for e in inside if e[0] == f"ffdl.learner.{kind}"]
            assert marks and all(_inside(e, guardians[0]) for e in marks)
        assert sum(e[0] == "ffdl.learner.dispatch" for e in inside) \
            == learner.steps_per_tick

    recs = recent_ticks(2)
    assert [r.shard for r in recs] == ["t-prof", "t-prof"]
    assert [r.counters["learner.steps"] for r in recs] == [5, 5]
    for r in recs:
        assert r.total_s("ffdl.learner.tick") <= r.total_s(
            "ffdl.tick.guardians")
        assert r.totals["ffdl.learner.dispatch"].count == 5


def test_the_tick_that_builds_the_step_counts_its_attention_path():
    # 128 tokens fit a kernel block: on the CPU the scan is still the path
    p, _ = _platform_with_learner("t-attn", seq=128)
    built = recent_ticks(1)[0]
    assert built.shard == "t-attn"
    assert built.counters["attention.chunked"] >= 1
    assert "attention.kernel" not in built.counters
    p.tick()  # the step is traced once: later ticks count nothing
    assert "attention.chunked" not in recent_ticks(1)[0].counters


# -- /metrics -------------------------------------------------------------

def test_metrics_serve_tick_phases_and_gc_pauses():
    assert "ffdl_tick_phase_seconds" in METRIC_NAMES
    assert "ffdl_gc_pause_seconds_total" in METRIC_NAMES
    p = FfDLPlatform(n_hosts=1, chips_per_host=1, shard_id="t-metrics")
    for _ in range(3):
        p.tick()
    gc.collect()
    server = ApiHttpServer(p).start()
    try:
        with urllib.request.urlopen(server.base_url + "/metrics",
                                    timeout=10) as resp:
            text = resp.read().decode()
    finally:
        server.stop()
    assert "# TYPE ffdl_tick_phase_seconds histogram" in text
    for ph in PHASES:
        assert (f'ffdl_tick_phase_seconds_count{{shard="t-metrics",'
                f'phase="{ph}"}} 3') in text
    # other shards of this process are not this server's
    assert 'shard="t-hist"' not in text
    assert "# TYPE ffdl_gc_pause_seconds_total counter" in text
    assert 'ffdl_gc_pause_seconds_total{generation="2"}' in text
