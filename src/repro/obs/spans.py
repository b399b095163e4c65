"""Program spans and counters: where a platform tick's host time goes.

A shard's ``FfDLPlatform.tick`` runs the whole control plane and every
learner's tick on one thread, and while it runs nothing new is queued on
the device. Spans name that host time for operators and for the profiler:

  * ``span(name)`` times a stretch of host work on ``time.perf_counter_ns``
    and remembers its enclosing span (per thread: a federation ticks its
    shards under ``deadline_scope``, and HTTP handler threads must not
    nest into a tick). Once JAX is loaded it also enters a
    ``jax.profiler.TraceAnnotation`` of the same name, so a profile shows
    the span on the clock of the device's operations. It never imports
    JAX itself: the control plane runs without it;
  * a span closes, and is recorded, even when its body raises — a tick
    cut by ``DeadlineExceeded`` still shows where its time went;
  * ``count(name, n)`` adds to a counter of the open root span;
  * the outermost span on a thread is its root. Each completed
    ``ffdl.tick`` root becomes one :class:`TickRecord` in a bounded
    per-process ring (``RING_TICKS``): for each span name its total and
    self time (self = duration less what its child spans cover), the
    root's counters and its shard. Other roots go to the profiler only;
  * a ``gc.callbacks`` hook, installed once per process, counts the
    collector's pauses into the open root (``gc.pause_s``) and into
    per-generation totals.

``/metrics`` serves the tick phases' times as ``ffdl_tick_phase_seconds``
and the collector's pauses as ``ffdl_gc_pause_seconds_total``
(``api/http.py``). A phase is a child of the root and phases never nest
in each other, so a phase's time includes what it calls (the guardians'
phase holds every learner's tick) and the phases add up to the tick less
the gaps between them.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import NamedTuple, Optional

from repro.obs.metrics import Histogram

ROOT = "ffdl.tick"
# Completed ticks kept per process: a shard ticks about once a
# second in simulation and every few seconds on a chip, so this holds the
# last minutes of ticks, well over what a reader asks for.
RING_TICKS = 512
# Phase times span microseconds (an idle scheduler) to seconds (the
# guardians' phase with its learners' ticks, a checkpoint).
PHASE_BUCKETS = (1e-5, 1e-4, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0,
                 5.0, 10.0)


class SpanRecord(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: int  # index of the enclosing span in TickRecord.spans; -1: root


class SpanTotal(NamedTuple):
    total_s: float
    self_s: float
    count: int


@dataclass(frozen=True)
class TickRecord:
    """One completed ``ffdl.tick``: ``spans`` in the order they opened
    (the root first), ``totals`` per span name, ``counters`` of the
    root."""
    shard: Optional[str]
    spans: tuple
    totals: dict
    counters: dict

    @property
    def duration_s(self) -> float:
        root = self.spans[0]
        return (root.end_ns - root.start_ns) / 1e9

    def total_s(self, name: str) -> float:
        t = self.totals.get(name)
        return t.total_s if t is not None else 0.0

    def self_s(self, name: str) -> float:
        t = self.totals.get(name)
        return t.self_s if t is not None else 0.0


_lock = threading.Lock()  # leaf: the ring and the phase histograms
_ring: deque = deque(maxlen=RING_TICKS)
_phases: dict = {}  # (shard, phase) -> Histogram
_gc_pause_s: dict = {}  # generation -> seconds (written by the gc hook only)
_local = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, once JAX is loaded


class _Root:
    __slots__ = ("shard", "spans", "counters")

    def __init__(self, shard):
        self.shard = shard
        self.spans: list = []
        self.counters: dict = {}


class _Span:
    __slots__ = ("name", "shard", "idx", "parent", "start", "child_ns",
                 "root", "ann")

    def __init__(self, name: str, shard: Optional[str]):
        self.name = name
        self.shard = shard

    def __enter__(self):
        stack = _stack()
        if stack:
            top = stack[-1]
            self.root, self.parent = top.root, top.idx
        else:
            self.root, self.parent = _Root(self.shard), -1
        self.idx = len(self.root.spans)
        self.root.spans.append(None)  # filled in at close, in open order
        self.child_ns = 0
        stack.append(self)
        self.ann = _annotate(self.name)
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        stack = _stack()
        stack.pop()
        self.root.spans[self.idx] = (self.name, self.start, end,
                                     self.parent, self.child_ns)
        if stack:
            stack[-1].child_ns += end - self.start
        else:
            _close_root(self.name, self.root)
        return False


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _annotate(name: str):
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is None:
            return None
        _annotation = profiler.TraceAnnotation
    ann = _annotation(name)
    ann.__enter__()
    return ann


def _close_root(name: str, root: _Root):
    if name != ROOT:
        return  # timed for the profiler, not kept
    spans, totals, phases = [], {}, {}
    for sname, start, end, parent, child_ns in root.spans:
        spans.append(SpanRecord(sname, start, end, parent))
        own = end - start - child_ns
        t = totals.get(sname, (0, 0, 0))
        totals[sname] = (t[0] + end - start, t[1] + own, t[2] + 1)
        if parent == 0:
            phase = sname.removeprefix(ROOT + ".")
            phases[phase] = phases.get(phase, 0) + end - start
    rec = TickRecord(
        shard=root.shard, spans=tuple(spans),
        totals={k: SpanTotal(a / 1e9, b / 1e9, n)
                for k, (a, b, n) in totals.items()},
        counters=dict(root.counters))
    with _lock:
        _ring.append(rec)
        hists = []
        for phase, ns in phases.items():
            h = _phases.get((root.shard, phase))
            if h is None:
                h = _phases[(root.shard, phase)] = Histogram(PHASE_BUCKETS)
            hists.append((h, ns / 1e9))
    for h, seconds in hists:
        h.observe(seconds)


def span(name: str, shard: Optional[str] = None) -> _Span:
    """Context manager timing one stretch of host work; ``shard`` tags a
    root span (``FfDLPlatform.tick`` passes its shard id)."""
    return _Span(name, shard)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` of this thread's open root span; a
    no-op with no span open."""
    stack = getattr(_local, "stack", None)
    if stack:
        counters = stack[-1].root.counters
        counters[name] = counters.get(name, 0) + n


def recent_ticks(n: Optional[int] = None) -> list:
    """The last ``n`` (default all kept) completed ticks, oldest first."""
    with _lock:
        recs = list(_ring)
    if n is None:
        return recs
    return recs[-n:] if n > 0 else []


def phase_histograms() -> dict:
    """(shard, phase) -> Histogram of each tick phase's time."""
    with _lock:
        return dict(_phases)


def gc_pause_totals() -> dict:
    """generation -> seconds the collector paused this process."""
    return dict(_gc_pause_s)


_gc_t0 = [0]


def _on_gc(phase: str, info: dict) -> None:
    # Runs inside the collection, on the thread it paused; takes no lock.
    if phase == "start":
        _gc_t0[0] = time.perf_counter_ns()
        return
    seconds = (time.perf_counter_ns() - _gc_t0[0]) / 1e9
    gen = info.get("generation", 0)
    _gc_pause_s[gen] = _gc_pause_s.get(gen, 0.0) + seconds
    count("gc.pause_s", seconds)


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)
