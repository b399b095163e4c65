"""From a profiler trace to device busy time, idle gaps and top operations.

The reduction works on plain lists of intervals in nanoseconds, so a test
can check it on a small recorded trace (``tests/trace_sample.json``).
``load`` turns the profiler's ``.xplane.pb`` into those lists:

* device intervals: the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane, the operations the device ran;
* host spans: the benchmark's own ``jax.profiler.TraceAnnotation`` spans,
  whose names start with ``bench.``; ``bench.traced`` marks the traced
  window (bench/host.py, Trace).
"""

from __future__ import annotations

from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"
TOP = 10


def merge(intervals):
    """Sorted union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(intervals, lo, hi) -> int:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(e - s for s, e in merge(clip(intervals, lo, hi)))


def gaps(intervals, lo, hi):
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(gap, spans) -> str:
    """The innermost host span that holds the middle of ``gap``."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for name, s, e in spans:
        if s <= mid <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "no bench span"


def op_name(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.12 = bf16[...]
    fusion(...)`` becomes ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def top_ops(events, lo, hi, n=TOP):
    """The operations that took most device time inside [lo, hi]."""
    total = {}
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            total[name] = total.get(name, 0) + (e - s)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def reduce(devices: dict, spans: list, window=None) -> dict:
    """``devices``: device name -> [(op name, start_ns, end_ns)];
    ``spans``: [(name, start_ns, end_ns)] of host spans. The window is
    the ``bench.traced`` span unless given. Busy time is averaged over
    the devices; gaps and operations are those of the first device."""
    if window is None:
        marks = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
        if not marks:
            raise ValueError("the trace holds no bench.traced span")
        window = marks[0]
    lo, hi = window
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy = [busy_ns([(s, e) for _, s, e in ev], lo, hi)
            for ev in devices.values()]
    first = devices[sorted(devices)[0]]
    idle = gaps([(s, e) for _, s, e in first], lo, hi)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:TOP]
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": top_ops(first, lo, hi),
        "idle_gaps": [[name_gap(g, spans), (g[1] - g[0]) / 1e9]
                      for g in longest],
    }


def load(log_dir) -> tuple:
    """Read the newest ``.xplane.pb`` under ``log_dir``: returns
    (devices, spans) as ``reduce`` takes them."""
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (op_name(ev.name), int(ev.start_ns), int(ev.end_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.end_ns)))
    return devices, spans
