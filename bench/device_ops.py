"""The device's operations in a traced window, by name, from the
profiler's trace.

``bench/trace.py`` reduces a trace to the busiest operations; a reader
that needs every event of a kernel (``gmm_roofline.moe8k``: each
``%gmm``/``%tgmm`` call) takes them from here. On a TPU the ``XLA Ops``
events carry only their timing as stats (``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier``; PERF.md section 3), so
an operation is known by its name alone. The program's named scopes are
in the compiled module's ``op_name`` metadata: a run that writes the
module's text beside its trace (``STEP_HLO``) lets a reader join the
two by instruction name (``op_scopes``).
"""

from __future__ import annotations

import re
from pathlib import Path

from bench.trace import WINDOW_SPAN, op_name

STEP_HLO = "train_step.hlo.txt"  # the compiled step's text, in trace_dir
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*\bmetadata=\{[^}]*'
    r'\bop_name="([^"]*)"')


def op_scopes(trace_dir) -> dict:
    """{instruction name without its ``%``: its ``op_name``} of the
    compiled module whose text the run wrote to ``trace_dir/STEP_HLO``;
    {} where there is none. The device trace names its operations by
    these instruction names."""
    path = None if trace_dir is None else Path(trace_dir) / STEP_HLO
    if path is None or not path.is_file():
        return {}
    out = {}
    for line in path.read_text().splitlines():
        hit = _INSTRUCTION.match(line)
        if hit:
            out[hit.group(1)] = hit.group(2)
    return out


def window_ops(trace_dir) -> list:
    """[(op name, seconds)] of the first TPU device's
    operations inside the ``bench.traced`` window of the newest trace
    under ``trace_dir``; [] where there is no trace or no device."""
    from jax.profiler import ProfileData

    if trace_dir is None:
        return []
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return []
    data = ProfileData.from_file(str(files[-1]))
    window, devices = None, {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = line.events
        elif plane.name.startswith("/host:") and window is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
    if window is None or not devices:
        return []
    lo, hi = window
    out = []
    for ev in devices[sorted(devices)[0]]:
        s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
        if e > s:
            out.append((op_name(ev.name), (e - s) / 1e9))
    return out

