"""Small helpers of the runners: spans, device memory, the traced window."""

from __future__ import annotations

import gc

import jax

# what the profiler records while a traced run's window is open: the
# benchmark's own spans and the device's operations, no Python tracing
PROFILE_OPTIONS = dict(host_tracer_level=1, python_tracer_level=0)


def span(name: str):
    """A host span in the profiler's trace (free when nothing traces)."""
    return jax.profiler.TraceAnnotation(name)


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def bytes_in_use() -> int:
    """Bytes in use now on the fullest local device."""
    return int(max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in jax.local_devices()))


def free(*holders):
    """Drop the program's objects and what they hold on the device."""
    for h in holders:
        h.clear()
    gc.collect()


class Trace:
    """Profiles what runs inside it into ``log_dir``, and marks that
    stretch with the ``bench.traced`` span: the traced window."""

    def __init__(self, log_dir, on: bool):
        self.log_dir, self.on = str(log_dir), on
        self._span = None

    def __enter__(self):
        if self.on:
            opts = jax.profiler.ProfileOptions()
            for k, v in PROFILE_OPTIONS.items():
                setattr(opts, k, v)
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._span = span("bench.traced")
            self._span.__enter__()
        return self

    def __exit__(self, *exc):
        if self.on:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return False
