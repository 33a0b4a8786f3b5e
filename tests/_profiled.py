"""Runs a callable under ``jax.profiler`` on the CPU and returns the host
spans it opened, as the profiler recorded them: the same reduction the
benchmark applies to a traced window (``bench/common/trace.py``)."""
import glob
import os
from typing import Any, Callable, NamedTuple

import jax


class HostSpan(NamedTuple):
    name: str
    start_ns: float
    duration_ns: float
    stats: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.duration_ns

    def holds(self, other: "HostSpan") -> bool:
        return self.start_ns <= other.start_ns and other.end_ns <= self.end_ns


def profiled(fn: Callable[[], Any], log_dir,
             prefixes: tuple[str, ...] = ("engine.", "executor.", "test.")
             ) -> tuple[Any, list[HostSpan]]:
    """``fn()`` under a host-only trace (tracer level 1, as the benchmark
    takes it); its result and the host spans whose names start with one
    of ``prefixes``, by start time."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    spans = [HostSpan(ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(prefixes)]
    return out, sorted(spans, key=lambda s: s.start_ns)
