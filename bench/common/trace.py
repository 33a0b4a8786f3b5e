"""Reduction of a profiler trace to device busy time, program time and
idle gaps, on one clock with the benchmark's own host spans.

A traced run records its window with ``jax.profiler`` and wraps the work
in ``jax.profiler.TraceAnnotation`` spans whose names start with
``bench.``.  :func:`load` keeps from the ``.xplane.pb`` only what the
reduction needs (the device planes' operations and programs, and those
host spans); :class:`Trace` does the arithmetic on plain lists, so it is
tested on a small recorded trace without a chip.
"""
from __future__ import annotations

import glob
import os
import re
import statistics
import time
from typing import Iterable

#: host spans the benchmark writes (TraceAnnotation names)
HOST_PREFIX = "bench."
#: zero-length spans that open and close the traced window (written by
#: whichever thread is at the boundary, so not one span across threads)
WINDOW_BEGIN = "bench.window.begin"
WINDOW_END = "bench.window.end"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_LINES = {"XLA Ops": "ops", "XLA Modules": "modules"}

#: host spans around a tiny program run on each device at the window's
#: edges: they put each device's clock onto the host's
SYNC = "bench.sync."
SYNC_PROGRAM = "bench_sync"


class Recorder:
    """Starts and stops the profiler around a traced window.

    The host tracer keeps only annotations (level 1), no Python tracer
    and no HLO protos: the trace holds device operations and the
    benchmark's spans, and stopping it takes seconds, not minutes."""

    def __init__(self, trace_dir: str, devices) -> None:
        import jax
        import jax.numpy as jnp

        self.trace_dir = os.path.join(str(trace_dir), "warm")
        self.devices = list(devices)

        def bench_sync(x):
            return x + 1

        self._sync_fn = jax.jit(bench_sync)
        self._probes = [jax.device_put(jnp.zeros((8, 128), jnp.float32), d)
                        for d in self.devices]
        self.seconds: dict[str, float] = {}
        # a first trace, thrown away: the profiler's own start-up and the
        # sync program's compile happen here, in set-up, not in a window
        self.start("warm_start")
        self.stop("warm_stop")
        self.trace_dir = str(trace_dir)

    def _sync(self) -> None:
        import jax

        # under the device's own default, as inside an executor kernel,
        # so the program compiled in set-up is the one the window runs
        for d, x in zip(self.devices, self._probes):
            with jax.default_device(d), \
                    jax.profiler.TraceAnnotation(f"{SYNC}{d.id}"):
                self._sync_fn(x).block_until_ready()

    def start(self, name: str = "start") -> None:
        import jax

        t = time.perf_counter()
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._sync()
        mark(WINDOW_BEGIN)
        self.seconds[name] = time.perf_counter() - t

    def stop(self, name: str = "stop") -> None:
        import jax

        t = time.perf_counter()
        mark(WINDOW_END)
        self._sync()
        jax.profiler.stop_trace()
        self.seconds[name] = time.perf_counter() - t


def mark(name: str) -> None:
    import jax

    with jax.profiler.TraceAnnotation(name):
        pass


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced to
    ``{"devices": {id: {"ops": [...], "modules": [...]}}, "host": [...]}``
    with events as ``[name, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData

    paths = sorted((p for p in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                         recursive=True)
                    if os.sep + "warm" + os.sep not in p), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: dict[str, dict[str, list]] = {}
    host: list = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(m.group(1), {"ops": [], "modules": []})
            for line in plane.lines:
                key = _LINES.get(line.name)
                if key:
                    dev[key].extend([ev.name, ev.start_ns, ev.duration_ns]
                                    for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, ev.start_ns, ev.duration_ns]
                            for ev in line.events
                            if ev.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def union(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    """Merged, sorted [start, end] intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(merged: list[list[float]], lo: float, hi: float) -> float:
    """Length of ``merged`` (from :func:`union`) inside [lo, hi]."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged
               if e > lo and s < hi)


def clock_offsets(raw: dict) -> dict[str, float]:
    """Nanoseconds to add to each device's times to put them on the host
    clock.  A sync program ends just before its host span (which waits
    for it) ends, while the span's start is early by the whole dispatch;
    so the ends are matched: the device's times come out late by the time
    the host takes to see a program finish, tens of microseconds.  Median
    over the pairs; 0 for a device with none."""
    out = {}
    for dev, evs in raw["devices"].items():
        hosts = sorted((h for h in raw["host"] if h[0] == f"{SYNC}{dev}"),
                       key=lambda h: h[1])
        progs = sorted((m for m in evs["modules"] if SYNC_PROGRAM in m[0]),
                       key=lambda m: m[1])
        ends = [(hs + hd) - (ms + md)
                for (_, hs, hd), (_, ms, md) in zip(hosts, progs)]
        out[dev] = statistics.median(ends) if ends and len(hosts) == len(progs) else 0.0
    return out


def _shifted(raw: dict) -> dict:
    offs = clock_offsets(raw)
    return {"host": raw["host"], "offsets": offs, "devices": {
        dev: {k: [[n, s + offs[dev], d] for n, s, d in v] for k, v in evs.items()}
        for dev, evs in raw["devices"].items()}}


class Trace:
    """Arithmetic over one reduced trace, restricted to its window, with
    every device's times moved onto the host clock."""

    def __init__(self, raw: dict, devices: list[str] | None = None):
        """``devices``: the ids of the chips the cell uses; a chip of
        theirs that ran nothing counts as idle (default: every device
        plane in the trace)."""
        raw = _shifted(raw)
        self.raw = raw
        self.offsets = raw["offsets"]
        begin = [h[1] for h in raw["host"] if h[0] == WINDOW_BEGIN]
        end = [h[1] for h in raw["host"] if h[0] == WINDOW_END]
        if not begin or not end:
            raise ValueError("trace has no window markers")
        self.lo, self.hi = float(min(begin)), float(max(end))
        self.devices = sorted(devices or raw["devices"], key=int)
        for dev in self.devices:
            raw["devices"].setdefault(dev, {"ops": [], "modules": []})
        self._busy = {dev: union(self._intervals(dev)) for dev in self.devices}

    # -- device time -----------------------------------------------------
    def _events(self, dev: str, kind: str) -> list:
        return [e for e in self.raw["devices"][dev][kind]
                if e[1] + e[2] > self.lo and e[1] < self.hi]

    def _intervals(self, dev: str):
        evs = self._events(dev, "ops") or self._events(dev, "modules")
        return [(max(s, self.lo), min(s + d, self.hi)) for _, s, d in evs]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the cell's
        devices."""
        if not self.devices:
            return 0.0
        return sum(overlap(self._busy[d], self.lo, self.hi)
                   for d in self.devices) / len(self.devices) / 1e9

    def idle_pct(self) -> float | None:
        if not self.window_s or not self.busy_s():
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def busy_in(self, lo: float, hi: float, dev: str | None = None) -> float:
        """Device-busy nanoseconds inside [lo, hi] (first used device)."""
        dev = dev or self._first_used()
        return overlap(self._busy[dev], lo, hi) if dev else 0.0

    def _first_used(self) -> str | None:
        return next((d for d in self.devices if self._busy[d]), None)

    def modules(self, pattern: str) -> list:
        """Program executions in the window whose name matches
        ``pattern`` (a regular expression), on every device."""
        rx = re.compile(pattern)
        return [e for dev in self.devices for e in self._events(dev, "modules")
                if rx.search(e[0])]

    # -- host spans ------------------------------------------------------
    def host(self, name: str) -> list:
        return [h for h in self.raw["host"] if h[0] == name
                and h[1] >= self.lo and h[1] + h[2] <= self.hi]

    def host_ms_per_span(self, name: str) -> float | None:
        """Mean of each ``name`` span less the device-busy time inside
        it, in milliseconds: the time the host keeps the device waiting
        within that span.  None where no such span lies in the window."""
        spans = self.host(name)
        if not spans:
            return None
        host_ns = [d - self.busy_in(s, s + d) for _, s, d in spans]
        return sum(host_ns) / len(host_ns) / 1e6

    def _host_label(self, t: float) -> str:
        inside = [h for h in self.raw["host"] if h[2] > 0
                  and h[1] <= t <= h[1] + h[2]]
        return min(inside, key=lambda h: h[2])[0] if inside else "bench.other"

    # -- breakdown -------------------------------------------------------
    def idle_gaps(self, top: int = 10) -> list:
        """The longest idle spells of the first used device, each named by
        the innermost benchmark span open at its middle."""
        dev = self._first_used()
        if dev is None:
            return []
        gaps, t = [], self.lo
        for s, e in self._busy[dev] + [[self.hi, self.hi]]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._host_label((a + b) / 2), (b - a) / 1e9]
                for a, b in gaps[:top]]

    def top_ops(self, top: int = 10) -> list:
        """Device operations by total time in the window, over all
        devices, each named ``program/operation``.  An operation that
        holds others (a loop) is left out: its time is theirs."""
        tot: dict[str, float] = {}
        for dev in self.devices:
            mods = sorted(self._events(dev, "modules"), key=lambda e: e[1])
            starts = [m[1] for m in mods]
            ops = sorted(self._events(dev, "ops"), key=lambda e: (e[1], -e[2]))
            for i, (name, s, d) in enumerate(ops):
                if i + 1 < len(ops) and ops[i + 1][1] < s + d:
                    continue                  # a parent of the next one
                prog = _containing(mods, starts, s)
                key = f"{_short(prog)}/{_short(name)}" if prog else _short(name)
                inside = min(s + d, self.hi) - max(s, self.lo)
                tot[key] = tot.get(key, 0.0) + inside / 1e9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:top]


def _short(name: str) -> str:
    """``%fusion.38 = bf16[...] fusion(...)`` -> ``%fusion.38``;
    ``jit_step(8911...)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", name.split(" = ")[0])


def _containing(mods: list, starts: list, t: float) -> str | None:
    import bisect
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i][1] + mods[i][2] >= t:
        return mods[i][0]
    return None
