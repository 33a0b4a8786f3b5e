"""The device's idle time split by what the program's host was doing.

The engine and the executor open ``repro.obs.span``s (``engine.tick``,
``engine.schedule``, ``engine.prefill``, ``engine.decode``,
``engine.read``, ``executor.task``).  They land in the profiler trace on
the host clock, onto which :class:`~bench.common.trace.Trace` moves the
device's times.  :func:`~bench.common.trace.load` keeps only the
benchmark's own ``bench.`` spans, so :func:`load_program` reads the
program's from the same ``.xplane.pb``, and :func:`attach` puts them
where the functions here read them (``bench/tools/idle_split.py`` does
both on a traced run of a cell).  :func:`idle_split` charges every idle instant of the window to
the innermost program span open then (the shortest one that holds it),
averaged over the cell's chips as ``Trace.busy_s`` is, so its buckets sum
to ``Trace.idle_pct()``.
"""
from __future__ import annotations

import bisect
import glob
import os
import statistics

#: host spans the program opens (``repro.obs.span`` names)
PROGRAM_PREFIXES = ("engine.", "executor.")

#: the buckets of :func:`idle_split`, and the span each one reads
BUCKETS = ("schedule", "dispatch", "read", "tick", "task", "none")
BUCKET_OF = {
    "engine.schedule": "schedule",     # placement: Scheduler.update and its graph
    "engine.prefill": "dispatch",      # upload, init_cache and the jitted prefill
    "engine.decode": "dispatch",       # upload and the jitted decode step
    "engine.read": "read",             # int(jnp.argmax(...)): waits for the device
    "engine.tick": "tick",             # the rest of the engine's tick
    "executor.task": "task",           # the executor task around it (the benchmark's tick code)
}
#: the decode program, as the benchmark's readers find it
DECODE_PROGRAM = r"\bjit_decode_step\b|\bdecode_step\("


def load_program(trace_dir: str) -> list:
    """The program's spans in the ``.xplane.pb`` that
    :func:`~bench.common.trace.load` reads from ``trace_dir`` (the
    newest, outside the warm-up's), as ``[name, start_ns, duration_ns,
    stats]`` on the host clock."""
    from jax.profiler import ProfileData

    paths = sorted((p for p in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                         recursive=True)
                    if os.sep + "warm" + os.sep not in p), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return [[ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats)]
            for plane in ProfileData.from_file(paths[-1]).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PROGRAM_PREFIXES)]


def attach(trace, program: list):
    """``trace`` with the program's spans put where the functions here
    read them."""
    trace.raw["program"] = program
    return trace


def program_spans(trace, name: str) -> list:
    """The program's ``name`` spans that lie inside the window, as
    ``[name, start_ns, duration_ns, stats]``."""
    return [p for p in trace.raw.get("program", []) if p[0] == name
            and p[1] >= trace.lo and p[1] + p[2] <= trace.hi]


def _labelled(trace) -> list[tuple[float, float, str]]:
    """The window cut into segments, each with the bucket of the
    innermost program span open over it (``none`` outside every one)."""
    lo, hi = trace.lo, trace.hi
    edges = []
    for name, s, d, _ in trace.raw.get("program", []):
        b = BUCKET_OF.get(name)
        if b is None or d <= 0 or s >= hi or s + d <= lo:
            continue
        edges.append((max(s, lo), 1, d, b))
        edges.append((min(s + d, hi), 0, d, b))
    edges.sort(key=lambda e: (e[0], e[1]))        # ends before starts
    out, open_, t = [], [], lo
    for at, starts, d, b in edges:
        if at > t:
            out.append((t, at, min(open_)[1] if open_ else "none"))
            t = at
        if starts:
            open_.append((d, b))
        else:
            open_.remove((d, b))
    if hi > t:
        out.append((t, hi, "none"))
    return out


def _idle(busy: list, lo: float, hi: float) -> list[tuple[float, float]]:
    """The gaps between merged busy intervals (already inside [lo, hi])."""
    gaps, t = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    return gaps


def idle_split(trace) -> dict[str, float] | None:
    """Percent of the window in which the device is idle, per bucket of
    :data:`BUCKETS`, averaged over the cell's chips.  None where the
    window holds no ``engine.tick`` span (a program that opens none), or
    where ``Trace.idle_pct`` is silent (no operation ran)."""
    if trace is None or trace.idle_pct() is None or not any(
            p[0] == "engine.tick" and p[1] < trace.hi and p[1] + p[2] > trace.lo
            for p in trace.raw.get("program", [])):
        return None
    segs = _labelled(trace)
    starts = [s for s, _, _ in segs]
    ns = dict.fromkeys(BUCKETS, 0.0)
    for dev in trace.devices:
        for a, b in _idle(trace._busy[dev], trace.lo, trace.hi):
            i = max(0, bisect.bisect_right(starts, a) - 1)
            while i < len(segs) and segs[i][0] < b:
                s, e, bucket = segs[i]
                ns[bucket] += max(0.0, min(e, b) - max(s, a))
                i += 1
    scale = 100.0 / (len(trace.devices) * (trace.hi - trace.lo))
    return {k: v * scale for k, v in ns.items()}


def decode_clock_check(trace) -> tuple[int, int]:
    """How many of the window's decode executions, on the shifted device
    clock, end before the end of the ``engine.read`` that follows their
    ``engine.decode``, out of how many could be matched.  An execution
    belongs to the last ``engine.decode`` that started before its
    middle; the read waits for its result, so on one clock it ends
    first."""
    decodes = sorted(program_spans(trace, "engine.decode"), key=lambda p: p[1])
    reads = sorted(program_spans(trace, "engine.read"), key=lambda p: p[1])
    d_starts, r_starts = [p[1] for p in decodes], [p[1] for p in reads]
    ok = n = 0
    for _, s, d in trace.modules(DECODE_PROGRAM):
        i = bisect.bisect_right(d_starts, s + d / 2) - 1
        if i < 0:
            continue
        j = bisect.bisect_left(r_starts, decodes[i][1] + decodes[i][2])
        if j == len(reads):
            continue
        n += 1
        ok += s + d <= reads[j][1] + reads[j][2]
    return ok, n


def note(trace, split: dict[str, float]) -> str:
    """The whole split, the batch occupancy, placement's cost and the
    clock check, for the log."""
    ticks = program_spans(trace, "engine.tick")
    sched = program_spans(trace, "engine.schedule")
    ok, n = decode_clock_check(trace)
    parts = [", ".join(f"{k} {split[k]!r}%" for k in BUCKETS),
             f"sum {sum(split.values())!r}% (device idle {trace.idle_pct()!r}%)"]
    if ticks:
        active = [p[3].get("active", 0) for p in ticks]
        parts.append(f"{statistics.fmean(active)!r} slots active at the end "
                     f"of a tick over {len(ticks)} ticks")
    if sched:
        parts.append(f"engine.schedule {statistics.fmean(p[2] for p in sched) / 1e6!r} "
                     f"ms mean over {len(sched)} spans, "
                     f"{statistics.fmean(p[3].get('nodes', 0) for p in sched)!r} nodes mean")
    parts.append(f"decode executions ending before their read: {ok} of {n}")
    return "; ".join(parts)
