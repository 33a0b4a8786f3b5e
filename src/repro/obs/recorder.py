"""Spans and the lock-cheap flight recorder they can write into.

:func:`span` is how program code opens a span.  It always enters a
``jax.profiler.TraceAnnotation``, so the span lands in a profiler trace,
on the host clock the device's programs are shown against, whenever one
is being taken; with a :class:`SpanRecorder` it also appends the span's
begin/end pair to that recorder's ring.

The ring's hot path takes no lock: every :meth:`~SpanRecorder.begin` /
:meth:`~SpanRecorder.end` / :meth:`~SpanRecorder.event` call appends
one small dict to a bounded ``collections.deque`` — atomic under
CPython — and span ids come from ``itertools.count`` (also atomic).
When the ring fills, the oldest entries fall off: the recorder is a
flight recorder, keeping the most recent window of activity so a
fault dump shows what led up to the crash, not the start of the run.

Entry shape (Chrome-trace phases, so export is a straight rendering):

* ``{"ph": "B", "span": id, "name": ..., "ts": ..., <attrs>}`` —
  span begin.  Attribution attrs (``bin``, ``lane``, ``node``,
  ``stage``, ``worker``, ...) are stored only when non-``None``.
* ``{"ph": "E", "span": id, "ts": ..., <attrs>}`` — span end, with the
  stats a span sets on the way out (:meth:`_RecordedSpan.set_metadata`).
* ``{"ph": "i", "name": ..., "ts": ..., <attrs>}`` — instant event.

Timestamps are ``time.perf_counter()`` seconds (same clock as
:class:`~repro.sched.TaskProfiler`); the timeline exporter rebases
them to zero and converts to microseconds.
"""
from __future__ import annotations

import itertools
import time
from collections import deque
from typing import Any

from jax.profiler import TraceAnnotation

DEFAULT_CAPACITY = 65536


class SpanRecorder:
    """Bounded ring of spans + instant events; dumps on fault.

    ``capacity`` bounds the ring (oldest entries evicted first).
    ``dump_path``, when set, is where :meth:`on_fault` writes a
    Perfetto-loadable Chrome-trace JSON of the ring's contents.
    """

    clock = staticmethod(time.perf_counter)

    def __init__(self, capacity: int = DEFAULT_CAPACITY, *,
                 dump_path: str | None = None) -> None:
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = capacity
        self.dump_path = dump_path
        self._ring: deque[dict[str, Any]] = deque(maxlen=capacity)
        self._ids = itertools.count(1)

    # -- recording (lock-free) -----------------------------------------
    def begin(self, name: str, *, bin: Any = None, lane: str | None = None,
              node: Any = None, stage: Any = None, **attrs: Any) -> int:
        """Open a span; returns the span id to pass to :meth:`end`."""
        sid = next(self._ids)
        e: dict[str, Any] = {"ph": "B", "span": sid, "name": name,
                             "ts": self.clock()}
        _put(e, bin=bin, lane=lane, node=node, stage=stage, **attrs)
        self._ring.append(e)
        return sid

    def end(self, span: int, **attrs: Any) -> None:
        e: dict[str, Any] = {"ph": "E", "span": span, "ts": self.clock()}
        _put(e, **attrs)
        self._ring.append(e)

    def event(self, name: str, *, bin: Any = None, lane: str | None = None,
              node: Any = None, span: int | None = None,
              **attrs: Any) -> None:
        """Record an instant event (spill, steal, preemption, ...)."""
        e: dict[str, Any] = {"ph": "i", "name": name, "ts": self.clock()}
        _put(e, bin=bin, lane=lane, node=node, span=span, **attrs)
        self._ring.append(e)

    # -- inspection / draining -----------------------------------------
    def __len__(self) -> int:
        return len(self._ring)

    def entries(self) -> list[dict[str, Any]]:
        """Snapshot of the ring, oldest first."""
        return list(self._ring)

    def events(self, name: str | None = None) -> list[dict[str, Any]]:
        """Instant events only, optionally filtered by name."""
        return [e for e in self._ring
                if e["ph"] == "i" and (name is None or e["name"] == name)]

    def spans(self) -> list[dict[str, Any]]:
        """Completed spans, paired from B/E entries still in the ring.

        Each returned dict is the begin entry, the end entry's attrs
        and ``end_ts``; spans whose begin fell off the ring, or that are
        still open, are dropped (the flight recorder keeps a window, not
        the world).
        """
        open_: dict[int, dict[str, Any]] = {}
        done: list[dict[str, Any]] = []
        for e in list(self._ring):
            if e["ph"] == "B":
                open_[e["span"]] = e
            elif e["ph"] == "E":
                b = open_.pop(e["span"], None)
                if b is not None:
                    done.append({**b, **{k: v for k, v in e.items()
                                         if k not in ("ph", "span", "ts")},
                                 "end_ts": e["ts"]})
        return done

    def clear(self) -> None:
        self._ring.clear()

    # -- fault handling ------------------------------------------------
    def dump(self, path: str | None = None) -> str | None:
        """Write the ring as Chrome-trace JSON; returns the path."""
        path = path or self.dump_path
        if path is None:
            return None
        from .timeline import save_timeline, timeline_from_recorder
        save_timeline(timeline_from_recorder(self), path)
        return path

    def on_fault(self, reason: Any = None, **attrs: Any) -> str | None:
        """Record a ``fault`` instant and dump the ring to ``dump_path``.

        Called by the executor when a topology fails; safe to call with
        no ``dump_path`` (records the event, skips the dump).  Dump
        errors are swallowed — the flight recorder must never turn a
        task fault into a crash.
        """
        self.event("fault", reason=None if reason is None else str(reason),
                   **attrs)
        try:
            return self.dump()
        except OSError:
            return None


def _put(e: dict[str, Any], **attrs: Any) -> None:
    for k, v in attrs.items():
        if v is not None:
            e[k] = v


def span(name: str, recorder: SpanRecorder | None = None, **stats: Any):
    """Context manager for one span named ``name`` with ``stats``.

    The span is always a ``jax.profiler.TraceAnnotation``: free of any
    recorder, it shows up under ``name`` with ``stats`` as its event
    stats in a profiler trace whenever one is being taken (about a
    microsecond when none is).  With ``recorder`` its begin/end pair
    also goes into that ring.  Stats known only at the end go through
    ``set_metadata(**stats)`` on the entered object.  Values are
    strings, numbers or booleans (the profiler writes ``None`` as the
    string ``"None"``).
    """
    ann = TraceAnnotation(name, **stats)
    return ann if recorder is None else _RecordedSpan(ann, recorder, name,
                                                      stats)


class _RecordedSpan:
    """A profiler annotation that also writes its begin/end pair into a
    :class:`SpanRecorder`'s ring."""

    __slots__ = ("_ann", "_rec", "_name", "_stats", "_end", "_sid")

    def __init__(self, ann: TraceAnnotation, rec: SpanRecorder, name: str,
                 stats: dict[str, Any]) -> None:
        self._ann, self._rec, self._name, self._stats = ann, rec, name, stats
        self._end: dict[str, Any] = {}

    def __enter__(self) -> "_RecordedSpan":
        self._ann.__enter__()
        self._sid = self._rec.begin(self._name, **self._stats)
        return self

    def set_metadata(self, **stats: Any) -> None:
        self._ann.set_metadata(**stats)
        self._end.update(stats)

    def __exit__(self, *exc: Any) -> None:
        self._rec.end(self._sid, **self._end)
        self._ann.__exit__(*exc)
