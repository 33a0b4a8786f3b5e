"""repro.obs — unified observability: spans, metrics, timelines.

Four cooperating pieces, each usable alone:

* :func:`span` — the one way program code opens a span: a
  ``jax.profiler.TraceAnnotation`` (so the engine's and the executor's
  spans land in a profiler trace, on one clock with the device's
  programs), also written into a :class:`SpanRecorder` when one is
  given.
* :class:`SpanRecorder` — a lock-cheap structured span/event recorder.
  Begin/end spans carry bin/lane/node/stage attribution; instant
  events mark spills, refills, steals, preemptions, straggler
  demotions, bin join/retire/fail, and chaos triggers.  Entries land
  in a bounded flight-recorder ring buffer that can :meth:`dump
  <SpanRecorder.dump>` a Perfetto-loadable trace on fault.
* :class:`MetricsRegistry` — named counters, gauges, and histograms
  (nearest-rank p50/p99).  The executor, serving engine, and
  simulator publish into one; their ``stats()`` dicts are back-compat
  views over it.
* the timeline exporters — :func:`timeline_from_trace` (a live
  :class:`~repro.sched.TaskProfiler` run), :func:`timeline_from_schedule`
  (a simulated :class:`~repro.sched.SimReport`), and
  :func:`timeline_from_recorder` (a flight-recorder ring) all render
  per-bin copy∥compute lane timelines as Chrome-trace JSON, openable
  at https://ui.perfetto.dev.  :func:`diff_timelines` aligns a
  measured run against its replayed simulation and quantifies
  per-bin/per-lane divergence.

Spans are always on: with no profiler running, one costs about a
microsecond (docs/observability.md gives the measured costs).
Components that accept an ``obs=`` recorder skip the ring and its
instant events when it is ``None``.  See docs/observability.md for the
span vocabulary, the span model and the workflow.
"""
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .recorder import SpanRecorder, span
from .timeline import (
    diff_timelines,
    merge_timelines,
    save_timeline,
    timeline_from_recorder,
    timeline_from_schedule,
    timeline_from_trace,
    validate_timeline,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "SpanRecorder",
    "diff_timelines",
    "merge_timelines",
    "save_timeline",
    "span",
    "timeline_from_recorder",
    "timeline_from_schedule",
    "timeline_from_trace",
    "validate_timeline",
]
