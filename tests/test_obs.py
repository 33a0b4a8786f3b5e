"""repro.obs (PR 9): flight recorder, metrics registry, Chrome-trace
timeline export, and the measured-vs-simulated diff loop.

Covers the span/event recorder (bounded ring, fault dump), the
get-or-create metrics registry (nearest-rank percentile parity with
``repro.sched.online``), the three timeline exporters against a
checked-in golden JSON + the Chrome-trace schema, ``diff_timelines``
on a replayed trace, and the obs-disabled parity guards (no recorder,
no perturbation — the runtime knobs must be invisible when off).
"""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))

from workloads import build_fanout  # noqa: E402
from _profiled import profiled  # noqa: E402

from repro.core import Executor, Heteroflow  # noqa: E402
from repro.obs import (  # noqa: E402
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    SpanRecorder,
    diff_timelines,
    merge_timelines,
    save_timeline,
    timeline_from_recorder,
    timeline_from_schedule,
    span,
    timeline_from_trace,
    validate_timeline,
)
from repro.sched import (  # noqa: E402
    ChaosPlan,
    CostModel,
    DeviceBin,
    TaskProfiler,
    get_scheduler,
    simulate,
)
from repro.sched.chaos import ChaosEvent  # noqa: E402
from repro.sched.online import percentile  # noqa: E402

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "obs_timeline_golden.json")

#: unit-rate, transfer-free model with kernel-declared costs (the
#: golden setup test_sched.py uses) — simulate() is then deterministic
MODEL = CostModel(compute_rate=1.0, h2d_bandwidth=float("inf"),
                  d2d_bandwidth=float("inf"), latency_s=0.0,
                  host_time_s=0.0,
                  cost_fn=lambda n: float(n.state.get("cost", 0.0)))


def _chain_fanout():
    """Small deterministic chain → fanout graph with declared costs."""
    G = Heteroflow("golden")
    prev = None
    for i in range(2):                         # chain segment
        p = G.pull(np.zeros(64), name=f"cp{i}")
        k = G.kernel(lambda a: a, p, cost=float(i + 1), name=f"ck{i}")
        k.succeed(p)
        if prev is not None:
            k.succeed(prev)
        prev = k
    for i in range(3):                         # fanout off the chain tail
        p = G.pull(np.zeros(64), name=f"fp{i}")
        k = G.kernel(lambda a: a, p, cost=2.0 + i, name=f"fk{i}")
        k.succeed(p, prev)
    return G


# ----------------------------------------------------------------------
# SpanRecorder: bounded ring, span pairing, fault dump
# ----------------------------------------------------------------------
def test_recorder_ring_is_bounded_and_keeps_newest():
    rec = SpanRecorder(capacity=8)
    for i in range(20):
        rec.event(f"e{i}")
    assert len(rec) == 8
    names = [e["name"] for e in rec.entries()]
    assert names == [f"e{i}" for i in range(12, 20)]   # oldest fell off
    with pytest.raises(ValueError, match="capacity"):
        SpanRecorder(capacity=0)


def test_recorder_spans_pair_and_open_spans_drop():
    rec = SpanRecorder()
    sid = rec.begin("work", bin="d0", lane="compute", node=3, stage=1,
                    worker=0)
    rec.end(sid, ok=True)
    rec.begin("never_closed", bin="d1")
    with span("ctx", rec, bin="d0", lane="copy"):
        pass
    spans = rec.spans()
    assert [s["name"] for s in spans] == ["work", "ctx"]
    first = spans[0]
    assert (first["bin"], first["lane"], first["node"]) == ("d0",
                                                           "compute", 3)
    assert first["end_ts"] >= first["ts"]
    # attribution attrs are stored only when non-None
    assert "stage" not in rec.entries()[2]              # never_closed
    assert rec.events() == []                           # no instants yet
    rec.event("steal", bin="d0", node=7, thief=1)
    assert rec.events("steal")[0]["thief"] == 1
    rec.clear()
    assert len(rec) == 0


def test_recorder_fault_dump_writes_valid_timeline(tmp_path):
    path = str(tmp_path / "flight.json")
    rec = SpanRecorder(dump_path=path)
    with span("doomed", rec, bin="d0", lane="compute"):
        pass
    out = rec.on_fault(RuntimeError("boom"), topology=1)
    assert out == path
    tl = json.load(open(path))
    assert validate_timeline(tl) == []
    faults = [e for e in tl["traceEvents"]
              if e.get("ph") == "i" and e["name"] == "fault"]
    assert faults and faults[0]["args"]["reason"] == "boom"
    # no dump_path → event recorded, dump skipped, no crash
    rec2 = SpanRecorder()
    assert rec2.on_fault("x") is None
    assert rec2.events("fault")


# ----------------------------------------------------------------------
# MetricsRegistry: instruments, percentile parity, snapshot
# ----------------------------------------------------------------------
def test_counter_gauge_histogram_basics():
    c = Counter("n")
    c.inc()
    c.inc(3)
    assert c.value == 4
    assert isinstance(c.value, int)              # int in, int out
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1)
    g = Gauge("g")
    g.set(2.5)
    assert g.value == 2.5
    h = Histogram("h")
    assert h.percentile(50) == 0.0               # empty → 0.0, no raise
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    h.extend(xs[:3])
    for v in xs[3:]:
        h.observe(v)
    # nearest-rank parity with the repro.sched.online rule — the
    # registry-backed stats() percentiles must be bit-identical
    for p in (50, 90, 99):
        assert h.percentile(p) == percentile(xs, p)
    assert h.summary() == {"count": 6, "sum": sum(xs),
                           "p50": percentile(xs, 50),
                           "p99": percentile(xs, 99)}


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    reg.gauge("y").set(1)
    reg.histogram("z").observe(2.0)
    with pytest.raises(TypeError, match="already registered"):
        reg.histogram("x")
    assert reg.names() == ["x", "y", "z"]
    assert "x" in reg and "nope" not in reg
    snap = reg.snapshot()
    assert snap["x"] == 0 and snap["y"] == 1
    assert snap["z"]["count"] == 1


# ----------------------------------------------------------------------
# timeline export: golden file, schema, merge
# ----------------------------------------------------------------------
def test_simulated_timeline_matches_golden(tmp_path):
    """Byte-exact golden: the simulator is deterministic and
    save_timeline sorts keys, so the export must reproduce the
    checked-in file.  Refresh after a reviewed format change with:

        PYTHONPATH=src:benchmarks python -c "
        import tests.test_obs as t; t._write_golden()"
    """
    tl = _golden_timeline()
    assert validate_timeline(tl) == []
    out = tmp_path / "golden.json"
    save_timeline(tl, str(out))
    assert out.read_bytes() == open(GOLDEN, "rb").read()


def _golden_timeline():
    G = _chain_fanout()
    bins = ["d0", "d1"]
    pl = get_scheduler("heft", cost_model=MODEL).schedule(G, bins)
    rep = simulate(G, pl, bins, cost_model=MODEL)
    tl = timeline_from_schedule(rep, bins, graph=G)
    # node ids are allocated globally (they depend on how many graphs
    # the process built before this one) — rebase to graph-local ids
    # so the export is byte-stable under any test execution order
    base = min(n.id for n in G.nodes)
    for e in tl["traceEvents"]:
        if "node" in e.get("args", {}):
            e["args"]["node"] -= base
    return tl


def _write_golden():
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    save_timeline(_golden_timeline(), GOLDEN)


def test_timeline_schema_requirements():
    tl = _golden_timeline()
    evs = tl["traceEvents"]
    procs = [e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert procs[:2] == ["d0", "d1"]             # stable pid order
    slices = [e for e in evs if e["ph"] == "X"]
    assert slices and all(
        {"name", "ts", "dur", "pid", "tid"} <= set(e) for e in slices)
    assert {e["args"].get("sim") for e in slices} == {True}
    # broken events are reported, not silently exported
    assert validate_timeline({"traceEvents": [{"ph": "X", "ts": 0}]}) \
        == ["event 0 (ph=X): missing pid",
            "event 0 (ph=X): missing tid",
            "event 0: X slice missing dur",
            "event 0 (ph=X): missing name"]
    assert validate_timeline({}) == ["traceEvents missing or not a list"]


def test_merge_timelines_keeps_process_groups_distinct():
    a, b = _golden_timeline(), _golden_timeline()
    merged = merge_timelines(a, b)
    assert validate_timeline(merged) == []
    n = max(e["pid"] for e in a["traceEvents"])
    pids_b = {e["pid"] for e in merged["traceEvents"][len(a["traceEvents"]):]}
    assert min(pids_b) > n                       # second group shifted


# ----------------------------------------------------------------------
# live run: trace export, recorder export, replay diff
# ----------------------------------------------------------------------
def _live_run(obs=None, profiler=None):
    import jax

    G = build_fanout(width=6)
    with Executor(num_workers=2, devices=[jax.devices()[0]] * 2,
                  profiler=profiler, obs=obs) as ex:
        ex.run(G).result(timeout=120)
    return G, ex


def test_live_trace_and_recorder_timelines_validate():
    prof, rec = TaskProfiler(), SpanRecorder()
    G, ex = _live_run(obs=rec, profiler=prof)
    for tl in (timeline_from_trace(prof), timeline_from_recorder(rec)):
        assert validate_timeline(tl) == []
        slices = [e for e in tl["traceEvents"] if e["ph"] == "X"]
        assert len(slices) >= len(G)             # every node rendered
        assert all(e["dur"] >= 0 for e in slices)
    # executor spans carry bin/lane/node/worker attribution
    spans = rec.spans()
    assert len(spans) == len(G)
    assert {s["lane"] for s in spans} <= {"copy", "compute", "host"}
    assert all("node" in s and "worker" in s for s in spans)


def test_diff_timelines_on_replayed_trace():
    prof = TaskProfiler()
    G, ex = _live_run(profiler=prof)
    trace = prof.trace()
    assert trace["version"] == 6
    labels = ex.device_labels
    pl = {n.id: n.bin_key for n in G.nodes if n.bin_key is not None}
    rep = simulate(G, pl, labels, cost_model=CostModel.fit(trace),
                   replay=trace)
    diff = diff_timelines(timeline_from_trace(trace),
                          timeline_from_schedule(rep, labels, graph=G))
    assert diff["makespan"]["measured_s"] > 0
    assert diff["makespan"]["simulated_s"] > 0
    assert diff["bins"] and diff["lanes"]
    assert {r["bin"] for r in diff["bins"]} >= set(labels)
    for row in diff["lanes"]:
        assert 0.0 <= row["divergence"] <= 1.0
    assert diff["max_divergence"] == max(r["divergence"]
                                         for r in diff["lanes"])


def test_diff_timelines_identical_is_zero():
    tl = _golden_timeline()
    diff = diff_timelines(tl, tl)
    assert diff["max_divergence"] == 0.0
    assert diff["makespan"]["divergence"] == 0.0


# ----------------------------------------------------------------------
# executor + chaos + simulator integration; disabled-obs parity
# ----------------------------------------------------------------------
def test_executor_publishes_metrics_registry():
    G, ex = _live_run()
    s = ex.stats()                # publishes worker tallies into gauges
    snap = ex.metrics.snapshot()
    assert snap["executed"] == len(G)
    assert {"steals", "spills", "refills", "replacements",
            "workers"} <= set(snap)
    assert type(s["spills"]) is int              # back-compat view
    assert s["executed"] == snap["executed"]


def test_executor_spill_events_carry_correlation_ids():
    """Satellite of the v6 trace bump: spill/refill records and obs
    events both name the spilled pull (``node``) and the task whose
    allocation forced the round trip (``span``/``trigger``)."""
    import jax

    budget = 16384                 # room for 2 of the 4 8 KiB pulls
    dev = DeviceBin(jax.devices()[0], memory_bytes=budget)
    G = Heteroflow("spill")
    for i in range(4):
        p = G.pull(np.full(8192, i, np.uint8), name=f"p{i}")
        k = G.kernel(lambda a: np.asarray(a).sum(dtype=np.int64), p,
                     name=f"k{i}")
        k.succeed(p)
    prof, rec = TaskProfiler(), SpanRecorder()
    with Executor(num_workers=1, devices=[dev], profiler=prof,
                  obs=rec) as ex:
        ex.run(G).result(timeout=120)
        assert ex.stats()["spills"] >= 2
    spills = [e for e in prof.trace()["events"] if e["type"] == "spill"]
    assert spills and all(isinstance(e["node"], int) for e in spills)
    assert any("span" in e for e in spills)      # the forcing task
    obs_spills = rec.events("spill")
    assert obs_spills and all(e["lane"] == "arena" for e in obs_spills)
    assert any(e.get("trigger") is not None for e in obs_spills)


def test_chaos_runner_emits_trigger_events():
    rec = SpanRecorder()
    plan = ChaosPlan((ChaosEvent(2, "kill", 1),
                      ChaosEvent(4, "slow", 0, factor=3.0)))
    runner = plan.runner(obs=rec)
    assert runner.due(1) == []
    assert len(runner.due(5)) == 2               # both triggers fire
    evs = rec.events("chaos_trigger")
    assert [(e["action"], e["bin"]) for e in evs] == [("kill", 1),
                                                     ("slow", 0)]
    assert evs[1]["factor"] == 3.0


def test_simulate_metrics_publishing_does_not_perturb():
    """Obs-disabled parity at the simulator level: metrics= publishes
    after the report is built, so the numbers are identical either
    way (the bench-level twin is the obs_off_bit_identical gate)."""
    G = _chain_fanout()
    bins = ["d0", "d1"]
    pl = get_scheduler("heft", cost_model=MODEL).schedule(G, bins)
    plain = simulate(G, pl, bins, cost_model=MODEL)
    reg = MetricsRegistry()
    G2 = _chain_fanout()
    pl2 = get_scheduler("heft", cost_model=MODEL).schedule(G2, bins)
    published = simulate(G2, pl2, bins, cost_model=MODEL, metrics=reg)
    assert published.makespan == plain.makespan
    # node ids are allocated globally, so compare the id-free shape
    assert [row[1:] for row in published.schedule] \
        == [row[1:] for row in plain.schedule]
    snap = reg.snapshot()
    assert snap["sim_runs"] == 1
    assert snap["sim_makespan_s"] == plain.makespan
    assert snap["sim_task_seconds"]["count"] == len(plain.schedule)


def test_executor_without_obs_matches_with_obs():
    """The recorder must observe, never steer: the same graph produces
    the same results and the same task tallies with and without it."""
    G1, ex1 = _live_run()
    G2, ex2 = _live_run(obs=SpanRecorder())
    r1 = sorted((n.name, int(np.asarray(n.state["result"]).sum()))
                for n in G1.nodes if n.state.get("result") is not None)
    r2 = sorted((n.name, int(np.asarray(n.state["result"]).sum()))
                for n in G2.nodes if n.state.get("result") is not None)
    assert r1 == r2
    assert ex1.stats()["executed"] == ex2.stats()["executed"]


# ----------------------------------------------------------------------
# obs.span: a profiler annotation, also written into a ring when given
# ----------------------------------------------------------------------
def test_span_lands_in_profiler_trace_with_stats(tmp_path):
    def work():
        with span("test.outer", slot=3, request=0) as outer:
            with span("test.inner", node="k1", worker=1):
                pass
            outer.set_metadata(active=2)

    _, got = profiled(work, tmp_path)
    assert [s.name for s in got] == ["test.outer", "test.inner"]
    outer, inner = got
    assert outer.stats == {"slot": 3, "request": 0, "active": 2}
    assert inner.stats == {"node": "k1", "worker": 1}
    assert outer.holds(inner)


def test_span_writes_ring_only_when_recorder_given(tmp_path):
    rec, idle = SpanRecorder(), SpanRecorder()

    def work():
        with span("test.bare", request=1):
            pass
        with span("test.ringed", rec, request=2) as s:
            s.set_metadata(active=4)

    _, got = profiled(work, tmp_path)
    assert [(s.name, s.stats) for s in got] == [
        ("test.bare", {"request": 1}),
        ("test.ringed", {"request": 2, "active": 4})]
    spans = rec.spans()
    assert [(s["name"], s["request"], s["active"]) for s in spans] == [
        ("test.ringed", 2, 4)]
    assert spans[0]["end_ts"] >= spans[0]["ts"]
    assert len(idle) == 0 and len(rec) == 2          # one B/E pair


@pytest.mark.parametrize("with_obs", [False, True],
                         ids=["no_recorder", "recorder"])
def test_executor_task_spans_reach_profiler(tmp_path, with_obs):
    """Every task runs inside one ``executor.task`` span naming the task
    and its worker, whether or not a recorder is attached; with one, the
    ring holds the same spans."""
    rec = SpanRecorder() if with_obs else None
    (G, _), got = profiled(lambda: _live_run(obs=rec), tmp_path)
    tasks = [s for s in got if s.name == "executor.task"]
    names = sorted(n.name for n in G.nodes)
    assert sorted(s.stats["node"] for s in tasks) == names
    assert all(s.stats["worker"] in (0, 1) for s in tasks)
    assert {s.stats["lane"] for s in tasks} <= {"copy", "compute", "host"}
    if with_obs:
        ring = [s for s in rec.spans() if s["name"] == "executor.task"]
        assert sorted(s["node"] for s in ring) == names
