"""Pipeline parallelism as a scheduled workload (distributed/pipeline.py).

The pipeline emits stage-tagged task groups and the ``repro.sched``
subsystem places them onto ``StageBin`` pools — these tests cover the
whole loop: stage-atomic grouping, scheduled-vs-pinned makespan parity,
mixed-member stage pools on the real executor, inter-stage link
costing, trace-v4 recording (stage ids + link descriptors) with
v1/v2/v3 regression, ``CostModel.fit`` link calibration, replay
validation, stage-atomic migration, and the cost-asymmetric
``pipeline_schedule_length`` lower bound.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))

from repro.core import Executor, Heteroflow  # noqa: E402
from repro.core.graph import TaskType  # noqa: E402
from repro.distributed.pipeline import (Stage, build_pipeline_graph,  # noqa: E402
                                        pinned_placement,
                                        pipeline_schedule_length)
from repro.sched import (CostModel, DeviceBin, HostBin, MeshBin,  # noqa: E402
                         StageBin, TaskProfiler, bins_from_trace,
                         build_groups, get_scheduler, load_trace, simulate,
                         stage_bins)


def _stages(n, d=8, costs=None):
    key = jax.random.PRNGKey(0)
    ws = [jax.random.normal(jax.random.fold_in(key, i), (d, d)) * 0.3
          for i in range(n)]
    fn = jax.jit(lambda w, x: jnp.tanh(x @ w))
    return [Stage(fn=fn, params=np.asarray(w),
                  cost=(costs[i] if costs else 1.0))
            for i, w in enumerate(ws)]


def _expected(stages, mbs):
    outs = []
    for mb in mbs:
        want = mb
        for st in stages:
            want = np.tanh(want @ np.asarray(st.params))
        outs.append(want)
    return outs


def _sim_pipeline(n_stages=4, n_mb=6, costs=None):
    """Simulator-only pipeline over synthetic stage members."""
    sts = [Stage(fn=lambda w, x: x, params=np.zeros((4, 4), np.float32),
                 cost=(costs[s] if costs else 100.0))
           for s in range(n_stages)]
    mbs = [np.zeros((2, 4), np.float32) for _ in range(n_mb)]
    return build_pipeline_graph(sts, mbs)


# ----------------------------------------------------------------------
# executor end-to-end
# ----------------------------------------------------------------------
def test_pipeline_output_matches_sequential_on_stage_bins():
    stages = _stages(3)
    mbs = [np.random.default_rng(i).normal(size=(4, 8)).astype(np.float32)
           for i in range(5)]
    out: list = []
    G = build_pipeline_graph(stages, mbs, collect=out)
    pool = stage_bins([jax.devices()[0]] * 3)
    with Executor(num_workers=4, devices=pool) as ex:
        ex.run(G).result(timeout=120)
    assert len(out) == 5
    for got, want in zip(out, _expected(stages, mbs)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # no placement logic in the pipeline: the scheduler decided, and it
    # kept every stage atomic on one stage slot
    by_stage = {}
    for n in G.nodes:
        if n.state.get("stage") is not None:
            by_stage.setdefault(n.state["stage"], set()).add(id(n.device))
    assert len(by_stage) == 3
    assert all(len(v) == 1 for v in by_stage.values())


def test_pipeline_untagged_runs_on_plain_default_executor():
    """require_stage_bins=False keeps the graph schedulable on raw
    jax.Device bins — the back-compat path."""
    stages = _stages(2)
    mbs = [np.random.default_rng(9).normal(size=(4, 8)).astype(np.float32)]
    out: list = []
    G = build_pipeline_graph(stages, mbs, collect=out,
                             require_stage_bins=False)
    with Executor(num_workers=2) as ex:
        ex.run(G).result(timeout=120)
    np.testing.assert_allclose(out[0], _expected(stages, mbs)[0],
                               rtol=1e-5, atol=1e-5)


def test_pipeline_over_mixed_member_stage_pool():
    """Stage slots backed by a HostBin, a DeviceBin, and a real 1x1
    MeshBin all execute correctly — stage-scope dispatch delegates to
    whatever member backs the slot."""
    from repro.launch.mesh import make_local_mesh

    (mesh_bin,) = MeshBin.from_mesh(make_local_mesh())
    pool = stage_bins([HostBin(), DeviceBin(jax.devices()[0]), mesh_bin])
    stages = _stages(3)
    mbs = [np.random.default_rng(i).normal(size=(4, 8)).astype(np.float32)
           for i in range(4)]
    out: list = []
    G = build_pipeline_graph(stages, mbs, collect=out)
    with Executor(num_workers=3, devices=pool) as ex:
        ex.run(G).result(timeout=120)
    assert len(out) == 4
    for got, want in zip(out, _expected(stages, mbs)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# grouping + placement semantics
# ----------------------------------------------------------------------
def test_stage_groups_are_atomic_and_tagged():
    G = _sim_pipeline(n_stages=3, n_mb=4)
    groups = build_groups(G)
    staged = {g.stage_id: g for g in groups if g.stage_id is not None}
    assert set(staged) == {0, 1, 2}
    assert len(groups) == 3                    # mb pulls fold into stage 0
    for s, g in staged.items():
        assert "stage" in g.requires
        names = {n.name for n in g.nodes}
        assert f"weights[{s}]" in names
        assert all(f"f[{s},{m}]" in names for m in range(4))
    # microbatch feeds are co-placed with the stage that consumes them
    assert {"mb[0]", "mb[3]"} <= {n.name for n in staged[0].nodes}


def test_conflicting_stage_tags_in_one_group_raise():
    G = Heteroflow()
    p = G.pull(np.zeros(8), name="shared")
    G.kernel(lambda a: a, p, stage=0, name="k0")
    G.kernel(lambda a: a, p, stage=1, name="k1")
    with pytest.raises(ValueError, match="stage atomicity"):
        build_groups(G)


def test_stage_tagged_graph_requires_stage_bins():
    G = _sim_pipeline(n_stages=2, n_mb=2)
    with pytest.raises(ValueError, match="requires capabilities"):
        get_scheduler("balanced").schedule(G, ["d0", "d1"])


@pytest.mark.parametrize("policy", ["balanced", "heft"])
def test_scheduled_placement_not_worse_than_hand_pinned(policy):
    """Acceptance: the scheduler placing free stage groups never loses
    to the historical stage-s-to-bin-s hand-pinning."""
    model = CostModel()
    pool = stage_bins([f"d{i}" for i in range(4)])
    kwargs = {"cost_model": model} if policy == "heft" else {}
    G = _sim_pipeline(n_stages=4, n_mb=8)
    pl = get_scheduler(policy, **kwargs).schedule(G, pool)
    sched_ms = simulate(G, pl, pool, cost_model=model).makespan
    Gp = _sim_pipeline(n_stages=4, n_mb=8)
    pin_ms = simulate(Gp, pinned_placement(Gp, pool), pool,
                      cost_model=model).makespan
    assert sched_ms <= pin_ms * (1 + 1e-9)


def test_pinned_placement_covers_all_device_tasks():
    G = _sim_pipeline(n_stages=3, n_mb=2)
    pool = stage_bins(["a", "b"])
    pl = pinned_placement(G, pool)
    device_tasks = [n for n in G.nodes
                    if n.type in (TaskType.KERNEL, TaskType.PULL)]
    assert set(pl) == {n.id for n in device_tasks}
    # wrap-around: stage 2 shares bin 0 with stage 0
    names = {n.id: n.name for n in G.nodes}
    assert {pl[i].stage_id for i in pl if names[i] == "weights[2]"} == {0}


# ----------------------------------------------------------------------
# inter-stage link costing
# ----------------------------------------------------------------------
def test_transfer_time_uses_destination_stage_link():
    m = CostModel(d2d_bandwidth=1e9, latency_s=1e-6,
                  stage_link_bandwidth=2e9)
    fat = StageBin(1, "d1", link_bandwidth=1e10, link_latency_s=1e-7)
    bare = StageBin(2, "d2")
    # explicit destination link wins
    assert m.transfer_time(1000, "d0", fat) == pytest.approx(
        1e-7 + 1000 / 1e10)
    # undeclared stage link falls back to the fitted stage bandwidth
    assert m.transfer_time(1000, fat, bare) == pytest.approx(
        1e-6 + 1000 / 2e9)
    # no stage endpoint: legacy d2d path, bit-identical
    assert m.transfer_time(1000) == pytest.approx(1e-6 + 1000 / 1e9)
    assert m.transfer_time(1000, "d0", "d1") == m.transfer_time(1000)


def test_simulator_charges_stage_links():
    """A thin inter-stage link slows the simulated pipeline; a fat one
    does not — the link, not generic d2d, carries activations."""
    def run(bw):
        pool = stage_bins(["a", "b"], link_bandwidth=bw)
        G = _sim_pipeline(n_stages=2, n_mb=4)
        pl = pinned_placement(G, pool)
        return simulate(G, pl, pool, cost_model=CostModel()).makespan
    assert run(1e4) > run(1e12) * 2


def test_stage_bin_rejects_non_positive_link_figures():
    """Only None means 'fall back to the cost model' — a zero bandwidth
    would silently model as full-speed d2d."""
    with pytest.raises(ValueError, match="link_bandwidth"):
        StageBin(0, "d0", link_bandwidth=0.0)
    with pytest.raises(ValueError, match="link_latency_s"):
        StageBin(0, "d0", link_latency_s=-1e-6)
    assert StageBin(0, "d0", link_latency_s=0.0).link_latency_s == 0.0


def test_heft_pipelined_eft_requires_cellwise_coupling():
    """A lone edge between adjacent stage groups (reduction-style) must
    NOT trigger first-cell readiness: the reduction truly waits for the
    whole upstream stage, so spreading it to another bin only adds the
    transfer — HEFT must co-locate.  (Under the ungated heuristic the
    cross-bin EFT looks one cell after the upstream START, which beats
    the same-bin group finish and wrongly spreads.)"""
    pool = stage_bins(["a", "b"])           # default (fat) links
    G = Heteroflow()
    prev = None
    for m in range(4):                      # stage 0: 4 chained cells
        p = G.pull(np.zeros(4000), name=f"p0_{m}", stage=0)
        k = G.kernel(lambda a: a, p, cost=100.0, stage=0,
                     requires=("stage",), name=f"s0_{m}")
        k.succeed(p)
        if prev is not None:
            prev.precede(k)
        prev = k
    pr = G.pull(np.zeros(4000), name="p1", stage=1)
    red = G.kernel(lambda a, b: a, pr, prev, cost=100.0, stage=1,
                   requires=("stage",), name="reduce")
    red.succeed(pr, prev)                   # ONE cross-stage edge
    model = CostModel()
    pl = get_scheduler("heft", cost_model=model).schedule(G, pool)
    assert pl[red._node.id] is pl[prev._node.id]


def test_heft_pipelined_eft_ignores_last_cell_fanout():
    """M edges all rooted in the upstream LAST cell are not cell-wise
    coupling either (distinct producers, not edge count, gate the
    pipelined EFT): the consumers wait for the group finish, so HEFT
    must co-locate instead of spreading for phantom overlap."""
    pool = stage_bins(["a", "b"])
    G = Heteroflow()
    prev = None
    for m in range(4):                      # stage 0: 4 chained cells
        p = G.pull(np.zeros(4000), name=f"p0_{m}", stage=0)
        k = G.kernel(lambda a: a, p, cost=100.0, stage=0,
                     requires=("stage",), name=f"s0_{m}")
        k.succeed(p)
        if prev is not None:
            prev.precede(k)
        prev = k
    heads = []
    for m in range(4):                      # stage 1: 4 cells, ALL fed
        p = G.pull(np.zeros(4000), name=f"p1_{m}", stage=1)
        k = G.kernel(lambda a, b: a, p, prev, cost=100.0, stage=1,
                     requires=("stage",), name=f"s1_{m}")
        k.succeed(p, prev)                  # ... by the LAST s0 cell
        heads.append(k)
    model = CostModel()
    pl = get_scheduler("heft", cost_model=model).schedule(G, pool)
    assert pl[heads[0]._node.id] is pl[prev._node.id]


# ----------------------------------------------------------------------
# collective-overhead (non-ideal sharded scaling)
# ----------------------------------------------------------------------
def test_collective_overhead_formula_and_default_off():
    m = CostModel()
    assert m.collective_overhead(8, 1 << 20) == 0.0      # default: off
    m = CostModel(collective_alpha=1e-5, collective_beta=1e9)
    assert m.collective_overhead(1, 1 << 20) == 0.0      # single device
    n, b = 4, 1 << 20
    assert m.collective_overhead(n, b) == pytest.approx(
        1e-5 * 3 + b * 3 / (4 * 1e9))
    # alpha-only model still charges the latency term
    m2 = CostModel(collective_alpha=2e-5)
    assert m2.collective_overhead(4, 0) == pytest.approx(6e-5)
    # negative knobs would silently shrink sharded durations — rejected
    with pytest.raises(ValueError, match="collective_alpha"):
        CostModel(collective_alpha=-1e-5)
    with pytest.raises(ValueError, match="collective_beta"):
        CostModel(collective_beta=-1.0)


def test_collective_overhead_slows_mesh_compute_in_sim_and_heft():
    from workloads import build_sharded_stack

    pool = [MeshBin("m", {"data": 2, "model": 2}), "d0", "d1"]
    ideal = CostModel()
    lossy = CostModel(collective_alpha=1e-4, collective_beta=1e6)

    def makespan(model):
        G = build_sharded_stack()
        pl = get_scheduler("heft", cost_model=model).schedule(G, pool)
        return simulate(G, pl, pool, cost_model=model).makespan

    base = makespan(ideal)
    assert makespan(lossy) > base
    # PR 4 baseline reproduces bit-for-bit with the knobs at zero
    assert makespan(CostModel(collective_alpha=0.0,
                              collective_beta=0.0)) == base
    # the sync is a COMPUTE cost: sharded pulls keep their ideal split
    # (same rule HEFT charges — only kernel durations grow)
    G = build_sharded_stack()
    pl = get_scheduler("heft", cost_model=ideal).schedule(G, pool)
    kinds = {n.id: n.type.value for n in G.nodes}
    for model in (ideal, lossy):
        rep = simulate(G, pl, pool, cost_model=model)
        pulls = sorted((nid, e - s) for nid, _, b, s, e in rep.schedule
                       if kinds[nid] == "pull" and b == 0)
        if model is ideal:
            ideal_pulls = pulls
        else:
            assert pulls == ideal_pulls


# ----------------------------------------------------------------------
# trace v4: stage ids + link descriptors, fit, replay, old versions
# ----------------------------------------------------------------------
def _profiled_pipeline_run(workers=1):
    pool = stage_bins([jax.devices()[0]] * 2, link_bandwidth=4e9)
    stages = _stages(2)
    mbs = [np.random.default_rng(i).normal(size=(4, 8)).astype(np.float32)
           for i in range(3)]
    G = build_pipeline_graph(stages, mbs)
    prof = TaskProfiler()
    with Executor(num_workers=workers, devices=pool, profiler=prof) as ex:
        ex.run(G).result(timeout=120)
    return G, prof, pool, ex


def test_trace_v4_records_stages_and_link_descriptors(tmp_path):
    G, prof, pool, ex = _profiled_pipeline_run()
    trace = prof.trace()
    assert trace["version"] == 6
    descs = trace["meta"]["bin_descriptors"]
    assert [d["kind"] for d in descs] == ["stage", "stage"]
    for s, d in enumerate(descs):
        assert d["stage_id"] == s
        assert d["link_bandwidth"] == pytest.approx(4e9)
        assert d["member"]["kind"] == "device"
    cells = [r for r in trace["records"] if r["name"].startswith("f[")]
    assert cells and all("stage" in r for r in cells)
    assert {r["stage"] for r in cells} == {0, 1}
    # untagged records carry no stage key at all
    assert all("stage" not in r for r in trace["records"]
               if r["name"].startswith("mb["))
    # roundtrip through disk, then rebuild the stage pool from the trace
    path = tmp_path / "pipe.json"
    prof.save(str(path))
    loaded = load_trace(str(path))
    rebuilt = bins_from_trace(loaded)
    assert [b.kind for b in rebuilt] == ["stage", "stage"]
    assert [b.stage_id for b in rebuilt] == [0, 1]
    assert [b.link_bandwidth for b in rebuilt] == [4e9, 4e9]
    assert [b.label for b in rebuilt] == ex.device_labels


def test_trace_v4_fit_replay_within_divergence_bound():
    """Acceptance: a recorded pipeline run round-trips through
    CostModel.fit → simulate(replay=...) within the 15% bound."""
    errs = []
    for _ in range(3):
        G, prof, pool, ex = _profiled_pipeline_run(workers=1)
        CostModel.fit(prof)                   # fit must accept v4 traces
        pl = {n.id: n.device for n in G.nodes if n.device is not None}
        rep = simulate(G, pl, pool, replay=prof)
        assert rep.measured_makespan == pytest.approx(prof.makespan())
        assert rep.divergence is not None
        errs.append(abs(rep.divergence))
        if errs[-1] <= 0.15:
            break
    assert min(errs) <= 0.15, (
        f"replay never within 15% of measurement: "
        f"{[f'{e:.2f}' for e in errs]}")


def _synthetic_records(with_xfer=True, with_stage=False):
    recs = [
        {"node": 0, "name": "p0", "type": "pull", "bin": "s0",
         "worker": 0, "iteration": 0, "start": 0.0, "end": 0.001,
         "cost": 8000.0, "bytes": 8000},
        {"node": 1, "name": "k0", "type": "kernel", "bin": "s0",
         "worker": 0, "iteration": 0, "start": 0.001, "end": 0.002,
         "cost": 1000.0, "bytes": 0},
        {"node": 2, "name": "k1", "type": "kernel", "bin": "s1",
         "worker": 0, "iteration": 0, "start": 0.002, "end": 0.007,
         "cost": 1000.0, "bytes": 0},
    ]
    if with_xfer:
        recs[2]["xfer_bytes"] = 4000
    if with_stage:
        recs[1]["stage"] = 0
        recs[2]["stage"] = 1
    return recs


def test_old_trace_versions_still_load_and_replay(tmp_path):
    """v1/v2/v3 pipeline-era traces keep loading and replaying — the v4
    bump must not orphan recorded history."""
    import json

    G = Heteroflow()
    p0 = G.pull(np.zeros(1000), name="p0")
    k0 = G.kernel(lambda a: a, p0, cost=1000.0, name="k0")
    k1 = G.kernel(lambda a: a + 1, k0, cost=1000.0, name="k1")
    k1.succeed(k0)
    bins = ["s0", "s1"]
    pl = get_scheduler("round_robin").schedule(G, bins)
    for version in (1, 2, 3):
        recs = _synthetic_records(with_xfer=version >= 2)
        meta = {"bins": bins, "workers": 1}
        if version >= 3:
            meta["bin_descriptors"] = [
                {"kind": "device", "label": b, "device_count": 1,
                 "capabilities": ["device"]} for b in bins]
        trace = {"version": version, "meta": meta, "records": recs,
                 "lanes": {}}
        path = tmp_path / f"v{version}.json"
        path.write_text(json.dumps(trace))
        loaded = load_trace(str(path))
        assert loaded["version"] == version
        rep = simulate(G, pl, bins, replay=loaded)
        # replay is ground truth: last record ends at 7ms
        assert rep.makespan == pytest.approx(0.007)
        assert rep.divergence == pytest.approx(0.0)
        fitted = CostModel.fit(loaded)
        assert fitted.compute_rate > 0
        # v1 has no xfer_bytes: d2d calibration skipped, default kept
        if version == 1:
            assert fitted.d2d_bandwidth == CostModel().d2d_bandwidth


def test_fit_calibrates_stage_link_bandwidth():
    """Kernels that ran on stage bins with cross-bin operands calibrate
    stage_link_bandwidth; without stage descriptors the same records
    calibrate generic d2d (v2/v3 behavior preserved)."""
    stage_meta = {
        "bins": ["s0", "s1"], "workers": 1,
        "bin_descriptors": [
            {"kind": "stage", "label": f"s{i}", "stage_id": i,
             "device_count": 1, "capabilities": ["device", "stage"],
             "member": {"kind": "device", "label": f"d{i}",
                        "device_count": 1}}
            for i in range(2)]}
    v4 = {"version": 4, "meta": stage_meta,
          "records": _synthetic_records(with_stage=True), "lanes": {}}
    fitted = CostModel.fit(v4)
    # k0 (local) pins the rate: 1000 cost / 1ms = 1e6.  k1 took 5ms —
    # 1ms compute + 4ms excess for 4000 cross-stage bytes, minus the
    # fitted latency — so the link comes out just above 1e6 B/s.
    assert fitted.compute_rate == pytest.approx(1e6)
    assert fitted.stage_link_bandwidth == pytest.approx(4000 / 0.003,
                                                        rel=0.35)
    assert fitted.d2d_bandwidth == CostModel().d2d_bandwidth  # untouched
    # same records, plain device descriptors → d2d calibrated instead
    dev_meta = {"bins": ["s0", "s1"], "workers": 1,
                "bin_descriptors": [
                    {"kind": "device", "label": f"s{i}", "device_count": 1}
                    for i in range(2)]}
    v3 = {"version": 3, "meta": dev_meta,
          "records": _synthetic_records(), "lanes": {}}
    f3 = CostModel.fit(v3)
    assert f3.stage_link_bandwidth == 0.0
    assert f3.d2d_bandwidth != CostModel().d2d_bandwidth


# ----------------------------------------------------------------------
# dynamic re-placement keeps stages atomic
# ----------------------------------------------------------------------
def _reschedule(sched, G, bins, *, measured_load, migrate_top_k=0):
    """Measured-load rebalance via the event loop — the migration-guide
    recipe (docs/scheduling.md) that replaced the removed
    ``Scheduler.reschedule()`` shim."""
    from repro.sched import SchedulerState, SchedulerUpdate, apply_assignment
    groups = build_groups(G)
    state = SchedulerState(bins, migrate_top_k=migrate_top_k)
    for g in groups:
        state.add_group(g)
    state.measured_load = measured_load
    sched.update(state, SchedulerUpdate(), graph=G)
    return apply_assignment(G, groups, bins, state.assignment)


@pytest.mark.parametrize("top_k", [1, 2])
def test_reschedule_migration_is_stage_atomic(top_k):
    pool = stage_bins([f"d{i}" for i in range(3)])
    G = _sim_pipeline(n_stages=3, n_mb=4)
    sched = get_scheduler("balanced")
    sched.schedule(G, pool)
    # heavily imbalanced measured window forces migration pressure
    pl = _reschedule(sched, G, pool,
                     measured_load={0: 100.0, 1: 1.0, 2: 1.0},
                     migrate_top_k=top_k)
    by_stage = {}
    for n in G.nodes:
        sid = n.state.get("stage")
        if sid is not None:
            by_stage.setdefault(sid, set()).add(id(pl[n.id]))
    assert len(by_stage) == 3
    # every stage still lives on exactly one bin, and only stage bins
    assert all(len(v) == 1 for v in by_stage.values())
    assert all(getattr(b, "kind", None) == "stage" for b in pl.values())


# ----------------------------------------------------------------------
# schedule-length lower bound (cost-asymmetric)
# ----------------------------------------------------------------------
def test_schedule_length_formula():
    # unit costs recover the classic GPipe count
    assert pipeline_schedule_length(4, 8) == 11
    # the bottleneck stage dominates: fill Σc + (M−1)·max c
    assert pipeline_schedule_length(3, 4, [1.0, 5.0, 2.0]) == \
        pytest.approx(8.0 + 3 * 5.0)
    assert pipeline_schedule_length(2, 3, {1: 4.0}) == \
        pytest.approx(5.0 + 2 * 4.0)
    assert pipeline_schedule_length(0, 5) == 0.0
    with pytest.raises(ValueError, match="stage costs"):
        pipeline_schedule_length(3, 2, [1.0])


@pytest.mark.parametrize("n_bins", [1, 2, 4])
@pytest.mark.parametrize("policy", ["balanced", "heft"])
def test_simulator_never_beats_schedule_length_bound(n_bins, policy):
    costs = [100.0, 300.0, 200.0, 100.0]
    model = CostModel()
    pool = stage_bins([f"d{i}" for i in range(n_bins)])
    G = _sim_pipeline(n_stages=4, n_mb=6, costs=costs)
    kwargs = {"cost_model": model} if policy == "heft" else {}
    pl = get_scheduler(policy, **kwargs).schedule(G, pool)
    ms = simulate(G, pl, pool, cost_model=model, host_workers=8).makespan
    bound = pipeline_schedule_length(4, 6, costs) / model.compute_rate
    assert ms >= bound * (1 - 1e-9)
