"""repro.sched.bins: execution-bin kinds, capability eligibility, mesh
cost scaling, trace-v3 descriptors + back-compat, hot-group migration,
and per-kernel-name cost-model history."""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))

from repro.core import Executor, Heteroflow
from repro.sched import (
    CostModel,
    DeviceBin,
    HostBin,
    MeshBin,
    TaskProfiler,
    available_policies,
    bin_capabilities,
    bins_from_trace,
    build_groups,
    describe_bin,
    eligible_bins,
    get_scheduler,
    load_trace,
    simulate,
)

# unit-rate, transfer-free model with kernel-declared costs (the golden
# setup test_sched.py uses)
MODEL = CostModel(compute_rate=1.0, h2d_bandwidth=float("inf"),
                  d2d_bandwidth=float("inf"), latency_s=0.0, host_time_s=0.0,
                  cost_fn=lambda n: float(n.state.get("cost", 0.0)))


def _kern(G, name, cost, *deps, requires=()):
    p = G.pull(np.zeros(8), name=f"p_{name}")
    k = G.kernel(lambda own, *d: None, p, *deps, cost=cost, name=name,
                 requires=requires)
    k.succeed(p)
    for d in deps:
        k.succeed(d)
    return k


def _mixed_graph():
    """2 untagged kernels + 1 mesh-tagged sharded kernel."""
    G = Heteroflow("mixed")
    a = _kern(G, "a", 4.0)
    _kern(G, "b", 4.0, a)
    _kern(G, "sh", 8.0, a, requires=("mesh",))
    return G


def _mesh22():
    return MeshBin("mesh:2x2[0]", {"data": 2, "model": 2})


# ----------------------------------------------------------------------
# bin kinds, labels, capabilities
# ----------------------------------------------------------------------
def test_bin_kinds_labels_and_capabilities():
    import jax
    dev = jax.devices()[0]
    db = DeviceBin(dev)
    assert db.kind == "device" and db.device_count == 1
    assert db.label == f"{dev.platform}:{dev.id}"
    assert {"device", dev.platform} <= set(db.capabilities)
    assert db.put_target() is dev

    hb = HostBin()
    assert hb.kind == "host" and hb.capabilities == frozenset({"host"})
    assert hb.put_target() is None

    mb = _mesh22()
    assert mb.kind == "mesh" and mb.device_count == 4
    assert "mesh" in mb.capabilities
    # synthetic slices are simulator-only: executing one must fail
    # loudly, not silently run unsharded on the default device
    with pytest.raises(RuntimeError, match="synthetic"):
        mb.put_target()
    assert "tpu" in MeshBin("m", {"data": 2},
                            capabilities=("tpu",)).capabilities

    # raw objects are device bins with a platform capability
    assert bin_capabilities(dev) == frozenset({"device", dev.platform})
    assert bin_capabilities("d0") == frozenset({"device"})

    # stable labels flow into bin_labels / device_key
    from repro.core.streams import bin_labels
    assert bin_labels([db, hb, mb]) == [db.label, "host", "mesh:2x2[0]"]

    with pytest.raises(ValueError, match="axis_shape"):
        MeshBin("empty", {})


def test_mesh_bin_from_mesh_enumerates_slices():
    import jax
    from jax.sharding import Mesh
    d = jax.devices()[0]
    # validation is lazy, so a 4x2 mesh over the repeated host device is
    # a legitimate enumeration fixture
    mesh = Mesh(np.array([[d] * 2] * 4), ("data", "model"))
    slices = MeshBin.from_mesh(mesh, {"data": 2})
    assert [b.label for b in slices] == ["mesh:2x2[0]", "mesh:2x2[1]"]
    assert all(b.device_count == 4 for b in slices)
    assert all(b.axis_shape == {"data": 2, "model": 2} for b in slices)
    assert all(b.mesh is not None and b.mesh.devices.shape == (2, 2)
               for b in slices)
    assert all("cpu" in b.capabilities and "mesh" in b.capabilities
               for b in slices)
    with pytest.raises(ValueError, match="does not divide"):
        MeshBin.from_mesh(mesh, {"data": 3})
    with pytest.raises(ValueError, match="no axis"):
        MeshBin.from_mesh(mesh, {"nope": 1})


# ----------------------------------------------------------------------
# capability eligibility across every registered policy
# ----------------------------------------------------------------------
def test_all_policies_respect_capability_tags():
    bins = [_mesh22(), "d0", HostBin()]
    for policy in available_policies():
        G = _mixed_graph()
        kwargs = {"cost_model": MODEL} if policy == "heft" else {}
        pl = get_scheduler(policy, **kwargs).schedule(G, bins, MODEL.cost_fn)
        by_name = {n.name: pl[n.id] for n in G.nodes if n.id in pl}
        assert by_name["sh"] is bins[0], policy       # mesh-tagged → MeshBin
        assert by_name["p_sh"] is bins[0], policy     # whole group rides along


def test_untagged_groups_eligible_everywhere():
    assert eligible_bins(frozenset(), ["d0", "d1"]) == [0, 1]
    assert eligible_bins(frozenset({"mesh"}), [_mesh22(), "d0"]) == [0]
    assert eligible_bins(frozenset({"host"}), [HostBin(), "d0"]) == [0]


def test_unsatisfiable_tags_raise_for_every_policy():
    for policy in available_policies():
        G = _mixed_graph()
        with pytest.raises(ValueError, match="requires capabilities"):
            get_scheduler(policy).schedule(G, ["d0", "d1"], MODEL.cost_fn)


def test_group_requires_unions_member_kernels():
    G = Heteroflow()
    p = G.pull(np.zeros(4))
    k1 = G.kernel(lambda a: a, p, requires=("mesh",))
    k1.succeed(p)
    # second kernel shares the pull → same affinity group, tags union
    k2 = G.kernel(lambda a: a, p, requires=("tpu",))
    k2.succeed(p)
    (g,) = build_groups(G)
    assert g.requires == frozenset({"mesh", "tpu"})


# ----------------------------------------------------------------------
# mesh cost scaling + per-member lane pairs in the simulator
# ----------------------------------------------------------------------
def test_sharded_kernel_scales_with_slice_device_count():
    for shape, count in (({"data": 1}, 1), ({"data": 2}, 2),
                         ({"data": 2, "model": 2}, 4)):
        bins = [MeshBin("m", shape)]
        G = Heteroflow()
        _kern(G, "sh", 8.0, requires=("mesh",))
        pl = get_scheduler("balanced").schedule(G, bins, MODEL.cost_fn)
        rep = simulate(G, pl, bins, cost_model=MODEL)
        assert rep.makespan == pytest.approx(8.0 / count), shape


def test_mesh_bin_runs_untagged_kernels_on_parallel_lanes():
    """A 2-device slice owns two compute lanes: two independent untagged
    kernels overlap on it, while a 1-device bin serializes them."""
    G = Heteroflow()
    _kern(G, "a", 4.0)
    _kern(G, "b", 4.0)
    pl = get_scheduler("balanced").schedule(
        G, [MeshBin("m", {"data": 2})], MODEL.cost_fn)
    two_lane = simulate(G, pl, [MeshBin("m", {"data": 2})],
                        cost_model=MODEL)
    G2 = Heteroflow()
    _kern(G2, "a", 4.0)
    _kern(G2, "b", 4.0)
    pl2 = get_scheduler("balanced").schedule(G2, ["d0"], MODEL.cost_fn)
    one_lane = simulate(G2, pl2, ["d0"], cost_model=MODEL)
    assert two_lane.makespan == pytest.approx(4.0)
    assert one_lane.makespan == pytest.approx(8.0)


def test_sharded_kernel_occupies_every_lane_of_its_slice():
    """A mesh-wide kernel blocks the whole slice: an untagged kernel
    queued behind it cannot start until the sharded one finishes."""
    bins = [MeshBin("m", {"data": 2})]
    G = Heteroflow()
    root = _kern(G, "root", 0.0)
    _kern(G, "sh", 8.0, root, requires=("mesh",))
    _kern(G, "u1", 2.0, root)
    _kern(G, "u2", 2.0, root)
    pl = get_scheduler("balanced").schedule(G, bins, MODEL.cost_fn)
    rep = simulate(G, pl, bins, cost_model=MODEL, host_workers=8)
    start = {nid: s for nid, _, _, s, _ in rep.schedule}
    end = {nid: e for nid, _, _, _, e in rep.schedule}
    ids = {n.name: n.id for n in G.nodes}
    sh_s, sh_e = start[ids["sh"]], end[ids["sh"]]
    assert sh_e - sh_s == pytest.approx(4.0)          # 8.0 / 2 devices
    for u in ("u1", "u2"):
        # untagged kernels either both fit before (two lanes) or wait out
        # the slice-wide kernel — never overlap it
        assert end[ids[u]] <= sh_s + 1e-9 or start[ids[u]] >= sh_e - 1e-9


def test_heft_exploits_wider_slice_on_sharded_workload():
    """Acceptance (bench gate, pinned): the 2x2 slice pool's HEFT
    makespan is <= the same pool with a single-device slice."""
    from workloads import build_sharded_stack

    def pool(shape):
        return [MeshBin("m", shape), "d0", "d1"]

    model = CostModel()
    res = {}
    for name, shape in (("1x1", {"data": 1}),
                        ("2x2", {"data": 2, "model": 2})):
        G = build_sharded_stack()
        pl = get_scheduler("heft", cost_model=model).schedule(
            G, pool(shape))
        res[name] = simulate(G, pl, pool(shape), cost_model=model).makespan
    assert res["2x2"] <= res["1x1"] * (1 + 1e-9)
    assert res["2x2"] < 0.7 * res["1x1"]     # and decisively so


# ----------------------------------------------------------------------
# executor end-to-end over execution bins
# ----------------------------------------------------------------------
def _exec_graph(out):
    G = Heteroflow()
    p1 = G.pull(np.arange(8, dtype=np.float32), name="p1")
    k1 = G.kernel(lambda a: float(np.asarray(a).sum()), p1, name="k1")
    k1.succeed(p1)
    p2 = G.pull(np.ones(4, np.float32), name="p2")
    k2 = G.kernel(lambda a, b: float(np.asarray(a).sum()) + b, p2, k1,
                  name="k2", requires=("mesh",))
    k2.succeed(p2, k1)
    ph = G.pull(np.full(2, 2.0, np.float32), name="ph")
    kh = G.kernel(lambda a: float(np.asarray(a).sum()), ph, name="kh",
                  requires=("host",))
    kh.succeed(ph)
    h = G.host(lambda: out.update(
        k2=k2._node.state["result"], kh=kh._node.state["result"]))
    h.succeed(k2, kh)
    return G


def _run_mixed_bins():
    import jax
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh()
    (mesh_bin,) = MeshBin.from_mesh(mesh)
    bins = [DeviceBin(jax.devices()[0]), HostBin(), mesh_bin]
    out = {}
    G = _exec_graph(out)
    prof = TaskProfiler()
    with Executor(num_workers=2, devices=bins, profiler=prof) as ex:
        assert ex.run(G).result(timeout=60) == 1
        stats = ex.stats()
    return prof, bins, G, out, stats


def test_executor_runs_mixed_bin_kinds_end_to_end():
    prof, bins, G, out, stats = _run_mixed_bins()
    assert out["k2"] == pytest.approx(8 * 7 / 2 + 4.0)   # sum(0..7)+sum(ones)
    assert out["kh"] == pytest.approx(4.0)
    # placement respected the tags end to end
    nodes = {n.name: n for n in G.nodes}
    assert nodes["k2"].device is bins[2]
    assert isinstance(nodes["kh"].device, HostBin)
    # host-bin pull stayed host-resident; mesh-bin pull is sharded
    assert isinstance(nodes["ph"].state["device_data"], np.ndarray)
    assert hasattr(nodes["p2"].state["device_data"], "sharding")
    assert set(stats["lane_depths"]) <= {b.label for b in bins}


# ----------------------------------------------------------------------
# trace v3: descriptors round-trip; v1/v2 still load and replay
# ----------------------------------------------------------------------
def test_trace_v3_descriptors_roundtrip(tmp_path):
    prof, bins, G, _, _ = _run_mixed_bins()
    trace = prof.trace()
    assert trace["version"] == 6
    descs = trace["meta"]["bin_descriptors"]
    assert [d["kind"] for d in descs] == ["device", "host", "mesh"]
    assert descs[2]["axis_shape"] == {"data": 1, "model": 1}
    path = tmp_path / "v3.json"
    prof.save(str(path))
    loaded = load_trace(str(path))
    assert loaded["meta"]["bin_descriptors"] == descs

    rebuilt = bins_from_trace(loaded)
    assert [b.kind for b in rebuilt] == ["device", "host", "mesh"]
    assert [b.label for b in rebuilt] == [b.label for b in bins]
    assert rebuilt[2].device_count == 1
    assert rebuilt[2].axis_shape == {"data": 1, "model": 1}
    assert describe_bin(rebuilt[2])["capabilities"] == \
        descs[2]["capabilities"]

    # replay the measured run over the RECONSTRUCTED bins
    pl = {n.id: rebuilt[[b.label for b in rebuilt].index(n.bin_key)]
          for n in G.nodes if n.bin_key is not None}
    rep = simulate(G, pl, rebuilt, replay=loaded)
    assert rep.measured_makespan == pytest.approx(prof.makespan(), rel=1e-6)
    assert rep.makespan > 0


def test_trace_v1_and_v2_still_load_and_replay(tmp_path):
    records = [
        {"node": 0, "name": "p_a", "type": "pull", "bin": "d0",
         "worker": 0, "iteration": 0, "start": 0.0, "end": 1.0,
         "cost": 0.0, "bytes": 64},
        {"node": 1, "name": "a", "type": "kernel", "bin": "d0",
         "worker": 0, "iteration": 0, "start": 1.0, "end": 3.0,
         "cost": 5.0, "bytes": 0},
    ]
    for version in (1, 2):
        recs = ([dict(r, xfer_bytes=0) for r in records]
                if version == 2 else records)
        trace = {"version": version, "meta": {"bins": ["d0"], "workers": 1},
                 "records": recs, "lanes": {}}
        path = tmp_path / f"v{version}.json"
        path.write_text(json.dumps(trace))
        loaded = load_trace(str(path))
        assert loaded["version"] == version
        # no descriptors → label-only device bins
        rebuilt = bins_from_trace(loaded)
        assert [b.kind for b in rebuilt] == ["device"]
        assert rebuilt[0].label == "d0"
        G = Heteroflow()
        _kern(G, "a", 5.0)
        pl = get_scheduler("balanced").schedule(G, rebuilt, MODEL.cost_fn)
        rep = simulate(G, pl, rebuilt, cost_model=MODEL, replay=loaded)
        assert rep.makespan == pytest.approx(3.0)
        assert rep.divergence == pytest.approx(0.0)
        assert CostModel.fit(loaded).compute_rate == pytest.approx(2.5)


def test_mesh_replay_uses_slice_lane_widths():
    """simulate(..., replay=) over mesh bins: two untagged kernels with
    measured 2s durations overlap on a 2-device slice (4s serial)."""
    mb = MeshBin("mesh:2x1[0]", {"data": 2})
    trace = {
        "version": 3,
        "meta": {"bins": [mb.label], "workers": 4,
                 "bin_descriptors": [describe_bin(mb)]},
        "records": [
            {"node": 0, "name": "a", "type": "kernel", "bin": mb.label,
             "worker": 0, "iteration": 0, "start": 0.0, "end": 2.0,
             "cost": 1.0, "bytes": 0, "xfer_bytes": 0},
            {"node": 1, "name": "b", "type": "kernel", "bin": mb.label,
             "worker": 1, "iteration": 0, "start": 0.0, "end": 2.0,
             "cost": 1.0, "bytes": 0, "xfer_bytes": 0},
        ],
        "lanes": {},
    }
    bins = bins_from_trace(trace)
    assert bins[0].device_count == 2
    G = Heteroflow()
    a = G.kernel(lambda: 0.0, name="a")
    b = G.kernel(lambda: 0.0, name="b")
    assert a and b
    pl = {n.id: bins[0] for n in G.nodes}
    rep = simulate(G, pl, bins, replay=trace)
    assert rep.makespan == pytest.approx(2.0)      # lanes overlap
    one = MeshBin(mb.label, {"data": 1})
    rep1 = simulate(G, {n.id: one for n in G.nodes}, [one], replay=trace)
    assert rep1.makespan == pytest.approx(4.0)     # single lane serializes


# ----------------------------------------------------------------------
# hot-group migration (measured-load rebalance, migrate_top_k)
# ----------------------------------------------------------------------
def _reschedule(sched, G, bins, cost_fn, *, measured_load,
                migrate_top_k=0):
    """Measured-load rebalance via the event loop — the migration-guide
    recipe (docs/scheduling.md) that replaced the removed
    ``Scheduler.reschedule()`` shim."""
    from repro.sched import SchedulerState, SchedulerUpdate, apply_assignment
    groups = build_groups(G, cost_fn)
    state = SchedulerState(bins, migrate_top_k=migrate_top_k)
    for g in groups:
        state.add_group(g)
    state.measured_load = measured_load
    sched.update(state, SchedulerUpdate(), graph=G)
    return apply_assignment(G, groups, bins, state.assignment)


def _eight_placed(policy="balanced"):
    G = Heteroflow()
    for i in range(8):
        _kern(G, f"k{i}", float(10 + i))
    sched = get_scheduler(policy)
    sched.schedule(G, ["d0", "d1"], MODEL.cost_fn)
    return G, sched


@pytest.mark.parametrize("policy", ["balanced", "heft"])
def test_migrate_near_equal_loads_do_not_churn(policy):
    G, sched = _eight_placed(policy)
    before = {n.id: n.device for n in G.nodes}
    pl = _reschedule(sched, G, ["d0", "d1"], MODEL.cost_fn,
                     measured_load={0: 1.0, 1: 1.05},
                     migrate_top_k=4)
    assert {n.id: n.device for n in G.nodes} == before
    assert pl == {nid: d for nid, d in before.items()}
    # full repacking under the same window is free to churn — the
    # migration mode is what pins the placement
    G2, sched2 = _eight_placed(policy)
    pl2 = _reschedule(sched2, G2, ["d0", "d1"], MODEL.cost_fn,
                      measured_load={0: 1.0, 1: 1.05})
    assert len(pl2) == len(pl)


def test_migrate_moves_at_most_k_hottest_groups():
    G, sched = _eight_placed()
    before = {n.id: n.device for n in G.nodes}
    groups = build_groups(G, MODEL.cost_fn)
    hottest_on_d0 = max(
        (g for g in groups if g.nodes[0].device == "d0"),
        key=lambda g: g.cost)
    pl = _reschedule(sched, G, ["d0", "d1"], MODEL.cost_fn,
                     measured_load={0: 10.0, 1: 0.5},
                     migrate_top_k=1)
    moved = [nid for nid, d in pl.items() if d != before[nid]]
    # exactly the hottest d0 group moved, nothing else
    assert set(moved) == {t.id for t in hottest_on_d0.nodes}
    assert all(pl[nid] == "d1" for nid in moved)


def test_migrate_honors_capability_tags():
    bins = [_mesh22(), "d0"]
    G = Heteroflow()
    _kern(G, "sh", 50.0, requires=("mesh",))
    _kern(G, "u", 1.0)
    sched = get_scheduler("balanced")
    sched.schedule(G, bins, MODEL.cost_fn)
    nodes = {n.name: n for n in G.nodes}
    assert nodes["sh"].device is bins[0]
    # the mesh bin is overloaded, but the sharded group cannot leave it
    pl = _reschedule(sched, G, bins, MODEL.cost_fn,
                     measured_load={0: 10.0, 1: 0.0},
                     migrate_top_k=2)
    assert pl[nodes["sh"].id] is bins[0]


def test_migrate_without_prior_placement_falls_back_to_repack():
    G = Heteroflow()
    for i in range(4):
        _kern(G, f"k{i}", 1.0)
    pl = _reschedule(
        get_scheduler("balanced"), G, ["d0", "d1"], MODEL.cost_fn,
        measured_load={0: 5.0, 1: 0.0}, migrate_top_k=2)
    assert len(pl) == len(G)
    assert set(pl.values()) <= {"d0", "d1"}


def test_executor_migrate_top_k_knob():
    import jax
    from repro.configs import SchedConfig

    assert SchedConfig().migrate_top_k == 0
    with pytest.raises(ValueError, match="migrate_top_k"):
        Executor(num_workers=1, devices=list(jax.devices()),
                 migrate_top_k=-1)
    G = Heteroflow()
    for i in range(4):
        _kern(G, f"k{i}", 1.0)
    with Executor(num_workers=2, devices=list(jax.devices()),
                  replace_every=1, migrate_top_k=2) as ex:
        assert ex.run_n(G, 3).result(timeout=60) == 3
        assert ex.stats()["replacements"] == 2


# ----------------------------------------------------------------------
# per-kernel-name CostModel history (StarPU per-codelet calibration)
# ----------------------------------------------------------------------
def _rec(name, cost, start, end, bin_="d0"):
    return {"type": "kernel", "name": name, "bin": bin_, "cost": cost,
            "bytes": 0, "start": start, "end": end}


def test_fit_keeps_per_kernel_name_rates():
    trace = {
        "version": 3,
        "meta": {"bins": ["d0"]},
        "records": [
            _rec("fast", 100.0, 0.0, 0.1),      # rate 1000
            _rec("slow", 100.0, 0.0, 1.0),      # rate 100
        ],
        "lanes": {},
    }
    m = CostModel.fit(trace)
    assert m.compute_rate == pytest.approx(200.0 / 1.1)   # aggregate
    assert m.kernel_rate("fast") == (pytest.approx(1000.0), 0.0)
    assert m.kernel_rate("slow") == (pytest.approx(100.0), 0.0)
    # unseen names fall back to the aggregate rate
    assert m.kernel_rate("unseen") == (m.compute_rate, 0.0)

    G = Heteroflow()
    _kern(G, "fast", 100.0)
    _kern(G, "slow", 100.0)
    model = CostModel.fit(
        trace, base=CostModel(cost_fn=MODEL.cost_fn,
                              latency_s=0.0,
                              h2d_bandwidth=float("inf")))
    nodes = {n.name: n for n in G.nodes}
    assert model.node_time(nodes["fast"]) == pytest.approx(0.1)
    assert model.node_time(nodes["slow"]) == pytest.approx(1.0)


def test_fit_per_name_latency_from_varied_costs():
    """Two observations at different costs pin (latency, rate):
    duration = 0.1 + cost/100."""
    trace = {
        "version": 3,
        "meta": {"bins": ["d0"]},
        "records": [
            _rec("k", 100.0, 0.0, 1.1),
            _rec("k", 200.0, 0.0, 2.1),
        ],
        "lanes": {},
    }
    m = CostModel.fit(trace)
    rate, lat = m.kernel_rate("k")
    assert rate == pytest.approx(100.0)
    assert lat == pytest.approx(0.1)


def test_fit_undoes_mesh_slice_speedup():
    """A sharded kernel's measured duration embeds the device_count×
    slice speedup; fit must normalize it out (the simulator re-applies
    the speedup at predict time — without normalization it would be
    double-counted and predictions off by device_count)."""
    mb = MeshBin("mesh:2x2[0]", {"data": 2, "model": 2})
    trace = {
        "version": 3,
        "meta": {"bins": [mb.label],
                 "bin_descriptors": [describe_bin(mb)]},
        "records": [
            # 400 cost units in 0.25 s ON A 4-DEVICE SLICE → true
            # single-device rate is 400 units/s, not 1600
            {"type": "kernel", "name": "sh", "bin": mb.label,
             "cost": 400.0, "bytes": 0, "requires": ["mesh"],
             "start": 0.0, "end": 0.25},
        ],
        "lanes": {},
    }
    m = CostModel.fit(trace)
    assert m.compute_rate == pytest.approx(400.0)
    assert m.kernel_rate("sh")[0] == pytest.approx(400.0)
    # round trip: predicting the same placement reproduces the measured
    # duration instead of measured/4
    G = Heteroflow()
    _kern(G, "sh", 400.0, requires=("mesh",))
    model = CostModel.fit(
        trace, base=CostModel(cost_fn=MODEL.cost_fn, latency_s=0.0,
                              h2d_bandwidth=float("inf")))
    pl = get_scheduler("balanced").schedule(G, [mb], model.cost_fn)
    rep = simulate(G, pl, [mb], cost_model=model)
    assert rep.makespan == pytest.approx(0.25)
    # untagged kernels on the same slice are NOT normalized
    trace["records"][0].pop("requires")
    assert CostModel.fit(trace).compute_rate == pytest.approx(1600.0)


def test_mesh_utilization_normalized_by_lane_width():
    """A slice saturated by one mesh-wide kernel reports utilization
    1.0, not 1/width; concurrent untagged kernels cannot exceed 1.0."""
    mb = MeshBin("m", {"data": 2})
    G = Heteroflow()
    _kern(G, "sh", 8.0, requires=("mesh",))
    pl = get_scheduler("balanced").schedule(G, [mb], MODEL.cost_fn)
    rep = simulate(G, pl, [mb], cost_model=MODEL)
    assert rep.utilization[0] == pytest.approx(1.0)
    G2 = Heteroflow()
    _kern(G2, "a", 4.0)
    _kern(G2, "b", 4.0)
    pl2 = get_scheduler("balanced").schedule(G2, [mb], MODEL.cost_fn)
    rep2 = simulate(G2, pl2, [mb], cost_model=MODEL)
    assert rep2.utilization[0] == pytest.approx(1.0)


def test_requires_accepts_a_bare_string_tag():
    G = Heteroflow()
    p = G.pull(np.zeros(2))
    k = G.kernel(lambda a: a, p, requires="mesh")
    k.succeed(p)
    (g,) = build_groups(G)
    assert g.requires == frozenset({"mesh"})


def test_fit_without_names_keeps_aggregate_only():
    trace = {
        "version": 1,
        "meta": {"bins": ["d0"]},
        "records": [
            {"type": "kernel", "bin": "d0", "cost": 400.0, "bytes": 0,
             "start": 0.0, "end": 1.0},
        ],
        "lanes": {},
    }
    m = CostModel.fit(trace)
    assert m.kernel_rates == ()
    assert m.kernel_rate("anything") == (m.compute_rate, 0.0)
