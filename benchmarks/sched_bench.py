"""Scheduler study: sweep placement policies × synthetic graph shapes.

For every (policy, shape) cell, schedules the graph onto ``--bins``
simulated device bins and reports the discrete-event simulator's
makespan under the overlapped lane model (copy lane ∥ compute lane per
bin, ``--lane-depth``) next to the serialized single-lane makespan and
the overlap gain — no JAX devices involved, runs on any CPU-only host
(estee-style offline scheduler comparison).

    PYTHONPATH=src python benchmarks/sched_bench.py
    PYTHONPATH=src python benchmarks/sched_bench.py --bins 4 \
        --speeds 1.0,1.0,0.5,0.5 --shapes fanout,diamond

``--bins mesh:NxM`` swaps the homogeneous device pool for a mixed
execution-bin pool (one synthetic NxM ``MeshBin`` slice + two device
bins, ``repro.sched.bins``) and adds the ``sharded`` shape, whose
``requires={"mesh"}`` kernels only the mesh slice may run; two extra
check rows gate capability eligibility and the slice's advantage over
a single-device slice (see docs/scheduling.md "Execution bins").

``--bins stage:N`` builds a pipeline pool of N ``StageBin`` slots over
a mixed member cycle (device / host / 1×1 mesh slice) and adds the
``pipeline_staged`` shape (``distributed.pipeline`` cells tagged
``requires={"stage"}``); gate rows assert the scheduled placement
never loses to the historical hand-pinning and that the 1F1B
fill/drain interleaving survives free placement.
``--collective-alpha`` / ``--collective-beta`` switch mesh-wide compute
from ideal linear scaling to the α-β ring-collective model
(``CostModel.collective_overhead``; 0/0 = off, baseline-identical).

``--memory-bytes N`` gives every bin an ``N``-byte ``memory_bytes``
budget (plain bins are wrapped in ``DeviceBin``): policies pack group
footprints against it and the simulator converts overflow into forced
spill charges.  Two gate rows cover the memory dimension:
``memory_capped_not_worse_than_2x_uncapped`` (budgeted makespans stay
within 2× of the unbudgeted run — spill cost is bounded, not
pathological) when the knob is set, and ``budgets_off_bit_identical``
(the gated policy's makespans equal the checked-in baseline EXACTLY,
not just within tolerance) when it is off at the default config.

``--arrival poisson:RATE`` appends the live-traffic serving study:
a Poisson request trace (``--requests``) is replayed through the
event-driven ``Scheduler.update()`` loop — one ``SchedulerUpdate`` per
arriving request, no global graph — and per-request TTFT p50/p99 is
reported per policy next to the static-batching strawman
(``--serving-batch`` requests per batch, each batch admitted only when
the previous one fully completes).  The
``online_p99_ttft_not_worse_than_static`` gate row requires the gated
policy's online p99 TTFT to beat or tie static batching.  Without the
flag nothing changes — the baseline rows stay bit-identical.

``--timeline out.json`` exports a Perfetto-loadable Chrome-trace JSON
(load at https://ui.perfetto.dev): one live executor run of the gated
policy's fanout cell — per-bin copy ∥ compute lane rows rendered from
the profiler trace — merged with its replay-simulated twin, plus
``timeline,...`` rows quantifying per-bin divergence
(``repro.obs.diff_timelines``).  Additive: the sweep rows and the
``--json`` payload never change; without the flag the
``obs_off_bit_identical`` gate row asserts the gated policy's
makespans still equal the checked-in baseline EXACTLY.

``--measure`` additionally executes every cell on the real executor
(one JAX-device bin per simulated bin), fits a ``CostModel`` from the
recorded trace, and appends measured wall-clock + the fitted
simulator's divergence — the replay-validation loop, side by side with
the offline numbers (see docs/scheduling.md; expect positive
divergence on CPU hosts, where JAX runs kernels from several workers
concurrently on one device while real accelerators serialize them).

Random is averaged over ``--random-seeds`` draws (a single unlucky or
lucky seed is not a baseline).  The trailing ``check`` rows assert the
paper-level sanity conditions: HEFT's critical-path scheduling beats
the random baseline on the shapes with real placement freedom
(fan-out / diamond), and the overlapped model never trails the
serialized one.

CI perf-regression gate (the simulator is deterministic, so drift means
a code change — see docs/scheduling.md for the baseline-refresh
procedure)::

    python benchmarks/sched_bench.py --json BENCH_sched.json --check-baseline
    python benchmarks/sched_bench.py --write-baseline \
        benchmarks/baselines/sched_baseline.json   # refresh after review
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.workloads import (
    build_chain,
    build_diamond,
    build_fanout,
    build_pipeline,
    build_random_dag,
    build_serving_trace,
    build_sharded_stack,
    build_timing_graph,
    serving_specs,
)
from repro.configs import DEFAULT_SCHED
from repro.core.streams import DEFAULT_LANE_DEPTH
from repro.sched import (
    ChaosPlan,
    CostModel,
    DeviceBin,
    HostBin,
    MeshBin,
    RandomPolicy,
    get_scheduler,
    online_report,
    percentile,
    poisson,
    simulate,
    stage_bins,
    static_batching_latency,
)

SHAPES = {
    "chain": lambda: build_chain(n=12),
    "fanout": lambda: build_fanout(width=10),
    "diamond": lambda: build_diamond(width=8),
    "random_dag": lambda: build_random_dag(n_kernels=96, seed=7,
                                           with_pushes=False)[0],
    # untagged pipeline: stage-atomic groups, schedulable on plain bins
    "pipeline": lambda: build_pipeline(n_stages=4, n_microbatches=8),
    # the paper's propagation DAG at sweep size (64 KiB pins so the
    # copy lane has real work to overlap); the million-task throughput
    # study runs the same shape at 10^5+ via --shape timing
    "timing": lambda: build_timing_graph(400, fanout=4, nbytes=65536),
}
#: shapes needing a MeshBin in the bin list (capability-tagged kernels);
#: swept only under ``--bins mesh:NxM``
MESH_SHAPES = {
    "sharded": lambda: build_sharded_stack(n_sharded=4, width=6),
}
#: shapes whose cells carry ``requires={"stage"}`` — swept only under
#: ``--bins stage:N`` (a StageBin pool over mixed member bins)
STAGE_SHAPES = {
    "pipeline_staged": lambda: build_pipeline(
        n_stages=4, n_microbatches=8, require_stage_bins=True),
}
ALL_SHAPES = {**SHAPES, **MESH_SHAPES, **STAGE_SHAPES}
POLICIES = ("balanced", "heft", "round_robin", "random")

DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__), "baselines",
                                "sched_baseline.json")
#: policy the CI gate watches; regressions elsewhere are advisory CSV rows
GATED_POLICY = "heft"
#: relative makespan increase that fails the gate
REGRESSION_RTOL = 0.10


def score(policy_name: str, shape: str, bins: list, model: CostModel,
          random_seeds: int, host_workers: int,
          ) -> tuple[float, float, dict[int, float]]:
    """Mean simulated makespan (s) under the overlapped lane model, the
    serialized (lane_depth=1) makespan, and mean utilization for one
    cell (random is averaged over seeds — all columns, consistently)."""
    serial_model = dataclasses.replace(model, lane_depth=1)
    if policy_name == "random":
        makespans: list[float] = []
        serials: list[float] = []
        util_sum: dict[int, float] = {i: 0.0 for i in range(len(bins))}
        for s in range(random_seeds):
            G = ALL_SHAPES[shape]()
            pl = RandomPolicy(seed=s).schedule(G, bins)
            rep = simulate(G, pl, bins, cost_model=model,
                           host_workers=host_workers)
            makespans.append(rep.makespan)
            serials.append(simulate(G, pl, bins, cost_model=serial_model,
                                    host_workers=host_workers).makespan)
            for i, u in rep.utilization.items():
                util_sum[i] += u
        n = len(makespans)
        return (sum(makespans) / n, sum(serials) / n,
                {i: u / n for i, u in util_sum.items()})
    G = ALL_SHAPES[shape]()
    kwargs = {"cost_model": model} if policy_name == "heft" else {}
    pl = get_scheduler(policy_name, **kwargs).schedule(G, bins)
    rep = simulate(G, pl, bins, cost_model=model, host_workers=host_workers)
    serial = simulate(G, pl, bins, cost_model=serial_model,
                      host_workers=host_workers).makespan
    return rep.makespan, serial, rep.utilization


def parse_bins(spec: str) -> list:
    """Build the bin list from ``--bins``.

    ``"3"`` → three simulated device bins (the legacy sweep).
    ``"mesh:2x2"`` → a synthetic 2×2 MeshBin slice plus two device bins
    — the mixed pool the ``sharded`` shape's capability-tagged kernels
    need (only the MeshBin may run them).
    ``"stage:4"`` → four StageBin pipeline-stage slots over a *mixed*
    member cycle (device / host / synthetic 1×1 mesh slice) — the pool
    the ``pipeline_staged`` shape's ``requires={"stage"}`` cells need;
    adds the scheduled-vs-pinned and 1F1B gate rows.
    """
    if spec.isdigit():
        return [f"d{i}" for i in range(int(spec))]
    if spec.startswith("mesh:"):
        dims = [int(x) for x in spec[5:].split("x") if x]
        if not dims or any(d < 1 for d in dims):
            raise ValueError(f"bad mesh shape in --bins {spec!r}")
        shape = {f"ax{i}": d for i, d in enumerate(dims)}
        return [MeshBin(f"{spec}[0]", shape), "d0", "d1"]
    if spec.startswith("stage:"):
        try:
            n = int(spec[6:])
        except ValueError:
            n = 0
        if n < 1:
            raise ValueError(f"bad stage count in --bins {spec!r}")
        members: list = []
        for i in range(n):
            if i % 3 == 1:
                members.append(HostBin(label=f"host{i}"))
            elif i % 3 == 2:
                members.append(MeshBin(f"m1x1[{i}]", {"ax0": 1}))
            else:
                members.append(f"d{i}")
        return stage_bins(members)
    raise ValueError(
        f"--bins must be an integer, mesh:NxM, or stage:N, got {spec!r}")


def budget_bins(bins: list, memory_bytes: int) -> list:
    """Give every bin a ``memory_bytes`` budget: execution bins get the
    attribute set in place, plain string/device bins are wrapped in a
    budgeted :class:`DeviceBin` (same label, so placements stay
    comparable)."""
    out = []
    for b in bins:
        if hasattr(b, "_set_memory_bytes"):
            b._set_memory_bytes(memory_bytes)
            out.append(b)
        else:
            out.append(DeviceBin(b, memory_bytes=memory_bytes))
    return out


def has_mesh_bin(bins: list) -> bool:
    return any(getattr(b, "kind", None) == "mesh" for b in bins)


def has_stage_bin(bins: list) -> bool:
    return any(getattr(b, "kind", None) == "stage" for b in bins)


def _device_bins(n: int) -> list:
    """``n`` executor bins over the devices present, round robin (bins
    share a device only when there are fewer devices than bins)."""
    import jax

    devs = jax.devices()
    return [devs[i % len(devs)] for i in range(n)]


def measure(policy_name: str, shape: str, n_bins: int, workers: int,
            ) -> tuple[float, float]:
    """Execute one cell on the real executor (one JAX-device bin per
    simulated bin), fit a CostModel from the recorded trace, and return
    (measured makespan, fitted-simulator prediction) in seconds —
    the profile → fit → predict loop, inline."""
    from repro.core import Executor
    from repro.sched import TaskProfiler

    bins = _device_bins(n_bins)
    prof = TaskProfiler()
    G = ALL_SHAPES[shape]()
    sched = get_scheduler(policy_name,
                          **({"seed": 0} if policy_name == "random" else {}))
    with Executor(num_workers=workers, devices=bins, scheduler=sched,
                  profiler=prof) as ex:
        ex.run(G).result(timeout=600)
    fitted = CostModel.fit(prof)
    # simulate over the per-slot LABELS, not the device objects: the n
    # bins may share a physical jax.Device, which an identity-keyed
    # placement would collapse onto one simulated bin.  bin_key carries
    # the slot in device_labels order — the same order fit() calibrated
    # device_speed in.
    placement = {n.id: n.bin_key for n in G.nodes if n.bin_key is not None}
    pred = simulate(G, placement, ex.device_labels, cost_model=fitted,
                    host_workers=workers).makespan
    return prof.makespan(), pred


def timeline_study(args, bins: list, out_path: str) -> None:
    """Export the per-bin lane timeline of one live executor run next
    to its replay-simulated twin (``--timeline``).

    Runs the gated policy's fanout cell on the real executor (one
    JAX-device bin per simulated bin), fits a ``CostModel`` from the
    recorded trace, replays the measured placement through the
    simulator, and writes one merged Perfetto-loadable Chrome-trace
    JSON — the measured process group first, the simulated one second.
    Prints ``timeline,...`` divergence rows (``repro.obs
    .diff_timelines``, the CostModel-calibration feedback signal — see
    docs/observability.md).  Additive by construction: the sweep rows
    and the ``--json`` payload never change.
    """
    from repro.core import Executor
    from repro.obs import (
        diff_timelines,
        merge_timelines,
        save_timeline,
        timeline_from_schedule,
        timeline_from_trace,
    )
    from repro.sched import TaskProfiler

    dev = _device_bins(len(bins))
    prof = TaskProfiler()
    G = ALL_SHAPES["fanout"]()
    with Executor(num_workers=args.measure_workers, devices=dev,
                  scheduler=get_scheduler(GATED_POLICY),
                  profiler=prof) as ex:
        ex.run(G).result(timeout=600)
    labels = list(ex.device_labels)
    fitted = CostModel.fit(prof)
    # replay over the per-slot labels, same reasoning as measure()
    placement = {n.id: n.bin_key for n in G.nodes if n.bin_key is not None}
    rep = simulate(G, placement, labels, cost_model=fitted,
                   host_workers=args.measure_workers)
    measured = timeline_from_trace(prof)
    simulated = timeline_from_schedule(rep, labels, graph=G)
    diff = diff_timelines(measured, simulated)
    save_timeline(merge_timelines(measured, simulated), out_path)
    print("timeline,bin,measured_busy_ms,sim_busy_ms,divergence")
    for row in diff["bins"]:
        print(f"timeline,{row['bin']},{row['measured_busy_s'] * 1e3:.4f},"
              f"{row['simulated_busy_s'] * 1e3:.4f},"
              f"{row['divergence']:.3f}")
    mk = diff["makespan"]
    print(f"timeline,makespan,{mk['measured_s'] * 1e3:.4f},"
          f"{mk['simulated_s'] * 1e3:.4f},{mk['divergence']:.3f}")
    print(f"timeline,{out_path}")


def parse_arrival(spec: str):
    """Parse ``--arrival``: ``poisson:RATE`` (requests/second) → a
    deterministic :func:`~repro.sched.poisson` arrival process."""
    if spec.startswith("poisson:"):
        try:
            rate = float(spec.split(":", 1)[1])
        except ValueError:
            rate = 0.0
        if rate <= 0:
            raise ValueError(f"--arrival rate must be > 0, got {spec!r}")
        return poisson(rate, seed=1)
    raise ValueError(f"--arrival must be poisson:RATE, got {spec!r}")


def serving_study(args, bins_spec: str, policies: list[str],
                  model: CostModel) -> tuple[dict, bool]:
    """Live-traffic serving study (``--arrival``): replay a Poisson
    request trace through the event-driven :meth:`Scheduler.update`
    loop (one :class:`SchedulerUpdate` per arriving request, no global
    graph) and score per-request TTFT p50/p99 + completion p99, next to
    the static-batching strawman (fixed batches admitted only after the
    previous batch fully completes).  The gate row requires the gated
    policy's online p99 TTFT to beat — or tie — static batching.

    Returns ``(payload_section, gate_ok)``.
    """
    arrival = parse_arrival(args.arrival)
    specs = serving_specs(args.requests)
    times = arrival.times(len(specs))

    def fresh_bins() -> list:
        b = parse_bins(bins_spec)
        return budget_bins(b, args.memory_bytes) if args.memory_bytes else b

    def stats(rows: list[dict[str, float]]) -> tuple[float, float, float]:
        ttft = [r["ttft"] for r in rows]
        comp = [r["complete"] for r in rows]
        return (percentile(ttft, 50), percentile(ttft, 99),
                percentile(comp, 99))

    out = {"arrival": args.arrival, "requests": args.requests,
           "batch_size": args.serving_batch, "online": {},
           "static_batching": {}}
    print("serving,mode,policy,ttft_p50_ms,ttft_p99_ms,complete_p99_ms")
    # the gate needs the gated policy even when --policies excludes it
    online_pols = list(dict.fromkeys(list(policies) + [GATED_POLICY]))
    for pol in online_pols:
        kwargs = {"cost_model": model} if pol == "heft" else {}
        if pol == "random":
            kwargs["seed"] = 0
        sched = get_scheduler(pol, **kwargs)
        rep = online_report(build_serving_trace(specs), fresh_bins(),
                            sched, times, cost_model=model,
                            host_workers=args.host_workers)
        p50, p99, c99 = stats(rep.request_latency)
        out["online"][pol] = {"ttft_p50_s": p50, "ttft_p99_s": p99,
                              "complete_p99_s": c99}
        print(f"serving,online,{pol},{p50 * 1e3:.4f},{p99 * 1e3:.4f},"
              f"{c99 * 1e3:.4f}", flush=True)
    rows = static_batching_latency(
        specs, times, build_serving_trace, fresh_bins, GATED_POLICY,
        batch_size=args.serving_batch, cost_model=model,
        host_workers=args.host_workers)
    s50, s99, sc99 = stats(rows)
    out["static_batching"][GATED_POLICY] = {
        "ttft_p50_s": s50, "ttft_p99_s": s99, "complete_p99_s": sc99}
    print(f"serving,static_batching,{GATED_POLICY},{s50 * 1e3:.4f},"
          f"{s99 * 1e3:.4f},{sc99 * 1e3:.4f}")
    online_p99 = out["online"][GATED_POLICY]["ttft_p99_s"]
    good = online_p99 <= s99 * (1 + 1e-9)
    print(f"check,online_p99_ttft_not_worse_than_static,"
          f"{'PASS' if good else 'FAIL'},"
          f"online_p99={online_p99 * 1e3:.4f}ms,"
          f"static_p99={s99 * 1e3:.4f}ms")
    return out, good


def results_payload(args, results: dict[tuple[str, str], float],
                    utils: dict[tuple[str, str], float]) -> dict:
    """Machine-readable sweep outcome (the --json artifact / baseline)."""
    makespan_s: dict[str, dict[str, float]] = {}
    mean_util: dict[str, dict[str, float]] = {}
    for (shape, pol), ms in results.items():
        makespan_s.setdefault(shape, {})[pol] = ms
        mean_util.setdefault(shape, {})[pol] = utils[(shape, pol)]
    return {
        "version": 2,
        "bins": args.bins,
        "speeds": list(args.parsed_speeds),
        "host_workers": args.host_workers,
        "lane_depth": args.lane_depth,
        "random_seeds": args.random_seeds,
        "collective_alpha": args.collective_alpha,
        "collective_beta": args.collective_beta,
        "memory_bytes": args.memory_bytes,
        "chaos": args.chaos or "",
        "makespan_s": makespan_s,
        "mean_util": mean_util,
    }


def check_baseline(payload: dict, baseline: dict, *,
                   policy: str = GATED_POLICY,
                   rtol: float = REGRESSION_RTOL) -> list[str]:
    """Compare ``policy``'s simulated makespans against a baseline.

    Returns a list of human-readable failures (empty = gate passes):
    per-shape regressions beyond ``rtol``, plus configuration mismatches
    that would make the comparison meaningless.
    """
    failures: list[str] = []
    for knob in ("bins", "speeds", "host_workers", "lane_depth"):
        if baseline.get(knob) != payload.get(knob):
            failures.append(
                f"config mismatch on {knob!r}: baseline "
                f"{baseline.get(knob)!r} vs run {payload.get(knob)!r} "
                f"(re-run with matching flags or refresh the baseline)")
    for knob in ("collective_alpha", "collective_beta", "memory_bytes"):
        # older baselines lack the keys: absent means 0 / 0.0 (off)
        if baseline.get(knob, 0.0) != payload.get(knob, 0.0):
            failures.append(
                f"config mismatch on {knob!r}: baseline "
                f"{baseline.get(knob, 0.0)!r} vs run "
                f"{payload.get(knob, 0.0)!r}")
    # the chaos study is additive (the sweep rows never see faults) but
    # the knob is recorded, so a baseline refreshed under --chaos stays
    # visibly distinct; absent means "" (off) for older baselines
    if baseline.get("chaos", "") != payload.get("chaos", ""):
        failures.append(
            f"config mismatch on 'chaos': baseline "
            f"{baseline.get('chaos', '')!r} vs run "
            f"{payload.get('chaos', '')!r}")
    base_ms = baseline.get("makespan_s", {})
    cur_ms = payload.get("makespan_s", {})
    for shape, policies in sorted(base_ms.items()):
        if policy not in policies:
            continue
        base = policies[policy]
        cur = cur_ms.get(shape, {}).get(policy)
        if cur is None:
            failures.append(f"{shape}: no {policy} result in this run "
                            f"(baseline has {base:.6g}s)")
            continue
        if cur > base * (1.0 + rtol):
            failures.append(
                f"{shape}: {policy} makespan regressed "
                f"{cur * 1e3:.4f}ms vs baseline {base * 1e3:.4f}ms "
                f"(+{(cur / base - 1.0) * 100:.1f}% > {rtol * 100:.0f}% "
                f"tolerance)")
    return failures


def exact_baseline_gate(name: str, payload: dict) -> bool:
    """Print one ``check,<name>`` row requiring the gated policy's
    makespans to equal the checked-in default baseline EXACTLY (``==``,
    not within tolerance) — the bit-identical claim a knob makes when
    it is off.  Config mismatches make the comparison meaningless, so
    they only WARN (returns True: advisory, not a failure)."""
    try:
        with open(DEFAULT_BASELINE) as f:
            base = json.load(f)
    except (OSError, ValueError) as e:
        print(f"check,{name},WARN,unreadable baseline: {e}")
        return True
    mismatch = [k for k in ("bins", "speeds", "host_workers", "lane_depth")
                if base.get(k) != payload.get(k)]
    mismatch += [k for k in ("collective_alpha", "collective_beta",
                             "memory_bytes")
                 if base.get(k, 0.0) != payload.get(k, 0.0)]
    # absent means "" (off): the chaos study never perturbs the sweep
    # rows, but a baseline refreshed under --chaos should downgrade the
    # exactness claim to a config WARN
    mismatch += ["chaos"] if (base.get("chaos", "")
                              != payload.get("chaos", "")) else []
    if mismatch:
        print(f"check,{name},WARN,config mismatch on {mismatch}")
        return True
    bad = []
    for shape, pols in sorted(base.get("makespan_s", {}).items()):
        if GATED_POLICY not in pols:
            continue
        cur = payload["makespan_s"].get(shape, {}).get(GATED_POLICY)
        if cur is not None and cur != pols[GATED_POLICY]:
            bad.append((shape, cur, pols[GATED_POLICY]))
    good = not bad
    detail = ";".join(f"{s}:run={c!r},baseline={b!r}"
                      for s, c, b in bad) or DEFAULT_BASELINE
    print(f"check,{name},{'PASS' if good else 'FAIL'},{detail}")
    return good


def timing_study(args, p) -> int:
    """Million-task throughput study (``--shape timing``).

    Builds the paper's propagation DAG at ``--nodes`` cells and measures
    the scheduling *pipeline's* throughput, not simulated makespan:

    * grouping rate (``build_groups``, the affinity phase alone);
    * ``tasks_placed_per_sec`` of the hierarchical path (grouping →
      ``coarsen`` → windowed HEFT → expansion, end to end) at full
      scale, against the uncoarsened whole-graph HEFT baseline at
      ``min(nodes, 10^4)`` cells — the in-run ratio is the gate, so the
      number is machine-relative and CI-stable;
    * placement-quality and fused-dispatch context rows at small scale
      (simulated makespans; ``dispatch_overhead_us`` is the measured
      makespan inflation per task under a 5 µs per-dispatch charge,
      fused vs unfused).

    Hard gates: ``coarse_off_bit_identical`` always; the 10× throughput
    gate only at >= 10^5 cells (below that the coarse path has nothing
    to amortize — smaller runs print the ratio as an advisory row).
    ``--grouping-only`` stops after the grouping rate (the CI smoke
    mode).  Rates count placed *nodes* (pulls + kernels) per second.
    """
    import gc

    from repro.sched import build_groups, hierarchical_schedule

    if args.nodes < 100:
        p.error(f"--nodes must be >= 100, got {args.nodes}")
    if args.fanout < 0:
        p.error(f"--fanout must be >= 0, got {args.fanout}")
    spec = str(args.bins)
    if spec == p.get_default("bins"):
        nbins = 32          # scheduler-study scale (the HEFT-literature
        #                     norm; the coarse advantage is O(bins) vs
        #                     O(nodes x bins), so report it at scale)
    elif spec.isdigit():
        nbins = int(spec)
    else:
        p.error(f"--shape timing needs an integer --bins, got {spec!r}")
    bins = [f"d{i}" for i in range(nbins)]
    n, fanout = args.nodes, args.fanout
    perf = time.perf_counter

    t0 = perf()
    G = build_timing_graph(n, fanout=fanout)
    t_build = perf() - t0
    # GC pauses are comparable to the measured sections at this
    # allocation volume; park it around every timed region
    gc.disable()
    try:
        t0 = perf()
        groups = build_groups(G)
        t_group = perf() - t0
    finally:
        gc.enable()
    rows: dict[str, object] = {
        "nodes": n, "fanout": fanout, "bins": nbins,
        "grouping_only": bool(args.grouping_only),
        "graph_build_s": t_build, "grouping_s": t_group,
        "groups_per_sec": len(groups) / t_group,
    }
    print("study,metric,value")
    print(f"study,nodes,{n}")
    print(f"study,bins,{nbins}")
    print(f"study,graph_build_s,{t_build:.3f}")
    print(f"study,grouping_s,{t_group:.3f}")
    print(f"study,groups_per_sec,{len(groups) / t_group:,.0f}")

    ok = True
    if not args.grouping_only:
        target, window = args.coarsen_target, args.window
        base_n = min(n, 10_000)
        Gb = build_timing_graph(base_n, fanout=fanout)
        gc.disable()
        try:
            t0 = perf()
            pl_plain = get_scheduler(GATED_POLICY).schedule(Gb, bins)
            t_r1 = perf() - t0
            t0 = perf()
            pl_h = hierarchical_schedule(G, bins, policy=GATED_POLICY,
                                         target=target, window=window)
            t_r2 = perf() - t0
        finally:
            gc.enable()
        r1 = len(pl_plain) / t_r1
        r2 = len(pl_h) / t_r2
        ratio = r2 / r1
        rows.update({
            "coarsen_target": target, "window": window,
            "baseline_nodes": base_n,
            "baseline_tasks_per_sec": r1,
            "tasks_placed_per_sec": r2,
            "coarse_speedup": ratio,
        })
        print(f"study,baseline_tasks_per_sec,{r1:,.0f}")
        print(f"study,tasks_placed_per_sec,{r2:,.0f}")
        print(f"study,coarse_speedup,{ratio:.2f}x")
        complete = len(pl_h) == len(G.nodes)
        ok &= complete
        print(f"check,coarse_places_all_nodes,"
              f"{'PASS' if complete else 'FAIL'},"
              f"placed={len(pl_h)},nodes={len(G.nodes)}")
        if n >= 100_000:
            good = ratio >= 10.0
            ok &= good
            print(f"check,coarse_throughput_10x,"
                  f"{'PASS' if good else 'FAIL'},"
                  f"hierarchical={r2:,.0f}/s,baseline={r1:,.0f}/s,"
                  f"ratio={ratio:.2f}x")

        # default-off bit-identity: the hierarchical entry point with
        # both knobs at 0 must be the plain scheduler, placement for
        # placement (same discipline as budgets_off_bit_identical)
        pl_off = hierarchical_schedule(Gb, bins, policy=GATED_POLICY)
        same = pl_off == pl_plain
        ok &= same
        print(f"check,coarse_off_bit_identical,"
              f"{'PASS' if same else 'FAIL'},nodes={base_n}")

        # placement quality at baseline scale: simulate the exact and
        # the coarse placement under the default model (advisory — the
        # coarse path trades quality for throughput by design)
        model = CostModel()
        bt = max(2, target * base_n // max(n, 1))
        pl_hb = hierarchical_schedule(Gb, bins, policy=GATED_POLICY,
                                      target=bt, window=window)
        ms_exact = simulate(Gb, pl_plain, bins, cost_model=model).makespan
        ms_coarse = simulate(Gb, pl_hb, bins, cost_model=model).makespan
        rows.update({
            "makespan_exact_s": ms_exact,
            "makespan_coarse_s": ms_coarse,
            "coarse_makespan_ratio": (ms_coarse / ms_exact
                                      if ms_exact > 0 else 1.0),
        })
        print(f"study,makespan_exact_ms,{ms_exact * 1e3:.4f}")
        print(f"study,makespan_coarse_ms,{ms_coarse * 1e3:.4f}")

        # fused batch dispatch: the simulator charges a 5 us per-unit
        # dispatch cost; fusing runs of <=16 same-bin tasks must recover
        # most of it (Executor(fuse_batch=N) mirrors this charging)
        ov = 5e-6
        Gd = build_timing_graph(2_000, fanout=fanout)
        pl_d = get_scheduler(GATED_POLICY).schedule(Gd, bins)
        nd = len(Gd.nodes)
        m_ov = CostModel(dispatch_overhead_s=ov)
        ms0 = simulate(Gd, pl_d, bins, cost_model=model).makespan
        msu = simulate(Gd, pl_d, bins, cost_model=m_ov).makespan
        msf = simulate(Gd, pl_d, bins, cost_model=m_ov,
                       fuse_batch=16).makespan
        ou = (msu - ms0) / nd * 1e6
        of = (msf - ms0) / nd * 1e6
        rows.update({
            "dispatch_overhead_s": ov,
            "dispatch_overhead_us": ou,
            "dispatch_overhead_us_fused": of,
        })
        print(f"study,dispatch_overhead_us,{ou:.3f}")
        print(f"study,dispatch_overhead_us_fused,{of:.3f}")
        good = msf < msu
        ok &= good
        print(f"check,fused_dispatch_cheaper,"
              f"{'PASS' if good else 'FAIL'},"
              f"fused={msf * 1e3:.4f}ms,unfused={msu * 1e3:.4f}ms")

    if args.json:
        payload = {"version": 2, "study": "timing", "timing_study": rows}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"json,{args.json}")
    return 0 if ok else 1


def chaos_study(args, bins: list, shapes: list[str], policies: list[str],
                model: CostModel) -> bool:
    """Fault-injected twin study (``--chaos``): replay every plain-shape
    cell under a seeded :class:`ChaosPlan` and gate graceful recovery.

    Additive by construction — the main sweep's ``results`` (and every
    baseline comparison built from them) is computed before this runs
    and never touched; the study only prints its own ``chaos,...`` rows
    plus two gate rows:

    * ``chaos_completes_all_tasks`` — every faulted run still finishes
      every task (the lost frontier was re-executed, not dropped);
    * ``chaos_makespan_degrades_gracefully`` — the gated policy's
      faulted makespan stays within 2x of a no-fault run on the
      SURVIVING bins (scaled by the slowdown factor for slow specs).
    """
    ok = True
    eligible = [s for s in shapes if s in SHAPES]
    incomplete: list[str] = []
    ungraceful: list[str] = []
    cells = 0
    n_reexec_total = 0
    print("chaos,shape,policy,nofault_ms,faulted_ms,reexecuted,recovery_ms")
    for shape in eligible:
        for pol in policies:
            G = ALL_SHAPES[shape]()
            if pol == "random":
                pl = RandomPolicy(seed=0).schedule(G, bins)
            else:
                kw = {"cost_model": model} if pol == "heft" else {}
                pl = get_scheduler(pol, **kw).schedule(G, bins)
            ref = simulate(G, pl, bins, cost_model=model,
                           host_workers=args.host_workers)
            plan = ChaosPlan.plan(args.chaos, n_tasks=len(G),
                                  n_bins=len(bins), seed=0)
            fs = plan.fault_schedule(G, pl, bins, cost_model=model,
                                     host_workers=args.host_workers)
            rep = simulate(G, pl, bins, cost_model=model,
                           host_workers=args.host_workers, faults=fs)
            cells += 1
            n_reexec_total += rep.n_reexecuted
            print(f"chaos,{shape},{pol},{ref.makespan * 1e3:.4f},"
                  f"{rep.makespan * 1e3:.4f},{rep.n_reexecuted},"
                  f"{rep.recovery_seconds * 1e3:.4f}", flush=True)
            if len(rep.finish_times) != len(G):
                incomplete.append(f"{shape}/{pol}")
            if pol != GATED_POLICY:
                continue
            # graceful-degradation bound: the same policy, no faults,
            # on the pool that survives the kills
            killed = {e.bin for e in plan.events if e.action == "kill"}
            survivors = [b for i, b in enumerate(bins) if i not in killed]
            G2 = ALL_SHAPES[shape]()
            pl2 = get_scheduler(GATED_POLICY,
                                cost_model=model).schedule(G2, survivors)
            ms_surv = simulate(G2, pl2, survivors, cost_model=model,
                               host_workers=args.host_workers).makespan
            slow = max((e.factor for e in plan.events
                        if e.action == "slow"), default=1.0)
            bound = 2.0 * max(slow, 1.0) * ms_surv
            if rep.makespan > bound * (1 + 1e-9):
                ungraceful.append(
                    f"{shape}:faulted={rep.makespan * 1e3:.4f}ms,"
                    f"bound={bound * 1e3:.4f}ms")
    good = not incomplete
    ok &= good
    print(f"check,chaos_completes_all_tasks,{'PASS' if good else 'FAIL'},"
          + (";".join(incomplete)
             or f"cells={cells},reexecuted={n_reexec_total}"))
    if GATED_POLICY in policies and eligible:
        good = not ungraceful
        ok &= good
        print(f"check,chaos_makespan_degrades_gracefully,"
              f"{'PASS' if good else 'FAIL'},"
              + (";".join(ungraceful)
                 or f"bound=2x_nofault_{GATED_POLICY}_on_survivors"))
    return ok


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--bins", default="3",
                   help="simulated device bin count, or mesh:NxM for a "
                        "mixed pool of one NxM mesh-slice bin plus two "
                        "device bins (adds the 'sharded' shape whose "
                        "capability-tagged kernels only MeshBins may run)")
    p.add_argument("--speeds",
                   default=",".join(str(s) for s in DEFAULT_SCHED.device_speed),
                   help="comma-separated relative speed per bin "
                        "(e.g. 1.0,0.5,0.5); empty = homogeneous")
    p.add_argument("--shapes", default=",".join(SHAPES),
                   help=f"subset of {sorted(SHAPES)}")
    p.add_argument("--policies", default=",".join(POLICIES))
    p.add_argument("--random-seeds", type=int, default=5)
    p.add_argument("--host-workers", type=int,
                   default=DEFAULT_SCHED.host_workers,
                   help="simulated worker-pool concurrency")
    p.add_argument("--lane-depth", type=int, default=DEFAULT_LANE_DEPTH,
                   help="per-bin in-flight ops: >=2 overlaps the copy "
                        "lane with the compute lane (default), 1 "
                        "serializes each bin")
    p.add_argument("--collective-alpha", type=float,
                   default=DEFAULT_SCHED.collective_alpha,
                   help="ring-collective latency (s) per hop charged on "
                        "mesh-wide compute — non-ideal sharded scaling; "
                        "0 (default) keeps the ideal linear model")
    p.add_argument("--collective-beta", type=float,
                   default=DEFAULT_SCHED.collective_beta,
                   help="ring-collective per-link bandwidth (bytes/s) "
                        "for the bytes term; 0 (default) = off")
    p.add_argument("--memory-bytes", type=int,
                   default=DEFAULT_SCHED.memory_bytes,
                   help="per-bin memory budget in bytes: policies pack "
                        "group footprints against it and the simulator "
                        "charges forced spills for overflow; 0 (default) "
                        "= unlimited, baseline-identical")
    p.add_argument("--arrival", metavar="SPEC",
                   help="serving study under live traffic: poisson:RATE "
                        "(requests/s) replays a request trace through the "
                        "event-driven Scheduler.update() loop and gates "
                        "the gated policy's p99 TTFT against static "
                        "batching; off by default (baseline rows are "
                        "untouched either way)")
    p.add_argument("--requests", type=int, default=80,
                   help="request count for the --arrival serving study")
    p.add_argument("--serving-batch", type=int, default=8,
                   help="batch size of the static-batching strawman in "
                        "the --arrival serving study")
    p.add_argument("--chaos", metavar="SPEC",
                   help="fault-injected twin study: kill:N (kill N "
                        "seeded-random bins at task-count triggers, "
                        "N < bin count) or slow:BIN:FACTOR (stretch one "
                        "bin's service times mid-run); re-simulates every "
                        "plain-shape cell under the faults and gates "
                        "completion + graceful degradation; off by "
                        "default (baseline rows are untouched either way)")
    p.add_argument("--timeline", metavar="PATH",
                   help="export a Perfetto-loadable Chrome-trace JSON: "
                        "one live executor run of the gated policy's "
                        "fanout cell (per-bin copy/compute lane rows) "
                        "merged with its replay-simulated twin, plus "
                        "timeline,... divergence rows; off by default "
                        "(sweep rows and --json payload are untouched "
                        "either way)")
    p.add_argument("--measure", action="store_true",
                   help="also run every cell on the real executor, fit "
                        "a CostModel from its trace, and report measured "
                        "wall-clock + fitted-simulator divergence")
    p.add_argument("--measure-workers", type=int, default=2,
                   help="executor workers for --measure runs")
    p.add_argument("--json", metavar="PATH",
                   help="write the sweep results as JSON (CI artifact)")
    p.add_argument("--check-baseline", nargs="?", metavar="PATH",
                   const=DEFAULT_BASELINE, default=None,
                   help="fail (exit 1) if the gated policy's makespan "
                        f"regressed >{REGRESSION_RTOL:.0%} vs the baseline "
                        "JSON (default: benchmarks/baselines/"
                        "sched_baseline.json)")
    p.add_argument("--write-baseline", metavar="PATH",
                   help="write the gated policy's makespans as a new "
                        "baseline JSON and exit")
    p.add_argument("--shape", choices=("timing",),
                   help="run a single-shape scale study INSTEAD of the "
                        "sweep (only 'timing': the propagation DAG at "
                        "--nodes cells, measuring scheduling throughput "
                        "of the coarsened windowed-HEFT path)")
    p.add_argument("--nodes", type=int, default=100_000,
                   help="cell count for --shape timing (the 10x "
                        "throughput gate only arms at >= 100000)")
    p.add_argument("--fanout", type=int, default=4,
                   help="max fan-in per cell for --shape timing")
    p.add_argument("--grouping-only", action="store_true",
                   help="with --shape timing: stop after the affinity "
                        "grouping rate (the fast CI smoke mode)")
    p.add_argument("--coarsen-target", type=int, default=2_000,
                   help="super-group count for the coarse path")
    p.add_argument("--window", type=int, default=256,
                   help="windowed-HEFT window (groups per rank/place "
                        "round) for the coarse path")
    args = p.parse_args(argv)

    if args.shape:
        return timing_study(args, p)

    try:
        args.parsed_speeds = (tuple(float(s) for s in args.speeds.split(","))
                              if args.speeds else ())
    except ValueError:
        p.error(f"--speeds must be comma-separated floats, got {args.speeds!r}")
    if args.memory_bytes < 0:
        p.error(f"--memory-bytes must be >= 0, got {args.memory_bytes}")
    bins_spec = args.bins
    try:
        bins = parse_bins(args.bins)
    except ValueError as e:
        p.error(str(e))
    if args.memory_bytes:
        bins = budget_bins(bins, args.memory_bytes)
    if args.chaos:
        try:   # validate spec + victim bounds up front, not mid-study
            ChaosPlan.plan(args.chaos, n_tasks=max(2, len(bins)),
                           n_bins=len(bins), seed=0)
        except ValueError as e:
            p.error(str(e))
    mesh = has_mesh_bin(bins)
    staged = has_stage_bin(bins)
    if (args.measure or args.timeline) and (mesh or staged):
        p.error("--measure/--timeline run on real JAX devices; mesh:NxM "
                "and stage:N bins are simulator-only")
    model = CostModel(device_speed=args.parsed_speeds,
                      lane_depth=args.lane_depth,
                      collective_alpha=args.collective_alpha,
                      collective_beta=args.collective_beta)
    shapes = [s for s in args.shapes.split(",") if s]
    if mesh and args.shapes == p.get_default("shapes"):
        shapes.append("sharded")        # the mesh pool's signature shape
    if staged and args.shapes == p.get_default("shapes"):
        shapes.append("pipeline_staged")  # the stage pool's signature shape
    bad_shapes = [s for s in shapes if s in MESH_SHAPES and not mesh]
    if bad_shapes:
        p.error(f"shapes {bad_shapes} carry mesh-tagged kernels; run "
                f"them with --bins mesh:NxM")
    bad_shapes = [s for s in shapes if s in STAGE_SHAPES and not staged]
    if bad_shapes:
        p.error(f"shapes {bad_shapes} carry stage-tagged kernels; run "
                f"them with --bins stage:N")
    policies = [s for s in args.policies.split(",") if s]

    results: dict[tuple[str, str], float] = {}
    serials: dict[tuple[str, str], float] = {}
    utils: dict[tuple[str, str], float] = {}
    header = "shape,policy,makespan_ms,serial_ms,overlap_gain,mean_util,per_bin_util"
    if args.measure:
        header += ",measured_ms,fitted_sim_ms,divergence"
    print(header)
    for shape in shapes:
        for pol in policies:
            ms, serial, util = score(pol, shape, bins, model,
                                     args.random_seeds, args.host_workers)
            results[(shape, pol)] = ms
            serials[(shape, pol)] = serial
            utils[(shape, pol)] = sum(util.values()) / len(util)
            per_bin = "/".join(f"{util[i]:.2f}" for i in sorted(util))
            gain = 1.0 - ms / serial if serial > 0 else 0.0
            row = (f"{shape},{pol},{ms * 1e3:.4f},{serial * 1e3:.4f},"
                   f"{gain:+.3f},{utils[(shape, pol)]:.3f},{per_bin}")
            if args.measure:
                wall, pred = measure(pol, shape, len(bins),
                                     args.measure_workers)
                div = (pred - wall) / wall if wall > 0 else 0.0
                row += (f",{wall * 1e3:.4f},{pred * 1e3:.4f},{div:+.3f}")
            print(row, flush=True)

    serving_payload, serving_ok = None, True
    if args.arrival:
        if args.requests < 1:
            p.error(f"--requests must be >= 1, got {args.requests}")
        if args.serving_batch < 1:
            p.error(f"--serving-batch must be >= 1, got {args.serving_batch}")
        try:
            serving_payload, serving_ok = serving_study(
                args, bins_spec, policies, model)
        except ValueError as e:
            p.error(str(e))

    chaos_ok = True
    if args.chaos:
        chaos_ok = chaos_study(args, bins, shapes, policies, model)

    if args.timeline:
        timeline_study(args, bins, args.timeline)

    # baseline payloads keep the legacy integer bin count; mesh pools
    # record their spec string (config mismatch vs an int baseline is
    # exactly right — the sweeps are not comparable)
    args.bins = int(args.bins) if args.bins.isdigit() else args.bins
    payload = results_payload(args, results, utils)
    if serving_payload is not None:
        # additive section: baseline comparisons only read the sweep
        # keys, so --arrival runs stay comparable with no-arrival ones
        payload["serving"] = serving_payload
    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"json,{args.json}")
    if args.write_baseline:
        os.makedirs(os.path.dirname(args.write_baseline) or ".",
                    exist_ok=True)
        baseline = {k: payload[k] for k in
                    ("version", "bins", "speeds", "host_workers",
                     "lane_depth", "collective_alpha", "collective_beta",
                     "memory_bytes", "chaos")}
        baseline["makespan_s"] = {
            shape: {GATED_POLICY: pols[GATED_POLICY]}
            for shape, pols in payload["makespan_s"].items()
            if GATED_POLICY in pols}
        with open(args.write_baseline, "w") as f:
            json.dump(baseline, f, indent=1)
        print(f"baseline,{args.write_baseline}")
        return 0

    ok = serving_ok and chaos_ok
    for shape in ("fanout", "diamond"):
        if ("heft" in policies and "random" in policies and shape in shapes):
            h, r = results[(shape, "heft")], results[(shape, "random")]
            # a single bin has no placement freedom: equality is correct
            good = h < r if len(bins) > 1 else h <= r
            verdict = "PASS" if good else "FAIL"
            ok &= good
            print(f"check,heft_beats_random_{shape},{verdict},"
                  f"heft={h * 1e3:.4f}ms,random={r * 1e3:.4f}ms")
    if mesh and "sharded" in shapes and "heft" in policies:
        from repro.sched import build_groups

        # capability eligibility: every mesh-tagged group on a MeshBin
        G = ALL_SHAPES["sharded"]()
        pl = get_scheduler("heft", cost_model=model).schedule(G, bins)
        tagged = [g for g in build_groups(G) if "mesh" in g.requires]
        placed_ok = bool(tagged) and all(
            getattr(pl[g.nodes[0].id], "kind", None) == "mesh"
            for g in tagged)
        ok &= placed_ok
        print(f"check,mesh_tagged_only_on_mesh_bins,"
              f"{'PASS' if placed_ok else 'FAIL'},tagged_groups={len(tagged)}")
        # slice advantage: the NxM slice must beat (or tie) the same
        # pool with a single-device slice — HEFT exploiting the mesh
        single = [MeshBin("mesh:1x1[0]", {"ax0": 1}), "d0", "d1"]
        G1 = ALL_SHAPES["sharded"]()
        pl1 = get_scheduler("heft", cost_model=model).schedule(G1, single)
        ms_single = simulate(G1, pl1, single, cost_model=model,
                             host_workers=args.host_workers).makespan
        ms_mesh = results[("sharded", "heft")]
        good = ms_mesh <= ms_single * (1 + 1e-9)
        # only an invariant under IDEAL scaling: with the α-β collective
        # overhead on, a wider slice may legitimately lose (that is the
        # point of the non-ideal model) — advisory there, hard otherwise
        ideal = not (args.collective_alpha or args.collective_beta)
        if good:
            verdict = "PASS"
        elif ideal:
            verdict = "FAIL"
            ok = False
        else:
            verdict = "WARN"
        print(f"check,mesh_slice_not_worse_than_single_device,{verdict},"
              f"slice={ms_mesh * 1e3:.4f}ms,single={ms_single * 1e3:.4f}ms")
    if staged and "pipeline_staged" in shapes and "heft" in policies:
        import re as _re

        from repro.distributed.pipeline import pinned_placement

        # scheduled-vs-pinned parity: HEFT freely placing stage groups
        # over the StageBin pool must never lose to the historical
        # hand-pinning (stage s → bin s) it replaced
        G = ALL_SHAPES["pipeline_staged"]()
        pl = get_scheduler("heft", cost_model=model).schedule(G, bins)
        rep = simulate(G, pl, bins, cost_model=model,
                       host_workers=args.host_workers)
        Gp = ALL_SHAPES["pipeline_staged"]()
        rep_pin = simulate(Gp, pinned_placement(Gp, bins), bins,
                           cost_model=model,
                           host_workers=args.host_workers)
        good = rep.makespan <= rep_pin.makespan * (1 + 1e-9)
        ok &= good
        print(f"check,scheduled_pipeline_not_worse_than_pinned,"
              f"{'PASS' if good else 'FAIL'},"
              f"scheduled={rep.makespan * 1e3:.4f}ms,"
              f"pinned={rep_pin.makespan * 1e3:.4f}ms")
        # 1F1B fill/drain: each stage runs its cells in microbatch
        # order, and adjacent stages overlap in time — the pipelined
        # interleaving the graph's dependency structure promises
        names = {n.id: n.name for n in G.nodes}
        cells: dict[tuple[int, int], tuple[float, float]] = {}
        for nid, _lane, _b, t0, t1 in rep.schedule:
            cell = _re.fullmatch(r"f\[(\d+),(\d+)\]", names.get(nid, ""))
            if cell:
                cells[(int(cell.group(1)), int(cell.group(2)))] = (t0, t1)
        stages_n = 1 + max(s for s, _ in cells)
        mbs_n = 1 + max(m for _, m in cells)
        ordered = all(cells[(s, m)][0] <= cells[(s, m + 1)][0]
                      for s in range(stages_n) for m in range(mbs_n - 1))
        overlap = any(
            cells[(s, m1)][0] < cells[(s + 1, m2)][1]
            and cells[(s + 1, m2)][0] < cells[(s, m1)][1]
            for s in range(stages_n - 1)
            for m1 in range(mbs_n) for m2 in range(mbs_n))
        good = ordered and (overlap or len(bins) == 1)
        ok &= good
        print(f"check,pipeline_1f1b_interleaving,"
              f"{'PASS' if good else 'FAIL'},"
              f"ordered={ordered},adjacent_overlap={overlap}")
    if args.lane_depth >= 2:
        # stream overlap must never hurt on these shapes (test_sched.py
        # pins the same condition).  The hard gate applies only to the
        # DEFAULT sweep config, whose cells were verified anomaly-free;
        # custom --bins/--speeds/--host-workers sweeps can legitimately
        # hit Graham list-scheduling anomalies, so there the row is
        # advisory (WARN) and does not flip the exit code.
        default_cfg = all(
            str(getattr(args, k)) == str(p.get_default(k))
            for k in ("bins", "speeds", "host_workers", "lane_depth",
                      "random_seeds"))
        bad = [(s, p_) for (s, p_), ms in results.items()
               if ms > serials[(s, p_)] * (1 + 1e-9)]
        if not bad:
            verdict = "PASS"
        elif default_cfg:
            verdict = f"FAIL,{bad}"
            ok = False
        else:
            verdict = f"WARN,{bad}"
        print(f"check,overlap_not_worse_than_serialized,{verdict}")
    if args.memory_bytes and GATED_POLICY in policies:
        # budgeted vs unbudgeted: forced spills must cost bounded time,
        # not blow the makespan up pathologically.  Re-score the gated
        # policy on the same pool WITHOUT budgets and require every
        # capped cell to stay within 2x of its uncapped twin.
        plain = parse_bins(bins_spec)
        bad = []
        for shape in shapes:
            if (shape, GATED_POLICY) not in results:
                continue
            ms_u, _, _ = score(GATED_POLICY, shape, plain, model,
                               args.random_seeds, args.host_workers)
            ms_c = results[(shape, GATED_POLICY)]
            if ms_c > 2.0 * ms_u * (1 + 1e-9):
                bad.append((shape, ms_c, ms_u))
        good = not bad
        ok &= good
        detail = ";".join(
            f"{s}:capped={c * 1e3:.4f}ms,uncapped={u * 1e3:.4f}ms"
            for s, c, u in bad) or f"budget={args.memory_bytes}B"
        print(f"check,memory_capped_not_worse_than_2x_uncapped,"
              f"{'PASS' if good else 'FAIL'},{detail}")
    if not args.memory_bytes and GATED_POLICY in policies:
        # budgets off must be the legacy scheduler byte for byte
        ok &= exact_baseline_gate("budgets_off_bit_identical", payload)
    if not args.timeline and GATED_POLICY in policies:
        # observability off must not perturb a single simulated number:
        # the instrumented executor/simulator with obs=None is the
        # pre-obs code path, byte for byte
        ok &= exact_baseline_gate("obs_off_bit_identical", payload)

    if args.check_baseline:
        try:
            with open(args.check_baseline) as f:
                baseline = json.load(f)
        except (OSError, ValueError) as e:   # missing file or corrupt JSON
            print(f"check,baseline,FAIL,unreadable baseline: {e}")
            return 1
        failures = check_baseline(payload, baseline)
        for msg in failures:
            print(f"check,baseline_regression,FAIL,{msg}")
        if not failures:
            print(f"check,baseline,PASS,{args.check_baseline}")
        ok &= not failures

    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
