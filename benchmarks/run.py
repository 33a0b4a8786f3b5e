"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Wall-clock numbers are
CPU-host numbers (this container has one core and no TPU); the roofline
rows are derived from the compiled dry-run artifacts in
``results/baseline`` (run ``python -m repro.launch.dryrun --all`` first
for the full table).

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# repo root too: `python benchmarks/run.py` puts benchmarks/ (not the
# root) on sys.path, breaking the `from benchmarks.workloads import`
# inside bench_sched_scaling
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np


def _time(fn, *, reps: int = 3) -> float:
    fn()                                   # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6   # µs


def bench_fig6_timing_analysis(quick: bool) -> list[str]:
    """Paper Fig. 6: multi-view timing analysis vs worker count."""
    from benchmarks.workloads import build_timing_analysis
    from repro.core import Executor
    rows = []
    views = 8 if quick else 32
    for workers in (1, 2, 4):
        def run(workers=workers):
            G, _ = build_timing_analysis(views)
            with Executor(num_workers=workers) as ex:
                ex.run(G).result(timeout=600)
        us = _time(run, reps=1 if quick else 2)
        rows.append(f"fig6_timing_analysis_w{workers},{us:.0f},"
                    f"views={views};views_per_s={views / (us / 1e6):.1f}")
    return rows


def bench_fig9_detailed_placement(quick: bool) -> list[str]:
    """Paper Fig. 9: flattened iterative placement vs worker count."""
    from benchmarks.workloads import build_detailed_placement
    from repro.core import Executor
    rows = []
    iters = 4 if quick else 16
    for workers in (1, 2, 4):
        def run(workers=workers):
            G, _ = build_detailed_placement(iters)
            with Executor(num_workers=workers) as ex:
                ex.run(G).result(timeout=600)
        us = _time(run, reps=1 if quick else 2)
        rows.append(f"fig9_detailed_placement_w{workers},{us:.0f},"
                    f"iters={iters};iters_per_s={iters / (us / 1e6):.1f}")
    return rows


def bench_scheduler_throughput(quick: bool) -> list[str]:
    """Executor overhead: empty-task graph throughput (paper §III-C)."""
    from repro.core import Executor, Heteroflow
    n = 200 if quick else 2000
    G = Heteroflow("empty")
    prev = None
    for i in range(n):
        t = G.host(lambda: None)
        if prev is not None and i % 10 == 0:
            prev.precede(t)
        prev = t

    def run():
        with Executor(num_workers=4) as ex:
            ex.run(G).result(timeout=600)

    us = _time(run, reps=1)
    return [f"scheduler_throughput,{us / n:.1f},"
            f"tasks={n};tasks_per_s={n / (us / 1e6):.0f}"]


def bench_sched_scaling(quick: bool) -> list[str]:
    """Fig. 6/9-style scaling: makespan vs device-bin count from the
    REAL executor and the lane-model simulator side by side.

    Each bin count runs the timing-analysis workload under a profiling
    executor, then replays the recorded trace through
    ``repro.sched.simulate`` (measured durations + recorded bins, lane
    overlap on) and reports both makespans plus their divergence.  On a
    CPU host expect positive divergence at higher worker counts: JAX
    executes kernels from several workers concurrently on one CPU
    device, while the simulator serializes a bin's compute lane the way
    real accelerators do.
    """
    from benchmarks.workloads import build_timing_analysis
    from repro.core import Executor
    from repro.sched import TaskProfiler, simulate
    rows = []
    views = 8 if quick else 16
    dev = jax.devices()[0]
    for nbins in (1, 2, 4):
        bins = [dev] * nbins
        prof = TaskProfiler()
        G, _ = build_timing_analysis(views)
        with Executor(num_workers=2, devices=bins, profiler=prof) as ex:
            ex.run(G).result(timeout=600)
        measured = prof.makespan()
        # label-keyed placement: the bins are one physical device, which
        # an identity-keyed map would collapse to a single simulated bin
        placement = {n.id: n.bin_key for n in G.nodes
                     if n.bin_key is not None}
        rep = simulate(G, placement, ex.device_labels, replay=prof)
        rows.append(
            f"sched_scaling_b{nbins},{measured * 1e6:.0f},"
            f"views={views};sim_us={rep.makespan * 1e6:.0f};"
            f"divergence={rep.divergence:+.3f}")
    # mesh-bin curve (repro.sched.bins): the same fig6-style axis, but
    # the bin pool is one synthetic NxM mesh slice + two device bins and
    # the workload carries capability-tagged sharded kernels — simulated
    # only (slices wider than the host's device count cannot execute
    # here), showing HEFT exploit the slice as it widens
    from benchmarks.sched_bench import parse_bins
    from benchmarks.workloads import build_sharded_stack
    from repro.sched import CostModel, get_scheduler
    model = CostModel()
    for tile in ("1x1", "2x1", "2x2"):
        bins = parse_bins(f"mesh:{tile}")     # same pool the gate sweeps
        G = build_sharded_stack()
        pl = get_scheduler("heft", cost_model=model).schedule(G, bins)
        rep = simulate(G, pl, bins, cost_model=model)
        rows.append(
            f"sched_scaling_mesh_{tile},{rep.makespan * 1e6:.0f},"
            f"slice_devices={bins[0].device_count};"
            f"sim_only=1;policy=heft")
    return rows


def bench_buddy_allocator(quick: bool) -> list[str]:
    """Paper §III-C memory pool: alloc/free latency."""
    from repro.core import BuddyAllocator
    n = 2000 if quick else 20000
    rng = np.random.default_rng(0)
    sizes = rng.integers(256, 1 << 16, n)

    def run():
        b = BuddyAllocator(1 << 26, 256)
        live = []
        for s in sizes:
            if live and len(live) > 64:
                b.free(live.pop(0))
            live.append(b.allocate(int(s)))
        for o in live:
            b.free(o)

    us = _time(run, reps=2)
    return [f"buddy_allocator,{us / n:.2f},ops={n};ops_per_s={n / (us / 1e6):.0f}"]


def bench_kernels(quick: bool) -> list[str]:
    """Pallas kernels vs their jnp oracle: on the CPU backend the kernels
    are interpreted (functional parity timing; real perf target is TPU)."""
    import jax.numpy as jnp
    from repro.kernels import flash_attention, moe_gating
    from repro.kernels.flash_attention.ref import attention_ref
    rows = []
    B, S, H, K, D = 1, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
    us_k = _time(lambda: jax.block_until_ready(
        flash_attention(q, k, v, q_block=128, kv_block=128)))
    us_r = _time(lambda: jax.block_until_ready(attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3))))
    rows.append(f"kernel_flash_attention_interp,{us_k:.0f},ref_us={us_r:.0f}")

    T, E = 512, 16
    logits = jax.random.normal(jax.random.PRNGKey(1), (T, E))
    us_g = _time(lambda: jax.block_until_ready(
        moe_gating(logits, top_k=2, capacity=80)))
    rows.append(f"kernel_moe_gating_interp,{us_g:.0f},tokens={T}")
    return rows


def bench_roofline_table(quick: bool) -> list[str]:
    """Derived rows from the dry-run artifacts (§Roofline source data)."""
    rows = []
    for path in sorted(glob.glob("results/final/*__pod1.json") or glob.glob("results/baseline/*__pod1.json")):
        with open(path) as f:
            rec = json.load(f)
        r = rec["roofline"]
        name = f"roofline_{rec['arch']}_{rec['shape']}"
        bound_s = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        rows.append(
            f"{name},{bound_s * 1e6:.0f},"
            f"bound={r['bottleneck']};mfu_bound={r['mfu_bound']:.4f};"
            f"mem_gib={rec['memory']['per_device_total'] / 2**30:.2f}")
    if not rows:
        rows.append("roofline_table,0,missing=run dryrun --all first")
    return rows


BENCHES = [
    bench_fig6_timing_analysis,
    bench_fig9_detailed_placement,
    bench_sched_scaling,
    bench_scheduler_throughput,
    bench_buddy_allocator,
    bench_kernels,
    bench_roofline_table,
]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true")
    p.add_argument("--only", default=None,
                   help="substring filter on bench name")
    args = p.parse_args()
    print("name,us_per_call,derived")
    for bench in BENCHES:
        if args.only and args.only not in bench.__name__:
            continue
        for row in bench(args.quick):
            print(row, flush=True)


if __name__ == "__main__":
    main()
