"""Executor semantics (paper §III-B/C): saxpy, repeats, errors, stealing."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import Executor, Heteroflow


@pytest.fixture(scope="module")
def executor():
    with Executor(num_workers=4) as ex:
        yield ex


def test_saxpy_end_to_end(executor):
    N = 4096
    x = np.zeros(N, np.float32)
    y = np.zeros(N, np.float32)
    G = Heteroflow("saxpy")
    hx = G.host(lambda: x.__setitem__(slice(None), 1.0))
    hy = G.host(lambda: y.__setitem__(slice(None), 2.0))
    px = G.pull(x)
    py = G.pull(y)
    saxpy = jax.jit(lambda a, xx, yy: a * xx + yy)
    k = G.kernel(saxpy, 2.0, px, py, writes=(py,))
    push = G.push(py, y)
    hx.precede(px)
    hy.precede(py)
    k.succeed(px, py).precede(push)
    assert executor.run(G).result(timeout=60) == 1
    np.testing.assert_allclose(y, 4.0)


def test_run_n_stateful(executor):
    log = []
    G = Heteroflow()
    a = G.host(lambda: log.append("a"))
    b = G.host(lambda: log.append("b"))
    a.precede(b)
    assert executor.run_n(G, 5).result(timeout=60) == 5
    assert len(log) == 10
    # order within every iteration
    for i in range(0, 10, 2):
        assert log[i] == "a" and log[i + 1] == "b"


def test_run_n_zero(executor):
    G = Heteroflow()
    G.host(lambda: None)
    assert executor.run_n(G, 0).result(timeout=10) == 0


def test_run_until(executor):
    counter = []
    G = Heteroflow()
    G.host(lambda: counter.append(1))
    fut = executor.run_until(G, lambda: len(counter) >= 7)
    assert fut.result(timeout=60) == 7
    assert len(counter) == 7


def test_error_propagation(executor):
    G = Heteroflow()
    G.host(lambda: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        executor.run(G).result(timeout=60)


def test_error_skips_downstream(executor):
    ran = []
    G = Heteroflow()
    bad = G.host(lambda: 1 / 0)
    after = G.host(lambda: ran.append(1))
    bad.precede(after)
    with pytest.raises(ZeroDivisionError):
        executor.run(G).result(timeout=60)
    assert not ran


def test_thread_safe_submission(executor):
    results = []

    def submit(i):
        G = Heteroflow(f"t{i}")
        G.host(lambda i=i: results.append(i))
        return executor.run(G)

    futs = []
    threads = [threading.Thread(target=lambda i=i: futs.append(submit(i)))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    executor.wait_for_all()
    assert sorted(results) == list(range(8))


def test_kernel_chaining_device_dataflow(executor):
    """A kernel may consume another kernel's output without a host trip."""
    G = Heteroflow()
    import jax.numpy as jnp
    k1 = G.kernel(jax.jit(lambda: jnp.arange(8.0)))
    k2 = G.kernel(jax.jit(lambda a: a * 2), k1)
    k1.precede(k2)
    executor.run(G).result(timeout=60)
    np.testing.assert_allclose(np.asarray(k2._node.state["result"]),
                               np.arange(8.0) * 2)


def test_wide_graph_parallelism_and_stats():
    with Executor(num_workers=4) as ex:
        G = Heteroflow()
        gate = threading.Barrier(4, timeout=30)
        for _ in range(4):
            G.host(lambda: gate.wait())   # deadlocks unless 4 run in parallel
        assert ex.run(G).result(timeout=60) == 1
        stats = ex.stats()
        assert stats["executed"] == 4


def test_lane_depths_keyed_by_stable_device_id():
    """Profiler traces correlate lanes across runs: stats() must key
    lanes by the stable device identifier, not enumeration order."""
    from repro.core.streams import device_key

    x = np.ones(16, np.float32)
    keys = []
    for _ in range(2):
        with Executor(num_workers=1) as ex:
            G = Heteroflow()
            G.pull(x)
            ex.run(G).result(timeout=60)
            depths = ex.stats()["lane_depths"]
            assert set(depths) == {device_key(ex.devices[0])}
            assert all(isinstance(k, str) for k in depths)
            keys.append(sorted(depths))
    assert keys[0] == keys[1]  # stable across runs


def test_straggler_detection_and_last_thief_completion():
    """Deterministic straggler scenario: one worker blocks inside a host
    task; the other must finish every unblocked node (adaptive last-thief
    keeps it alive while its peer is active), stragglers() must flag the
    stall, and releasing the block must complete the graph promptly."""
    release = threading.Event()
    done: list = []
    with Executor(num_workers=2) as ex:
        G = Heteroflow()
        blocker = G.host(lambda: release.wait(timeout=30))
        for i in range(16):
            G.host(lambda i=i: done.append(i))
        tail = G.host(lambda: done.append("tail"))
        blocker.precede(tail)
        fut = ex.run(G)

        # remaining worker drains all 16 quick tasks despite the stall
        deadline = time.monotonic() + 10
        while len(done) < 16 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(done) == 16 and "tail" not in done

        time.sleep(0.25)
        stragglers = ex.stragglers(threshold_s=0.2)
        assert stragglers, "blocked worker not flagged as straggler"

        t0 = time.monotonic()
        release.set()
        assert fut.result(timeout=30) == 1
        # the lone thief was spinning (peer active) → prompt pickup
        assert time.monotonic() - t0 < 5.0
        assert done[-1] == "tail" and ex.stats()["executed"] == 18
        assert ex.stragglers(threshold_s=30.0) == []


def test_locality_aware_steal_prefers_matching_bin_victim():
    """Deterministic unit test of the steal path: a thief whose last
    device task ran on bin B steals from the victim whose deque head is
    also placed on B, and the hit/miss counters record it."""
    from repro.core.graph import Node, TaskType

    with Executor(num_workers=3, devices=["d0", "d1"]) as ex:
        pass  # workers stopped; drive _steal by hand below

    def device_node(key):
        n = Node(TaskType.KERNEL)
        n.bin_key = key
        return n

    w0, w1, w2 = ex._workers
    on_d1, on_d0 = device_node("d1"), device_node("d0")
    w1.deque.append(on_d1)
    w2.deque.append(on_d0)
    w0.last_bin = "d0"
    assert ex._steal(w0) is on_d0            # matching victim wins
    assert (w0.steal_local, w0.steal_cross) == (1, 0)

    w2.deque.append(device_node("d0"))       # victims now: w1=d1, w2=d0
    w0.last_bin = "d1"
    assert ex._steal(w0) is on_d1            # preference follows last_bin
    assert (w0.steal_local, w0.steal_cross) == (2, 0)

    # with locality disabled the counters still record cross-bin steals
    with Executor(num_workers=2, devices=["d0", "d1"],
                  steal_locality=False) as ex2:
        pass
    t, v = ex2._workers
    v.deque.append(device_node("d1"))
    t.last_bin = "d0"
    assert ex2._steal(t).bin_key == "d1"
    assert (t.steal_local, t.steal_cross) == (0, 1)
    assert ex2.stats()["steal_locality"] is False


def test_dynamic_replacement_reschedules_with_measured_load():
    """Executor(replace_every=N) re-invokes the scheduler between
    iterations, feeding measured per-bin load through initial_load —
    keyed by bin INDEX so duplicate bin objects (two scheduling bins on
    one device) cannot collapse the per-slot imbalance signal."""
    import jax

    from repro.sched import BalancedBins

    calls: list = []

    class CountingBalanced(BalancedBins):
        def assign(self, graph, groups, bins, *, initial_load=None):
            calls.append(initial_load)
            return super().assign(graph, groups, bins,
                                  initial_load=initial_load)

    log: list = []
    G = Heteroflow()
    p = G.pull(np.ones(16, np.float32))
    k = G.kernel(lambda a: a * 2, p)
    k.succeed(p)
    k.precede(G.host(lambda: log.append(1)))
    bins = list(jax.devices()) * 2             # duplicate bin objects
    with Executor(num_workers=2, devices=bins,
                  scheduler=CountingBalanced(), replace_every=2) as ex:
        assert ex.run_n(G, 5).result(timeout=60) == 5
        stats = ex.stats()
    assert len(log) == 5
    assert stats["replacements"] == 2          # after iterations 2 and 4
    assert len(calls) == 3                     # initial + two re-placements
    assert calls[0] is None                    # no arenas → no initial load
    for load in calls[1:]:                     # measured, scaled to cost units
        assert load is not None
        assert set(load) == {0, 1}             # one entry PER SLOT, by index
        assert all(v >= 0.0 for v in load.values())
    assert sum(stats["bin_busy_s"].values()) >= 0.0


def test_raising_profiler_fails_future_not_worker():
    """Telemetry exceptions must surface through the topology future —
    not kill the worker thread and hang result() forever."""
    from repro.core import TaskType

    class BadProfiler:
        def record(self, node, **kwargs):
            if node.type is TaskType.KERNEL:
                raise RuntimeError("boom in profiler")

        def finalize(self, executor):
            pass

    done: list = []
    with Executor(num_workers=2, profiler=BadProfiler()) as ex:
        G = Heteroflow()
        p = G.pull(np.ones(8, np.float32))
        k = G.kernel(lambda a: a, p)
        k.succeed(p)
        with pytest.raises(RuntimeError, match="boom in profiler"):
            ex.run(G).result(timeout=30)
        # workers survived: a host-only graph (profiler stays quiet)
        # still completes on the same executor
        G2 = Heteroflow()
        G2.host(lambda: done.append(1))
        assert ex.run(G2).result(timeout=30) == 1
    assert done == [1]


def test_raising_profiler_finalize_fails_future_not_worker():
    """finalize() runs at topology retire — an exception there must
    resolve the future too, same rule as record()."""

    class BadFinalize:
        def record(self, node, **kwargs):
            pass

        def finalize(self, executor):
            raise OSError("disk full in finalize")

    with Executor(num_workers=2, profiler=BadFinalize()) as ex:
        G = Heteroflow()
        G.host(lambda: None)
        with pytest.raises(OSError, match="disk full"):
            ex.run(G).result(timeout=30)
        ex.wait_for_all()   # topology retired despite the failure


def test_replacement_moves_arena_blocks_with_the_group():
    """When re-placement moves a pull to another bin, its buddy-arena
    block must be freed on the old device's arena and re-allocated on
    the new one — occupancy follows the placement."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from repro.sched import Scheduler

    class Flip(Scheduler):
        """Assigns everything to bin (calls-1) % 2 — every re-placement
        moves the whole graph to the other bin."""
        name = "flip"

        def __init__(self):
            self.calls = 0

        def assign(self, graph, groups, bins, *, initial_load=None):
            self.calls += 1
            return {g.root: (self.calls - 1) % 2 for g in groups}

    dev = jax.devices()[0]
    bins = [SingleDeviceSharding(dev), SingleDeviceSharding(dev)]
    G = Heteroflow()
    p = G.pull(np.ones(256, np.float32))          # 1024 B -> one min_block
    k = G.kernel(lambda a: a * 1.0, p)
    k.succeed(p)
    with Executor(num_workers=1, devices=bins, scheduler=Flip(),
                  arena_bytes=1 << 20, replace_every=1) as ex:
        assert ex.run_n(G, 4).result(timeout=60) == 4
        a0 = ex.arenas[id(bins[0])]
        a1 = ex.arenas[id(bins[1])]
    # schedule ran 4x (initial + 3 re-placements): final home is bin 1;
    # the stale-block bug leaves the block stranded on bin 0 instead
    assert a0.bytes_in_use == 0
    assert a1.bytes_in_use == a1.min_block        # exactly one live block


_ACROSS_DEVICES = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import Executor, Heteroflow, TaskType

xs = [np.full(64, i, np.float32) for i in range(8)]
G = Heteroflow("fan_in")
double = jax.jit(lambda x: 2.0 * x)
parts = []
for x in xs:
    p = G.pull(x)
    parts.append(G.kernel(double, p).succeed(p))
total = G.kernel(jax.jit(lambda *a: sum(a)), *parts).succeed(*parts)
with Executor(num_workers=4, devices=jax.devices()) as ex:
    ex.run(G).result(timeout=60)
homes = set()
for n in G.nodes:
    if n.type is TaskType.KERNEL and n is not total:
        homes |= n.state["result"].devices()
assert len(homes) >= 2, homes
np.testing.assert_array_equal(np.asarray(total.result()), 2.0 * sum(xs))
print("ok", len(homes))
"""


def test_kernel_operands_follow_it_across_devices():
    """A kernel whose operands were left on other devices by upstream
    kernels gets them copied to its own.  Four virtual CPU devices need
    a fresh JAX, so the graph runs in a child process."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _ACROSS_DEVICES], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.startswith("ok")
