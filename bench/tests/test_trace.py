"""The trace reduction: busy/idle union, device time inside a span, gap
attribution and program time, on a hand-made trace and on a small trace
recorded on a v5e chip (``data/trace_small.json``, written by
``bench/tools/trace_probe.py --save``)."""
import json
from pathlib import Path

import pytest

from bench.common import trace as tr

DATA = Path(__file__).parent / "data" / "trace_small.json"

# times in ns; the window is [100, 1100]
HAND = {
    "devices": {"0": {
        "ops": [["%while.1 = (...) while(...)", 150, 150],       # a loop
                ["%fusion.1 = bf16[] fusion(...)", 150, 100],     # its body
                ["%fusion.2 = bf16[] fusion(...)", 250, 50],
                ["%dot.3 = f32[] dot(...)", 600, 200],
                ["%fusion.1 = bf16[] fusion(...)", 1050, 100]],   # crosses hi
        "modules": [["jit_step(1)", 140, 170], ["jit_step(2)", 590, 220],
                    ["jit_other", 1040, 120]]}},
    "host": [[tr.WINDOW_BEGIN, 100, 0], [tr.WINDOW_END, 1100, 0],
             ["bench.tick", 120, 400], ["bench.submit", 320, 100],
             ["bench.wait", 820, 200]],
}


def test_union_and_overlap():
    m = tr.union([(5, 7), (1, 3), (2, 4), (7, 8)])
    assert m == [[1, 4], [5, 8]]
    assert tr.overlap(m, 3, 6) == 2


def test_busy_and_idle_share():
    t = tr.Trace(HAND)
    assert t.window_s == pytest.approx(1000e-9)
    # [150, 300] + [600, 800] + [1050, 1100]
    assert t.busy_s() == pytest.approx(400e-9)
    assert t.idle_pct() == pytest.approx(60.0)


def test_device_time_inside_a_span():
    t = tr.Trace(HAND)
    (_, s, d), = t.host("bench.tick")
    assert t.busy_in(s, s + d) == pytest.approx(150)
    # the tick's 400 ns less its 150 ns of device time, in milliseconds
    assert t.host_ms_per_span("bench.tick") == pytest.approx(250e-6)
    assert t.host_ms_per_span("bench.none") is None


def test_gaps_named_by_the_innermost_host_span():
    gaps = tr.Trace(HAND).idle_gaps(10)
    # [300, 600] mid 450: inside tick only (submit ends at 420);
    # [800, 1050] mid 925: inside wait; [100, 150] mid 125: tick
    assert gaps == [["bench.tick", pytest.approx(300e-9)],
                    ["bench.wait", pytest.approx(250e-9)],
                    ["bench.tick", pytest.approx(50e-9)]]


def test_programs_and_top_ops():
    t = tr.Trace(HAND)
    assert [m[0] for m in t.modules(r"jit_step")] == ["jit_step(1)", "jit_step(2)"]
    top = dict(t.top_ops(10))
    # the loop is left out, its body counted; names lose their hash and text
    assert top == {"jit_step/%dot.3": pytest.approx(200e-9),
                   "jit_step/%fusion.1": pytest.approx(100e-9),
                   "jit_step/%fusion.2": pytest.approx(50e-9),
                   "jit_other/%fusion.1": pytest.approx(50e-9)}


def test_device_clock_moved_onto_the_host_clock():
    """A sync program ends just before the host span that waits for it:
    the ends are matched."""
    raw = json.loads(json.dumps(HAND))
    raw["host"] += [[tr.SYNC + "0", 1000, 100], [tr.SYNC + "0", 5000, 100]]
    # host ends at 1100 and 5100, the programs at 1020 and 5020
    raw["devices"]["0"]["modules"] += [["jit_bench_sync(7)", 980, 40],
                                       ["jit_bench_sync(7)", 4980, 40]]
    t = tr.Trace(raw)
    assert t.offsets == {"0": pytest.approx(80)}
    assert t.modules("jit_step")[0][1] == pytest.approx(220)


def test_several_devices_average():
    """The mean over the cell's chips: one of them that ran nothing
    counts as idle, a chip outside the cell is left out."""
    raw = json.loads(json.dumps(HAND))
    raw["devices"]["1"] = {"ops": [["x", 100, 1000]], "modules": []}
    raw["devices"]["3"] = {"ops": [["x", 100, 1000]], "modules": []}
    t = tr.Trace(raw, ["0", "1", "2"])
    assert t.busy_s() == pytest.approx((400e-9 + 1000e-9 + 0.0) / 3)


def test_no_device_work_reads_nothing():
    raw = {"devices": {}, "host": HAND["host"]}
    assert tr.Trace(raw).idle_pct() is None


def test_recorded_chip_trace():
    """Four ticks of a 2048-square bf16 matmul program with 3 ms sleeps
    between them, recorded on one v5e."""
    t = tr.Trace(json.loads(DATA.read_text()))
    ticks = t.host("bench.tick")
    assert len(ticks) == 4
    progs = t.modules("probe_step")
    assert len(progs) == 4
    # each program runs inside its tick on the host span's clock, to the
    # tens of microseconds the host takes to see a program end
    slack = 100e3
    for (_, s, d), (_, ps, pd) in zip(sorted(ticks, key=lambda h: h[1]),
                                      sorted(progs, key=lambda m: m[1])):
        assert s <= ps and ps + pd <= s + d + slack
        assert t.busy_in(s, s + d) >= pd - slack
    assert 0 < t.busy_s() < t.window_s
    labels = {g[0] for g in t.idle_gaps(3)}
    assert "bench.wait" in labels


def test_load_keeps_the_benchmark_host_spans(tmp_path):
    """``load`` reads an .xplane.pb (here one recorded on the CPU, which
    has no device plane) and keeps the ``bench.`` host spans."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_BEGIN):
        pass
    with jax.profiler.TraceAnnotation("bench.tick"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation(tr.WINDOW_END):
        pass
    jax.profiler.stop_trace()
    raw = tr.load(str(tmp_path))
    names = [h[0] for h in raw["host"]]
    assert names.count("bench.tick") == 1
    assert tr.WINDOW_BEGIN in names and tr.WINDOW_END in names
    t = tr.Trace(raw)
    (_, s, d), = t.host("bench.tick")
    assert t.lo <= s and s + d <= t.hi
