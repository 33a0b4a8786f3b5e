"""The device's idle time split by the program's spans
(``bench/common/spans.py``), on hand-made traces; the program's spans
read from a trace recorded on the CPU; and ``bench/tools/idle_split.py``
on a traced run at CPU size."""
import copy
from pathlib import Path

import pytest

from bench.common import spans
from bench.common import trace as tr

DATA = Path(__file__).parent / "data" / "trace_small.json"

# times in ns; the window is [100, 1100]; the device runs [200, 300] and
# the decode program [500, 700]
HAND = {
    "devices": {"0": {
        "ops": [["%fusion.1 = bf16[] fusion(...)", 200, 100],
                ["%fusion.2 = bf16[] fusion(...)", 500, 200]],
        "modules": [["jit_other(1)", 200, 100], ["jit_decode_step(2)", 500, 200]]}},
    "host": [[tr.WINDOW_BEGIN, 100, 0], [tr.WINDOW_END, 1100, 0]],
    "program": [
        ["executor.task", 110, 790, {"node": "bench_tick", "worker": 0}],
        ["engine.tick", 150, 700, {"active": 2, "queued": 0}],
        ["engine.schedule", 160, 40, {"event": "admit", "request": 0, "nodes": 10}],
        ["engine.decode", 300, 120, {"request": 0, "slot": 0}],
        ["engine.read", 420, 300, {"request": 0}],
        ["engine.schedule", 720, 40, {"event": "finish", "request": 0, "nodes": 14}],
    ],
}


def _trace(raw: dict, devices=None) -> tr.Trace:
    raw = copy.deepcopy(raw)
    program = raw.pop("program")
    return spans.attach(tr.Trace(raw, devices), program)


def test_idle_charged_to_the_innermost_span():
    split = spans.idle_split(_trace(HAND))
    # idle [100, 200], [300, 500], [700, 1100]: outside the task
    # [100, 110] + [900, 1100]; task alone [110, 150] + [850, 900]; tick
    # alone [150, 160] + [760, 850]; schedule [160, 200] + [720, 760];
    # decode [300, 420]; read [420, 500] + [700, 720]
    assert split == pytest.approx({"schedule": 8.0, "dispatch": 12.0, "read": 10.0,
                                   "tick": 10.0, "task": 9.0, "none": 21.0})


def test_buckets_sum_to_idle_share_over_chips():
    t = _trace(HAND, devices=["0", "1"])   # chip 1 ran nothing
    split = spans.idle_split(t)
    assert sum(split.values()) == pytest.approx(t.idle_pct()) == pytest.approx(85.0)
    # chip 1 is idle under every span: the read's whole 300 ns there
    assert split["read"] == pytest.approx((100 + 300) / 2 / 10)
    assert split["tick"] == pytest.approx((100 + 200) / 2 / 10)


def test_silent_without_engine_ticks():
    no_tick = copy.deepcopy(HAND)
    no_tick["program"] = [p for p in no_tick["program"] if p[0] != "engine.tick"]
    assert spans.idle_split(_trace(no_tick)) is None
    # a reduced trace with no program key at all (taken before the
    # program opened spans) reads the same as before, and splits nothing
    import json
    raw = json.loads(DATA.read_text())
    assert "program" not in raw
    t = tr.Trace(raw)
    assert spans.idle_split(t) is None
    assert spans.idle_split(None) is None


def test_decode_clock_check():
    t = _trace(HAND)
    assert spans.decode_clock_check(t) == (1, 1)      # 700 <= read end 720
    late = copy.deepcopy(HAND)
    late["program"][4] = ["engine.read", 420, 250, {"request": 0}]   # ends 670
    assert spans.decode_clock_check(_trace(late)) == (0, 1)


def test_note_gives_the_whole_split():
    t = _trace(HAND)
    note = spans.note(t, spans.idle_split(t))
    assert "schedule 8.0%" in note and "none 21.0%" in note
    assert "sum 70.0%" in note and "device idle 70.0%" in note
    assert "2.0 slots active" in note and "12.0 nodes mean" in note
    assert "ending before their read: 1 of 1" in note


def test_load_program_reads_the_programs_spans(tmp_path):
    """``load_program`` reads the program's spans with their stats from
    the trace ``trace.load`` reads, which keeps only the benchmark's."""
    import jax

    from repro.obs import span

    jax.profiler.start_trace(str(tmp_path))
    tr.mark(tr.WINDOW_BEGIN)
    with span("engine.tick") as tick:
        with span("engine.read", request=3):
            pass
        tick.set_metadata(active=1, queued=0)
    with span("other.thing"):
        pass
    tr.mark(tr.WINDOW_END)
    jax.profiler.stop_trace()
    raw = tr.load(str(tmp_path))
    assert [h[0] for h in raw["host"]] == [tr.WINDOW_BEGIN, tr.WINDOW_END]
    assert "program" not in raw
    program = spans.load_program(str(tmp_path))
    assert [(p[0], p[3]) for p in sorted(program, key=lambda p: p[1])] == [
        ("engine.tick", {"active": 1, "queued": 0}), ("engine.read", {"request": 3})]
    t = spans.attach(tr.Trace(raw), program)
    assert len(spans.program_spans(t, "engine.read")) == 1


def test_idle_split_tool_runs_a_traced_cell(tiny, capsys):
    """The tool runs the cell as ``bench/run.py --trace 1`` does, logs
    its split line, and leaves the harness as it found it."""
    import json

    from bench.common import harness
    from bench.tools import idle_split

    load, read_layers = tr.load, harness.read_layers
    rc = idle_split.main(["--workload", "phi3-chat-steady", "--seed", str(2**31 + 5),
                          "--seconds", "2"], root=tiny, require_tpu=False,
                         device_kind="TPU v5 lite")
    assert rc == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert "breakdown" in result and result["correct"] is True
    assert "idle_split: " in err
    assert (tr.load, harness.read_layers) == (load, read_layers)
