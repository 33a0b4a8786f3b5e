"""Runs one cell traced, as ``bench/run.py --trace 1`` does, and splits
the device's idle time in its window by the program's spans
(``bench/common/spans.py``).

    python3 bench/tools/idle_split.py --workload <cell> --seed <n> --seconds <s>

It prints the run's own result line.  Before it, on standard error, a
line ``idle_split:`` gives the six buckets and their sum beside the
device's idle share, the slots active per tick, placement's mean time
and graph size, and how many decode executions end before their read on
the shifted device clock.  ``bench/common/trace.load`` keeps no program
spans, so the tool reads them from the same trace before the run's
temporary directory goes away.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None, **run_kw) -> int:
    """``run_kw`` goes to ``bench.run.main`` (tests: ``root``,
    ``require_tpu``, ``device_kind``)."""
    from bench import run
    from bench.common import harness, spans
    from bench.common import trace as tr

    found = {}
    load, read_layers = tr.load, harness.read_layers

    def load_with_program(trace_dir):
        found["program"] = spans.load_program(trace_dir)
        return load(trace_dir)

    def split_then_read(root, metrics, readings, log):
        split = spans.idle_split(spans.attach(readings.trace, found["program"]))
        log("idle_split: " + ("nothing to split (no engine.tick span, or no "
                              "operation ran in the window)" if split is None
                              else spans.note(readings.trace, split)))
        return read_layers(root, metrics, readings, log)

    tr.load, harness.read_layers = load_with_program, split_then_read
    try:
        args = sys.argv[1:] if argv is None else list(argv)
        return run.main([*args, "--trace", "1"], **run_kw)
    finally:
        tr.load, harness.read_layers = load, read_layers


if __name__ == "__main__":
    raise SystemExit(main())
