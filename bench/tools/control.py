"""Readings that a cell's limits are set from: for each seed, the number a
run compares for the program and the same number for the control, the
plain reference put in the program's place one precision below the
configuration's (phi3: float8 projections).

    python3 bench/tools/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

One process runs every seed (the programs compile once), each through
the cell's own driver at the cell's own load; prints one JSON line per
seed and, last, the largest program reading and the smallest control
reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def _with_control(reference, got: dict):
    """The configuration's reference, which also reads the control on the
    same inputs the run compares, as the run's driver reads the program."""
    from bench.common import serving

    mod = types.SimpleNamespace(**vars(reference))

    def served_gaps(conf, w, seqs, margins=True):
        ctl = serving.gap_numbers(reference.served_gaps(
            conf, w, seqs, control=True, margins=True))
        got["control"] = ctl[serving.COMPARED]
        got["control_numbers"] = ctl
        return reference.served_gaps(conf, w, seqs, margins=margins)

    mod.served_gaps = served_gaps
    return mod


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args()
    import jax

    from bench.common import device, harness, serving

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    counter = device.CompileCounter()
    program, control = [], []
    for seed in args.seeds:
        _, cell = harness.make_cell(ROOT, args.workload, seed, args.seconds,
                                    False, time.perf_counter())
        cell.devices = jax.devices()[: cell.chips]
        got: dict = {}
        plain = cell.module

        def module(name, plain=plain, got=got):
            m = plain(name)
            return _with_control(m, got) if name == "reference" else m

        cell.module = module
        out = harness.driver(cell).run(cell, counter, lambda m: None)
        name = serving.COMPARED
        value = out.notes[name]
        program.append(value)
        control.append(got["control"])
        print(json.dumps({"seed": seed, "number": name, "program": value,
                          "control": got["control"],
                          "control_numbers": got.get("control_numbers"),
                          "failed": out.failed,
                          "metrics": out.metrics, "notes": out.notes}),
              flush=True)
    print(json.dumps({"workload": args.workload, "lower": max(program),
                      "upper": min(control), "program": program,
                      "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
