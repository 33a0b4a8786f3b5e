"""Finds the knee of a serving cell once: the same cell run in one
process at several open-loop rates, each for ``--seconds``.  A rate the
system sustains ends its window with few requests still waiting for a
first token; above the knee the wait grows through the window.

    python3 bench/tools/sweep.py --workload phi3-chat-steady --seconds 30 --seed 7 --rates 1.0 1.4 1.8
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args()
    import jax

    from bench.common import device, harness

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    counter = device.CompileCounter()
    for rate in args.rates:
        _, cell = harness.make_cell(ROOT, args.workload, args.seed,
                                    args.seconds, False, time.perf_counter())
        cell.devices = jax.devices()[: cell.chips]
        cell.traffic.update(rate_per_s=rate, check_requests=0)
        out = harness.driver(cell).run(cell, counter, lambda m: None)
        n = out.notes
        print(json.dumps({"rate_per_s": rate, "metrics": out.metrics,
                          "sent": n["requests_sent"],
                          "finished_in_window": n["finished_in_window"],
                          "waiting_at_close": n["waiting_at_close"],
                          "window_s": n["window_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
