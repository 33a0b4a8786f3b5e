"""Runs one cell of the benchmark once and prints its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, its traffic
mix and its metrics are found by name in ``BENCHMARK.json`` (see
``bench/common/harness.py``).  The run refuses to start without a TPU, or
with fewer chips than the cell asks for: it then exits non-zero and
prints no result.  Otherwise it sets up (weights or data from the seed,
every shape the cell uses warmed up), measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints, as
the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end ones, or with
``--trace 1`` the per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared beside its
limit.  Everything else goes to standard error, the checks last.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True,
         device_kind: str | None = None) -> int:
    """Runs the cell; ``require_tpu=False`` (tests only) skips the look
    for a chip and reads the peaks of ``device_kind``."""
    args = parse(argv)
    from bench.common import device, harness, peaks
    from bench.common import trace as tr

    bench, cell = harness.make_cell(root, args.workload, args.seed,
                                    args.seconds, bool(args.trace), T_START)
    import jax

    # one fixed cache inside the checkout; every program is kept, so a
    # second run of a cell compiles nothing
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        log(f"no TPU: JAX found {devices[0].platform} devices; no result")
        return 3
    if len(devices) < cell.chips:
        log(f"the cell asks for {cell.chips} chips, JAX found {len(devices)}")
        return 3
    cell.devices = devices[: cell.chips]
    row = peaks.peak(device_kind or devices[0].device_kind)
    counter = device.CompileCounter()

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
        if cell.trace:
            cell.recorder = tr.Recorder(tmp, cell.devices)
        out = harness.driver(cell).run(cell, counter, log)
        readings = out.readings
        readings.peak = row
        if cell.trace:
            trace = tr.Trace(tr.load(tmp), [str(d.id) for d in cell.devices])
            readings.trace = trace
            log(f"note device_clock_offsets_ns: {trace.offsets}")
            log(f"note profiler_seconds: {cell.recorder.seconds}")

    wanted = harness.metrics_of(bench, args.workload, cell.trace)
    if cell.trace:
        metrics = harness.read_layers(root, wanted, readings, log)
    else:
        missing = [m["name"] for m in wanted if m["name"] not in out.metrics]
        if missing:
            raise RuntimeError(f"the run measured no {missing}")
        metrics = {m["name"]: {"value": float(out.metrics[m["name"]]),
                               "unit": m["unit"]} for m in wanted}

    dev = device.describe(devices)
    dev["memory_peak_bytes"] = out.memory_peak_bytes
    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": dev}
    if cell.trace:
        dev["busy_s"] = readings.trace.busy_s()
        dev["window_s"] = readings.trace.window_s
        result["breakdown"] = {"device_ops": readings.trace.top_ops(10),
                               "idle_gaps": readings.trace.idle_gaps(10)}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out.checks.items()}
    result["checks"]["failed"] = {"value": out.failed, "limit": 0}

    for k, v in out.notes.items():
        log(f"note {k}: {v}")
    for k, c in result["checks"].items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
