"""Records a short profiler trace of a small program on the chip, prints
the planes and lines it holds and what the reduction in
``bench/common/trace.py`` reads from it, and with ``--save PATH`` writes
the reduced trace (the test data of ``bench/tests/test_trace.py``).

    python3 bench/tools/trace_probe.py [--save PATH]
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--save")
    args = p.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    from bench.common import trace as tr

    @jax.jit
    def probe_step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    probe_step(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        rec = tr.Recorder(d, jax.devices()[:1])
        t0 = time.perf_counter()
        rec.start()
        for _ in range(4):
            with TraceAnnotation("bench.tick"):
                probe_step(x).block_until_ready()
            with TraceAnnotation("bench.wait"):
                time.sleep(0.003)
        rec.stop()
        print("start and stop took", time.perf_counter() - t0, "s")
        path = sorted(Path(d).rglob("*.xplane.pb"))[-1]
        data = ProfileData.from_file(str(path))
        for plane in data.planes:
            lines = [(ln.name, len(list(ln.events))) for ln in plane.lines]
            print("plane", plane.name, lines[:12])
            for ln in plane.lines:
                evs = list(ln.events)[:3]
                for ev in evs:
                    print("   ", ln.name, "|", ev.name[:80], ev.start_ns,
                          ev.duration_ns)
        raw = tr.load(d)
    t = tr.Trace(raw)
    print("offsets_ns", t.offsets)
    print("window_s", t.window_s, "busy_s", t.busy_s(), "idle_pct",
          t.idle_pct())
    print("modules", [(m[0], m[2]) for m in t.modules("probe_step")])
    print("ticks", [(h[2], t.busy_in(h[1], h[1] + h[2])) for h in t.host("bench.tick")])
    print("idle_gaps", t.idle_gaps(5))
    print("top_ops", t.top_ops(5))
    if args.save:
        Path(args.save).write_text(json.dumps(raw))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
