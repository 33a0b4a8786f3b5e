"""Chip smoke test: the main path on a TPU, each phase checked against an
independent answer.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the multi-chip path only

One chip runs three phases, in one process:

* ``kernels`` — the four Pallas kernels, compiled (never interpreted), at
  one real-width shape each, against their ``ref.py`` with the
  tolerances of ``tests/test_kernels.py``;
* ``paper`` — the paper's two applications, timing analysis (64 views)
  and detailed placement (4096 cells, a 64 MB adjacency matrix), under an
  ``Executor`` over the chips, against direct calls of the same jitted
  kernels; every kernel result must live on a TPU;
* ``serving`` — phi3-mini-3.8b at its published widths with bf16
  weights, answering requests through the code behind
  ``python -m repro.launch.serve`` (engine ticks on an ``Executor``),
  against a direct greedy prefill / ``decode_step`` loop.

``--chips 4`` runs only what exists across chips: the two paper
workloads over all four chips and the ``StageBin`` pipeline over four
chips, each against the same graph on one chip in the same process.
Results must be bit-identical and kernel results must land on at least
two chips.

Each phase prints one JSON line: its seconds, the seconds of its first
(compiling) call, and the chips' ``peak_bytes_in_use``.  The last line
is ``{"ok": true, "device": {...}}``.  With no TPU, or any check
failing, the script exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: one real-width shape per kernel: phi3-mini prefill and decode
#: attention, recurrentgemma-2b's RG-LRU width, deepseek-v2's router
KERNEL_SHAPES = {
    "flash_attention": dict(B=1, S=2048, H=32, K=32, D=96),
    "decode_attention": dict(B=4, S=2048, H=32, K=32, D=96),
    "rglru_scan": dict(B=1, S=2048, dr=2560),
    "moe_gating": dict(T=4096, E=160, k=6),
}


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def timed(fn):
    """(result, seconds) of ``fn()``, waiting for device results."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# ----------------------------------------------------------------------
# kernels
# ----------------------------------------------------------------------
def kernel_cases(shapes: dict):
    """(name, public op call, ref call, tolerance) per kernel."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import (decode_attention, flash_attention, moe_gating,
                               rglru_scan)
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.moe_gating.ref import moe_gating_ref
    from repro.kernels.rglru_scan.ref import rglru_scan_ref

    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 12)
    bf_tol = dict(rtol=5e-2, atol=5e-2)

    s = shapes["flash_attention"]
    q = jax.random.normal(ks[0], (s["B"], s["S"], s["H"], s["D"])).astype(bf)
    k = jax.random.normal(ks[1], (s["B"], s["S"], s["K"], s["D"])).astype(bf)
    v = jax.random.normal(ks[2], (s["B"], s["S"], s["K"], s["D"])).astype(bf)
    t = (lambda a: a.transpose(0, 2, 1, 3))
    yield ("flash_attention", flash_attention, (q, k, v), {},
           lambda: (t(attention_ref(t(q), t(k), t(v))),), bf_tol)

    s = shapes["decode_attention"]
    dq = jax.random.normal(ks[3], (s["B"], s["H"], s["D"])).astype(bf)
    dk = jax.random.normal(ks[4], (s["B"], s["S"], s["K"], s["D"])).astype(bf)
    dv = jax.random.normal(ks[5], (s["B"], s["S"], s["K"], s["D"])).astype(bf)
    vl = jnp.array([max(1, s["S"] - (s["S"] // s["B"]) * i)
                    for i in range(s["B"])], jnp.int32)
    yield ("decode_attention", decode_attention, (dq, dk, dv, vl), {},
           lambda: (decode_attention_ref(dq, dk, dv, vl),), bf_tol)

    s = shapes["rglru_scan"]
    x = jax.random.normal(ks[6], (s["B"], s["S"], s["dr"])).astype(bf)
    a = jax.nn.sigmoid(
        jax.random.normal(ks[7], (s["B"], s["S"], s["dr"]))).astype(bf)
    h0 = jax.random.normal(ks[8], (s["B"], s["dr"]), jnp.float32)
    yield ("rglru_scan", rglru_scan, (x, a, h0), {},
           lambda: (rglru_scan_ref(x, a, h0),), dict(rtol=1e-1, atol=1e-1))

    s = shapes["moe_gating"]
    T, E, top = s["T"], s["E"], s["k"]
    # every row a permutation of E evenly spaced logits: no two experts
    # tie, so the chosen experts cannot depend on the last ulp of exp
    logits = jnp.argsort(jax.random.uniform(ks[9], (T, E)), axis=1
                         ).astype(jnp.float32) * (4.0 / E)
    cap = math.ceil(T * top / E * 1.25)
    yield ("moe_gating", moe_gating, (logits,), dict(top_k=top, capacity=cap),
           lambda: moe_gating_ref(logits, top_k=top, capacity=cap),
           dict(rtol=1e-5, atol=1e-6))


def kernels_phase(shapes: dict = KERNEL_SHAPES) -> dict:
    import numpy as np

    out = {}
    for name, op, args, kw, ref, tol in kernel_cases(shapes):
        compiled = "tpu_custom_call" in op.lower(*args, **kw).as_text()
        check(compiled, f"{name}: the kernel was interpreted, not compiled")
        got, first_s = timed(lambda: op(*args, **kw))
        _, warm_s = timed(lambda: op(*args, **kw))
        got = got if isinstance(got, tuple) else (got,)
        want = ref()
        err = 0.0
        for g, w in zip(got, want):
            g = np.asarray(g, np.float32)
            w = np.asarray(w, np.float32)
            np.testing.assert_allclose(g, w, err_msg=name, **tol)
            err = max(err, float(np.max(np.abs(g - w))))
        out[name] = {"compiled": compiled, "first_call_s": first_s,
                     "s": warm_s, "max_abs_err": err,
                     "shape": shapes[name]}
    return out


# ----------------------------------------------------------------------
# the paper's applications under the Executor
# ----------------------------------------------------------------------
def _host(graph, name: str):
    import numpy as np

    node = next(n for n in graph.nodes if n.name == name)
    src = node.state["source"]
    return np.asarray(src() if callable(src) else src)


def _kernel_devices(graph) -> set:
    """Devices holding the kernel results; a result that is not a
    device array maps to the string ``"host"``."""
    import jax

    from repro.core import TaskType

    devs = set()
    for n in graph.nodes:
        if n.type is TaskType.KERNEL:
            r = n.state.get("result")
            devs |= r.devices() if isinstance(r, jax.Array) else {"host"}
    return devs


def run_timing(devices, n_views: int, workers: int = 4):
    """Timing analysis under the executor: (pushed weights, graph, stats)."""
    from benchmarks.workloads import build_timing_analysis
    from repro.core import Executor

    G, outputs = build_timing_analysis(n_views)
    with Executor(num_workers=workers, devices=devices) as ex:
        ex.run(G).result(timeout=600)
        stats = ex.stats()
    return [o.copy() for o in outputs], G, stats


def timing_reference(G, n_views: int, device):
    """Each view's four gradient steps, called directly on ``device``."""
    import jax
    import numpy as np

    from benchmarks.workloads import _logreg_step

    out = []
    for v in range(n_views):
        x = jax.device_put(_host(G, f"pull_x{v}"), device)
        y = jax.device_put(_host(G, f"pull_y{v}"), device)
        w = jax.device_put(_host(G, f"pull_w{v}"), device)
        for _ in range(4):
            w = _logreg_step(x, y, w)
        out.append(np.asarray(w))
    return out


def run_placement(devices, n_iters: int, n_cells: int, workers: int = 4):
    """Detailed placement under the executor: (objective, graph, stats)."""
    from benchmarks.workloads import build_detailed_placement
    from repro.core import Executor

    G, objective = build_detailed_placement(n_iters, n_cells=n_cells)
    with Executor(num_workers=workers, devices=devices) as ex:
        ex.run(G).result(timeout=600)
        stats = ex.stats()
    return list(objective), G, stats


def placement_reference(G, n_iters: int, device):
    """The MIS and matching kernels of every iteration, called directly."""
    import jax

    from benchmarks.workloads import _matching_kernel, _mis_kernel

    adj = jax.device_put(_host(G, "pull_adj"), device)
    w = jax.device_put(_host(G, "pull_w"), device)
    out = []
    for it in range(n_iters):
        scores = jax.device_put(_host(G, f"pull_scores[{it}]"), device)
        out.append(float(_matching_kernel(w, _mis_kernel(adj, scores))))
    return out


def _exec_stats(stats: dict) -> dict:
    keys = ("devices", "workers", "executed", "steals", "bin_busy_s",
            "lane_depths")
    return {k: stats[k] for k in keys}


def paper_phase(devices, n_views: int = 64, n_iters: int = 8,
                n_cells: int = 4096) -> dict:
    import numpy as np

    dev0 = devices[0]
    rec = {}
    for label in ("first_call_s", "s"):
        t0 = time.perf_counter()
        w, Gt, st_t = run_timing(devices, n_views)
        obj, Gp, st_p = run_placement(devices, n_iters, n_cells)
        rec[label] = time.perf_counter() - t0
        want_w = timing_reference(Gt, n_views, dev0)
        check(all(np.array_equal(a, b) for a, b in zip(w, want_w))
              and len(w) == n_views,
              "timing analysis: pushed weights differ from direct calls")
        check(obj == placement_reference(Gp, n_iters, dev0),
              "detailed placement: objective differs from direct calls")
        for G in (Gt, Gp):
            devs = _kernel_devices(G)
            check(all(getattr(d, "platform", None) == "tpu" for d in devs),
                  f"{G.name}: kernel results off the TPU: {devs}")
    rec.update(timing_views=n_views, placement_iters=n_iters,
               placement_cells=n_cells,
               adjacency_bytes=int(_host(Gp, "pull_adj").nbytes),
               objective=obj, timing_stats=_exec_stats(st_t),
               placement_stats=_exec_stats(st_p))
    return rec


# ----------------------------------------------------------------------
# serving
# ----------------------------------------------------------------------
def greedy_reference(cfg, params, prompt, max_new: int, max_seq: int):
    """Direct greedy loop: prefill, then ``decode_step`` per token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer

    prefill = jax.jit(transformer.prefill, static_argnums=0)
    decode = jax.jit(transformer.decode_step, static_argnums=0)
    caches = transformer.init_cache(cfg, 1, max_seq)
    logits, caches = prefill(cfg, params, jnp.asarray(prompt[None]), caches)
    finite = bool(np.isfinite(np.asarray(logits)).all())
    toks = [int(jnp.argmax(logits[0]))]
    while len(toks) < max_new:
        logits, caches = decode(cfg, params,
                                jnp.asarray([toks[-1]], jnp.int32), caches)
        finite &= bool(np.isfinite(np.asarray(logits)).all())
        toks.append(int(jnp.argmax(logits[0])))
    return toks, finite


def serving_phase(cfg, n_requests: int = 4, slots: int = 4,
                  max_seq: int = 128, max_new: int = 8) -> dict:
    import jax

    from repro.launch.serve import make_prompts, serve
    from repro.models import init_params

    params, init_s = timed(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(params)
    prompts = make_prompts(cfg, n_requests)
    rec = {"arch": cfg.arch_id, "d_model": cfg.d_model,
           "n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
           "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim_,
           "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
           "param_dtype": cfg.param_dtype,
           "weight_dtypes": sorted({str(x.dtype) for x in leaves}),
           "weight_bytes": sum(x.nbytes for x in leaves),
           "init_s": init_s, "requests": n_requests, "slots": slots,
           "prompt_lens": [len(p) for p in prompts], "max_new": max_new}
    want = None
    for label in ("first_call_s", "s"):
        t0 = time.perf_counter()
        done, stats, ex_stats = serve(cfg, params, prompts, slots=slots,
                                      max_seq=max_seq, max_new=max_new)
        rec[label] = time.perf_counter() - t0
        check(len(done) == n_requests and all(r.done for r in done),
              f"serving: {len(done)} of {n_requests} requests finished")
        got = {r.id: r.generated for r in done}
        if want is None:
            want = {}
            for r in done:
                toks, finite = greedy_reference(cfg, params, r.prompt,
                                                max_new, max_seq)
                check(finite, f"serving: non-finite logits, request {r.id}")
                want[r.id] = toks
        check(got == want,
              f"serving: engine tokens {got} != direct greedy loop {want}")
    rec.update(tokens=want, engine_stats=stats,
               executor_stats=_exec_stats(ex_stats))
    return rec


# ----------------------------------------------------------------------
# four chips
# ----------------------------------------------------------------------
def pipeline_graph(n_stages: int, n_mb: int, d: int):
    """``n_stages`` jitted ``tanh(x @ w)`` stages over ``n_mb``
    microbatches of (256, d), stage-tagged for ``StageBin`` pools."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed.pipeline import Stage, build_pipeline_graph

    rng = np.random.default_rng(3)
    fn = jax.jit(lambda w, x: jnp.tanh(x @ w))
    stages = [Stage(fn=fn, params=(rng.normal(size=(d, d)) / math.sqrt(d)
                                   ).astype(np.float32))
              for _ in range(n_stages)]
    mbs = [rng.normal(size=(256, d)).astype(np.float32) for _ in range(n_mb)]
    out: list = []
    return build_pipeline_graph(stages, mbs, collect=out), out


def run_pipeline(members, n_mb: int = 8, d: int = 1024):
    from repro.core import Executor
    from repro.sched.bins import stage_bins

    G, out = pipeline_graph(len(members), n_mb, d)
    with Executor(num_workers=4, devices=stage_bins(members)) as ex:
        ex.run(G).result(timeout=600)
        stats = ex.stats()
    return out, G, stats


def multichip_phase(devices, n_views: int = 64, n_iters: int = 8,
                    n_cells: int = 4096) -> dict:
    import numpy as np

    one = [devices[0]]
    rec = {}
    t0 = time.perf_counter()
    w_n, Gt_n, st_t = run_timing(devices, n_views)
    w_1, Gt_1, _ = run_timing(one, n_views)
    check(all(np.array_equal(a, b) for a, b in zip(w_n, w_1)),
          "timing analysis: weights over all chips != over one chip")
    obj_n, Gp_n, st_p = run_placement(devices, n_iters, n_cells)
    obj_1, _, _ = run_placement(one, n_iters, n_cells)
    check(obj_n == obj_1,
          f"detailed placement: objective {obj_n} over all chips != {obj_1}")
    pipe_n, Gs_n, st_s = run_pipeline(list(devices))
    pipe_1, _, _ = run_pipeline(one * len(devices))
    check(len(pipe_n) == len(pipe_1) and all(
        np.array_equal(a, b) for a, b in zip(pipe_n, pipe_1)),
        "pipeline: outputs over all chips != over one chip")
    rec["s"] = time.perf_counter() - t0
    for name, G in (("timing", Gt_n), ("placement", Gp_n),
                    ("pipeline", Gs_n)):
        devs = _kernel_devices(G)
        check(all(getattr(d, "platform", None) == "tpu" for d in devs),
              f"{name}: kernel results off the TPU: {devs}")
        rec[f"{name}_result_chips"] = sorted(d.id for d in devs)
    check(len(rec["timing_result_chips"]) >= 2
          and len(rec["pipeline_result_chips"]) >= 2,
          f"kernel results on fewer than 2 chips: {rec}")
    rec.update(objective=obj_n, pipeline_microbatches=len(pipe_n),
               timing_stats=_exec_stats(st_t),
               placement_stats=_exec_stats(st_p),
               pipeline_stats=_exec_stats(st_s))
    return rec


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip path, against one chip")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.serve import serving_config

    use_compile_cache()
    emit({"device_kind": devices[0].device_kind,
          "device_count": len(jax.devices()), "chips_used": len(devices),
          "jax": jax.__version__})

    if args.chips == 1:
        phases = [("kernels", kernels_phase),
                  ("paper", lambda: paper_phase(devices)),
                  ("serving", lambda: serving_phase(
                      serving_config("phi3-mini-3.8b", reduced=False)))]
    else:
        phases = [("multichip", lambda: multichip_phase(devices))]
    for name, fn in phases:
        t0 = time.perf_counter()
        rec = fn()
        emit({"phase": name, "phase_s": time.perf_counter() - t0,
              "peak_bytes_in_use": peak_bytes(devices), **rec})

    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
