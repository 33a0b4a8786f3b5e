"""Serving launcher: continuous batching engine for ``--arch <id>``.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-vl-7b \
        --reduced --requests 8

Without ``--reduced`` the config keeps its published widths and its
weights are held in bf16, as serving deployments hold them; ``--reduced``
is the smoke-size CPU path.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from ..configs import get_config, list_archs, reduced as reduce_cfg
from ..configs.base import ModelConfig
from ..core import Executor
from ..models import init_params
from ..serving import Request, ServingEngine
from .compile_cache import use_compile_cache


def serving_config(arch: str, reduced: bool) -> ModelConfig:
    """``arch`` cut to smoke size, or at its published widths with bf16
    weights."""
    cfg = get_config(arch)
    if reduced:
        return reduce_cfg(cfg)
    return dataclasses.replace(cfg, param_dtype="bfloat16")


def make_prompts(cfg: ModelConfig, n: int) -> list[np.ndarray]:
    """``n`` seeded random prompts of 4..12 tokens (mixed lengths)."""
    rng = np.random.default_rng(0)
    return [rng.integers(0, cfg.vocab_size, size=4 + i % 9).astype(np.int32)
            for i in range(n)]


def serve(cfg: ModelConfig, params, prompts, *, slots: int, max_seq: int,
          max_new: int) -> tuple[list[Request], dict, dict]:
    """Answer ``prompts`` with a :class:`ServingEngine` whose ticks run
    on an :class:`Executor`; returns the finished requests, the engine's
    stats and the executor's stats."""
    with Executor(num_workers=2) as ex:
        eng = ServingEngine(cfg, params, max_slots=slots, max_seq=max_seq,
                            executor=ex)
        for prompt in prompts:
            eng.submit(prompt, max_new_tokens=max_new)
        done = eng.run()
        ex_stats = ex.stats()
    return done, eng.stats(), ex_stats


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=list_archs(), required=True)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-seq", type=int, default=128)
    p.add_argument("--max-new", type=int, default=8)
    args = p.parse_args(argv)

    use_compile_cache()
    cfg = serving_config(args.arch, args.reduced)
    params = init_params(cfg, jax.random.PRNGKey(0))
    t0 = time.time()
    done, stats, _ = serve(cfg, params, make_prompts(cfg, args.requests),
                           slots=args.slots, max_seq=args.max_seq,
                           max_new=args.max_new)
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in done)
    print(f"{len(done)} requests / {toks} tokens in {dt:.2f}s; "
          f"stats={stats}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
