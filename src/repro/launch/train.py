"""Production training launcher: ``--arch <id>`` over a real mesh.

With 256 or more devices this builds the production mesh and pjit's the
train step with its shardings; with fewer it builds a data-parallel mesh
over every device present (one on a CPU host, four on a four-chip TPU
host; ``dryrun.py`` is the at-scale compile proof).  The loop itself is
a hetflow graph: host(data) → pull(batch) → kernel(step) →
push(metrics), with async checkpoints and straggler monitoring.

    PYTHONPATH=src python -m repro.launch.train --arch phi3-mini-3.8b \
        --steps 20 --reduced
"""
from __future__ import annotations

import argparse
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import get_config, list_archs, reduced as reduce_cfg
from ..core import Executor, Heteroflow
from ..data import Pipeline, PipelineConfig, SyntheticSource
from ..distributed import named, state_pspecs, use_sharding_rules
from ..training import (AdamWConfig, checkpoint, cosine_schedule,
                        init_train_state, make_train_step, wsd_schedule)
from .compile_cache import use_compile_cache
from .mesh import make_local_mesh, make_production_mesh


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=list_archs(), required=True)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--reduced", action="store_true",
                   help="smoke-size config (CPU)")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=100)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--multi-pod", action="store_true")
    args = p.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    # WSD for minicpm (its assigned schedule), cosine otherwise
    sched = (wsd_schedule(3e-4, 100, max(args.steps - 200, 100), 100)
             if args.arch == "minicpm-2b"
             else cosine_schedule(3e-4, 100, max(args.steps, 1000)))
    opt = AdamWConfig(schedule=sched)

    n_dev = jax.device_count()
    if n_dev >= 256:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:
        mesh = make_local_mesh()
    print(f"devices={n_dev} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    with use_sharding_rules(mesh=mesh):
        state = init_train_state(cfg, jax.random.PRNGKey(0))
        step_fn = make_train_step(cfg, opt, remat_policy="none"
                                  if args.reduced else "full")
        sspec = named(mesh, state_pspecs(cfg, jax.eval_shape(lambda: state),
                                         mesh))
        with jax.set_mesh(mesh):
            jitted = jax.jit(step_fn, in_shardings=(sspec, None),
                             out_shardings=(sspec, None))

        start = 0
        if args.resume and args.ckpt_dir:
            state, start = checkpoint.restore(
                args.ckpt_dir, jax.eval_shape(lambda: state))
            print(f"resumed from step {start}")
        # the state lives where the step's shardings say, the batch is
        # split over the data axis
        state = jax.device_put(state, sspec)
        data = NamedSharding(mesh, P("data"))

        pipe = Pipeline(SyntheticSource(cfg.vocab_size),
                        PipelineConfig(batch=args.batch, seq=args.seq))
        buffer: dict = {}
        losses: list[float] = []
        box = {"state": state}
        t0 = time.time()

        hf = Heteroflow("train")
        host, pull_t, pull_l = pipe.host_task_graph(hf, buffer)

        def do_step(tokens, labels):
            with jax.set_mesh(mesh):
                new_state, metrics = jitted(box["state"], jax.device_put(
                    {"tokens": tokens, "labels": labels}, data))
            box["state"] = new_state
            return metrics["total_loss"]

        kernel = hf.kernel(do_step, pull_t, pull_l, name="train_step")
        sink = hf.host(lambda: losses.append(
            float(kernel.result())), name="metrics")
        kernel.succeed(pull_t, pull_l).precede(sink)

        with Executor(num_workers=2) as ex:
            futs = []

            def stop():
                n = len(losses)
                if n % 5 == 0 and n:
                    print(f"step {start + n}: loss={losses[-1]:.4f}",
                          flush=True)
                if (args.ckpt_dir and n
                        and n % args.ckpt_every == 0
                        and len(futs) < n // args.ckpt_every):
                    futs.append(checkpoint.async_save(
                        ex, args.ckpt_dir, start + n, box["state"]))
                slow = ex.stragglers(threshold_s=120.0)
                if slow:
                    print(f"straggler warning: workers {slow}", flush=True)
                return n >= args.steps

            ex.run_until(hf, stop).result()
            for f in futs:
                f.result(timeout=600)

        dt = time.time() - t0
        print(f"{args.steps} steps in {dt:.1f}s "
              f"({args.steps * args.batch * args.seq / dt:,.0f} tok/s); "
              f"loss {losses[0]:.3f} → {losses[-1]:.3f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
