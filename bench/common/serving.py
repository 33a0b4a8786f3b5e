"""Driver of serving cells.

The system under test is ``repro.serving.ServingEngine`` over a model,
with its ticks run as an ``Executor`` kernel through ``run_until``, as
``repro.launch.serve`` runs it.  The benchmark's tick kernel first sends
the requests that are due (open loop) or the next request of each client
whose last one finished (closed loop), then steps the engine once.

Times are taken from when each request was due, not from when it was
submitted, so a tick that keeps a due request waiting counts against it.
After the window no new request is sent, and the engine runs on until
every request sent has finished, or ``drain_s`` has passed: a request
that never finishes is a failure.
"""
from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from . import device, flops, traffic
from .harness import Cell, Outcome, Readings, limit


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def p95_ms(values: list[float]) -> float:
    return 1e3 * float(np.percentile(np.asarray(values, float), 95))


#: the number ``correct`` compares where a cell's limits file names none:
#: the mean gap of a served token's logit below the reference's best (the
#: widest gap does not separate the program from its control, PERF.md)
COMPARED = "mean_logit_gap"
#: a reference margin under this (in logits) makes a position a near tie
NEAR_TIE = 0.1


def gap_numbers(read: list) -> dict:
    """From the reference's (gaps, margins) of every served token
    compared: the mean and widest gap of a served token's logit below the
    reference's best, and the share of positions whose two best reference
    logits lie within ``NEAR_TIE`` (where a lower precision can flip the
    choice)."""
    if not read:
        return {"mean_logit_gap": float("inf"), "max_logit_gap": float("inf")}
    gaps = np.concatenate([g for g, _ in read])
    near = float(np.mean(np.concatenate([m for _, m in read]) < NEAR_TIE))
    return {"mean_logit_gap": float(gaps.mean()),
            "max_logit_gap": float(gaps.max()), "near_tie_share": near}


class Loop:
    """The traffic of one run, driven from inside the engine's ticks."""

    def __init__(self, engine, executor, reqs, prompts, mix, cell, clock,
                 counter):
        self.engine, self.ex = engine, executor
        self.reqs, self.prompts, self.mix, self.cell = reqs, prompts, mix, cell
        self.clock = clock
        self.counter = counter
        self.closed = mix["kind"] == "closed_loop"
        self.live: dict[int, object] = {}       # request index -> Request
        self.next = 0
        self.busy = False
        self.clients: list[int | None] = [None] * mix.get("clients", 0)
        self.late: list[float] = []
        self.window_end = None
        self.at_end: dict[int, int] = {}
        self.trace_state = "off" if not cell.trace else "idle"
        self.trace_marks: list[dict[int, int]] = []

    # -- the executor's kernel and stop predicate ---------------------------
    def _drive(self, tick, stop) -> None:
        from repro.core import Heteroflow

        g = Heteroflow("bench_ticks")
        g.kernel(tick, name="bench_tick")
        self.ex.run_until(g, stop).result()

    def _submit(self, i: int, now: float) -> None:
        r = self.reqs[i]
        rid = self.engine.submit(self.prompts[i], max_new_tokens=r.output_tokens)
        self.live[i] = self.engine.poll(rid)
        self.busy = True
        if not self.closed:
            self.late.append(now - (self.t0 + r.due_s))

    def _send(self, now: float) -> None:
        if self.closed:
            if now >= self.t_end:
                return
            for c, i in enumerate(self.clients):
                if (i is None or self.live[i].done) and self.next < len(self.reqs):
                    self.clients[c] = self.next
                    self._submit(self.next, now)
                    self.next += 1
            return
        while (self.next < len(self.reqs)
               and self.t0 + self.reqs[self.next].due_s <= now):
            self._submit(self.next, now)
            self.next += 1

    def _tick(self) -> None:
        now = self.clock()
        self._trace_step(now)
        with _annotate("bench.submit"):
            self._send(now)
        if self.busy:
            with _annotate("bench.tick"):
                self.busy = self.engine.step()
        elif not self.closed and self.next < len(self.reqs):
            due = self.t0 + self.reqs[self.next].due_s
            with _annotate("bench.wait"):
                time.sleep(min(0.05, max(0.0, due - self.clock())))
        t = self.clock()
        if self.window_end is None and t >= self.t_end:
            self.window_end = t
            self.itl1 = self._itl().count
            self.compiles1 = self.counter.total
            self.at_end = self._generated()
            self.backlog = sum(1 for r in self.live.values()
                               if r.first_token_s is None)

    def _stop(self) -> bool:
        now = self.clock()
        if now < self.t_end or self.window_end is None:
            return False
        finished = all(r.done for r in self.live.values())
        sent_all = self.closed or self.next == len(self.reqs)
        return (finished and sent_all) or now >= self.drain_from + self.mix["drain_s"]

    def _itl(self):
        return self.engine.metrics.histogram("itl_s")

    def _generated(self) -> dict[int, int]:
        return {i: len(r.generated) for i, r in self.live.items()}

    # -- traced sub-window -------------------------------------------------
    def _trace_step(self, now: float) -> None:
        if self.trace_state == "idle" and now >= self.trace_t0:
            self.cell.recorder.start()
            self.trace_marks.append(self._generated())
            self.trace_state = "on"
        elif self.trace_state == "on" and (
                now >= self.trace_t0 + self.trace_s or now >= self.t_end):
            self._trace_stop()

    def _trace_stop(self) -> None:
        if self.trace_state != "on":
            return
        self.trace_marks.append(self._generated())
        self.cell.recorder.stop()
        self.trace_state = "done"
        # the drain's clock starts after the profiler has let go
        self.drain_from = max(self.drain_from, self.clock())

    # -- phases --------------------------------------------------------------
    def warm_up(self) -> None:
        """One request per prompt length the run sends, two tokens each:
        every prefill shape, the decode step and the host-side programs."""
        seen = {}
        for i, r in enumerate(self.reqs):
            seen.setdefault(r.prompt_tokens, i)
        recs = [self.engine.poll(self.engine.submit(self.prompts[i],
                                                    max_new_tokens=2))
                for i in seen.values()]
        self._drive(lambda: self.engine.step(),
                    lambda: all(r.done for r in recs))

    def measure(self) -> None:
        self.t0 = self.clock()
        self.t_end = self.t0 + self.cell.seconds
        self.drain_from = self.t_end
        self.trace_s = min(self.mix["trace_seconds"], 0.8 * self.cell.seconds)
        self.trace_t0 = self.t0 + 0.2 * self.cell.seconds
        self.itl0 = self._itl().count
        self.compiles0 = self.counter.total
        self._drive(self._tick, self._stop)
        self._trace_stop()


def _traced_flops(conf: dict, reqs, before: dict, after: dict) -> float:
    """Model operations of every prompt and output token processed
    between two snapshots of the tokens generated (a request's prompt
    counts where its first token falls between them)."""
    total = 0
    for i, g1 in after.items():
        p, g0 = reqs[i].prompt_tokens, before.get(i, 0)
        if g0 == 0 < g1:
            total += flops.prefill_flops(conf, p)
        total += sum(flops.token_flops(conf, p + j - 1)
                     for j in range(max(g0, 1), g1))
    return float(total)


def _decode_work(conf: dict, reqs, before: dict, after: dict) -> dict:
    """Tokens decoded between two snapshots, their operations and the KV
    bytes they needed (tokens after the first come from decode calls)."""
    tokens, ops, kv = 0, 0, 0
    for i, g1 in after.items():
        p = reqs[i].prompt_tokens
        for j in range(max(before.get(i, 0), 1), g1):
            tokens += 1
            ops += flops.token_flops(conf, p + j - 1)
            kv += flops.decode_bytes(conf, p + j - 1)
    return {"decoded_tokens": tokens, "decode_flops": float(ops),
            "decode_kv_bytes": float(kv)}


def _sample(loop: Loop, seed: int, k: int) -> list[int]:
    """``k`` finished requests drawn from the seed, the longest among
    them."""
    done = [i for i, r in loop.live.items()
            if r.done and len(r.generated) == loop.reqs[i].output_tokens]
    if not done or k <= 0:
        return []
    longest = max(done, key=lambda i: (loop.reqs[i].output_tokens,
                                       loop.reqs[i].prompt_tokens))
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def run(cell: Cell, counter, log) -> Outcome:
    import jax
    from repro.core import Executor
    from repro.serving import ServingEngine

    conf, mix = cell.config, cell.traffic
    system, reference = cell.module("system"), cell.module("reference")
    reqs = traffic.schedule(mix, cell.seed, cell.seconds)
    prompts = traffic.prompts(reqs, cell.seed, conf["vocab_size"])
    weights = reference.make_weights(conf, cell.seed)
    params = system.program_params(conf, weights)
    jax.block_until_ready(params)
    serving = conf["serving"]
    clock = time.perf_counter
    ex = Executor(num_workers=serving["executor_workers"], devices=cell.devices)
    engine = ServingEngine(system.program_config(conf), params,
                           max_slots=serving["slots"],
                           max_seq=serving["max_seq"], executor=ex,
                           clock=clock)
    loop = Loop(engine, ex, reqs, prompts, mix, cell, clock, counter)
    loop.warm_up()
    setup_s = clock() - cell.t_start
    loop.measure()

    window_s = loop.window_end - loop.t0
    gen_end = loop.at_end
    metrics = {"setup_s": setup_s}
    if loop.closed:
        metrics["output_tokens_per_s"] = sum(gen_end.values()) / window_s
    else:
        ttft = [r.first_token_s - (loop.t0 + reqs[i].due_s)
                for i, r in loop.live.items() if r.first_token_s is not None]
        itl = loop._itl().samples[loop.itl0:loop.itl1]
        if ttft:
            metrics["ttft_p95_ms"] = p95_ms(ttft)
        if itl:
            metrics["itl_p95_ms"] = p95_ms(itl)
    finished = [i for i, r in loop.live.items() if r.done]
    failed = len(loop.live) - len(finished)
    peaks = device.peak_bytes(cell.devices)
    counts = {"window_s": window_s,
              "output_tokens": sum(gen_end.values())}
    if len(loop.trace_marks) == 2:
        counts.update(_decode_work(conf, reqs, *loop.trace_marks))
        counts["traced_flops"] = _traced_flops(conf, reqs, *loop.trace_marks)
    notes = {
        "requests_sent": len(loop.live),
        "requests_finished": len(finished),
        "finished_in_window": sum(1 for i in finished
                                  if loop.live[i].finished_s <= loop.window_end),
        "compiles_in_window": loop.compiles1 - loop.compiles0,
        "compiled_in_window": counter.names[loop.compiles0:loop.compiles1],
        "waiting_at_close": loop.backlog,
        "generator_late_ms_max": 1e3 * max(loop.late, default=0.0),
        "generator_late_ms_mean": 1e3 * statistics.fmean(loop.late) if loop.late else 0.0,
        "peak_bytes_in_use": peaks,
        "window_s": window_s,
    }

    # the program's state goes before the reference runs
    sample = _sample(loop, cell.seed, mix["check_requests"])
    seqs = [(prompts[i], np.asarray(loop.live[i].generated, np.int32))
            for i in sample]
    ex.shutdown()
    del engine, params, loop
    gc.collect()
    t = clock()
    read = reference.served_gaps(conf, weights, seqs, margins=True) if seqs else []
    notes["reference_s"] = clock() - t
    notes["tokens_compared"] = int(sum(len(g) for g, _ in read))
    notes["requests_compared"] = len(seqs)
    numbers = gap_numbers(read)
    notes.update(numbers)
    # every number the cell's limits file names is compared
    checks = {k: (numbers.get(k, float("inf")), limit(cell, k))
              for k in (cell.limits or [COMPARED])}
    return Outcome(
        attempted=notes["requests_sent"], failed=failed, metrics=metrics,
        checks=checks, notes=notes, memory_peak_bytes=max(peaks),
        readings=Readings(trace=None, counts=counts, config=conf,
                          traffic=mix, peak={}, chips=cell.chips))
