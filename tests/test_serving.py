"""Serving engine: continuous batching + paged arena integration."""
import dataclasses

import jax
import numpy as np
import pytest
from _profiled import profiled

from repro.configs import get_config, reduced
from repro.core import Executor
from repro.models import init_params
from repro.serving import ServingEngine


@pytest.fixture(scope="module")
def rig():
    cfg = reduced(get_config("phi3-mini-3.8b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.mark.slow
def test_engine_completes_all_requests(rig):
    cfg, params = rig
    eng = ServingEngine(cfg, params, max_slots=2, max_seq=64)
    ids = [eng.submit(np.arange(4 + i) % cfg.vocab_size, max_new_tokens=3)
           for i in range(5)]
    done = eng.run()
    assert sorted(r.id for r in done) == sorted(ids)
    assert all(len(r.generated) == 3 for r in done)
    assert eng.arena.pages_in_use == 0          # everything released


def test_engine_greedy_determinism(rig):
    cfg, params = rig
    prompt = np.arange(6) % cfg.vocab_size
    outs = []
    for _ in range(2):
        eng = ServingEngine(cfg, params, max_slots=1, max_seq=64)
        eng.submit(prompt, max_new_tokens=4)
        outs.append(eng.run()[0].generated)
    assert outs[0] == outs[1]


def test_engine_rejects_oversize(rig):
    cfg, params = rig
    eng = ServingEngine(cfg, params, max_slots=1, max_seq=16)
    eng.submit(np.zeros(30, np.int32), max_new_tokens=4)   # 34 > 16
    done = eng.run()
    assert len(done) == 1 and done[0].generated == []


@pytest.mark.slow
def test_engine_under_hetflow_executor(rig):
    cfg, params = rig
    with Executor(num_workers=2) as ex:
        eng = ServingEngine(cfg, params, max_slots=2, max_seq=64,
                            executor=ex)
        for i in range(3):
            eng.submit(np.arange(5) % cfg.vocab_size, max_new_tokens=2)
        done = eng.run()
    assert len(done) == 3


def test_launch_serve_finishes_every_request_with_bf16_weights():
    """The function behind ``python -m repro.launch.serve`` answers every
    prompt, on an executor, with bf16 weights as served at full width."""
    from repro.launch.serve import make_prompts, serve

    cfg = dataclasses.replace(reduced(get_config("phi3-mini-3.8b")),
                              param_dtype="bfloat16")
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert {x.dtype for x in jax.tree.leaves(params)} == {
        jax.numpy.dtype("bfloat16")}
    prompts = make_prompts(cfg, 5)
    assert len({len(p) for p in prompts}) == 5          # mixed lengths
    done, stats, ex_stats = serve(cfg, params, prompts, slots=4,
                                  max_seq=64, max_new=3)
    assert len(done) == 5 and all(r.done for r in done)
    assert all(len(r.generated) == 3 for r in done)
    assert stats["completed"] == 5 and ex_stats["executed"] > 0


@pytest.mark.slow
def test_engine_matches_raw_decode(rig):
    """Engine generation == direct prefill+decode of the model."""
    from repro.models import decode_step, init_cache, prefill
    import jax.numpy as jnp
    cfg, params = rig
    prompt = np.arange(7) % cfg.vocab_size
    eng = ServingEngine(cfg, params, max_slots=1, max_seq=32)
    eng.submit(prompt, max_new_tokens=3)
    got = eng.run()[0].generated

    caches = init_cache(cfg, 1, 32)
    logits, caches = prefill(cfg, params, jnp.asarray(prompt[None]), caches)
    want = [int(jnp.argmax(logits[0]))]
    for _ in range(2):
        logits, caches = decode_step(
            cfg, params, jnp.asarray([want[-1]], jnp.int32), caches)
        want.append(int(jnp.argmax(logits[0])))
    assert got == want


def _direct_tokens(cfg, params, prompt, n, max_seq):
    """``n`` greedy tokens of one request by a batch-1 prefill and a
    ``decode_step`` loop, without the engine."""
    import jax.numpy as jnp
    from repro.models import decode_step, init_cache, prefill

    logits, caches = jax.jit(prefill, static_argnums=0)(
        cfg, params, jnp.asarray(prompt[None]), init_cache(cfg, 1, max_seq))
    out = [int(jnp.argmax(logits[0]))]
    while len(out) < n:
        logits, caches = jax.jit(decode_step, static_argnums=0)(
            cfg, params, jnp.asarray([out[-1]], jnp.int32), caches)
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_engine_rows_match_direct_decode_per_request(rig, monkeypatch):
    """The stacked ragged decode gives every request the tokens of its
    own prefill and batch-1 decode loop: five requests of different
    prompt and answer lengths, seated at different ticks on two slots
    (so slots retire and re-seat), one of them preempted by a grow that
    found the arena full, re-queued and recomputed."""
    from repro.core.memory import OutOfMemory
    from repro.serving.kv_cache import PagedKVArena

    cfg, params = rig
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    eng = ServingEngine(cfg, params, max_slots=2, max_seq=32)
    assert eng._rows is not None
    rng = np.random.default_rng(5)
    asks = [(5, 6), (9, 3), (3, 7), (7, 4), (4, 5)]      # (prompt, answer)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n, _ in asks]

    # the grower's page-run grow meets a full arena once, when request 2
    # holds its third token: the engine preempts the other seated one
    real = PagedKVArena.extend
    planted = {"left": 1}

    def extend(arena, rid, new_tokens=1):
        req = next(r for r in eng._slots if r is not None and r.id == rid)
        if rid == 2 and planted["left"] and len(req.generated) == 3:
            planted["left"] = 0
            raise OutOfMemory("planted: arena full")
        return real(arena, rid, new_tokens)

    monkeypatch.setattr(PagedKVArena, "extend", extend)
    ids = [eng.submit(prompts[i], max_new_tokens=asks[i][1]) for i in (0, 1)]
    eng.step()
    ids.append(eng.submit(prompts[2], max_new_tokens=asks[2][1]))
    eng.step()
    ids += [eng.submit(prompts[i], max_new_tokens=asks[i][1]) for i in (3, 4)]
    done = {r.id: r.generated for r in eng.run()}

    assert planted["left"] == 0 and eng.preemptions == 1
    assert sorted(done) == ids == list(range(5))
    for i, (prompt, (_, n)) in enumerate(zip(prompts, asks)):
        assert done[i] == _direct_tokens(cfg, params, prompt, n, 32), i
    # one call per tick with seated rows; the victim's row was decoded
    # in the call whose grow preempted it, and its tokens were dropped
    rows = eng.metrics.histogram("decode_rows").samples
    assert set(rows) == {1, 2} and len(rows) <= eng.ticks
    assert sum(rows) > sum(n - 1 for _, n in asks)


@pytest.mark.parametrize("arch", ["recurrentgemma-2b", "deepseek-v2-236b"])
def test_engine_serves_one_position_layers_batch1(arch):
    """A config with a ring window (``attn_local``) or a latent cache
    (``mla``) still serves, through one batch-1 cache and call per slot,
    with each request's own tokens."""
    cfg = reduced(get_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_slots=2, max_seq=32)
    assert eng._rows is None
    asks = [(5, 3), (8, 2), (4, 4)]
    prompts = [np.arange(n, dtype=np.int32) * 7 % cfg.vocab_size
               for n, _ in asks]
    for p, (_, k) in zip(prompts, asks):
        eng.submit(p, max_new_tokens=k)
    done = {r.id: r.generated for r in eng.run()}
    for i, (p, (_, k)) in enumerate(zip(prompts, asks)):
        assert done[i] == _direct_tokens(cfg, params, p, k, 32)
    assert set(eng.metrics.histogram("decode_rows").samples) == {1}


def test_oversize_reject_retries_slot_in_same_tick(rig):
    """Rejecting an oversize request must not waste the slot for the
    whole tick: the next queued request is seated immediately."""
    cfg, params = rig
    eng = ServingEngine(cfg, params, max_slots=1, max_seq=16)
    eng.submit(np.zeros(30, np.int32), max_new_tokens=4)   # 34 > 16
    fit_id = eng.submit(np.arange(4) % cfg.vocab_size, max_new_tokens=8)
    eng._tick()
    assert eng._slots[0] is not None and eng._slots[0].id == fit_id
    rejected = eng.completed[0]
    assert rejected.done and rejected.generated == []
    done = eng.run()
    assert sorted(r.id for r in done) == [0, 1]


def test_grow_oom_preempts_youngest_and_requeues(rig):
    """Grow-OOM preempts the youngest active request: pages released,
    generated tokens reset (greedy re-decode is identical), request
    back at the queue head — and the grow then succeeds."""
    from repro.serving.engine import Request

    cfg, params = rig
    # 2 slots x 16-token pages over a 2-page arena: seat both requests
    # with NO reservation so the first grow collides with a full arena
    eng = ServingEngine(cfg, params, max_slots=2, max_seq=16,
                        page_tokens=16)
    r0 = Request(0, np.zeros(16, np.int32), 4)
    r1 = Request(1, np.zeros(16, np.int32), 4)
    r1.generated.extend([7, 8])
    eng._slots[0], eng._slots[1] = r0, r1
    eng.arena.admit(0, 16, reserve_tokens=0)
    eng.arena.admit(1, 16, reserve_tokens=0)
    assert not eng.arena.can_admit(1)                      # full

    assert eng._grow(r0) is True                           # preempts r1
    assert eng.preemptions == 1
    assert eng.stats()["preemptions"] == 1
    assert eng._slots[1] is None
    assert eng._queue[0] is r1 and r1.generated == []
    assert 1 not in eng.arena.tables                       # pages freed
    assert eng.arena.tables[0].n_pages == 2                # grow landed


def test_grow_oom_with_no_other_victim_returns_false(rig):
    """When the requester is itself the youngest (or only) active
    request, _grow gives up: the request goes back to the queue and the
    tick continues instead of crashing."""
    from repro.serving.engine import Request

    cfg, params = rig
    eng = ServingEngine(cfg, params, max_slots=1, max_seq=16,
                        page_tokens=16)
    # fill the 1-page arena with a foreign table so the grow cannot fit
    eng.arena.admit(99, 16, reserve_tokens=0)
    r0 = Request(0, np.zeros(16, np.int32), 4)
    eng._slots[0] = r0
    eng.arena.tables[0] = eng.arena.tables.pop(99)         # alias pages
    eng.arena.tables[0].request_id = 0

    assert eng._grow(r0) is False
    assert eng._slots[0] is None and eng._queue[0] is r0
    assert eng.preemptions == 1                            # self-preempt


def test_grow_oom_prefers_other_victim_over_self(rig):
    """Livelock regression: when the GROWER is the youngest active
    request, _grow must evict the other (older) request rather than
    preempt itself — the old youngest-wins rule evicted the grower,
    which then re-seated, re-grew, and re-evicted itself forever while
    the older request's pages sat untouched."""
    from repro.serving.engine import Request

    cfg, params = rig
    eng = ServingEngine(cfg, params, max_slots=2, max_seq=16,
                        page_tokens=16)
    r0 = Request(0, np.zeros(16, np.int32), 4)      # older
    r1 = Request(1, np.zeros(16, np.int32), 4)      # younger = grower
    eng._slots[0], eng._slots[1] = r0, r1
    eng.arena.admit(0, 16, reserve_tokens=0)
    eng.arena.admit(1, 16, reserve_tokens=0)
    assert not eng.arena.can_admit(1)                      # full

    assert eng._grow(r1) is True                    # r0 evicted, not r1
    assert eng.preemptions == 1
    assert eng._slots[0] is None and eng._slots[1] is r1
    assert eng._queue[0] is r0 and 0 not in eng.arena.tables
    assert eng.arena.tables[1].n_pages == 2                # grow landed


def test_request_is_frozen_public_record(rig):
    """Identity fields of the public Request are immutable; lifecycle
    state is engine-advanced, and `done` reflects it."""
    import dataclasses

    from repro.serving import DONE, QUEUED, Request

    r = Request(3, np.arange(4, dtype=np.int32), 8)
    assert r.state == QUEUED and not r.done
    with pytest.raises(dataclasses.FrozenInstanceError):
        r.max_new_tokens = 99
    with pytest.raises(dataclasses.FrozenInstanceError):
        r.state = DONE
    r.generated.extend([1, 2])                   # token list is mutable
    assert r.total_tokens == 6


def test_public_lifecycle_submit_poll_step(rig):
    """submit()/poll()/step() drive a request queued → prefill →
    decoding → done with TTFT/ITL recorded against the injected clock."""
    from repro.serving import DECODING, DONE, QUEUED

    cfg, params = rig
    t = {"now": 100.0}
    eng = ServingEngine(cfg, params, max_slots=1, max_seq=64,
                        clock=lambda: t["now"])
    rid = eng.submit(np.arange(5) % cfg.vocab_size, max_new_tokens=3)
    assert eng.poll(rid).state == QUEUED
    assert eng.poll(rid).arrival_s == 100.0
    t["now"] = 100.5
    assert eng.step() is True                    # admit + prefill + decode
    req = eng.poll(rid)
    assert req.state in (DECODING, DONE)
    assert req.first_token_s == 100.5
    while eng.step():
        pass
    req = eng.poll(rid)
    assert req.state == DONE and req.done and len(req.generated) == 3
    assert req.finished_s is not None
    s = eng.stats()
    assert s["ttft_p50_s"] == pytest.approx(0.5)
    assert s["ttft_p99_s"] == pytest.approx(0.5)
    assert eng.poll(12345) is None


def test_multi_bin_kv_locality_and_moves(rig):
    """With several KV bins, admission places each request's groups via
    Scheduler.update(); a decode group landing off the prefill bin
    migrates the pages and charges CostModel.transfer_time (kv_moves /
    kv_move_seconds), and HEFT's transfer charging keeps decode
    co-located (zero moves)."""
    cfg, params = rig
    prompts = [np.arange(8) % cfg.vocab_size for _ in range(4)]

    heft = ServingEngine(cfg, params, max_slots=2, max_seq=64, bins=2)
    for p in prompts:
        heft.submit(p, max_new_tokens=2)
    done = heft.run()
    assert len(done) == 4 and all(r.done for r in done)
    assert heft.stats()["bins"] == 2
    assert heft.kv_moves == 0                    # decode follows its KV

    bal = ServingEngine(cfg, params, max_slots=2, max_seq=64, bins=2,
                        scheduler="balanced")
    for p in prompts:
        bal.submit(p, max_new_tokens=2)
    done = bal.run()
    assert len(done) == 4
    # balanced ignores the prefill→decode edge, so the heavy decode
    # group lands on the other bin and the KV span is moved (charged)
    assert bal.kv_moves > 0
    assert bal.stats()["kv_move_seconds"] > 0.0


def test_engine_add_and_retire_bin(rig):
    """add_bin()/retire_bin() feed SchedulerUpdate bin events at the
    next tick: joins widen the pool, drains migrate or preempt the
    drained bin's residents and drop its arena."""
    cfg, params = rig
    eng = ServingEngine(cfg, params, max_slots=2, max_seq=64, bins=1)
    assert eng.stats()["bins"] == 1
    eng.add_bin("kv1")
    eng.submit(np.arange(6) % cfg.vocab_size, max_new_tokens=2)
    eng.step()
    assert eng.stats()["bins"] == 2
    eng.retire_bin("kv1")
    while eng.step():
        pass
    assert eng.stats()["bins"] == 1
    assert eng.stats()["completed"] == 1
    assert eng.arena.pages_in_use == 0


# ----------------------------------------------------------------------
# engine spans (repro.obs.span): names and stats are a contract with the
# benchmark's readers (bench/common/spans.py)
# ----------------------------------------------------------------------
def test_engine_spans_nest_in_ticks(rig, tmp_path):
    """One ``engine.tick`` per step; every other engine span lies inside
    a tick and names its request, or for the tick's one decode call and
    its one read, its rows; placement is entered once per admission and
    once per retirement."""
    cfg, params = rig
    eng = ServingEngine(cfg, params, max_slots=2, max_seq=64)
    asks = ((5, 3), (6, 2), (5, 4))               # (prompt, new tokens)
    ids = [eng.submit(np.arange(n) % cfg.vocab_size, max_new_tokens=k)
           for n, k in asks]

    def drive():
        steps = 1
        while eng.step():
            steps += 1
        return steps

    steps, got = profiled(drive, tmp_path)

    def named(name):
        return [s for s in got if s.name == name]

    ticks = named("engine.tick")
    assert len(ticks) == steps == eng.ticks
    # seated at the tick's end: the two-token request retired in it
    assert (ticks[0].stats["active"], ticks[0].stats["queued"]) == (1, 1)
    assert (ticks[-1].stats["active"], ticks[-1].stats["queued"]) == (0, 0)
    inner = [s for s in got if s.name.startswith("engine.")
             and s.name != "engine.tick"]
    assert all(any(t.holds(s) for t in ticks) for s in inner)
    assert all(s.stats["request"] in ids if "request" in s.stats
               else s.name in ("engine.decode", "engine.read")
               and s.stats["rows"] >= 1
               for s in inner if s.name != "engine.schedule")

    assert eng.preemptions == 0
    sched = named("engine.schedule")
    assert sorted(s.stats["event"] for s in sched) == ["admit"] * 3 + ["finish"] * 3
    assert sorted(s.stats["request"] for s in sched) == sorted(ids * 2)
    admits = [s.stats["nodes"] for s in sched if s.stats["event"] == "admit"]
    assert admits == sorted(admits) and len(set(admits)) == 3   # A3 growth

    prefills = named("engine.prefill")
    assert sorted((s.stats["request"], s.stats["tokens"]) for s in prefills) \
        == sorted(zip(ids, (n for n, _ in asks)))
    # one decode call and one read per tick with seated rows: both
    # requests, then the second and the third, then the third alone
    decodes, reads = named("engine.decode"), named("engine.read")
    assert [d.stats["rows"] for d in decodes] == [2, 2, 1, 1]
    assert sum(d.stats["rows"] for d in decodes) == sum(k - 1 for _, k in asks)
    assert eng.metrics.histogram("decode_rows").samples == [2, 2, 1, 1]
    assert [sum(t.holds(d) for d in decodes) for t in ticks] == [1] * steps
    assert len(reads) == len(prefills) + len(decodes)
    assert sorted(r.stats["request"] for r in reads if "request" in r.stats) \
        == sorted(ids)                             # each prefill's token
    for d in decodes:                              # its rows' read follows
        r = next(r for r in reads if r.start_ns >= d.end_ns)
        assert r.stats == {"rows": d.stats["rows"]}


def test_engine_spans_reach_flight_recorder(rig):
    """With ``obs=``, the engine's spans also land in the ring, so a
    fault dump shows them."""
    from repro.obs import SpanRecorder, timeline_from_recorder, validate_timeline

    cfg, params = rig
    rec = SpanRecorder()
    eng = ServingEngine(cfg, params, max_slots=1, max_seq=64, obs=rec)
    rid = eng.submit(np.arange(5) % cfg.vocab_size, max_new_tokens=3)
    eng.run()
    spans = rec.spans()
    assert [s["name"] for s in spans].count("engine.tick") == eng.ticks
    assert {s["name"] for s in spans} == {
        "engine.tick", "engine.schedule", "engine.prefill", "engine.decode",
        "engine.read"}
    assert all(s["request"] == rid for s in spans if "request" in s)
    assert validate_timeline(timeline_from_recorder(rec)) == []


@pytest.mark.parametrize("program,reader", [
    ("_prefill", "chat.prefill_ms"),
    ("_decode", "batch.decode_roofline"),
])
def test_engine_program_names_match_bench_readers(rig, program, reader):
    """The benchmark finds the engine's compiled programs by name in the
    device trace (``jit_prefill``, ``jit_decode_step``): a rename must
    fail here rather than silence its reader."""
    import json
    import jax.numpy as jnp
    from pathlib import Path

    from bench.common import peaks
    from bench.common.harness import Readings, load_module
    from bench.common.trace import Trace
    from repro.serving import engine

    cfg, params = rig
    # the engine's stacked cache, donated: the prefill writes its fresh
    # cache of 32 positions over row 1, the decode step every row
    eng = engine.ServingEngine(cfg, params, max_slots=2, max_seq=32)
    if program == "_prefill":
        args = (jnp.zeros((1, 4), jnp.int32), 32, eng._rows, 1)
    else:
        args = (jnp.zeros((2,), jnp.int32), eng._rows)
    text = getattr(engine, program).lower(cfg, params, *args).as_text()
    name = text.split("module @", 1)[1].split()[0]
    assert name == {"_prefill": "jit_prefill", "_decode": "jit_decode_step"}[program]
    # each leaf of the stacked cache aliases an output
    assert text.count("tf.aliasing_output") == len(jax.tree.leaves(eng._rows))

    # the reader, over a window holding one execution of that program as
    # the chip's trace names it
    root = Path(__file__).resolve().parents[1]
    raw = {"devices": {"0": {"ops": [], "modules": [[f"{name}(4711)", 100, 50]]}},
           "host": [["bench.window.begin", 0, 0], ["bench.window.end", 1000, 0]]}
    conf = json.loads((root / "bench/configs/phi3-mini-3.8b/config.json").read_text())
    counts = {"decoded_tokens": 1, "decode_flops": 1.0, "decode_kv_bytes": 1.0}
    r = Readings(trace=Trace(raw), counts=counts, config=conf, traffic={},
                 peak=peaks.peak("TPU v5 lite"), chips=1)
    assert load_module(root / "bench" / "layers" / f"{reader}.py").read(r) is not None
