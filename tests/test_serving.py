"""Serving engine: continuous batching + paged arena integration."""
import dataclasses

import jax
import numpy as np
import pytest

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
