"""The one traffic generator: deterministic per seed, the stated
distributions, and the same work for every seed."""
import json
from collections import Counter

import numpy as np
import pytest

from bench.common import traffic
from bench.tests.conftest import REPO

CHAT = json.loads((REPO / "bench/traffic/chat-steady.json").read_text())
BATCH = json.loads((REPO / "bench/traffic/batch-saturated.json").read_text())
BIG_SEED = 2**31 + 12345


def test_same_seed_same_requests():
    a = traffic.schedule(CHAT, BIG_SEED, 51)
    b = traffic.schedule(CHAT, BIG_SEED, 51)
    assert a == b
    pa = traffic.prompts(a, BIG_SEED, 32064)
    pb = traffic.prompts(b, BIG_SEED, 32064)
    assert all(np.array_equal(x, y) for x, y in zip(pa, pb))
    other = traffic.prompts(a, BIG_SEED + 1, 32064)
    assert not all(np.array_equal(x, y) for x, y in zip(pa, other))
    assert traffic.schedule(BATCH, BIG_SEED + 1, 51) != traffic.schedule(BATCH, BIG_SEED, 51)


def test_fixed_order_with_blocks_of_one():
    """The chat mix replays one schedule: every seed sends the same sizes
    at the same times (the seed draws the token ids and the weights)."""
    assert CHAT["shuffle_block"] == 1
    assert traffic.schedule(CHAT, 1, 51) == traffic.schedule(CHAT, BIG_SEED, 51)


@pytest.mark.parametrize("mix", [CHAT, BATCH], ids=["open", "closed"])
def test_every_seed_same_work(mix):
    runs = [traffic.schedule(mix, s, 51) for s in (1, 2, BIG_SEED)]
    for key in ("prompt_tokens", "output_tokens"):
        counts = [Counter(getattr(r, key) for r in run) for run in runs]
        assert counts[0] == counts[1] == counts[2]
    block = mix["shuffle_block"]
    for lo in range(0, len(runs[0]), block):
        sets = [sorted((r.prompt_tokens, r.output_tokens) for r in run[lo:lo + block])
                for run in runs]
        assert sorted(x for x, _ in sets[0]) == sorted(x for x, _ in sets[1])


def test_prompt_lengths_in_stated_shares():
    reqs = traffic.schedule(CHAT, 5, 100)
    n = len(reqs)
    c = Counter(r.prompt_tokens for r in reqs)
    for length, w in zip(CHAT["prompt_tokens"], CHAT["prompt_weights"]):
        assert abs(c[length] - w * n) <= 1
    assert set(c) <= set(CHAT["prompt_tokens"])


def test_output_lengths_lognormal_and_clipped():
    out = traffic.output_lengths(CHAT, 2001)
    o = CHAT["output_tokens"]
    assert np.median(out) == o["median"]
    assert min(out) == o["min"] and max(out) == o["max"]
    # the clip lies beyond the quartiles, whose logs spread by sigma
    q1, q3 = np.percentile(np.log(out), [25, 75])
    assert abs((q3 - q1) / 1.349 - o["sigma"]) < 0.1


def test_open_loop_rate_and_window():
    reqs = traffic.schedule(CHAT, 9, 51)
    assert len(reqs) == round(CHAT["rate_per_s"] * 51)
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues) and dues[0] > 0
    assert dues[-1] == pytest.approx(51)
    gaps = np.diff([0.0] + dues)
    # exponential gaps: the standard deviation is about the mean
    assert 0.8 < gaps.std() / gaps.mean() < 1.1


def test_closed_loop_has_enough_requests():
    reqs = traffic.schedule(BATCH, 9, 51)
    assert len(reqs) >= BATCH["clients"] + BATCH["max_rate_per_s"] * 51
    assert all(r.due_s == 0 for r in reqs)
