"""The one generator of traffic: it reads a mix's parameters from its data
file (``bench/traffic/<mix>.json``) and turns them into requests.

Every seed gets the same set of sizes and arrival gaps; the seed only
changes their order, within blocks of ``shuffle_block`` requests (with
blocks of one, not at all: the mix replays one schedule), and the token
ids.  So two seeds do the same work, and the load over each block of the
window is the same, which keeps the spread between seeds close to the
spread between two runs of one seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

#: the fixed order every seed's blocks are cut from
_BASE_SEED = 20220317


@dataclass(frozen=True)
class Request:
    due_s: float          # open loop: seconds after the window opens
    prompt_tokens: int
    output_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _counts(n: int, weights) -> list[int]:
    """Largest-remainder split of ``n`` by ``weights``."""
    w = np.asarray(weights, float) / sum(weights)
    raw = w * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[: n - out.sum()]:
        out[i] += 1
    return out.tolist()


def prompt_lengths(mix: dict, n: int) -> list[int]:
    out = []
    for length, c in zip(mix["prompt_tokens"], _counts(n, mix["prompt_weights"])):
        out += [int(length)] * c
    return out


def output_lengths(mix: dict, n: int) -> list[int]:
    """Lognormal by its median and sigma, read at ``n`` evenly spaced
    quantiles and clipped to [min, max]."""
    o = mix["output_tokens"]
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    v = o["median"] * np.exp(o["sigma"] * z)
    return np.clip(np.rint(v), o["min"], o["max"]).astype(int).tolist()


def arrival_gaps(n: int, seconds: float) -> list[float]:
    """Exponential gaps read at ``n`` evenly spaced quantiles, scaled so
    that the ``n``-th request is due when the window closes."""
    g = -np.log1p(-_quantiles(n))
    return (g * (seconds / g.sum())).tolist()


def request_count(mix: dict, seconds: float) -> int:
    if mix["kind"] == "open_loop":
        return max(1, round(mix["rate_per_s"] * seconds))
    if mix["kind"] == "closed_loop":
        return mix["clients"] + math.ceil(mix["max_rate_per_s"] * seconds)
    raise ValueError(f"mix kind {mix['kind']!r} has no requests")


def _block_shuffle(rng: np.random.Generator, items: list, block: int) -> list:
    out = list(items)
    for lo in range(0, len(out), block):
        part = out[lo: lo + block]
        out[lo: lo + block] = [part[i] for i in rng.permutation(len(part))]
    return out


def schedule(mix: dict, seed: int, seconds: float) -> list[Request]:
    """The requests of one run, in the order they are sent."""
    n = request_count(mix, seconds)
    base = np.random.default_rng(_BASE_SEED)
    rng = np.random.default_rng(seed)
    block = mix.get("shuffle_block", n)
    columns = [prompt_lengths(mix, n), output_lengths(mix, n)]
    if mix["kind"] == "open_loop":
        columns.append(arrival_gaps(n, seconds))
    # one fixed interleaving, then the seed's order inside each block
    columns = [[c[i] for i in base.permutation(n)] for c in columns]
    columns = [_block_shuffle(rng, c, block) for c in columns]
    dues = (np.cumsum(columns[2]).tolist() if mix["kind"] == "open_loop"
            else [0.0] * n)
    return [Request(d, p, o) for d, p, o in zip(dues, columns[0], columns[1])]


def prompts(reqs: list[Request], seed: int, vocab: int) -> list[np.ndarray]:
    """Token ids of every prompt, drawn from the seed."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab, size=r.prompt_tokens, dtype=np.int32)
            for r in reqs]
