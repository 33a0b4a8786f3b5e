"""MiniCPM-2B on the serving path: its three muP multipliers (the
embedding's, each residual branch's, the head input's) and its tied
head, served by the ragged stacked decode and compared with the
benchmark's plain reference (``bench/configs/minicpm-2b/reference.py``)
at a tiny shape on the CPU."""
import dataclasses
import math
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench.common.harness import load_json, load_module
from repro.configs import get_config, reduced
from repro.models import transformer
from repro.serving import engine

MINICPM = Path(__file__).resolve().parents[1] / "bench/configs/minicpm-2b"
#: d 64, 2 layers, 4 MHA heads of 16, d_ff 160, an odd vocabulary, tied
TINY = {"hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "vocab_size": 1009, "tie_word_embeddings": True, "scale_emb": 12,
        "scale_depth": 1.4, "dim_model_base": 16}
#: three requests of different prompt lengths, served together
PROMPTS, NEW_TOKENS = (5, 11, 17), (6, 4, 8)
#: the widest served logit's distance from the reference's, over the
#: widest reference logit.  Program and reference both compute in
#: float32 here (weights bfloat16, upcast), the program's K/V cache too,
#: so they differ only in the order of their sums (1.2e-6 at most); a
#: cache in bfloat16 alone would put 1e-3 to 7e-3 here, as wide as the
#: residual multiplier's effect at this depth (1.4 / sqrt(2) = 0.99,
#: whose removal reads 1.5e-3 to 5.6e-3).
TOL = 1e-4

SCALES = ("embed_scale", "residual_scale", "logit_scale")


@pytest.fixture(scope="module")
def tiny():
    ref = load_module(MINICPM / "reference.py")
    system = load_module(MINICPM / "system.py")
    conf = load_json(MINICPM / "config.json") | TINY
    conf["serving"] |= {"compute_dtype": "float32"}
    w = ref.make_weights(conf, 2**31 + 11)
    return conf, ref, system, w


def _served_logits(cfg, params, monkeypatch):
    """Each request's prompt, served tokens and the logits each served
    token was chosen from, read from the engine's prefill and its one
    decode call per tick over the stacked cache (kept in float32)."""
    init_cache = transformer.init_cache
    monkeypatch.setattr(transformer, "init_cache", lambda cfg, b, n, dtype=None:
                        init_cache(cfg, b, n, jnp.float32))
    eng = engine.ServingEngine(cfg, params, max_slots=4, max_seq=64)
    got: dict[int, list] = {}
    prefill, decode = engine._prefill, engine._decode

    def reading_prefill(cfg, params, tokens, max_seq, rows=None, slot=None):
        logits, rows = prefill(cfg, params, tokens, max_seq, rows, slot)
        got[eng._slots[slot].id] = [np.asarray(logits[0])]
        return logits, rows

    def reading_decode(cfg, params, tok, caches):
        seated = [(i, r.id) for i, r in enumerate(eng._slots) if r is not None]
        logits, caches = decode(cfg, params, tok, caches)
        for i, rid in seated:
            got[rid].append(np.asarray(logits[i]))
        return logits, caches

    monkeypatch.setattr(engine, "_prefill", reading_prefill)
    monkeypatch.setattr(engine, "_decode", reading_decode)
    rng = np.random.default_rng(3)
    for n, k in zip(PROMPTS, NEW_TOKENS):
        eng.submit(rng.integers(0, cfg.vocab_size, n, dtype=np.int32),
                   max_new_tokens=k)
    assert eng._rows is not None                  # the ragged stacked path
    done = sorted(eng.run(), key=lambda r: r.id)
    assert max(eng.metrics.histogram("decode_rows").samples) == len(PROMPTS)
    return [(r.prompt, np.asarray(r.generated), np.stack(got[r.id]))
            for r in done]


def _miss(tiny, cfg, monkeypatch) -> float:
    """The widest distance of a served logit from the reference's, over
    the widest reference logit."""
    conf, ref, system, w = tiny
    served = _served_logits(cfg, system.program_params(conf, w), monkeypatch)
    worst = 0.0
    for prompt, generated, got in served:
        assert len(got) == len(generated)
        seq = np.concatenate([prompt, generated[:-1]])[None]
        want = np.asarray(ref.logits(conf, w, seq))[0, len(prompt) - 1:]
        worst = max(worst, float(np.abs(got - want).max() / np.abs(want).max()))
    return worst


def test_served_logits_match_reference(tiny, monkeypatch):
    conf, _, system, _ = tiny
    assert _miss(tiny, system.program_config(conf), monkeypatch) < TOL


@pytest.mark.parametrize("scale", SCALES)
def test_each_scaling_matters(tiny, scale, monkeypatch):
    """Serving without any one of the multipliers misses the reference."""
    conf, _, system, _ = tiny
    cfg = system.program_config(conf)
    assert getattr(cfg, scale) != 1
    cfg = dataclasses.replace(cfg, **{scale: 1.0})
    assert _miss(tiny, cfg, monkeypatch) > TOL


@pytest.mark.parametrize("cut", [False, True])
def test_registry_keeps_the_scalings(cut):
    """The published multipliers: scale_emb 12, scale_depth 1.4 over the
    published 40 layers, dim_model_base 256 over hidden_size 2304; a cut
    to two layers keeps them."""
    cfg = get_config("minicpm-2b")
    if cut:
        cfg = reduced(cfg)
    assert cfg.tie_embeddings
    assert (cfg.embed_scale, cfg.logit_scale) == (12.0, 256 / 2304)
    assert math.isclose(cfg.residual_scale, 1.4 / math.sqrt(40))
