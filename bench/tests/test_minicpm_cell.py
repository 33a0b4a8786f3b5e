"""The minicpm-2b cell at a size the CPU holds (the tier-1 tests' tiny
MiniCPM: d 64, 2 layers, 4 heads of 16, d_ff 160, vocabulary 1009,
tied, the three muP multipliers): a sound run reads correct, the
control reads wider than the program, and a run whose rows each decode
against another row's cache reads not correct, so the weights rule
leaves the served tokens depending on the cache and the check bites."""
import json

import numpy as np
import pytest

from bench.common import serving
from bench.common.harness import load_json, load_module
from bench.tests.conftest import REPO, TINY_CHAT, tiny_root

CELL = "minicpm-2b-chat-steady"
MINICPM = REPO / "bench/configs/minicpm-2b"
TINY_MINICPM = {"hidden_size": 64, "intermediate_size": 160,
                "num_hidden_layers": 2, "num_attention_heads": 4,
                "num_key_value_heads": 4, "vocab_size": 1009,
                "dim_model_base": 16}
SERVING = {"slots": 4, "max_seq": 48, "reference_batch": 2}
# the limit at this size, set as on the chip (lower^0.4 x upper^0.6) from
# the readings of ``_readings``: the program's mean gap reads 1.7e-7 to
# 1.9e-5 over seeds 1-6, the control's 4.2e-4 to 1.1e-3
LIMITS = {CELL: {"mean_logit_gap": {"limit": 1.2e-4}}}


def tiny_conf() -> dict:
    conf = load_json(MINICPM / "config.json") | TINY_MINICPM
    conf["serving"] = conf["serving"] | SERVING
    return conf


@pytest.fixture
def root(tmp_path):
    root = tiny_root(tmp_path, LIMITS)
    (root / "bench/configs/minicpm-2b/config.json").write_text(
        json.dumps(tiny_conf()))
    mix = root / "bench/traffic/chat-steady-minicpm2b.json"
    mix.write_text(json.dumps(json.loads(mix.read_text()) | TINY_CHAT))
    return root


def _run(root, capsys):
    from bench import run

    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 91),
                   "--seconds", "2"], root=root, require_tpu=False,
                  device_kind="TPU v5 lite")
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _rows_swapped():
    """The ragged decode step with every row decoding against the next
    row's cache (its K/V and its length), the new rows written back
    where they came from."""
    import jax
    import jax.numpy as jnp
    from repro.serving import engine

    real = engine._decode

    def roll(caches, by):
        return jax.tree.map(lambda a: jnp.roll(a, by, axis=1), caches)

    def swapped(cfg, params, tok, caches):
        logits, new = real(cfg, params, tok, roll(caches, 1))
        return logits, roll(new, -1)

    return swapped


def test_sound_minicpm_run_is_correct(root, capsys):
    result = _run(root, capsys)
    assert result["correct"] is True
    assert result["failed"] == 0


@pytest.mark.parametrize("rule,caught", [("cell", True), ("phi3", False)])
def test_rows_decoding_against_other_caches(root, rule, caught, monkeypatch,
                                            capsys):
    """Caught under the cell's weights rule.  Under phi3's (embedding at
    variance 1, every projection at 1/fan_in) the tied head gives each
    position its own token back whatever the cache holds, and the same
    fault passes the check."""
    from repro.serving import engine

    if rule == "phi3":
        ref = load_module(root / "bench/configs/minicpm-2b/reference.py")
        monkeypatch.setattr(ref, "EMBED_STD", 1.0)
        monkeypatch.setattr(ref, "WO_GAIN", 1.0)
        monkeypatch.setattr(ref, "W_DOWN_GAIN", 1.0)
    monkeypatch.setattr(engine, "_decode", _rows_swapped())
    assert _run(root, capsys)["correct"] is not caught


def _readings(seed: int) -> tuple[float, float]:
    from repro.serving import ServingEngine

    conf = tiny_conf()
    ref, system = load_module(MINICPM / "reference.py"), load_module(MINICPM / "system.py")
    w = ref.make_weights(conf, seed)
    eng = ServingEngine(system.program_config(conf), system.program_params(conf, w),
                        max_slots=4, max_seq=48)
    rng = np.random.default_rng(seed)
    for n in (8, 16, 24, 32):
        eng.submit(rng.integers(0, conf["vocab_size"], n, dtype=np.int32),
                   max_new_tokens=16)
    seqs = [(r.prompt, np.asarray(r.generated, np.int32))
            for r in sorted(eng.run(), key=lambda r: r.id)]
    program = serving.gap_numbers(ref.served_gaps(conf, w, seqs, margins=True))
    control = serving.gap_numbers(ref.served_gaps(conf, w, seqs, control=True,
                                                  margins=True))
    return program[serving.COMPARED], control[serving.COMPARED]


def test_control_reads_wider_than_the_program():
    readings = [_readings(s) for s in (1, 2, 3)]
    lower = max(p for p, _ in readings)
    upper = min(c for _, c in readings)
    assert upper >= 3 * lower, readings
