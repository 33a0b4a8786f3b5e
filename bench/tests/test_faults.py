"""A run with its timed path broken underneath comes out not correct:
every fault each cell can have, planted in the program, with the rest of
the run driven as on the chip (only the look for a chip is skipped).

A decode that returns its cache unchanged (state unchanged), half of the
slots' decode calls skipped (half the batch left out), a token altered
where it is produced.  The cells run on one chip, so there is no
exchange between chips to leave out."""
import json

import pytest

from bench.tests.conftest import tiny_root

# limits for the CPU size, set as on the chip from the readings of
# test_control.py at this size: the program's mean gap reads 0 to 4e-5
# over seeds 1-6, the control's 2.9e-3 to 5.9e-3
LIMITS = {"phi3-chat-steady": {"mean_logit_gap": {"limit": 3e-4}},
          "phi3-batch-saturated": {"mean_logit_gap": {"limit": 3e-4}}}


def _run(root, workload, capsys):
    from bench import run

    rc = run.main(["--workload", workload, "--seed", str(2**31 + 77),
                   "--seconds", "2"], root=root, require_tpu=False,
                  device_kind="TPU v5 lite")
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def root(tmp_path):
    return tiny_root(tmp_path, LIMITS)


def _decode_fault(kind):
    from repro.serving import engine

    real = engine._decode
    calls = {"n": 0}

    def broken(cfg, params, tok, caches):
        logits, new = real(cfg, params, tok, caches)
        calls["n"] += 1
        if kind == "state_unchanged":
            return logits, caches
        if kind == "token_altered":
            return logits.at[0, logits[0].argmax()].set(-1e9), new
        if kind == "half_batch" and calls["n"] % 2:
            return logits * 0, caches
        return logits, new

    return broken


@pytest.mark.parametrize("workload", ["phi3-chat-steady", "phi3-batch-saturated"])
def test_sound_serving_run_is_correct(root, workload, capsys):
    assert _run(root, workload, capsys)["correct"] is True


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "token_altered"])
def test_serving_fault_is_caught(root, fault, monkeypatch, capsys):
    from repro.serving import engine

    monkeypatch.setattr(engine, "_decode", _decode_fault(fault))
    assert _run(root, "phi3-chat-steady", capsys)["correct"] is False
