"""Tests of the benchmark, on the CPU at tiny sizes:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests -p no:cacheprovider

Nothing here loads the TPU's library."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (REPO / "src", REPO):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: a phi3-shaped model small enough for the CPU
TINY_PHI3 = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
             "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
             "vocab_size": 256}
TINY_CHAT = {"rate_per_s": 6.0, "prompt_tokens": [8, 16, 24, 32],
             "output_tokens": {"median": 4, "sigma": 1.0, "min": 2, "max": 8},
             "trace_seconds": 1, "check_requests": 4}


def tiny_root(tmp: Path, limits: dict | None = None) -> Path:
    """A checkout holding the benchmark with every cell cut to CPU size:
    the same files, the sizes in the data files replaced."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp)

    def patch(rel: str, upd: dict) -> None:
        path = tmp / rel
        data = json.loads(path.read_text())
        data.update(upd)
        path.write_text(json.dumps(data))

    patch("bench/configs/phi3-mini-3.8b/config.json", TINY_PHI3)
    conf = json.loads((tmp / "bench/configs/phi3-mini-3.8b/config.json").read_text())
    conf["serving"].update(slots=2, max_seq=48, reference_batch=2)
    (tmp / "bench/configs/phi3-mini-3.8b/config.json").write_text(json.dumps(conf))
    patch("bench/traffic/chat-steady.json", TINY_CHAT)
    patch("bench/traffic/batch-saturated.json",
          {k: v for k, v in TINY_CHAT.items() if k != "rate_per_s"}
          | {"clients": 4, "max_rate_per_s": 20.0})
    (tmp / "bench/limits").mkdir(exist_ok=True)
    for name, lim in (limits or {}).items():
        (tmp / "bench/limits" / f"{name}.json").write_text(json.dumps(lim))
    return tmp


@pytest.fixture
def tiny(tmp_path):
    return tiny_root(tmp_path)
