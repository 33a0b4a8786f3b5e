"""The harness refuses to measure without a TPU, or on fewer chips than
the cell asks for: a non-zero exit and no result line."""
import os
import subprocess
import sys

from bench.tests.conftest import REPO


def test_cpu_only_platform_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi3-chat-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_too_few_chips_is_refused(tiny, capsys):
    import json

    from bench import run

    bench = json.loads((tiny / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "chat-4chip", "config": "phi3-mini-3.8b",
                               "traffic": "chat-steady", "chips": 4, "why": "test"})
    (tiny / "BENCHMARK.json").write_text(json.dumps(bench))
    rc = run.main(["--workload", "chat-4chip", "--seed", "1",
                   "--seconds", "1"], root=tiny, require_tpu=False,
                  device_kind="TPU v5 lite")
    assert rc != 0
    assert "{" not in capsys.readouterr().out


def test_no_result_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    files has no system to measure."""
    import shutil

    shutil.copytree(REPO / "bench", tmp_path / "bench")
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi3-chat-steady",
         "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
