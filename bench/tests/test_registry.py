"""A configuration, a traffic mix and a per-layer metric are added with
new files and new BENCHMARK.json entries alone: no file of the harness
changes."""
import json
import shutil

from bench.common import harness


def test_new_cell_from_files_alone(tiny, capsys):
    from bench import run

    # a second configuration: its own directory, beside the others
    shutil.copytree(tiny / "bench/configs/phi3-mini-3.8b",
                    tiny / "bench/configs/phi3-copy")
    conf_file = tiny / "bench/configs/phi3-copy/config.json"
    conf = json.loads(conf_file.read_text())
    conf.update(name="phi3-copy", num_hidden_layers=1)
    conf_file.write_text(json.dumps(conf))
    # a traffic mix: a data file
    mix = json.loads((tiny / "bench/traffic/chat-steady.json").read_text())
    mix["rate_per_s"] = 3.0
    (tiny / "bench/traffic/chat-slow.json").write_text(json.dumps(mix))
    # a per-layer metric: a reader of its own
    (tiny / "bench/layers/copy.tokens.py").write_text(
        "def read(r):\n    return r.counts.get('output_tokens')\n")
    (tiny / "bench/limits/copy-chat.json").write_text(
        json.dumps({"mean_logit_gap": {"limit": 10.0}}))
    bench = json.loads((tiny / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "phi3-copy", "source": "test",
                             "file": "bench/configs/phi3-copy/config.json",
                             "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({"name": "copy-chat", "config": "phi3-copy",
                               "traffic": "chat-slow", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "copy.tokens", "unit": "tokens",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine", "moves": "ttft_p95_ms",
                               "workloads": ["copy-chat"]})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p95_ms", "itl_p95_ms"):
            m["workloads"].append("copy-chat")
    (tiny / "BENCHMARK.json").write_text(json.dumps(bench))

    _, cell = harness.make_cell(tiny, "copy-chat", 5, 2.0, True)
    assert cell.config["num_hidden_layers"] == 1
    assert cell.traffic["rate_per_s"] == 3.0
    assert [m["name"] for m in harness.metrics_of(bench, "copy-chat", True)] \
        == ["copy.tokens"]
    rc = run.main(["--workload", "copy-chat", "--seed", "5", "--seconds", "2",
                   "--trace", "1"], root=tiny, require_tpu=False,
                  device_kind="TPU v5 lite")
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["metrics"]["copy.tokens"]["value"] > 0
    assert result["correct"] is True
