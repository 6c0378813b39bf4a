"""BENCHMARK.json and the files it names: the contract's shape, the
published widths, and a harness that is driven by data."""
import json
import re
import shutil
import subprocess
from pathlib import Path

import pytest

from benchmark.harness import spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# the models' own config.json, as published (the keys that fix a shape)
PUBLISHED = {
    "https://huggingface.co/deepseek-ai/deepseek-llm-7b-base/blob/main/"
    "config.json": dict(
        hidden_size=4096, intermediate_size=11008, num_attention_heads=32,
        num_key_value_heads=32, num_hidden_layers=30, vocab_size=102400,
        max_position_embeddings=4096, rms_norm_eps=1e-6, rope_theta=10000.0,
        tie_word_embeddings=False, torch_dtype="bfloat16",
        hidden_act="silu", initializer_range=0.02),
    "https://huggingface.co/mistralai/Mistral-7B-v0.1/blob/main/"
    "config.json": dict(
        hidden_size=4096, intermediate_size=14336, num_attention_heads=32,
        num_key_value_heads=8, num_hidden_layers=32, vocab_size=32000,
        max_position_embeddings=32768, rms_norm_eps=1e-5,
        rope_theta=10000.0, sliding_window=4096, tie_word_embeddings=False,
        torch_dtype="bfloat16", hidden_act="silu", initializer_range=0.02),
}


def test_top_level_keys_are_the_contracts():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_names_and_units_hold_only_the_allowed_characters():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        names += [w["name"], w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in metrics:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names), names
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert len({w["name"] for w in BENCH["workloads"]}) == len(
        BENCH["workloads"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_every_cell_reports_setup_another_end_metric_and_a_layer_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for w in BENCH["workloads"]:
        cell = spec.load(w["name"])
        mine = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in mine and len(mine) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in mine, (w["name"], m["name"])
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_is_the_published_one_but_for_depth(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    pub = PUBLISHED[entry["source"]]
    assert cfg["source"] == entry["source"]
    assert entry["file"].startswith("benchmark/configs/")
    changed = {k for k, v in pub.items() if cfg.get(k) != v}
    assert changed == set(entry["reduced"]) == {"num_hidden_layers"}
    assert cfg["reduced"]["num_hidden_layers"] == {
        "published": pub["num_hidden_layers"],
        "here": cfg["num_hidden_layers"]}
    assert cfg["deployment"] and cfg["assumed"] and cfg["limits"]


def test_a_configuration_kept_for_a_later_cell_is_published_but_for_depth():
    """The fleet cell's groundwork: its files are there, no cell runs them
    yet (PERF.md, Open questions), and the widths are the model's own."""
    file = "benchmark/configs/mistral-7b-v0.1.fleet-L4.json"
    cfg = json.loads((ROOT / file).read_text())
    assert file not in {c["file"] for c in BENCH["configs"]}
    pub = PUBLISHED[cfg["source"]]
    assert {k for k, v in pub.items() if cfg.get(k) != v} == {
        "num_hidden_layers"}
    assert cfg["strategy"] == {"sharding_stage": 3, "sharding_degree": 2,
                               "mp_degree": 2, "dp_degree": 1}
    mix = json.loads((ROOT / "benchmark/traffic" /
                      f"{cfg['aot_traffic']}.json").read_text())
    assert (mix["batch"], mix["sequence"]) == (2, 4096)
    degrees = cfg["strategy"]
    assert mix["batch"] % degrees["sharding_degree"] == 0
    assert cfg["num_attention_heads"] % degrees["mp_degree"] == 0
    assert cfg["num_key_value_heads"] % degrees["mp_degree"] == 0


def _copy(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


@pytest.mark.parametrize("victim", [
    "benchmark/configs/deepseek-llm-7b.serve-L8.json",
    "benchmark/traffic/backlog.json",
    "benchmark/layer_metrics/step_ms.py",
    "benchmark/end_metrics/serve_tok_s.py"])
def test_a_missing_file_is_refused(tmp_path, victim):
    root = _copy(tmp_path)
    (root / victim).unlink()
    with pytest.raises(SystemExit, match="no (file|reader)"):
        spec.load("deepseek7b.serve.backlog", root)


def test_variants_of_one_quantity_share_its_reader(tmp_path):
    root = _copy(tmp_path)
    base = spec.reader(root, "layer_metrics", "step_ms.rate")
    assert base.__name__ == "step_ms"
    assert spec.reader(root, "layer_metrics", "step_ms.fleet").__name__ == \
        "step_ms"                       # a later PR's variant: no new file
    # ... unless the variant brings a reader of its own
    (root / "benchmark/layer_metrics/step_ms.rate.py").write_text(
        "def read(run):\n    return 1.0\n")
    assert spec.reader(root, "layer_metrics", "step_ms.rate")(None) == 1.0
    with pytest.raises(SystemExit, match="no reader"):
        spec.reader(root, "layer_metrics", "nothing.rate")


def test_an_unknown_cell_is_refused():
    with pytest.raises(SystemExit, match="no workload"):
        spec.load("nobody.serves.this")


def test_new_files_and_entries_are_picked_up_with_no_edit(tmp_path):
    """A later PR's configuration, mix, cell and per-layer metric: new
    files, new entries in BENCHMARK.json, no file that is there edited."""
    root = _copy(tmp_path)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    cfg = json.loads((root / "benchmark/configs/"
                      "mistral-7b-v0.1.serve-L8.json").read_text())
    cfg["num_hidden_layers"] = 4
    (root / "benchmark/configs/new-model.serve-L4.json").write_text(
        json.dumps(cfg))
    mix = json.loads((root / "benchmark/traffic/chat.json").read_text())
    mix["rate_per_s"] = 2.0
    (root / "benchmark/traffic/slow_chat.json").write_text(json.dumps(mix))
    (root / "benchmark/layer_metrics/requests_seen.py").write_text(
        "def read(run):\n    return float(len(run.records)) or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "new-model.serve-L4", "source": cfg["source"],
        "file": "benchmark/configs/new-model.serve-L4.json",
        "reduced": ["num_hidden_layers"], "why": "a later PR's"})
    bench["workloads"].append({
        "name": "newmodel.serve.slow_chat", "config": "new-model.serve-L4",
        "traffic": "slow_chat", "chips": 1, "why": "a later PR's"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p95_ms", "gap_p95_ms"):
            m["workloads"].append("newmodel.serve.slow_chat")
    bench["per_layer"].append({
        "name": "requests_seen", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "ttft_p95_ms", "workloads": ["newmodel.serve.slow_chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load("newmodel.serve.slow_chat", root)
    assert cell.config["num_hidden_layers"] == 4
    assert cell.traffic["rate_per_s"] == 2.0
    assert [m["name"] for m in cell.per_layer] == ["requests_seen"]

    class Run:
        records = [1, 2, 3]
    assert spec.reader(root, "layer_metrics", "requests_seen")(Run) == 3.0
    assert all(p.read_bytes() == b for p, b in before.items())


def test_benchmark_alone_exits_non_zero_and_prints_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` the program is missing: no result."""
    root = _copy(tmp_path)
    shutil.copytree(ROOT / "tests" / "benchmark", root / "tests/benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        BENCH["command"] + ["--workload", "deepseek7b.serve.backlog",
                            "--seed", "1", "--seconds", "1", "--trace", "0",
                            "--rehearse"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin:/usr/local/bin:/opt/venv/bin",
             "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""
