"""BENCHMARK.json and the files it names: the contract's shape, the
published widths and the rule of the cut (``spec.check_cut`` over
``benchmark/published/``), and a harness that is driven by data."""
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
def test_configuration_is_the_published_one_but_for_its_cut(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert entry["file"].startswith("benchmark/configs/")
    pub = spec.published(entry["source"])
    spec.check_cut(entry, cfg, pub)
    # no width is ever cut, whatever a later rule allows
    widths = set(pub["keys"]) - set(pub["counts"])
    assert all(cfg[k] == pub["keys"][k] for k in widths)
    assert set(entry["reduced"]) <= set(pub["counts"])
    assert cfg["deployment"] and cfg["assumed"] and cfg["limits"]


def test_a_configuration_kept_for_a_later_cell_is_published_but_for_depth():
    """The fleet cell's groundwork: its files are there, no cell runs them
    yet (PERF.md, Open questions), and the widths are the model's own:
    the rule passes with the entry the file would have."""
    file = "benchmark/configs/mistral-7b-v0.1.fleet-L4.json"
    cfg = json.loads((ROOT / file).read_text())
    assert file not in {c["file"] for c in BENCH["configs"]}
    spec.check_cut({"source": cfg["source"], "file": file,
                    "reduced": ["num_hidden_layers"]},
                   cfg, spec.published(cfg["source"]))
    assert cfg["strategy"] == {"sharding_stage": 3, "sharding_degree": 2,
                               "mp_degree": 2, "dp_degree": 1}
    mix = json.loads((ROOT / "benchmark/traffic" /
                      f"{cfg['aot_traffic']}.json").read_text())
    assert (mix["batch"], mix["sequence"]) == (2, 4096)
    degrees = cfg["strategy"]
    assert mix["batch"] % degrees["sharding_degree"] == 0
    assert cfg["num_attention_heads"] % degrees["mp_degree"] == 0
    assert cfg["num_key_value_heads"] % degrees["mp_degree"] == 0


# -- the rule of the cut, on made-up files -------------------------------------

SRC = "https://example.org/made-up/model-x/blob/main/config.json"
MODEL_X = {"source": SRC, "model": "model-x", "keys": dict(
    hidden_size=1024, intermediate_size=4096, moe_intermediate_size=512,
    num_hidden_layers=42, num_attention_heads=16, num_key_value_heads=4,
    n_routed_experts=512, num_experts_per_tok=8, vocab_size=160000,
    sliding_window=None, rms_norm_eps=1e-6, model_type="model_x"),
    "counts": ["num_hidden_layers", "vocab_size", "num_attention_heads",
               "num_key_value_heads", "n_routed_experts"]}


def model_x(reduced=None, **changed):
    """(entry, configuration file) of the made-up model: the published
    keys with ``changed`` laid over them and ``reduced`` recorded."""
    reduced = reduced or {}
    cfg = {**MODEL_X["keys"], "source": SRC, "reduced": reduced,
           "deployment": "the whole model on one chip", **changed}
    return {"source": SRC, "reduced": sorted(reduced)}, cfg


def share(key, here, chips=None):
    rec = {"published": MODEL_X["keys"][key], "here": here}
    return {key: dict(rec, chips=chips) if chips else rec}


SHARE = {**share("num_hidden_layers", 7), **share("n_routed_experts", 128, 4),
         **share("vocab_size", 40000, 4)}
SHARE_KEYS = dict(num_hidden_layers=7, n_routed_experts=128,
                  vocab_size=40000)
CUTS = {
    "a_width_changed": (model_x(intermediate_size=2048),
                        "'intermediate_size' is 2048, published 4096: a "
                        "width"),
    "experts_per_token_changed": (model_x(num_experts_per_tok=4),
                                  "'num_experts_per_tok'.*a width or a "
                                  "constant"),
    "a_count_changed_and_not_listed": (model_x(num_hidden_layers=7),
                                       "'num_hidden_layers'.*does not list"),
    "listed_and_not_changed": (model_x(share("num_hidden_layers", 42)),
                               "lists 'num_hidden_layers', which is the "
                               "published"),
    "here_not_under_published": (
        model_x(share("num_hidden_layers", 50), num_hidden_layers=50),
        "'num_hidden_layers' is 50: a cut is a whole number"),
    "recorded_another_number": (
        model_x(share("num_hidden_layers", 8), num_hidden_layers=7),
        "records 'num_hidden_layers'"),
    "a_share_without_chips": (
        model_x(share("n_routed_experts", 128), n_routed_experts=128),
        "'n_routed_experts' is the chip's share.*\"chips\""),
    "a_share_too_small_for_its_chips": (
        model_x(share("n_routed_experts", 100, 4), n_routed_experts=100),
        "100 held on each of 4 chips is not the published 512"),
    "a_share_of_fewer_chips_than_said": (
        model_x(share("n_routed_experts", 256, 4), n_routed_experts=256),
        "256 held on each of 4 chips"),
    "two_numbers_of_chips": (
        model_x({**share("n_routed_experts", 128, 4),
                 **share("vocab_size", 80000, 2)},
                n_routed_experts=128, vocab_size=80000),
        "one number of chips"),
    "no_deployment": (model_x(deployment=""), "'deployment'"),
    "another_source": (model_x(source=SRC + "?"), "'source'"),
    "held_whole": (model_x(), None),
    "depth_alone": (model_x(share("num_hidden_layers", 7),
                            num_hidden_layers=7), None),
    "depth_experts_and_vocabulary": (model_x(SHARE, **SHARE_KEYS), None),
}


@pytest.mark.parametrize("case", sorted(CUTS))
def test_check_cut_holds_a_configuration_to_its_source(case):
    (entry, cfg), refused = CUTS[case]
    if refused is None:
        spec.check_cut(entry, cfg, MODEL_X)
    else:
        with pytest.raises(spec.SpecError, match=refused):
            spec.check_cut(entry, cfg, MODEL_X)


def test_a_source_no_published_file_has_is_refused(tmp_path):
    with pytest.raises(spec.SpecError, match="no file under benchmark/"
                                             "published/ has the source"):
        spec.published(SRC)
    root = _copy(tmp_path)
    (root / "benchmark/published/model-x.json").write_text(
        json.dumps(MODEL_X))
    assert spec.published(SRC, root)["model"] == "model-x"


def test_load_refuses_a_configuration_whose_width_differs(tmp_path):
    """A run starts with ``spec.load``: a width halved never runs."""
    root = _copy(tmp_path)
    file = root / "benchmark/configs/mistral-7b-v0.1.serve-L8.json"
    cfg = json.loads(file.read_text())
    cfg["intermediate_size"] //= 2
    file.write_text(json.dumps(cfg))
    with pytest.raises(SystemExit, match="'intermediate_size' is 7168, "
                                         "published 14336"):
        spec.load("mistral7b.serve.chat", root)
    assert spec.load("deepseek7b.serve.backlog", root)


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
    """A later PR's model (its published keys), two configurations of it
    (one held whole, ``reduced: []``, and one chip's share of a
    deployment: 7 of 42 layers, 128 of 512 experts and a quarter of the
    vocabulary over 4 chips), a mix, two cells and a per-layer metric:
    new files, new entries in BENCHMARK.json, no file that is there
    edited."""
    root = _copy(tmp_path)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    (root / "benchmark/published/model-x.json").write_text(
        json.dumps(MODEL_X))
    extra = dict(assumed=["engine.*"], limits={"unfinished_requests": 0},
                 engine={"max_seq_len": 1024})
    _, whole = model_x()
    _, part = model_x(SHARE, **SHARE_KEYS)
    part["deployment"] = "6 stages of 7 layers, each layer over 4 chips"
    (root / "benchmark/configs/model-x.serve.json").write_text(
        json.dumps(dict(whole, **extra)))
    (root / "benchmark/configs/model-x.serve-L7-ep4.json").write_text(
        json.dumps(dict(part, **extra)))
    mix = json.loads((root / "benchmark/traffic/chat.json").read_text())
    mix["rate_per_s"] = 2.0
    (root / "benchmark/traffic/slow_chat.json").write_text(json.dumps(mix))
    (root / "benchmark/layer_metrics/requests_seen.py").write_text(
        "def read(run):\n    return float(len(run.records)) or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"] += [
        {"name": "model-x.serve", "source": SRC,
         "file": "benchmark/configs/model-x.serve.json",
         "reduced": [], "why": "a later PR's, held whole"},
        {"name": "model-x.serve-L7-ep4", "source": SRC,
         "file": "benchmark/configs/model-x.serve-L7-ep4.json",
         "reduced": sorted(SHARE), "why": "a later PR's, a chip's share"}]
    cells = {"modelx.serve.slow_chat": "model-x.serve",
             "modelx-ep4.serve.slow_chat": "model-x.serve-L7-ep4"}
    for name, config in cells.items():
        bench["workloads"].append({
            "name": name, "config": config, "traffic": "slow_chat",
            "chips": 1, "why": "a later PR's"})
    for m in bench["end_to_end"]:
        if m["name"] == "gap_p95_ms":
            m["workloads"] += list(cells)
    bench["per_layer"].append({
        "name": "requests_seen", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "gap_p95_ms", "workloads": list(cells)})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load("modelx.serve.slow_chat", root)
    assert cell.config["num_hidden_layers"] == 42
    assert cell.config["reduced"] == {}
    cut = spec.load("modelx-ep4.serve.slow_chat", root)
    assert (cut.config["num_hidden_layers"], cut.config["n_routed_experts"],
            cut.config["vocab_size"]) == (7, 128, 40000)
    assert cut.config["num_experts_per_tok"] == 8      # the router's width
    assert cell.traffic["rate_per_s"] == 2.0
    assert [m["name"] for m in cell.per_layer] == ["requests_seen"]
    assert {m["name"] for m in cut.end_to_end} == {"gap_p95_ms", "setup_s"}

    class Run:
        records = [1, 2, 3]
    assert spec.reader(root, "layer_metrics", "requests_seen")(Run) == 3.0
    assert all(p.read_bytes() == b for p, b in before.items())
    assert spec.load("mistral7b.serve.chat", root)     # the old cells too


def test_aot_main_describes_its_family_and_names_the_others(monkeypatch,
                                                             capsys):
    """``python3 -m benchmark.harness.aot`` used to fail on the first
    configuration of another family: it describes the Llama shape's and
    says which files it leaves (nothing compiles here: stubs)."""
    from benchmark.harness import aot
    seen = []
    monkeypatch.setattr(aot, "describe_v5e", lambda: None)
    monkeypatch.setattr(aot, "serve_step", lambda cfg, topo, mixed=True:
                        seen.append(cfg["model_type"]) or {"tokens": 1})
    monkeypatch.setattr(aot, "train_loop", lambda cfg, mix, topo:
                        seen.append(cfg["model_type"]) or {"tokens": 1})
    import jax
    was = jax.config.jax_enable_compilation_cache
    try:
        aot.main()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    out = capsys.readouterr().out
    assert set(seen) == {"llama", "mistral"} and len(seen) == 5
    left = [ln for ln in out.splitlines() if "not described" in ln]
    assert [ln.split()[0] for ln in left] == ["joyai-llm-flash.serve-L5"]
    assert "aot_latent_moe.py" in left[0]
    assert "mistral-7b-v0.1.fleet-L4" not in out     # waits for its driver


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
