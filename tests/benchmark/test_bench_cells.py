"""Every cell, end to end, at its rehearsal size on the CPU, through the
benchmark's own command: the last line's keys, no device metric, the
control coming out as not correct, and ``correct`` false with the timed
path broken underneath. The runs are started together, once a module."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
FAULTS = [("altered_token", "serve"), ("dropped_logprobs", "serve"),
          ("state_unchanged", "train"),
          ("half_batch", "train")]
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT),
       "BENCH_RUN": "ignored"}


def _driver(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    mix = json.loads((ROOT / "benchmark" / "traffic" /
                      f"{w['traffic']}.json").read_text())
    return mix["driver"]


def _cell_for(driver):
    return next(c for c in CELLS if _driver(c) == driver)


def _args(cell, *more):
    return ["--workload", cell, "--seed", "3000000019", "--seconds", "1",
            "--trace", "1", "--rehearse", *more]


@pytest.fixture(scope="module")
def runs():
    cmd = BENCH["command"]
    procs = {}
    for cell in CELLS:
        procs[cell] = subprocess.Popen(
            cmd + _args(cell, "--control"), cwd=ROOT, env=ENV,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    for fault, driver in FAULTS:
        procs[fault] = subprocess.Popen(
            [sys.executable, "tests/benchmark/faulty_run.py", fault]
            + _args(_cell_for(driver)), cwd=ROOT, env=ENV,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    out = {}
    for key, p in procs.items():
        try:
            so, se = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            p.kill()
            so, se = p.communicate()
        out[key] = (p.returncode, so, se)
    return out


def _line(runs, key):
    rc, so, se = runs[key]
    assert rc == 0, se[-3000:]
    return json.loads(so.strip().splitlines()[-1]), se


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_with_the_contracts_last_line(runs, cell):
    line, se = _line(runs, cell)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["compiles_in_window"] == 0
    # each number compared stands beside its limit, on stderr too
    for name, row in line["compared"].items():
        assert row["value"] <= row["limit"]
        assert f"compared {name} = " in se
    assert se.strip().splitlines()[-1] == "benchmark: correct = true"


@pytest.mark.parametrize("cell", CELLS)
def test_a_cpu_run_names_no_device_metric(runs, cell):
    line, _ = _line(runs, cell)
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in line["metrics"]:
        assert by_name[name]["source"] == "program_counter"


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_comes_out_as_not_correct(runs, cell):
    line, se = _line(runs, cell)
    # judged by the harness's own comparison, under the cell's own limits
    assert line["correct"] is True and line["control"]["correct"] is False
    assert any(r["value"] > r["limit"]
               for r in line["control"]["compared"].values())
    assert "benchmark: control_correct = false" in se


@pytest.mark.parametrize("fault", [f for f, _ in FAULTS])
def test_a_broken_timed_path_is_not_correct(runs, fault):
    line, se = _line(runs, fault)
    assert line["correct"] is False
    assert any(r["value"] > r["limit"] for r in line["compared"].values())
    assert se.strip().splitlines()[-1] == "benchmark: correct = false"


def test_half_batch_fault_planted_in_the_reference_fails_too(runs):
    line, _ = _line(runs, _cell_for("train"))
    assert line["fault_half_batch"]["correct"] is False


def test_the_train_window_closes_after_all_that_was_sent(runs):
    """Calls are dispatched ahead of the one waited for; when the time is
    up nothing more is sent and the clock is read once all that was sent
    is done, so every step counted lies inside the time it is held to."""
    cell = _cell_for("train")
    line, _ = _line(runs, cell)
    info = line["info"]
    mix = json.loads((ROOT / "benchmark" / "traffic" / (next(
        w["traffic"] for w in BENCH["workloads"] if w["name"] == cell)
        + ".json")).read_text())
    assert info["calls_ahead"] == mix["calls_ahead"] >= 1
    assert info["steps"] == line["attempted"]
    assert 1.0 <= info["sent_all_after_s"] <= info["window_s"]


def test_no_accelerator_means_no_result():
    p = subprocess.run(
        BENCH["command"] + ["--workload", CELLS[0], "--seed", "1",
                            "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
