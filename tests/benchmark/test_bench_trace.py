"""The reduction from the profiler's trace to numbers, on two small
recorded traces (the first quarter second of a traced window of the
serving and of the training cell on a TPU v5e, in the form that
``tests/benchmark/record_trace_head.py`` writes) and on synthetic events
with known answers."""
import json
from pathlib import Path

import pytest

from benchmark.harness import trace

DATA = Path(__file__).parent / "data"


def recorded(name):
    return json.loads((DATA / name).read_text())


def test_recorded_serving_trace():
    r = trace.reduce(recorded("trace_serve_head.json"), chips=1)
    assert r.window_s == pytest.approx(0.25)
    # one ragged step ran in that quarter second: 21.3 ms of device time
    (name, times), = r.module_s.items()
    assert name.startswith("jit__unknown") and times == [
        pytest.approx(0.021301092)]
    assert r.busy_s == pytest.approx(0.02129959)
    assert r.idle_share() == pytest.approx(1 - 0.02129959 / 0.25)
    assert sum(r.op_self_s.values()) == pytest.approx(r.busy_s, rel=1e-3)
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(
        0.25 - r.busy_s, rel=1e-6)
    assert r.op_seconds(r"^%sort\.") == pytest.approx(
        0.000997078 + 0.000983362)
    assert r.op_seconds("no such kernel") is None


def test_recorded_training_trace_finds_the_flash_kernels():
    r = trace.reduce(recorded("trace_train_head.json"), chips=1)
    mix = json.loads((Path(__file__).resolve().parents[2] /
                      "benchmark/traffic/s4096.json").read_text())
    fwd = r.op_seconds(mix["flash_fwd_op_match"])
    bwd = r.op_seconds(mix["flash_bwd_op_match"])
    # two layers' forward kernels at 20.7 ms each; dq and dk/dv behind
    assert fwd == pytest.approx(0.020722223 + 0.020697505)
    assert bwd is not None and bwd > fwd * 0.5
    # the while that holds a scan's body is not work of its own
    assert r.busy_s < 0.25 and r.idle_share() < 0.2
    labels = {g[0] for g in r.idle_gaps}
    assert "bench.train_batch_loop" in labels


def synthetic():
    ms = 1_000_000
    return {"devices": {"0": {"ops": [
        ["%while.1 = body", 0, 100 * ms],               # a container
        ["%fusion.1 = f32[8]", 0, 10 * ms],
        ["%all-gather.1 = bf16[4]", 10 * ms, 20 * ms],  # exposed 20
        ["%all-reduce.2 = bf16[4]", 40 * ms, 10 * ms],
        ["%fusion.2 = f32[8]", 60 * ms, 30 * ms]],
        "modules": [["jit_step(1)", 0, 50 * ms], ["jit_step(1)", 50 * ms,
                                                  40 * ms]]},
        "1": {"ops": [["%fusion.1 = f32[8]", 0, 100 * ms]], "modules": []}},
        "host": [["bench.window", 0, 100 * ms],
                 ["bench.submit", 30 * ms, 10 * ms],
                 ["bench.data_fetch", 50 * ms, 10 * ms]]}


def test_busy_idle_self_time_and_gaps():
    r = trace.reduce(synthetic(), chips=1)
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.070)          # 10 + 20 + 10 + 30
    assert r.idle_share() == pytest.approx(0.30)
    # the while's own time is what its children leave: the 30 ms of gaps
    assert r.op_self_s["%while.1 = body"] == pytest.approx(0.030)
    assert r.op_self_s["%fusion.2 = f32[8]"] == pytest.approx(0.030)
    assert r.module_classes("jit_step") == [[pytest.approx(0.05),
                                             pytest.approx(0.04)]]
    assert r.module_classes("no such program") == []
    gaps = dict(r.idle_gaps)
    assert gaps["bench.submit"] == pytest.approx(0.010)
    assert gaps["bench.data_fetch"] == pytest.approx(0.010)
    assert gaps["unattributed"] == pytest.approx(0.010)


def _traced(modules, **mix):
    from benchmark.harness.window import Run
    t = synthetic()
    t["devices"]["0"]["modules"] = modules
    run = Run(cfg={}, mix={"step_module_match": "^jit_step", **mix},
              peaks=None, chips=1)
    run.trace = trace.reduce(t, chips=1)
    return run


def test_step_time_is_all_step_time_over_all_steps():
    from benchmark.harness import readers
    ms = 1_000_000
    # a serving engine's two programs: three decode-only steps of 10 ms
    # and one of 40 ms that carries a chunk; another program beside them
    run = _traced([["jit_step(1)", 0, 10 * ms], ["jit_step(1)", 10 * ms,
                                                 10 * ms],
                   ["jit_step(2)", 20 * ms, 40 * ms],
                   ["jit_step(1)", 60 * ms, 10 * ms],
                   ["jit_other(3)", 70 * ms, 5 * ms]])
    assert readers.step_ms(run) == pytest.approx(70.0 / 4)
    assert readers.step_decode_ms(run) == pytest.approx(10.0)
    assert readers.step_chunk_ms(run) == pytest.approx(40.0)
    # a mean moves with either class; the median sat on the decode step
    slow = _traced([["jit_step(1)", 0, 10 * ms],
                    ["jit_step(2)", 20 * ms, 60 * ms]])
    assert readers.step_ms(slow) == pytest.approx(35.0)
    # one class alone cannot be told from the other: nothing to read
    one = _traced([["jit_step(1)", 0, 10 * ms]])
    assert readers.step_decode_ms(one) is None
    assert readers.step_chunk_ms(one) is None
    assert readers.step_ms(one) == pytest.approx(10.0)


def test_a_loop_programs_time_is_shared_among_its_steps():
    from benchmark.harness import readers
    ms = 1_000_000
    run = _traced([["jit_step(1)", 0, 40 * ms], ["jit_step(1)", 50 * ms,
                                                 48 * ms]],
                  steps_per_call=4)
    assert readers.step_ms(run) == pytest.approx(11.0)


def test_the_busiest_device_sets_the_idle_share():
    r = trace.reduce(synthetic(), chips=2)
    assert r.busy_s == pytest.approx((0.070 + 0.100) / 2)
    assert r.idle_share() == pytest.approx(0.0)


def test_a_trace_without_its_window_or_device_is_refused():
    t = synthetic()
    t["host"] = t["host"][1:]
    with pytest.raises(SystemExit, match="bench.window"):
        trace.reduce(t, 1)
    t = synthetic()
    t["devices"] = {}
    with pytest.raises(SystemExit, match="no device plane"):
        trace.reduce(t, 1)
