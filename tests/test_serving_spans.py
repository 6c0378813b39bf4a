"""The engine loop's host phases as ``paddle_tpu.profiler`` spans: one
``serving.step`` a step, tiled by one span a phase, none a lane or a
token; the front-end's ``serving.lock_wait`` and ``serving.admit``; and
the benchmark's readers of them (``host_ms_per_step``,
``lock_wait_ms_per_step``) on a window of spans written by hand."""
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.profiler as prof
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ServingEngine, ServingFrontend

from serving_utils import wait_until

ROOT = Path(__file__).resolve().parents[1]

PHASES = ["serving.schedule", "serving.pack", "serving.dispatch",
          "serving.overlap", "serving.fetch", "serving.emit",
          "serving.upkeep"]


def make_engine(max_batch=4, num_pages=200):
    P.seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=97, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64))
    m.eval()
    return ServingEngine(m, page_size=4, num_pages=num_pages,
                         max_batch=max_batch, prefill_chunk=8)


def prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 97, int(rng.integers(3, 12))).astype(np.int32)
            for _ in range(n)]


def spans_since(mark, tid):
    """(name, start_ns, end_ns) of ``tid``'s spans written since ``mark``,
    in the order they began."""
    got = [(name, start, start + dur)
           for name, t, start, dur in prof._entries(mark) if t == tid]
    return sorted(got, key=lambda s: s[1])


def children_of(step, spans):
    return [s for s in spans if s[0] != "serving.step"
            and step[1] <= s[1] and s[2] <= step[2]]


class TestStepSpans:
    def test_each_step_one_span_each_phase_in_order(self):
        eng = make_engine()
        fe = ServingFrontend(eng).start()
        try:
            mark = prof._written()
            d0 = eng.metrics.step_dispatches.value
            for s in [fe.submit(p, max_new_tokens=5) for p in prompts(6)]:
                s.result(timeout=120)
            dispatches = eng.metrics.step_dispatches.value - d0
            tid = fe._thread.ident
        finally:
            fe.close(timeout=60)
        spans = spans_since(mark, tid)
        steps = [s for s in spans if s[0] == "serving.step"]
        dispatched = [st for st in steps if "serving.dispatch"
                      in [c[0] for c in children_of(st, spans)]]
        assert dispatches > 0 and len(dispatched) == dispatches
        for st in steps:
            names = [c[0] for c in children_of(st, spans)]
            # a step that found no live lane after allocation packs
            # nothing and dispatches nothing
            assert names in (PHASES, ["serving.schedule", "serving.pack",
                                      "serving.upkeep"]), names
        # every phase span lies inside exactly one step
        for s in spans:
            if s[0] in PHASES:
                assert sum(st[1] <= s[1] and s[2] <= st[2]
                           for st in steps) == 1, s
        # the phases tile the step: what they leave is microseconds
        for st in dispatched:
            covered = sum(c[2] - c[1] for c in children_of(st, spans))
            assert (st[2] - st[1]) - covered < 2e6     # < 2 ms of CPU

    @pytest.mark.parametrize("lanes", [1, 40])
    def test_a_step_makes_no_span_per_lane_or_token(self, lanes):
        eng = make_engine(max_batch=40, num_pages=800)
        for p in prompts(lanes):
            eng.add_request(p[:4], max_new_tokens=56)
        for _ in range(3 * lanes):     # every prompt prefilled
            if (len(eng.scheduler.running) == lanes
                    and not eng.scheduler.prefill_queue):
                break
            eng.step()
        assert len(eng.scheduler.running) == lanes
        tid = threading.get_ident()
        for _ in range(2):             # decode steps of ``lanes`` lanes
            mark = prof._written()
            eng.step()
            got = [s[0] for s in spans_since(mark, tid)]
            assert got == ["serving.step"] + PHASES

    def test_lock_wait_and_admit_when_a_caller_contends(self):
        eng = make_engine()
        fe = ServingFrontend(eng).start()
        try:
            loop = fe._thread.ident
            # the idle loop polls the lock: hold it, and its wait shows
            wait_until(lambda: "serving.lock_wait" in prof.span_totals(
                0.0, time.perf_counter(), thread=loop))
            t0 = time.perf_counter()
            with fe.lock:
                time.sleep(0.2)
            t1 = time.perf_counter()
            # the span is written when the loop has the lock again
            waited = wait_until(lambda: prof.span_totals(
                t0, t1, thread=loop).get("serving.lock_wait"))
            assert waited[1] >= 0.1
            # a caller's admission, on the caller's thread, while the
            # loop steps the earlier requests
            first = [fe.submit(p, max_new_tokens=6) for p in prompts(3)]
            a0 = time.perf_counter()
            later = [fe.submit(p, max_new_tokens=6)
                     for p in prompts(4, seed=1)]
            a1 = time.perf_counter()
            for s in first + later:
                s.result(timeout=120)
            mine = prof.span_totals(a0, a1, thread=threading.get_ident())
            assert mine["serving.admit"][0] == 4
            assert "serving.lock_wait" in prof.span_totals(
                a0, time.perf_counter(), thread=loop)
        finally:
            fe.close(timeout=60)


# -- the benchmark's readers, on spans written by hand ------------------------

def _reader(name):
    from benchmark.harness import spec
    return spec.reader(ROOT, "layer_metrics", name)


def _write(name, start_s, dur_s):
    prof._ring[next(prof._seq) & prof._MASK] = (
        name, threading.get_ident(), int(start_s * 1e9), int(dur_s * 1e9))


def _run(t0, t1):
    from benchmark.harness.window import Run
    return Run(cfg={}, mix={}, peaks=None, chips=1, t0=t0, t1=t1)


# ms a step: schedule 1, pack 2, dispatch 3, overlap 4, fetch 10, emit
# 1.5, upkeep 0.5, lock_wait 0.25 -- the device waits for all but overlap
# and fetch: 8.25 ms a step, 0.25 of it the lock
STEP = [("serving.lock_wait", 0.25), ("serving.schedule", 1.0),
        ("serving.pack", 2.0), ("serving.dispatch", 3.0),
        ("serving.overlap", 4.0), ("serving.fetch", 10.0),
        ("serving.emit", 1.5), ("serving.upkeep", 0.5)]


def _write_steps(base, n):
    """``n`` steps of ``STEP``'s phases from ``base`` (seconds); returns
    where the last ends."""
    t = base
    for _ in range(n):
        total = sum(ms for _, ms in STEP[1:]) / 1e3
        _write("serving.lock_wait", t, STEP[0][1] / 1e3)
        t += STEP[0][1] / 1e3
        _write("serving.step", t, total)
        for name, ms in STEP[1:]:
            _write(name, t, ms / 1e3)
            t += ms / 1e3
    return t


class TestReaders:
    BASE = time.perf_counter() + 2e6       # far from any real span

    @pytest.mark.parametrize("name", ["host_ms_per_step.rate",
                                      "host_ms_per_step.tail"])
    def test_host_ms_per_step(self, name):
        b = self.BASE
        _write_steps(b - 0.1, 1)           # before the window
        end = _write_steps(b, 3)
        _write_steps(end + 1.0, 2)         # after the window
        got = _reader(name)(_run(b, end + 0.5))
        assert got == pytest.approx(8.25)

    def test_lock_wait_ms_per_step(self):
        b = self.BASE + 100.0
        end = _write_steps(b, 4)
        got = _reader("lock_wait_ms_per_step.tail")(_run(b, end))
        assert got == pytest.approx(0.25)

    @pytest.mark.parametrize("name", ["host_ms_per_step.rate",
                                      "lock_wait_ms_per_step.tail"])
    def test_no_step_in_the_window_reads_none(self, name):
        b = self.BASE + 200.0
        _write("serving.lock_wait", b + 0.1, 0.001)  # an idle poll
        assert _reader(name)(_run(b, b + 1.0)) is None
        assert _reader(name)(_run(b + 5.0, b + 6.0)) is None
