"""paddle_tpu.profiler — scheduler windows, RecordEvent nesting + chrome
export roundtrip, summary() aggregation, timer-only step stats, the
per-thread tids, and the span store: a fixed ring written without a
lock, its overwrite count since Profiler.start(), and the window query
span_totals(). CPU-mesh only; nothing here touches a device beyond
jax.profiler's host-side TraceAnnotation."""
import json
import os
import sys
import threading
import time

import pytest

import paddle_tpu.profiler as prof
from paddle_tpu.profiler import (Profiler, ProfilerState, RecordEvent,
                                 load_profiler_result, make_scheduler)


class TestMakeScheduler:
    def test_basic_cycle_windows(self):
        # cycle = closed(1) + ready(1) + record(2): the last record
        # step of each cycle returns RECORD_AND_RETURN
        sched = make_scheduler(closed=1, ready=1, record=2)
        want = [ProfilerState.CLOSED, ProfilerState.READY,
                ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN]
        got = [sched(i) for i in range(8)]
        assert got == want + want  # cyclic

    def test_skip_first_and_repeat(self):
        sched = make_scheduler(closed=0, ready=0, record=1, repeat=2,
                               skip_first=3)
        assert [sched(i) for i in range(3)] == [ProfilerState.CLOSED] * 3
        assert sched(3) == ProfilerState.RECORD_AND_RETURN
        assert sched(4) == ProfilerState.RECORD_AND_RETURN
        # repeat exhausted -> closed forever
        assert sched(5) == ProfilerState.CLOSED
        assert sched(50) == ProfilerState.CLOSED

    def test_record_only_scheduler_always_records(self):
        sched = make_scheduler(record=1)
        assert sched(0) == ProfilerState.RECORD_AND_RETURN


class TestRecordEvent:
    def test_nesting_and_chrome_roundtrip(self, tmp_path):
        p = Profiler(timer_only=True)
        p.start()
        with RecordEvent("outer"):
            with RecordEvent("inner"):
                pass
            with RecordEvent("inner"):
                pass
        p.stop()
        path = p.export_chrome_tracing(str(tmp_path), "w0")
        out = load_profiler_result(path)
        evs = out["traceEvents"]
        by_name = {}
        for e in evs:
            by_name.setdefault(e["name"], []).append(e)
        assert len(by_name["inner"]) == 2
        assert len(by_name["outer"]) == 1
        outer = by_name["outer"][0]
        inner = by_name["inner"][0]
        # chrome "X" complete events, microseconds; the inner span nests
        # inside the outer one on the same thread lane
        assert outer["ph"] == "X" and inner["ph"] == "X"
        assert inner["tid"] == outer["tid"] == threading.get_ident()
        assert outer["ts"] <= inner["ts"]
        assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
        # the file is valid JSON end to end (the roundtrip IS the check)
        assert json.dumps(out)

    def test_begin_end_explicit(self):
        p = Profiler(timer_only=True)
        p.start()
        ev = RecordEvent("manual")
        ev.begin()
        ev.end()
        p.stop()
        names = [e[0] for e in prof._entries(prof._mark)]
        assert "manual" in names

    def test_multithread_tids_do_not_collide(self, tmp_path):
        """Concurrent threads used to interleave on a shared
        module-global stack and all export as tid 0; each thread's spans
        carry its own ident, and the ring's lock-free append loses none
        (each writer takes a slot of its own)."""
        p = Profiler(timer_only=True)
        p.start()
        # more writers than cores, switched as often as the interpreter
        # allows: a lost or shared slot would lose a span
        n_threads, n_spans = (os.cpu_count() or 4) + 2, 200
        # OS thread idents are recycled once a thread exits — hold all
        # alive until every span landed so the lanes are distinct
        done = threading.Barrier(n_threads)

        def work(i):
            for j in range(n_spans):
                with RecordEvent(f"t{i}"):
                    pass
            done.wait(timeout=30)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        finally:
            sys.setswitchinterval(switch)
        assert not any(t.is_alive() for t in threads)
        p.stop()
        path = p.export_chrome_tracing(str(tmp_path), "mt")
        evs = load_profiler_result(path)["traceEvents"]
        assert len(evs) == n_threads * n_spans  # none lost
        tids = {}
        for e in evs:
            tids.setdefault(e["name"], set()).add(e["tid"])
        # each logical thread exported under exactly ONE tid, and the
        # four lanes are distinct (no tid-0 collision)
        assert all(len(s) == 1 for s in tids.values()), tids
        assert len(set().union(*tids.values())) == n_threads

    def test_event_table_cap_counts_overflow(self, monkeypatch):
        """The ring keeps the NEWEST spans: past its size the oldest are
        overwritten and counted since start()'s mark, never the newest
        shed (a long-running server stays able to see)."""
        _small_ring(monkeypatch, 16)
        p = Profiler(timer_only=True)
        p.start()
        for i in range(25):
            with RecordEvent(f"burst{i}"):
                pass
        p.stop()
        held = [e[0] for e in prof._entries(prof._mark)]
        assert held == [f"burst{i}" for i in range(9, 25)]
        assert prof.events_dropped() == 9
        # start() moves the mark: nothing overwritten since
        p2 = Profiler(timer_only=True)
        p2.start()
        p2.stop()
        assert prof.events_dropped() == 0
        assert prof._entries(prof._mark) == []

    def test_ring_entries_are_tuples_not_dicts(self):
        with RecordEvent("shape"):
            pass
        entry = prof._ring[(prof._written() - 1) & prof._MASK]
        name, tid, start, dur = entry
        assert type(entry) is tuple and name == "shape"
        assert tid == threading.get_ident()
        assert isinstance(start, int) and 0 <= dur < 10 ** 9


def _small_ring(monkeypatch, size):
    monkeypatch.setattr(prof, "_RING_SIZE", size)
    monkeypatch.setattr(prof, "_MASK", size - 1)
    monkeypatch.setattr(prof, "_ring", [None] * size)


def _write(name, start_s, dur_s, tid=None):
    """One ring entry by hand, on the perf_counter clock in seconds."""
    prof._ring[next(prof._seq) & prof._MASK] = (
        name, threading.get_ident() if tid is None else tid,
        int(start_s * 1e9), int(dur_s * 1e9))


class TestSpanTotals:
    # far from any span a real clock reading stamps
    BASE = time.perf_counter() + 1e6

    def test_window_counts_the_spans_that_began_in_it(self):
        b = self.BASE
        _write("w.a", b - 0.5, 1.0)        # straddles t0: began before
        _write("w.a", b + 1.0, 0.25)
        _write("w.a", b + 1.5, 0.5)
        _write("w.b", b + 1.9, 0.5)        # straddles t1: began inside
        _write("w.b", b + 2.0, 0.1)        # begins at t1: outside
        got = prof.span_totals(b, b + 2.0)
        assert set(got) == {"w.a", "w.b"}
        assert got["w.a"][0] == 2
        assert got["w.a"][1] == pytest.approx(0.75)
        assert got["w.b"][0] == 1
        assert got["w.b"][1] == pytest.approx(0.5)
        assert prof.span_totals(b - 10.0, b - 5.0) == {}

    def test_thread_filter(self):
        b = self.BASE + 100.0
        _write("t.x", b + 0.1, 0.01, tid=11)
        _write("t.x", b + 0.2, 0.02, tid=22)
        _write("t.x", b + 0.3, 0.04, tid=22)
        assert prof.span_totals(b, b + 1.0)["t.x"][0] == 3
        one = prof.span_totals(b, b + 1.0, thread=22)["t.x"]
        assert one[0] == 2 and one[1] == pytest.approx(0.06)
        assert prof.span_totals(b, b + 1.0, thread=33) == {}

    def test_recorded_spans_on_the_perf_counter_clock(self):
        t0 = time.perf_counter()
        with RecordEvent("clock.outer"):
            with RecordEvent("clock.inner"):
                time.sleep(0.01)
        t1 = time.perf_counter()
        got = prof.span_totals(t0, t1, thread=threading.get_ident())
        assert got["clock.outer"][0] == got["clock.inner"][0] == 1
        assert 0.01 <= got["clock.inner"][1] <= got["clock.outer"][1] \
            <= t1 - t0

    def test_overwritten_spans_leave_the_window(self, monkeypatch):
        _small_ring(monkeypatch, 8)
        b = self.BASE + 200.0
        for i in range(12):
            _write("o.x", b + i, 0.5)
        got = prof.span_totals(b, b + 100.0)
        assert got["o.x"][0] == 8          # the newest eight


class TestProfilerSummary:
    def test_summary_aggregation(self, capsys):
        p = Profiler(timer_only=True)
        p.start()
        for _ in range(3):
            with RecordEvent("op_a"):
                pass
        with RecordEvent("op_b"):
            pass
        p.step()
        p.step()
        p.stop()
        out = p.summary()
        capsys.readouterr()
        lines = {ln.split()[0]: ln for ln in out.splitlines()
                 if ln and not ln.startswith(("-", "Name"))}
        assert "op_a" in lines and "op_b" in lines
        assert lines["op_a"].split()[1] == "3"  # call count
        assert lines["op_b"].split()[1] == "1"
        assert "steps: 2" in out  # timer stats ride the same summary

    def test_timer_only_step_stats(self):
        p = Profiler(timer_only=True)
        p.start()
        for i in range(5):
            p.step(num_samples=4)
        p.stop()
        assert len(p._step_times) == 5
        assert all(t >= 0 for t in p._step_times)
        # timer_only never opens a jax trace
        assert p._jax_tracing is False

    def test_scheduler_tuple_form(self):
        # paddle-style (start, end) tuple scheduler: closed until
        # start, recording inside the window
        p = Profiler(scheduler=(2, 4), timer_only=True)
        p.start()
        assert p._state == ProfilerState.CLOSED
        p.step()  # step 1
        assert p._state == ProfilerState.CLOSED
        p.step()  # step 2 -> window
        assert p._state in (ProfilerState.RECORD,
                            ProfilerState.RECORD_AND_RETURN)
        p.stop()

    def test_on_trace_ready_handler(self, tmp_path):
        from paddle_tpu.profiler import export_chrome_tracing
        handler = export_chrome_tracing(str(tmp_path), "h0")
        p = Profiler(timer_only=True, on_trace_ready=handler)
        p.start()
        with RecordEvent("spanned"):
            pass
        p.stop()  # handler fires here
        out = load_profiler_result(str(tmp_path / "h0.json"))
        assert any(e["name"] == "spanned" for e in out["traceEvents"])
