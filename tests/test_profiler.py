"""paddle_tpu.profiler — first test coverage for the profiler package
(ISSUE 9 satellite): scheduler windows, RecordEvent nesting + chrome
export roundtrip, summary() aggregation, timer-only step stats, and the
round-16 thread-safety fix (per-thread tid, locked/capped event table).
CPU-mesh only; nothing here touches a device beyond jax.profiler's
host-side TraceAnnotation."""
import json
import threading

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
        with prof._events_lock:
            names = [e["name"] for e in prof._events]
        assert "manual" in names

    def test_multithread_tids_do_not_collide(self, tmp_path):
        """Round-16 fix: concurrent threads used to interleave on a
        shared module-global stack and all export as tid 0; now each
        thread's spans carry its own ident and the table append is
        locked (no lost updates)."""
        p = Profiler(timer_only=True)
        p.start()
        n_threads, n_spans = 4, 50
        # OS thread idents are recycled once a thread exits — hold all
        # four alive until every span landed so the lanes are distinct
        done = threading.Barrier(n_threads)

        def work(i):
            for j in range(n_spans):
                with RecordEvent(f"t{i}"):
                    pass
            done.wait(timeout=30)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        p.stop()
        path = p.export_chrome_tracing(str(tmp_path), "mt")
        evs = load_profiler_result(path)["traceEvents"]
        assert len(evs) == n_threads * n_spans  # locked: none lost
        tids = {}
        for e in evs:
            tids.setdefault(e["name"], set()).add(e["tid"])
        # each logical thread exported under exactly ONE tid, and the
        # four lanes are distinct (no tid-0 collision)
        assert all(len(s) == 1 for s in tids.values()), tids
        assert len(set().union(*tids.values())) == n_threads

    def test_event_table_cap_counts_overflow(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PROFILE_MAX_EVENTS", "10")
        p = Profiler(timer_only=True)
        p.start()
        for _ in range(25):
            with RecordEvent("burst"):
                pass
        p.stop()
        with prof._events_lock:
            n = len(prof._events)
        assert n == 10
        assert prof.events_dropped() == 15
        # start() resets the drop counter with the table
        p2 = Profiler(timer_only=True)
        p2.start()
        p2.stop()
        assert prof.events_dropped() == 0


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
