"""paddle_tpu.serving.trace — serving-wide request tracing + the
engine flight recorder (ISSUE 9): span catalogue and caps, coalesced
decode runs, finish-log phase breakdown, flight-recorder dump on loop
failure (with the failing step's batch composition), /debug/trace +
/debug/flight over HTTP, router-merged cross-replica stitching, and
the acceptance drill — a disaggregated, seeded-sampled request that
suffers a forced mid-decode failover yields ONE stitched timeline at
the router covering prefill replica, migration, decode replica and the
splice, pinned against wall-clock bounds."""
import http.client
import json
import logging
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (DisaggRouter, FlightRecorder,
                                InProcessReplica, RequestTrace,
                                ServingEngine, ServingFrontend,
                                ServingServer, ServingTrace,
                                export_chrome_trace)
from paddle_tpu.serving.trace import chrome_trace_events


def tiny_model(seed=0, **kw):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def make_engine(seed=0, **kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 200)
    kw.setdefault("max_batch", 8)
    kw.setdefault("prefill_chunk", 8)
    return ServingEngine(tiny_model(seed), **kw)


def rng_prompts(n, lo=3, hi=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 97, int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


def consume(stream, timeout=120):
    return [ev["token"] for ev in stream.events(timeout=timeout)
            if ev["type"] == "token"]


def span_names(timeline):
    return [s["name"] for s in timeline["spans"]]


# ---------------------------------------------------------------------------
# unit level: RequestTrace / FlightRecorder / ServingTrace


class TestTraceUnits:
    def test_span_cap_counts_overflow(self):
        tr = RequestTrace(1, cap=4)
        for i in range(10):
            tr.add("s", float(i), 0.5)
        assert len(tr.spans) == 4
        assert tr.dropped == 6
        assert tr.to_json()["dropped"] == 6

    def test_add_run_coalesces_contiguous_rounds(self):
        tr = RequestTrace(1, cap=16)
        tr.add_run("decode_round", 1.0, 0.1, batch=2)
        tr.add_run("decode_round", 1.2, 0.1, batch=3)
        tr.add_run("decode_round", 1.4, 0.1, batch=3)
        assert len(tr.spans) == 1
        s = tr.spans[0]
        assert s["attrs"]["rounds"] == 3
        assert s["attrs"]["batch"] == 3           # latest composition
        assert s["t0"] == 1.0
        assert s["dur"] == pytest.approx(0.5)     # 1.4 + 0.1 - 1.0
        # a differently-named span breaks the run
        tr.add("preempted", 1.6)
        tr.add_run("decode_round", 1.7, 0.1, batch=1)
        assert [x["name"] for x in tr.spans] == [
            "decode_round", "preempted", "decode_round"]

    def test_add_run_accumulates_counters(self):
        tr = RequestTrace(1, cap=16)
        tr.add_run("spec_round", 1.0, 0.1, proposed=4, accepted=2)
        tr.add_run("spec_round", 1.2, 0.1, proposed=4, accepted=4)
        a = tr.spans[0]["attrs"]
        assert a["proposed"] == 8 and a["accepted"] == 6
        assert a["rounds"] == 2

    def test_t0_unix_anchor_mapping(self):
        wall0, mono0 = 1000.0, 50.0
        tr = RequestTrace(1, anchor=(wall0, mono0))
        tr.add("s", 51.5, 0.25)
        out = tr.to_json()["spans"][0]
        assert out["t0_unix"] == pytest.approx(1001.5)

    def test_flight_ring_is_bounded_oldest_evicted(self):
        fr = FlightRecorder(cap=4)
        for i in range(10):
            fr.record("k", i=i)
        events = fr.dump()
        assert len(events) == 4
        assert [e["i"] for e in events] == [6, 7, 8, 9]
        assert fr.recorded == 10
        assert fr.cap == 4

    def test_store_lookup_and_finish_eviction(self, monkeypatch):
        from paddle_tpu.serving import trace as trace_mod
        monkeypatch.setattr(trace_mod, "_KEEP_FINISHED", 2)
        st = ServingTrace(enabled=True)
        for rid in (1, 2, 3):
            st.begin(rid, f"req-{rid}")
            st.span(rid, "queued", 0.0, 0.1)
            st.finish(rid)
        # bound: only the 2 newest finished traces survive
        assert st.get(1) is None
        assert st.get(2) is not None and st.get(3) is not None
        assert st.timelines(request_id="req-1") == []
        assert len(st.timelines(request_id="req-3")) == 1
        assert len(st.timelines()) == 2

    def test_disabled_store_is_inert(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_TRACE", "0")
        st = ServingTrace()
        assert st.enabled is False
        st.begin(1, "x")
        st.span(1, "queued", 0.0, 0.1)
        assert st.timelines() == []

    def test_env_caps(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_TRACE_SPANS", "32")
        monkeypatch.setenv("PADDLE_TPU_SERVING_TRACE_FLIGHT", "64")
        st = ServingTrace()
        tr = st.begin(7, None)
        assert tr.cap == 32
        assert st.flight.cap == 64


# ---------------------------------------------------------------------------
# engine level: span catalogue, phases, caps


class TestEngineSpans:
    def test_request_lifecycle_spans_and_wall_bounds(self):
        eng = make_engine()
        t_start = time.time()
        rid = eng.add_request(rng_prompts(1, lo=9, hi=10)[0],
                              max_new_tokens=6, request_id="life-1")
        eng.run()
        t_end = time.time()
        [tl] = eng.trace.timelines(request_id="life-1")
        names = span_names(tl)
        # queued -> chunked prefill (9 tokens / chunk 8 = 2 chunks)
        # -> one coalesced decode run (5 rounds: token 1 is prefill's)
        assert names[0] == "queued"
        assert names.count("prefill_chunk") == 2
        assert names[-1] == "decode_round"
        decode = tl["spans"][-1]
        assert decode["attrs"]["rounds"] == 5
        # monotonic-clock spans map onto the wall window of the run
        for s in tl["spans"]:
            assert t_start - 0.05 <= s["t0_unix"] <= t_end + 0.05
            assert s["t0_unix"] + s["dur"] <= t_end + 0.05
        assert tl["req_id"] == rid
        assert tl["dropped"] == 0

    def test_span_cap_env_knob_and_overflow(self, monkeypatch):
        # decode rounds coalesce, so overflow needs many DISTINCT
        # spans: a long prompt over a tiny prefill chunk gives one
        # span per chunk (33 tokens / chunk 4 = 9 chunks > cap 8)
        monkeypatch.setenv("PADDLE_TPU_SERVING_TRACE_SPANS", "8")
        eng = make_engine(prefill_chunk=4)
        prompt = np.arange(33, dtype=np.int32) % 97
        eng.add_request(prompt, max_new_tokens=4, request_id="cap")
        eng.run()
        [tl] = eng.trace.timelines(request_id="cap")
        assert len(tl["spans"]) == 8
        assert tl["dropped"] > 0

    def test_trace_off_engine_records_nothing(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_TRACE", "0")
        eng = make_engine()
        eng.add_request(rng_prompts(1)[0], max_new_tokens=4)
        eng.run()
        assert eng.trace.enabled is False
        assert eng.trace.timelines() == []
        assert eng.trace.flight.dump() == []

    def test_prefix_hit_span(self):
        eng = make_engine(prefix_cache=True)
        prompt = rng_prompts(1, lo=11, hi=12, seed=5)[0]
        eng.add_request(prompt, max_new_tokens=4, request_id="warm")
        eng.run()
        eng.add_request(prompt, max_new_tokens=4, request_id="hit")
        eng.run()
        [tl] = eng.trace.timelines(request_id="hit")
        hits = [s for s in tl["spans"] if s["name"] == "prefix_hit"]
        assert hits and hits[0]["attrs"]["pages"] >= 1

    def test_preemption_emits_preempted_and_recompute(self):
        """Same pressure config as the round-8 exactness test: 4
        requests want 16 pages, 9 allocatable -> decode growth
        preempts."""
        eng = make_engine(num_pages=10, max_batch=4)
        rng = np.random.default_rng(1)
        for i in range(4):
            eng.add_request(rng.integers(0, 97, 3).astype(np.int32),
                            max_new_tokens=12, request_id=f"p{i}")
        eng.run()
        assert eng.metrics.preemptions.value > 0, \
            "config failed to force preemption"
        spans = [s for tl in eng.trace.timelines()
                 for s in tl["spans"]]
        names = {s["name"] for s in spans}
        assert "preempted" in names
        assert "recompute" in names
        # a victim's requeue wait lands as a SECOND queued span
        victims = [tl for tl in eng.trace.timelines()
                   if "preempted" in span_names(tl)]
        assert all(span_names(tl).count("queued") >= 2
                   for tl in victims)

    def test_spec_round_spans_carry_acceptance(self):
        target = tiny_model(seed=0)
        draft = tiny_model(seed=1)
        eng = ServingEngine(target, page_size=4, num_pages=200,
                            max_batch=4, prefill_chunk=8,
                            draft_model=draft, speculative_k=2)
        eng.add_request(rng_prompts(1, seed=9)[0], max_new_tokens=8,
                        request_id="spec")
        eng.run()
        [tl] = eng.trace.timelines(request_id="spec")
        spec = [s for s in tl["spans"] if s["name"] == "spec_round"]
        assert spec, span_names(tl)
        a = spec[0]["attrs"]
        assert a["proposed"] >= a["accepted"] >= 0
        assert a["rounds"] >= 1 and a["emitted"] >= 1

    def test_finish_log_carries_phase_breakdown(self, caplog):
        eng = make_engine()
        with caplog.at_level(logging.INFO, "paddle_tpu.serving"):
            eng.add_request(rng_prompts(1, lo=9, hi=10)[0],
                            max_new_tokens=6, request_id="log-1")
            eng.run()
        lines = [json.loads(r.message) for r in caplog.records
                 if r.message.startswith("{")]
        fin = [ln for ln in lines
               if ln.get("event") == "request_finished"]
        assert fin, "no structured finish log"
        ph = fin[0]["phases"]
        for key in ("queue_s", "prefill_s", "decode_s", "stall_s"):
            assert key in ph and ph[key] >= 0.0
        # the decomposition is real time, not zeros
        assert ph["prefill_s"] > 0 and ph["decode_s"] > 0
        assert ph["stall_s"] == 0  # nothing preempted this run

    def test_held_and_migration_spans_ride_export_import(self):
        src = make_engine(seed=0)
        dst = make_engine(seed=0)
        prompt = rng_prompts(1, lo=9, hi=10, seed=11)[0]
        rid = src.add_request(prompt, max_new_tokens=6,
                              prefill_only=True, request_id="mig-1")
        src.run()
        meta, k, v = src.export_request(rid)
        assert meta["request_id"] == "mig-1"  # trace context rides
        dst.adopt_request(meta, k, v, max_new_tokens=6)
        src.release_request(rid)
        dst.run()
        [stl] = src.trace.timelines(request_id="mig-1")
        s_names = span_names(stl)
        assert "migration" in s_names and "held" in s_names
        exp = next(s for s in stl["spans"] if s["name"] == "migration")
        assert exp["attrs"]["direction"] == "export"
        assert exp["attrs"]["pages"] == meta["n_pages"]
        # the adopted timeline keys on the SAME request_id via meta
        [dtl] = dst.trace.timelines(request_id="mig-1")
        imp = next(s for s in dtl["spans"] if s["name"] == "migration")
        assert imp["attrs"]["direction"] == "import"
        assert "decode_round" in span_names(dtl)

    def test_step_duration_metric_records(self):
        eng = make_engine()
        eng.add_request(rng_prompts(1)[0], max_new_tokens=4)
        eng.run()
        ex = eng.metrics.export()
        assert ex["step_duration_s"]["count"] > 0
        assert ex["step_duration_s"]["p50"] > 0


# ---------------------------------------------------------------------------
# flight recorder: loop-failure dump with the failing step's composition


class TestFlightRecorder:
    def test_engine_ring_kinds(self):
        eng = make_engine()
        eng.add_request(rng_prompts(1)[0], max_new_tokens=4)
        eng.run()
        eng.start_drain()
        kinds = {e["kind"] for e in eng.trace.flight.dump()}
        assert {"admit", "step_begin", "step_end", "drain"} <= kinds
        begin = next(e for e in eng.trace.flight.dump()
                     if e["kind"] == "step_begin")
        assert "decode" in begin and "waiting" in begin

    def test_loop_failure_dumps_ring_with_batch_composition(
            self, caplog):
        """Acceptance: a forced loop failure (decode step raises)
        flips the front-end to failed and the structured log carries
        the flight ring — whose last step_begin holds the failing
        step's batch composition."""
        eng = make_engine()
        fe = ServingFrontend(eng)
        boom = RuntimeError("forced decode failure")
        orig = eng._ragged_step

        def exploding(out, events):
            if any(r.out_tokens for r in out.decode):
                # the first token lands at prefill completion, so this
                # fires on the request's FIRST decode round
                raise boom
            return orig(out, events)

        eng._ragged_step = exploding
        with caplog.at_level(logging.ERROR, "paddle_tpu.serving"):
            fe.start()
            stream = fe.submit(rng_prompts(1)[0], max_new_tokens=8)
            with pytest.raises(RuntimeError):
                consume(stream)
        assert fe.state == "failed"
        dumps = [json.loads(r.message) for r in caplog.records
                 if r.message.startswith("{")
                 and "flight_recorder_dump" in r.message]
        assert dumps, "loop failure did not dump the flight ring"
        events = dumps[0]["events"]
        assert events[-1]["kind"] == "loop_error"
        assert "forced decode failure" in events[-1]["error"]
        begins = [e for e in events if e["kind"] == "step_begin"]
        assert begins, "ring lost the failing step"
        # the failing step was a decode step over one running lane
        assert begins[-1]["decode"] == 1
        # post-mortem access also works through the debug surface
        post = fe.debug_flight()
        assert post["events"][-1]["kind"] == "loop_error"

    def test_shed_and_fault_events_recorded(self, monkeypatch):
        eng = make_engine()
        fe = ServingFrontend(eng, max_queued=1)
        # UNSTARTED front-end: admission is pure reservation math
        # under the lock (round-11 addenda), so counts are exact
        fe.submit(rng_prompts(1)[0], max_new_tokens=4)
        from paddle_tpu.serving import Rejected
        with pytest.raises(Rejected):
            fe.submit(rng_prompts(1)[0], max_new_tokens=4)
        kinds = [e["kind"] for e in eng.trace.flight.dump()]
        assert "shed" in kinds
        shed = next(e for e in eng.trace.flight.dump()
                    if e["kind"] == "shed")
        assert shed["cause"] == "queue_full"
        # fault injection records before raising
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_ERROR_RATE", "1")
        from paddle_tpu.serving import FaultInjected
        with pytest.raises(FaultInjected):
            eng.step()
        assert any(e["kind"] == "fault"
                   for e in eng.trace.flight.dump())


# ---------------------------------------------------------------------------
# HTTP surface: /debug/trace + /debug/flight


class TestDebugEndpoints:
    def _get_json(self, host, port, path):
        conn = http.client.HTTPConnection(host, port, timeout=30)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read())
        finally:
            conn.close()

    def test_server_debug_endpoints(self):
        eng = make_engine()
        srv = ServingServer(eng)
        host, port = srv.start()
        try:
            body = json.dumps({
                "prompt": [int(t) for t in rng_prompts(1)[0]],
                "max_tokens": 4})
            req = urllib.request.Request(
                f"http://{host}:{port}/v1/completions",
                data=body.encode(),
                headers={"Content-Type": "application/json",
                         "X-Request-Id": "http-trace-1"})
            with urllib.request.urlopen(req, timeout=60) as r:
                assert r.status == 200
            status, out = self._get_json(
                host, port, "/debug/trace?request_id=http-trace-1")
            assert status == 200
            assert len(out["timelines"]) == 1
            names = span_names(out["timelines"][0])
            assert "prefill_chunk" in names
            assert "decode_round" in names
            # unknown id -> empty, not an error
            status, out = self._get_json(
                host, port, "/debug/trace?request_id=nope")
            assert status == 200 and out["timelines"] == []
            status, out = self._get_json(host, port, "/debug/flight")
            assert status == 200
            kinds = {e["kind"] for e in out["events"]}
            assert "admit" in kinds and "step_begin" in kinds
            # bad req_id is a 400, not a handler crash
            status, out = self._get_json(
                host, port, "/debug/trace?req_id=xyz")
            assert status == 400
        finally:
            srv.close()

    def test_http_replica_debug_passthrough(self):
        from paddle_tpu.serving import HTTPReplica
        eng = make_engine()
        srv = ServingServer(eng)
        host, port = srv.start()
        try:
            rep = HTTPReplica(host, port)
            stream = rep.submit(rng_prompts(1)[0], max_new_tokens=4,
                                request_id="rep-1")
            assert len(consume(stream)) == 4
            out = rep.debug_trace(request_id="rep-1")
            assert len(out["timelines"]) == 1
            assert rep.debug_flight()["events"]
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# chrome export


class TestChromeExport:
    def test_export_roundtrips_through_profiler(self, tmp_path):
        from paddle_tpu.profiler import load_profiler_result
        eng = make_engine()
        for i, p in enumerate(rng_prompts(3, seed=21)):
            eng.add_request(p, max_new_tokens=5, request_id=f"x{i}")
        eng.run()
        path = str(tmp_path / "serving_trace.json")
        export_chrome_trace(
            path, [(0, "replica 0", eng.trace.timelines())])
        out = load_profiler_result(path)
        evs = out["traceEvents"]
        spans = [e for e in evs if e["ph"] == "X"]
        metas = [e for e in evs if e["ph"] == "M"]
        assert spans and metas
        # one tid per request lane, all under pid 0, µs timestamps
        assert len({e["tid"] for e in spans}) == 3
        assert all(e["pid"] == 0 for e in spans)
        assert all(e["dur"] >= 0 for e in spans)
        assert any(e["name"] == "decode_round"
                   and e["args"].get("rounds") for e in spans)

    def test_multi_pid_export(self, tmp_path):
        a, b = make_engine(seed=0), make_engine(seed=1)
        for eng in (a, b):
            eng.add_request(rng_prompts(1)[0], max_new_tokens=3)
            eng.run()
        evs = (chrome_trace_events(a.trace.timelines(), pid=0)
               + chrome_trace_events(b.trace.timelines(), pid=1))
        assert {e["pid"] for e in evs} == {0, 1}


# ---------------------------------------------------------------------------
# the acceptance drill: disagg + forced mid-decode failover -> ONE
# stitched timeline at the router


class TestDisaggStitchedTimeline:
    def test_failover_mid_decode_stitches_one_timeline(
            self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.02")
        prompt = rng_prompts(1, lo=9, hi=12, seed=31)[0]
        # oracle: the uninterrupted seeded-sampled stream
        oracle_eng = make_engine(prefix_cache=True)
        orid = oracle_eng.add_request(prompt, max_new_tokens=10,
                                      do_sample=True, seed=77)
        want = oracle_eng.run()[orid]["tokens"]

        reps = [InProcessReplica(make_engine(prefix_cache=True),
                                 role=r)
                for r in ("prefill", "decode", "decode")]
        router = DisaggRouter(reps, page_size=4).start()
        try:
            t_start = time.time()
            stream = router.submit(prompt, max_new_tokens=10,
                                   do_sample=True, seed=77,
                                   request_id="stitch-1")
            toks = []
            for ev in stream.events(timeout=120):
                if ev["type"] == "token":
                    toks.append(ev["token"])
                    if len(toks) == 4:
                        # phase is decode by token 4: kill the decode
                        # replica mid-stream
                        router.kill_replica(stream.replica_idx)
            t_end = time.time()
            assert toks == want            # token-exact through it all
            assert stream.migrations >= 1
            assert stream.failovers >= 1

            out = router.debug_trace(request_id="stitch-1")
            stitched = out["stitched"]
            assert stitched, "no stitched timeline"
            # ONE timeline: wall-ordered and inside the request window
            t0s = [s["t0_unix"] for s in stitched]
            assert t0s == sorted(t0s)
            assert t0s[0] >= t_start - 0.1
            assert max(s["t0_unix"] + s["dur"]
                       for s in stitched) <= t_end + 0.1
            by_name = {}
            for s in stitched:
                by_name.setdefault(s["name"], []).append(s)
            # prefill-replica spans (replica 0 is the prefill role)
            assert any(s["replica"] == 0
                       for s in by_name["prefill_chunk"])
            # the migration: engine export/import spans AND the
            # router's own span with page counts
            mig = by_name["migration"]
            assert any(s["replica"] == "router" and
                       s["attrs"].get("pages", 0) >= 1 for s in mig)
            assert any(s["attrs"].get("direction") == "export"
                       for s in mig)
            assert any(s["attrs"].get("direction") == "import"
                       for s in mig)
            # decode-replica spans from a decode-role replica
            assert any(s["replica"] in (1, 2)
                       for s in by_name["decode_round"])
            # the splice
            splices = by_name["failover_splice"]
            assert splices and all(s["replica"] == "router"
                                   for s in splices)
            assert splices[0]["attrs"]["spliced_tokens"] >= 4
            # the phases stitch in causal order on the shared clock
            assert (min(s["t0_unix"]
                        for s in by_name["prefill_chunk"])
                    <= min(s["t0_unix"] for s in mig)
                    <= min(s["t0_unix"]
                           for s in by_name["decode_round"])
                    + 0.001)
            # at least two replicas plus the router contributed
            contributors = {s["replica"] for s in stitched}
            assert "router" in contributors
            assert len(contributors - {"router"}) >= 2
            # the fleet flight view covers the kill and the migration
            flights = router.debug_flight()
            assert {"kill_replica", "migrate", "failover"} <= {
                e["kind"] for e in flights["router"]["events"]}
            killed = str(
                next(e for e in flights["router"]["events"]
                     if e["kind"] == "kill_replica")["replica"])
            assert any(
                e["kind"] == "loop_error"
                for e in flights["replicas"][killed]["events"])
        finally:
            router.close()


# ---------------------------------------------------------------------------
# conftest guard wiring (satellite: the replay class is guarded)


class TestGuardWiring:
    def test_replay_class_is_bench_artifact_guarded(self):
        import os
        conftest = open(os.path.join(os.path.dirname(__file__),
                                     "conftest.py")).read()
        assert "TestServingTraceReplay" in conftest


@pytest.mark.slow
class TestServingTraceReplay:
    def test_bench_trace_smoke_subprocess(self):
        """End-to-end overhead-guard replay through the repo-root
        driver (slow: excluded from tier-1; the banked quiet-VM
        artifact is the real gate — smoke mode measures but never
        asserts the 3% contract, CLAUDE.md round-4 marginal hygiene).
        The conftest BENCH-artifact guard snapshots and restores the
        banked BENCH_serving*.json around this class; byte-identity is
        re-verified here via md5 at teardown by the autouse fixture."""
        import hashlib
        import os
        import subprocess
        import sys
        root = os.path.join(os.path.dirname(__file__), "..")
        banked = os.path.join(root, "BENCH_serving_trace.json")
        md5_before = (hashlib.md5(open(banked, "rb").read())
                      .hexdigest() if os.path.exists(banked) else None)
        p = subprocess.run(
            [sys.executable, "bench_serving.py", "--smoke", "--trace"],
            cwd=root, capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        out = json.loads(p.stdout.strip().splitlines()[-1])
        assert out["metric"].startswith("serving_trace_marginal_ratio")
        assert out["smoke"] is True
        assert out["traced_requests"] > 0
        assert out["chrome_events"] > 0
        assert out["trace_on"]["tok_per_s_marginal"] > 0
        assert out["trace_off"]["tok_per_s_marginal"] > 0
        # the subprocess rewrote the artifact with in-suite numbers;
        # the conftest guard owns restoration — record what it must
        # restore so a guard regression fails loudly here
        if md5_before is not None:
            assert os.path.exists(banked)
            self.__class__._md5_expected = md5_before

    def test_artifact_restored_after_replay(self):
        """Runs AFTER the subprocess test in the same class: the
        autouse guard restored the banked artifact between tests, so
        the md5 must match the pre-subprocess snapshot."""
        import hashlib
        import os
        root = os.path.join(os.path.dirname(__file__), "..")
        banked = os.path.join(root, "BENCH_serving_trace.json")
        expected = getattr(self.__class__, "_md5_expected", None)
        if expected is None or not os.path.exists(banked):
            pytest.skip("no banked artifact to verify")
        got = hashlib.md5(open(banked, "rb").read()).hexdigest()
        assert got == expected, \
            "BENCH_serving_trace.json not byte-identical after replay"
