"""paddle_tpu.serving.server — the streaming HTTP front-end over real
sockets (stdlib http.client driving stdlib http.server): token
exactness vs the offline engine, disconnect-driven cancellation with
page accounting, overload shedding (429, zero preemptions), graceful
drain, Prometheus exposition validity, and fault-injection resilience.
"""
import contextlib
import http.client
import json
import os
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ServingEngine, ServingServer
from serving_utils import wait_until
from serving_utils import wait_until


def tiny_model(seed=0, **kw):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@contextlib.contextmanager
def served(model, *, server_kw=None, **engine_kw):
    engine_kw.setdefault("page_size", 4)
    engine_kw.setdefault("num_pages", 200)
    engine_kw.setdefault("max_batch", 8)
    engine_kw.setdefault("prefill_chunk", 8)
    eng = ServingEngine(model, **engine_kw)
    srv = ServingServer(eng, **(server_kw or {}))
    host, port = srv.start()
    try:
        yield srv, eng, host, port
    finally:
        srv.close(timeout=60)


def _post(host, port, path, body, timeout=120):
    c = http.client.HTTPConnection(host, port, timeout=timeout)
    c.request("POST", path, json.dumps(body),
              {"Content-Type": "application/json"})
    r = c.getresponse()
    status, headers, data = r.status, dict(r.getheaders()), r.read()
    c.close()
    return status, headers, data


def _get(host, port, path, timeout=30):
    c = http.client.HTTPConnection(host, port, timeout=timeout)
    c.request("GET", path)
    r = c.getresponse()
    status, headers, data = r.status, dict(r.getheaders()), r.read()
    c.close()
    return status, headers, data


def _sse_events(data):
    """Parse an SSE byte stream into chunk dicts; asserts the [DONE]
    terminator arrived."""
    evs, done = [], False
    for line in data.decode().splitlines():
        if line == "data: [DONE]":
            done = True
        elif line.startswith("data: "):
            evs.append(json.loads(line[6:]))
    assert done, "stream ended without data: [DONE]"
    return evs


def _stream_tokens(host, port, body, path="/v1/completions"):
    status, _, data = _post(host, port, path, dict(body, stream=True))
    assert status == 200, data
    toks, reasons = [], []
    for ev in _sse_events(data):
        ch = ev["choices"][0]
        if "token_id" in ch:
            toks.append(ch["token_id"])
        if ch.get("finish_reason"):
            reasons.append(ch["finish_reason"])
    return toks, reasons


# ---------------------------------------------------------------------------
# acceptance: token exactness over the wire


class TestStreamingExactness:
    def test_8way_concurrent_sse_matches_engine_run(self):
        """Acceptance: 8 concurrent streamed HTTP requests return token
        sequences bit-identical to the same prompts through
        ServingEngine.run()."""
        m = tiny_model()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 97, int(rng.integers(3, 12)))
                   .astype(np.int32) for _ in range(8)]
        oracle_eng = ServingEngine(m, page_size=4, num_pages=200,
                                   max_batch=8, prefill_chunk=8)
        rids = [oracle_eng.add_request(p, max_new_tokens=6)
                for p in prompts]
        oracle = oracle_eng.run()
        with served(m) as (srv, eng, host, port):
            out = [None] * 8

            def one(i):
                out[i], reasons = _stream_tokens(
                    host, port,
                    {"prompt": [int(t) for t in prompts[i]],
                     "max_tokens": 6})
                assert reasons == ["length"]

            th = [threading.Thread(target=one, args=(i,))
                  for i in range(8)]
            for t in th:
                t.start()
            for t in th:
                t.join(60)
            for i, rid in enumerate(rids):
                assert out[i] == oracle[rid]["tokens"], i
            assert eng.metrics.batch_size.export()["max"] > 1  # batched

    def test_nonstream_completion_usage_and_chat(self):
        m = tiny_model(seed=1)
        prompt = np.random.default_rng(1).integers(0, 97, 7).astype(
            np.int32)
        want = np.asarray(m.generate(P.to_tensor(prompt[None]),
                                     max_new_tokens=5)._data)[0]
        with served(m) as (srv, eng, host, port):
            st, _, data = _post(host, port, "/v1/completions",
                                {"prompt": [int(t) for t in prompt],
                                 "max_tokens": 5})
            assert st == 200
            body = json.loads(data)
            ch = body["choices"][0]
            np.testing.assert_array_equal(ch["token_ids"], want)
            assert ch["finish_reason"] == "length"
            assert body["usage"] == {"prompt_tokens": 7,
                                     "completion_tokens": 5,
                                     "total_tokens": 12}
            # chat endpoint: same ids through the messages shape
            st, _, data = _post(
                host, port, "/v1/chat/completions",
                {"messages": [
                    {"role": "user",
                     "content": [int(t) for t in prompt[:4]]},
                    {"role": "user",
                     "content": [int(t) for t in prompt[4:]]}],
                 "max_tokens": 5})
            assert st == 200
            body = json.loads(data)
            assert body["object"] == "chat.completion"
            ch = body["choices"][0]
            np.testing.assert_array_equal(ch["token_ids"], want)
            assert ch["message"]["role"] == "assistant"

    def test_chat_stream_deltas(self):
        m = tiny_model(seed=2)
        prompt = np.random.default_rng(2).integers(0, 97, 5).astype(
            np.int32)
        with served(m) as (srv, eng, host, port):
            body = {"messages": [{"role": "user",
                                  "content": [int(t) for t in prompt]}],
                    "max_tokens": 4}
            toks, reasons = _stream_tokens(host, port, body,
                                           path="/v1/chat/completions")
            st, _, data = _post(host, port, "/v1/chat/completions", body)
            assert st == 200
            assert toks == json.loads(data)["choices"][0]["token_ids"]
            assert reasons == ["length"]


# ---------------------------------------------------------------------------
# cancellation: disconnect mid-decode returns the pages


class TestCancellation:
    def test_disconnect_mid_stream_frees_pages(self, monkeypatch):
        # slow the step boundary so the hang-up lands mid-decode
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.05")
        m = tiny_model(seed=3)
        with served(m, num_pages=64, max_batch=4) as \
                (srv, eng, host, port):
            free0 = eng.cache.allocatable_pages
            c = http.client.HTTPConnection(host, port, timeout=60)
            c.request("POST", "/v1/completions",
                      json.dumps({"prompt": [1, 2, 3], "max_tokens": 50,
                                  "stream": True}), {})
            r = c.getresponse()
            seen = 0
            while seen < 2:  # two streamed chunks prove decode started
                if r.fp.readline().startswith(b"data: "):
                    seen += 1
            r.close()  # hang up mid-decode (closes the socket fd)
            c.close()
            wait_until(lambda: eng.metrics.cancellations.value
                       and eng.cache.free_pages == free0,
                       msg="disconnect-cancel never landed")
            assert eng.metrics.cancellations.value == 1
            assert eng.cache.free_pages == free0  # allocator restored
            (res,) = eng.results().values()
            assert res["finish_reason"] == "cancelled"
            assert 0 < len(res["tokens"]) < 50  # partial output kept
            assert eng.metrics.preemptions.value == 0


# ---------------------------------------------------------------------------
# overload: burst beyond capacity sheds with 429, running decodes safe


class TestOverload:
    def test_burst_sheds_429_zero_preemptions(self):
        """Reservation admission: with 19 allocatable pages, watermark 1
        and 5 pages/request worst-case, exactly 3 of 8 burst requests
        are admitted; the rest shed with 429 + Retry-After, and NO
        running decode is ever preempted."""
        m = tiny_model(seed=4)
        with served(m, num_pages=20, max_batch=8) as \
                (srv, eng, host, port):
            results = [None] * 8

            def fire(i):
                results[i] = _post(
                    host, port, "/v1/completions",
                    {"prompt": [5] * 8, "max_tokens": 12})

            th = [threading.Thread(target=fire, args=(i,))
                  for i in range(8)]
            for t in th:
                t.start()
            for t in th:
                t.join(60)
            codes = sorted(st for st, _, _ in results)
            assert codes == [200] * 3 + [429] * 5
            for st, headers, data in results:
                if st == 200:
                    ch = json.loads(data)["choices"][0]
                    assert len(ch["token_ids"]) == 12
                    assert ch["finish_reason"] == "length"
                else:
                    assert headers.get("Retry-After") == "1"
                    assert json.loads(data)["error"]["type"] == \
                        "overloaded"
            assert eng.metrics.preemptions.value == 0
            assert eng.metrics.rejections.value == 5

    def test_intake_queue_bound(self):
        m = tiny_model(seed=5)
        with served(m, server_kw={"max_queued": 0}) as \
                (srv, eng, host, port):
            # max_queued=0 closes the intake entirely: every submission
            # is shed before the page-reservation check
            st, headers, data = _post(host, port, "/v1/completions",
                                      {"prompt": [1, 2, 3],
                                       "max_tokens": 2})
            assert st == 429
            assert "intake queue full" in \
                json.loads(data)["error"]["message"]
            assert headers.get("Retry-After") == "1"


# ---------------------------------------------------------------------------
# graceful drain


class TestDrain:
    def test_drain_finishes_inflight_rejects_new(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.05")
        m = tiny_model(seed=6)
        with served(m, num_pages=64, max_batch=4) as \
                (srv, eng, host, port):
            inflight = {}

            def request():
                inflight["r"] = _post(
                    host, port, "/v1/completions",
                    {"prompt": [1, 2, 3, 4], "max_tokens": 20})

            t = threading.Thread(target=request)
            t.start()
            # deadline-poll, not a fixed sleep: admitted and decoding
            wait_until(lambda: eng.metrics.tokens_generated.value > 0,
                       msg="request never started decoding")
            drained = {}
            td = threading.Thread(
                target=lambda: drained.setdefault(
                    "ok", srv.drain(timeout=120)))
            td.start()
            # drain must grab the engine lock behind an in-flight step
            # (50 ms each), so poll instead of racing a fixed sleep
            def _draining():
                st, _, data = _get(host, port, "/healthz")
                assert st == 200
                return json.loads(data)["status"] == "draining"

            wait_until(_draining, timeout=15,
                       msg="healthz never reported draining")
            st, _, data = _post(host, port, "/v1/completions",
                                {"prompt": [9], "max_tokens": 2})
            assert st == 503
            assert json.loads(data)["error"]["type"] == "unavailable"
            t.join(60)
            td.join(60)
            assert drained["ok"] is True
            st, _, data = inflight["r"]
            ch = json.loads(data)["choices"][0]
            assert st == 200 and len(ch["token_ids"]) == 20
            assert ch["finish_reason"] == "length"
            assert eng.scheduler.all_done()
            assert eng.cache.free_pages == eng.cache.allocatable_pages


class TestTeardownRace:
    def test_concurrent_close_and_abort(self):
        """close() and abort() can run concurrently (a chaos kill drill
        aborting while the fleet supervisor tears the replica down,
        round-22 in-suite flake): exactly one caller must win the
        listener handoff — the loser used to dereference a None
        _httpd."""
        m = tiny_model(seed=11)
        for trial in range(4):
            eng = ServingEngine(m, page_size=4, num_pages=64,
                                max_batch=4, prefill_chunk=8)
            srv = ServingServer(eng)
            srv.start()
            errs = []
            tearers = (lambda: srv.close(timeout=30), srv.abort,
                       srv.abort, lambda: srv.close(timeout=30))
            barrier = threading.Barrier(len(tearers))

            def tear(fn):
                barrier.wait(60)
                try:
                    fn()
                except Exception as e:  # pragma: no cover - the bug
                    errs.append(e)

            threads = [threading.Thread(target=tear, args=(f,))
                       for f in tearers]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not errs, errs
            assert srv._httpd is None


# ---------------------------------------------------------------------------
# observability


_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_]+=\"[^\"]*\"(,[a-zA-Z0-9_]+"
    r"=\"[^\"]*\")*\})? [-+]?([0-9]*\.)?[0-9]+([eE][-+]?[0-9]+)?$")


class TestMetricsEndpoint:
    def test_prometheus_exposition_valid(self):
        m = tiny_model(seed=7)
        with served(m) as (srv, eng, host, port):
            st, _, _ = _post(host, port, "/v1/completions",
                             {"prompt": [1, 2, 3], "max_tokens": 3})
            assert st == 200
            st, headers, data = _get(host, port, "/metrics")
            assert st == 200
            assert headers["Content-Type"].startswith("text/plain")
            assert "version=0.0.4" in headers["Content-Type"]
            text = data.decode()
            families = set()
            for line in text.splitlines():
                if not line:
                    continue
                if line.startswith("# TYPE "):
                    name, kind = line.split()[2:4]
                    assert kind in ("counter", "gauge", "summary",
                                    "histogram"), line
                    families.add(name)
                else:
                    assert _PROM_LINE.match(line), f"invalid: {line!r}"
            for want in ("paddle_tpu_serving_tokens_generated",
                         "paddle_tpu_serving_queue_depth_gauge",
                         "paddle_tpu_serving_page_occupancy_gauge",
                         "paddle_tpu_serving_running_gauge",
                         "paddle_tpu_serving_ttft_s",
                         "paddle_tpu_serving_rejections"):
                assert want in families, want
            # round 11: TTFT/TPOT expose REAL cumulative buckets (the
            # 0.0.4 histogram shape — aggregatable across replicas),
            # and the cumulative-monotone property holds
            assert "# TYPE paddle_tpu_serving_ttft_s histogram" in text
            counts = [int(mo.group(1)) for mo in re.finditer(
                r'paddle_tpu_serving_ttft_s_bucket\{le="[^"]+"\} (\d+)',
                text)]
            assert counts and counts == sorted(counts)
            assert counts[-1] == 1  # one request -> +Inf bucket == 1
            assert 'paddle_tpu_serving_ttft_s_bucket{le="+Inf"} 1' \
                in text

    def test_healthz_shape(self):
        m = tiny_model(seed=8)
        with served(m) as (srv, eng, host, port):
            st, _, data = _get(host, port, "/healthz")
            assert st == 200
            h = json.loads(data)
            assert h["status"] == "ok"
            for key in ("waiting", "live", "free_pages",
                        "requests_finished", "cache_dtype",
                        "weight_quant", "tp_degree", "tp_mesh"):
                assert key in h, key
            assert h["tp_degree"] == 1  # non-TP engine advertises 1


# ---------------------------------------------------------------------------
# fault injection: the loop survives injected step errors


class TestFaultInjection:
    def test_injected_errors_do_not_lose_requests(self, monkeypatch):
        # seed 3's step_fault stream fires on the FIRST draw (the
        # round-17 chaos layer derives one RNG stream per fault point,
        # so the old seed-7 schedule no longer applies)
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_ERROR_RATE", "0.3")
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_SEED", "3")
        m = tiny_model(seed=9)
        prompt = np.random.default_rng(9).integers(0, 97, 6).astype(
            np.int32)
        want = np.asarray(m.generate(P.to_tensor(prompt[None]),
                                     max_new_tokens=8)._data)[0]
        with served(m) as (srv, eng, host, port):
            st, _, data = _post(host, port, "/v1/completions",
                                {"prompt": [int(t) for t in prompt],
                                 "max_tokens": 8})
            assert st == 200
            ch = json.loads(data)["choices"][0]
            np.testing.assert_array_equal(ch["token_ids"], want)
            assert eng.metrics.faults_injected.value > 0


# ---------------------------------------------------------------------------
# request validation


class TestValidation:
    def test_bad_requests(self):
        m = tiny_model(seed=10)
        with served(m) as (srv, eng, host, port):
            cases = [
                ("/v1/completions", b"{not json",
                 "invalid JSON"),
                ("/v1/completions", json.dumps({"max_tokens": 4}),
                 "prompt is required"),
                ("/v1/completions", json.dumps(
                    {"prompt": "text prompt", "max_tokens": 4}),
                 "no tokenizer"),
                ("/v1/completions", json.dumps(
                    {"prompt": [1] * 60, "max_tokens": 30}),
                 "max_seq_len"),
                ("/v1/chat/completions", json.dumps({"messages": []}),
                 "non-empty"),
            ]
            for path, raw, msg in cases:
                c = http.client.HTTPConnection(host, port, timeout=30)
                c.request("POST", path, raw,
                          {"Content-Type": "application/json"})
                r = c.getresponse()
                assert r.status == 400, (path, msg)
                assert msg in json.loads(r.read())["error"]["message"]
                c.close()
            st, _, _ = _post(host, port, "/v1/nope", {})
            assert st == 404
            st, _, _ = _get(host, port, "/nope")
            assert st == 404

    def test_string_prompt_with_tokenizer(self):
        m = tiny_model(seed=11)
        tok = {"server_kw": {
            "tokenizer": lambda s: [ord(c) % 97 for c in s],
            "detokenizer": lambda t: chr(97 + t % 26)}}
        with served(m, **tok) as (srv, eng, host, port):
            st, _, data = _post(host, port, "/v1/completions",
                                {"prompt": "hello", "max_tokens": 3})
            assert st == 200
            body = json.loads(data)
            assert len(body["choices"][0]["token_ids"]) == 3
            assert len(body["choices"][0]["text"]) == 3  # detokenized


# ---------------------------------------------------------------------------
# long replay over sockets (slow tier; tools/serving_server_smoke.sh)


@pytest.mark.slow
class TestServerReplay:
    def test_bench_serving_http_subprocess(self):
        import subprocess
        import sys
        root = os.path.join(os.path.dirname(__file__), "..")
        p = subprocess.run(
            [sys.executable, "bench_serving.py", "--server", "--smoke"],
            cwd=root, capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        out = json.loads(p.stdout.strip().splitlines()[-1])
        assert out["metric"].startswith("serving_http_tok_per_s")
        assert out["value"] > 0
        assert out["ttft_p50_s"] is not None
        assert out["preemptions"] == 0
