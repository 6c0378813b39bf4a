"""paddle_tpu.serving.router — the multi-replica tier: routing
policies (round-robin / least-loaded / cache-aware with load cap),
token-exact mid-stream failover against a single-engine oracle (greedy
AND seeded-sampled; the determinism → transparent-retry link),
aggregated admission (429 only when every replica sheds), rolling
drain with weight-reload re-admit, merged replica-labelled /metrics,
and the router behind a real ServingServer (HTTP replicas included).
"""
import json
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (HTTPReplica, InProcessReplica, Rejected,
                                ReplicaFailed, ServingEngine,
                                ServingRouter, ServingServer,
                                Unavailable)
from serving_utils import wait_until, wait_until_reserved


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


def make_router(n=2, seed=0, policy="round_robin", engine_kw=None,
                **router_kw):
    # one model PER replica, identical weights (same init seed) — the
    # multi-replica contract; page_size matches the engines so the
    # router's affinity tree sees the same page boundaries
    reps = [InProcessReplica(make_engine(seed, **(engine_kw or {})))
            for _ in range(n)]
    router_kw.setdefault("page_size", 4)
    return ServingRouter(reps, policy=policy, **router_kw).start()


def oracle_tokens(prompts, max_new, model_seed=0, engine_kw=None,
                  **req_kw):
    """Single-engine oracle: the token streams an uninterrupted run
    produces (list-of-kw per prompt supported via req_kw lists)."""
    eng = make_engine(model_seed, **(engine_kw or {}))
    rids = []
    for i, p in enumerate(prompts):
        kw = {k: (v[i] if isinstance(v, list) else v)
              for k, v in req_kw.items()}
        rids.append(eng.add_request(p, max_new_tokens=max_new, **kw))
    res = eng.run()
    return [res[r]["tokens"] for r in rids]


def rng_prompts(n, lo=3, hi=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 97, int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


# ---------------------------------------------------------------------------
# routing policies


class TestPolicies:
    def test_round_robin_spreads(self):
        router = make_router(3, policy="round_robin")
        try:
            for p in rng_prompts(6):
                router.submit(p, max_new_tokens=2).result(timeout=60)
            routed = router.metrics.routed_total
            assert [routed.value(policy="round_robin", replica=i)
                    for i in range(3)] == [2, 2, 2]
        finally:
            router.close()

    def test_least_loaded_avoids_busy_replica(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.05")
        router = make_router(2, policy="least_loaded")
        try:
            # park a long request on whichever replica takes it
            busy = router.submit(np.asarray([1, 2, 3], np.int32),
                                 max_new_tokens=30)
            wait_until_reserved(router.replicas[busy.replica_idx])
            other = router.submit(np.asarray([4, 5], np.int32),
                                  max_new_tokens=2)
            assert other.replica_idx != busy.replica_idx
            other.result(timeout=60)
            busy.result(timeout=120)
        finally:
            router.close()

    def test_cache_aware_sticks_and_reuses(self):
        router = make_router(2, policy="cache_aware",
                             engine_kw={"prefix_cache": True})
        try:
            rng = np.random.default_rng(3)
            shared = rng.integers(0, 97, 16).astype(np.int32)
            idxs = set()
            for _ in range(5):
                p = np.concatenate(
                    [shared, rng.integers(0, 97, 3).astype(np.int32)])
                s = router.submit(p, max_new_tokens=2)
                s.result(timeout=60)
                idxs.add(s.replica_idx)
            assert len(idxs) == 1  # shared prefix stuck to one replica
            (idx,) = idxs
            eng = router.replicas[idx].engine
            assert eng.cache.prefix_hit_pages > 0  # engine cache reused
            # a DIFFERENT prefix is free to land elsewhere (falls back
            # to least-loaded, no affinity)
            q = rng.integers(0, 97, 19).astype(np.int32)
            s2 = router.submit(q, max_new_tokens=2)
            s2.result(timeout=60)
        finally:
            router.close()

    def test_cache_aware_load_cap_spills(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.05")
        router = make_router(2, policy="cache_aware", cache_load_cap=1,
                             engine_kw={"prefix_cache": True})
        try:
            rng = np.random.default_rng(4)
            shared = rng.integers(0, 97, 16).astype(np.int32)

            def req(tail_seed, max_new):
                p = np.concatenate(
                    [shared, np.asarray([tail_seed], np.int32)])
                return router.submit(p, max_new_tokens=max_new)

            first = req(1, 30)  # sticky replica now exceeds the cap
            wait_until_reserved(router.replicas[first.replica_idx])
            second = req(2, 2)  # hot prefix must SPILL, not queue
            assert second.replica_idx != first.replica_idx
            second.result(timeout=60)
            first.result(timeout=120)
        finally:
            router.close()


# ---------------------------------------------------------------------------
# mid-stream failover: the determinism -> transparent-retry centerpiece


class TestFailover:
    def _run_failover(self, router, prompts, max_new, kill_after,
                      **req_kw):
        """Submit all prompts, kill the replica serving stream 0 after
        it delivered ``kill_after`` tokens, return per-prompt tokens."""
        streams = [router.submit(
            p, max_new_tokens=max_new,
            **{k: (v[i] if isinstance(v, list) else v)
               for k, v in req_kw.items()})
            for i, p in enumerate(prompts)]
        out = [None] * len(streams)
        errs = []

        def consume(i):
            toks = []
            try:
                for ev in streams[i].events(timeout=120):
                    if ev["type"] == "token":
                        toks.append(ev["token"])
                        if i == 0 and len(toks) == kill_after:
                            router.kill_replica(
                                streams[0].replica_idx)
            except Exception as e:
                errs.append((i, repr(e)))
            out[i] = toks

        th = [threading.Thread(target=consume, args=(i,))
              for i in range(len(streams))]
        for t in th:
            t.start()
        for t in th:
            t.join(60)
        assert not errs, errs
        return out

    def test_greedy_failover_token_exact(self, monkeypatch):
        """Acceptance: 3 replicas, one killed mid-stream; every
        in-flight stream completes and the spliced streams are
        token-exact vs the single-engine oracle."""
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.02")
        prompts = rng_prompts(4, seed=10)
        want = oracle_tokens(prompts, 10)
        router = make_router(3, policy="round_robin")
        try:
            got = self._run_failover(router, prompts, 10, kill_after=3)
            assert got == want
            assert router.metrics.failovers_total.total >= 1
            assert router.metrics.spliced_tokens_total.value >= 3
        finally:
            router.close()

    def test_seeded_sampled_failover_token_exact(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.02")
        prompts = rng_prompts(4, seed=11)
        seeds = [100 + i for i in range(4)]
        want = oracle_tokens(prompts, 10, do_sample=True, seed=seeds,
                             temperature=0.9, top_k=20)
        router = make_router(3, policy="round_robin")
        try:
            got = self._run_failover(router, prompts, 10, kill_after=3,
                                     do_sample=True, seed=seeds,
                                     temperature=0.9, top_k=20)
            assert got == want
        finally:
            router.close()

    def test_router_assigns_seed_for_unseeded_sampling(self):
        """A sampled request with no client seed still fails over
        token-exactly: the router pins a seed at submit."""
        router = make_router(2)
        try:
            s = router.submit(np.asarray([1, 2, 3], np.int32),
                              max_new_tokens=2, do_sample=True)
            assert s.kwargs["seed"] is not None
            s.result(timeout=60)
        finally:
            router.close()

    def test_env_gated_kill_failover(self, monkeypatch):
        """PADDLE_TPU_SERVING_ROUTER_KILL=<replica>:<tokens> — the
        env-gated fault drill: the router kills the replica itself once
        it delivered that many tokens; streams still complete exactly."""
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.02")
        monkeypatch.setenv("PADDLE_TPU_SERVING_ROUTER_KILL", "0:2")
        prompts = rng_prompts(2, seed=12)
        want = oracle_tokens(prompts, 8)
        reps = [InProcessReplica(make_engine()) for _ in range(2)]
        router = ServingRouter(reps, policy="round_robin",
                               page_size=4).start()
        try:
            streams = [router.submit(p, max_new_tokens=8)
                       for p in prompts]
            got = [[ev["token"] for ev in s.events(timeout=120)
                    if ev["type"] == "token"] for s in streams]
            assert got == want
            assert router.metrics.failovers_total.value(replica=0) >= 1
            assert router.replicas[0].state == "failed"
        finally:
            router.close()

    def test_fault_injected_escalation_fails_over(self, monkeypatch):
        """A FaultInjected STREAK (>= PADDLE_TPU_SERVING_FAULT_
        ESCALATE_N) escalates to a loop failure — the router treats the
        sick replica like a crash and fails the streams over."""
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_ERROR_RATE", "1.0")
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_ESCALATE_N", "3")
        rep = InProcessReplica(make_engine())
        router = ServingRouter([rep], page_size=4).start()
        try:
            s = router.submit(np.asarray([1, 2], np.int32),
                              max_new_tokens=2)
            # rate 1.0: every step faults -> streak hits 3 -> loop fails
            # -> failover finds no survivor -> the stream errors loudly
            with pytest.raises(RuntimeError, match="failover failed"):
                s.result(timeout=60)
            assert rep.state == "failed"
            assert "escalation" in str(rep.frontend.error)
            assert rep.engine.metrics.faults_injected.value >= 3
        finally:
            router.close()

    def test_no_survivor_raises(self):
        router = make_router(1)
        try:
            s = router.submit(np.asarray([1, 2], np.int32),
                              max_new_tokens=4)
            router.kill_replica(0)
            with pytest.raises(RuntimeError):
                s.result(timeout=60)
        finally:
            router.close()


# ---------------------------------------------------------------------------
# aggregated admission


class TestAdmission:
    def test_rejected_only_when_all_replicas_shed(self):
        """2 replicas x 20-page pools, 5 pages/request worst-case:
        exactly 3 fit per replica. The router is NOT started for the
        burst — admission is pure reservation math under each frontend
        lock with zero engine steps, so the fleet-wide capacity
        arithmetic is exact (no race against requests finishing
        mid-burst); the loops then start and everything admitted runs
        to completion."""
        reps = [InProcessReplica(make_engine(0, num_pages=20))
                for _ in range(2)]
        router = ServingRouter(reps, policy="round_robin",
                               page_size=4)
        try:
            oks = [router.submit([5] * 8, max_new_tokens=12)
                   for _ in range(6)]
            # round-robin + shed-fallthrough packed both replicas full
            assert sorted(s.replica_idx for s in oks) \
                == [0, 0, 0, 1, 1, 1]
            sheds = []
            for _ in range(6):  # fleet is full: EVERY submit 429s
                with pytest.raises(Rejected) as ei:
                    router.submit([5] * 8, max_new_tokens=12)
                sheds.append(ei.value)
            for s in sheds:
                assert s.retry_after >= 1
                assert "all replicas shed" in str(s)
            assert router.metrics.router_shed_total.value == 6
            router.start()
            for s in oks:
                (res,) = s.result(timeout=120)
                assert len(res["tokens"]) == 12
                assert res["finish_reason"] == "length"
            # no replica preempted a running decode to admit the burst
            for rep in router.replicas:
                assert rep.engine.metrics.preemptions.value == 0
        finally:
            router.close()

    def test_unavailable_when_no_replica_routable(self):
        router = make_router(1)
        try:
            router.kill_replica(0)
            with pytest.raises(Unavailable):
                router.submit([1, 2], max_new_tokens=2)
        finally:
            router.close()


# ---------------------------------------------------------------------------
# rolling drain + weight-reload re-admit


class TestRollingDrain:
    def test_drain_under_load_loses_nothing_then_readmits(
            self, monkeypatch):
        """Acceptance: draining one replica under load loses zero
        requests; the drained replica re-admits after a (simulated)
        weight reload and serves traffic again."""
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.02")
        router = make_router(2, policy="round_robin",
                             engine_kw={"prefix_cache": True})
        try:
            prompts = rng_prompts(4, seed=20)
            streams = [router.submit(p, max_new_tokens=12)
                       for p in prompts]
            # both replicas picked their work up (live mid-decode, or
            # already finished — either way the drain drains real
            # state; deadline-poll, never a fixed sleep)
            for i in range(2):
                wait_until(
                    lambda i=i: (lambda h: h.get("live", 0)
                                 or h.get("requests_finished", 0))
                    (router.replicas[i].health()),
                    msg=f"replica {i} never picked up work")
            target = streams[0].replica_idx
            done = {}
            td = threading.Thread(target=lambda: done.setdefault(
                "ok", router.drain_replica(target, timeout=120)))
            td.start()
            wait_until(lambda: target in router._draining)
            # new work while draining: routed AWAY, never 5xx
            extra = [router.submit(p, max_new_tokens=4)
                     for p in rng_prompts(3, seed=21)]
            for s in extra:
                assert s.replica_idx != target
            td.join(60)
            assert done["ok"] is True
            # zero lost requests: every pre-drain stream completed
            for s in streams:
                res = s.result(timeout=120)
                assert len(res[0]["tokens"]) == 12
                assert res[0]["finish_reason"] == "length"
            for s in extra:
                s.result(timeout=120)
            assert router.replicas[target].state == "draining"
            # simulated weight reload + re-admit
            reloaded = {}
            router.readmit_replica(
                target, reload=lambda m: reloaded.setdefault("m", m))
            assert reloaded["m"] is router.replicas[target].engine.model
            assert router.replicas[target].state == "ok"
            # prefix cache was flushed with the old weights
            assert router.replicas[target].engine.cache.cached_pages \
                == 0
            # traffic reaches it again under round-robin
            idxs = {router.submit(p, max_new_tokens=2).replica_idx
                    for p in rng_prompts(4, seed=22)}
            assert target in idxs
        finally:
            router.close()


# ---------------------------------------------------------------------------
# merged observability


_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:+]*(\{[a-zA-Z0-9_]+=\"[^\"]*\"(,[a-zA-Z0-9_]+"
    r"=\"[^\"]*\")*\})? [-+]?([0-9]*\.)?[0-9]+([eE][-+]?[0-9]+)?$")


class TestMergedMetrics:
    def test_replica_labels_and_router_counters(self):
        router = make_router(2, policy="round_robin")
        try:
            for p in rng_prompts(4, seed=30):
                router.submit(p, max_new_tokens=2).result(timeout=60)
            text = router.prometheus()
            families = set()
            seen_type = set()
            for line in text.splitlines():
                if not line:
                    continue
                if line.startswith("# TYPE "):
                    name, kind = line.split()[2:4]
                    assert name not in seen_type, f"dup TYPE {name}"
                    seen_type.add(name)
                    assert kind in ("counter", "gauge", "summary",
                                    "histogram")
                    families.add(name)
                else:
                    assert _PROM_LINE.match(line), repr(line)
            # engine families, replica-labelled, both replicas present
            for i in (0, 1):
                assert (f'paddle_tpu_serving_tokens_generated'
                        f'{{replica="{i}"}} 4') in text
            # TTFT buckets survive the merge (aggregatable histograms);
            # 2 of the 4 requests landed on replica 0 -> 2 TTFT samples
            assert re.search(
                r'paddle_tpu_serving_ttft_s_bucket\{replica="0",'
                r'le="\+Inf"\} 2', text)
            # router-level families
            for fam in ("paddle_tpu_serving_router_routed_total",
                        "paddle_tpu_serving_router_failovers_total",
                        "paddle_tpu_serving_router_spliced_tokens_total",
                        "paddle_tpu_serving_router_router_shed_total",
                        "paddle_tpu_serving_router_replica_healthy"):
                assert fam in families, fam
            assert ('paddle_tpu_serving_router_routed_total'
                    '{policy="round_robin",replica="0"} 2') in text
            assert ('paddle_tpu_serving_router_replica_healthy'
                    '{replica="0"} 1') in text
        finally:
            router.close()

    def test_health_aggregates(self):
        router = make_router(2)
        try:
            h = router.health()
            assert h["status"] == "ok"
            assert len(h["replicas"]) == 2
            assert all(r["status"] == "ok" for r in h["replicas"])
            router.kill_replica(1)
            h = router.health()
            assert h["status"] == "ok"  # one survivor still routable
            assert h["replicas"][1]["status"] == "down"
        finally:
            router.close()


# ---------------------------------------------------------------------------
# the router behind a real ServingServer (same OpenAI-shaped API)


class TestRouterBehindServer:
    def test_sse_through_router_matches_oracle(self):
        import http.client
        prompts = rng_prompts(4, seed=40)
        want = oracle_tokens(prompts, 6)
        router = make_router(2, policy="round_robin")
        srv = ServingServer(router)
        host, port = srv.start()
        try:
            got = []
            for p in prompts:
                c = http.client.HTTPConnection(host, port, timeout=60)
                c.request("POST", "/v1/completions", json.dumps(
                    {"prompt": [int(t) for t in p], "max_tokens": 6,
                     "stream": True}),
                    {"Content-Type": "application/json",
                     "X-Request-Id": "router-e2e"})
                r = c.getresponse()
                assert r.status == 200
                toks = []
                for raw in r.read().splitlines():
                    if raw.startswith(b"data: ") \
                            and b"token_id" in raw:
                        ch = json.loads(raw[6:])
                        toks.append(ch["choices"][0]["token_id"])
                        assert ch["request_id"] == "router-e2e"
                got.append(toks)
                c.close()
            assert got == want
            # /metrics through the server is the MERGED exposition
            c = http.client.HTTPConnection(host, port, timeout=30)
            c.request("GET", "/metrics")
            text = c.getresponse().read().decode()
            c.close()
            assert 'replica="0"' in text and 'replica="1"' in text
            assert "paddle_tpu_serving_router_routed_total" in text
        finally:
            srv.close(timeout=60)

    def test_http_replica_roundtrip_and_failover(self):
        """An HTTPReplica (remote ServingServer) serves through the
        router; killing the remote engine loop mid-stream fails the
        request over to the in-process survivor, token-exactly."""
        import os
        prompts = rng_prompts(2, seed=41)
        want = oracle_tokens(prompts, 8)
        remote_eng = make_engine()
        remote_srv = ServingServer(remote_eng)
        host, port = remote_srv.start()
        local = InProcessReplica(make_engine())
        remote = HTTPReplica(host, port)
        router = ServingRouter([remote, local], policy="round_robin",
                               page_size=4).start()
        try:
            assert remote.state == "ok"
            assert remote.load() == 0.0
            assert "paddle_tpu_serving_tokens_generated" \
                in remote.prometheus()
            # route one through each; both must match the oracle
            s0 = router.submit(prompts[0], max_new_tokens=8)
            s1 = router.submit(prompts[1], max_new_tokens=8)
            assert {s0.replica_idx, s1.replica_idx} == {0, 1}
            by_idx = {s.replica_idx: s for s in (s0, s1)}
            got_remote = [ev["token"]
                          for ev in by_idx[0].events(timeout=120)
                          if ev["type"] == "token"]
            got_local = [ev["token"]
                         for ev in by_idx[1].events(timeout=120)
                         if ev["type"] == "token"]
            assert got_remote == want[0 if by_idx[0] is s0 else 1]
            assert got_local == want[0 if by_idx[1] is s0 else 1]
            # mid-stream kill of the REMOTE: SSE truncates -> failover
            os.environ["PADDLE_TPU_SERVING_FAULT_LATENCY_S"] = "0.02"
            try:
                s = router.submit(prompts[0], max_new_tokens=8)
                while s.replica_idx != 0:  # force it onto the remote
                    s.result(timeout=60)
                    s = router.submit(prompts[0], max_new_tokens=8)
                toks = []
                for ev in s.events(timeout=120):
                    if ev["type"] == "token":
                        toks.append(ev["token"])
                        if len(toks) == 2:
                            remote_srv.frontend.fail(
                                ReplicaFailed("remote killed"))
                assert toks == want[0]
                assert router.metrics.failovers_total.value(
                    replica=0) == 1
            finally:
                del os.environ["PADDLE_TPU_SERVING_FAULT_LATENCY_S"]
        finally:
            router.close()
            remote_srv.close(timeout=30)


# ---------------------------------------------------------------------------
# background health prober (round 12): down replicas auto-readmit


class _ScriptedReplica:
    """Minimal replica stub whose health status the test flips."""

    def __init__(self):
        self.status = "ok"
        self.health_calls = 0

    def start(self):
        return self

    def health(self):
        self.health_calls += 1
        return {"status": self.status}

    @property
    def state(self):
        return self.status

    def load(self):
        return 0.0

    def submit(self, prompt, **kw):
        raise Unavailable("stub never admits")

    def prometheus(self):
        return ""

    def drain(self, timeout=120.0):
        return True

    def resume(self):
        return self

    def fail(self, exc=None):
        self.status = "failed"

    def close(self, timeout=0.0):
        return True


class TestHealthProber:
    def test_probe_now_readmits_only_recovered(self):
        stub = _ScriptedReplica()
        local = InProcessReplica(make_engine())
        router = ServingRouter([stub, local], policy="round_robin",
                               page_size=4)
        router._down.add(0)
        stub.status = "failed"
        assert router.probe_now() == []           # still sick: stays down
        assert 0 in router._down
        stub.status = "ok"
        assert router.probe_now() == [0]          # recovered: readmitted
        assert 0 not in router._down
        assert router.metrics.readmissions_total.value(replica=0) == 1
        # draining replicas are never auto-readmitted
        router._down.add(0)
        router._draining.add(0)
        assert router.probe_now() == []
        assert 0 in router._down

    def test_failed_inprocess_replica_stays_down(self):
        """A killed in-process replica reports "failed" — the prober
        must NOT readmit it (it needs readmit_replica with a reload)."""
        router = make_router(2, policy="round_robin")
        try:
            router.kill_replica(0)
            assert router.probe_now() == []
            assert 0 in router._down
        finally:
            router.close()

    def test_probe_readmits_restarted_http_replica(self):
        """The ROADMAP round-11 item: an HTTPReplica whose remote
        server died stays down today until manual readmission — the
        prober re-probes it on a bounded interval and readmits once a
        restarted server answers /healthz ok."""
        remote_eng = make_engine()
        remote_srv = ServingServer(remote_eng)
        host, port = remote_srv.start()
        local = InProcessReplica(make_engine())
        remote = HTTPReplica(host, port)
        router = ServingRouter([remote, local], policy="round_robin",
                               page_size=4,
                               probe_interval_s=0.05).start()
        try:
            prompts = rng_prompts(1, seed=77)
            # kill the remote server entirely: submits to it fail over,
            # the router marks it down
            remote_srv.frontend.fail(ReplicaFailed("boom"))
            remote_srv.close(timeout=10)
            deadline = time.monotonic() + 10
            while 0 not in router._down \
                    and time.monotonic() < deadline:
                got = router.submit(prompts[0],
                                    max_new_tokens=4).result(60)
                assert got[0]["finish_reason"] == "length"
            assert 0 in router._down
            # restart a fresh server on the SAME port; the prober
            # thread readmits within its interval (poll w/ deadline)
            remote_srv2 = ServingServer(make_engine(), port=port)
            remote_srv2.start()
            try:
                wait_until(lambda: 0 not in router._down, timeout=10,
                           interval=0.05,
                           msg="prober never readmitted")
                assert router.metrics.readmissions_total.value(
                    replica=0) == 1
                # and the readmitted replica serves traffic again
                want = oracle_tokens(prompts, 6)
                for _ in range(4):
                    s = router.submit(prompts[0], max_new_tokens=6)
                    got = [ev["token"] for ev in s.events(timeout=60)
                           if ev["type"] == "token"]
                    assert got == want[0]
                assert router.metrics.routed_total.value(
                    policy="round_robin", replica=0) > 0
            finally:
                remote_srv2.close(timeout=30)
        finally:
            router.close(timeout=30)

    def test_env_knob_and_disabled_default(self, monkeypatch):
        router = make_router(1)
        try:
            assert router.probe_interval_s == 0.0
            assert router._probe_thread is None
        finally:
            router.close()
        monkeypatch.setenv("PADDLE_TPU_SERVING_PROBE_S", "7.5")
        router = ServingRouter(
            [InProcessReplica(make_engine())], page_size=4)
        assert router.probe_interval_s == 7.5
