"""paddle_tpu.serving.chaos (ISSUE 10) — the unified fault layer and
the production hardening it demands: ChaosConfig legacy-knob aliasing,
deterministic per-point injection, the pinned backoff schedule, the
circuit breaker's open→half-open→close transitions (fake clock),
engine-level step faults / latency / allocator-pressure spikes,
held-page release on deadline expiry (the round-14 rule enforced for
timeouts), migration fault points with bounded retry + re-prefill
fallback (token exactness preserved), HTTP replica network faults with
hop retries, the flight-recorder dump on fault escalation and breaker
open (chaos visible as spans/flight events, router-merged), and the
multi-seed fleet fuzz (slow) with all-points coverage."""
import json
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (Backoff, ChaosConfig, ChaosInjector,
                                CircuitBreaker, DisaggRouter,
                                FAULT_POINTS, HTTPReplica,
                                InProcessReplica, ReplicaFailed,
                                ServingEngine, ServingFrontend,
                                ServingRouter, ServingServer)
from paddle_tpu.serving.chaos import (fleet_invariants, parse_rates,
                                      verify_engine_quiescent,
                                      verify_page_conservation)
from serving_utils import wait_until


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


def oracle_tokens(prompts, max_new, model_seed=0, engine_kw=None):
    eng = make_engine(model_seed, **(engine_kw or {}))
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
    res = eng.run()
    return [res[r]["tokens"] for r in rids]


def rng_prompts(n, lo=4, hi=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 97, int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


def consume(stream, timeout=120):
    return [ev["token"] for ev in stream.events(timeout=timeout)
            if ev["type"] == "token"]


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# ChaosConfig: the unified schedule + legacy-knob aliases


class TestChaosConfig:
    def test_parse_rates_roundtrips_every_point(self):
        spec = ",".join(f"{p}:0.25" for p in FAULT_POINTS)
        rates = parse_rates(spec)
        assert rates == {p: 0.25 for p in FAULT_POINTS}

    def test_unknown_point_raises(self):
        with pytest.raises(ValueError, match="unknown chaos fault"):
            parse_rates("step_fautl:0.5")
        with pytest.raises(ValueError, match="unknown chaos fault"):
            ChaosConfig(rates={"nope": 1.0})

    def test_legacy_knobs_alias_into_config(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_ERROR_RATE", "0.4")
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.02")
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_SEED", "11")
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_ESCALATE_N", "5")
        monkeypatch.setenv("PADDLE_TPU_SERVING_ROUTER_KILL", "1:7")
        cfg = ChaosConfig.from_env()
        assert cfg.rate("step_fault") == 0.4
        assert cfg.rate("step_latency") == 1.0  # latency knob implies
        assert cfg.step_latency_s == 0.02
        assert cfg.seed == 11
        assert cfg.escalate_n == 5
        assert cfg.router_kill == (1, 7)

    def test_chaos_seed_wins_over_fault_seed(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_SEED", "11")
        monkeypatch.setenv("PADDLE_TPU_SERVING_CHAOS_SEED", "23")
        assert ChaosConfig.from_env().seed == 23

    def test_chaos_schedule_knob(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_CHAOS",
                           "http_connect:0.5,crash_drain")
        cfg = ChaosConfig.from_env()
        assert cfg.rate("http_connect") == 0.5
        assert cfg.rate("crash_drain") == 1.0  # bare point = rate 1

    def test_explicit_config_freezes_schedule(self, monkeypatch):
        inj = ChaosInjector(ChaosConfig(rates={"step_fault": 0.0}))
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_ERROR_RATE", "1.0")
        assert inj.cfg.rate("step_fault") == 0.0  # env ignored
        env_inj = ChaosInjector()                 # env mode follows it
        assert env_inj.cfg.rate("step_fault") == 1.0


class TestChaosInjector:
    def test_same_seed_same_schedule(self):
        cfg = ChaosConfig(seed=5, rates={"step_fault": 0.5})
        seq = [ChaosInjector(cfg).fire("step_fault")
               for _ in range(1)]  # noqa: F841 - warm the pattern
        a = ChaosInjector(cfg)
        b = ChaosInjector(cfg)
        sa = [a.fire("step_fault") for _ in range(32)]
        sb = [b.fire("step_fault") for _ in range(32)]
        assert sa == sb and any(sa) and not all(sa)
        assert a.counts["step_fault"] == sum(sa)
        assert a.evaluated["step_fault"] == 32

    def test_points_draw_independent_streams(self):
        # enabling a SECOND point must not perturb the first point's
        # schedule — the property that makes fuzz failures shrinkable
        one = ChaosInjector(ChaosConfig(seed=5,
                                        rates={"step_fault": 0.5}))
        both = ChaosInjector(ChaosConfig(
            seed=5, rates={"step_fault": 0.5, "http_connect": 0.5}))
        sa = [one.fire("step_fault") for _ in range(32)]
        sb = []
        for _ in range(32):
            both.fire("http_connect")
            sb.append(both.fire("step_fault"))
        assert sa == sb

    def test_zero_rate_never_draws(self):
        inj = ChaosInjector(ChaosConfig(seed=1, rates={}))
        assert not any(inj.fire("step_fault") for _ in range(8))
        assert inj.evaluated["step_fault"] == 0

    def test_injected_sleeper(self):
        naps = []
        inj = ChaosInjector(ChaosConfig(), sleep=naps.append)
        inj.sleep(0.25)
        inj.sleep(0)
        assert naps == [0.25, 0]


# ---------------------------------------------------------------------------
# Backoff: the pinned deterministic schedule


class TestBackoff:
    def test_schedule_is_deterministic_per_seed(self):
        a = Backoff(base_s=0.05, max_s=2.0, retries=4, seed=9)
        b = Backoff(base_s=0.05, max_s=2.0, retries=4, seed=9)
        assert a.delays() == b.delays()
        assert a.delays() != Backoff(base_s=0.05, max_s=2.0, retries=4,
                                     seed=10).delays()

    def test_exponential_growth_with_bounded_jitter(self):
        b = Backoff(base_s=0.1, factor=2.0, max_s=100.0,
                    jitter_frac=0.1, retries=4, seed=3)
        ds = b.delays()
        for i, d in enumerate(ds):
            nominal = 0.1 * 2.0 ** i
            assert nominal * 0.9 <= d <= nominal * 1.1

    def test_no_jitter_schedule_exact_and_capped(self):
        b = Backoff(base_s=0.05, factor=2.0, max_s=0.15,
                    jitter_frac=0.0, retries=4, seed=0)
        assert b.delays() == [0.05, 0.1, 0.15, 0.15]  # cap at max_s


# ---------------------------------------------------------------------------
# CircuitBreaker: open -> half-open -> close, pinned on a fake clock


class TestCircuitBreaker:
    def test_transitions(self):
        clock = FakeClock()
        br = CircuitBreaker(threshold=2, cooldown_s=5.0, clock=clock)
        assert br.state == "closed" and br.allow()
        assert br.record_failure() is False   # 1/2: still closed
        assert br.record_failure() is True    # 2/2: OPEN transition
        assert br.state == "open" and not br.allow()
        assert br.opens == 1
        clock.t = 4.9
        assert not br.allow()                 # cooldown not elapsed
        clock.t = 5.0
        assert br.allow()                     # half-open trial admitted
        assert br.state == "half_open"
        br.record_success()
        assert br.state == "closed" and br.allow()
        assert br.failures == 0

    def test_half_open_failure_reopens(self):
        clock = FakeClock()
        br = CircuitBreaker(threshold=1, cooldown_s=2.0, clock=clock)
        assert br.record_failure() is True
        clock.t = 2.5
        assert br.allow() and br.state == "half_open"
        assert br.record_failure() is True    # trial failed: re-open
        assert br.opens == 2
        assert not br.allow()                 # fresh cooldown from 2.5
        clock.t = 4.6
        assert br.allow()

    def test_threshold_zero_disables(self):
        br = CircuitBreaker(threshold=0, cooldown_s=1.0,
                            clock=FakeClock())
        for _ in range(10):
            assert br.record_failure() is False
        assert br.state == "closed" and br.allow()


# ---------------------------------------------------------------------------
# Engine-level chaos: step faults, latency, allocator pressure


class TestEngineChaos:
    def test_step_faults_retried_token_exact(self):
        prompts = rng_prompts(3, seed=2)
        want = oracle_tokens(prompts, 6)
        cfg = ChaosConfig(seed=4, rates={"step_fault": 0.3})
        fe = ServingFrontend(make_engine(chaos=cfg)).start()
        try:
            streams = [fe.submit(p, max_new_tokens=6) for p in prompts]
            got = [consume(s) for s in streams]
            assert got == want
            assert fe.engine.metrics.faults_injected.value > 0
            assert fe.engine.chaos.counts["step_fault"] > 0
        finally:
            fe.drain()
        verify_engine_quiescent(fe.engine)

    def test_step_latency_via_injected_sleeper(self):
        naps = []
        cfg = ChaosConfig(seed=0, rates={"step_latency": 1.0},
                          step_latency_s=0.5)
        inj = ChaosInjector(cfg, name="engine",
                            sleep=lambda s: naps.append(s))
        eng = make_engine(chaos=inj)
        eng.add_request(np.arange(4, dtype=np.int32),
                        max_new_tokens=2)
        eng.run()
        # a 0.5 s/step schedule under a fake sleeper costs NO wall
        # time — the serving-raw-sleep rule's whole point
        assert naps and all(s == 0.5 for s in naps)

    def test_alloc_pressure_spike_degrades_not_deadlocks(self):
        prompts = rng_prompts(4, seed=5)
        want = oracle_tokens(prompts, 6)
        cfg = ChaosConfig(seed=2, rates={"alloc_pressure": 0.3},
                          alloc_pressure_frac=0.5,
                          alloc_pressure_steps=2)
        eng = make_engine(chaos=cfg, num_pages=64)
        fe = ServingFrontend(eng).start()
        try:
            streams = [fe.submit(p, max_new_tokens=6) for p in prompts]
            got = [consume(s) for s in streams]
            assert got == want
            assert eng.chaos.counts["alloc_pressure"] > 0
        finally:
            fe.drain()
        # spike fully released: conservation AND zero residue
        verify_engine_quiescent(eng)

    def test_spike_expires_while_idle(self):
        cfg = ChaosConfig(seed=0, rates={"alloc_pressure": 1.0},
                          alloc_pressure_frac=0.5,
                          alloc_pressure_steps=3)
        eng = make_engine(chaos=cfg)
        fe = ServingFrontend(eng).start()
        try:
            fe.submit(np.arange(4, dtype=np.int32),
                      max_new_tokens=2).result(timeout=60)
            # the request finished mid-spike; the IDLE loop must count
            # the spike down and release it (chaos_idle_tick), or an
            # idle engine would shed admissions forever
            wait_until(lambda: eng._chaos_spike is None, timeout=10,
                       msg="idle engine never released the spike")
            wait_until(lambda: eng.cache.available_pages
                       == eng.cache.allocatable_pages, timeout=10)
        finally:
            fe.drain()
        verify_engine_quiescent(eng)


class TestStepFailsAfterDispatch:
    """A step program that fails once it was handed the pools takes
    them with it (they are donated: PR 34). The engine comes back with
    empty caches and an empty prefix tree, and the next ``run()``
    recomputes every requeued request to the uninterrupted streams."""

    @pytest.mark.parametrize("ekw", [
        dict(prefix_cache=True), dict(prefix_cache=True,
                                      cache_dtype="int8"),
        dict(speculative_k=2)], ids=["prefix_cache", "int8_kv", "draft"])
    def test_lost_pools_come_back_empty_and_the_streams_whole(
            self, ekw, caplog):
        rng = np.random.default_rng(6)
        head = rng.integers(0, 97, 9).astype(np.int32)
        prompts = [np.concatenate([head, p]) for p in rng_prompts(5, seed=6)]
        ekw = dict(ekw, max_batch=4)
        if "speculative_k" in ekw:
            ekw["draft_model"] = tiny_model(seed=3)
        want = oracle_tokens(prompts, 8, engine_kw=dict(ekw))
        eng = make_engine(**ekw)
        first = [eng.add_request(p, max_new_tokens=8) for p in prompts[:2]]
        done = eng.run()                 # fills the prefix tree, if any
        assert eng.cache.cached_pages > 0 or not ekw.get("prefix_cache")
        evicted = eng.cache.prefix_evictions
        rest = [eng.add_request(p, max_new_tokens=8) for p in prompts[2:]]
        real, calls = eng._step_program(), []

        def fails_after_dispatch(*operands):
            out = real(*operands)        # the pools are the program's now
            calls.append(1)
            if len(calls) == 4:
                calls.append(eng.cache.cached_pages)
                raise RuntimeError("the device fell over")
            return out

        eng._ragged_fn = fails_after_dispatch
        with caplog.at_level("ERROR", logger="paddle_tpu.serving"):
            with pytest.raises(RuntimeError, match="fell over"):
                eng.run()
        assert "pools_lost" in caplog.text
        c, cached = eng.cache, calls[-1]
        # the step's pools are new and empty; the draft's were not in
        # the failed program and are as its last program left them
        for cache, lost in ((c, True), (eng._draft_cache, False)):
            if cache is not None:
                pools = jax.tree.leaves(cache.program_operands())
                assert pools and not any(a.is_deleted() for a in pools)
                assert not lost or not any(np.asarray(a).any()
                                           for a in pools)
                assert not cache.live_seqs()
        assert c.cached_pages == 0 and c.prefix_tree_depth == 0
        assert c.prefix_evictions == evicted + cached
        assert c.available_pages == c.allocatable_pages
        # requeued, generated tokens kept; the next run recomputes them
        assert eng.scheduler.queue_depth() == len(rest)
        eng._ragged_fn = real
        res = {**done, **eng.run()}
        assert [res[r]["tokens"] for r in first + rest] == want
        assert sum(res[r]["preemptions"] for r in rest) > 0
        verify_engine_quiescent(eng)


    def test_a_failed_draft_program_costs_the_drafts_pools_alone(
            self, caplog):
        """The proposal scan is handed the draft's pools the same way:
        when it fails after dispatch the draft's cache is built anew,
        the target's pools and prefix tree are untouched."""
        prompts = rng_prompts(3, seed=8)
        ekw = dict(speculative_k=2, draft_model=tiny_model(seed=3),
                   prefix_cache=True, max_batch=4)
        want = oracle_tokens(prompts, 8, engine_kw=dict(ekw))
        eng = make_engine(**ekw)
        first = eng.add_request(prompts[0], max_new_tokens=8)
        done = eng.run()                 # builds the draft's programs
        rest = [eng.add_request(p, max_new_tokens=8) for p in prompts[1:]]
        real, calls = eng._propose_fn, []

        def fails_after_dispatch(*operands):
            out = real(*operands)
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("the device fell over")
            return out

        eng._propose_fn = fails_after_dispatch
        with caplog.at_level("ERROR", logger="paddle_tpu.serving"):
            with pytest.raises(RuntimeError, match="fell over"):
                eng.run()
        assert "the draft's cache" in caplog.text
        assert "after the cache's" not in caplog.text
        cached = eng.cache.cached_pages
        assert cached > 0                # the target's tree stands
        pools = jax.tree.leaves(eng._draft_cache.program_operands())
        assert not any(a.is_deleted() or np.asarray(a).any()
                       for a in pools)
        eng._propose_fn = real
        res = {**done, **eng.run()}
        assert [res[r]["tokens"] for r in [first] + rest] == want
        verify_engine_quiescent(eng)


# ---------------------------------------------------------------------------
# Held pages released on deadline expiry (round-14 rule for timeouts)


class TestHeldDeadline:
    def test_held_pages_release_on_expiry(self):
        eng = make_engine()
        fe = ServingFrontend(eng).start()
        try:
            # warm the compiled step programs first: the deadline must
            # race the HOLD, not the first-call jit trace
            fe.submit(np.arange(9, dtype=np.int32),
                      max_new_tokens=2).result(timeout=60)
            free0 = eng.cache.free_pages
            s = fe.submit(np.arange(9, dtype=np.int32),
                          max_new_tokens=6, prefill_only=True,
                          deadline_s=1.0)
            out = s.result(timeout=60)
            assert out[0]["finish_reason"] == "prefilled"
            with fe.lock:
                assert len(eng._held) == 1
                assert eng.cache.free_pages < free0  # pages held
            # the engine is IDLE now (held request finished): the
            # front-end's idle sweep must still expire the hold
            wait_until(lambda: eng.metrics.held_expired.value == 1,
                       timeout=15,
                       msg="held deadline never expired")
            with fe.lock:
                assert not eng._held
                assert eng.cache.free_pages == free0
            flight = [ev["kind"] for ev in eng.trace.flight.dump()]
            assert "held_expired" in flight
        finally:
            fe.drain()
        verify_engine_quiescent(eng)

    def test_no_deadline_holds_indefinitely(self):
        eng = make_engine()
        fe = ServingFrontend(eng).start()
        try:
            s = fe.submit(np.arange(9, dtype=np.int32),
                          max_new_tokens=6, prefill_only=True)
            s.result(timeout=60)
            time.sleep(0.15)  # idle sweeps run; nothing must expire
            with fe.lock:
                assert len(eng._held) == 1
                assert eng.metrics.held_expired.value == 0
            fe.release_request(list(eng._held)[0])
        finally:
            fe.drain()
        verify_engine_quiescent(eng)


# ---------------------------------------------------------------------------
# Fault escalation dumps the flight ring (satellite: PR-9 gap)


class TestEscalationFlightDump:
    def test_escalation_fails_loop_and_dumps_ring(self, caplog):
        cfg = ChaosConfig(seed=0, rates={"step_fault": 1.0},
                          escalate_n=3)
        fe = ServingFrontend(make_engine(chaos=cfg)).start()
        try:
            s = fe.submit(np.arange(5, dtype=np.int32),
                          max_new_tokens=4)
            with caplog.at_level("ERROR", "paddle_tpu.serving"):
                with pytest.raises(RuntimeError,
                                   match="fault escalation"):
                    consume(s)
                wait_until(lambda: fe.state == "failed", timeout=10)
            dumps = [r for r in caplog.records
                     if "flight_recorder_dump" in r.getMessage()]
            assert dumps, "escalation did not dump the flight ring"
            payload = json.loads(dumps[-1].getMessage())
            kinds = [ev["kind"] for ev in payload["events"]]
            # the injected faults AND the terminal loop error are in
            # the ring — the post-mortem shows WHY the loop died
            assert "fault" in kinds and "loop_error" in kinds
        finally:
            fe._stop.set()
        # escalation released the live pages before failing
        verify_engine_quiescent(fe.engine, require_drained=False)


# ---------------------------------------------------------------------------
# Circuit breaker wired through the router (healthz + /metrics + dump)


class TestRouterBreaker:
    def _router(self, clock, n=2, breaker_n=2):
        reps = [InProcessReplica(make_engine(seed=0))
                for _ in range(n)]
        cfg = ChaosConfig(seed=0, breaker_n=breaker_n,
                          breaker_cooldown_s=5.0)
        return ServingRouter(reps, policy="round_robin", page_size=4,
                             chaos=cfg, breaker_clock=clock).start()

    def test_open_half_open_close_through_router(self, caplog):
        clock = FakeClock()
        router = self._router(clock)
        try:
            with caplog.at_level("ERROR", "paddle_tpu.serving"):
                router._record_replica_failure(1, "transport flake")
                assert router.breaker_state(1) == "closed"
                router._record_replica_failure(1, "transport flake")
            assert router.breaker_state(1) == "open"
            assert router.metrics.breaker_opens_total.value(
                replica=1) == 1
            # advertised in /healthz ...
            h = router.health()
            assert h["replicas"][1]["breaker"] == "open"
            assert h["replicas"][0]["breaker"] == "closed"
            # ... counted in /metrics ...
            text = router.prometheus()
            assert 'breaker_opens_total{replica="1"} 1' in text
            assert 'replica_breaker_open{replica="1"} 1' in text
            # ... excluded from routing while open ...
            assert router._routable() == [0]
            # ... and the open DUMPED the router flight ring
            dumps = [r for r in caplog.records
                     if "flight_recorder_dump" in r.getMessage()]
            assert dumps and json.loads(
                dumps[-1].getMessage())["cause"] == "breaker_open"
            kinds = [ev["kind"]
                     for ev in router.trace.flight.dump()]
            assert "breaker_open" in kinds
            # cooldown -> half-open trial -> success closes
            clock.t = 5.0
            assert 1 in router._routable()
            assert router.breaker_state(1) == "half_open"
            s = router.submit(np.asarray([1, 2, 3], np.int32),
                              max_new_tokens=2)
            s.result(timeout=60)
            router._breakers[1].record_success() \
                if router.breaker_state(1) != "closed" else None
            assert router.breaker_state(1) in ("closed", "half_open")
        finally:
            router.close()

    def test_breaker_gates_the_prober(self):
        clock = FakeClock()
        router = self._router(clock, breaker_n=1)
        try:
            router.kill_replica(1, ReplicaFailed("hard kill"))
            router._record_replica_failure(1, "hard kill")
            assert router.breaker_state(1) == "open"
            # open + cooling: the prober must NOT probe (or readmit)
            assert router.probe_now() == []
            assert 1 in router._down
            # cooldown elapsed: the prober may probe again; the
            # replica's loop FAILED so it stays down (round-12 rule)
            clock.t = 6.0
            assert router.probe_now() == []
            assert 1 in router._down
        finally:
            router.close()


# ---------------------------------------------------------------------------
# HTTP replica network faults + hop retries


class TestHTTPChaos:
    def test_connect_refused_exhausts_bounded_retries(self):
        naps = []
        cfg = ChaosConfig(seed=0, rates={"http_connect": 1.0},
                          retry_max=3, retry_base_s=0.01,
                          retry_max_s=0.05)
        inj = ChaosInjector(cfg, name="http",
                            sleep=lambda s: naps.append(s))
        rep = HTTPReplica("127.0.0.1", 1, chaos=inj)  # port unused
        assert rep.health()["status"] == "unreachable"
        assert rep.retry_count == 3          # bounded, counted
        assert len(naps) == 3                # backoff slept via chaos
        assert naps == sorted(naps) or len(set(naps)) > 1

    def test_midstream_eof_fails_over_token_exact(self):
        prompts = rng_prompts(2, seed=8)
        want = oracle_tokens(prompts, 5)
        remote = make_engine(seed=0)
        srv = ServingServer(remote)
        host, port = srv.start()
        http_cfg = ChaosConfig(seed=1,
                               rates={"http_midstream_eof": 1.0})
        reps = [HTTPReplica(host, port, chaos=http_cfg),
                InProcessReplica(make_engine(seed=0))]
        router = ServingRouter(reps, policy="round_robin", page_size=4)
        router.start()
        try:
            got = []
            for p in prompts:
                s = router.submit(p, max_new_tokens=5)
                got.append(consume(s, timeout=60))
            assert got == want  # spliced across the EOF failover
            assert reps[0].chaos.counts["http_midstream_eof"] >= 1
        finally:
            router.close()
            srv.close()
        verify_engine_quiescent(remote, require_drained=False,
                                what="remote")


# ---------------------------------------------------------------------------
# Migration fault points: bounded retry, fallback, exactness, spans


class TestMigrationChaos:
    def _disagg(self, rates, seed=0, **cfg_kw):
        cfg_kw.setdefault("retry_base_s", 0.001)
        cfg_kw.setdefault("retry_max_s", 0.01)
        cfg = ChaosConfig(seed=seed, rates=rates, **cfg_kw)
        reps = [InProcessReplica(make_engine(0, prefix_cache=True),
                                 role=r)
                for r in ("prefill", "decode")]
        return DisaggRouter(reps, page_size=4, chaos=cfg).start()

    @pytest.mark.parametrize("point", ["migrate_import_bounce",
                                       "migrate_transfer_kill",
                                       "migrate_export_fail"])
    def test_migration_faults_keep_streams_exact(self, point):
        prompts = rng_prompts(2, lo=8, hi=14, seed=9)
        want = oracle_tokens(prompts, 6)
        router = self._disagg({point: 1.0})
        try:
            got = [consume(router.submit(p, max_new_tokens=6),
                           timeout=60) for p in prompts]
            assert got == want
            assert router.chaos.counts[point] >= 1
            if point == "migrate_transfer_kill":
                # the transient path retried with backoff first
                assert router.metrics.retries_total.value(
                    op="migrate") > 0
            if point != "migrate_export_fail":
                assert router.metrics.migration_fallbacks_total.value \
                    >= 1 or router.metrics.failovers_total.total >= 1
            # chaos visible as spans + flight events, router-merged
            d = router.debug_trace()
            span_names = {s["name"] for s in d["stitched"]}
            assert "chaos" in span_names
            fl = router.debug_flight()
            kinds = [ev["kind"] for ev in fl["router"]["events"]]
            assert "chaos" in kinds
        finally:
            router.close()
        fleet_invariants(router)

    def test_clean_fleet_unaffected_by_zero_rates(self):
        prompts = rng_prompts(2, seed=10)
        want = oracle_tokens(prompts, 6)
        router = self._disagg({})
        try:
            got = [consume(router.submit(p, max_new_tokens=6),
                           timeout=60) for p in prompts]
            assert got == want
            assert sum(router.chaos.counts.values()) == 0
        finally:
            router.close()
        fleet_invariants(router)


# ---------------------------------------------------------------------------
# invariant helpers are themselves honest


class TestInvariantHelpers:
    def test_conservation_catches_a_seeded_leak(self):
        from paddle_tpu.serving import PagedKVCache
        c = PagedKVCache(2, 2, 4, page_size=4, num_pages=16)
        c.alloc_seq("a")
        c.append_slots("a", 6)
        verify_page_conservation(c)
        # simulate a leak: drop a page from the free list
        c._free.pop()
        with pytest.raises(AssertionError, match="page leak"):
            verify_page_conservation(c)

    def test_quiescence_catches_held_leak(self):
        eng = make_engine()
        rid = eng.add_request(np.arange(6, dtype=np.int32),
                              max_new_tokens=3, prefill_only=True)
        eng.run()
        assert rid in eng._held
        with pytest.raises(AssertionError, match="held"):
            verify_engine_quiescent(eng)
        eng.release_request(rid)
        verify_engine_quiescent(eng)


# ---------------------------------------------------------------------------
# the capstone: multi-seed fleet fuzz with all-points coverage


@pytest.mark.slow
class TestChaosFuzz:
    def test_eight_seeds_all_points_fired(self):
        """Acceptance: >= 8 distinct seeds through the mixed
        disagg/spec/quantized fleets + HTTP wave, invariants after
        every convulsion, and EVERY registered fault point fired at
        least once across the run (never-fired points fail)."""
        proc = subprocess.run(
            [sys.executable, "tools/chaos_fuzz.py", "--seeds", "8",
             "--json"],
            capture_output=True, text=True, timeout=1800)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout[proc.stdout.index("{"):])
        assert report["ok"] and not report["never_fired"]
        assert set(report["per_point"]) == set(FAULT_POINTS)
        assert all(report["per_point"][p] > 0 for p in FAULT_POINTS)
