#!/usr/bin/env python3
"""Fleet-wide chaos fuzz — the ISSUE-10 capstone harness.

Replays seeded request waves through a mixed disagg/spec/quantized
fleet while the unified chaos layer (paddle_tpu.serving.chaos) fires a
random fault schedule — step faults, latency, allocator pressure
spikes, migration export/import/transfer failures, HTTP connect/EOF/
slow-read faults, and (round 18) fleet prefix-ship faults: donor gone
mid-export, probe→import eviction races, torn wire payloads (both
fleets run with ``prefix_fleet=True`` over shared-prefix prompt waves,
so ships actually happen) — and the harness applies external convulsions
(replica kill, drain + readmit, fleet grow + crash-y shrink).  Round 19
adds a CONTROL-PLANE wave: a RouterSupervisor-fronted fleet (primary +
warm standby over a journal) with a ProcessReplicaBackend-supervised
replica, firing the four fleet fault points — ``router_crash`` (primary
dies mid-stream, clients splice onto the promoted standby),
``standby_takeover_race`` (a concurrent promotion races the idempotence
guard), ``journal_torn_write`` (recovery must skip the torn record),
``replica_proc_kill`` (the replica server is killed and supervision
restarts it within budget).  Round 20 adds a hierarchical-KV-tier
wave: a page-starved engine spilling evicted prefix chains to a tiny
host/disk pool and restoring them on the second pass, under
``tier_spill_fail`` / ``tier_restore_fail`` / ``tier_slow_io`` /
``tier_corrupt_payload`` (the pagewire CRC catches the bit-rot).
Round 21 adds a VERSIONED-DEPLOYMENT wave: a RollingDeployer rolls new
target weights across a spec fleet mid-traffic under
``deploy_swap_fail`` (pre-swap bounce → old version serves, re-rollout
converges) and ``deploy_stale_version`` (stale advertisement → one
fresh re-read converges), with version-pinned exactness — every client
stream matches ONE version's oracle in its entirety, never a
cross-version splice — then trains a draft on the wave's logged verify
pairs and pushes it under ``distill_push_torn`` (a torn payload
bounces whole on the engine's all-or-nothing validation).
After every wave the GLOBAL recovery invariants are asserted:

- two-allocator page conservation on every engine (target + draft),
- greedy token-exactness vs a fault-free single-engine oracle
  (client-side splice over bounded resubmits — the determinism
  contract: token t is pure in (weights, history, seed, t)),
- zero leaked reservations / held pages / chaos residue,
- router metrics consistency (every request finished somewhere),
- loop liveness: every stream completes under a 60 s deadline.

The run REPORTS per-fault-point fired counts aggregated over every
injector in the fleet and (by default) FAILS on a fault point that
never fired — a silent never-fired hook is a coverage hole, not a
pass.

Usage:
    python tools/chaos_fuzz.py [--seeds N] [--seed-base K] [--smoke]
                               [--json] [--no-require-points]

``--smoke`` is the tier-1 gate shape (tools/chaos_smoke.sh): one fixed
seed, small waves, no all-points requirement (single-seed firing is
rate-dependent); the full multi-seed run is the ``slow``-marked test in
tests/test_serving_chaos.py and the acceptance artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from collections import Counter as Tally

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# standalone driver: pick the CPU platform before any paddle_tpu/jax
# work
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as P  # noqa: E402
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.serving import (ChaosConfig, DisaggRouter,  # noqa: E402
                                FAULT_POINTS, HTTPReplica,
                                InProcessReplica,
                                ProcessReplicaBackend, Rejected,
                                ReplicaSpec, RouterSupervisor,
                                ServingEngine, ServingServer,
                                ServingRouter, ThreadLauncher,
                                Unavailable)
from paddle_tpu.serving.chaos import (fleet_invariants,  # noqa: E402
                                      verify_engine_quiescent)

VOCAB = 97
LIVENESS_S = 60.0  # the no-deadlock deadline per stream/wave

# internal fault-point rates for the fuzz fleets (latencies kept tiny:
# the schedules, not the waits, are under test)
ENGINE_RATES = {"step_fault": 0.03, "step_latency": 0.05,
                "alloc_pressure": 0.03,
                # tensor-parallel serving (round 23): tp-skewed page
                # geometry on adopt/import — must bounce to the
                # re-prefill/recompute fallback, never fail a request
                "shard_geometry_mismatch": 0.10}
ROUTER_RATES = {"migrate_export_fail": 0.10,
                "migrate_import_bounce": 0.20,
                "migrate_transfer_kill": 0.20,
                "crash_drain": 0.5, "crash_readmit": 0.5,
                "crash_shrink": 0.5,
                # fleet prefix ships (round 18): donor vanishing and
                # the probe->import eviction race, both of which must
                # degrade to recompute with conservation intact
                "prefix_export_gone": 0.30,
                "prefix_import_drift": 0.50}
HTTP_RATES = {"http_connect": 0.15, "http_midstream_eof": 0.15,
              "http_slow_read": 0.30,
              # torn prefix payload over the wire (WireFormatError)
              "prefix_wire_truncate": 0.50}
# fleet control plane (round 19): the supervisor's schedule drives the
# router-crash drill (per delivered token), the takeover-race probe
# (per promotion) and the journal tear (per appended record); the
# backend's schedule kills the supervised replica process (per
# supervision pass)
SUPERVISOR_RATES = {"router_crash": 0.05,
                    "standby_takeover_race": 1.0,
                    "journal_torn_write": 0.2}
BACKEND_RATES = {"replica_proc_kill": 0.05}
# hierarchical KV tiers (round 20): faults on the host/disk spill and
# restore paths — every one must degrade to the eviction/recompute the
# engine would have done anyway (token exactness holds regardless)
KVTIER_RATES = {"tier_spill_fail": 0.15, "tier_restore_fail": 0.15,
                "tier_slow_io": 0.3, "tier_corrupt_payload": 0.3}
# versioned live deployment (round 21): the deployer's swap chaos and
# the distiller's torn-push chaos — every one must degrade to the OLD
# version serving, never a failed request, never a cross-version splice
DEPLOY_RATES = {"deploy_swap_fail": 0.35, "deploy_stale_version": 0.5}
DISTILL_RATES = {"distill_push_torn": 0.5}


def tiny_model(seed=0, **kw):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=64, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def tiny_draft(seed=1):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=16,
                      intermediate_size=32, num_hidden_layers=1,
                      num_attention_heads=2,
                      max_position_embeddings=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def make_engine(model_seed=0, chaos=None, **kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 160)
    kw.setdefault("max_batch", 8)
    kw.setdefault("prefill_chunk", 8)
    return ServingEngine(tiny_model(model_seed), chaos=chaos, **kw)


def engine_chaos(seed, i):
    return ChaosConfig(seed=seed * 31 + i, rates=ENGINE_RATES,
                       step_latency_s=0.002, escalate_n=4,
                       alloc_pressure_frac=0.4, alloc_pressure_steps=3,
                       retry_base_s=0.001, retry_max_s=0.01)


def rng_prompts(rng, n, lo=4, hi=14, shared_frac=0.5):
    """Random prompts; a ``shared_frac`` fraction opens with one
    common 8-token (2-page) prefix, so the fleet prefix-ship path has
    real cross-replica hits to move (the round-18 fault points only
    fire on attempted ships)."""
    shared = rng.integers(0, VOCAB, 8).astype(np.int32)
    out = []
    for i in range(n):
        tail = rng.integers(0, VOCAB, int(rng.integers(lo, hi)))\
            .astype(np.int32)
        out.append(np.concatenate([shared, tail])
                   if i < int(round(n * shared_frac)) else tail)
    return out


def warm_engine(eng, seed=1234):
    """Compile the engine's bucketed program classes off-wave (one
    tiny request stepped to completion, FaultInjected retried).  The
    wave choreography — migrations teaching owners, then a prefix
    flush, then gated placements that ship — needs real step timings,
    and a first-call jit compile of several seconds swamps them."""
    from paddle_tpu.serving import FaultInjected
    rng = np.random.default_rng(seed)
    eng.add_request(rng.integers(0, VOCAB, 6).astype(np.int32),
                    max_new_tokens=2)
    for _ in range(500):
        if eng.scheduler.all_done():
            break
        try:
            eng.step()
        except FaultInjected:
            continue
    eng.cache.clear_prefix()  # the wave must start prefix-cold


def oracle_tokens(prompts, max_new, engine_kw=None):
    """The fault-free single-engine oracle streams."""
    eng = make_engine(**(engine_kw or {}))
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
    res = eng.run()
    return [res[r]["tokens"] for r in rids]


def consume_spliced(router, prompt, max_new, deadline_s=LIVENESS_S):
    """Client-side bounded retry with splice: a stream that dies
    (failover exhausted mid-convulsion) is resubmitted and the
    greedy-deterministic replay's already-delivered prefix dropped —
    the client-visible token sequence stays exactly the oracle's.
    Raises on liveness-deadline expiry (the no-deadlock gate)."""
    got = []
    deadline = time.monotonic() + deadline_s
    while True:
        if time.monotonic() >= deadline:
            raise TimeoutError(
                f"liveness: request not completed in {deadline_s}s")
        skip = len(got)
        try:
            stream = router.submit(prompt, max_new_tokens=max_new)
        except (Rejected, Unavailable):
            time.sleep(0.02)  # shed/drained: client retry-after
            continue
        try:
            for ev in stream.events(timeout=deadline_s):
                if ev["type"] != "token":
                    continue
                if skip > 0:
                    skip -= 1  # replayed prefix of a resubmission
                    continue
                got.append(ev["token"])
            return got
        except RuntimeError:
            continue  # stream died terminally: resubmit + splice


def collect_counts(router, extra_injectors=()):
    """Aggregate per-fault-point fired counts over every injector in
    the fleet (engines, router, HTTP replicas, extras)."""
    total = Tally()
    total.update(router.chaos.counts)
    for rep in router.replicas:
        eng = getattr(rep, "engine", None)
        if eng is not None:
            total.update(eng.chaos.counts)
        rep_chaos = getattr(rep, "chaos", None)
        if rep_chaos is not None:
            total.update(rep_chaos.counts)
    for inj in extra_injectors:
        total.update(inj.counts)
    return total


def check_metrics_consistency(router, n_requests):
    """Router bookkeeping after a drained wave: every client request
    finished on SOME replica at least once (failovers re-run them, so
    >= not ==), and the routed counter saw every placement."""
    finished = router.health().get("requests_finished", 0)
    assert finished >= 0  # down replicas drop out of the sum
    routed = router.metrics.routed_total.total
    assert routed >= n_requests, (
        f"routed_total={routed} < {n_requests} client requests")


def run_disagg_wave(seed, n_requests, max_new, flavor, smoke=False):
    """One disagg-fleet wave: prefill + decode(+spec) + decode under
    internal chaos, one external convulsion mid-flight, then drain +
    invariants + exactness.  Returns the wave's fault-count tally."""
    rng = np.random.default_rng(seed)
    engine_kw = {}
    if flavor == "int8":
        engine_kw["cache_dtype"] = "int8"
    # every prompt shares the 2-page prefix: migrations spread owners
    # over the decode side, the flush convulsion makes the prefill
    # replica miss, and every gated placement is a real ship candidate
    prompts = rng_prompts(rng, n_requests, shared_frac=1.0)
    want = oracle_tokens(prompts, max_new, engine_kw=engine_kw)

    def engine(i, **kw):
        return make_engine(0, chaos=engine_chaos(seed, i),
                           prefix_cache=True, **dict(engine_kw, **kw))

    spec_kw = {}
    if flavor == "spec":
        spec_kw = {"draft_model": tiny_draft(), "speculative_k": 2}
    reps = [InProcessReplica(engine(0), role="prefill"),
            InProcessReplica(engine(1, **spec_kw), role="decode"),
            InProcessReplica(engine(2), role="decode")]
    for rep in reps:
        warm_engine(rep.engine)
    router_cfg = ChaosConfig(seed=seed * 131, rates=ROUTER_RATES,
                             retry_base_s=0.001, retry_max_s=0.01,
                             breaker_n=3, breaker_cooldown_s=0.2)
    # prefix_max_owners=2 keeps the fleet prefix DEDUPED (prefill +
    # one decode copy): every surplus landing triggers a router-driven
    # drop, so later placements miss again and the ship path stays hot
    # for the round-18 fault points
    router = DisaggRouter(reps, chaos=router_cfg, page_size=4,
                          prefix_fleet=True, prefix_max_owners=2)
    router.start()
    results = [None] * n_requests
    errs = []
    flushed = threading.Event()  # the prefix_flush convulsion landed
    stop_flush = threading.Event()

    def flusher():
        """Rolling prefix-flush convulsion: once the first migration
        taught a decode owner, keep dropping the prefill replica's
        shared-prefix subtree — every recompute recommits it, so a
        one-shot flush opens exactly one miss window.  The rolling
        drop keeps the round-18 ship path (and its eviction-race
        fault point) hot for every gated placement."""
        deadline = time.monotonic() + 20.0
        while router.metrics.migrations_total.value < 1 \
                and time.monotonic() < deadline \
                and not stop_flush.is_set():
            time.sleep(0.05)
        flushed.set()
        while not stop_flush.wait(0.1):
            try:
                reps[0].drop_prefix(prompts[0][:8])
            except Exception:
                pass

    def worker(i):
        try:
            if i >= 2:
                # gated arrivals (first-call jit compiles make
                # wall-clock staggers useless): the late placements
                # must land AFTER the prefill replica's prefix flush,
                # with decode owners already recorded by the early
                # requests' migrations — that is the shape where the
                # fleet prefix-ship path (round 18) runs for real
                flushed.wait(timeout=30.0)
                time.sleep((i - 2) * 0.1)
            results[i] = consume_spliced(router, prompts[i], max_new)
        except Exception as e:  # noqa: BLE001 - recorded, re-raised
            errs.append((i, repr(e)))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_requests)]
    try:
        for t in threads:
            t.start()
        # external convulsions while the wave runs (the chaos crash_*
        # points fire INSIDE these calls per the router config)
        convulsions = ["prefix_flush", "drain_readmit"] if smoke else \
            ["prefix_flush", "drain_readmit", "grow_shrink"]
        for conv in convulsions:
            if conv == "prefix_flush":
                threading.Thread(target=flusher, daemon=True).start()
            elif conv == "drain_readmit":
                victim = int(rng.integers(0, len(reps)))
                router.drain_replica(victim, timeout=LIVENESS_S)
                try:
                    router.readmit_replica(victim)
                except RuntimeError:
                    pass  # crashed mid-drain: stays down (capacity
                    #      degraded, requests already failed over)
            elif conv == "grow_shrink":
                j = router.add_replica(
                    InProcessReplica(engine(9), role="decode"),
                    role="decode")
                router.retire_replica(j, timeout=LIVENESS_S)
        for t in threads:
            t.join(timeout=LIVENESS_S)
            assert not t.is_alive(), "liveness: consumer thread stuck"
        stop_flush.set()
        assert not errs, f"stream failures: {errs}"
        assert results == want, (
            "token exactness violated vs the fault-free oracle: "
            + json.dumps({"got": results, "want": want}))
        router.drain(timeout=LIVENESS_S)
        check_metrics_consistency(router, n_requests)
        fleet_invariants(router)
        return collect_counts(router)
    finally:
        stop_flush.set()
        router.close(timeout=LIVENESS_S)


def run_http_wave(seed, n_requests, max_new):
    """One HTTP wave: a remote ServingServer behind an HTTPReplica
    (network fault injection + hop retries) with an in-process
    fallback replica; exactness via failover, then invariants on the
    remote engine too (we own it in-process)."""
    rng = np.random.default_rng(seed + 7)
    # every prompt shares the prefix: round-robin placement lands the
    # shared pages on replica 0 first, so the next placements attempt
    # real cross-replica ships over the /v1/_pages/prefix wire (the
    # prefix_wire_truncate point only evaluates on HTTP exports)
    prompts = rng_prompts(rng, n_requests, shared_frac=1.0)
    want = oracle_tokens(prompts, max_new)
    remote_eng = make_engine(0, prefix_cache=True)
    warm_engine(remote_eng)
    srv = ServingServer(remote_eng, max_queued=n_requests + 2)
    host, port = srv.start()
    http_cfg = ChaosConfig(seed=seed * 17, rates=HTTP_RATES,
                           slow_read_s=0.01, retry_base_s=0.001,
                           retry_max_s=0.01)
    inproc_eng = make_engine(0, prefix_cache=True)
    warm_engine(inproc_eng)
    reps = [HTTPReplica(host, port, chaos=http_cfg),
            InProcessReplica(inproc_eng)]
    # the prober re-admits the HTTP replica after chaos EOF kills (the
    # remote server itself is healthy) — without it the wave collapses
    # to one replica and the ship path has no donors left; no dedup
    # cap here, the remote must STAY the warm donor
    router = ServingRouter(
        reps, policy="round_robin", page_size=4, prefix_fleet=True,
        probe_interval_s=0.05,
        chaos=ChaosConfig(seed=seed * 19,
                          rates={"prefix_export_gone": 0.25,
                                 "prefix_import_drift": 0.50},
                          retry_base_s=0.001,
                          retry_max_s=0.01, breaker_n=3,
                          breaker_cooldown_s=0.2))
    router.start()
    try:
        got = []
        for j, p in enumerate(prompts):
            got.append(consume_spliced(router, p, max_new))
            # convulsion: flush the shared prefix on the IN-PROCESS
            # replica after each request — the remote stays the warm
            # donor, so every in-process placement re-attempts a ship
            # whose export crosses the wire (the torn-payload fault
            # point only evaluates on HTTP exports)
            try:
                reps[1].drop_prefix(p[:8])
            except Exception:
                pass
        assert got == want, (
            "token exactness violated on the HTTP wave: "
            + json.dumps({"got": got, "want": want}))
        router.drain(timeout=LIVENESS_S)
        counts = collect_counts(router)
        return counts
    finally:
        router.close(timeout=LIVENESS_S)
        srv.close(timeout=LIVENESS_S)
        # the remote engine is ours: it must come back clean too
        from paddle_tpu.serving.chaos import verify_engine_quiescent
        verify_engine_quiescent(remote_eng, what="remote")


def run_fleet_wave(seed, n_requests, max_new):
    """One control-plane wave (round 19): a RouterSupervisor-fronted
    fleet — 2 in-process replicas + 1 ProcessReplicaBackend-supervised
    replica (ThreadLauncher: the identical supervision machinery, no
    process spawn cost) — under router crashes, takeover races, torn
    journal writes and replica-process kills, with exactness vs the
    fault-free oracle and conservation/quiescence/zero-leak checks
    after drain."""
    import tempfile
    rng = np.random.default_rng(seed + 13)
    prompts = rng_prompts(rng, n_requests, shared_frac=0.5)
    want = oracle_tokens(prompts, max_new)
    engines = [make_engine(0, chaos=engine_chaos(seed, 10 + i))
               for i in range(2)]
    for eng in engines:
        warm_engine(eng)
    reps = [InProcessReplica(eng) for eng in engines]
    backend = ProcessReplicaBackend(
        ReplicaSpec(), launcher=ThreadLauncher(),
        startup_s=LIVENESS_S, restart_budget=8,
        supervise_interval_s=0.2,
        chaos=ChaosConfig(seed=seed * 41, rates=BACKEND_RATES,
                          retry_base_s=0.001, retry_max_s=0.01))
    sup = None
    try:
        reps.append(backend.provision("mixed"))
        sup = RouterSupervisor(
            reps, journal_path=tempfile.mktemp(prefix="pdtpu_fuzz_j"),
            policy="round_robin", page_size=4, probe_interval_s=0.05,
            chaos=ChaosConfig(seed=seed * 43, rates=SUPERVISOR_RATES,
                              retry_base_s=0.001, retry_max_s=0.01,
                              breaker_n=3, breaker_cooldown_s=0.2))
        sup.start()
        results = [None] * n_requests
        errs = []

        def worker(i):
            try:
                results[i] = consume_spliced(sup, prompts[i], max_new)
            except Exception as e:  # noqa: BLE001 - recorded, gated
                errs.append((i, repr(e)))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=LIVENESS_S)
            assert not t.is_alive(), "liveness: consumer thread stuck"
        assert not errs, f"fleet-wave stream failures: {errs}"
        assert results == want, (
            "token exactness violated on the fleet wave: "
            + json.dumps({"got": results, "want": want}))
        sup.drain(timeout=LIVENESS_S)
        fleet_invariants(sup.active)
        # the supervised replica's engine lives behind HTTP — check it
        # directly (a killed incarnation's pages were released by the
        # kill path; the CURRENT one must simply be clean)
        entry = reps[2].backend_entry
        if entry is not None and entry.handle.engine is not None:
            verify_engine_quiescent(
                entry.handle.engine, what="proc-replica",
                require_drained=entry.handle.alive())
        counts = Tally()
        counts.update(sup.chaos.counts)
        counts.update(sup.journal.chaos.counts)
        counts.update(backend.chaos.counts)
        for eng in engines:
            counts.update(eng.chaos.counts)
        return counts
    finally:
        if sup is not None:
            sup.close(timeout=LIVENESS_S)
        assert backend.close(grace=10.0), "backend reap left orphans"
        assert not backend.live_pids(), "fleet wave leaked processes"


def run_kvtier_wave(seed, n_requests, max_new, flavor):
    """One hierarchical-KV-tier wave (round 20): a single small-pool
    engine whose radix tree THRASHES (num_pages sized below the wave's
    working set), so allocation pressure spills rc-0 chains to a tiny
    host pool with a file-backed disk tier under it (demotions and
    capacity sheds included), and the second pass over the same
    prompts attempts restores — with the four tier fault points firing
    on those paths, plus at-rest corruption that the pagewire CRC must
    catch.  The tier is strictly best-effort: token exactness vs the
    fault-free oracle must hold whatever fires, and cross-tier
    conservation (device + host + disk) must close after the wave."""
    from paddle_tpu.serving import DiskPagePool, HostPagePool
    from paddle_tpu.serving.chaos import verify_page_conservation
    rng = np.random.default_rng(seed + 23)
    engine_kw = {"cache_dtype": "int8"} if flavor == "int8" else {}
    # 5-6 page prompts against a 15-usable-page pool: even the 3-prompt
    # smoke working set overflows the device tree, so evictions (and
    # therefore spills, demotions and second-sweep restores) are
    # guaranteed, not rate-dependent
    prompts = rng_prompts(rng, n_requests, lo=20, hi=26,
                          shared_frac=0.5)
    want = oracle_tokens(prompts, max_new, engine_kw=engine_kw)
    cfg = ChaosConfig(seed=seed * 53, rates=KVTIER_RATES,
                      tier_slow_io_s=0.001,
                      retry_base_s=0.001, retry_max_s=0.01)
    pool = HostPagePool(budget_bytes=8 * 1024,
                        disk=DiskPagePool(budget_bytes=64 * 1024))
    eng = make_engine(0, chaos=cfg, prefix_cache=True, num_pages=16,
                      host_pool=pool, **engine_kw)
    warm_engine(eng)  # note: clear_prefix invalidates the tier too
    try:
        for _sweep in range(2):
            got = []
            for p in prompts:
                rid = eng.add_request(p, max_new_tokens=max_new)
                res = eng.run()
                got.append(res[rid]["tokens"])
            assert got == want, (
                "token exactness violated on the kvtier wave: "
                + json.dumps({"got": got, "want": want}))
        eng.prewarm_prefix()  # the autoscaler's grow hook, same path
        m = eng.metrics
        assert m.tier_spill_pages.value + m.tier_spill_dropped.value \
            > 0, "kvtier wave never spilled — pool sizing broken"
        assert m.tier_restore_hits.value + m.tier_restore_misses.value \
            > 0, "kvtier wave never attempted a restore"
        verify_page_conservation(eng.cache, "kvtier-wave")
        verify_engine_quiescent(eng, what="kvtier-wave")
        return Tally(eng.chaos.counts)
    finally:
        pool.clear()


def consume_pinned(router, prompt, max_new, deadline_s=LIVENESS_S):
    """Version-pinned client for the deploy wave: a stream that dies
    terminally is resubmitted from SCRATCH (the partial is dropped),
    never spliced — the resubmission may land on a different weight
    version, and a splice across versions is exactly the bug class the
    wave hunts.  Returns the one full stream that completed."""
    deadline = time.monotonic() + deadline_s
    while True:
        if time.monotonic() >= deadline:
            raise TimeoutError(
                f"liveness: request not completed in {deadline_s}s")
        try:
            stream = router.submit(prompt, max_new_tokens=max_new)
        except (Rejected, Unavailable):
            time.sleep(0.02)  # drained/deploying: client retry-after
            continue
        got = []
        try:
            for ev in stream.events(timeout=deadline_s):
                if ev["type"] == "token":
                    got.append(ev["token"])
            return got
        except RuntimeError:
            continue  # stream died: restart fresh on some version


def run_deploy_wave(seed, n_requests, max_new):
    """One versioned-deployment wave (round 21): a 3-replica spec fleet
    serves client streams WHILE a RollingDeployer rolls the target
    weights to a new version under ``deploy_swap_fail`` (pre-swap
    bounce: the old version keeps serving, a re-rollout converges by
    idempotence) and ``deploy_stale_version`` (stale advertisement:
    one fresh re-read converges, never a re-roll).  Exactness is
    version-pinned: every client stream must match ONE version's
    fault-free oracle in its entirety — a mixed-oracle stream is a
    cross-version splice, the structural failure the per-stream pin
    exists to prevent.  Then the distill leg trains a draft copy on
    the verify pairs engine 0 logged and pushes it through the same
    deployer under ``distill_push_torn``: a torn payload must bounce
    WHOLE on the engine's all-or-nothing validation (no replica ever
    advertises a torn version) and a later clean push must land."""
    from paddle_tpu.serving import (DistillBuffer, DraftDistiller,
                                    RollingDeployer, WeightRegistry,
                                    snapshot_weights)
    rng = np.random.default_rng(seed + 29)
    prompts = rng_prompts(rng, n_requests, shared_frac=0.25)
    want_old = oracle_tokens(prompts, max_new)
    want_new = oracle_tokens(prompts, max_new,
                             engine_kw={"model_seed": 7})
    assert want_old != want_new, "oracle versions indistinguishable"
    buf = DistillBuffer(capacity=256, max_history=8)
    engines = [make_engine(0, chaos=engine_chaos(seed, 20 + i),
                           draft_model=tiny_draft(1), speculative_k=2,
                           distill=buf if i == 0 else None)
               for i in range(3)]
    for eng in engines:
        warm_engine(eng)
    router = ServingRouter([InProcessReplica(e) for e in engines],
                           page_size=4)
    reg = WeightRegistry()
    new_v = reg.publish("target", snapshot_weights(tiny_model(7)))
    dep = RollingDeployer(
        router, reg, drain_timeout_s=LIVENESS_S,
        chaos=ChaosConfig(seed=seed * 59, rates=DEPLOY_RATES,
                          retry_base_s=0.001, retry_max_s=0.01))
    router.start()
    try:
        results = [None] * n_requests
        errs = []

        def worker(i):
            try:
                results[i] = consume_pinned(router, prompts[i],
                                            max_new)
            except Exception as e:  # noqa: BLE001 - recorded, gated
                errs.append((i, repr(e)))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_requests)]
        for t in threads:
            t.start()
        # roll mid-traffic; chaos swap failures leave failed entries
        # with the old version serving — re-running the SAME rollout
        # finishes it (idempotence is the retry contract)
        deadline = time.monotonic() + LIVENESS_S
        while True:
            report = dep.rollout("target", new_v)
            if report["complete"]:
                break
            assert time.monotonic() < deadline, (
                "target rollout never completed: "
                + json.dumps(report["replicas"]))
        for t in threads:
            t.join(timeout=LIVENESS_S)
            assert not t.is_alive(), "liveness: consumer thread stuck"
        assert not errs, f"deploy-wave stream failures: {errs}"
        for i, got in enumerate(results):
            assert got in (want_old[i], want_new[i]), (
                "cross-version splice on the deploy wave: "
                + json.dumps({"i": i, "got": got, "old": want_old[i],
                              "new": want_new[i]}))
        for rep in router.replicas:
            assert rep.weight_version("target") == new_v, (
                "replica not on the rolled version after completion")
        # post-rollout traffic is exclusively on the new version
        tail = consume_pinned(router, prompts[0], max_new)
        assert tail == want_new[0], (
            "post-rollout stream not on the new version")
        router.drain(timeout=LIVENESS_S)
        fleet_invariants(router)
        check_metrics_consistency(router, n_requests)
        # distill leg: engine 0's verify step fed the buffer during the
        # wave; train the draft copy and push under torn-payload chaos
        assert len(buf) > 0, "spec wave logged no distill pairs"
        dist = DraftDistiller(
            tiny_draft(9), buf, lr=1e-2, batch_size=16, min_pairs=1,
            chaos=ChaosConfig(seed=seed * 61, rates=DISTILL_RATES,
                              retry_base_s=0.001, retry_max_s=0.01))
        dist.train_once(max_steps=2)
        landed = None
        deadline = time.monotonic() + LIVENESS_S
        while landed is None and time.monotonic() < deadline:
            out = dist.push(reg, dep)
            v, rolled = out["version"], out["rolled"]
            # a swap-chaos bounce converges by re-rolling the SAME
            # version; a torn payload never can (the arrays themselves
            # are short) — the error text tells them apart
            while (not rolled["complete"]
                   and any(e["error"] and "deploy_swap_fail"
                           in e["error"]
                           for e in rolled["replicas"])
                   and time.monotonic() < deadline):
                rolled = dep.rollout("draft", v)
            if rolled["complete"]:
                landed = v
            else:
                for rep in router.replicas:
                    assert rep.weight_version("draft") != v, (
                        "torn draft push half-landed on a replica")
        assert landed is not None, (
            "no clean draft push landed within the deadline")
        for rep in router.replicas:
            assert rep.weight_version("draft") == landed
        return collect_counts(router,
                              extra_injectors=(dep.chaos, dist.chaos))
    finally:
        router.close()


def run_seed(seed, smoke=False):
    """One full fuzz round for one seed: a disagg wave (flavor cycles
    fp32-spec / int8 by seed parity) + an HTTP wave + the round-19
    control-plane wave + the round-20 hierarchical-KV-tier wave + the
    round-21 versioned-deployment wave."""
    flavor = "spec" if seed % 2 == 0 else "int8"
    n = 3 if smoke else 6
    counts = Tally()
    counts.update(run_disagg_wave(seed, n, max_new=6, flavor=flavor,
                                  smoke=smoke))
    counts.update(run_http_wave(seed, 2 if smoke else 4, max_new=6))
    counts.update(run_fleet_wave(seed, 2 if smoke else 5, max_new=6))
    counts.update(run_kvtier_wave(seed, 3 if smoke else 6, max_new=6,
                                  flavor=flavor))
    counts.update(run_deploy_wave(seed, 2 if smoke else 4, max_new=6))
    return flavor, counts


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--seed-base", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 shape: one seed, small waves, no "
                         "all-points requirement")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--no-require-points", action="store_true",
                    help="report never-fired fault points without "
                         "failing")
    args = ap.parse_args(argv)
    if args.smoke:
        args.seeds = 1
        args.no_require_points = True

    total = Tally()
    rounds = []
    t0 = time.monotonic()
    for k in range(args.seeds):
        seed = args.seed_base + k
        flavor, counts = run_seed(seed, smoke=args.smoke)
        rounds.append({"seed": seed, "flavor": flavor,
                       "counts": dict(counts)})
        total.update(counts)
        if not args.json:
            print(f"seed {seed} [{flavor}]: ok "
                  f"({sum(counts.values())} faults fired)")
    never = [p for p in FAULT_POINTS if total.get(p, 0) == 0]
    report = {
        "seeds": args.seeds, "seed_base": args.seed_base,
        "smoke": args.smoke,
        "wall_s": round(time.monotonic() - t0, 1),
        "per_point": {p: total.get(p, 0) for p in FAULT_POINTS},
        "never_fired": never,
        "total_fired": sum(total.values()),
        "ok": not never or args.no_require_points,
    }
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        print(json.dumps(report["per_point"], indent=1))
        if never:
            print(f"never fired: {never}", file=sys.stderr)
    if args.smoke and report["total_fired"] == 0:
        print("chaos smoke fired ZERO faults — schedule wiring broken",
              file=sys.stderr)
        return 1
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
