"""Fleet-wide prefix cache (round 18): the router's radix tree as a
KV-page TRANSFER INDEX.

Layers under test:
- allocator: ``export_prefix_pages`` / ``import_prefix_pages`` /
  ``drop_prefix`` (byte-exact roundtrip, drift/geometry bounces, full
  rollback, subtree-drop semantics, conservation under interleaved
  ships),
- engine/frontend: the blessed locked wrappers + capacity shed +
  /healthz ``cached_pages``/``prefix_tree_depth`` advertisement,
- router: the ship decision (dtype-skew guard both paths, donor
  liveness, eviction-race drift retry, min-pages threshold, dedup
  eviction pressure), token-exactness vs a single-engine oracle for
  greedy AND seeded device sampling,
- wire: the ``/v1/_pages/prefix`` endpoint family (roundtrip over real
  sockets, truncation 400, drift 409 carrying ``cached_pages``),
- chaos: the three round-18 fault points degrade to recompute with
  conservation intact.

Healthz assertions against a LIVE loop poll with a deadline
(serving_utils.wait_until) per the round-11 rule, never fixed sleeps.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (ChaosConfig, GeometryMismatch,
                                HTTPReplica, InProcessReplica,
                                OutOfPages, PagedKVCache, PrefixDrift,
                                Rejected, ServingEngine, ServingRouter,
                                ServingServer, WireFormatError,
                                deserialize_pages, serialize_pages)
from paddle_tpu.serving.chaos import (fleet_invariants,
                                      verify_page_conservation)
from paddle_tpu.serving.frontend import ServingFrontend

from serving_utils import wait_until

VOCAB = 97
PS = 4  # page size everywhere in this file


def make_cache(dtype="float32", num_pages=64, prefix_cache=True):
    return PagedKVCache(2, 2, 8, page_size=PS, num_pages=num_pages,
                        dtype=dtype, prefix_cache=prefix_cache)


def seed_prefix(cache, prompt, fill=None):
    """Prefill-and-free a prompt so its full pages sit CACHED (rc==0)
    in the radix tree, with distinguishable K/V content."""
    import jax.numpy as jnp
    sid = ("seed", int(cache._clock))
    cache.alloc_seq(sid)
    slots, _ = cache.append_slots(sid, len(prompt))
    if fill is not None:
        for li in range(cache.n_layers):
            flat = np.zeros((cache.num_pages * PS, cache.n_kv_heads,
                             cache.head_dim), np.float32)
            flat[slots] = fill + li + np.arange(len(prompt))[:, None,
                                                            None]
            shaped = flat.reshape(cache.num_pages, PS,
                                  cache.n_kv_heads, cache.head_dim)
            cache.k_pages[li] = jnp.asarray(shaped).astype(
                cache.dtype)
            cache.v_pages[li] = (jnp.asarray(shaped) * 2).astype(
                cache.dtype)
    cache.commit_prefix(sid, prompt, len(prompt))
    cache.free_seq(sid)


def model(seed=0, **kw):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=64, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def make_engine(seed=0, **kw):
    kw.setdefault("page_size", PS)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("prefix_cache", True)
    return ServingEngine(model(seed), **kw)


def oracle_tokens(prompts, max_new, sample_seeds=None, **engine_kw):
    eng = make_engine(**engine_kw)
    rids = []
    for i, p in enumerate(prompts):
        kw = {}
        if sample_seeds is not None:
            kw = {"do_sample": True, "temperature": 0.8,
                  "seed": sample_seeds[i]}
        rids.append(eng.add_request(p, max_new_tokens=max_new, **kw))
    res = eng.run()
    return [res[r]["tokens"] for r in rids]


def consume(stream):
    return [ev["token"] for ev in stream.events(timeout=60)
            if ev["type"] == "token"]


def shared_prompts(n_tail=2, shared_pages=3, seed=0):
    """One shared full-page prefix + distinct tails."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, VOCAB, shared_pages * PS).astype(np.int32)
    tails = [rng.integers(0, VOCAB, 5 + i).astype(np.int32)
             for i in range(n_tail)]
    return shared, [np.concatenate([shared, t]) for t in tails]


# ---------------------------------------------------------------------------
# 1. allocator level


class TestPrefixTransferAllocator:
    @pytest.mark.parametrize("dtype", ["float32", "int8"])
    def test_roundtrip_byte_exact(self, dtype):
        c1 = make_cache(dtype)
        c2 = make_cache(dtype)
        prompt = np.arange(3 * PS, dtype=np.int32)
        seed_prefix(c1, prompt, fill=1.0)
        meta, k, v = c1.export_prefix_pages(prompt)
        assert meta["kind"] == "prefix"
        assert meta["n_pages"] == 3 and meta["cached_pages"] == 3
        assert c2.import_prefix_pages(meta, k, v) == 3
        assert c2.cached_pages == 3
        # re-export from the importer: identical bytes (scales too)
        m2, k2, v2 = c2.export_prefix_pages(prompt)
        for a, b in zip(k + v, k2 + v2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        verify_page_conservation(c1)
        verify_page_conservation(c2)

    def test_export_refreshes_lru_and_skips(self):
        c1 = make_cache()
        prompt = np.arange(3 * PS, dtype=np.int32)
        seed_prefix(c1, prompt)
        meta, k, v = c1.export_prefix_pages(prompt, skip_pages=2)
        assert meta["skip_pages"] == 2 and meta["n_pages"] == 1
        assert len(meta["prompt"]) == 3 * PS  # FULL matched prefix
        with pytest.raises(PrefixDrift) as ei:
            c1.export_prefix_pages(prompt, skip_pages=5)
        assert ei.value.cached_pages == 3

    def test_import_drift_carries_true_count(self):
        c1 = make_cache()
        c2 = make_cache()
        prompt = np.arange(3 * PS, dtype=np.int32)
        seed_prefix(c1, prompt)
        meta, k, v = c1.export_prefix_pages(prompt)
        c2.import_prefix_pages(meta, k, v)
        # second import of the same skip=0 payload: local tree already
        # matches 3 pages -> drift, carrying the true count
        with pytest.raises(PrefixDrift) as ei:
            c2.import_prefix_pages(meta, k, v)
        assert ei.value.cached_pages == 3
        # the bounce recipe: re-export the corrected suffix (empty)
        m3, k3, v3 = c1.export_prefix_pages(prompt, skip_pages=3)
        assert c2.import_prefix_pages(m3, k3, v3) == 0
        verify_page_conservation(c2)

    def test_geometry_and_disabled_bounce(self):
        c1 = make_cache()
        prompt = np.arange(2 * PS, dtype=np.int32)
        seed_prefix(c1, prompt)
        meta, k, v = c1.export_prefix_pages(prompt)
        other = PagedKVCache(2, 2, 4, page_size=PS, num_pages=64,
                             prefix_cache=True)  # head_dim skew
        with pytest.raises(GeometryMismatch):
            other.import_prefix_pages(meta, k, v)
        int8 = make_cache("int8")
        with pytest.raises(GeometryMismatch):
            int8.import_prefix_pages(meta, k, v)  # dtype skew
        off = make_cache(prefix_cache=False)
        with pytest.raises(GeometryMismatch):
            off.import_prefix_pages(meta, k, v)  # nowhere to register
        bad = dict(meta, prompt=list(meta["prompt"]) + [1])
        with pytest.raises(ValueError):
            make_cache().import_prefix_pages(bad, k, v)
        verify_page_conservation(other)

    def test_out_of_pages_rolls_back(self):
        c1 = make_cache()
        prompt = np.arange(6 * PS, dtype=np.int32)
        seed_prefix(c1, prompt)
        meta, k, v = c1.export_prefix_pages(prompt)
        tiny = make_cache(num_pages=4)  # 3 allocatable < 6
        with pytest.raises(OutOfPages):
            tiny.import_prefix_pages(meta, k, v)
        assert tiny.cached_pages == 0
        assert tiny.free_pages == tiny.allocatable_pages
        verify_page_conservation(tiny)

    def test_drop_prefix_prunes_subtree(self):
        c = make_cache()
        shared, prompts = shared_prompts(n_tail=2, shared_pages=2)
        # commit shared prefix + two tails (the hot-system-prompt tree)
        for p in prompts:
            full = p[:len(p) - len(p) % PS]
            seed_prefix(c, full)
        assert c.cached_pages > 2
        assert c.prefix_tree_depth >= 2
        dropped = c.drop_prefix(shared)
        assert dropped == c.prefix_evictions
        assert c.cached_pages == 0  # whole subtree went
        assert c.free_pages == c.allocatable_pages
        verify_page_conservation(c)

    def test_drop_prefix_respects_pins(self):
        c = make_cache()
        prompt = np.arange(3 * PS, dtype=np.int32)
        seed_prefix(c, prompt)
        # a live sequence pins the chain
        matched = c.acquire_prefix("live", prompt, len(prompt) + 1)
        assert matched == 3
        assert c.drop_prefix(prompt) == 0
        c.free_seq("live")
        assert c.drop_prefix(prompt) == 3
        verify_page_conservation(c)

    def test_conservation_fuzz_interleaved_ships(self):
        rng = np.random.default_rng(7)
        caches = [make_cache(num_pages=32), make_cache(num_pages=32)]
        prefixes = [np.asarray(rng.integers(0, VOCAB, pages * PS),
                               np.int32)
                    for pages in (2, 3, 4)]
        for step in range(400):
            c = caches[rng.integers(0, 2)]
            other = caches[1 - caches.index(c)]
            p = prefixes[rng.integers(0, len(prefixes))]
            op = rng.integers(0, 4)
            try:
                if op == 0:
                    seed_prefix(c, p)
                elif op == 1:
                    meta, k, v = c.export_prefix_pages(
                        p, int(rng.integers(0, 2)))
                    other.import_prefix_pages(meta, k, v)
                elif op == 2:
                    c.drop_prefix(p)
                else:
                    sid = ("fuzz", step)
                    c.acquire_prefix(sid, p, len(p) + 1)
                    c.free_seq(sid)
            except (PrefixDrift, OutOfPages):
                pass
            if step % 50 == 0:
                for i, cc in enumerate(caches):
                    verify_page_conservation(cc, f"fuzz[{i}]")
        for i, cc in enumerate(caches):
            verify_page_conservation(cc, f"fuzz-final[{i}]")


# ---------------------------------------------------------------------------
# 2. engine/frontend wrappers + healthz


class TestPrefixFrontend:
    def test_wrappers_and_capacity_shed(self):
        donor_eng = make_engine()
        rid = donor_eng.add_request(np.arange(3 * PS + 2,
                                              dtype=np.int32),
                                    max_new_tokens=2)
        donor_eng.run()
        donor = ServingFrontend(donor_eng)
        prompt = np.arange(3 * PS + 2, dtype=np.int32)
        meta, k, v = donor.export_prefix(prompt)
        assert meta["n_pages"] == 3
        assert donor_eng.metrics.prefix_pages_exported.value == 3
        taker_eng = make_engine(1)
        taker = ServingFrontend(taker_eng)
        assert taker.import_prefix(meta, k, v) == 3
        assert taker_eng.metrics.prefix_pages_imported.value == 3
        assert taker.drop_prefix(prompt) == 3
        assert taker_eng.metrics.prefix_drops.value == 3
        # capacity shed: a payload the watermark cannot host
        tiny_eng = make_engine(2, num_pages=4)
        tiny = ServingFrontend(tiny_eng)
        with pytest.raises(Rejected):
            tiny.import_prefix(meta, k, v)
        verify_page_conservation(tiny_eng.cache)

    def test_healthz_advertises_prefix_stats(self):
        eng = make_engine()
        fe = ServingFrontend(eng)
        h = fe.health()
        assert h["cached_pages"] == 0
        assert h["prefix_tree_depth"] == 0
        assert "reclaimable_pages" in h
        fe.start()
        stream = fe.submit(np.arange(3 * PS + 1, dtype=np.int32),
                           max_new_tokens=2)
        consume(stream)
        # live loop: poll with a deadline, never a fixed sleep
        wait_until(lambda: fe.health()["cached_pages"] >= 3,
                   msg="cached_pages never advertised")
        assert fe.health()["prefix_tree_depth"] >= 3
        fe.drain()


# ---------------------------------------------------------------------------
# 3. the router ship (in-process fleet)


def make_fleet(n=2, dtypes=None, **router_kw):
    reps = []
    for i in range(n):
        kw = {}
        if dtypes is not None and dtypes[i] is not None:
            kw["cache_dtype"] = dtypes[i]
        reps.append(InProcessReplica(make_engine(0, **kw)))
    router_kw.setdefault("policy", "round_robin")
    router_kw.setdefault("page_size", PS)
    router_kw.setdefault("prefix_fleet", True)
    return ServingRouter(reps, **router_kw), reps


class TestFleetPrefixShip:
    def test_cross_replica_hit_exact_greedy(self):
        shared, prompts = shared_prompts()
        want = oracle_tokens(prompts, 6)
        router, reps = make_fleet()
        router.start()
        assert consume(router.submit(prompts[0],
                                     max_new_tokens=6)) == want[0]
        s = router.submit(prompts[1], max_new_tokens=6)
        assert s.replica_idx == 1
        assert consume(s) == want[1]
        m = router.metrics
        assert m.prefix_ships_total.value == 1
        assert m.prefix_shipped_pages_total.value == 3
        assert m.prefix_ship_fallbacks_total.value == 0
        # the recipient served the shipped pages as radix hits
        assert reps[1].engine.cache.prefix_hit_pages >= 3
        wait_until(lambda: router.health()["replicas"][1]
                   .get("cached_pages", 0) > 0,
                   msg="recipient never advertised cached pages")
        router.close()
        fleet_invariants(router)

    def test_cross_replica_hit_exact_seeded_sampling(self):
        shared, prompts = shared_prompts()
        want = oracle_tokens(prompts, 6, sample_seeds=[11, 22])
        router, reps = make_fleet()
        router.start()
        for i, p in enumerate(prompts):
            s = router.submit(p, max_new_tokens=6, do_sample=True,
                              temperature=0.8, seed=[11, 22][i])
            assert consume(s) == want[i]
        assert router.metrics.prefix_ships_total.value == 1
        router.close()
        fleet_invariants(router)

    def test_min_ship_pages_threshold(self):
        shared, prompts = shared_prompts()
        want = oracle_tokens(prompts, 4)
        router, reps = make_fleet(prefix_ship_min_pages=5)
        router.start()
        for i, p in enumerate(prompts):
            assert consume(router.submit(p, max_new_tokens=4)) \
                == want[i]
        assert router.metrics.prefix_ships_total.value == 0
        router.close()

    def test_donor_gone_falls_back_to_recompute(self):
        shared, prompts = shared_prompts()
        want = oracle_tokens(prompts, 4)
        router, reps = make_fleet()
        router.start()
        assert consume(router.submit(prompts[0],
                                     max_new_tokens=4)) == want[0]
        router.kill_replica(0)
        s = router.submit(prompts[1], max_new_tokens=4)
        assert s.replica_idx == 1
        assert consume(s) == want[1]
        assert router.metrics.prefix_ships_total.value == 0
        router.close()

    def test_eviction_race_no_ship(self):
        # the donor's cache was flushed after its ownership was
        # recorded: the probe sees the truth and the ship is skipped
        shared, prompts = shared_prompts()
        want = oracle_tokens(prompts, 4)
        router, reps = make_fleet()
        router.start()
        consume(router.submit(prompts[0], max_new_tokens=4))
        reps[0].drop_prefix(shared)
        s = router.submit(prompts[1], max_new_tokens=4)
        assert consume(s) == want[1]
        assert router.metrics.prefix_ships_total.value == 0
        router.close()

    def test_import_drift_bounce_retries(self):
        # chaos models the probe->import eviction race for REAL: the
        # target's matched lead is dropped mid-ship, the import
        # bounces with the true count, the re-export lands
        shared, prompts = shared_prompts()
        want = oracle_tokens(prompts, 4)
        router, reps = make_fleet(chaos=ChaosConfig(
            seed=0, rates={"prefix_import_drift": 1.0}))
        router.start()
        consume(router.submit(prompts[0], max_new_tokens=4))
        # pre-seed the target with the first shared page so the ship
        # starts at skip=1 and the chaos drop forces a REAL drift
        meta, k, v = reps[0].export_prefix(shared[:PS])
        reps[1].import_prefix(meta, k, v)
        s = router.submit(prompts[1], max_new_tokens=4)
        assert consume(s) == want[1]
        m = router.metrics
        assert m.prefix_ships_total.value == 1
        # the retry re-exported the WHOLE chain after the drop
        assert m.prefix_shipped_pages_total.value == 3
        router.close()
        fleet_invariants(router)

    def test_dtype_skew_guard_skips_up_front(self):
        shared, prompts = shared_prompts()
        router, reps = make_fleet(dtypes=["float32", "int8"])
        want0 = oracle_tokens([prompts[0]], 4)[0]
        want1 = oracle_tokens([prompts[1]], 4,
                              cache_dtype="int8")[0]
        router.start()
        assert consume(router.submit(prompts[0],
                                     max_new_tokens=4)) == want0
        s = router.submit(prompts[1], max_new_tokens=4)
        assert s.replica_idx == 1
        assert consume(s) == want1
        m = router.metrics
        assert m.prefix_ships_total.value == 0
        assert m.prefix_ship_skipped_total.value(
            reason="dtype_skew") == 1
        router.close()

    def test_broken_advertisement_bounces_on_geometry(self):
        # the up-front guard needs the advertisement; when it lies the
        # GeometryMismatch bounce is the backstop — recompute, never a
        # failed request
        shared, prompts = shared_prompts()
        router, reps = make_fleet(dtypes=["float32", "int8"])
        want1 = oracle_tokens([prompts[1]], 4, cache_dtype="int8")[0]
        reps[1].cache_dtype = lambda: "float32"  # lying advertisement
        router.start()
        consume(router.submit(prompts[0], max_new_tokens=4))
        s = router.submit(prompts[1], max_new_tokens=4)
        assert consume(s) == want1
        m = router.metrics
        assert m.prefix_ships_total.value == 0
        assert m.prefix_ship_skipped_total.value(
            reason="geometry_bounce") == 1
        router.close()

    def test_dedup_evicts_surplus_owner(self):
        shared, prompts = shared_prompts(n_tail=3)
        want = oracle_tokens(prompts, 4)
        router, reps = make_fleet(n=3, prefix_max_owners=2)
        router.start()
        for i, p in enumerate(prompts):
            s = router.submit(p, max_new_tokens=4)
            assert s.replica_idx == i
            assert consume(s) == want[i]
        m = router.metrics
        assert m.prefix_ships_total.value == 2  # r0->r1, then ->r2
        assert m.prefix_dedup_drops_total.value > 0
        # exactly max_owners replicas still hold the shared pages
        wait_until(lambda: sum(
            1 for rep in reps
            if rep.engine.cache.probe_prefix(
                shared, len(shared) + 1) > 0) == 2,
            msg="dedup never converged to the owner cap")
        router.close()
        fleet_invariants(router)

    def test_inflight_dedup_under_concurrent_burst(self):
        import threading
        shared, prompts = shared_prompts(n_tail=6)
        want = oracle_tokens(prompts, 4)
        router, reps = make_fleet()
        router.start()
        consume(router.submit(prompts[0], max_new_tokens=4))
        outs = [None] * 5
        errs = []

        def worker(i):
            try:
                router._rr = 1  # steer the burst at the cold replica
                outs[i] = consume(router.submit(prompts[i + 1],
                                                max_new_tokens=4))
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errs, errs
        assert outs == want[1:]
        # the dogpile collapsed to at most one real transfer of the
        # shared chain; redundant attempts were skipped or shipped 0
        assert router.metrics.prefix_shipped_pages_total.value <= 3
        router.close()
        fleet_invariants(router)


# ---------------------------------------------------------------------------
# 4. the wire (/v1/_pages/prefix over real sockets)


class TestPrefixWire:
    def setup_method(self):
        self.eng = make_engine()
        self.srv = ServingServer(self.eng)
        host, port = self.srv.start()
        self.rep = HTTPReplica(host, port)

    def teardown_method(self):
        self.srv.close()

    def seed_remote(self, prompt):
        consume(self.rep.submit(prompt, max_new_tokens=2))
        wait_until(lambda: self.rep.health()["cached_pages"] >= 3,
                   msg="remote never cached the prefix")

    def test_roundtrip_drift_and_drop(self):
        prompt = np.arange(3 * PS + 1, dtype=np.int32)
        self.seed_remote(prompt)
        meta, k, v = self.rep.export_prefix(prompt)
        assert meta["n_pages"] == 3
        # drift on the remote exporter: skip beyond its chain -> 409
        with pytest.raises(PrefixDrift) as ei:
            self.rep.export_prefix(prompt, skip_pages=5)
        assert ei.value.cached_pages == 3
        # import back: the remote already holds the chain -> 409 drift
        with pytest.raises(PrefixDrift) as ei:
            self.rep.import_prefix(meta, k, v)
        assert ei.value.cached_pages == 3
        assert self.rep.drop_prefix(prompt[:3 * PS]) == 3
        # now the import lands
        assert self.rep.import_prefix(meta, k, v) == 3
        verify_page_conservation(self.eng.cache)

    def test_truncated_payload_400(self):
        import http.client
        prompt = np.arange(3 * PS + 1, dtype=np.int32)
        self.seed_remote(prompt)
        meta, k, v = self.rep.export_prefix(prompt)
        self.rep.drop_prefix(prompt[:3 * PS])
        payload = serialize_pages(meta, k, v)[:-7]  # torn transfer
        conn = http.client.HTTPConnection(self.rep.host, self.rep.port,
                                          timeout=60)
        conn.request("POST", "/v1/_pages/prefix", payload,
                     {"Content-Type":
                      "application/x-paddle-tpu-kv-pages"})
        resp = conn.getresponse()
        assert resp.status == 400
        body = json.loads(resp.read())
        assert "payload" in body["error"]["message"]
        conn.close()
        # nothing landed
        assert self.eng.cache.cached_pages == 0
        verify_page_conservation(self.eng.cache)

    def test_router_ships_over_http(self):
        shared, prompts = shared_prompts()
        want = oracle_tokens(prompts, 4)
        inproc = InProcessReplica(make_engine(0))
        router = ServingRouter([self.rep, inproc], prefix_fleet=True,
                               policy="round_robin", page_size=PS)
        router.start()
        assert consume(router.submit(prompts[0],
                                     max_new_tokens=4)) == want[0]
        s = router.submit(prompts[1], max_new_tokens=4)
        assert s.replica_idx == 1
        assert consume(s) == want[1]
        assert router.metrics.prefix_ships_total.value == 1
        assert router.metrics.prefix_shipped_pages_total.value == 3
        router.close()


# ---------------------------------------------------------------------------
# 5. chaos: the round-18 fault points degrade to recompute


class TestPrefixShipChaos:
    def test_export_gone_recomputes(self):
        shared, prompts = shared_prompts()
        want = oracle_tokens(prompts, 4)
        router, reps = make_fleet(chaos=ChaosConfig(
            seed=0, rates={"prefix_export_gone": 1.0}))
        router.start()
        for i, p in enumerate(prompts):
            assert consume(router.submit(p, max_new_tokens=4)) \
                == want[i]
        assert router.metrics.prefix_ships_total.value == 0
        assert router.chaos.counts["prefix_export_gone"] >= 1
        router.close()
        fleet_invariants(router)

    def test_wire_truncate_recomputes(self):
        shared, prompts = shared_prompts()
        want = oracle_tokens(prompts, 4)
        eng = make_engine(0)
        srv = ServingServer(eng)
        host, port = srv.start()
        rep0 = HTTPReplica(host, port, chaos=ChaosConfig(
            seed=0, rates={"prefix_wire_truncate": 1.0}))
        inproc = InProcessReplica(make_engine(0))
        router = ServingRouter([rep0, inproc], prefix_fleet=True,
                               policy="round_robin", page_size=PS)
        router.start()
        try:
            assert consume(router.submit(prompts[0],
                                         max_new_tokens=4)) == want[0]
            s = router.submit(prompts[1], max_new_tokens=4)
            assert consume(s) == want[1]
            m = router.metrics
            assert m.prefix_ships_total.value == 0
            assert m.prefix_ship_fallbacks_total.value == 1
            assert rep0.chaos.counts["prefix_wire_truncate"] == 1
            verify_page_conservation(inproc.engine.cache)
        finally:
            router.close()
            srv.close()
        verify_page_conservation(eng.cache)


# ---------------------------------------------------------------------------
# 6. the banked-bench replay (slow; conftest guards the artifact)


@pytest.mark.slow
class TestServingPrefixFleetReplay:
    def test_smoke_replay(self):
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        # Popen + communicate, not run(timeout=): this file trips the
        # chip-marker heuristic (the pagewire content type), and the
        # kill-on-timeout semantics are banned in chip-marked tests
        proc = subprocess.Popen(
            [sys.executable, "bench_serving.py", "--smoke",
             "--prefix-fleet"],
            cwd=repo, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT)
        stdout, _ = proc.communicate(timeout=900)
        text = stdout.decode(errors="replace")
        assert proc.returncode == 0, text[-2000:]
        line = [ln for ln in text.splitlines()
                if ln.startswith("{")][-1]
        out = json.loads(line)
        probes = out["probes"]
        assert probes["prefix_ships"] == probes["reps"]
        assert probes["pages_per_ship"] > 0
        fleet = out["fleet_replay"]
        for cfgname in ("ships_off", "ships_on"):
            assert fleet[cfgname]["exact_greedy"]
            assert fleet[cfgname]["exact_sampled"]
        assert fleet["ships_on"]["prefix_ships"] > 0
