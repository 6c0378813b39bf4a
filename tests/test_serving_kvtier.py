"""Hierarchical KV-cache tiers (round 20): host-RAM/disk page pools
behind the pagewire, with prefix restore and replica pre-warm.

Pinned here:
- pool mechanics: LRU byte-budget enforcement, disk demotion and
  promote-through-RAM, over-budget sheds, no-mutation residency
  probes, torn-file disposal, hottest-chain ranking with prefix dedup;
- spill→restore BIT-exactness per cache_dtype (fp32 and int8 — the
  int8 scales must ride the spill payload; direct ``k_pages`` access
  is the known scale-dropping hazard) via ``export_prefix`` byte
  comparison plus end-to-end token exactness over a restored prefix;
- strictly-best-effort degradation under EVERY tier fault point
  (spill drop, restore fail, slow I/O, at-rest corruption caught by
  the pagewire CRC — entry disposed, request recomputes);
- cross-tier allocator conservation (device + host + disk) under a
  seeded thrash fuzz;
- weight-reload invalidation (``clear_prefix`` drops the tier too);
- the serving surfaces: /healthz host-tier occupancy, the
  ``/v1/_pages/prefix/restore``+``prewarm`` endpoints, the router's
  device→host-tier→donor probe order, and pre-warm-on-grow through
  the autoscaler's replica factory.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (ChaosConfig, DiskPagePool,
                                FleetAutoscaler, HostPagePool,
                                InProcessReplica, KVTier, ServingEngine,
                                ServingFrontend, ServingRouter,
                                ServingServer, chain_key,
                                host_pool_from_env)
from paddle_tpu.serving.chaos import verify_page_conservation
from paddle_tpu.serving.replica import HTTPReplica


def tiny_model(seed=0, **kw):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=64, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def make_engine(pool=None, chaos=None, **kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("prefix_cache", True)
    return ServingEngine(tiny_model(0), host_pool=pool, chaos=chaos,
                         **kw)


def evict_all_cached(eng):
    """Drain the device radix tree through the LRU eviction path (the
    spill hook) and land the deferred spills in the pool."""
    n = 0
    while eng.cache._evict_lru_leaf():
        n += 1
    if eng.kvtier is not None:
        eng.kvtier.flush()
    return n


PROMPT = np.arange(1, 13, dtype=np.int32)  # 3 full pages


# ---------------------------------------------------------------------------
# pool mechanics (no engine, no jax)


class TestHostPagePool:
    def test_lru_budget_enforced_without_disk(self):
        pool = HostPagePool(budget_bytes=100)
        for i in range(3):
            assert pool.put(chain_key([i]), bytes(40))
        st = pool.stats()
        assert st["host_pool_bytes"] <= 100
        assert st["host_pool_pages"] == 2
        assert st["dropped_pages"] == 1
        assert pool.get(chain_key([0])) is None       # LRU tail gone
        assert pool.get(chain_key([2])) == bytes(40)

    def test_over_budget_payload_shed(self):
        pool = HostPagePool(budget_bytes=100)
        assert not pool.put(b"big", bytes(200))
        assert pool.stats()["shed_pages"] == 1
        assert pool.stats()["host_pool_pages"] == 0

    def test_disk_demotion_and_promotion(self, tmp_path):
        disk = DiskPagePool(str(tmp_path / "tier"), budget_bytes=1000)
        pool = HostPagePool(budget_bytes=100, disk=disk)
        for i in range(3):
            assert pool.put(chain_key([i]), bytes([i]) * 40)
        st = pool.stats()
        assert st["host_pool_pages"] == 2
        assert st["disk_pool_pages"] == 1      # demoted, not dropped
        assert st["demoted_pages"] == 1
        # a disk hit promotes back through RAM (demoting the RAM tail)
        assert pool.get(chain_key([0])) == bytes([0]) * 40
        st = pool.stats()
        assert st["host_pool_pages"] == 2
        assert st["disk_pool_pages"] == 1
        assert pool.stats()["demoted_pages"] == 2

    def test_over_budget_payload_demotes_to_disk(self, tmp_path):
        disk = DiskPagePool(str(tmp_path / "tier"), budget_bytes=1000)
        pool = HostPagePool(budget_bytes=100, disk=disk)
        assert pool.put(b"big", bytes(200))    # too big for RAM budget
        assert pool.stats()["disk_pool_pages"] == 1
        assert pool.get(b"big") == bytes(200)  # served from disk

    def test_contains_does_not_mutate_lru_order(self):
        pool = HostPagePool(budget_bytes=100)
        pool.put(b"a", bytes(40))
        pool.put(b"b", bytes(40))
        assert pool.contains(b"a")
        pool.put(b"c", bytes(40))  # evicts the true LRU tail: a
        assert not pool.contains(b"a")
        assert pool.contains(b"b") and pool.contains(b"c")

    def test_disk_torn_file_disposed(self, tmp_path):
        disk = DiskPagePool(str(tmp_path / "tier"), budget_bytes=1000)
        pool = HostPagePool(budget_bytes=10, disk=disk)
        pool.put(b"k", bytes(40))              # straight to disk
        snap = pool.snapshot()
        (key, path, nbytes), = snap["disk"]["entries"]
        with open(path, "wb") as f:
            f.write(bytes(10))                 # torn write / bit-rot
        assert pool.get(b"k") is None
        assert pool.snapshot()["disk"]["entries"] == []

    def test_hottest_ranks_by_heat_and_dedups_prefixes(self):
        pool = HostPagePool(budget_bytes=10_000)
        shallow = chain_key([1, 2, 3, 4])
        deep = chain_key([1, 2, 3, 4, 5, 6, 7, 8])
        other = chain_key([9, 9, 9, 9])
        for k in (shallow, deep, other):
            pool.put(k, bytes(8))
        for _ in range(3):
            pool.get(other)
        picks = pool.hottest(2)
        assert picks[0] == other
        # shallow is a strict byte-prefix of deep: restoring deep pulls
        # the whole path, so only the deeper chain is picked
        assert picks[1] == deep
        assert shallow not in picks

    def test_clear_flushes_every_tier(self, tmp_path):
        disk = DiskPagePool(str(tmp_path / "tier"), budget_bytes=1000)
        pool = HostPagePool(budget_bytes=50, disk=disk)
        for i in range(3):
            pool.put(chain_key([i]), bytes(40))
        pool.clear()
        assert pool.pages == 0
        assert pool.snapshot()["disk"]["entries"] == []

    def test_env_knobs_build_pool(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_SERVING_HOST_POOL_MB",
                           raising=False)
        assert host_pool_from_env() is None
        monkeypatch.setenv("PADDLE_TPU_SERVING_HOST_POOL_MB", "2")
        pool = host_pool_from_env()
        assert pool is not None and pool.disk is None
        assert pool.budget_bytes == 2 * 2 ** 20
        monkeypatch.setenv("PADDLE_TPU_SERVING_DISK_POOL_MB", "1")
        pool = host_pool_from_env()
        assert pool.disk is not None
        assert pool.disk.budget_bytes == 2 ** 20


# ---------------------------------------------------------------------------
# spill -> restore exactness


class TestSpillRestore:
    @pytest.mark.parametrize("cache_dtype", [None, "int8"])
    def test_spill_restore_bit_exact(self, cache_dtype):
        """The spilled payload restores BYTE-identical device pages —
        for int8 the scales ride the pagewire payload (the known
        hazard: touching ``k_pages`` directly drops them)."""
        eng = make_engine(pool=HostPagePool(budget_bytes=4 << 20),
                          cache_dtype=cache_dtype)
        rid = eng.add_request(PROMPT, max_new_tokens=2)
        toks = eng.run()[rid]["tokens"]
        meta0, k0, v0 = eng.export_prefix(PROMPT, 0)
        assert evict_all_cached(eng) > 0
        assert eng.cache.probe_prefix(PROMPT) == 0
        assert eng.restore_prefix(PROMPT) == len(PROMPT) // 4
        meta1, k1, v1 = eng.export_prefix(PROMPT, 0)
        assert len(k0) == len(k1)  # int8: n_layers codes + scales
        for a, b in zip(k0 + v0, k1 + v1):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and the stream over the restored prefix stays token-exact
        rid2 = eng.add_request(PROMPT, max_new_tokens=2)
        assert eng.run()[rid2]["tokens"] == toks

    def test_restore_counts_like_shipped_pages_in_admission(self):
        """Restored pages land CACHED at rc==0, so the front-end shed
        gate's probe-based accounting covers them with no new case."""
        pool = HostPagePool(budget_bytes=4 << 20)
        eng = make_engine(pool=pool)
        rid = eng.add_request(PROMPT, max_new_tokens=2)
        eng.run()
        evict_all_cached(eng)
        fe = ServingFrontend(eng)
        assert fe.restore_prefix(PROMPT) > 0
        need_cold = eng.cache.pages_for(len(PROMPT) + 2)
        # an unstarted frontend's reservation math (round-11 rule):
        # admission subtracts the probed prefix, so the reservation is
        # strictly below the cold-prompt worst case
        fe.submit(PROMPT, max_new_tokens=2)
        assert fe.load() < need_cold

    def test_partial_chain_restore(self):
        """A chain whose deeper entries were shed restores the
        contiguous front and leaves the tail to recompute."""
        pool = HostPagePool(budget_bytes=4 << 20)
        eng = make_engine(pool=pool)
        eng.add_request(PROMPT, max_new_tokens=2)
        eng.run()
        evict_all_cached(eng)
        pool.pop(chain_key(PROMPT[:8]))        # hole at depth 2
        assert eng.restore_prefix(PROMPT) == 1
        assert eng.cache.probe_prefix(PROMPT) == 1

    def test_tier_gated_on_prefix_cache(self):
        eng = make_engine(pool=HostPagePool(budget_bytes=1 << 20),
                          prefix_cache=False)
        assert eng.kvtier is None
        assert eng.restore_prefix(PROMPT) == 0
        assert eng.tier_stats() is None

    def test_clear_prefix_invalidates_tier(self):
        """Weight reload: spilled K/V of the OLD weights must never
        restore afterwards."""
        pool = HostPagePool(budget_bytes=4 << 20)
        eng = make_engine(pool=pool)
        eng.add_request(PROMPT, max_new_tokens=2)
        eng.run()
        evict_all_cached(eng)
        assert pool.pages > 0
        eng.cache.clear_prefix()
        assert pool.pages == 0
        assert eng.restore_prefix(PROMPT) == 0

    def test_geometry_skewed_pool_entry_is_a_miss(self):
        """Two engines sharing one pool with different geometry: the
        restore probe validates per-cache and simply misses."""
        pool = HostPagePool(budget_bytes=4 << 20)
        eng8 = make_engine(pool=pool, page_size=8)
        eng8.add_request(np.arange(1, 17, dtype=np.int32),
                         max_new_tokens=2)
        eng8.run()
        evict_all_cached(eng8)
        assert pool.pages > 0
        eng4 = make_engine(pool=pool)          # page_size=4
        assert eng4.restore_prefix(np.arange(1, 17, dtype=np.int32)) \
            == 0
        verify_page_conservation(eng4.cache, "geometry-skew")


# ---------------------------------------------------------------------------
# best-effort degradation under every tier fault point


class TestTierFaultPoints:
    def _spilled_engine(self, rates, **cfg_kw):
        chaos = ChaosConfig(seed=7, rates=rates, **cfg_kw)
        pool = HostPagePool(budget_bytes=4 << 20)
        eng = make_engine(pool=pool, chaos=chaos)
        rid = eng.add_request(PROMPT, max_new_tokens=2)
        toks = eng.run()[rid]["tokens"]
        return eng, pool, toks

    def _still_serves(self, eng, toks):
        rid = eng.add_request(PROMPT, max_new_tokens=2)
        assert eng.run()[rid]["tokens"] == toks
        verify_page_conservation(eng.cache, "fault-point")

    def test_spill_fail_drops_entry_never_raises(self):
        eng, pool, toks = self._spilled_engine({"tier_spill_fail": 1.0})
        evict_all_cached(eng)
        assert pool.pages == 0                 # every spill dropped
        assert eng.metrics.tier_spill_dropped.value > 0
        assert eng.restore_prefix(PROMPT) == 0
        self._still_serves(eng, toks)          # plain recompute

    def test_restore_fail_degrades_to_recompute(self):
        eng, pool, toks = self._spilled_engine(
            {"tier_restore_fail": 1.0})
        evict_all_cached(eng)
        assert pool.pages > 0                  # spills landed
        assert eng.restore_prefix(PROMPT) == 0
        assert eng.metrics.tier_restore_misses.value > 0
        self._still_serves(eng, toks)

    def test_corrupt_payload_caught_by_crc_and_disposed(self):
        eng, pool, toks = self._spilled_engine(
            {"tier_corrupt_payload": 1.0})
        evict_all_cached(eng)
        before = pool.pages
        assert before > 0
        assert eng.restore_prefix(PROMPT) == 0
        assert eng.metrics.tier_corrupt_dropped.value > 0
        assert pool.pages < before             # bad entry disposed
        self._still_serves(eng, toks)

    def test_slow_io_fires_and_still_restores(self):
        eng, pool, toks = self._spilled_engine(
            {"tier_slow_io": 1.0}, tier_slow_io_s=0.001)
        evict_all_cached(eng)
        assert eng.restore_prefix(PROMPT) > 0
        assert eng.chaos.counts["tier_slow_io"] > 0
        self._still_serves(eng, toks)


# ---------------------------------------------------------------------------
# cross-tier conservation fuzz


class TestCrossTierConservation:
    def test_thrash_fuzz_conserves_across_tiers(self, tmp_path):
        """Seeded thrash against a page-starved engine with tiny RAM +
        disk budgets: demotions, sheds, restores and disposals all
        fire, and after every round the device allocator AND the tier
        snapshot (RAM sums, disk file sizes, RAM∩disk disjoint)
        close."""
        rng = np.random.default_rng(0)
        disk = DiskPagePool(str(tmp_path / "tier"), budget_bytes=24_000)
        pool = HostPagePool(budget_bytes=6_000, disk=disk)
        eng = make_engine(pool=pool, num_pages=16)
        prompts = [rng.integers(0, 97, int(rng.integers(20, 27)))
                   .astype(np.int32) for _ in range(4)]
        for _round in range(3):
            for p in prompts:
                rid = eng.add_request(p, max_new_tokens=4)
                eng.run()
                verify_page_conservation(eng.cache, "thrash")
            eng.prewarm_prefix()
            verify_page_conservation(eng.cache, "thrash-prewarm")
        st = pool.stats()
        assert st["spilled_pages"] > 0
        assert eng.metrics.tier_restore_hits.value \
            + eng.metrics.tier_restore_misses.value > 0


# ---------------------------------------------------------------------------
# serving surfaces: healthz, HTTP endpoints, router probe order, prewarm


class TestServingSurfaces:
    def test_health_advertises_host_tier(self):
        pool = HostPagePool(budget_bytes=4 << 20)
        eng = make_engine(pool=pool)
        eng.add_request(PROMPT, max_new_tokens=2)
        eng.run()
        evict_all_cached(eng)
        h = ServingFrontend(eng).health()
        assert h["host_pool_pages"] == pool.stats()["host_pool_pages"]
        assert h["kvtier"]["spilled_pages"] > 0
        # a tierless engine advertises the absence, not a crash
        h0 = ServingFrontend(make_engine()).health()
        assert h0["host_pool_pages"] == 0 and h0["kvtier"] is None

    def test_http_restore_and_prewarm_endpoints(self):
        pool = HostPagePool(budget_bytes=4 << 20)
        eng = make_engine(pool=pool)
        eng.add_request(PROMPT, max_new_tokens=2)
        eng.run()
        evict_all_cached(eng)
        srv = ServingServer(eng)
        host, port = srv.start()
        try:
            rep = HTTPReplica(host, port)
            assert rep.health()["host_pool_pages"] > 0
            assert rep.restore_prefix(PROMPT) == len(PROMPT) // 4
            assert rep.restore_prefix(PROMPT) == 0   # now resident
            assert rep.prewarm_prefix() == 0         # nothing left
        finally:
            srv.close(timeout=30.0)

    def test_router_probe_order_restores_before_recompute(self):
        """Probe order: local device -> local host tier -> remote
        donor -> recompute.  A single-replica fleet has no donors, so
        a device miss that hits the host tier must restore locally."""
        pool = HostPagePool(budget_bytes=4 << 20)
        eng = make_engine(pool=pool)
        rid = eng.add_request(PROMPT, max_new_tokens=2)
        want = eng.run()[rid]["tokens"]
        evict_all_cached(eng)
        router = ServingRouter([InProcessReplica(eng)], page_size=4,
                               prefix_fleet=True)
        router.start()
        try:
            stream = router.submit(PROMPT, max_new_tokens=2)
            got = [ev["token"] for ev in stream.events(timeout=60.0)
                   if ev["type"] == "token"]
            assert got == want
            assert router.metrics.tier_restores_total.value >= 1
            assert router.metrics.tier_restored_pages_total.value >= 1
            assert eng.metrics.tier_restore_hits.value >= 1
        finally:
            router.close(timeout=30.0)

    def test_autoscale_grow_prewarms_from_shared_pool(self):
        """Pre-warm on grow: a freshly scaled-up replica sharing the
        host pool starts with the hottest spilled chains already
        device-resident."""
        pool = HostPagePool(budget_bytes=4 << 20)

        def factory(role):
            return InProcessReplica(make_engine(pool=pool), role=role)

        seed_rep = factory("mixed")
        eng = seed_rep.engine
        eng.add_request(PROMPT, max_new_tokens=2)
        eng.run()
        evict_all_cached(eng)
        assert pool.pages > 0
        router = ServingRouter([seed_rep], page_size=4)
        router.start()
        try:
            scaler = FleetAutoscaler(router, factory, interval_s=0)
            idx = scaler._scale_up("mixed")
            grown = router.replicas[idx]
            assert grown.engine.cache.probe_prefix(PROMPT) > 0
            assert router.metrics.prewarm_restored_pages_total.value \
                > 0
        finally:
            router.close(timeout=30.0)

    def test_prewarm_restores_hottest_chains_bounded(self, monkeypatch):
        pool = HostPagePool(budget_bytes=4 << 20)
        eng = make_engine(pool=pool)
        eng.add_request(PROMPT, max_new_tokens=2)
        eng.run()
        evict_all_cached(eng)
        eng2 = make_engine(pool=pool)
        assert eng2.prewarm_prefix(max_chains=0) == 0
        restored = eng2.prewarm_prefix()
        assert restored == len(PROMPT) // 4
        assert eng2.cache.probe_prefix(PROMPT) > 0


# ---------------------------------------------------------------------------
# KVTier unit edges


class TestKVTierUnit:
    def test_pending_spills_bounded_by_inline_flush(self):
        pool = HostPagePool(budget_bytes=16 << 20)
        eng = make_engine(pool=pool, num_pages=64)
        tier = eng.kvtier
        tier.max_pending = 2
        rng = np.random.default_rng(3)
        for i in range(3):
            p = rng.integers(0, 97, 12).astype(np.int32)
            eng.add_request(p, max_new_tokens=2)
            eng.run()
        while eng.cache._evict_lru_leaf():
            assert len(tier._pending) <= tier.max_pending
        tier.flush()
        assert tier.stats()["pending_spills"] == 0
        assert pool.pages > 0

    def test_respill_of_resident_chain_is_deduped(self):
        pool = HostPagePool(budget_bytes=4 << 20)
        eng = make_engine(pool=pool)
        eng.add_request(PROMPT, max_new_tokens=2)
        eng.run()
        evict_all_cached(eng)
        spilled = pool.stats()["spilled_pages"]
        eng.restore_prefix(PROMPT)
        evict_all_cached(eng)  # re-evict: already resident in the pool
        assert pool.stats()["spilled_pages"] == spilled

    def test_blessed_entry_points_never_raise(self):
        class BrokenPool:
            disk = None

            def __getattr__(self, name):
                raise RuntimeError("broken pool")

        eng = make_engine()
        tier = KVTier(BrokenPool(), metrics=eng.metrics)
        eng.cache.attach_tier(tier)
        eng.add_request(PROMPT, max_new_tokens=2)
        eng.run()
        while eng.cache._evict_lru_leaf():
            pass
        tier.flush()
        assert tier.restore(eng.cache, PROMPT) == 0
        assert tier.prewarm(eng.cache) == 0


# ---------------------------------------------------------------------------
# bench replay (BENCH artifact snapshot-guarded by conftest)


class TestServingKvtierReplay:
    def test_kvtier_smoke_replay(self):
        root = os.path.abspath(
            os.path.join(os.path.dirname(__file__), ".."))
        proc = subprocess.Popen(
            [sys.executable, "bench_serving.py", "--smoke", "--kvtier"],
            cwd=root, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        out, _ = proc.communicate(timeout=280)
        assert proc.returncode == 0, out.decode(errors="replace")[-2000:]
        rec = json.loads(out.decode().strip().splitlines()[-1])
        assert rec["smoke"] is True
        pools = {p["host_pool_mb"]: p for p in rec["pools"]}
        assert 0 in pools                      # tierless baseline
        warm = [p for mb, p in pools.items() if mb > 0]
        assert warm
        assert any(p["tier_restore_pages"] > 0 for p in warm)
