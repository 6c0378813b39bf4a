"""paddle_tpu.serving.disagg — disaggregated prefill/decode serving:
KV page migration (wire format, allocator export/import, conservation
under prefix/fork/rollback interleavings), the prefill-only hold
protocol, DisaggRouter handoff exactness vs the single-engine oracle
(greedy AND seeded-sampled, including forced mid-migration kills and
degenerate-fleet fallback), the reservation asymmetry (admission
through an UNSTARTED front-end per the round-11 addenda), the
/v1/_pages HTTP path, and the metrics-driven FleetAutoscaler
(hysteresis, per-role min/max, burst scale-up, idle drain with zero
lost requests)."""
import json
import os
import subprocess
import sys
import threading
import time
from collections import Counter as Tally

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (DisaggRouter, FleetAutoscaler,
                                GeometryMismatch, HTTPReplica,
                                InProcessReplica, PagedKVCache,
                                PrefixDrift, Rejected, ServingEngine,
                                ServingServer, WireFormatError,
                                deserialize_pages, serialize_pages)
from paddle_tpu.serving.autoscale import parse_role_spec
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


def make_disagg(roles=("prefill", "decode", "decode"), seed=0,
                engine_kw=None, start=True, **router_kw):
    ekw = dict(engine_kw or {})
    ekw.setdefault("prefix_cache", True)
    reps = [InProcessReplica(make_engine(seed, **ekw), role=r)
            for r in roles]
    router_kw.setdefault("page_size", 4)
    router = DisaggRouter(reps, **router_kw)
    return router.start() if start else router


def oracle_tokens(prompts, max_new, model_seed=0, engine_kw=None,
                  **req_kw):
    """Single-engine oracle: the uninterrupted streams (per-prompt kw
    via lists)."""
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


def consume(stream, timeout=120):
    return [ev["token"] for ev in stream.events(timeout=timeout)
            if ev["type"] == "token"]


# ---------------------------------------------------------------------------
# pagewire: serialization with geometry/dtype checks


class TestPagewire:
    def _payload(self):
        c = PagedKVCache(2, 2, 4, page_size=4, num_pages=16)
        c.alloc_seq("a")
        c.append_slots("a", 10)
        return c.export_pages("a")

    def test_roundtrip_bit_exact(self):
        meta, k, v = self._payload()
        buf = serialize_pages(meta, k, v,
                              request={"max_tokens": 8, "seed": 3})
        m2, k2, v2, req = deserialize_pages(buf)
        assert m2 == meta and req == {"max_tokens": 8, "seed": 3}
        for a, b in zip(k + v, k2 + v2):
            assert a.dtype == b.dtype
            assert (np.asarray(a) == b).all()

    def test_truncated_and_corrupt_payloads_raise(self):
        meta, k, v = self._payload()
        buf = serialize_pages(meta, k, v)
        with pytest.raises(WireFormatError):
            deserialize_pages(b"NOPE" + buf[4:])
        with pytest.raises(WireFormatError):
            deserialize_pages(buf[:len(buf) - 7])   # truncated arrays
        with pytest.raises(WireFormatError):
            deserialize_pages(buf + b"xx")          # trailing garbage

    def test_import_checks_geometry_and_dtype(self):
        meta, k, v = self._payload()
        for other in (PagedKVCache(2, 2, 8, page_size=4, num_pages=16),
                      PagedKVCache(3, 2, 4, page_size=4, num_pages=16),
                      PagedKVCache(2, 2, 4, page_size=8, num_pages=16),
                      PagedKVCache(2, 2, 4, page_size=4, num_pages=16,
                                   dtype="bfloat16")):
            with pytest.raises(GeometryMismatch):
                other.import_pages("x", meta, k, v)
            assert not other.has_seq("x")
            assert other.free_pages == other.allocatable_pages


# ---------------------------------------------------------------------------
# allocator-level migration semantics


def check_conservation(cache):
    """Free + (distinct mapped or cached) pages == allocatable; every
    refcount equals the number of sequences mapping the page; the free
    list never overlaps live/cached pages."""
    mapped = set()
    rc = Tally()
    for sid in cache.live_seqs():
        mapped.update(cache._tables[sid])
        rc.update(cache._tables[sid])
    resident = mapped | set(cache._cached)
    assert cache.free_pages + len(resident) == cache.allocatable_pages
    free = set(cache._free)
    assert not (free & resident)
    for p in range(1, cache.num_pages):
        assert cache.refcount(p) == rc.get(p, 0), f"page {p}"


class TestMigrationAllocator:
    def test_export_import_moves_exact_bytes(self):
        import jax.numpy as jnp
        src = PagedKVCache(2, 2, 4, page_size=4, num_pages=32)
        src.alloc_seq("s")
        slots, _ = src.append_slots("s", 11)
        # write recognizable K/V at the allocated slots
        for li in range(src.n_layers):
            flat = src.k_pages[li].reshape(-1, 2, 4)
            vals = jnp.arange(11 * 8, dtype=jnp.float32) \
                .reshape(11, 2, 4) + 100 * li
            src.k_pages[li] = flat.at[jnp.asarray(slots)].set(
                vals).reshape(src.k_pages[li].shape)
        meta, k, v = src.export_pages("s")
        dst = PagedKVCache(2, 2, 4, page_size=4, num_pages=32)
        dst.import_pages("d", meta, k, v)
        assert dst.seq_len("d") == 11
        table = dst._tables["d"]
        for li in range(2):
            flat = np.asarray(dst.k_pages[li]).reshape(-1, 2, 4)
            got = np.concatenate([flat[p * 4:(p + 1) * 4]
                                  for p in table])[:11]
            want = np.arange(11 * 8, dtype=np.float32) \
                .reshape(11, 2, 4) + 100 * li
            assert (got == want).all()
        check_conservation(src)
        check_conservation(dst)

    def test_prefix_skip_transfers_only_uncached_suffix(self):
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, 97, 19).astype(np.int32)
        src = PagedKVCache(2, 2, 4, page_size=4, num_pages=32,
                           prefix_cache=True)
        src.acquire_prefix("s", prompt, len(prompt))
        src.append_slots("s", 19)
        src.commit_prefix("s", prompt, 19)
        # destination already holds the first 2 prompt pages
        dst = PagedKVCache(2, 2, 4, page_size=4, num_pages=32,
                           prefix_cache=True)
        dst.acquire_prefix("warm", prompt[:8], 9)
        dst.append_slots("warm", 8)
        dst.commit_prefix("warm", prompt[:8], 8)
        dst.free_seq("warm")
        have = dst.probe_prefix(prompt, len(prompt) + 1)
        assert have == 2
        meta, k, v = src.export_pages("s", skip_pages=have)
        assert meta["n_pages"] == 3  # 5 total - 2 cached
        n = dst.import_pages("d", meta, k, v, prompt=prompt,
                             hist_len=len(prompt) + 1)
        assert n == 5 and dst.seq_len("d") == 19
        # the full prompt pages are now committed on the destination
        assert dst.probe_prefix(prompt, len(prompt) + 1) == 4
        check_conservation(dst)

    def test_prefix_drift_rolls_back_and_carries_truth(self):
        rng = np.random.default_rng(6)
        prompt = rng.integers(0, 97, 16).astype(np.int32)
        src = PagedKVCache(2, 2, 4, page_size=4, num_pages=32,
                           prefix_cache=True)
        src.acquire_prefix("s", prompt, len(prompt))
        src.append_slots("s", 16)
        dst = PagedKVCache(2, 2, 4, page_size=4, num_pages=32,
                           prefix_cache=True)
        free0 = dst.free_pages
        # exporter believed dst held 2 pages; it holds none
        meta, k, v = src.export_pages("s", skip_pages=2)
        with pytest.raises(PrefixDrift) as ei:
            dst.import_pages("d", meta, k, v, prompt=prompt,
                             hist_len=len(prompt) + 1)
        assert ei.value.cached_pages == 0
        assert not dst.has_seq("d") and dst.free_pages == free0
        # retry with the carried truth succeeds
        meta, k, v = src.export_pages("s",
                                      skip_pages=ei.value.cached_pages)
        dst.import_pages("d", meta, k, v, prompt=prompt,
                         hist_len=len(prompt) + 1)
        assert dst.seq_len("d") == 16
        check_conservation(dst)

    @pytest.mark.parametrize("dtype", ["float32", "int8"])
    def test_conservation_fuzz_with_migration(self, dtype):
        """2500 random ops over TWO allocators — append/fork/free/
        free_tail/prefix acquire+commit/export+import/release/clear —
        no leaked or double-freed page on either side, ever.  The int8
        geometry routes every migration through the WIRE FORMAT
        (serialize/deserialize) so the scale arrays must migrate,
        conserve, and roundtrip byte-exactly alongside the codes."""
        rng = np.random.default_rng(42)
        caches = [PagedKVCache(1, 2, 4, page_size=4, num_pages=48,
                               prefix_cache=True, dtype=dtype)
                  for _ in range(2)]
        quant = dtype == "int8"
        live = [dict(), dict()]  # per-cache: sid -> prompt
        next_id = [0]

        def fresh(side):
            next_id[0] += 1
            return f"c{side}-{next_id[0]}"

        def new_seq(side):
            c = caches[side]
            prompt = rng.integers(0, 97, int(rng.integers(3, 25))) \
                .astype(np.int32)
            sid = fresh(side)
            matched = c.acquire_prefix(sid, prompt, len(prompt))
            tail = len(prompt) - matched * c.page_size
            try:
                if tail > 0:
                    c.append_slots(sid, tail)
            except Exception:
                c.free_seq(sid)
                return
            c.commit_prefix(sid, prompt, len(prompt))
            live[side][sid] = prompt

        for step in range(2500):
            side = int(rng.integers(0, 2))
            c = caches[side]
            op = rng.random()
            sids = list(live[side])
            if op < 0.30 or not sids:
                new_seq(side)
            elif op < 0.45:
                sid = sids[int(rng.integers(len(sids)))]
                try:
                    c.append_slots(sid, int(rng.integers(1, 6)))
                except Exception:
                    pass
            elif op < 0.55:
                sid = sids[int(rng.integers(len(sids)))]
                child = fresh(side)
                c.fork(sid, child)
                live[side][child] = live[side][sid]
            elif op < 0.68:
                sid = sids[int(rng.integers(len(sids)))]
                c.free_seq(sid)
                del live[side][sid]
            elif op < 0.76:
                sid = sids[int(rng.integers(len(sids)))]
                ln = c.seq_len(sid)
                if ln:
                    c.free_tail(sid, int(rng.integers(0, ln + 1)))
            elif op < 0.80:
                c.clear_prefix()
            else:
                # migrate a random sequence to the OTHER cache
                sid = sids[int(rng.integers(len(sids)))]
                prompt = live[side][sid]
                other = caches[1 - side]
                seq_len = c.seq_len(sid)
                if seq_len < 1:
                    continue
                hist = seq_len + 1
                skip = other.probe_prefix(prompt, hist)
                skip = min(skip, len(c._tables[sid]))
                dst_id = fresh(1 - side)

                def ship(skip_pages):
                    meta, k, v = c.export_pages(sid,
                                                skip_pages=skip_pages)
                    if quant:
                        # int8 fuzz shape: every transfer crosses the
                        # wire — codes AND scales must come back
                        # byte-identical before they scatter
                        buf = serialize_pages(meta, k, v)
                        m2, k2, v2, _ = deserialize_pages(buf)
                        assert m2 == meta
                        for a, b in zip(k + v, k2 + v2):
                            assert a.dtype == b.dtype
                            assert (np.asarray(a) == b).all()
                        meta, k, v = m2, k2, v2
                    return meta, k, v

                try:
                    meta, k, v = ship(skip)
                    other.import_pages(dst_id, meta, k, v,
                                       prompt=prompt, hist_len=hist)
                except PrefixDrift as e:
                    meta, k, v = ship(min(e.cached_pages,
                                          len(c._tables[sid])))
                    try:
                        other.import_pages(dst_id, meta, k, v,
                                           prompt=prompt,
                                           hist_len=hist)
                    except Exception:
                        continue
                except Exception:
                    continue
                live[1 - side][dst_id] = prompt
                c.free_seq(sid)        # release the source
                del live[side][sid]
            if step % 100 == 0:
                for cc in caches:
                    check_conservation(cc)
        for cc in caches:
            check_conservation(cc)
        # drain everything: every page must come home
        for side in range(2):
            for sid in list(live[side]):
                caches[side].free_seq(sid)
            caches[side].clear_prefix()
            assert caches[side].free_pages \
                == caches[side].allocatable_pages


# ---------------------------------------------------------------------------
# the prefill-only hold protocol (engine level)


class TestPrefillHold:
    def test_hold_export_release_lifecycle(self):
        eng = make_engine()
        p = np.arange(3, 12, dtype=np.int32) % 97
        rid = eng.add_request(p, max_new_tokens=10, prefill_only=True)
        res = eng.run()
        assert res[rid]["finish_reason"] == "prefilled"
        assert len(res[rid]["tokens"]) == 1   # exactly the first token
        # pages are HELD, not freed
        assert eng.cache.has_seq(rid)
        assert eng.cache.seq_len(rid) == p.size
        meta, k, v = eng.export_request(rid)
        assert meta["seq_len"] == p.size
        assert meta["out_tokens"] == res[rid]["tokens"]
        assert "device_seed" in meta
        assert eng.metrics.prefills_held.value == 1
        assert eng.release_request(rid) is True
        assert not eng.cache.has_seq(rid)
        assert eng.release_request(rid) is False  # idempotent
        with pytest.raises(KeyError):
            eng.export_request(rid)

    def test_cancel_releases_held_pages(self):
        eng = make_engine()
        rid = eng.add_request(np.asarray([1, 2, 3, 4, 5], np.int32),
                              max_new_tokens=8, prefill_only=True)
        eng.run()
        free_before = eng.cache.free_pages
        assert eng.cancel(rid) is True
        assert eng.cache.free_pages > free_before
        assert not eng.cache.has_seq(rid)

    def test_max_new_one_finishes_normally(self):
        # nothing left to decode -> plain "length" finish, pages freed
        eng = make_engine()
        rid = eng.add_request(np.asarray([1, 2, 3], np.int32),
                              max_new_tokens=1, prefill_only=True)
        res = eng.run()
        assert res[rid]["finish_reason"] == "length"
        assert not eng.cache.has_seq(rid)

    def test_prefill_only_rejects_forks(self):
        eng = make_engine()
        with pytest.raises(ValueError, match="prefill_only"):
            eng.add_request(np.asarray([1, 2], np.int32),
                            max_new_tokens=4, prefill_only=True,
                            do_sample=True, n=2)

    def test_adopt_continues_token_exact(self):
        prompts = rng_prompts(3, seed=3)
        want = oracle_tokens(prompts, 9)
        src, dst = make_engine(), make_engine()
        for p, w in zip(prompts, want):
            rid = src.add_request(p, max_new_tokens=9,
                                  prefill_only=True)
            src.run()
            meta, k, v = src.export_request(rid)
            arid = dst.adopt_request(meta, k, v, max_new_tokens=9)
            src.release_request(rid)
            res = dst.run()
            # out_tokens carries the adopted first token, so the
            # engine-level result IS the full stream
            assert res[arid]["tokens"] == w
            assert res[arid]["tokens"][:1] == meta["out_tokens"]
            assert dst.metrics.adoptions.value >= 1

    def test_adopted_preemption_recomputes_exactly(self):
        """An adopted request squeezed by page pressure recomputes via
        the normal preemption path — stream unchanged."""
        prompts = rng_prompts(2, lo=6, hi=10, seed=4)
        want = oracle_tokens(prompts, 8)
        src = make_engine()
        dst = make_engine(num_pages=16)  # tight: forces preemption
        rids = []
        for p in prompts:
            rid = src.add_request(p, max_new_tokens=8,
                                  prefill_only=True)
            src.run()
            meta, k, v = src.export_request(rid)
            rids.append(dst.adopt_request(meta, k, v,
                                          max_new_tokens=8))
            src.release_request(rid)
        res = dst.run()
        for i, rid in enumerate(rids):
            # out_tokens carries the adopted first token, so the
            # result IS the full stream despite any preemption
            assert res[rid]["tokens"] == want[i]


# ---------------------------------------------------------------------------
# reservation asymmetry: admission math through an UNSTARTED front-end
# (round-11 addenda: step-free reservation arithmetic is exact)


class TestPrefillAdmission:
    def test_prefill_only_reserves_prompt_plus_one(self):
        # 20 pages => 19 allocatable, watermark 1, 18 usable.
        # prompt 8 + max_new 12, page_size 4:
        #   full request  -> pages_for(20) = 5 -> 3 admitted
        #   prefill_only  -> pages_for(9)  = 3 -> 6 admitted
        def burst(prefill_only):
            rep = InProcessReplica(make_engine(num_pages=20))
            ok = 0
            while True:
                try:
                    rep.frontend.submit([5] * 8, max_new_tokens=12,
                                        prefill_only=prefill_only)
                    ok += 1
                except Rejected:
                    return ok
                assert ok < 50

        assert burst(False) == 3
        assert burst(True) == 6


# ---------------------------------------------------------------------------
# DisaggRouter: split-phase routing + token-exact handoff


class TestDisaggHandoff:
    def test_8way_greedy_and_sampled_exactness(self):
        """Acceptance: 8 concurrent streams through 1 prefill + 2
        decode replicas, greedy AND seeded-sampled, all token-exact vs
        the single-engine oracle — the handoff point is invisible."""
        prompts = rng_prompts(8, seed=10)
        seeds = [100 + i for i in range(8)]
        sampled = [i % 2 == 1 for i in range(8)]
        want = oracle_tokens(prompts, 10, do_sample=sampled,
                             seed=seeds, temperature=0.9, top_k=20)
        router = make_disagg()
        try:
            streams = [router.submit(
                p, max_new_tokens=10, do_sample=sampled[i],
                seed=seeds[i], temperature=0.9, top_k=20)
                for i, p in enumerate(prompts)]
            out = [None] * 8
            errs = []

            def run(i):
                try:
                    out[i] = consume(streams[i])
                except Exception as e:
                    errs.append((i, repr(e)))

            th = [threading.Thread(target=run, args=(i,))
                  for i in range(8)]
            for t in th:
                t.start()
            for t in th:
                t.join(60)
            assert not errs, errs
            assert out == want
            assert router.metrics.migrations_total.value == 8
            assert router.metrics.migrated_pages_total.value > 0
            # prefill replica holds nothing after the handoffs
            assert len(router.replicas[0].engine._held) == 0
            # decode replicas actually shared the work
            routed = router.metrics.routed_total
            decode_counts = [routed.value(policy="disagg_decode",
                                          replica=i) for i in (1, 2)]
            assert sum(decode_counts) == 8
        finally:
            router.close()

    def test_shared_prefix_suffix_only_transfer(self):
        """The radix tree as transfer index: repeated shared-prefix
        requests migrate fewer pages once the decode replica holds the
        prefix resident."""
        rng = np.random.default_rng(11)
        shared = rng.integers(0, 97, 16).astype(np.int32)
        router = make_disagg(roles=("prefill", "decode"))
        try:
            pages = []
            for i in range(4):
                p = np.concatenate(
                    [shared, rng.integers(0, 97, 3).astype(np.int32)])
                before = router.metrics.migrated_pages_total.value
                s = router.submit(p, max_new_tokens=4)
                consume(s)
                pages.append(
                    router.metrics.migrated_pages_total.value - before)
            # first transfer moves the full chain; later ones skip the
            # now-resident shared prefix pages
            assert pages[0] == 5
            assert all(n == 1 for n in pages[1:]), pages
        finally:
            router.close()

    def test_mid_migration_decode_kill_token_exact(self, monkeypatch):
        """Acceptance: the decode replica serving a migrated stream is
        killed mid-decode; the request re-prefills on a survivor via
        the failover path and the client stream stays token-exact."""
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.02")
        prompts = rng_prompts(3, seed=12)
        want = oracle_tokens(prompts, 10)
        router = make_disagg()
        try:
            streams = [router.submit(p, max_new_tokens=10)
                       for p in prompts]
            out = [None] * 3
            errs = []

            def run(i):
                toks = []
                try:
                    for ev in streams[i].events(timeout=120):
                        if ev["type"] == "token":
                            toks.append(ev["token"])
                            if i == 0 and len(toks) == 4:
                                # phase is decode by token 4 (token 1
                                # came from prefill): kill the server
                                router.kill_replica(
                                    streams[0].replica_idx)
                except Exception as e:
                    errs.append((i, repr(e)))
                out[i] = toks

            th = [threading.Thread(target=run, args=(i,))
                  for i in range(3)]
            for t in th:
                t.start()
            for t in th:
                t.join(60)
            assert not errs, errs
            assert out == want
            assert router.metrics.failovers_total.total >= 1
        finally:
            router.close()

    def test_prefill_replica_kill_reprefills(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.05")
        prompts = rng_prompts(2, lo=12, hi=20, seed=13)
        want = oracle_tokens(prompts, 6)
        router = make_disagg(roles=("prefill", "prefill", "decode"))
        try:
            streams = [router.submit(p, max_new_tokens=6)
                       for p in prompts]
            # kill a prefill replica once its chunked prefill is in
            # flight (or already held — the 50 ms/step fault latency
            # makes mid-prefill the common case; deadline-poll, never
            # a fixed sleep)
            victim = router.replicas[streams[0].replica_idx]
            wait_until(
                lambda: (lambda h: h.get("live", 0) or h.get("held", 0))
                (victim.health()),
                msg="prefill never started on the victim replica")
            router.kill_replica(streams[0].replica_idx)
            got = [consume(s) for s in streams]
            assert got == want
        finally:
            router.close()

    def test_degenerate_fleet_falls_back_to_mixed(self):
        prompts = rng_prompts(2, seed=14)
        want = oracle_tokens(prompts, 6)
        # no decode replicas at all -> base placement, still exact
        router = make_disagg(roles=("prefill", "mixed"))
        try:
            streams = [router.submit(p, max_new_tokens=6)
                       for p in prompts]
            assert [consume(s) for s in streams] == want
            assert router.metrics.migrations_total.value == 0
            assert all(s.phase == "mixed" for s in streams)
        finally:
            router.close()

    def test_n_forks_route_mixed(self):
        router = make_disagg(roles=("prefill", "decode", "mixed"))
        try:
            s = router.submit(np.asarray([1, 2, 3], np.int32),
                              max_new_tokens=4, do_sample=True, n=2,
                              seed=7)
            res = s.result(timeout=120)
            assert len(res) == 2
            assert all(r["finish_reason"] == "length" for r in res)
            assert s.phase == "mixed"
        finally:
            router.close()

    def test_decode_exhaustion_falls_back_to_reprefill(self):
        """Every decode replica sheds the adoption -> the router
        re-prefills mixed-mode instead of failing the stream."""
        prompts = rng_prompts(1, lo=5, hi=7, seed=15)
        want = oracle_tokens(prompts, 6)
        # the decode replica's pool is STRUCTURALLY too small for any
        # adoption (3 allocatable pages < 3-page need + 1 watermark),
        # so the migration can never commit there
        reps = [InProcessReplica(make_engine(prefix_cache=True),
                                 role="prefill"),
                InProcessReplica(make_engine(num_pages=4),
                                 role="decode")]
        router = DisaggRouter(reps, page_size=4).start()
        try:
            s = router.submit(prompts[0], max_new_tokens=6)
            got = consume(s)
            assert got == want[0]
            assert router.metrics.migration_fallbacks_total.value == 1
            assert router.metrics.migrations_total.value == 0
        finally:
            router.close()

    def test_cancel_mid_hold_releases_everywhere(self):
        router = make_disagg(roles=("prefill", "decode"), start=False)
        try:
            s = router.submit(np.asarray(range(1, 9), np.int32),
                              max_new_tokens=8)
            # unstarted: the request is queued on the prefill replica,
            # nothing has run — cancel must purge it cleanly
            assert router.cancel(s.req_id) is True
            router.start()
            pre = router.replicas[0].engine
            assert pre.scheduler.all_done()
            assert pre.cache.free_pages == pre.cache.allocatable_pages
        finally:
            router.close()

    def test_health_shows_roles_and_held(self):
        router = make_disagg(roles=("prefill", "decode"))
        try:
            h = router.health()
            assert [r["role"] for r in h["replicas"]] \
                == ["prefill", "decode"]
            assert all("held" in r for r in h["replicas"])
        finally:
            router.close()


# ---------------------------------------------------------------------------
# the HTTP path: /v1/_pages + disagg over real sockets


class TestDisaggHTTP:
    def test_http_fleet_handoff_exactness(self):
        prompts = rng_prompts(3, seed=20)
        want = oracle_tokens(prompts, 8)
        srv_p = ServingServer(make_engine(prefix_cache=True),
                              role="prefill")
        srv_d = ServingServer(make_engine(prefix_cache=True),
                              role="decode")
        hp = srv_p.start()
        hd = srv_d.start()
        router = DisaggRouter([HTTPReplica(*hp), HTTPReplica(*hd)],
                              page_size=4).start()
        try:
            # roles resolved from the remote /healthz at construction
            assert router.roles == ["prefill", "decode"]
            got = []
            for p in prompts:
                got.append(consume(router.submit(p, max_new_tokens=8)))
            assert got == want
            assert router.metrics.migrations_total.value == 3
            # the remote prefill server holds nothing afterwards
            assert srv_p.frontend.health()["held"] == 0
        finally:
            router.close()
            srv_p.close(timeout=30)
            srv_d.close(timeout=30)

    def test_pages_endpoints_validate(self):
        import http.client
        srv = ServingServer(make_engine(prefix_cache=True),
                            role="decode")
        host, port = srv.start()

        def post(path, body, ctype="application/json"):
            c = http.client.HTTPConnection(host, port, timeout=30)
            payload = (json.dumps(body).encode()
                       if isinstance(body, dict) else body)
            c.request("POST", path, payload,
                      {"Content-Type": ctype})
            r = c.getresponse()
            data = r.read()
            c.close()
            return r.status, data

        try:
            # probe: empty cache -> 0
            st, data = post("/v1/_pages/probe",
                            {"prompt": [1, 2, 3, 4, 5]})
            assert st == 200 and json.loads(data)["cached_pages"] == 0
            # export of an unknown request -> 404
            st, _ = post("/v1/_pages/export", {"req_id": 12345})
            assert st == 404
            # release of an unknown request -> released: false
            st, data = post("/v1/_pages/release", {"req_id": 12345})
            assert st == 200 and not json.loads(data)["released"]
            # corrupt import payload -> 400
            st, _ = post("/v1/_pages", b"garbage",
                         "application/x-paddle-tpu-kv-pages")
            assert st == 400
            # geometry mismatch -> 409
            other = PagedKVCache(3, 2, 8, page_size=4, num_pages=16)
            other.alloc_seq("a")
            other.append_slots("a", 5)
            meta, k, v = other.export_pages("a")
            meta.update(prompt=[1, 2, 3, 4, 5], out_tokens=[9],
                        device_seed=1)
            st, data = post(
                "/v1/_pages", serialize_pages(
                    meta, k, v, request={"max_tokens": 4}),
                "application/x-paddle-tpu-kv-pages")
            assert st == 409
            assert json.loads(data)["error"]["type"] \
                == "geometry_mismatch"
        finally:
            srv.close(timeout=30)


# ---------------------------------------------------------------------------
# FleetAutoscaler: policy unit tests (fake clock + scripted loads)


class _ScriptedReplica:
    def __init__(self, role="decode", load=0.0):
        self.role = role
        self._load = load
        self.started = False
        self.drained = False
        self.closed = False
        self.prom = ""

    def start(self):
        self.started = True
        return self

    def health(self):
        return {"status": "ok", "role": self.role}

    @property
    def state(self):
        return "ok"

    def load(self):
        return self._load

    def prometheus(self):
        return self.prom

    def drain(self, timeout=120.0):
        self.drained = True
        return True

    def resume(self):
        return self

    def fail(self, exc=None):
        pass

    def close(self, timeout=0.0):
        self.closed = True
        return True

    def submit(self, prompt, **kw):
        raise Rejected("scripted replica never admits")


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestAutoscalerPolicy:
    def _rig(self, replicas, **kw):
        router = DisaggRouter(replicas, page_size=4)
        clock = _FakeClock()
        made = []

        def factory(role):
            r = _ScriptedReplica(role=role, load=0.0)
            made.append(r)
            return r

        kw.setdefault("up_pages", 10)
        kw.setdefault("down_pages", 2)
        kw.setdefault("up_window_s", 5)
        kw.setdefault("down_window_s", 20)
        kw.setdefault("min_per_role", {"prefill": 1, "decode": 1})
        kw.setdefault("max_per_role", {"prefill": 2, "decode": 3})
        aut = FleetAutoscaler(router, factory, clock=clock, **kw)
        return router, aut, clock, made

    def test_role_spec_parsing(self):
        assert parse_role_spec(None, 0) == {"__default__": 0}
        assert parse_role_spec("3", 0) == {"__default__": 3}
        assert parse_role_spec("prefill:1,decode:2", 0) == {
            "__default__": 0, "prefill": 1, "decode": 2}
        with pytest.raises(ValueError):
            parse_role_spec("prefill:", 0)

    def test_burst_scale_up_with_hysteresis(self):
        reps = [_ScriptedReplica("prefill"),
                _ScriptedReplica("decode", load=50.0)]
        router, aut, clock, made = self._rig(reps)
        assert aut.tick() == []          # condition just started
        clock.t = 3.0
        assert aut.tick() == []          # held 3s < 5s window
        clock.t = 6.0
        assert aut.tick() == [("up", "decode", 2)]
        assert made[0].role == "decode"
        assert len(router.replicas) == 3
        assert router.metrics.autoscale_events.value(
            direction="up", role="decode") == 1
        # a pressure BLIP between ticks resets the window
        reps[1]._load = 0.0
        made[0]._load = 0.0
        clock.t = 7.0
        aut.tick()
        reps[1]._load = 50.0
        made[0]._load = 50.0
        clock.t = 8.0
        assert aut.tick() == []          # window restarted at t=8

    def test_max_cap_blocks_scale_up(self):
        reps = [_ScriptedReplica("prefill"),
                _ScriptedReplica("decode", load=99.0)]
        router, aut, clock, made = self._rig(
            reps, max_per_role={"prefill": 1, "decode": 1})
        clock.t = 100.0
        aut.tick()
        clock.t = 200.0
        assert aut.tick() == []
        assert len(router.replicas) == 2

    def test_idle_scale_down_respects_min_and_drains(self):
        reps = [_ScriptedReplica("prefill"),
                _ScriptedReplica("decode", load=1.0),
                _ScriptedReplica("decode", load=0.5)]
        router, aut, clock, _ = self._rig(reps)
        aut.tick()
        clock.t = 25.0
        events = aut.tick()
        assert events == [("down", "decode", 2)]  # least-loaded victim
        assert reps[2].drained and reps[2].closed
        assert 2 in router._retired
        # at the floor now: no further shrink, ever
        clock.t = 100.0
        aut.tick()
        clock.t = 200.0
        assert aut.tick() == []
        assert len(router._routable()) == 2

    def test_below_floor_repairs_immediately(self):
        reps = [_ScriptedReplica("prefill")]
        router, aut, clock, made = self._rig(reps)
        events = aut.tick()              # no decode replica at all
        assert events == [("up", "decode", 1)]
        # add_replica starts replicas only on a LIVE router
        assert not made[0].started
        assert router.roles[1] == "decode"

    def test_ttft_slo_breach_drives_scale_up(self):
        reps = [_ScriptedReplica("prefill"),
                _ScriptedReplica("decode", load=0.0)]
        reps[0].prom = (
            "# TYPE paddle_tpu_serving_ttft_s histogram\n"
            'paddle_tpu_serving_ttft_s_bucket{le="0.25"} 10\n'
            'paddle_tpu_serving_ttft_s_bucket{le="+Inf"} 10\n')
        router, aut, clock, _ = self._rig(
            reps, ttft_slo_s=0.25, slo_breach_frac=0.2)
        aut.tick()                       # baseline window
        # next window: 10 more requests, 8 of them over the SLO
        reps[0].prom = (
            "# TYPE paddle_tpu_serving_ttft_s histogram\n"
            'paddle_tpu_serving_ttft_s_bucket{le="0.25"} 12\n'
            'paddle_tpu_serving_ttft_s_bucket{le="+Inf"} 20\n')
        clock.t = 1.0
        aut.tick()
        # the breach must be SUSTAINED across the hysteresis window —
        # another breaching window of traffic lands before t=7
        reps[0].prom = (
            "# TYPE paddle_tpu_serving_ttft_s histogram\n"
            'paddle_tpu_serving_ttft_s_bucket{le="0.25"} 14\n'
            'paddle_tpu_serving_ttft_s_bucket{le="+Inf"} 30\n')
        clock.t = 7.0
        events = aut.tick()
        assert ("up", "prefill", 2) in events \
            or ("up", "decode", 2) in events

    def test_started_router_starts_scaled_up_replicas(self):
        reps = [_ScriptedReplica("prefill"),
                _ScriptedReplica("decode", load=50.0)]
        router, aut, clock, made = self._rig(reps)
        router.start()
        try:
            clock.t = 6.0
            aut.tick()
            clock.t = 12.0
            aut.tick()
            assert made and made[0].started
        finally:
            router.close()


@pytest.mark.slow
class TestServingDisaggReplay:
    def test_disagg_smoke_replay(self):
        """End-to-end bench path in a subprocess (the conftest
        artifact guard snapshots BENCH_serving*.json around this —
        the subprocess rewrites BENCH_serving_disagg.json)."""
        root = os.path.abspath(
            os.path.join(os.path.dirname(__file__), ".."))
        proc = subprocess.Popen(
            [sys.executable, "bench_serving.py", "--smoke", "--disagg"],
            cwd=root, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        out, _ = proc.communicate(timeout=900)
        assert proc.returncode == 0, out.decode(errors="replace")[-2000:]
        line = out.decode().strip().splitlines()[-1]
        rec = json.loads(line)
        assert rec["smoke"] is True
        assert rec["disagg_fleet"]["migrations"] > 0
        assert rec["disagg_fleet"]["ttft_heavy_p50_s"] is not None
        assert rec["mixed_fleet"]["ttft_heavy_p50_s"] is not None


class TestAutoscalerDrill:
    def test_burst_scale_up_idle_drain_zero_lost(self, monkeypatch):
        """Acceptance drill: a burst scales the decode role up (real
        replica factory), every stream completes (zero lost, zero
        5xx), idleness drains the extra replica back down through the
        rolling-drain path."""
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.02")
        router = make_disagg(roles=("prefill", "decode"))
        clock = _FakeClock()

        def factory(role):
            return InProcessReplica(
                make_engine(prefix_cache=True), role=role)

        aut = FleetAutoscaler(
            router, factory, clock=clock, up_pages=3, down_pages=1,
            up_window_s=1, down_window_s=1,
            min_per_role={"prefill": 1, "decode": 1},
            max_per_role={"prefill": 1, "decode": 2})
        try:
            prompts = rng_prompts(6, seed=30)
            want = oracle_tokens(prompts, 12)
            streams = [router.submit(p, max_new_tokens=12)
                       for p in prompts]
            out = [None] * len(streams)
            errs = []

            def run(i):
                try:
                    out[i] = consume(streams[i])
                except Exception as e:
                    errs.append((i, repr(e)))

            th = [threading.Thread(target=run, args=(i,))
                  for i in range(len(streams))]
            for t in th:
                t.start()
            # sustained pressure -> scale up while the burst runs
            deadline = time.monotonic() + 30
            grew = False
            while not grew and time.monotonic() < deadline:
                clock.t += 2.0
                grew = any(d == "up" for d, _, _ in aut.tick())
                time.sleep(0.01)
            for t in th:
                t.join(60)
            assert not errs, errs
            assert grew, "burst never scaled up"
            assert len(router.replicas) == 3
            assert out == want            # zero lost, token-exact
            # idle now: ticks shrink decode back to the floor
            deadline = time.monotonic() + 30
            shrunk = False
            while not shrunk and time.monotonic() < deadline:
                clock.t += 2.0
                shrunk = any(d == "down" for d, _, _ in aut.tick())
            assert shrunk, "idle fleet never scaled down"
            assert len(router._routable()) == 2
            # the fleet still serves after the resize churn
            s = router.submit(prompts[0], max_new_tokens=12)
            assert consume(s) == want[0]
        finally:
            aut.stop()
            router.close()
