"""Quantized serving end-to-end (round 15): int8 paged KV cache
(per-slot/per-head absmax codes + f32 scales, quantize-on-append inside
the compiled step) and weight-only int8/int4 streaming through the
serving engine.

Pinned here:
- dequant-oracle parity: ``paged_attention`` over int8 pages vs the fp
  reference at 1e-2, and the interpret-gated Pallas stub vs the gather
  path on the same quantized pool;
- honest capacity math: ``page_bytes_per_page`` accounts for the scale
  rows, equal ``hbm_budget_bytes`` yields >= 1.8x the bf16 page count
  at head_dim 64;
- stream determinism WITHIN an int8 config: bit-exact across engines,
  preemption recompute, router failover and disagg page migration
  (greedy AND seeded-sampled) — exact within a config, never across
  dtypes (a dtype-skewed fleet degrades to mixed fallback, not to an
  outage);
- the draft-cache dtype unification regression (draft cache follows
  the resolved ``cache_dtype`` for EVERY value, incl. int8);
- weight-only quantization riding the engine (lm_head exempt, weights
  still step ARGUMENTS) and the
  PADDLE_TPU_SERVING_KV_DTYPE / PADDLE_TPU_SERVING_WEIGHT_QUANT knobs.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (DisaggRouter, GeometryMismatch,
                                InProcessReplica, PagedKVCache,
                                ServingEngine, ServingFrontend,
                                deserialize_pages, serialize_pages)
from paddle_tpu.serving.attention import (paged_attention,
                                          paged_attention_ref,
                                          quantize_q8)


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
    kw.setdefault("cache_dtype", "int8")
    return ServingEngine(tiny_model(seed), **kw)


def rng_prompts(n, lo=3, hi=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 97, int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


def run_tokens(prompts, max_new, model_seed=0, engine_kw=None,
               **req_kw):
    eng = make_engine(model_seed, **(engine_kw or {}))
    rids = []
    for i, p in enumerate(prompts):
        kw = {k: (v[i] if isinstance(v, list) else v)
              for k, v in req_kw.items()}
        rids.append(eng.add_request(p, max_new_tokens=max_new, **kw))
    res = eng.run()
    return [res[r]["tokens"] for r in rids], eng


def consume(stream, timeout=120):
    return [ev["token"] for ev in stream.events(timeout=timeout)
            if ev["type"] == "token"]


# ---------------------------------------------------------------------------
# dequant-oracle parity


def _quantized_pool(rng, np_, ps, nkv, d):
    """A random fp32 page pool plus its int8 (codes, scales) twin."""
    import jax.numpy as jnp
    kf = rng.standard_normal((np_, ps, nkv, d)).astype(np.float32)
    vf = rng.standard_normal((np_, ps, nkv, d)).astype(np.float32)
    kq, ks = quantize_q8(jnp.asarray(kf))
    vq, vs = quantize_q8(jnp.asarray(vf))
    return (jnp.asarray(kf), jnp.asarray(vf)), ((kq, ks), (vq, vs))


class TestPagedAttentionInt8:
    def test_int8_matches_fp_reference_at_1e2(self):
        """Dequant-oracle parity: attention over the quantized pool
        tracks the fp pool within 1e-2 of the K/V value range (the
        per-slot absmax recipe's intrinsic floor is ~amax/127 ≈ 8e-3
        per dequantized element, so 1e-2·range is the honest bound for
        unit-normal K/V)."""
        import jax.numpy as jnp
        rng = np.random.default_rng(0)
        b, s, nh, nkv, d, ps, np_, p = 3, 2, 4, 2, 16, 4, 12, 5
        (kf, vf), (kt, vt) = _quantized_pool(rng, np_, ps, nkv, d)
        q = jnp.asarray(rng.standard_normal((b, s, nh, d)),
                        jnp.float32)
        pt = jnp.asarray(rng.integers(1, np_, (b, p)), jnp.int32)
        cl = jnp.asarray([17, 9, 20], jnp.int32)
        qo = cl - s
        kwargs = dict(scale=d ** -0.5)
        ref = np.asarray(paged_attention_ref(q, kf, vf, pt, cl, qo,
                                             **kwargs))
        got = np.asarray(paged_attention_ref(q, kt, vt, pt, cl, qo,
                                             **kwargs))
        tol = 1e-2 * np.abs(np.asarray(vf)).max()
        assert np.abs(got - ref).max() < tol

    def test_windowed_int8_matches_fp_reference(self):
        import jax.numpy as jnp
        rng = np.random.default_rng(1)
        b, s, nh, nkv, d, ps, np_, p = 2, 1, 4, 4, 8, 4, 10, 4
        (kf, vf), (kt, vt) = _quantized_pool(rng, np_, ps, nkv, d)
        q = jnp.asarray(rng.standard_normal((b, s, nh, d)), jnp.float32)
        pt = jnp.asarray(rng.integers(1, np_, (b, p)), jnp.int32)
        cl = jnp.asarray([13, 7], jnp.int32)
        qo = cl - 1
        kwargs = dict(scale=d ** -0.5, window=6)
        ref = np.asarray(paged_attention_ref(q, kf, vf, pt, cl, qo,
                                             **kwargs))
        got = np.asarray(paged_attention_ref(q, kt, vt, pt, cl, qo,
                                             **kwargs))
        assert np.abs(got - ref).max() < 1e-2

    def test_kernel_stub_matches_gather_path_int8(self, monkeypatch):
        """The interpret-mode Pallas stub's inline per-page dequant
        agrees with the gather path on the SAME quantized pool."""
        import jax.numpy as jnp
        rng = np.random.default_rng(2)
        b, nh, nkv, d, ps, np_, p = 3, 4, 2, 8, 4, 10, 4
        _, (kt, vt) = _quantized_pool(rng, np_, ps, nkv, d)
        q = jnp.asarray(rng.standard_normal((b, 1, nh, d)), jnp.float32)
        pt = jnp.asarray(rng.integers(1, np_, (b, p)), jnp.int32)
        cl = jnp.asarray([9, 4, 15], jnp.int32)
        qo = cl - 1
        kwargs = dict(scale=d ** -0.5)
        ref = np.asarray(paged_attention_ref(q, kt, vt, pt, cl, qo,
                                             **kwargs))
        monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
        got = np.asarray(paged_attention(q, kt, vt, pt, cl, qo,
                                         **kwargs))
        np.testing.assert_allclose(got, ref, atol=1e-5)

    def test_quantize_q8_deterministic_and_bounded(self):
        import jax.numpy as jnp
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((4, 2, 16)) * 37.0)
        c1, s1 = quantize_q8(x)
        c2, s2 = quantize_q8(x)
        assert (np.asarray(c1) == np.asarray(c2)).all()
        assert (np.asarray(s1) == np.asarray(s2)).all()
        assert np.asarray(c1).dtype == np.int8
        assert np.abs(np.asarray(c1)).max() <= 127
        deq = np.asarray(c1, np.float32) * np.asarray(s1)[..., None]
        rel = np.abs(deq - np.asarray(x)).max() / np.abs(
            np.asarray(x)).max()
        assert rel < 1e-2


# ---------------------------------------------------------------------------
# capacity accounting


class TestCapacityAccounting:
    def test_page_bytes_accounts_scales(self):
        # int8: D code bytes + 4 scale bytes per (slot, kv head), K+V
        assert PagedKVCache.page_bytes_per_page(2, 2, 64, 16, "int8") \
            == 2 * 2 * 16 * 2 * (64 + 4)
        assert PagedKVCache.page_bytes_per_page(2, 2, 64, 16,
                                                "bfloat16") \
            == 2 * 2 * 16 * 2 * 64 * 2

    def test_equal_budget_allocatable_ratio_vs_bf16(self):
        """Acceptance: >= 1.8x allocatable pages at an equal HBM budget
        (2D/(D+4) = 1.88x at head_dim 64)."""
        budget = 8 << 20
        kw = dict(page_size=16, hbm_budget_bytes=budget)
        bf16 = PagedKVCache(2, 2, 64, dtype="bfloat16", **kw)
        int8 = PagedKVCache(2, 2, 64, dtype="int8", **kw)
        ratio = int8.allocatable_pages / bf16.allocatable_pages
        assert ratio >= 1.8, ratio

    def test_rejects_non_int8_integer_dtypes(self):
        with pytest.raises(ValueError):
            PagedKVCache(1, 1, 8, num_pages=4, dtype="int32")

    def test_engine_exports_page_bytes_metric(self):
        eng = make_engine()
        per_page = PagedKVCache.page_bytes_per_page(
            2, 4, 8, 4, "int8")
        assert eng.metrics.kv_page_bytes.value == per_page


# ---------------------------------------------------------------------------
# engine streams: determinism within the int8 config


class TestEngineInt8Streams:
    def test_greedy_bitexact_across_engines(self):
        prompts = rng_prompts(6, seed=4)
        a, _ = run_tokens(prompts, 10)
        b, _ = run_tokens(prompts, 10)
        assert a == b

    def test_preemption_recompute_token_exact(self):
        """Page pressure forces preemption; the recompute prefill
        re-QUANTIZES the history and must land bit-identical pages —
        greedy and seeded-sampled streams both match the unpressured
        int8 oracle."""
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 97, 3).astype(np.int32)
                   for _ in range(4)]
        seeds = [70 + i for i in range(4)]
        sampled = [i % 2 == 1 for i in range(4)]
        want, _ = run_tokens(prompts, 12, do_sample=sampled, seed=seeds,
                             temperature=0.9, top_k=20)
        got, eng = run_tokens(
            prompts, 12, do_sample=sampled, seed=seeds, temperature=0.9,
            top_k=20, engine_kw=dict(num_pages=10, max_batch=4))
        assert eng.metrics.preemptions.value > 0, \
            "config failed to force preemption"
        assert got == want

    def test_prefix_cache_reuses_quantized_pages_exactly(self):
        """Cached int8 prompt pages serve later shared-prefix requests;
        the dequantized K/V is identical, so streams match the
        cache-off int8 engine."""
        rng = np.random.default_rng(6)
        shared = rng.integers(0, 97, 12).astype(np.int32)
        prompts = [np.concatenate(
            [shared, rng.integers(0, 97, 3).astype(np.int32)])
            for _ in range(4)]
        want, _ = run_tokens(prompts, 8)
        eng = make_engine(prefix_cache=True)
        rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
        res = eng.run()
        assert [res[r]["tokens"] for r in rids] == want
        assert eng.cache.prefix_hit_pages > 0

    @pytest.mark.parametrize("dtype", [None, "float32", "bfloat16",
                                       "int8"])
    def test_draft_cache_follows_resolved_cache_dtype(self, dtype):
        """Regression (round-15 satellite): engine.__init__ once
        duplicated the bf16-or-f32 decision for the draft cache instead
        of following the resolved cache_dtype — draft and target caches
        could silently diverge."""
        eng = ServingEngine(tiny_model(0), page_size=4, num_pages=64,
                            max_batch=4, prefill_chunk=8,
                            cache_dtype=dtype,
                            draft_model=tiny_model(1),
                            speculative_k=2)
        assert eng._draft_cache.dtype == eng.cache.dtype
        assert eng._draft_cache.quantized == eng.cache.quantized

    def test_speculative_int8_matches_plain_int8(self):
        prompts = rng_prompts(4, seed=7)
        want, _ = run_tokens(prompts, 10)
        eng = ServingEngine(tiny_model(0), page_size=4, num_pages=200,
                            max_batch=8, prefill_chunk=8,
                            cache_dtype="int8",
                            draft_model=tiny_model(0),
                            speculative_k=3)
        rids = [eng.add_request(p, max_new_tokens=10) for p in prompts]
        res = eng.run()
        assert [res[r]["tokens"] for r in rids] == want
        # self-draft on a shared-seed model must accept proposals
        assert eng.metrics.spec_accepted_tokens.value > 0

    def test_weight_quant_converts_and_streams(self):
        m = tiny_model(0)
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=4,
                            prefill_chunk=8, weight_quant="int8")
        from paddle_tpu.nn.common import Linear
        from paddle_tpu.nn.quant import WeightOnlyLinear
        assert m._weight_only_converted > 0
        assert type(m.lm_head) is Linear  # exempt, full precision
        assert isinstance(m.llama.layers[0].self_attn.q_proj,
                          WeightOnlyLinear)
        rid = eng.add_request(np.arange(3, 9, dtype=np.int32),
                              max_new_tokens=6)
        res = eng.run()
        assert len(res[rid]["tokens"]) == 6
        assert eng.weight_quant == "int8"

    def test_weight_quant_int4_streams(self):
        eng = make_engine(weight_quant="int4")
        rid = eng.add_request(np.arange(5, 12, dtype=np.int32),
                              max_new_tokens=5)
        assert len(eng.run()[rid]["tokens"]) == 5

    def test_weight_quant_deterministic(self):
        prompts = rng_prompts(3, seed=8)
        a, _ = run_tokens(prompts, 8, engine_kw=dict(weight_quant="int8"))
        b, _ = run_tokens(prompts, 8, engine_kw=dict(weight_quant="int8"))
        assert a == b

    def test_env_knobs_resolve(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_KV_DTYPE", "int8")
        monkeypatch.setenv("PADDLE_TPU_SERVING_WEIGHT_QUANT", "int8")
        eng = ServingEngine(tiny_model(0), page_size=4, num_pages=64,
                            max_batch=4, prefill_chunk=8)
        assert eng.cache_dtype == "int8" and eng.cache.quantized
        assert eng.weight_quant == "int8"
        # explicit args beat the knobs
        monkeypatch.setenv("PADDLE_TPU_SERVING_KV_DTYPE", "float32")
        eng2 = ServingEngine(tiny_model(1), page_size=4, num_pages=64,
                             max_batch=4, prefill_chunk=8,
                             cache_dtype="int8", weight_quant=None)
        assert eng2.cache_dtype == "int8"

    def test_invalid_values_raise(self):
        with pytest.raises(ValueError):
            make_engine(cache_dtype="int4")
        with pytest.raises(ValueError):
            make_engine(weight_quant="fp8")

    def test_healthz_advertises_quantization(self):
        eng = make_engine(weight_quant="int8")
        fe = ServingFrontend(eng)     # unstarted: pure state reads
        h = fe.health()
        assert h["cache_dtype"] == "int8"
        assert h["weight_quant"] == "int8"
        fe2 = ServingFrontend(make_engine(seed=1, cache_dtype="float32"))
        h2 = fe2.health()
        assert h2["cache_dtype"] == "float32"
        assert h2["weight_quant"] is None


# ---------------------------------------------------------------------------
# migration / failover composition


def make_disagg_int8(roles=("prefill", "decode", "decode"), seed=0,
                     engine_kw=None, **router_kw):
    ekw = dict(engine_kw or {})
    ekw.setdefault("prefix_cache", True)
    reps = [InProcessReplica(make_engine(seed, **ekw), role=r)
            for r in roles]
    router_kw.setdefault("page_size", 4)
    return DisaggRouter(reps, **router_kw).start()


class TestInt8Migration:
    def test_pagewire_roundtrip_scales_byte_exact(self):
        eng = make_engine()
        rid = eng.add_request(np.arange(10, 23, dtype=np.int32),
                              max_new_tokens=4, prefill_only=True)
        eng.run()
        meta, k, v = eng.export_request(rid)
        assert meta["dtype"] == "int8"
        assert len(k) == 2 * eng.cache.n_layers
        buf = serialize_pages(meta, k, v, request={"max_tokens": 4})
        m2, k2, v2, _ = deserialize_pages(buf)
        assert m2 == meta
        for a, b in zip(k + v, k2 + v2):
            assert a.dtype == b.dtype
            assert (np.asarray(a) == b).all()
        # scales are the f32 arrays in the back half of each list
        assert all(a.dtype == np.float32
                   for a in k2[eng.cache.n_layers:])
        eng.release_request(rid)

    def test_cross_dtype_import_rejected(self):
        eng = make_engine()
        rid = eng.add_request(np.arange(4, 12, dtype=np.int32),
                              max_new_tokens=4, prefill_only=True)
        eng.run()
        meta, k, v = eng.export_request(rid)
        other = PagedKVCache(2, 4, 8, page_size=4, num_pages=32,
                             dtype="float32")
        with pytest.raises(GeometryMismatch):
            other.import_pages("x", meta, k, v)
        assert not other.has_seq("x")
        eng.release_request(rid)

    def test_handoff_8way_greedy_and_sampled_exact(self):
        """Acceptance: disagg handoff within the int8 config is
        token-exact vs the single-engine int8 oracle, greedy and
        seeded-sampled, 8 concurrent."""
        prompts = rng_prompts(8, seed=9)
        seeds = [50 + i for i in range(8)]
        sampled = [i % 2 == 1 for i in range(8)]
        want, _ = run_tokens(prompts, 10, do_sample=sampled, seed=seeds,
                             temperature=0.9, top_k=20)
        router = make_disagg_int8()
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
        finally:
            router.close()

    def test_failover_mid_decode_token_exact(self, monkeypatch):
        """Router failover within the int8 config: kill the decode
        replica mid-stream, the survivor re-prefills (re-quantizes) and
        the spliced stream stays token-exact."""
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.02")
        prompts = rng_prompts(3, seed=12)
        want, _ = run_tokens(prompts, 10)
        router = make_disagg_int8()
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

    def test_dtype_skew_fleet_degrades_to_fallback(self):
        """A decode replica with a DIFFERENT cache dtype bounces the
        page import on GeometryMismatch; the router falls back to a
        mixed re-prefill — the stream completes (availability), but
        exactness is only promised WITHIN a dtype config."""
        reps = [InProcessReplica(make_engine(0, prefix_cache=True),
                                 role="prefill"),
                InProcessReplica(
                    make_engine(0, prefix_cache=True,
                                cache_dtype="float32"),
                    role="decode")]
        router = DisaggRouter(reps, page_size=4).start()
        try:
            s = router.submit(np.arange(3, 11, dtype=np.int32),
                              max_new_tokens=8)
            toks = consume(s)
            assert len(toks) == 8
            assert router.metrics.migrations_total.value == 0
            assert router.metrics.migration_fallbacks_total.value >= 1
        finally:
            router.close()


# ---------------------------------------------------------------------------
# the bench path (subprocess; conftest guard snapshots BENCH_serving*)


@pytest.mark.slow
class TestServingKv8Replay:
    def test_kv8_smoke_replay(self):
        root = os.path.abspath(
            os.path.join(os.path.dirname(__file__), ".."))
        proc = subprocess.Popen(
            [sys.executable, "bench_serving.py", "--smoke", "--kv8"],
            cwd=root, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        out, _ = proc.communicate(timeout=900)
        assert proc.returncode == 0, out.decode(errors="replace")[-2000:]
        rec = json.loads(out.decode().strip().splitlines()[-1])
        assert rec["smoke"] is True
        assert rec["page_capacity_ratio"] >= 1.8
        assert abs(rec["quality"]["delta_nll_int8_kv"]) < 0.01
        assert rec["int8"]["shed"] <= rec["bf16"]["shed"]
