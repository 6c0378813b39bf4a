"""paddle_tpu.serving.tp — tensor-parallel SPMD serving replicas
(round 23 / ISSUE 19).

Layers under test:
- TPContext: the last-dim-only param placement rule (full contractions
  stay shard-local so TP=k is token-exact by construction), the
  dist_spec COMPOSITION invariant (never returned verbatim; fleet axes
  dropped), resolve_tp precedence (mesh > tp_degree > env knob) and
  validation,
- engine: TP∈{2,4} token-exactness vs TP=1 — greedy, seeded device
  sampling, under preemption/recompute, speculative
  decoding (self-draft AND distinct draft), int8 KV cache,
- pagewire: per-shard export payload format (layer-major/shard-minor,
  int8 scales ride every shard), wire roundtrip, tp_degree geometry
  skew bounces on GeometryMismatch with no residue, disagg migration
  between equal-degree replicas exact, skewed fleets complete via the
  re-prefill fallback,
- allocator: sharded-pool page conservation under a random
  append/fork/free/free_tail/migrate interleaving,
- control plane: /healthz tp advertisement, the router's up-front
  tp-skew ship guard, the Pallas kernel demotion guard (loud metric),
  and the shard_geometry_mismatch chaos fault point.

All on the conftest's 8-device virtual CPU mesh — no chip touches.
"""
import os

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (ChaosConfig, DisaggRouter,
                                GeometryMismatch, InProcessReplica,
                                PagedKVCache, ServingEngine,
                                ServingRouter, TP_AXIS, TPContext,
                                deserialize_pages, resolve_tp,
                                serialize_pages)
from paddle_tpu.serving.chaos import verify_page_conservation
from paddle_tpu.serving.frontend import ServingFrontend

VOCAB = 97
SAMPLE_KW = {"do_sample": True, "temperature": 0.8, "top_k": 20,
             "seed": 7}


def tiny_model(seed=0, **kw):
    P.seed(seed)
    kw.setdefault("vocab_size", VOCAB)
    kw.setdefault("hidden_size", 32)
    kw.setdefault("intermediate_size", 64)
    kw.setdefault("num_hidden_layers", 2)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("max_position_embeddings", 64)
    m = LlamaForCausalLM(LlamaConfig(**kw))
    m.eval()
    return m


def tiny_draft(seed=1):
    return tiny_model(seed, hidden_size=16, intermediate_size=32,
                      num_hidden_layers=1, num_attention_heads=2)


def make_engine(tp=None, seed=0, model_kw=None, **kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 160)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_chunk", 8)
    return ServingEngine(tiny_model(seed, **(model_kw or {})),
                         tp_degree=tp, **kw)


def rng_prompts(n, lo=3, hi=12, seed=0, vocab=VOCAB):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


def run_tokens(eng, prompts, max_new=8, **req_kw):
    rids = [eng.add_request(p, max_new_tokens=max_new, **req_kw)
            for p in prompts]
    res = eng.run()
    return [res[r]["tokens"] for r in rids]


def consume(stream, timeout=120):
    return [ev["token"] for ev in stream.events(timeout=timeout)
            if ev["type"] == "token"]


# ---------------------------------------------------------------------------
# 1. TPContext unit semantics


class TestTPContext:
    def test_resolve_precedence_and_disabled(self):
        assert resolve_tp() is None
        assert resolve_tp(tp_degree=1) is None
        ctx = resolve_tp(tp_degree=2)
        assert isinstance(ctx, TPContext)
        assert ctx.degree == 2 and ctx.axis == TP_AXIS
        assert ctx.mesh_shape == {TP_AXIS: 2}

    def test_resolve_env_knob(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_TP", "2")
        assert resolve_tp().degree == 2
        monkeypatch.setenv("PADDLE_TPU_SERVING_TP", "1")
        assert resolve_tp() is None
        monkeypatch.delenv("PADDLE_TPU_SERVING_TP")
        # explicit ctor degree beats the knob
        monkeypatch.setenv("PADDLE_TPU_SERVING_TP", "4")
        assert resolve_tp(tp_degree=2).degree == 2

    def test_resolve_validation(self):
        import jax
        from jax.sharding import Mesh
        with pytest.raises(ValueError, match="exceeds"):
            resolve_tp(tp_degree=999)
        with pytest.raises(ValueError, match="axis"):
            resolve_tp(mesh=Mesh(np.array(jax.devices()[:2]),
                                 ("model",)))
        # a mesh with a size-1 tp axis is disabled, not an error
        assert resolve_tp(mesh=Mesh(np.array(jax.devices()[:1]),
                                    (TP_AXIS,))) is None

    def test_param_spec_last_dim_only(self):
        ctx = resolve_tp(tp_degree=2)
        # ndim>=2, divisible last dim -> shard it
        assert tuple(ctx.param_spec((32, 64))) == (None, TP_AXIS)
        assert tuple(ctx.param_spec((8, 16, 64))) == (None, None,
                                                      TP_AXIS)
        # 1-D params replicate (norm scales, biases)
        assert tuple(ctx.param_spec((64,))) == ()
        # non-divisible last dim replicates — NEVER a different dim
        # (that would shard a contraction and partial-sum)
        assert tuple(ctx.param_spec((64, 97))) == ()

    def test_param_spec_composes_dist_spec_never_verbatim(self):
        from jax.sharding import PartitionSpec as PS
        ctx = resolve_tp(tp_degree=2)
        # a fleet TP spec: 'mp' on the last dim. _add_sharding must
        # compose on top; 'mp' occupies the last dim, so the serving
        # tp axis cannot land there -> replicate (fleet axis dropped:
        # the serving mesh doesn't know 'mp')
        dist = PS(None, "mp")
        got = ctx.param_spec((32, 64), dist)
        assert got != dist        # never verbatim
        assert "mp" not in tuple(got)
        # fleet axis on a NON-last dim: composition lands tp on the
        # free last dim, 'mp' itself is dropped from the placement
        got = ctx.param_spec((32, 64), PS("mp", None))
        assert tuple(got) == (None, TP_AXIS)
        # non-divisible last dim with a dist_spec: replicate over tp
        got = ctx.param_spec((32, 97), PS("mp", None))
        assert TP_AXIS not in tuple(got)
        assert "mp" not in tuple(got)

    def test_engine_divisibility_validation(self):
        with pytest.raises(ValueError, match="divide"):
            make_engine(tp=3)   # nh=4, nkv=4: 3 doesn't divide

    def test_env_knob_builds_tp_engine(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_TP", "2")
        eng = make_engine()
        assert eng.tp_degree == 2
        assert eng.tp_mesh_shape == {TP_AXIS: 2}
        assert eng.cache.tp_degree == 2


# ---------------------------------------------------------------------------
# 2. token-exactness vs TP=1 (the contract)


class TestTPExactness:
    def _want(self, prompts, max_new=8, **req_kw):
        return run_tokens(make_engine(), prompts, max_new, **req_kw)

    @pytest.mark.parametrize("tp,seed", [(2, 0), (4, 0), (2, 3)])
    def test_greedy_exact(self, tp, seed):
        prompts = rng_prompts(4, seed=seed)
        want = self._want(prompts)
        eng = make_engine(tp=tp)
        assert run_tokens(eng, prompts) == want
        # the SPMD step is still the one step: two token capacities
        assert eng.metrics.step_program_classes.value <= 2

    def test_greedy_exact_sharded_vocab(self):
        # vocab 96 divides 4: the lm_head column shard + the
        # sampled-lane all-gather actually engage (vocab 97 replicates)
        mk = {"vocab_size": 96}
        prompts = rng_prompts(3, vocab=96)
        want = run_tokens(make_engine(model_kw=mk), prompts)
        got = run_tokens(make_engine(tp=4, model_kw=mk), prompts)
        assert got == want

    def test_seeded_sampling_exact(self):
        prompts = rng_prompts(4, seed=1)
        want = self._want(prompts, **SAMPLE_KW)
        got = run_tokens(make_engine(tp=2), prompts, **SAMPLE_KW)
        assert got == want

    def test_exact_across_preemption_recompute(self):
        # the round-11 preemption-forcing config: page pressure makes
        # the scheduler evict+recompute mid-stream; token t is pure in
        # (weights, history, seed, t) so the stream must not notice
        kw = dict(num_pages=10)
        prompts = rng_prompts(4, lo=3, hi=4, seed=2)
        e1 = make_engine(**kw)
        want = run_tokens(e1, prompts, max_new=12)
        e2 = make_engine(tp=2, **kw)
        got = run_tokens(e2, prompts, max_new=12)
        assert got == want
        assert e1.metrics.preemptions.value > 0
        assert e2.metrics.preemptions.value > 0

    def test_speculative_self_draft_exact(self):
        prompts = rng_prompts(3, seed=4)
        want = self._want(prompts)

        def spec_engine(tp):
            m = tiny_model(0)
            return ServingEngine(m, page_size=4, num_pages=160,
                                 max_batch=4, prefill_chunk=8,
                                 draft_model=m, speculative_k=2,
                                 tp_degree=tp)
        # self-draft must accept 100% and equal the plain stream at
        # BOTH degrees (deterministic-sample verify)
        assert run_tokens(spec_engine(None), prompts) == want
        e = spec_engine(2)
        assert run_tokens(e, prompts) == want
        assert e.metrics.spec_accepted_tokens.value > 0

    def test_speculative_distinct_draft_exact(self):
        # ANY draft: verify recomputes the target sample, so the
        # emitted stream is exact even with a replicated distinct
        # draft riding a TP target
        prompts = rng_prompts(3, seed=5)
        want = self._want(prompts)
        eng = ServingEngine(tiny_model(0), page_size=4, num_pages=160,
                            max_batch=4, prefill_chunk=8,
                            draft_model=tiny_draft(), speculative_k=2,
                            tp_degree=2)
        assert run_tokens(eng, prompts) == want

    def test_int8_cache_exact_within_config(self):
        # round-15 rule: exactness is WITHIN a cache_dtype — TP=2
        # int8 vs TP=1 int8 (scales shard with the codes)
        prompts = rng_prompts(4, seed=6)
        want = run_tokens(make_engine(cache_dtype="int8"), prompts)
        got = run_tokens(make_engine(tp=2, cache_dtype="int8"),
                         prompts)
        assert got == want


# ---------------------------------------------------------------------------
# 3. pagewire: per-shard payloads + geometry skew


class TestTPPagewire:
    def _filled(self, tp, dtype="float32", n=11):
        c = PagedKVCache(2, 4, 8, page_size=4, num_pages=32,
                         dtype=dtype, tp_degree=tp)
        c.alloc_seq("s")
        c.append_slots("s", n)
        return c

    def test_export_is_per_shard_layer_major(self):
        c = self._filled(tp=2)
        meta, k, v = c.export_pages("s")
        assert meta["tp_degree"] == 2
        # 2 layers x 2 shards, layer-major/shard-minor; each chunk
        # carries KV//t heads
        assert len(k) == len(v) == 4
        for a in k + v:
            assert a.shape[2] == 2   # 4 kv heads / 2 shards
        # the two shards of layer 0 reassemble the full-head export
        full = np.asarray(
            self._filled(tp=1).export_pages("s")[1][0])
        assert (np.concatenate([np.asarray(k[0]), np.asarray(k[1])],
                               axis=2) == full).all()

    @pytest.mark.parametrize("dtype", ["float32", "int8"])
    def test_wire_roundtrip_and_equal_degree_import(self, dtype):
        c = self._filled(tp=2, dtype=dtype)
        meta, k, v = c.export_pages("s")
        if dtype == "int8":
            # scales ride every shard: codes + per-layer scale arrays
            assert len(k) > 4
        buf = serialize_pages(meta, k, v)
        m2, k2, v2, _ = deserialize_pages(buf)
        assert m2 == meta
        for a, b in zip(k + v, k2 + v2):
            assert a.dtype == b.dtype
            assert (np.asarray(a) == b).all()
        other = PagedKVCache(2, 4, 8, page_size=4, num_pages=32,
                             dtype=dtype, tp_degree=2)
        other.import_pages("d", m2, k2, v2)
        assert other.seq_len("d") == c.seq_len("s")
        verify_page_conservation(other, "import target")

    def test_tp_skew_bounces_with_no_residue(self):
        c2 = self._filled(tp=2)
        meta, k, v = c2.export_pages("s")
        for skew_tp in (1, 4):
            other = PagedKVCache(2, 4, 8, page_size=4, num_pages=32,
                                 tp_degree=skew_tp)
            with pytest.raises(GeometryMismatch):
                other.import_pages("x", meta, k, v)
            assert not other.has_seq("x")
            assert other.free_pages == other.allocatable_pages

    def test_torn_shard_payload_rejected(self):
        c = self._filled(tp=2)
        meta, k, v = c.export_pages("s")
        other = PagedKVCache(2, 4, 8, page_size=4, num_pages=32,
                             tp_degree=2)
        # drop one shard chunk: the per-shard count check must fire
        with pytest.raises(GeometryMismatch):
            other.import_pages("x", meta, k[:-1], v)
        assert other.free_pages == other.allocatable_pages


# ---------------------------------------------------------------------------
# 4. sharded-pool conservation fuzz


class TestTPConservationFuzz:
    @pytest.mark.parametrize("dtype", ["float32", "int8"])
    def test_conservation_fuzz_sharded_pools(self, dtype):
        """800 random ops over two tp_degree=2 allocators with
        migrations crossing the wire as per-shard payloads — no leaked
        or double-freed page, scales conserved with the codes."""
        rng = np.random.default_rng(23)
        caches = [PagedKVCache(2, 4, 4, page_size=4, num_pages=48,
                               prefix_cache=True, dtype=dtype,
                               tp_degree=2) for _ in range(2)]
        live = [dict(), dict()]
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

        for step in range(800):
            side = int(rng.integers(0, 2))
            c = caches[side]
            op = rng.random()
            sids = list(live[side])
            if op < 0.32 or not sids:
                new_seq(side)
            elif op < 0.48:
                sid = sids[int(rng.integers(len(sids)))]
                try:
                    c.append_slots(sid, int(rng.integers(1, 6)))
                except Exception:
                    pass
            elif op < 0.62:
                sid = sids[int(rng.integers(len(sids)))]
                c.free_seq(sid)
                del live[side][sid]
            elif op < 0.72:
                sid = sids[int(rng.integers(len(sids)))]
                ln = c.seq_len(sid)
                if ln:
                    c.free_tail(sid, int(rng.integers(0, ln + 1)))
            elif op < 0.78:
                c.clear_prefix()
            else:
                sid = sids[int(rng.integers(len(sids)))]
                prompt = live[side][sid]
                other = caches[1 - side]
                if c.seq_len(sid) < 1:
                    continue
                dst = fresh(1 - side)
                try:
                    meta, k, v = c.export_pages(sid)
                    buf = serialize_pages(meta, k, v)
                    m2, k2, v2, _ = deserialize_pages(buf)
                    other.import_pages(dst, m2, k2, v2, prompt=prompt,
                                       hist_len=c.seq_len(sid) + 1)
                except Exception:
                    continue
                live[1 - side][dst] = prompt
                c.free_seq(sid)
                del live[side][sid]
            if step % 100 == 0:
                for cc in caches:
                    verify_page_conservation(cc, "fuzz")
        for side in range(2):
            for sid in list(live[side]):
                caches[side].free_seq(sid)
            caches[side].clear_prefix()
            assert caches[side].free_pages \
                == caches[side].allocatable_pages


# ---------------------------------------------------------------------------
# 5. disagg migration between TP replicas


class TestTPDisagg:
    def _fleet(self, tps, **engine_kw):
        engine_kw.setdefault("prefix_cache", True)
        roles = ["prefill"] + ["decode"] * (len(tps) - 1)
        reps = [InProcessReplica(
                    make_engine(tp=(t if t and t > 1 else None),
                                **engine_kw), role=r)
                for t, r in zip(tps, roles)]
        return DisaggRouter(reps, page_size=4).start(), reps

    def _oracle(self, prompts, max_new=8, **req_kw):
        return run_tokens(make_engine(prefix_cache=True), prompts,
                          max_new, **req_kw)

    @pytest.mark.parametrize("dtype", [None, "int8"])
    def test_equal_degree_migration_exact(self, dtype):
        ekw = {"cache_dtype": dtype} if dtype else {}
        want = run_tokens(make_engine(prefix_cache=True, **ekw),
                          rng_prompts(3, seed=8), 8)
        router, reps = self._fleet([2, 2], **ekw)
        try:
            streams = [router.submit(p, max_new_tokens=8)
                       for p in rng_prompts(3, seed=8)]
            assert [consume(s) for s in streams] == want
            moved = sum(r.engine.metrics.adoptions.value
                        for r in reps)
            assert moved >= 1   # the handoff actually migrated pages
        finally:
            router.close()

    def test_skewed_fleet_completes_via_reprefill(self):
        # tp=2 prefill, tp=1 decode: every handoff bounces on
        # GeometryMismatch and the decode replica re-prefills — the
        # stream still completes token-exact
        prompts = rng_prompts(3, seed=9)
        want = self._oracle(prompts)
        router, reps = self._fleet([2, 1])
        try:
            streams = [router.submit(p, max_new_tokens=8)
                       for p in prompts]
            assert [consume(s) for s in streams] == want
            assert sum(r.engine.metrics.adoptions.value
                       for r in reps) == 0
        finally:
            router.close()


# ---------------------------------------------------------------------------
# 6. control plane: healthz, ship guard, kernel guard, chaos point


class TestTPControlPlane:
    def test_healthz_advertises_geometry(self):
        h = ServingFrontend(make_engine(tp=2)).health()
        assert h["tp_degree"] == 2
        assert h["tp_mesh"] == {TP_AXIS: 2}
        h1 = ServingFrontend(make_engine()).health()
        assert h1["tp_degree"] == 1
        assert h1["tp_mesh"] is None

    def test_replica_tp_degree_surface(self):
        assert InProcessReplica(make_engine(tp=2)).tp_degree() == 2
        assert InProcessReplica(make_engine()).tp_degree() == 1

    def test_router_tp_skew_ship_guard(self):
        # round-18 dtype-skew shape, tp flavour: donor tp=1, target
        # tp=2 — the ship is skipped UP FRONT (metric, zero transfers)
        # and the target recomputes, exact
        rng = np.random.default_rng(10)
        shared = rng.integers(0, VOCAB, 12).astype(np.int32)
        prompts = [np.concatenate([shared,
                                   rng.integers(0, VOCAB, 5 + i)
                                   .astype(np.int32)])
                   for i in range(2)]
        want = self._oracle_pair(prompts)
        reps = [InProcessReplica(make_engine(prefix_cache=True)),
                InProcessReplica(make_engine(tp=2,
                                             prefix_cache=True))]
        router = ServingRouter(reps, policy="round_robin",
                               page_size=4, prefix_fleet=True)
        router.start()
        try:
            assert consume(router.submit(
                prompts[0], max_new_tokens=4)) == want[0]
            s = router.submit(prompts[1], max_new_tokens=4)
            assert s.replica_idx == 1
            assert consume(s) == want[1]
            m = router.metrics
            assert m.prefix_ships_total.value == 0
            assert m.prefix_ship_skipped_total.value(
                reason="tp_skew") == 1
        finally:
            router.close()

    def _oracle_pair(self, prompts):
        eng = make_engine(prefix_cache=True)
        return run_tokens(eng, prompts, 4)

    def test_pallas_kernel_request_demotes_loudly(self, monkeypatch):
        # the GSPMD constraint: asking for the Pallas paged kernel
        # under TP falls back to the jnp gather path with a metric —
        # never silently, never a crash, streams stay exact
        prompts = rng_prompts(2, seed=11)
        want = run_tokens(make_engine(), prompts)
        monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
        eng = make_engine(tp=2)
        assert run_tokens(eng, prompts) == want
        assert eng.metrics.tp_kernel_fallbacks.value > 0

    def test_chaos_point_raises_and_fleet_degrades(self):
        # direct: the fault point bounces imports as a tp-skew would
        eng = make_engine(
            prefix_cache=True,
            chaos=ChaosConfig(rates={"shard_geometry_mismatch": 1.0}))
        with pytest.raises(GeometryMismatch):
            eng.import_prefix({}, [], [])
        with pytest.raises(GeometryMismatch):
            eng.adopt_request({}, [], [], max_new_tokens=1)
        # fleet: a decode replica whose imports always bounce still
        # completes every stream via the re-prefill fallback
        prompts = rng_prompts(2, seed=12)
        want = run_tokens(make_engine(prefix_cache=True), prompts, 6)
        chaos = ChaosConfig(rates={"shard_geometry_mismatch": 1.0})
        reps = [InProcessReplica(make_engine(prefix_cache=True),
                                 role="prefill"),
                InProcessReplica(
                    make_engine(prefix_cache=True, chaos=chaos),
                    role="decode")]
        router = DisaggRouter(reps, page_size=4).start()
        try:
            streams = [router.submit(p, max_new_tokens=6)
                       for p in prompts]
            assert [consume(s) for s in streams] == want
        finally:
            router.close()
