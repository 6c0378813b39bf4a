"""Batched speculative decoding in the continuous-batching serving
engine (round 12).

The determinism contract is the spine of every test here: verification
is DETERMINISTIC-SAMPLE MATCHING — the [B, k+1] verify step recomputes
the target's own counter-RNG sample at every position (token t pure in
(weights, history, seed, t), the PR-3 property), so the speculative
engine's streams are token-exact vs the non-speculative engine in
greedy AND seeded-sampled modes, with ANY draft (a bad draft only
lowers the acceptance rate). The paged allocator's rollback
(``free_tail``) is pinned by unit tests and a conservation fuzz that
interleaves accept/reject rollback with prefix-cache acquire/commit/
evict and n>1 forks.
"""
import itertools
import os

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (PagedKVCache, Request, Scheduler,
                                ServingEngine, ServingMetrics)


def tiny_model(seed=0, layers=2, hidden=32, **kw):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=97, hidden_size=hidden,
                      intermediate_size=2 * hidden,
                      num_hidden_layers=layers, num_attention_heads=4,
                      max_position_embeddings=64, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def tiny_draft(seed=5):
    """A narrow 1-layer draft — random weights, so acceptance is low;
    output exactness must hold regardless."""
    return tiny_model(seed=seed, layers=1, hidden=16)


ENG_KW = dict(page_size=4, num_pages=200, max_batch=8, prefill_chunk=8)


def run_engine(model, prompts, req_kws, max_new=6, **ekw):
    kw = dict(ENG_KW, **ekw)
    eng = ServingEngine(model, **kw)
    rids = [eng.add_request(p, max_new_tokens=max_new, **r)
            for p, r in zip(prompts, req_kws)]
    res = eng.run()
    return [res[r]["tokens"] for r in rids], eng


# ---------------------------------------------------------------------------
# allocator: free_tail rollback semantics


class TestFreeTail:
    def cache(self, **kw):
        kw.setdefault("page_size", 4)
        kw.setdefault("num_pages", 9)
        return PagedKVCache(1, 1, 4, **kw)

    def test_rollback_releases_whole_pages_only(self):
        c = self.cache()
        c.alloc_seq("a")
        c.append_slots("a", 11)            # 3 pages, last 3/4 full
        free0 = c.free_pages
        c.free_tail("a", 9)                # still 3 pages (ceil(9/4))
        assert c.free_pages == free0
        assert c.seq_len("a") == 9
        c.free_tail("a", 4)                # 1 page kept, 2 released
        assert c.free_pages == free0 + 2
        # slots reallocate over the rolled-back region with no aliasing
        slots, _ = c.append_slots("a", 8)
        assert len(set(slots.tolist())) == 8

    def test_rollback_to_zero_and_guards(self):
        c = self.cache()
        c.alloc_seq("a")
        c.append_slots("a", 6)
        c.free_tail("a", 0)
        assert c.seq_len("a") == 0
        assert c.free_pages == 8
        with pytest.raises(ValueError, match="outside"):
            c.free_tail("a", 1)            # beyond current length
        with pytest.raises(KeyError):
            c.free_tail("nope", 0)

    def test_fork_shared_pages_only_decref(self):
        c = self.cache()
        c.alloc_seq("p")
        c.append_slots("p", 8)             # 2 full pages
        c.fork("p", "c")
        # child grows a page of its own, then rolls it back
        c.append_slots("c", 4)
        free0 = c.free_pages
        c.free_tail("c", 8)
        assert c.free_pages == free0 + 1   # only the child's own page
        # shared pages survived for BOTH sequences
        assert c.seq_len("p") == 8 and c.seq_len("c") == 8
        for p in c._tables["p"]:
            assert c.refcount(p) == 2
        c.free_seq("c")
        c.free_seq("p")
        assert c.free_pages == 8

    def test_cached_page_stays_resident_on_rollback(self):
        c = self.cache(prefix_cache=True)
        prompt = np.arange(8, dtype=np.int32)
        c.acquire_prefix("a", prompt, 8)
        c.append_slots("a", 8)
        c.commit_prefix("a", prompt, 8)    # 2 full prompt pages cached
        cached = set(c._tables["a"])
        free0 = c.free_pages
        c.free_tail("a", 0)                # roll back THROUGH the
        assert c.seq_len("a") == 0         # cached prompt pages
        # cached pages became reclaimable, NOT free-listed
        assert c.free_pages == free0
        assert c.reclaimable_pages == 2
        assert all(p in c._cached for p in cached)
        # and a fresh sequence still prefix-matches them
        assert c.probe_prefix(prompt, 9) == 2


class TestAllocatorConservationFuzz:
    def test_fuzz_accept_reject_prefix_forks(self):
        """Random interleaving of append/rollback/fork/free with
        prefix-cache acquire/commit/evict: after EVERY op the page pool
        partitions exactly into {free} ∪ {referenced} ∪ {cached rc==0},
        refcounts equal table references, and nothing aliases."""
        rng = np.random.default_rng(0)
        c = PagedKVCache(1, 1, 4, page_size=4, num_pages=33,
                         prefix_cache=True)
        ids = itertools.count()
        live = {}                           # sid -> prompt array

        def invariant():
            refs = {}
            for table in c._tables.values():
                for p in table:
                    refs[p] = refs.get(p, 0) + 1
            for p in range(c.num_pages):
                assert c.refcount(p) == refs.get(p, 0)
            free = list(c._free)
            assert len(free) == len(set(free))       # no dup frees
            free = set(free)
            assert 0 not in free and 0 not in refs
            assert not free & set(refs)
            assert not free & set(c._cached)
            cached0 = {p for p in c._cached if c.refcount(p) == 0}
            whole = set(range(1, c.num_pages))
            assert free | set(refs) | cached0 == whole

        for step in range(2500):
            op = rng.integers(0, 100)
            if op < 22 or not live:
                sid = next(ids)
                prompt = rng.integers(0, 3, int(rng.integers(1, 14))
                                      ).astype(np.int32)
                c.acquire_prefix(sid, prompt, len(prompt))
                live[sid] = prompt
            elif op < 50:
                sid = rng.choice(list(live))
                n = int(rng.integers(1, 7))
                try:
                    c.append_slots(sid, n)
                except Exception:
                    pass
            elif op < 65:                    # speculative rollback
                sid = rng.choice(list(live))
                ln = c.seq_len(sid)
                c.free_tail(sid, int(rng.integers(0, ln + 1)))
            elif op < 75:
                sid = rng.choice(list(live))
                c.commit_prefix(sid, live[sid],
                                min(c.seq_len(sid), len(live[sid])))
            elif op < 85 and len(live) < 12:
                sid = rng.choice(list(live))
                child = next(ids)
                c.fork(sid, child)
                live[child] = live[sid]
            elif op < 97:
                sid = rng.choice(list(live))
                c.free_seq(sid)
                del live[sid]
            else:
                c.clear_prefix()
            invariant()
        for sid in list(live):
            c.free_seq(sid)
        c.clear_prefix()
        assert c.free_pages == c.allocatable_pages


# ---------------------------------------------------------------------------
# multi-token verify oracle parity


class TestVerifyOracle:
    def test_extend_logits_match_sequential_decode(self):
        """A verify lane's k+1 packed tokens get the logits of k+1
        sequential single-token decode steps over the paged cache at
        1e-5 — the step's own rows ARE the verify oracle."""
        m = tiny_model(seed=4)
        k = 3
        prompt = np.random.default_rng(4).integers(0, 97, 7).astype(
            np.int32)
        eng = ServingEngine(m, **ENG_KW)
        eng.add_request(prompt, max_new_tokens=k + 2)
        seq_logits = []
        while not eng.scheduler.all_done():
            evs = eng.step()
            if any(e["type"] == "token" for e in evs):
                seq_logits.append(
                    np.asarray(eng._logits_dev, np.float32)[0])
        assert len(seq_logits) == k + 2    # prefill + k+1 decode steps

        spec = ServingEngine(m, draft_model=m, speculative_k=k,
                             **ENG_KW)
        spec.add_request(prompt, max_new_tokens=k + 2)
        evs = []
        while not any(e["type"] == "token" for e in evs):
            evs += spec.step()             # prefill emits token 1
        spec.step()                        # first draft/verify round
        ml = np.asarray(spec._logits_dev, np.float32)   # [T, V]
        # the lone verify lane packs first: rows 0..k are its tokens
        # (k+1 of them fit the all-decode capacity)
        assert ml.shape[0] == spec._ragged_tok_small >= k + 1
        for j in range(k + 1):
            np.testing.assert_allclose(ml[j], seq_logits[1 + j],
                                       atol=1e-5)


class TestDraftCatchup:
    def test_catchup_is_the_trunk_and_writes_the_targets_kv(self):
        """The draft's catchup program is its trunk over one
        rectangular chunk: it returns the draft's pools and nothing
        else (no head, no sample), and with a self-draft the K/V it
        writes for a history is what the target's step wrote."""
        import jax

        from paddle_tpu.serving import engine as eng_mod
        m = tiny_model(seed=6)
        prompt = np.random.default_rng(6).integers(0, 97, 11).astype(
            np.int32)                     # two catchup chunks of 8
        eng = ServingEngine(m, draft_model=m, speculative_k=2,
                            **ENG_KW)
        rid = eng.add_request(prompt, max_new_tokens=6)
        evs = []
        while not any(e["type"] == "token" for e in evs):
            evs += eng.step()             # prefill emits token 1
        d0 = eng.metrics.step_dispatches.value
        eng.step()                        # catchup, propose, verify
        # two catchup chunks + the proposal scan + the step
        assert eng.metrics.step_dispatches.value - d0 == 4
        assert eng.metrics.step_program_classes.value <= 2
        sid = eng.request(rid).seq_id
        n = prompt.size                   # history but its last token
        tc, dc = eng.cache, eng._draft_cache
        tpt = tc.page_table(sid, eng.max_pages_per_seq)
        dpt = dc.page_table(sid, eng.max_pages_per_seq)
        pos = np.arange(n)
        for pools_t, pools_d in ((tc.k_pages, dc.k_pages),
                                 (tc.v_pages, dc.v_pages)):
            for pt_, pd_ in zip(pools_t, pools_d):
                want = np.asarray(pt_)[tpt[pos // 4], pos % 4]
                got = np.asarray(pd_)[dpt[pos // 4], pos % 4]
                assert np.abs(want).max() > 0
                np.testing.assert_allclose(got, want, atol=1e-6)
        k_ops, v_ops = dc.program_operands()
        i32 = lambda *sh: jax.ShapeDtypeStruct(sh, np.int32)  # noqa: E731
        out = jax.eval_shape(
            lambda *a: eng_mod._draft_catchup_pure(
                m, eng._draft_core, None, *a),
            [t._data for t in m._gen_state_tensors()], i32(1, 8),
            i32(1, 8), i32(1, eng.max_pages_per_seq), i32(1), i32(1, 8),
            k_ops, v_ops)
        assert [[a.shape for a in pools] for pools in out] == \
            [[a.shape for a in k_ops], [a.shape for a in v_ops]]


# ---------------------------------------------------------------------------
# end-to-end token exactness vs the non-speculative engine


def mixed_requests(n=8):
    """Greedy and seeded-sampled lanes interleaved (the 8-way sweep
    shape): temperature/top-k/top-p variety on the sampled ones."""
    out = []
    for i in range(n):
        if i % 2 == 0:
            out.append({})
        elif i % 4 == 1:
            out.append(dict(do_sample=True, seed=100 + i,
                            temperature=0.9, top_k=5))
        else:
            out.append(dict(do_sample=True, seed=200 + i,
                            temperature=1.3, top_p=0.8))
    return out


class TestSpecE2E:
    def test_8way_exactness_random_draft(self):
        """A RANDOM draft (near-zero acceptance) still yields token-
        exact streams — correctness never depends on draft quality."""
        m = tiny_model()
        prompts = [np.random.default_rng(i).integers(0, 97, 3 + i)
                   .astype(np.int32) for i in range(8)]
        kws = mixed_requests()
        base, _ = run_engine(m, prompts, kws)
        spec, eng = run_engine(m, prompts, kws,
                               draft_model=tiny_draft(),
                               speculative_k=3)
        assert base == spec
        assert eng.metrics.spec_rounds.value > 0
        assert eng.cache.free_pages == eng.cache.allocatable_pages
        assert eng._draft_cache.free_pages \
            == eng._draft_cache.allocatable_pages

    def test_8way_exactness_and_full_acceptance_self_draft(self):
        """Self-draft (draft IS the target): every usable proposal must
        be accepted — deterministic-sample verification has no
        distributional slack to lose."""
        m = tiny_model(seed=1)
        prompts = [np.random.default_rng(10 + i).integers(0, 97, 4 + i)
                   .astype(np.int32) for i in range(8)]
        kws = mixed_requests()
        base, _ = run_engine(m, prompts, kws)
        spec, eng = run_engine(m, prompts, kws, draft_model=m,
                               speculative_k=3)
        assert base == spec
        ex = eng.metrics.export()
        assert ex["spec_draft_tokens"] > 0
        assert ex["spec_accepted_tokens"] == ex["spec_draft_tokens"]
        assert ex["spec_acceptance_rate"] == 1.0

    def test_exactness_under_preemption(self):
        """Page pressure forces preemption mid-speculation; recompute +
        draft-cache rebuild must reproduce the streams exactly."""
        m = tiny_model(seed=2)
        prompts = [np.random.default_rng(2).integers(0, 97, 3)
                   .astype(np.int32) for _ in range(4)]
        kws = [{}] * 4
        base, _ = run_engine(m, prompts, kws, max_new=12,
                             num_pages=64, max_batch=4)
        spec, eng = run_engine(m, prompts, kws, max_new=12,
                               num_pages=12, max_batch=4,
                               draft_model=tiny_draft(seed=7),
                               speculative_k=2)
        assert base == spec
        assert eng.metrics.preemptions.value > 0, \
            "config failed to force preemption"

    def test_exactness_with_prefix_cache_and_forks(self):
        m = tiny_model(seed=3)
        prompt = np.random.default_rng(3).integers(0, 97, 9).astype(
            np.int32)
        kws = [dict(do_sample=True, seed=11, n=3)]

        def collect(**ekw):
            res, eng = run_engine(m, [prompt], kws, max_new=5, **ekw)
            all_res = sorted(tuple(v["tokens"])
                             for v in eng.results().values())
            return all_res, eng

        base, _ = collect()
        spec, eng = collect(draft_model=m, speculative_k=2,
                            prefix_cache=True)
        assert base == spec
        assert eng.metrics.cow_copies.value > 0
        # a second identical request decodes over the cached prefix
        rid = eng.add_request(prompt, max_new_tokens=5, do_sample=True,
                              seed=11)
        res = eng.run()
        assert len(res[rid]["tokens"]) == 5
        assert eng.cache.prefix_hit_pages > 0

    def test_eos_mid_accepted_prefix_stops_exactly(self):
        m = tiny_model(seed=4)
        prompt = np.random.default_rng(44).integers(0, 97, 5).astype(
            np.int32)
        ref = np.asarray(m.generate(P.to_tensor(prompt[None]),
                                    max_new_tokens=8)._data)[0]
        eos = int(ref[2])                  # stop at the 3rd token
        eng = ServingEngine(m, draft_model=m, speculative_k=4,
                            eos_token_id=eos, **ENG_KW)
        rid = eng.add_request(prompt, max_new_tokens=8)
        res = eng.run()
        assert res[rid]["finish_reason"] == "stop"
        np.testing.assert_array_equal(res[rid]["tokens"], ref[:3])
        assert eng.cache.free_pages == eng.cache.allocatable_pages
        assert eng._draft_cache.free_pages \
            == eng._draft_cache.allocatable_pages

    def test_per_request_opt_out(self):
        m = tiny_model(seed=5)
        prompt = np.random.default_rng(5).integers(0, 97, 5).astype(
            np.int32)
        eng = ServingEngine(m, draft_model=m, speculative_k=3,
                            **ENG_KW)
        rid = eng.add_request(prompt, max_new_tokens=6,
                              speculative=False)
        res = eng.run()
        assert eng.metrics.spec_rounds.value == 0   # plain decode only
        want = np.asarray(m.generate(P.to_tensor(prompt[None]),
                                     max_new_tokens=6)._data)[0]
        np.testing.assert_array_equal(res[rid]["tokens"], want)
        # mixed batch: opted-out and speculative lanes coexist
        r1 = eng.add_request(prompt, max_new_tokens=6,
                             speculative=False)
        r2 = eng.add_request(prompt, max_new_tokens=6)
        res = eng.run()
        assert eng.metrics.spec_rounds.value > 0
        np.testing.assert_array_equal(res[r1]["tokens"], want)
        np.testing.assert_array_equal(res[r2]["tokens"], want)

    def test_host_sample_oracle_exactness(self, monkeypatch):
        """PADDLE_TPU_SERVING_HOST_SAMPLE=1: the host numpy RNG draws
        one sample per EMITTED token in stream order, so the oracle
        path is exact under speculation too."""
        monkeypatch.setenv("PADDLE_TPU_SERVING_HOST_SAMPLE", "1")
        m = tiny_model(seed=6)
        prompts = [np.random.default_rng(60 + i).integers(0, 97, 5)
                   .astype(np.int32) for i in range(4)]
        kws = mixed_requests(4)
        base, _ = run_engine(m, prompts, kws)
        spec, _ = run_engine(m, prompts, kws, draft_model=m,
                             speculative_k=3)
        assert base == spec

    def test_guards(self):
        m = tiny_model(seed=7)
        with pytest.raises(ValueError, match="draft_model"):
            ServingEngine(m, speculative_k=2, **ENG_KW)
        with pytest.raises(ValueError, match="speculative_k"):
            ServingEngine(m, draft_model=m, speculative_k=0, **ENG_KW)
        with pytest.raises(ValueError, match="vocab"):
            P.seed(8)
            other = LlamaForCausalLM(LlamaConfig(
                vocab_size=50, hidden_size=16, intermediate_size=32,
                num_hidden_layers=1, num_attention_heads=4,
                max_position_embeddings=64))
            ServingEngine(m, draft_model=other, **ENG_KW)
        with pytest.raises(TypeError, match="draft_model"):
            ServingEngine(m, draft_model=object(), **ENG_KW)


# ---------------------------------------------------------------------------
# admission reserves the worst-case verify burst


class TestSpecAdmission:
    def test_scheduler_reserves_k_token_growth(self):
        c = PagedKVCache(1, 1, 4, page_size=4, num_pages=9)
        spec = Scheduler(c, max_batch=4, prefill_chunk=8,
                         watermark_frac=0.25,
                         spec_reserve_tokens=4)   # watermark 2 pages
        plain = Scheduler(c, max_batch=4, prefill_chunk=8,
                          watermark_frac=0.25)
        r = Request(prompt=np.zeros(8, np.int32), max_new_tokens=4)
        # one verify burst can append 5 tokens: 8+1+4 -> 4 pages
        assert spec.worst_case_need(r) == 4
        assert plain.worst_case_need(r) == 3
        a = Request(prompt=np.zeros(8, np.int32), max_new_tokens=4)
        b = Request(prompt=np.zeros(8, np.int32), max_new_tokens=4)
        spec.add(a)
        spec.add(b)
        spec.schedule(0.0)
        # a admitted (4 + watermark 2 <= 8 free); b deferred — its
        # burst reservation (4) on top of a's committed 4 won't fit
        assert a.state == "prefilling"
        assert b.state == "waiting"

    def test_running_lanes_reserve_next_burst(self):
        """Once a lane RUNS, admission keeps its next verify burst
        reserved — the committed-page math includes running lanes when
        spec_reserve_tokens > 0."""
        c = PagedKVCache(1, 1, 4, page_size=4, num_pages=9)
        s = Scheduler(c, max_batch=4, prefill_chunk=8,
                      watermark_frac=0.25, spec_reserve_tokens=4)
        a = Request(prompt=np.zeros(4, np.int32), max_new_tokens=8)
        s.add(a)
        s.schedule(0.0)
        c.alloc_seq(a.seq_id)
        c.append_slots(a.seq_id, 4)
        s.prefill_advanced(a, 4)
        assert a.state == "running"
        assert s._committed_pages() == s.worst_case_need(a) > 0

    def test_verify_burst_never_preempts_admitted_decode(self):
        """E2E: with the reserve in place a concurrent burst of
        speculative requests completes with ZERO preemptions — the
        verify bursts stay inside the admission envelope."""
        m = tiny_model(seed=9)
        prompts = [np.random.default_rng(90 + i).integers(0, 97, 4)
                   .astype(np.int32) for i in range(4)]
        spec, eng = run_engine(m, prompts, [{}] * 4, max_new=8,
                               num_pages=17, max_batch=4,
                               draft_model=m, speculative_k=2)
        assert eng.metrics.preemptions.value == 0
        assert eng.metrics.spec_rounds.value > 0
        base, _ = run_engine(m, prompts, [{}] * 4, max_new=8,
                             num_pages=64, max_batch=4)
        assert spec == base


# ---------------------------------------------------------------------------
# observability


class TestSpecMetrics:
    def test_metrics_exported_and_prometheus_lines(self):
        mt = ServingMetrics()
        ex = mt.export()
        for key in ("spec_rounds", "spec_draft_tokens",
                    "spec_accepted_tokens", "spec_fallbacks",
                    "spec_acceptance_rate"):
            assert key in ex, key
        text = mt.to_prometheus()
        assert "# TYPE paddle_tpu_serving_spec_rounds counter" in text
        assert ("# TYPE paddle_tpu_serving_spec_acceptance_rate gauge"
                in text)

    def test_acceptance_rate_in_healthz_and_metrics(self):
        from paddle_tpu.serving import ServingFrontend
        m = tiny_model(seed=10)
        eng = ServingEngine(m, draft_model=m, speculative_k=2,
                            **ENG_KW)
        fe = ServingFrontend(eng)
        assert fe.health()["speculative_k"] == 2
        prompt = np.random.default_rng(10).integers(0, 97, 5).astype(
            np.int32)
        rid = eng.add_request(prompt, max_new_tokens=6)
        eng.run()
        assert rid is not None
        text = fe.prometheus()
        assert "paddle_tpu_serving_spec_acceptance_rate 1.0" in text
        assert "paddle_tpu_serving_spec_rounds" in text

    def test_env_knob_documented(self):
        doc = open(os.path.join(os.path.dirname(__file__), "..",
                                "docs", "SERVING.md")).read()
        assert "PADDLE_TPU_SERVING_PROBE_S" in doc
        assert "speculative" in doc
