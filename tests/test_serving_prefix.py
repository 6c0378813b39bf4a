"""Round-10 serving decode hot path — on-device fused sampling and the
radix-tree prefix cache (SURVEY.md §4 oracle discipline; round-7 rule:
every new API surface gets its sweep in the same commit).

Covers: fused_sample unit semantics (greedy==argmax, counter-RNG
determinism, top-k/top-p masks, chi-square distribution, overflow
safety), the O(B) decode fetch, allocator invariants under
refcount/CoW/prefix-caching/LRU eviction (free-count conservation,
no cross-sequence aliasing, randomized fuzz), and engine/scheduler/
front-end integration: cached-prefix prefill skipping with token
exactness, preemption + recompute over a cached prefix, admission and
reservation accounting that counts only UNCACHED pages, and the burst
acceptance property (cache-hit admissions never preempt a running
decode)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (OutOfPages, PagedKVCache, Rejected,
                                Request, RequestState, Scheduler,
                                ServingEngine, ServingFrontend,
                                fused_sample)


def tiny_model(seed=0, **kw):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _sample_args(b, **kw):
    a = {"do_sample": np.ones(b, bool), "temperature": np.ones(b),
         "top_k": np.zeros(b, np.int32), "top_p": np.ones(b),
         "seeds": np.zeros(b, np.int32), "steps": np.zeros(b, np.int32)}
    a.update(kw)
    return (jnp.asarray(a["do_sample"]),
            jnp.asarray(a["temperature"], jnp.float32),
            jnp.asarray(a["top_k"], jnp.int32),
            jnp.asarray(a["top_p"], jnp.float32),
            jnp.asarray(a["seeds"], jnp.int32),
            jnp.asarray(a["steps"], jnp.int32))


# ---------------------------------------------------------------------------
# fused sampling unit semantics


class TestFusedSample:
    def test_greedy_is_argmax_token_exact(self):
        rng = np.random.default_rng(0)
        lg = jnp.asarray(rng.standard_normal((4, 33)), jnp.float32)
        tok, lp = fused_sample(
            lg, *_sample_args(4, do_sample=np.zeros(4, bool)))
        np.testing.assert_array_equal(
            np.asarray(tok), np.asarray(lg).argmax(-1))
        assert np.all(np.isfinite(np.asarray(lp)))
        # greedy-only static variant: identical tokens, no sort traced
        tok2, _ = fused_sample(
            lg, *_sample_args(4, do_sample=np.zeros(4, bool)),
            sample_capable=False)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok2))

    def test_counter_rng_deterministic_in_seed_and_step(self):
        rng = np.random.default_rng(1)
        lg = jnp.asarray(rng.standard_normal((1, 50)), jnp.float32)
        draw = lambda s, t: int(fused_sample(  # noqa: E731
            lg, *_sample_args(1, seeds=np.asarray([s], np.int32),
                              steps=np.asarray([t], np.int32)))[0][0])
        assert draw(7, 3) == draw(7, 3)        # pure in (seed, step)
        toks_by_step = [draw(7, t) for t in range(32)]
        toks_by_seed = [draw(s, 3) for s in range(32)]
        assert len(set(toks_by_step)) > 1      # step actually folds in
        assert len(set(toks_by_seed)) > 1      # seed actually folds in

    def test_top_k_mask(self):
        rng = np.random.default_rng(2)
        lg = jnp.asarray(rng.standard_normal((1, 24)), jnp.float32)
        top2 = set(np.asarray(lg[0]).argsort()[-2:].tolist())
        toks = {int(fused_sample(
            lg, *_sample_args(1, top_k=np.asarray([2], np.int32),
                              seeds=np.asarray([s], np.int32)))[0][0])
            for s in range(200)}
        assert toks <= top2 and len(toks) == 2

    def test_top_p_mask(self):
        rng = np.random.default_rng(3)
        lg = jnp.asarray(rng.standard_normal((1, 24)), jnp.float32)
        p = np.exp(np.asarray(lg[0]))
        p /= p.sum()
        order = np.argsort(p)[::-1]
        nucleus = set(
            order[:np.searchsorted(np.cumsum(p[order]), 0.5) + 1]
            .tolist())
        toks = {int(fused_sample(
            lg, *_sample_args(1, top_p=np.asarray([0.5], np.float32),
                              seeds=np.asarray([s], np.int32)))[0][0])
            for s in range(400)}
        assert toks <= nucleus

    def test_chi_square_matches_softmax(self):
        """Distributional parity of the counter-RNG Gumbel-max sampler
        against the exact softmax (the host oracle's distribution)."""
        rng = np.random.default_rng(4)
        v, n = 24, 4000
        lg = rng.standard_normal(v).astype(np.float32) * 0.5
        p = np.exp(lg - lg.max())
        p /= p.sum()
        big = jnp.tile(jnp.asarray(lg)[None], (n, 1))
        tok, _ = fused_sample(
            big, *_sample_args(
                n, seeds=np.full(n, 11, np.int32),
                steps=np.arange(n, dtype=np.int32)))
        obs = np.bincount(np.asarray(tok), minlength=v)
        stat = (((obs - n * p) ** 2) / (n * p)).sum()
        # chi^2 dof=23, p=0.001 critical value ~49.7; generous margin
        assert stat < 60.0, stat

    def test_large_logits_stay_finite(self):
        """Regression-class check: logits ~1e3 must not overflow the
        device sampler (log-softmax/Gumbel path is shift-invariant)."""
        rng = np.random.default_rng(5)
        lg = jnp.asarray(rng.standard_normal((2, 31)) * 1e3, jnp.float32)
        tok, lp = fused_sample(lg, *_sample_args(2))
        assert np.all(np.isfinite(np.asarray(lp)))
        assert np.all((np.asarray(tok) >= 0) & (np.asarray(tok) < 31))


# ---------------------------------------------------------------------------
# host oracle (numpy) sampling — regression + parity


class TestHostOracleSampling:
    def _req_engine(self, **req_kw):
        m = tiny_model(seed=6)
        eng = ServingEngine(m, page_size=4, num_pages=32, max_batch=2,
                            prefill_chunk=8)
        rid = eng.add_request(np.asarray([1, 2, 3], np.int32),
                              max_new_tokens=1, **req_kw)
        return eng, eng.request(rid)

    def test_large_logits_no_overflow(self):
        """Satellite regression: _sample must max-subtract before exp —
        logits ~1e3 otherwise overflow to inf/NaN and choice() raises
        on a non-normalizable p."""
        eng, req = self._req_engine(do_sample=True, seed=0,
                                    temperature=0.9, top_k=8)
        lg = np.random.default_rng(0).standard_normal(97) * 1e3
        tok = eng._sample(req, lg.astype(np.float32))
        assert 0 <= tok < 97

    def test_top_p_nucleus(self):
        eng, req = self._req_engine(do_sample=True, seed=1, top_p=0.5)
        lg = np.random.default_rng(1).standard_normal(97).astype(
            np.float32)
        p = np.exp(lg - lg.max())
        p /= p.sum()
        order = np.argsort(p)[::-1]
        nucleus = set(
            order[:np.searchsorted(np.cumsum(p[order]), 0.5) + 1]
            .tolist())
        toks = {eng._sample(req, lg) for _ in range(300)}
        assert toks <= nucleus

    def test_device_vs_host_greedy_token_exact_e2e(self, monkeypatch):
        """Acceptance: greedy decode is token-exact between the fused
        device sampler (default) and the host oracle path across an
        8-way continuous-batching run."""
        m = tiny_model(seed=7)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 97, int(rng.integers(3, 12)))
                   .astype(np.int32) for _ in range(8)]

        def run(host):
            if host:
                monkeypatch.setenv("PADDLE_TPU_SERVING_HOST_SAMPLE",
                                   "1")
            else:
                monkeypatch.delenv("PADDLE_TPU_SERVING_HOST_SAMPLE",
                                   raising=False)
            eng = ServingEngine(m, page_size=4, num_pages=200,
                                max_batch=8, prefill_chunk=8)
            rids = [eng.add_request(p, max_new_tokens=6)
                    for p in prompts]
            res = eng.run()
            return [res[r]["tokens"] for r in rids]

        assert run(host=False) == run(host=True)

    def test_decode_fetch_is_o_b(self):
        """Acceptance: per-decode-step host fetch is O(B) — token id +
        logprob (8 bytes/lane), not B*V*4 logits bytes."""
        m = tiny_model(seed=8)
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=2,
                            prefill_chunk=8)
        eng.add_request(np.arange(1, 6, dtype=np.int32),
                        max_new_tokens=5)
        while not eng.scheduler.running:      # prefill to completion
            eng.step()
        before = eng.metrics.fetch_bytes.value
        steps = eng.metrics.decode_steps.value
        eng.run()
        dsteps = eng.metrics.decode_steps.value - steps
        per_step = (eng.metrics.fetch_bytes.value - before) / dsteps
        assert dsteps > 0
        assert per_step <= 8 * eng.scheduler.max_batch
        assert per_step < 97 * 4  # strictly below one V-row of logits

    def test_logprobs_flow_to_events(self):
        m = tiny_model(seed=9)
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=2,
                            prefill_chunk=8)
        eng.add_request(np.arange(1, 6, dtype=np.int32),
                        max_new_tokens=3, logprobs=True)
        events = []
        while not eng.scheduler.all_done():
            events += eng.step()
        toks = [e for e in events if e["type"] == "token"]
        assert toks and all("logprob" in e for e in toks)
        assert all(np.isfinite(e["logprob"]) and e["logprob"] <= 0.0
                   for e in toks)

    def test_n_fork_recompute_does_not_duplicate_children(self):
        """Regression: a preempted n>1 PARENT used to re-fork at its
        recompute prefill, minting duplicate children."""
        m = tiny_model(seed=10)
        prompt = np.random.default_rng(10).integers(0, 97, 6).astype(
            np.int32)
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=8,
                            prefill_chunk=8)
        rid = eng.add_request(prompt, max_new_tokens=6, do_sample=True,
                              seed=3, n=3)
        events = []
        while not any(e["type"] == "token" and e["req_id"] == rid
                      for e in events):
            events += eng.step()
        eng._preempt(eng.request(rid))         # force parent recompute
        res = eng.run()
        assert len(res) == 3                   # parent + exactly 2 forks
        assert all(len(v["tokens"]) == 6 for v in res.values())


# ---------------------------------------------------------------------------
# allocator invariants with the prefix cache on


def prefix_cache(**kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 17)  # 16 allocatable
    kw.setdefault("prefix_cache", True)
    return PagedKVCache(1, 1, 4, **kw)


def _tok(i, n):
    return np.arange(i, i + n, dtype=np.int32)


class TestPrefixAllocator:
    def test_acquire_commit_hit_shares_pages(self):
        c = prefix_cache()
        prompt = _tok(0, 13)  # 3 full pages + 1 tail token
        c.acquire_prefix("a", prompt, 13)
        assert c.pages_held("a") == 0          # cold tree: no match
        c.append_slots("a", 13)
        c.commit_prefix("a", prompt, 13)
        assert c.cached_pages == 3             # only FULL prompt pages
        a_pages = list(c._tables["a"][:3])
        c.free_seq("a")
        assert c.reclaimable_pages == 3        # cached, not freed
        got = c.acquire_prefix("b", prompt, 13)
        assert got == 3
        assert c._tables["b"] == a_pages       # the same device pages
        assert c.seq_len("b") == 12            # prefill resumes at 12

    def test_last_token_never_served_from_cache(self):
        c = prefix_cache()
        prompt = _tok(0, 8)   # exactly 2 pages
        c.acquire_prefix("a", prompt, 8)
        c.append_slots("a", 8)
        c.commit_prefix("a", prompt, 8)
        c.free_seq("a")
        # a same-prompt lookup may use only (8-1)//4 = 1 page: the last
        # prompt token must be recomputed for its logits
        assert c.probe_prefix(prompt) == 1
        assert c.acquire_prefix("b", prompt, 8) == 1
        # with LONGER history (recompute path) both full pages match
        assert c.probe_prefix(prompt, hist_len=11) == 2

    def test_no_alias_across_unrelated_sequences(self):
        c = prefix_cache()
        pa, pb = _tok(0, 9), _tok(50, 9)
        c.acquire_prefix("a", pa, 9)
        c.append_slots("a", 9)
        c.commit_prefix("a", pa, 9)
        c.acquire_prefix("b", pb, 9)
        assert c.pages_held("b") == 0          # different tokens: miss
        c.append_slots("b", 9)
        c.commit_prefix("b", pb, 9)
        assert not (set(c._tables["a"]) & set(c._tables["b"]))

    def test_lru_eviction_leaf_first_under_pressure(self):
        c = prefix_cache(num_pages=9)  # 8 allocatable
        old, new = _tok(0, 9), _tok(40, 9)
        c.acquire_prefix("a", old, 9)
        c.append_slots("a", 9)                 # 3 pages
        c.commit_prefix("a", old, 9)           # caches 2
        c.free_seq("a")
        c.acquire_prefix("b", new, 9)
        c.append_slots("b", 9)
        c.commit_prefix("b", new, 9)
        c.free_seq("b")
        assert c.cached_pages == 4 and c.free_pages == 4
        # bump the NEW chain's recency, then demand 6 pages: both OLD
        # pages must be evicted (leaf first), the newer chain survives
        assert c.acquire_prefix("warm", new, 9) == 2
        c.free_seq("warm")
        c.acquire_prefix("big", _tok(80, 24), 24)
        c.append_slots("big", 24)              # 6 pages -> evicts 2
        assert c.prefix_evictions == 2
        assert c.probe_prefix(new, hist_len=99) == 2   # survivor
        assert c.probe_prefix(old, hist_len=99) == 0   # evicted
        # exhausted beyond reclaim: transactional OutOfPages
        with pytest.raises(OutOfPages):
            c.append_slots("big", 99)

    def test_tree_page_never_freed_while_shared(self):
        c = prefix_cache()
        prompt = _tok(0, 12)
        c.acquire_prefix("a", prompt, 12)
        c.append_slots("a", 12)
        c.commit_prefix("a", prompt, 12)
        c.acquire_prefix("b", prompt, 13)      # longer hist: 3 pages
        assert c.pages_held("b") == 3
        c.free_seq("a")
        # b still maps the cached pages; they are pinned, not evictable
        assert c.reclaimable_pages == 0
        for p in c._tables["b"]:
            assert c.refcount(p) == 1

    def test_conservation_fuzz(self):
        """Randomized alloc/append/commit/fork/free/evict cycles keep
        the allocator conserved: every page is in exactly one of
        {free list, live tables ∪ tree}, refcounts equal table
        multiplicity, scratch is never handed out."""
        rng = np.random.default_rng(0)
        c = prefix_cache(num_pages=17)
        live = {}       # seq -> prompt tokens
        nseq = 0
        for _ in range(300):
            op = rng.integers(0, 4)
            try:
                if op == 0:  # new sequence via acquire
                    nseq += 1
                    prompt = _tok(int(rng.integers(0, 40)),
                                  int(rng.integers(1, 14)))
                    c.acquire_prefix(nseq, prompt, len(prompt))
                    live[nseq] = prompt
                elif op == 1 and live:  # append + commit prompt pages
                    sid = int(rng.choice(list(live)))
                    miss = len(live[sid]) - c.seq_len(sid)
                    if miss > 0:
                        c.append_slots(sid, miss)
                        c.commit_prefix(sid, live[sid], len(live[sid]))
                    else:
                        c.append_slots(sid, int(rng.integers(1, 4)))
                elif op == 2 and live:  # fork
                    sid = int(rng.choice(list(live)))
                    nseq += 1
                    c.fork(sid, nseq)
                    live[nseq] = live[sid]
                elif op == 3 and live:  # free
                    sid = int(rng.choice(list(live)))
                    c.free_seq(sid)
                    del live[sid]
            except OutOfPages:
                pass
            used = set()
            for t in c._tables.values():
                used |= set(t)
            used |= set(c._cached)
            free = list(c._free)
            assert len(free) == len(set(free))
            assert not (set(free) & used)
            assert len(free) + len(used) == c.allocatable_pages
            assert 0 not in used and 0 not in free
            for p in range(1, c.num_pages):
                want = sum(p in t for t in c._tables.values())
                assert c.refcount(p) == want, (p, want)


# ---------------------------------------------------------------------------
# scheduler + engine + front-end integration


class TestPrefixScheduling:
    def test_admission_counts_only_uncached_pages(self):
        """Two same-prefix requests: with the cache the committed-page
        accounting counts each one's UNCACHED need (1 page), so both
        admit at once; the cold pool double-reserves the full prompt
        and defers the second."""
        def build(enabled):
            c = PagedKVCache(1, 1, 4, page_size=4, num_pages=10,
                             prefix_cache=enabled)
            prompt = _tok(0, 13)               # 3 full pages + 1 token
            if enabled:   # warm the tree: 3 full prompt pages
                c.acquire_prefix("warm", prompt, 13)
                c.append_slots("warm", 13)
                c.commit_prefix("warm", prompt, 13)
                c.free_seq("warm")
            # a small live sequence keeps the pool realistic
            c.alloc_seq("live")
            c.append_slots("live", 8)
            s = Scheduler(c, max_batch=4, prefill_chunk=8,
                          watermark_frac=0.05)  # watermark 1
            a = Request(prompt=prompt, max_new_tokens=2)
            b = Request(prompt=prompt, max_new_tokens=2)
            s.add(a)
            s.add(b)
            return c, s, a, b

        c, s, a, b = build(True)
        out = s.schedule(0.0)
        # cached: need = pages_for(14) - 3 held = 1 each; both admit
        assert a.state == RequestState.PREFILLING
        assert b.state == RequestState.PREFILLING
        assert a.cached_pages == 3 and b.cached_pages == 3
        assert out.prefill == (a, 12, 13)      # only the tail prefills
        c2, s2, a2, b2 = build(False)
        s2.schedule(0.0)
        # cold: a reserves 4 pages, b's 4 more overflow 7-free pool
        assert a2.state == RequestState.PREFILLING
        assert b2.state == RequestState.WAITING

    def test_second_request_skips_prefill_and_is_token_exact(self):
        m = tiny_model(seed=11)
        prompt = np.random.default_rng(11).integers(0, 97, 21).astype(
            np.int32)
        ref = ServingEngine(m, page_size=4, num_pages=64, max_batch=4,
                            prefill_chunk=8)
        r0 = ref.add_request(prompt, max_new_tokens=6)
        want = ref.run()[r0]["tokens"]

        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=4,
                            prefill_chunk=8, prefix_cache=True)
        ra = eng.add_request(prompt, max_new_tokens=6)
        assert eng.run()[ra]["tokens"] == want
        chunks_a = eng.metrics.prefill_chunks.value
        rb = eng.add_request(prompt, max_new_tokens=6)
        res = eng.run()
        assert res[rb]["tokens"] == want       # cached K/V is bit-exact
        assert eng.metrics.prefill_chunks.value - chunks_a == 1
        assert eng.request(rb).cached_pages == 5  # (21-1)//4 pages
        assert eng.cache.prefix_hit_pages == 5
        ex = eng.metrics.export()
        assert ex["prefix_hit_pages"] == 5
        assert ex["prefix_hit_rate"] == pytest.approx(0.5)
        assert (eng.cache.free_pages + eng.cache.cached_pages
                == eng.cache.allocatable_pages)

    def test_burst_same_prefix_single_prefill_pass(self):
        """Thundering-herd regression: a burst of same-prefix requests
        admitted BEFORE the first one prefilled must still reuse its
        pages (the match refreshes when each reaches the prefill
        head)."""
        m = tiny_model(seed=12)
        prompt = np.random.default_rng(12).integers(0, 97, 21).astype(
            np.int32)
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=4,
                            prefill_chunk=8, prefix_cache=True)
        rids = [eng.add_request(prompt, max_new_tokens=4)
                for _ in range(3)]
        res = eng.run()
        streams = [res[r]["tokens"] for r in rids]
        assert streams[0] == streams[1] == streams[2]
        # request 1: 3 chunks; requests 2,3: one tail chunk each
        assert eng.metrics.prefill_chunks.value == 5
        assert eng.cache.prefix_hit_pages == 10  # 2 x 5 pages

    def test_preemption_recompute_with_cached_prefix_bit_exact(self):
        """Preemption under page pressure with the prefix cache ON:
        recompute prefill rides the cached prompt pages and the streams
        stay identical to the sequential oracle."""
        m = tiny_model(seed=1)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 97, 3).astype(np.int32)
                   for _ in range(4)]
        oracle = []
        for p in prompts:
            e = ServingEngine(m, page_size=4, num_pages=64, max_batch=1,
                              prefill_chunk=8)
            r = e.add_request(p, max_new_tokens=12)
            oracle.append(e.run()[r]["tokens"])
        eng = ServingEngine(m, page_size=4, num_pages=10, max_batch=4,
                            prefill_chunk=8, prefix_cache=True)
        rids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
        res = eng.run()
        assert eng.metrics.preemptions.value > 0, \
            "config failed to force preemption"
        for rid, want in zip(rids, oracle):
            assert res[rid]["tokens"] == want

    def test_frontend_burst_cache_hit_no_preemption(self):
        """Acceptance: reservation shedding counts only uncached pages,
        so a shared-prefix burst is admitted where the cold math would
        shed it — and no running decode is ever preempted."""
        shared = np.arange(0, 16, dtype=np.int32)

        def run_burst(enabled):
            m = tiny_model(seed=13)
            eng = ServingEngine(m, page_size=4, num_pages=32,
                                max_batch=8, prefill_chunk=8,
                                prefix_cache=enabled)
            fe = ServingFrontend(eng).start()
            try:
                # warm the tree with one shared-prefix request
                fe.submit(np.concatenate([shared, _tok(60, 3)]),
                          max_new_tokens=2).result(timeout=60)
                # a long-running decode to protect from preemption
                longrun = fe.submit(_tok(70, 8), max_new_tokens=16)
                accepted, rejected = [], 0
                for i in range(6):
                    tail = _tok(40 + 3 * i, 3)
                    try:
                        accepted.append(fe.submit(
                            np.concatenate([shared, tail]),
                            max_new_tokens=4))
                    except Rejected:
                        rejected += 1
                results = [s.result(timeout=60) for s in accepted]
                long_res = longrun.result(timeout=120)
                assert fe.drain()
            finally:
                fe.close()
            assert len(long_res[0]["tokens"]) == 16
            assert all(len(r[0]["tokens"]) == 4 for r in results)
            return len(accepted), rejected, \
                eng.metrics.preemptions.value, eng

        acc_on, rej_on, preempt_on, eng_on = run_burst(True)
        acc_off, rej_off, preempt_off, _ = run_burst(False)
        assert preempt_on == 0 and preempt_off == 0
        assert acc_on == 6                  # every cache-hit admitted
        assert acc_off < acc_on             # cold math sheds the burst
        assert rej_off > 0
        assert eng_on.cache.prefix_hit_pages > 0

    def test_env_knob_enables_prefix_cache(self, monkeypatch):
        m = tiny_model(seed=14)
        monkeypatch.setenv("PADDLE_TPU_SERVING_PREFIX_CACHE", "1")
        eng = ServingEngine(m, page_size=4, num_pages=32, max_batch=2,
                            prefill_chunk=8)
        assert eng.cache.prefix_cache_enabled
        monkeypatch.delenv("PADDLE_TPU_SERVING_PREFIX_CACHE")
        eng = ServingEngine(m, page_size=4, num_pages=32, max_batch=2,
                            prefill_chunk=8)
        assert not eng.cache.prefix_cache_enabled
        # explicit kwarg wins over the (unset) env
        eng = ServingEngine(m, page_size=4, num_pages=32, max_batch=2,
                            prefill_chunk=8, prefix_cache=True)
        assert eng.cache.prefix_cache_enabled


# ---------------------------------------------------------------------------
# round-7 sweep rule: the new public surface


class TestPrefixSamplingSweep:
    def test_surface(self):
        import paddle_tpu.serving as sv
        assert "fused_sample" in sv.__all__
        import paddle_tpu.serving.sampling  # noqa: F401
        c = prefix_cache()
        for attr in ("prefix_cache_enabled", "acquire_prefix",
                     "commit_prefix", "probe_prefix", "cached_pages",
                     "reclaimable_pages", "available_pages",
                     "record_prefix_stats", "prefix_hit_pages",
                     "prefix_miss_pages", "prefix_evictions"):
            assert hasattr(c, attr), attr
        m = tiny_model(seed=15)
        eng = ServingEngine(m, page_size=4, num_pages=32, max_batch=2,
                            prefill_chunk=8)
        for attr in ("_run_ragged_step", "_release_waiting_pins",
                     "_host_sampling", "_fetch_logits",
                     "_sync_prefix_metrics"):
            assert hasattr(eng, attr), attr
