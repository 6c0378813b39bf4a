"""paddle_tpu.serving — paged KV cache, paged attention, continuous
batching (SURVEY.md §4 oracle discipline: every layer is pinned to a
reference — the allocator to its invariants, paged attention to a dense
oracle AND the contiguous static-cache path, the engine end-to-end to
one-at-a-time generate())."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (EngineDraining, FaultInjected,
                                OutOfPages, PagedKVCache, Request,
                                RequestState, Scheduler, ServingEngine,
                                ServingMetrics, paged_attention,
                                paged_attention_ref)
from serving_utils import sequential_oracle


def tiny_model(seed=0, **kw):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def tiny_cache(**kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 9)  # 8 allocatable
    return PagedKVCache(1, 1, 4, **kw)


# ---------------------------------------------------------------------------
# page allocator invariants


class TestPagedKVCache:
    def test_exact_capacity_fill(self):
        c = tiny_cache()
        # 8 allocatable pages of 4 slots = 32 tokens exactly
        c.alloc_seq("a")
        slots, copies = c.append_slots("a", 32)
        assert not copies
        assert c.free_pages == 0
        assert len(set(slots.tolist())) == 32  # all distinct
        assert all(s >= c.page_size for s in slots)  # never scratch
        with pytest.raises(OutOfPages):
            c.append_slots("a", 1)
        c.free_seq("a")
        assert c.free_pages == 8

    def test_out_of_pages_is_transactional(self):
        c = tiny_cache()
        c.alloc_seq("a")
        c.append_slots("a", 30)  # 8 pages held, 2 slots spare in last
        c.alloc_seq("b")
        with pytest.raises(OutOfPages):
            c.append_slots("b", 5)
        # failed alloc must not have leaked state
        assert c.seq_len("b") == 0
        assert c.free_pages == 0
        slots, _ = c.append_slots("a", 2)  # spare tail slots still work
        assert len(slots) == 2

    def test_double_free_raises(self):
        c = tiny_cache()
        c.alloc_seq("a")
        c.append_slots("a", 4)
        c.free_seq("a")
        with pytest.raises(KeyError):
            c.free_seq("a")

    def test_no_cross_sequence_slot_aliasing(self):
        c = tiny_cache(num_pages=17)
        seen = set()
        for sid in range(4):
            c.alloc_seq(sid)
            slots, _ = c.append_slots(sid, 7)
            s = set(slots.tolist())
            assert not (s & seen)
            seen |= s

    def test_budget_sizing(self):
        per_page = PagedKVCache.page_bytes_per_page(2, 4, 8, 16,
                                                   "float32")
        c = PagedKVCache(2, 4, 8, page_size=16,
                         hbm_budget_bytes=10 * per_page + 5)
        assert c.num_pages == 10
        assert c.k_pages[0].shape == (10, 16, 4, 8)
        with pytest.raises(ValueError, match="budget"):
            PagedKVCache(2, 4, 8, page_size=16,
                         hbm_budget_bytes=per_page)  # < 2 pages

    def test_fork_shares_pages_until_write(self):
        c = tiny_cache()
        c.alloc_seq("p")
        c.append_slots("p", 6)  # 2 pages, tail page half full
        used = c.used_pages
        c.fork("p", "c")
        assert c.used_pages == used  # zero new pages at fork
        # first child append copy-on-writes the SHARED partial tail page
        slots, copies = c.append_slots("c", 1)
        assert len(copies) == 1
        src, dst = copies[0]
        assert c.refcount(src) == 1 and c.refcount(dst) == 1
        # parent's next append must NOT see the child's page
        pslots, pcopies = c.append_slots("p", 1)
        assert not pcopies  # parent kept sole ownership of src
        assert slots[0] != pslots[0]

    def test_fork_full_tail_page_needs_no_cow(self):
        c = tiny_cache()
        c.alloc_seq("p")
        c.append_slots("p", 8)  # exactly 2 full pages
        c.fork("p", "c")
        _, copies = c.append_slots("c", 1)  # fresh page, no copy
        assert not copies

    def test_apply_copies_device_semantics(self):
        c = tiny_cache()
        c.alloc_seq("p")
        slots, _ = c.append_slots("p", 2)
        page = slots[0] // c.page_size
        # write a sentinel into the parent's page
        c.k_pages[0] = c.k_pages[0].at[page].set(7.0)
        c.fork("p", "c")
        _, copies = c.append_slots("c", 1)
        c.apply_copies(copies)
        (src, dst), = copies
        assert src == page
        np.testing.assert_array_equal(np.asarray(c.k_pages[0][dst]),
                                      np.asarray(c.k_pages[0][src]))

    def test_free_rejects_unknown_and_scratch_stays_reserved(self):
        c = tiny_cache()
        with pytest.raises(KeyError):
            c.free_seq("nope")
        c.alloc_seq("a")
        slots, _ = c.append_slots("a", 32)
        assert 0 not in (slots // c.page_size)


# ---------------------------------------------------------------------------
# paged attention vs dense oracle and the contiguous cache path


def _dense_oracle(q, ks, vs, lens, scale, offsets):
    """Row-by-row dense attention over each row's valid prefix.
    q [B,S,H,D]; ks/vs lists of [L_i, KV, D]."""
    b, s, nh, d = q.shape
    nkv = ks[0].shape[1]
    g = nh // nkv
    out = np.zeros((b, s, nh, d), np.float32)
    for i in range(b):
        for r in range(s):
            qpos = offsets[i] + r
            L = min(lens[i], qpos + 1)
            qi = np.asarray(q[i, r], np.float32).reshape(nkv, g, d)
            k = np.asarray(ks[i][:L], np.float32)        # [L,KV,D]
            v = np.asarray(vs[i][:L], np.float32)
            sc = np.einsum("kgd,tkd->kgt", qi, k) * scale
            sc -= sc.max(-1, keepdims=True)
            p = np.exp(sc)
            p /= p.sum(-1, keepdims=True)
            out[i, r] = np.einsum("kgt,tkd->kgd", p, v).reshape(nh, d)
    return out


def _paged_layout(ks, vs, page_size, num_pages, max_pages, seed=0):
    """Scatter per-row K/V into randomly-ordered pages (the layout a
    fragmented free list produces)."""
    rng = np.random.default_rng(seed)
    nkv, d = ks[0].shape[1], ks[0].shape[2]
    kp = np.zeros((num_pages, page_size, nkv, d), np.float32)
    vp = np.zeros((num_pages, page_size, nkv, d), np.float32)
    free = list(rng.permutation(np.arange(1, num_pages)))
    pt = np.zeros((len(ks), max_pages), np.int32)
    for i, (k, v) in enumerate(zip(ks, vs)):
        n_pages = -(-len(k) // page_size)
        pages = [free.pop() for _ in range(n_pages)]
        pt[i, :n_pages] = pages
        for t in range(len(k)):
            kp[pages[t // page_size], t % page_size] = k[t]
            vp[pages[t // page_size], t % page_size] = v[t]
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt)


class TestPagedAttention:
    def _rand_case(self, b, s, nh, nkv, d, lens, offsets, seed=0):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((b, s, nh, d)), jnp.float32)
        ks = [rng.standard_normal((L, nkv, d)).astype(np.float32)
              for L in lens]
        vs = [rng.standard_normal((L, nkv, d)).astype(np.float32)
              for L in lens]
        return q, ks, vs

    @pytest.mark.parametrize("nkv", [4, 2, 1])
    def test_decode_parity_mixed_lengths(self, nkv):
        lens = [1, 5, 12, 17]
        offsets = [L - 1 for L in lens]
        q, ks, vs = self._rand_case(4, 1, 4, nkv, 8, lens, offsets)
        kp, vp, pt = _paged_layout(ks, vs, page_size=4, num_pages=32,
                                   max_pages=5)
        got = paged_attention_ref(
            q, kp, vp, pt, jnp.asarray(lens, jnp.int32),
            jnp.asarray(offsets, jnp.int32), scale=0.35)
        want = _dense_oracle(q, ks, vs, lens, 0.35, offsets)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)

    def test_prefill_chunk_parity(self):
        # chunked prefill: rows at offset 3, causal over own prefix
        lens = [9]          # 3 already cached + 6 in this chunk
        q, ks, vs = self._rand_case(1, 6, 4, 2, 8, lens, [3], seed=1)
        kp, vp, pt = _paged_layout(ks, vs, page_size=4, num_pages=16,
                                   max_pages=3, seed=1)
        got = paged_attention_ref(
            q, kp, vp, pt, jnp.asarray(lens, jnp.int32),
            jnp.asarray([3], jnp.int32), scale=0.5)
        want = _dense_oracle(q, ks, vs, lens, 0.5, [3])
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)

    def test_sliding_window(self):
        lens = [16]
        q, ks, vs = self._rand_case(1, 1, 4, 4, 8, lens, [15], seed=2)
        kp, vp, pt = _paged_layout(ks, vs, page_size=4, num_pages=16,
                                   max_pages=4, seed=2)
        got = paged_attention_ref(
            q, kp, vp, pt, jnp.asarray(lens, jnp.int32),
            jnp.asarray([15], jnp.int32), scale=0.5, window=5)
        # window w: only the last w positions (incl. self) visible
        ks2 = [ks[0][11:]]
        vs2 = [vs[0][11:]]
        want = _dense_oracle(q, ks2, vs2, [5], 0.5, [4])
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)

    def test_kernel_stub_interpret_parity(self, monkeypatch):
        """PADDLE_TPU_PAGED_KERNEL=1 routes decode through the Pallas
        interpret-mode stub; parity vs the gather reference."""
        lens = [3, 11, 20]
        offsets = [L - 1 for L in lens]
        q, ks, vs = self._rand_case(3, 1, 4, 2, 8, lens, offsets, seed=3)
        kp, vp, pt = _paged_layout(ks, vs, page_size=4, num_pages=32,
                                   max_pages=5, seed=3)
        args = (q, kp, vp, pt, jnp.asarray(lens, jnp.int32),
                jnp.asarray(offsets, jnp.int32))
        ref = paged_attention_ref(*args, scale=0.35)
        monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
        got = paged_attention(*args, scale=0.35)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    @pytest.mark.parametrize("n", [9, 11])
    def test_engine_prefill_logits_match_contiguous_cache(self, n):
        """Acceptance: paged logits vs the contiguous static-cache
        oracle (models/generation.py path) to 1e-5; the prompt's last
        chunk of 4 holds one token or three (the probe reads the
        chunk's LAST row of the packed step, not its first)."""
        from paddle_tpu.core.tensor import Tensor
        m = tiny_model(seed=4)
        prompt = np.random.default_rng(4).integers(0, 97, n).astype(
            np.int32)
        caches = m._init_caches(1, len(prompt))
        ref_logits, _ = m._forward_cached(Tensor(prompt[None]), caches, 0)
        ref_last = np.asarray(ref_logits[:, -1], np.float32)

        eng = ServingEngine(m, page_size=4, num_pages=32, max_batch=2,
                            prefill_chunk=4)
        rid = eng.add_request(prompt, max_new_tokens=1)
        events = []
        while not any(e["type"] == "token" for e in events):
            events += eng.step()
        got_last = eng._last_logits_probe
        np.testing.assert_allclose(got_last, ref_last[0], atol=1e-5)
        assert events[0]["token"] == int(ref_last[0].argmax())


# ---------------------------------------------------------------------------
# scheduler properties


class TestScheduler:
    def test_watermark_admission_defers(self):
        c = tiny_cache(num_pages=5)  # 4 allocatable
        s = Scheduler(c, max_batch=4, prefill_chunk=8,
                      watermark_frac=0.25)  # watermark = 1 page
        a = Request(prompt=np.zeros(8, np.int32), max_new_tokens=4)
        b = Request(prompt=np.zeros(8, np.int32), max_new_tokens=4)
        s.add(a)
        s.add(b)
        out = s.schedule(0.0)
        # a admitted (needs 3 pages for 9 tokens, free 4 >= 3+1); b
        # deferred behind the watermark
        assert a.state == RequestState.PREFILLING
        assert b.state == RequestState.WAITING
        assert out.prefill[0] is a

    def test_decode_priority_and_chunking(self):
        c = tiny_cache(num_pages=64)
        s = Scheduler(c, max_batch=4, prefill_chunk=4,
                      watermark_frac=0.05)
        r = Request(prompt=np.zeros(10, np.int32), max_new_tokens=4)
        s.add(r)
        out = s.schedule(0.0)
        assert out.prefill == (r, 0, 4)  # chunked, not whole-prompt
        c.alloc_seq(r.seq_id)
        c.append_slots(r.seq_id, 4)
        s.prefill_advanced(r, 4)
        assert r.state == RequestState.PREFILLING
        out = s.schedule(0.0)
        assert out.prefill == (r, 4, 8)
        c.append_slots(r.seq_id, 6)
        s.prefill_advanced(r, 10)
        assert r.state == RequestState.RUNNING
        out = s.schedule(0.0)
        assert out.decode == [r] and out.prefill is None

    def test_deadline_eviction(self):
        c = tiny_cache(num_pages=64)
        s = Scheduler(c, max_batch=4, prefill_chunk=4)
        r = Request(prompt=np.zeros(4, np.int32), max_new_tokens=4,
                    deadline=1.0)
        s.add(r)
        s.schedule(0.5)
        assert r.state == RequestState.PREFILLING
        out = s.schedule(2.0)
        assert out.expired == [r]
        assert r.state == RequestState.FINISHED
        assert r.finish_reason == "deadline"
        assert s.all_done()

    def test_preemption_victim_is_newest_and_requeues_front(self):
        c = tiny_cache(num_pages=64)
        s = Scheduler(c, max_batch=4, prefill_chunk=32)
        reqs = [Request(prompt=np.zeros(3, np.int32), max_new_tokens=8)
                for _ in range(3)]
        for r in reqs:
            s.add(r)
        s.schedule(0.0)
        for r in reqs:
            c.alloc_seq(r.seq_id)
            c.append_slots(r.seq_id, 3)
            s.prefill_advanced(r, 3)
        old, mid, new = reqs
        assert s.pick_victim(exclude=(new,)) is mid   # newest non-self
        assert s.pick_victim() is new                 # LIFO
        c.free_seq(new.seq_id)
        s.preempt(new)
        assert new.state == RequestState.WAITING
        assert s.waiting[0] is new                    # front of queue
        assert new.preemptions == 1
        assert new.prefill_pos == 0                   # full recompute


# ---------------------------------------------------------------------------
# engine end-to-end


class TestEngineE2E:
    def test_8way_continuous_batching_matches_sequential(self):
        """Acceptance: 8 concurrent requests, batched decode tokens
        identical to one-at-a-time generation."""
        m = tiny_model()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 97, int(rng.integers(3, 12)))
                   .astype(np.int32) for _ in range(8)]
        eng = ServingEngine(m, page_size=4, num_pages=200, max_batch=8,
                            prefill_chunk=8)
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        res = eng.run()
        oracle = sequential_oracle(m, prompts, 6)
        for rid, want in zip(rids, oracle):
            np.testing.assert_array_equal(res[rid]["tokens"], want)
        ex = eng.metrics.export()
        assert ex["ttft_s"]["count"] == 8
        assert ex["requests_finished"] == 8
        assert ex["tokens_generated"] == 48
        assert ex["batch_size"]["max"] > 1  # actually batched

    def test_preemption_recompute_token_exactness(self):
        """Page pressure forces preemption; recompute-prefill must
        reproduce the uninterrupted token stream exactly (the logits
        bit-exactness property, observed through argmax at every
        step)."""
        m = tiny_model(seed=1)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 97, 3).astype(np.int32)
                   for _ in range(4)]
        # 15-token final length = 4 pages/request; 4 requests want 16
        # pages but only 9 are allocatable -> decode growth preempts
        eng = ServingEngine(m, page_size=4, num_pages=10, max_batch=4,
                            prefill_chunk=8)
        rids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
        res = eng.run()
        assert eng.metrics.preemptions.value > 0, \
            "config failed to force preemption"
        oracle = sequential_oracle(m, prompts, 12)
        for rid, want in zip(rids, oracle):
            np.testing.assert_array_equal(res[rid]["tokens"], want)

    def test_prefill_chunk_size_invariance(self):
        m = tiny_model(seed=2)
        prompt = np.random.default_rng(2).integers(0, 97, 11).astype(
            np.int32)
        outs = []
        for chunk in (2, 5, 16):
            eng = ServingEngine(m, page_size=4, num_pages=64,
                                max_batch=2, prefill_chunk=chunk)
            rid = eng.add_request(prompt, max_new_tokens=5)
            outs.append(eng.run()[rid]["tokens"])
        assert outs[0] == outs[1] == outs[2]

    def test_deadline_timeout_graceful(self):
        m = tiny_model(seed=3)
        rng = np.random.default_rng(3)
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=4,
                            prefill_chunk=8)
        ok = eng.add_request(rng.integers(0, 97, 4).astype(np.int32),
                             max_new_tokens=4)
        dead = eng.add_request(rng.integers(0, 97, 4).astype(np.int32),
                               max_new_tokens=4, deadline_s=-1.0)
        res = eng.run()
        assert res[dead]["finish_reason"] == "deadline"
        assert res[ok]["finish_reason"] == "length"
        assert len(res[ok]["tokens"]) == 4
        assert eng.metrics.deadline_evictions.value == 1
        assert eng.cache.free_pages == eng.cache.allocatable_pages

    def test_eos_stops_request(self):
        m = tiny_model(seed=4)
        prompt = np.random.default_rng(4).integers(0, 97, 5).astype(
            np.int32)
        ref = np.asarray(m.generate(P.to_tensor(prompt[None]),
                                    max_new_tokens=8)._data)[0]
        eos = int(ref[2])  # force a stop at the 3rd generated token
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=2,
                            prefill_chunk=8, eos_token_id=eos)
        rid = eng.add_request(prompt, max_new_tokens=8)
        res = eng.run()
        assert res[rid]["finish_reason"] == "stop"
        np.testing.assert_array_equal(res[rid]["tokens"], ref[:3])

    def test_fork_copy_on_write_sampling(self):
        m = tiny_model(seed=5)
        prompt = np.random.default_rng(5).integers(0, 97, 6).astype(
            np.int32)
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=8,
                            prefill_chunk=8)
        rid = eng.add_request(prompt, max_new_tokens=5, do_sample=True,
                              seed=7, n=3)
        res = eng.run()
        assert len(res) == 3  # parent + 2 forks
        streams = [tuple(v["tokens"]) for v in res.values()]
        assert all(len(s) == 5 for s in streams)
        assert len(set(streams)) > 1  # independent samples
        assert eng.metrics.cow_copies.value > 0  # CoW exercised
        assert eng.cache.free_pages == eng.cache.allocatable_pages
        with pytest.raises(ValueError, match="do_sample"):
            eng.add_request(prompt, max_new_tokens=2, n=2)

    def test_weight_update_flows_through_arguments(self):
        """Weights enter the compiled step as ARGUMENTS: an in-place
        update must be visible with no cache invalidation."""
        m = tiny_model(seed=6)
        prompt = np.random.default_rng(6).integers(0, 97, 5).astype(
            np.int32)
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=2,
                            prefill_chunk=8)
        r1 = eng.add_request(prompt, max_new_tokens=4)
        eng.run()
        w = m.lm_head.weight
        w._inplace_update(w._data + 0.5)
        r2 = eng.add_request(prompt, max_new_tokens=4)
        res = eng.run()
        want = np.asarray(m.generate(P.to_tensor(prompt[None]),
                                     max_new_tokens=4)._data)[0]
        np.testing.assert_array_equal(res[r2]["tokens"], want)

    def test_run_failure_releases_pages_and_engine_is_reusable(self):
        """Regression (round 9): a run() that raises used to leave the
        live requests' pages committed — the failure path must release
        them (requeue for recompute) so the engine survives the error
        and a retry reproduces the uninterrupted stream."""
        m = tiny_model(seed=9)
        prompt = np.random.default_rng(9).integers(0, 97, 9).astype(
            np.int32)
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=2,
                            prefill_chunk=4)
        rid = eng.add_request(prompt, max_new_tokens=8)
        with pytest.raises(RuntimeError, match="did not drain"):
            eng.run(max_steps=2)
        # pages released, request requeued — allocator is clean
        assert eng.cache.free_pages == eng.cache.allocatable_pages
        assert not eng.cache.live_seqs()
        # reusable: the retry recomputes and matches the oracle exactly
        res = eng.run()
        want = np.asarray(m.generate(P.to_tensor(prompt[None]),
                                     max_new_tokens=8)._data)[0]
        np.testing.assert_array_equal(res[rid]["tokens"], want)
        assert res[rid]["preemptions"] >= 1

    def test_release_live_frees_waiting_requests_prefix_pins(self):
        """Regression (round-20 chaos fuzz): a request still in the
        WAITING queue already pins its matched prefix — add_request
        acquires before the request is ever scheduled — so a loop
        failure landing between admit and first schedule used to leak
        those pins forever (pages neither free nor reclaimable after
        drain). release_live must free waiting seqs too; _admit
        re-matches the prefix on admission."""
        m = tiny_model(seed=11)
        prompt = np.arange(1, 13, dtype=np.int32)
        eng = ServingEngine(m, page_size=4, num_pages=32, max_batch=2,
                            prefill_chunk=8, prefix_cache=True)
        rid0 = eng.add_request(prompt, max_new_tokens=2)
        want = eng.run()[rid0]["tokens"]
        assert eng.cache.cached_pages > 0  # prefix committed rc==0
        # the second request sits in WAITING with the prefix pinned
        rid1 = eng.add_request(prompt, max_new_tokens=2)
        assert eng.cache.available_pages < eng.cache.allocatable_pages
        eng.release_live()
        assert eng.cache.available_pages == eng.cache.allocatable_pages
        # the request survives: admission re-matches and the retry is
        # token-exact vs the uninterrupted stream
        res = eng.run()
        np.testing.assert_array_equal(res[rid1]["tokens"], want)

    def test_cancel_mid_decode_frees_pages_and_purges_queues(self):
        m = tiny_model(seed=10)
        rng = np.random.default_rng(10)
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=4,
                            prefill_chunk=8)
        keep = eng.add_request(rng.integers(0, 97, 5).astype(np.int32),
                               max_new_tokens=6)
        kill = eng.add_request(rng.integers(0, 97, 5).astype(np.int32),
                               max_new_tokens=20)
        events = []
        while not any(e["type"] == "token" and e["req_id"] == kill
                      for e in events):
            events += eng.step()
        kill_req = eng.request(kill)
        assert eng.cancel(kill) is True
        assert eng.cancel(kill) is False       # already finished
        assert eng.cancel(987654) is False     # unknown id
        assert not eng.cache.has_seq(kill)     # pages returned
        assert kill_req not in eng.scheduler.running
        assert kill_req not in eng.scheduler._admit_order
        res = eng.run()                        # the other request rides on
        assert res[kill]["finish_reason"] == "cancelled"
        assert 0 < len(res[kill]["tokens"]) < 20
        want = np.asarray(m.generate(
            P.to_tensor(eng.request(keep).prompt[None]),
            max_new_tokens=6)._data)[0]
        np.testing.assert_array_equal(res[keep]["tokens"], want)
        assert eng.metrics.cancellations.value == 1
        assert eng.cache.free_pages == eng.cache.allocatable_pages

    def test_drain_rejects_admissions_finishes_inflight(self):
        m = tiny_model(seed=11)
        rng = np.random.default_rng(11)
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=4,
                            prefill_chunk=8)
        r1 = eng.add_request(rng.integers(0, 97, 4).astype(np.int32),
                             max_new_tokens=5)
        assert not eng.draining
        eng.start_drain()
        assert eng.draining
        with pytest.raises(EngineDraining):
            eng.add_request(rng.integers(0, 97, 4).astype(np.int32))
        res = eng.run()
        assert res[r1]["finish_reason"] == "length"
        assert len(res[r1]["tokens"]) == 5
        assert eng.scheduler.all_done()

    def test_fault_injection_env_knobs(self, monkeypatch):
        m = tiny_model(seed=12)
        prompt = np.random.default_rng(12).integers(0, 97, 5).astype(
            np.int32)
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=2,
                            prefill_chunk=8)
        rid = eng.add_request(prompt, max_new_tokens=4)
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_ERROR_RATE", "1.0")
        with pytest.raises(FaultInjected):
            eng.step()
        assert eng.metrics.faults_injected.value == 1
        monkeypatch.delenv("PADDLE_TPU_SERVING_FAULT_ERROR_RATE")
        # the fault fired at the boundary: nothing was mutated, the
        # retried run matches the oracle exactly
        res = eng.run()
        want = np.asarray(m.generate(P.to_tensor(prompt[None]),
                                     max_new_tokens=4)._data)[0]
        np.testing.assert_array_equal(res[rid]["tokens"], want)

    def test_on_event_streams_every_event(self):
        m = tiny_model(seed=13)
        prompt = np.random.default_rng(13).integers(0, 97, 5).astype(
            np.int32)
        streamed = []
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=2,
                            prefill_chunk=8,
                            on_event=streamed.append)
        eng.add_request(prompt, max_new_tokens=4)
        collected = []
        while not eng.scheduler.all_done():
            collected += eng.step()
        assert streamed == collected  # callback sees the same events
        assert [e["type"] for e in streamed] == \
            ["token"] * 4 + ["finish"]

    def test_guards(self):
        m = tiny_model(seed=7)
        eng = ServingEngine(m, page_size=4, num_pages=64, max_batch=2,
                            prefill_chunk=8)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.add_request(np.zeros(60, np.int32), max_new_tokens=10)
        with pytest.raises(ValueError, match="empty"):
            eng.add_request(np.zeros(0, np.int32))
        # a request that can NEVER fit the pool fails loudly, not spins
        small = ServingEngine(m, page_size=4, num_pages=3, max_batch=2,
                              prefill_chunk=8)
        small.add_request(np.zeros(20, np.int32), max_new_tokens=4)
        with pytest.raises(RuntimeError, match="never be admitted"):
            small.run()


# ---------------------------------------------------------------------------
# round-7 sweep rule: every new public surface registered


class TestServingSweep:
    """test_serving_sweep: the subsystem's public surface (round-7 rule:
    new API surfaces get a sweep in the same commit)."""

    def test_namespace_surface(self):
        import paddle_tpu
        import paddle_tpu.serving as sv
        assert paddle_tpu.serving is sv
        for name in sv.__all__:
            assert getattr(sv, name) is not None, name
        # the subsystem layers + bench driver exist as modules
        import paddle_tpu.serving.attention  # noqa: F401
        import paddle_tpu.serving.engine  # noqa: F401
        import paddle_tpu.serving.frontend  # noqa: F401
        import paddle_tpu.serving.kv_cache  # noqa: F401
        import paddle_tpu.serving.metrics  # noqa: F401
        import paddle_tpu.serving.scheduler  # noqa: F401
        import paddle_tpu.serving.server  # noqa: F401
        for name in ("ServingFrontend", "ServingServer", "RequestStream",
                     "Rejected", "Unavailable", "EngineDraining",
                     "FaultInjected", "Gauge"):
            assert name in sv.__all__, name
        # round-21 deploy/distill subsystem surface
        import paddle_tpu.serving.deploy  # noqa: F401
        import paddle_tpu.serving.distill  # noqa: F401
        for name in ("WeightRegistry", "RollingDeployer", "DeployError",
                     "snapshot_weights", "DistillBuffer",
                     "DraftDistiller", "distill_buffer_from_env"):
            assert name in sv.__all__, name
        # round-22: the step's token-packed attention entry
        assert "ragged_paged_attention" in sv.__all__
        # round-23 tensor-parallel surface
        import paddle_tpu.serving.tp  # noqa: F401
        for name in ("TPContext", "resolve_tp", "TP_AXIS"):
            assert name in sv.__all__, name

    def test_deploy_surface(self):
        from paddle_tpu.serving import (DraftDistiller, DistillBuffer,
                                        RollingDeployer, WeightRegistry)
        for attr in ("publish", "latest", "versions", "get", "spill",
                     "drop", "stats"):
            assert hasattr(WeightRegistry, attr), attr
        for attr in ("rollout", "rollback", "sync_replica", "replicas"):
            assert hasattr(RollingDeployer, attr), attr
        for attr in ("log", "snapshot", "stats"):
            assert hasattr(DistillBuffer, attr), attr
        for attr in ("train_once", "push", "run_background", "stop"):
            assert hasattr(DraftDistiller, attr), attr
        # the locked swap chain exists end to end (graftlint
        # weight-swap-lock polices that these stay the ONLY doors)
        from paddle_tpu.serving import (InProcessReplica, HTTPReplica,
                                        ServingFrontend, ServingEngine)
        for cls in (InProcessReplica, HTTPReplica, ServingFrontend):
            assert hasattr(cls, "swap_weights"), cls
            assert hasattr(cls, "weight_version"), cls
        assert hasattr(ServingEngine, "set_weights")

    def test_engine_surface(self):
        m = tiny_model(seed=8)
        eng = ServingEngine(m, page_size=4, num_pages=32, max_batch=2,
                            prefill_chunk=8)
        for attr in ("add_request", "step", "run", "results", "metrics",
                     "cache", "scheduler", "cancel", "drain",
                     "start_drain", "draining", "release_live",
                     "on_event", "request", "draft", "spec_k",
                     "tp_degree", "tp_mesh_shape"):
            assert hasattr(eng, attr), attr
        # one step: the switch is gone with the path it selected
        assert not hasattr(eng, "ragged")
        # TP off by default: degree 1, no mesh advertised
        assert eng.tp_degree == 1 and eng.tp_mesh_shape is None

    def test_frontend_server_surface(self):
        from paddle_tpu.serving import ServingFrontend, ServingServer
        for attr in ("start", "submit", "cancel", "drain", "close",
                     "health", "prometheus", "state"):
            assert hasattr(ServingFrontend, attr), attr
        for attr in ("start", "drain", "close", "cancel", "url"):
            assert hasattr(ServingServer, attr), attr
        from paddle_tpu.serving import RequestStream
        for attr in ("events", "result", "all_ids", "done"):
            assert hasattr(RequestStream, attr), attr

    def test_metrics_export_schema(self):
        mt = ServingMetrics()
        mt.ttft_s.record(0.1)
        mt.preemptions.inc()
        ex = mt.export()
        for key in ("ttft_s", "inter_token_s", "step_duration_s",
                    "queue_depth",
                    "batch_size", "page_occupancy", "prefill_chunks",
                    "decode_steps", "tokens_generated",
                    "requests_finished", "preemptions",
                    "deadline_evictions", "cow_copies",
                    "cancellations", "rejections", "faults_injected",
                    "fetch_bytes", "step_dispatches", "step_fetches",
                    "step_program_classes", "prefix_hit_pages",
                    "prefix_miss_pages", "prefix_evictions",
                    "queue_depth_gauge", "page_occupancy_gauge",
                    "running_gauge", "prefix_hit_rate",
                    "cached_pages_gauge", "spec_rounds",
                    "spec_draft_tokens", "spec_accepted_tokens",
                    "spec_fallbacks", "spec_acceptance_rate",
                    "kv_page_bytes",
                    # round-21 deploy/distill families
                    "weight_swaps", "weight_swap_rejects",
                    "weight_swap_s", "weight_version_target",
                    "weight_version_draft", "distill_pairs"):
            assert key in ex, key
        assert ex["ttft_s"]["p50"] == pytest.approx(0.1)
        import json
        json.loads(mt.to_json(extra=1))

    def test_metrics_prometheus_exposition(self):
        mt = ServingMetrics()
        text = mt.to_prometheus()  # EMPTY metrics must still render
        assert "# TYPE paddle_tpu_serving_tokens_generated counter" \
            in text
        assert "# TYPE paddle_tpu_serving_running_gauge gauge" in text
        assert "paddle_tpu_serving_ttft_s_count 0" in text
        assert "quantile" not in text  # no samples -> no quantile rows
        # TTFT/TPOT are REAL histograms (round 11): cumulative buckets
        # render even when empty (all zero)
        assert "# TYPE paddle_tpu_serving_ttft_s histogram" in text
        assert 'paddle_tpu_serving_ttft_s_bucket{le="+Inf"} 0' in text
        mt.ttft_s.record(0.25)
        mt.batch_size.record(4)
        mt.queue_depth_gauge.set(3)
        text = mt.to_prometheus()
        # cumulative _bucket lines: 0.25 lands in le=0.25 (inclusive)
        # and every wider bucket
        assert 'paddle_tpu_serving_ttft_s_bucket{le="0.1"} 0' in text
        assert 'paddle_tpu_serving_ttft_s_bucket{le="0.25"} 1' in text
        assert 'paddle_tpu_serving_ttft_s_bucket{le="0.5"} 1' in text
        assert 'paddle_tpu_serving_ttft_s_bucket{le="+Inf"} 1' in text
        assert "paddle_tpu_serving_ttft_s_sum 0.25" in text
        # bucket-less histograms stay summaries with quantile rows
        assert "# TYPE paddle_tpu_serving_batch_size summary" in text
        assert 'paddle_tpu_serving_batch_size{quantile="0.5"} 4.0' \
            in text
        assert "paddle_tpu_serving_queue_depth_gauge 3.0" in text
        # round-16 observability families: step duration is a REAL
        # latency histogram, queue depth a count-bucketed one (both
        # must stay aggregatable across the router's merged /metrics)
        assert "# TYPE paddle_tpu_serving_step_duration_s histogram" \
            in text
        assert "# TYPE paddle_tpu_serving_queue_depth histogram" in text
        mt.step_duration_s.record(0.004)
        mt.queue_depth.record(3)
        text = mt.to_prometheus()
        assert ('paddle_tpu_serving_step_duration_s_bucket'
                '{le="0.005"} 1') in text
        assert 'paddle_tpu_serving_queue_depth_bucket{le="4"} 1' in text
        assert 'paddle_tpu_serving_queue_depth_bucket{le="2"} 0' in text

    def test_histogram_percentiles(self):
        from paddle_tpu.serving import Histogram
        # regression (round 9): empty histogram percentile is None, not
        # a numpy raise — /metrics scrapes happen before traffic
        h = Histogram()
        assert h.percentile(50) is None
        assert h.export()["p99"] is None
        for v in range(100):
            h.record(v)
        assert h.percentile(50) == pytest.approx(49.5)
        ex = h.export()
        assert ex["count"] == 100 and ex["max"] == 99
        assert h.total == pytest.approx(sum(range(100)))

    def test_env_knobs_documented(self):
        """Every serving env knob stays documented in docs/SERVING.md."""
        doc = open(os.path.join(os.path.dirname(__file__), "..",
                                "docs", "SERVING.md")).read()
        for knob in ("PADDLE_TPU_PAGED_KERNEL",
                     "PADDLE_TPU_SERVING_FAULT_LATENCY_S",
                     "PADDLE_TPU_SERVING_FAULT_ERROR_RATE",
                     "PADDLE_TPU_SERVING_FAULT_SEED",
                     "PADDLE_TPU_SERVING_HOST_SAMPLE",
                     "PADDLE_TPU_SERVING_PREFIX_CACHE",
                     "PADDLE_TPU_SERVING_PROBE_S",
                     # round-21 deploy/distill knobs
                     "PADDLE_TPU_SERVING_DEPLOY_DIR",
                     "PADDLE_TPU_SERVING_DEPLOY_DRAIN_S",
                     "PADDLE_TPU_SERVING_DISTILL",
                     "PADDLE_TPU_SERVING_DISTILL_BUFFER",
                     "PADDLE_TPU_SERVING_DISTILL_HIST"):
            assert knob in doc, knob


@pytest.mark.slow
class TestServingReplay:
    def test_bench_serving_smoke_subprocess(self):
        """End-to-end Poisson replay through the repo-root driver
        (slow: excluded from tier-1; tools/serving_smoke.sh runs
        it)."""
        import json
        import subprocess
        import sys
        root = os.path.join(os.path.dirname(__file__), "..")
        p = subprocess.run(
            [sys.executable, "bench_serving.py", "--smoke"],
            cwd=root, capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        out = json.loads(p.stdout.strip().splitlines()[-1])
        assert out["metric"].startswith("serving_tok_per_s")
        assert out["value"] > 0
        assert out["ttft_p50_s"] is not None
