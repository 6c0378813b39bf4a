"""Unified ragged paged-attention step (round 22).

One token-packed program class for mixed prefill+decode+verify batches:
``ragged_paged_attention`` packs every lane's query tokens into a [T]
axis with per-lane ``(query_len, context_len)`` metadata, and the
engine's step rides a prefill chunk, the decode batch, and speculative
verify slots on ONE dispatch + ONE host fetch per step.

Oracle discipline (SURVEY.md §4): the ragged entry is pinned per-lane to
``paged_attention_ref`` (the gather oracle that is itself pinned to the
dense oracle and the contiguous cache), fp and int8 (tolerance at 1e-2
of the K/V VALUE range, round-15 addenda); the interpret-mode Pallas
kernel is pinned to the ragged reference INCLUDING the exact bench
shape (interpret mode only: the chip's compiler refuses the kernel as
written, tests/test_aot_tpu_compile.py records it).
Engine exactness is the hard gate: greedy streams are those of
``model.generate()`` one request at a time, and every stream, greedy or
seeded, is the one its request gets served alone (tokens and logprob
bits), in a crowd, under preemption, at any prefill chunk, and through
speculative rounds (self-draft accepts 100%).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (ServingEngine, paged_attention,
                                paged_attention_ref,
                                ragged_paged_attention)
from paddle_tpu.serving.attention import quantize_q8
from serving_utils import (sequential_oracle, serve_streams,
                           served_alone)


def tiny_model(seed=0, **kw):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


# ---------------------------------------------------------------------------
# ragged oracle: packed entry vs per-lane gather reference


def _ragged_case(lane_spec, nh=4, nkv=2, d=8, page_size=4, num_pages=64,
                 max_pages=8, pad_tokens=0, pad_lanes=0, seed=0):
    """Build a packed ragged case from ``lane_spec`` = [(context_len,
    query_len), ...].  Each lane's queries are its LAST ql positions
    (q_offset = cl - ql), K/V for all cl positions already scattered
    into randomly-ordered pages — exactly the engine's layout after
    append_slots.  Returns (packed q [T,H,D], pages, per-lane arrays,
    per-lane dense q list) with T = sum(ql) + pad_tokens."""
    rng = np.random.default_rng(seed)
    lanes = len(lane_spec) + pad_lanes
    kp = np.zeros((num_pages, page_size, nkv, d), np.float32)
    vp = np.zeros((num_pages, page_size, nkv, d), np.float32)
    free = list(rng.permutation(np.arange(1, num_pages)))
    pt = np.zeros((lanes, max_pages), np.int32)
    cl = np.ones(lanes, np.int32)       # padded lanes keep cl=1
    ql = np.zeros(lanes, np.int32)
    qoff = np.zeros(lanes, np.int32)
    q_rows, lane_q = [], []
    for i, (c, qn) in enumerate(lane_spec):
        assert qn <= c
        k = rng.standard_normal((c, nkv, d)).astype(np.float32)
        v = rng.standard_normal((c, nkv, d)).astype(np.float32)
        n_pages = -(-c // page_size)
        pages = [free.pop() for _ in range(n_pages)]
        pt[i, :n_pages] = pages
        for t in range(c):
            kp[pages[t // page_size], t % page_size] = k[t]
            vp[pages[t // page_size], t % page_size] = v[t]
        cl[i], ql[i], qoff[i] = c, qn, c - qn
        qi = rng.standard_normal((qn, nh, d)).astype(np.float32)
        q_rows.append(qi)
        lane_q.append(qi)
    if pad_tokens:
        q_rows.append(rng.standard_normal(
            (pad_tokens, nh, d)).astype(np.float32))
    q = np.concatenate(q_rows, axis=0)
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(pt), jnp.asarray(cl), jnp.asarray(ql),
            jnp.asarray(qoff), lane_q)


def _per_lane_ref(kp, vp, pt, cl, ql, qoff, lane_q, scale, window=None):
    """The oracle: each lane independently through paged_attention_ref
    at [1, ql], one rectangular call a lane."""
    outs = []
    for i, qi in enumerate(lane_q):
        o = paged_attention_ref(
            jnp.asarray(qi)[None], kp, vp, pt[i][None], cl[i][None],
            qoff[i][None], scale=scale, window=window)
        outs.append(np.asarray(o[0]))
    return np.concatenate(outs, axis=0)                    # [sum ql,H,D]


MIXED = [(17, 1), (3, 1), (9, 6), (20, 4), (5, 5), (12, 1)]
#         decode  decode  prefill verify  full-pf decode


class TestRaggedOracle:
    @pytest.mark.parametrize("nkv", [4, 2, 1])
    def test_mixed_lane_parity(self, nkv):
        q, kp, vp, pt, cl, ql, qoff, lane_q = _ragged_case(
            MIXED, nkv=nkv, seed=nkv)
        got = ragged_paged_attention(q, kp, vp, pt, cl, ql, qoff,
                                     scale=0.35)
        want = _per_lane_ref(kp, vp, pt, cl, ql, qoff, lane_q, 0.35)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)

    def test_sliding_window(self):
        q, kp, vp, pt, cl, ql, qoff, lane_q = _ragged_case(MIXED, seed=7)
        got = ragged_paged_attention(q, kp, vp, pt, cl, ql, qoff,
                                     scale=0.5, window=5)
        want = _per_lane_ref(kp, vp, pt, cl, ql, qoff, lane_q, 0.5,
                             window=5)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)

    def test_int8_pages_parity(self):
        """int8 (codes, scales) tuples ride the ragged entry unchanged;
        tolerance at 1e-2 of the K/V value RANGE (round-15: unit-normal
        V alone has ~1.2e-2 max dequant error at absolute scale)."""
        q, kp, vp, pt, cl, ql, qoff, lane_q = _ragged_case(MIXED, seed=9)
        k8, v8 = quantize_q8(kp), quantize_q8(vp)
        got = ragged_paged_attention(q, k8, v8, pt, cl, ql, qoff,
                                     scale=0.35)
        want = _per_lane_ref(k8, v8, pt, cl, ql, qoff, lane_q, 0.35)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
        # and vs the fp oracle within the recipe's intrinsic floor
        fp = _per_lane_ref(kp, vp, pt, cl, ql, qoff, lane_q, 0.35)
        span = float(np.ptp(np.asarray(vp)))
        np.testing.assert_allclose(np.asarray(got), fp,
                                   atol=1e-2 * span)

    def test_padding_rows_finite(self):
        """Padding tokens (beyond sum(query_lens)) and padded lanes
        (ql=0, cl=1, scratch pages) must stay NaN-free — the engine
        discards them but jnp.where grads/argmax must not poison."""
        q, kp, vp, pt, cl, ql, qoff, lane_q = _ragged_case(
            MIXED, pad_tokens=5, pad_lanes=2, seed=11)
        got = np.asarray(ragged_paged_attention(
            q, kp, vp, pt, cl, ql, qoff, scale=0.35, window=4))
        assert np.isfinite(got).all()
        n = sum(qn for _, qn in MIXED)
        want = _per_lane_ref(kp, vp, pt, cl, ql, qoff, lane_q, 0.35,
                             window=4)
        np.testing.assert_allclose(got[:n], want, atol=1e-5)


# ---------------------------------------------------------------------------
# unified Pallas kernel, interpret mode (CPU only)


class TestRaggedKernelInterpret:
    def test_kernel_mixed_parity(self, monkeypatch):
        q, kp, vp, pt, cl, ql, qoff, lane_q = _ragged_case(MIXED, seed=3)
        ref = ragged_paged_attention(q, kp, vp, pt, cl, ql, qoff,
                                     scale=0.35)
        monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
        got = ragged_paged_attention(q, kp, vp, pt, cl, ql, qoff,
                                     scale=0.35)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    def test_kernel_int8_and_window(self, monkeypatch):
        q, kp, vp, pt, cl, ql, qoff, lane_q = _ragged_case(MIXED, seed=4)
        k8, v8 = quantize_q8(kp), quantize_q8(vp)
        ref = ragged_paged_attention(q, k8, v8, pt, cl, ql, qoff,
                                     scale=0.5, window=6)
        monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
        got = ragged_paged_attention(q, k8, v8, pt, cl, ql, qoff,
                                     scale=0.5, window=6)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    def test_kernel_exact_bench_shape(self, monkeypatch):
        """Round-3b addenda: a small-shape smoke does NOT clear a
        kernel config — validate the EXACT shape the bench dispatches.
        bench_serving.py's engine geometry: 8 decode lanes + one
        32-token prefill chunk -> T=40 packed tokens, 9 lanes,
        page_size 16, 4 heads, head_dim 32."""
        spec = [(33 + 2 * i, 1) for i in range(8)] + [(48, 32)]
        q, kp, vp, pt, cl, ql, qoff, lane_q = _ragged_case(
            spec, nh=4, nkv=4, d=32, page_size=16, num_pages=48,
            max_pages=7, seed=5)
        assert q.shape[0] == 40
        ref = ragged_paged_attention(q, kp, vp, pt, cl, ql, qoff,
                                     scale=32 ** -0.5)
        monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
        got = ragged_paged_attention(q, kp, vp, pt, cl, ql, qoff,
                                     scale=32 ** -0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    def test_rectangular_routes_through_ragged_kernel(self, monkeypatch):
        """Satellite: the decode-only stub is GONE — rectangular [B,S]
        calls (including S>1 prefill chunks, which the old stub
        asserted away) expand through the same unified kernel."""
        rng = np.random.default_rng(6)
        lens = [9]
        spec = [(9, 6)]
        q, kp, vp, pt, cl, ql, qoff, lane_q = _ragged_case(spec, seed=6)
        args = (jnp.asarray(lane_q[0])[None], kp, vp, pt,
                jnp.asarray(lens, jnp.int32), qoff[:1])
        ref = paged_attention_ref(*args, scale=0.5)
        monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
        got = paged_attention(*args, scale=0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# engine: the step's streams against oracles that share none of its code
# (greedy: model.generate() one at a time) or none of its schedule
# (seeded: the same request served alone)

ENG_KW = dict(page_size=4, num_pages=200, max_batch=4, prefill_chunk=8)


def run_fleet(m, prompts, req_kws, max_new=6, **ekw):
    eng = ServingEngine(m, **{**ENG_KW, **ekw})
    rids = [eng.add_request(p, max_new_tokens=max_new, **r)
            for p, r in zip(prompts, req_kws)]
    res = eng.run()
    return [list(map(int, res[r]["tokens"])) for r in rids], eng


def crowd(m, prompts, req_kws, max_new, **ekw):
    eng = ServingEngine(m, **{**ENG_KW, **ekw})
    return serve_streams(eng, prompts, req_kws, max_new), eng


def assert_greedy_is_generate(m, prompts, req_kws, got, max_new):
    rows = [i for i, kw in enumerate(req_kws) if not kw.get("do_sample")]
    want = sequential_oracle(m, [prompts[i] for i in rows], max_new)
    for i, w in zip(rows, want):
        assert got[i][0] == list(map(int, w)), i


MIXED_REQ = [dict(), dict(do_sample=True, temperature=0.9, seed=7),
             dict(do_sample=True, top_k=5, seed=3), dict(),
             dict(do_sample=True, top_p=0.8, seed=11), dict()]


class TestRaggedEngine:
    def test_token_exactness_greedy_and_seeded(self):
        m = tiny_model()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 97, int(rng.integers(3, 14)))
                   .astype(np.int32) for _ in range(6)]
        got, eng = crowd(m, prompts, MIXED_REQ, 6)
        assert got == served_alone(m, prompts, MIXED_REQ, 6)
        assert_greedy_is_generate(m, prompts, MIXED_REQ, got, 6)
        assert eng.metrics.step_program_classes.value <= 2, \
            eng._program_classes

    def test_token_exactness_under_preemption(self):
        """Page pressure preempts mid-decode AND the prefill-lane
        allocation itself can preempt staged decode lanes; recompute
        must replay every stream token-exactly (schedule independence:
        token t is pure in (weights, history, seed, t))."""
        m = tiny_model(seed=1)
        prompts = [np.random.default_rng(1).integers(0, 97, 3)
                   .astype(np.int32) for _ in range(4)]
        kws = [dict(), dict(do_sample=True, top_k=7, seed=2), dict(),
               dict(do_sample=True, temperature=1.2, seed=9)]
        got, eng = crowd(m, prompts, kws, 12, num_pages=10)
        assert eng.metrics.preemptions.value > 0, \
            "config failed to force preemption"
        assert got == served_alone(m, prompts, kws, 12)
        assert_greedy_is_generate(m, prompts, kws, got, 12)

    def test_prefill_chunk_invariance(self):
        m = tiny_model(seed=2)
        prompt = np.random.default_rng(2).integers(0, 97, 11).astype(
            np.int32)
        outs = []
        for chunk in (2, 5, 16):
            got, _ = run_fleet(m, [prompt], [dict()], max_new=6,
                               prefill_chunk=chunk)
            outs.append(got[0])
        assert outs[0] == outs[1] == outs[2]

    def test_speculative_self_draft_exact_full_acceptance(self):
        """Verify slots ride the same dispatch; deterministic-sample
        matching means a self-draft must accept 100% and every stream
        is the one its request gets served alone with no draft."""
        m = tiny_model(seed=2)
        prompts = [np.random.default_rng(2).integers(0, 97, 5)
                   .astype(np.int32) for _ in range(3)]
        kws = [dict(), dict(do_sample=True, seed=5), dict()]
        got, eng = crowd(m, prompts, kws, 8, draft_model=m,
                         speculative_k=3)
        assert got == served_alone(m, prompts, kws, 8)
        assert_greedy_is_generate(m, prompts, kws, got, 8)
        ex = eng.metrics.export()
        assert ex["spec_draft_tokens"] > 0
        assert ex["spec_accepted_tokens"] == ex["spec_draft_tokens"]
        assert ex["spec_acceptance_rate"] == 1.0
        # draft-model programs never count as step classes
        assert eng.metrics.step_program_classes.value <= 2, \
            eng._program_classes

    @pytest.mark.parametrize("mode", ["int8_kv", "prefix_cache",
                                      "sliding_window"])
    def test_modes_serve_the_lone_requests_streams(self, mode):
        """The modes the step carries, each under page pressure in a
        crowd of greedy and seeded lanes: quantize-on-append pools, a
        shared prefix served from the radix tree, a window that
        binds."""
        m = tiny_model(seed=3, **(dict(sliding_window=6)
                                  if mode == "sliding_window" else {}))
        rng = np.random.default_rng(3)
        shared = rng.integers(0, 97, 9).astype(np.int32)
        prompts = [np.concatenate([shared, rng.integers(
            0, 97, int(rng.integers(1, 9))).astype(np.int32)])
            for _ in range(5)]
        kws = MIXED_REQ[:5]
        ekw = dict(int8_kv=dict(cache_dtype="int8"),
                   prefix_cache=dict(prefix_cache=True),
                   sliding_window={})[mode]
        got, eng = crowd(m, prompts, kws, 16, num_pages=20, **ekw)
        assert eng.metrics.preemptions.value > 0
        if mode == "prefix_cache":
            assert eng.metrics.prefix_hit_pages.value > 0
            ekw = {}                 # alone, nothing to share it with
        assert got == served_alone(m, prompts, kws, 16, **ekw)
        assert eng.metrics.step_program_classes.value <= 2

    def test_mixed_step_one_dispatch_one_fetch(self):
        """The acceptance criterion, asserted by the metrics: a step
        carrying a prefill chunk AND decode lanes issues ONE dispatch +
        ONE host fetch (per-dispatch fixed cost ~0.79 of a small CPU
        step — FEASIBILITY.md), and a whole run compiles at most two
        program classes."""
        m = tiny_model()
        rng = np.random.default_rng(3)
        eng = ServingEngine(m, **ENG_KW)
        eng.add_request(rng.integers(0, 97, 4).astype(np.int32),
                        max_new_tokens=10)
        eng.step()                       # short prompt finishes prefill
        eng.add_request(rng.integers(0, 97, 30).astype(np.int32),
                        max_new_tokens=4)
        mixed = 0
        for _ in range(6):
            d0 = eng.metrics.step_dispatches.value
            f0 = eng.metrics.step_fetches.value
            eng.step()
            rec = [e for e in eng.trace.flight.dump()
                   if e.get("kind") == "ragged_step"][-1:]
            if rec and rec[0].get("prefill") is not None \
                    and rec[0].get("plain", 0) > 0:
                mixed += 1
                assert eng.metrics.step_dispatches.value - d0 == 1
                assert eng.metrics.step_fetches.value - f0 == 1
        assert mixed > 0, "no mixed prefill+decode step occurred"
        eng.run()
        ex = eng.metrics.export()
        assert ex["step_dispatches"] > 0
        assert ex["step_program_classes"] <= 2

    def test_the_keyword_selects_nothing(self):
        """``ragged=`` outlives the switch only because the benchmark's
        drivers pass it: True and absent build the same engine, False
        names the step that is gone."""
        m = tiny_model()
        with pytest.raises(ValueError, match="removed in PR 29"):
            ServingEngine(m, ragged=False, **ENG_KW)
        prompts = [np.arange(3, 12, dtype=np.int32)]
        kws = [dict(do_sample=True, top_p=0.9, seed=4)]
        a, ea = crowd(m, prompts, kws, 5)
        b, eb = crowd(m, prompts, kws, 5, ragged=True)
        assert a == b
        for attr in ("_ragged_lanes", "_ragged_tok_small",
                     "_ragged_tok_mixed"):
            assert getattr(ea, attr) == getattr(eb, attr), attr
        assert ea._program_classes == eb._program_classes
        assert not hasattr(ea, "ragged")
