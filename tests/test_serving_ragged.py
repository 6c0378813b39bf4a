"""Unified ragged paged-attention step (round 22).

One token-packed program class for mixed prefill+decode+verify batches:
``ragged_paged_attention`` packs every lane's query tokens into a [T]
axis with per-lane ``(query_len, context_len)`` metadata, and the
engine's step rides a prefill chunk, the decode batch, and speculative
verify slots on ONE dispatch + ONE host fetch per step.

Oracle discipline (SURVEY.md §4): the ragged entry (a rectangle of k1
rows a decode/verify lane, then the chunk's rows: a lane's page table
gathered once) is pinned on every live row to one row of
``paged_attention_ref`` a token (the gather oracle that is itself
pinned to the dense oracle and the contiguous cache), fp and int8
(tolerance at 1e-2 of the K/V VALUE range, round-15 addenda); the interpret-mode Pallas
kernel is pinned to the ragged reference INCLUDING the exact bench
shape (interpret mode only: the chip's compiler refuses the kernel as
written, tests/test_aot_tpu_compile.py records it).
Engine exactness is the hard gate: greedy streams are those of
``model.generate()`` one request at a time, and every stream, greedy or
seeded, is the one its request gets served alone (tokens and logprob
bits), in a crowd, under preemption, at any prefill chunk, and through
speculative rounds (self-draft accepts 100%); where a recompute or a
verify round runs a token through a product of another row count, the
tokens and the logprobs within ``CROSS_SHAPE_ULPS``.
"""
import functools
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import engine as eng_mod
from paddle_tpu.serving import (ServingEngine, paged_attention,
                                paged_attention_ref,
                                ragged_paged_attention)
from paddle_tpu.serving.attention import quantize_q8, tables_gathered
from serving_utils import (assert_streams_within_ulps, logprob_ulps,
                           sequential_oracle, serve_streams,
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
# ragged oracle: the regioned entry vs one gather-reference row a token


def _ragged_case(rect, chunk=None, chunk_cap=0, k1=1, nh=4, nkv=2, d=8,
                 page_size=4, num_pages=64, max_pages=8, seed=0):
    """Build a case in the step's layout. ``rect`` = one ``(context_len,
    query_len <= k1)`` a decode/verify lane, ``None`` for a dead lane
    (ql=0, cl=1, scratch pages); ``chunk`` = the last lane's
    ``(context_len, query_len <= chunk_cap)``, ``None`` with
    ``chunk_cap=0`` for the decode-only class. Each lane's queries are
    its LAST ql positions (q_offset = cl - ql), K/V for all cl
    positions already scattered into randomly-ordered pages — exactly
    the engine's layout after append_slots. Lane i's rows are
    ``[i*k1, i*k1 + ql)``, the chunk's ``[len(rect)*k1, ... + ql)``;
    every other row is padding (random q: garbage the caller
    discards). Returns ``(q [T,H,D], kp, vp, pt, cl, ql, qoff, rows)``
    with ``rows`` the ``(row, lane, position)`` of every live row."""
    rng = np.random.default_rng(seed)
    spec = list(rect) + [chunk]
    lanes = len(spec)
    t = len(rect) * k1 + chunk_cap
    kp = np.zeros((num_pages, page_size, nkv, d), np.float32)
    vp = np.zeros((num_pages, page_size, nkv, d), np.float32)
    free = list(rng.permutation(np.arange(1, num_pages)))
    pt = np.zeros((lanes, max_pages), np.int32)
    cl = np.ones(lanes, np.int32)       # padded lanes keep cl=1
    ql = np.zeros(lanes, np.int32)
    qoff = np.zeros(lanes, np.int32)
    q = rng.standard_normal((t, nh, d)).astype(np.float32)
    rows = []
    for i, lane in enumerate(spec):
        if lane is None:
            continue
        c, qn = lane
        assert qn <= (chunk_cap if i == len(rect) else k1) and qn <= c
        k = rng.standard_normal((c, nkv, d)).astype(np.float32)
        v = rng.standard_normal((c, nkv, d)).astype(np.float32)
        n_pages = -(-c // page_size)
        pages = [free.pop() for _ in range(n_pages)]
        pt[i, :n_pages] = pages
        for j in range(c):
            kp[pages[j // page_size], j % page_size] = k[j]
            vp[pages[j // page_size], j % page_size] = v[j]
        cl[i], ql[i], qoff[i] = c, qn, c - qn
        rows += [(i * k1 + j, i, c - qn + j) for j in range(qn)]
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(pt), jnp.asarray(cl), jnp.asarray(ql),
            jnp.asarray(qoff), rows)


def _per_token_ref(q, kp, vp, pt, cl, rows, scale, window=None):
    """The oracle: one ``paged_attention_ref`` row per live token, each
    with its own copy of its lane's page-table row (the per-token form
    the step ran before PR 30). Returns ``(row indices, [n, H, D])``."""
    row, lane, pos = (jnp.asarray(a, jnp.int32) for a in zip(*rows))
    o = paged_attention_ref(q[row][:, None], kp, vp, pt[lane], cl[lane],
                            pos, scale=scale, window=window)
    return np.asarray(row), np.asarray(o[:, 0])


def _assert_live_rows(got, q, kp, vp, pt, cl, rows, scale, window=None,
                      atol=0):
    """Live rows against the per-token oracle: to 0 (the same einsums
    over the same operands, gathered once), but for the shapes whose
    caller passes an ``atol``."""
    idx, want = _per_token_ref(q, kp, vp, pt, cl, rows, scale, window)
    np.testing.assert_allclose(np.asarray(got)[idx], want, rtol=0,
                               atol=atol)


# decode, decode, a dead lane in the middle, verify (full, part), decode;
# at k1 = 1 every lane is a one-token lane
def _rect(k1):
    return [(17, 1), (3, 1), None, (20, k1), (12, -(-k1 // 2)), (5, 1)]


CHUNK = dict(chunk=(9, 6), chunk_cap=8)        # a part-filled chunk


class TestRaggedOracle:
    @pytest.mark.parametrize("k1", [1, 3])
    @pytest.mark.parametrize("nkv", [4, 2, 1])
    def test_mixed_lane_parity(self, nkv, k1):
        q, kp, vp, pt, cl, ql, qoff, rows = _ragged_case(
            _rect(k1), k1=k1, nkv=nkv, seed=nkv, **CHUNK)
        got = ragged_paged_attention(q, kp, vp, pt, cl, ql, qoff,
                                     scale=0.35, k1=k1)
        assert got.shape == q.shape
        # GQA 4 over 2 reads 0.0; with one query row a KV head (MHA) or
        # one KV head (MQA) XLA:CPU sums the one-row product in another
        # order than the many-row one: 1.2e-7 to 4.2e-7
        _assert_live_rows(got, q, kp, vp, pt, cl, rows, 0.35,
                          atol=0 if nkv == 2 else 1e-6)

    @pytest.mark.parametrize("k1", [1, 3])
    def test_no_chunk(self, k1):
        """The decode class: T == (L - 1) * k1, the last lane owns no
        row and its table is not gathered."""
        q, kp, vp, pt, cl, ql, qoff, rows = _ragged_case(
            _rect(k1), k1=k1, seed=5)
        assert q.shape[0] == 6 * k1 and pt.shape[0] == 7
        got = ragged_paged_attention(q, kp, vp, pt, cl, ql, qoff,
                                     scale=0.35, k1=k1)
        _assert_live_rows(got, q, kp, vp, pt, cl, rows, 0.35)
        assert np.isfinite(np.asarray(got)).all()

    def test_full_chunk_alone(self):
        """A whole-prompt prefill with every decode lane dead."""
        q, kp, vp, pt, cl, ql, qoff, rows = _ragged_case(
            [None, None], chunk=(5, 5), chunk_cap=5, seed=8)
        got = ragged_paged_attention(q, kp, vp, pt, cl, ql, qoff,
                                     scale=0.35)
        _assert_live_rows(got, q, kp, vp, pt, cl, rows, 0.35)

    @pytest.mark.parametrize("k1", [1, 3])
    def test_sliding_window(self, k1):
        q, kp, vp, pt, cl, ql, qoff, rows = _ragged_case(
            _rect(k1), k1=k1, seed=7, **CHUNK)
        got = ragged_paged_attention(q, kp, vp, pt, cl, ql, qoff,
                                     scale=0.5, window=5, k1=k1)
        _assert_live_rows(got, q, kp, vp, pt, cl, rows, 0.5, window=5)

    def test_int8_pages_parity(self):
        """int8 (codes, scales) tuples ride the ragged entry unchanged;
        tolerance at 1e-2 of the K/V value RANGE (round-15: unit-normal
        V alone has ~1.2e-2 max dequant error at absolute scale)."""
        q, kp, vp, pt, cl, ql, qoff, rows = _ragged_case(
            _rect(3), k1=3, seed=9, **CHUNK)
        k8, v8 = quantize_q8(kp), quantize_q8(vp)
        got = ragged_paged_attention(q, k8, v8, pt, cl, ql, qoff,
                                     scale=0.35, k1=3)
        _assert_live_rows(got, q, k8, v8, pt, cl, rows, 0.35)
        # and vs the fp oracle within the recipe's intrinsic floor
        span = float(np.ptp(np.asarray(vp)))
        _assert_live_rows(got, q, kp, vp, pt, cl, rows, 0.35,
                          atol=1e-2 * span)

    @pytest.mark.parametrize("window", [None, 2, 4])
    def test_padding_rows_finite(self, window):
        """Padding rows (a dead lane's, a part-filled verify lane's and
        chunk's) come out zero, with or without a window — the engine
        discards them but jnp.where grads/argmax must not poison. A
        window narrower than a lane's unused rows leaves them no
        visible key: the all-masked softmax must not reach the
        output."""
        q, kp, vp, pt, cl, ql, qoff, rows = _ragged_case(
            _rect(3), k1=3, seed=11, **CHUNK)
        got = np.asarray(ragged_paged_attention(
            q, kp, vp, pt, cl, ql, qoff, scale=0.35, window=window,
            k1=3))
        live = np.zeros(got.shape[0], bool)
        live[[r for r, _, _ in rows]] = True
        assert live.sum() < got.shape[0] - 8     # most rows are padding
        assert (got[~live] == 0).all() and np.isfinite(got).all()
        _assert_live_rows(got, q, kp, vp, pt, cl, rows, 0.35,
                          window=window)

    @pytest.mark.parametrize("t, k1, want", [
        (6, 1, 6), (7, 1, 7), (14, 1, 7), (18, 3, 6), (26, 3, 7)])
    def test_tables_gathered(self, t, k1, want):
        """Seven lanes: a table a rectangle lane, one more where the
        chunk has rows. Fewer rows than the rectangle's are no
        layout."""
        assert tables_gathered(7, t, k1) == want
        with pytest.raises(ValueError):
            tables_gathered(7, 6 * k1 - 1, k1)

    def test_a_lanes_table_is_gathered_once(self):
        """The lowered step of the chunk-carrying class holds the
        rectangle's and the chunk's gathers, [lanes, pages, ...], and
        no [T, pages, ...] one; XLA:CPU's temporaries of the attention
        call are under a quarter of the per-token form's."""
        from serving_utils import ragged_step_avals
        eng = ServingEngine(tiny_model(), **ENG_KW)
        t, mp = eng._ragged_tok_mixed, eng.max_pages_per_seq
        text = eng._step_program().lower(
            *ragged_step_avals(eng, t)).as_text()
        assert t == 12 and f"tensor<4x{mp}x4x4x8xf32>" in text
        assert f"tensor<1x{mp}x4x4x8xf32>" in text
        assert f"tensor<{t}x{mp}x" not in text
        # and the engine's counter counts those tables, from the same
        # regions the call is cut by
        assert tables_gathered(eng._ragged_lanes, t) == 4 + 1

        q, kp, vp, pt, cl, ql, qoff, rows = _ragged_case(
            [(17, 1)] * 8, chunk=(40, 32), chunk_cap=32, nh=4, nkv=4,
            d=32, page_size=16, num_pages=48, max_pages=7, seed=5)

        def compiled(fn):
            return jax.jit(fn).lower(q, pt, cl, ql, qoff).compile()

        def per_token(q, pt, cl, ql, qoff):
            lane = jnp.minimum(jnp.arange(40), 8)
            return paged_attention_ref(
                q[:, None], kp, vp, pt[lane], cl[lane], qoff[lane],
                scale=0.2)

        def by_lane(q, pt, cl, ql, qoff):
            return ragged_paged_attention(q, kp, vp, pt, cl, ql, qoff,
                                          scale=0.2)

        old, new = compiled(per_token), compiled(by_lane)
        assert "[280," in old.as_text()         # T x pages = 40 x 7
        assert "[280," not in new.as_text()
        assert (new.memory_analysis().temp_size_in_bytes * 4
                < old.memory_analysis().temp_size_in_bytes)


# ---------------------------------------------------------------------------
# unified Pallas kernel, interpret mode (CPU only)


class TestRaggedKernelInterpret:
    @pytest.mark.parametrize("k1", [1, 3])
    def test_kernel_mixed_parity(self, monkeypatch, k1):
        q, kp, vp, pt, cl, ql, qoff, rows = _ragged_case(
            _rect(k1), k1=k1, seed=3, **CHUNK)
        ref = ragged_paged_attention(q, kp, vp, pt, cl, ql, qoff,
                                     scale=0.35, k1=k1)
        monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
        got = ragged_paged_attention(q, kp, vp, pt, cl, ql, qoff,
                                     scale=0.35, k1=k1)
        live = [r for r, _, _ in rows]
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(ref)[live], atol=1e-5)
        assert np.isfinite(np.asarray(got)).all()

    def test_kernel_int8_and_window(self, monkeypatch):
        q, kp, vp, pt, cl, ql, qoff, rows = _ragged_case(
            _rect(3), k1=3, seed=4, **CHUNK)
        k8, v8 = quantize_q8(kp), quantize_q8(vp)
        ref = ragged_paged_attention(q, k8, v8, pt, cl, ql, qoff,
                                     scale=0.5, window=6, k1=3)
        monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
        got = ragged_paged_attention(q, k8, v8, pt, cl, ql, qoff,
                                     scale=0.5, window=6, k1=3)
        live = [r for r, _, _ in rows]
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(ref)[live], atol=1e-5)

    def test_kernel_exact_bench_shape(self, monkeypatch):
        """Round-3b addenda: a small-shape smoke does NOT clear a
        kernel config — validate the EXACT shape the bench dispatches.
        bench_serving.py's engine geometry: 8 decode lanes + one
        32-token prefill chunk -> T=40 packed tokens, 9 lanes,
        page_size 16, 4 heads, head_dim 32."""
        q, kp, vp, pt, cl, ql, qoff, rows = _ragged_case(
            [(33 + 2 * i, 1) for i in range(8)], chunk=(48, 32),
            chunk_cap=32, nh=4, nkv=4, d=32, page_size=16,
            num_pages=48, max_pages=7, seed=5)
        assert q.shape[0] == 40 and len(rows) == 40
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
        q, kp, vp, pt, cl, ql, qoff, rows = _ragged_case(
            [], chunk=(9, 6), chunk_cap=6, seed=6)
        args = (q[None], kp, vp, pt, cl, qoff)
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


def assert_same_but_for_recompute(eng, got, alone):
    """A crowd under page pressure against the lone requests: a request
    nothing preempted kept every product's row count and has the lone
    request's tokens and logprob bits; one that was recomputed has its
    tokens, and its logprobs within ``CROSS_SHAPE_ULPS``."""
    res = eng.results()
    preempted = [res[r]["preemptions"] for r in sorted(res)]
    assert len(preempted) == len(got) and any(preempted)
    for i, n in enumerate(preempted):
        if n:
            assert_streams_within_ulps([got[i]], [alone[i]],
                                       CROSS_SHAPE_ULPS)
        else:
            assert got[i] == alone[i], i


def assert_greedy_is_generate(m, prompts, req_kws, got, max_new):
    rows = [i for i, kw in enumerate(req_kws) if not kw.get("do_sample")]
    want = sequential_oracle(m, [prompts[i] for i in rows], max_new)
    for i, w in zip(rows, want):
        assert got[i][0] == list(map(int, w)), i


# A token's logprob bits hold across schedules as long as its products
# keep their row counts: a decode row of the [max_batch, k1] rectangle,
# a prompt row of the [1, chunk] call (the crowd against the lone
# request: ``==``). A preemption recomputes decoded tokens as chunk rows
# and a verify round runs them k1 rows a lane, and XLA:CPU sums a
# one-row product in another order than a many-row one. Readings (this
# tree, the cases below): preemption 2 ulps, speculative 3, int8 pools
# 8, prefix cache 2, window 3; a row that attends from one position
# early reads 285,271 or flips a token
# (test_a_one_row_fault_breaks_the_ulp_allowance).
CROSS_SHAPE_ULPS = 32

MIXED_REQ = [dict(), dict(do_sample=True, temperature=0.9, seed=7),
             dict(do_sample=True, top_k=5, seed=3), dict(),
             dict(do_sample=True, top_p=0.8, seed=11), dict()]


# -- the step owns its pools (PR 34): each case builds an engine and
# drives it to a comparable result; ``undonated`` takes donation out

def undonated(eng):
    """The engine's programs as plain jits of the same pure functions:
    what the step was before it was handed its pools for good."""
    eng._ragged_fn = jax.jit(functools.partial(
        eng_mod._ragged_step_pure, eng.model, eng._core, eng.window,
        eng._tp, k1=eng.spec_k + 1))
    if eng.draft is not None:
        eng._draft_fn = jax.jit(functools.partial(
            eng_mod._draft_catchup_pure, eng.draft, eng._draft_core,
            eng._draft_window))
        eng._propose_fn = jax.jit(functools.partial(
            eng_mod._spec_draft_pure, eng.draft, eng._draft_core,
            eng._draft_window), static_argnums=(0,))
    return eng


def _llama_case(max_new=8, n_prompts=5, shared=0, seed=3, draft_seed=None,
                **ekw):
    def build():
        kw = {**ENG_KW, **ekw}
        if draft_seed is not None:    # its own weights: some rounds reject
            kw["draft_model"] = tiny_model(seed=draft_seed)
        return ServingEngine(tiny_model(seed=seed), **kw)

    def drive(eng):
        rng = np.random.default_rng(seed)
        head = rng.integers(0, 97, shared).astype(np.int32)
        prompts = [np.concatenate([head, rng.integers(
            0, 97, int(rng.integers(2, 11))).astype(np.int32)])
            for _ in range(n_prompts)]
        return serve_streams(eng, prompts, MIXED_REQ[:n_prompts], max_new)
    return build, drive


def _latent_case():
    def build():
        from benchmark.harness import weights_latent_moe as W
        from paddle_tpu.models import (LatentMoEConfig,
                                       LatentMoEForCausalLM)
        from test_latent_moe import CFG, ENGINE, place
        with P.LazyGuard():
            model = LatentMoEForCausalLM(
                LatentMoEConfig.from_published(CFG))
        place(model, W.make(11, CFG), W.program_names(CFG))
        model.eval()
        return ServingEngine(model, eos_token_id=None, **ENGINE)

    def drive(eng):
        assert eng.cache.latent and not eng.cache.v_pages
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 320, n).astype(np.int32)
                   for n in (11, 5, 19)]
        return serve_streams(eng, prompts, MIXED_REQ[:3], 6)
    return build, drive


def _mixed_case():
    def build():
        import test_sambay
        model, _ = test_sambay.build()
        return ServingEngine(model, ragged=True, **test_sambay.ENGINE)

    def drive(eng):
        from test_sambay import prompt
        assert eng.cache.mixed and eng.cache.w_pages \
            and eng.cache.lane_state
        prompts = [prompt(n, seed=n) for n in (19, 11, 27)]
        return serve_streams(eng, prompts, MIXED_REQ[:3], 6)
    return build, drive


def _fork_case():
    """n = 3: the children share the prompt's pages and the first
    append into the shared tail page copies it (``apply_copies``, eager,
    between the steps, on the pools the last step gave back)."""
    build, _ = _llama_case()

    def drive(eng):
        events = []
        eng.on_event = events.append
        eng.add_request(np.arange(5, 16, dtype=np.int32), max_new_tokens=7,
                        do_sample=True, temperature=0.9, seed=5, n=3,
                        logprobs=True)
        eng.run()
        assert eng.metrics.cow_copies.value > 0
        streams = {}
        for ev in events:
            if ev["type"] == "token":
                streams.setdefault(ev["req_id"], []).append(
                    (int(ev["token"]), np.float32(
                        ev["logprob"]).view(np.uint32).item()))
        assert len(streams) == 3
        return [streams[r] for r in sorted(streams)]
    return build, drive


def _export_case():
    """A live sequence's pages exported between two steps (the gather
    reads the pools the last step gave back), then the run goes on."""
    build, _ = _llama_case()

    def drive(eng):
        rid = eng.add_request(np.arange(3, 22, dtype=np.int32),
                              max_new_tokens=9, logprobs=True)
        for _ in range(5):
            eng.step()
        seq = eng.request(rid).seq_id
        meta, k, v = eng.cache.export_pages(seq)
        assert meta["n_pages"] > 0
        eng.step()
        again = eng.cache.export_pages(seq)
        res = eng.run()
        return ([a.tolist() for a in k + v],
                [a.tolist() for a in again[1] + again[2]],
                list(map(int, res[rid]["tokens"])))
    return build, drive


DONATION_CASES = {
    "dense": _llama_case(),
    "int8": _llama_case(cache_dtype="int8"),
    "latent": _latent_case(),
    "mixed": _mixed_case(),
    "prefix_cache": _llama_case(shared=9, prefix_cache=True, max_new=12,
                                num_pages=24),
    "fork_copy_on_write": _fork_case(),
    "page_export_between_steps": _export_case(),
    "speculative_k2_with_a_draft": _llama_case(draft_seed=9,
                                               speculative_k=2),
}


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
        assert_same_but_for_recompute(
            eng, got, served_alone(m, prompts, kws, 12))
        assert_greedy_is_generate(m, prompts, kws, got, 12)

    def test_a_one_row_fault_breaks_the_ulp_allowance(self, monkeypatch):
        """What ``CROSS_SHAPE_ULPS`` lets through is rounding, not a
        fault: the preemption case with ONE row of the rectangle (lane
        0's) attending from one position early, as a packer off by one
        in ``q_offsets`` would have it, flips tokens, and where the
        tokens still agree reads ten thousand times the allowance."""
        from paddle_tpu.serving import attention
        real = attention.paged_attention_ref

        def one_row_early(q, kp, vp, pt, cl, qoff, **kw):
            out = real(q, kp, vp, pt, cl, qoff, **kw)
            if q.shape[0] == ENG_KW["max_batch"]:        # the rectangle
                early = real(q[:1, :1], kp, vp, pt[:1], cl[:1],
                             jnp.maximum(qoff[:1] - 1, 0), **kw)
                out = out.at[0, 0].set(early[0, 0])
            return out

        m = tiny_model(seed=1)
        prompts = [np.random.default_rng(1).integers(0, 97, 3)
                   .astype(np.int32) for _ in range(4)]
        kws = [dict(), dict(do_sample=True, top_k=7, seed=2), dict(),
               dict(do_sample=True, temperature=1.2, seed=9)]
        alone = served_alone(m, prompts, kws, 12)
        monkeypatch.setattr(attention, "paged_attention_ref",
                            one_row_early)
        got, _ = crowd(m, prompts, kws, 12, num_pages=10)
        monkeypatch.undo()
        assert logprob_ulps(got, alone) > 1000 * CROSS_SHAPE_ULPS
        with pytest.raises(AssertionError):
            assert_streams_within_ulps(got, alone, CROSS_SHAPE_ULPS)
        sound, _ = crowd(m, prompts, kws, 12, num_pages=10)
        assert 0 < logprob_ulps(sound, alone) <= CROSS_SHAPE_ULPS // 4

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
        assert_streams_within_ulps(got, served_alone(m, prompts, kws, 8),
                                   CROSS_SHAPE_ULPS)
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
        assert_same_but_for_recompute(
            eng, got, served_alone(m, prompts, kws, 16, **ekw))
        assert eng.metrics.step_program_classes.value <= 2

    @pytest.mark.parametrize("case", sorted(DONATION_CASES))
    def test_donated_pools_serve_the_undonated_streams(self, case):
        """The step is handed its pools for good (donated) and writes
        its rows in place: the same operations on the same values, one
        buffer fewer. Against the same engine with donation taken out
        (the same pure functions under a plain jit): tokens and logprob
        bits equal, and whatever else the case reads between steps;
        ``pool_bytes_donated`` is every byte of the cache's state, and
        no step leaves a donated buffer unused."""
        build, drive = DONATION_CASES[case]
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            eng = build()
            handed = sum(a.nbytes for a in jax.tree.leaves(
                (eng.cache.program_operands(), eng.cache.extra_operands())))
            got = drive(eng)
            plain = undonated(build())
            want = drive(plain)
        assert got == want
        assert handed > 0
        assert eng.metrics.pool_bytes_donated.value == handed
        assert plain.metrics.pool_bytes_donated.value == 0
        assert not [w for w in caught if "donated" in str(w.message)], \
            [str(w.message) for w in caught]
        # the cache holds live arrays after the run: nothing it owns was
        # left behind in a program
        assert not any(a.is_deleted() for a in jax.tree.leaves(
            (eng.cache.program_operands(), eng.cache.extra_operands())))

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

    def test_gathered_and_live_pages_of_a_three_lane_step(self):
        """``attn_pages_gathered`` / ``attn_pages_live`` against a hand
        count: two decode lanes beside a chunk (a table a lane, the
        chunk's too: 5 of ``max_pages_per_seq``), then decode-only
        steps (the rectangle's 4)."""
        m = tiny_model()
        eng = ServingEngine(m, **ENG_KW)        # page 4, 4 lanes + chunk
        mp, mt = eng.max_pages_per_seq, eng.metrics
        rng = np.random.default_rng(4)

        def step():
            was = (mt.attn_pages_gathered.value, mt.attn_pages_live.value)
            eng.step()
            return (mt.attn_pages_gathered.value - was[0],
                    mt.attn_pages_live.value - was[1])

        eng.add_request(rng.integers(0, 97, 4).astype(np.int32),
                        max_new_tokens=8)
        assert step() == (5 * mp, 1)          # a chunk of 4 alone
        eng.add_request(rng.integers(0, 97, 5).astype(np.int32),
                        max_new_tokens=8)
        assert step() == (5 * mp, 2 + 2)      # decode at 5, chunk of 5
        eng.add_request(rng.integers(0, 97, 30).astype(np.int32),
                        max_new_tokens=2)
        # the three-lane step: contexts 6 and 6 beside a chunk to 8
        assert step() == (5 * mp, 2 + 2 + 2)
        assert step() == (5 * mp, 2 + 2 + 4)  # 7, 7, chunk to 16
        eng.cancel(sorted(eng._requests)[-1])
        assert step() == (4 * mp, 2 + 2)      # decode-only: 8 and 8
        ex = mt.export()
        assert ex["attn_pages_gathered"] > ex["attn_pages_live"] > 0

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
