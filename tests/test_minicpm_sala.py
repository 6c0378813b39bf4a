"""``models/minicpm_sala.py`` (block-selected sparse attention beside
lightning attention) on the serving path, against the plain reference
(``benchmark/harness/reference_sala.py``) at a small size on the CPU: 4
layers held of a published 6 (sparse, lightning, lightning, sparse), 4
query heads and 2 KV heads of 16, vocabulary 512, and a selection cut to
the size: kernel 4 at stride 2, blocks (pages) of 4, top-2, one init
block, a window of 8 and dense_len 16, so that rows past position 15
select 6 or 7 of up to 24 blocks. Seeded float32 weights; logits and
log-probabilities are compared, never tokens.

The tolerances. Program and reference both compute in float32 here, in
different orders (the program runs lightning in sub-chunks and carries
its state from step to step, gathers the selected pages, streams the
chunk's pages with an online softmax; the reference runs a token at a
time and attends every key under the selection as a mask): they differ
by rounding alone, measured at most 4.8e-7 on log-probabilities of
magnitude 6, so ``TOL`` = 2e-5 leaves about forty times of room. Dense
attention in place of the selection and lightning without its decay move
the same numbers by 1e-2 and more.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import reference_sala as R
from benchmark.harness import weights_sala as W
from paddle_tpu.models import MiniCPMSalaConfig, MiniCPMSalaForCausalLM
from paddle_tpu.models import minicpm_sala as ms
from paddle_tpu.ops import delta_rule as dr
from paddle_tpu.ops.lightning_attn import (SUB, lightning_attention,
                                           lightning_chunk, lightning_step)
from paddle_tpu.serving import ServingEngine

TOL = 2e-5
MIX = ["lightning-attn", "minicpm4", "lightning-attn", "lightning-attn",
       "minicpm4", "lightning-attn"]
SPARSE_CFG = dict(kernel_size=4, kernel_stride=2, block_size=4, topk=2,
                  init_blocks=1, window_size=8, dense_len=16)
CFG = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
           num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, lightning_nh=4, lightning_nkv=4,
           lightning_head_dim=16, rms_norm_eps=1e-6, rope_theta=10000,
           scale_emb=12, scale_depth=1.4, mup_denominator=6,
           dim_model_base=16, mixer_types=MIX, first_layer=1,
           reduced={"num_hidden_layers": {"published": 6, "here": 4}},
           sparse_config=SPARSE_CFG, torch_dtype="float32",
           initializer_range=0.2, max_position_embeddings=512)
ENGINE = dict(page_size=4, num_pages=120, max_batch=3, prefill_chunk=24,
              max_seq_len=96, eos_token_id=None)
PAD = 96


def build(cfg=CFG, seed=7):
    w = W.make(seed, cfg)
    model = MiniCPMSalaForCausalLM(MiniCPMSalaConfig.from_published(
        cfg, **W.program_kw(cfg)))
    params = dict(model.named_parameters())
    names = W.program_names(cfg)
    assert set(names.values()) == set(params)
    for path, name in names.items():
        arr = W.get(w, path)
        assert tuple(params[name].shape) == tuple(arr.shape), name
        params[name]._data = arr
    model.eval()
    return model, w


@pytest.fixture(scope="module")
def built():
    return build()


def prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(
        0, CFG["vocab_size"], n).astype(np.int32)


def reference_rows(w, seq, positions, cfg=CFG, **kw):
    ids = np.zeros((1, PAD), np.int32)
    ids[0, :len(seq)] = seq
    return np.asarray(R.logits_at(w, cfg, ids, np.asarray(positions),
                                  rows=8, ffn_rows=16, **kw))


def serve(model, requests, **kw):
    served = {}

    def on_event(ev):
        if ev["type"] == "token":
            served.setdefault(ev["req_id"], []).append(
                (int(ev["token"]), float(ev["logprob"])))

    eng = ServingEngine(model, ragged=True, on_event=on_event,
                        **{**ENGINE, **kw})
    ids = [eng.add_request(p, max_new_tokens=n, logprobs=True)
           for p, n in requests]
    eng.run()
    return eng, {i: served[rid] for i, rid in enumerate(ids)}


def rule_count(cfg, pos):
    """Blocks a (row, KV head) at each of ``pos`` attends by the rule of
    ``models/minicpm_sala.py``, counted: every block the top-k may take
    holds scored keys, so the count does not depend on the scores."""
    sc = cfg.sparse_config
    b, pos = sc["block_size"], np.asarray(pos, np.int64)
    held = pos // b + 1
    wfirst = np.maximum(pos - sc["window_size"] + 1, 0) // b
    init = np.minimum(sc["init_blocks"], wfirst)
    chosen = np.minimum(sc["topk"], np.maximum(wfirst - sc["init_blocks"],
                                               0))
    return np.where(pos + 1 <= sc["dense_len"], held,
                    (held - wfirst) + init + chosen)


def worst_error(w, requests, served, **kw):
    """The largest |served log-probability - the reference's| over every
    served token, and the largest gap of a served token's reference logit
    under the reference's best."""
    gap = err = 0.0
    for i, (p, n) in enumerate(requests):
        toks = np.asarray([t for t, _ in served[i]], np.int32)
        lps = np.asarray([lp for _, lp in served[i]])
        assert len(toks) == n
        ref = reference_rows(w, np.concatenate([p, toks]),
                             len(p) - 1 + np.arange(n), **kw)
        lsm = np.asarray(jax.nn.log_softmax(ref, -1))
        rows = np.arange(n)
        err = max(err, float(np.abs(lps - lsm[rows, toks]).max()))
        gap = max(gap, float((ref.max(-1) - ref[rows, toks]).max()))
    return gap, err


# -- the serving path against the reference ---------------------------------

@pytest.mark.parametrize("plen", [5, 30, 61])
def test_alone_chunked_prefill_then_decode_is_the_references(built, plen):
    """Within dense_len, across it, and a prompt of three chunks whose
    decode rows select 7 of 17-18 blocks."""
    model, w = built
    reqs = [(prompt(plen), 10)]
    _, served = serve(model, reqs)
    gap, err = worst_error(w, reqs, served)
    assert gap == 0.0 and err < TOL


def test_in_a_crowd_and_what_the_counters_count(built):
    """Three lanes decode beside chunks of the others; the selection
    counts the step makes on the device are the rule's (``rule_count``)
    for every row it packed (a prompt's rows and each served token but
    the last), and the chunk form and the decode route both ran."""
    model, w = built
    reqs = [(prompt(50), 9), (prompt(13), 12), (prompt(34), 7),
            (prompt(70, 1), 6)]
    dr.reset_dispatch_stats()
    eng, served = serve(model, reqs)
    gap, err = worst_error(w, reqs, served)
    assert gap == 0.0 and err < TOL
    m = eng.metrics
    pos = np.concatenate([np.arange(len(p) + n - 1) for p, n in reqs])
    # two sparse layers of two KV heads
    assert m.sparse_blocks_read.value == 4 * rule_count(model.cfg, pos).sum()
    assert m.sparse_blocks_live.value == 4 * (pos // 4 + 1).sum()
    assert 0 < m.sparse_pages_selected.value < m.sparse_blocks_read.value
    assert m.lightning_layer_steps.value > 0
    # two lightning layers, every prompt row through the chunk form
    assert m.lightning_rows_chunked.value == 2 * sum(len(p) for p, _ in reqs)
    assert dr.dispatch_stats()["chunk"] > 0 and dr.dispatch_stats()["step"]
    assert m.delta_layer_steps.value == 0          # lightning is not KDA


def test_a_preempted_request_is_recomputed_to_the_same_logits(built):
    """A pool too small for all: a lane is preempted, its state dropped,
    and it is prefilled again from token 0 to the same log-probabilities
    (lane reuse: the next owner of a slot starts from nought)."""
    model, w = built
    reqs = [(prompt(40, s), 14) for s in range(4)]
    eng, served = serve(model, reqs, num_pages=40)
    assert eng.metrics.preemptions.value > 0
    gap, err = worst_error(w, reqs, served)
    assert gap == 0.0 and err < TOL


def test_dense_in_place_of_the_selection_and_no_decay_fail(built,
                                                          monkeypatch):
    """The two planted faults of ``check_sala``, in the program: a sparse
    layer that attends every key, and lightning with lambda = 1, each move
    the served log-probabilities far past ``TOL`` from the reference."""
    model, w = built
    reqs = [(prompt(61), 6)]
    monkeypatch.setattr(ms, "select_blocks",
                        lambda q, ck, pos, cfg, scale: jnp.ones(
                            q.shape[:2] + (ck.shape[2],
                                           ck.shape[1] // 2), bool)
                        & (jnp.arange(ck.shape[1] // 2)
                           <= (pos // 4)[..., None, None]))
    eng, served = serve(model, reqs)
    assert worst_error(w, reqs, served)[1] > 100 * TOL
    # the counters read what the step attended: every block it held
    m = eng.metrics
    assert m.sparse_blocks_read.value == m.sparse_blocks_live.value > 0
    monkeypatch.undo()
    for lyr in model.layers:
        if lyr.kind == ms.LIGHTNING:
            monkeypatch.setattr(lyr.self_attn, "log_decay",
                                np.zeros_like(lyr.self_attn.log_decay))
    _, served = serve(model, reqs)
    assert worst_error(w, reqs, served)[1] > 100 * TOL
    # ... and that program is the reference's no-decay fault, to rounding
    assert worst_error(w, reqs, served, decay=False)[1] < TOL


# -- the sparse layer ---------------------------------------------------------

def _selection_case(seed=0, n=2, s=3, p=12):
    cfg = MiniCPMSalaConfig.from_published(CFG, **W.program_kw(CFG))
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(n, s, 4, 16)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(n, 2 * p, 2, 16)), jnp.float32)
    pos = jnp.asarray(rng.integers(16, 4 * p, (n, s)), jnp.int32)
    return cfg, q, ck, pos


def test_the_sparse_layer_is_dense_attention_within_its_blocks(built):
    """Where every block a row holds is selected (within dense_len, or so
    few blocks that the init, the window and the top-k cover them), the
    sparse layer is dense attention; past that it differs."""
    _, w = built
    seq = prompt(60, 3)
    short = np.arange(16)                  # p + 1 <= dense_len
    np.testing.assert_array_equal(
        reference_rows(w, seq, short), reference_rows(w, seq, short,
                                                      dense=True))
    # the init block, the window's and the top-2 cover every block a row
    # holds through p = 22 (at 23: 5 of 6)
    covered = np.arange(16, 23)
    np.testing.assert_array_equal(
        reference_rows(w, seq, covered), reference_rows(w, seq, covered,
                                                        dense=True))
    late = np.arange(40, 60)
    assert np.abs(reference_rows(w, seq, late) - reference_rows(
        w, seq, late, dense=True)).max() > 1e-3
    cfg, q, ck, _ = _selection_case()
    pos = jnp.full((2, 3), 22, jnp.int32)
    sel = np.asarray(ms.select_blocks(q, ck, pos, cfg, 0.25))
    assert sel[..., :6].all() and not sel[..., 6:].any()


def test_the_selection_is_a_groups_and_sees_no_unfinished_key():
    """A KV group's selection is its own query heads' (another group's
    heads do not move it; its two heads share it), and a compressed key
    whose kernel ends past the row moves nothing."""
    cfg, q, ck, pos = _selection_case()
    sel = np.asarray(ms.select_blocks(q, ck, pos, cfg, 0.25))
    other = q.at[:, :, 2:].set(-q[:, :, 2:])        # group 1's heads
    sel2 = np.asarray(ms.select_blocks(other, ck, pos, cfg, 0.25))
    np.testing.assert_array_equal(sel[:, :, 0], sel2[:, :, 0])
    assert (sel[:, :, 1] != sel2[:, :, 1]).any()
    # at p = 30 the kernels starting at 2c end at 2c + 3 > 30 for c >= 14
    at = jnp.full_like(pos, 30)
    junk = ck.at[:, 14:].add(1e3)
    np.testing.assert_array_equal(
        np.asarray(ms.select_blocks(q, junk, at, cfg, 0.25)),
        np.asarray(ms.select_blocks(q, ck, at, cfg, 0.25)))
    assert (np.asarray(ms.select_blocks(q, ck.at[:, 13].add(1e3), at, cfg,
                                        0.25))
            != np.asarray(ms.select_blocks(q, ck, at, cfg, 0.25))).any()


def test_the_counted_rule_is_the_references_selection():
    """``rule_count`` (what the device counts are held to) against the
    number of blocks the reference's own selection gives, row by row, and
    ``kernels_scored`` (what the engine counts on the host)."""
    cfg, _, _, _ = _selection_case()
    sc = cfg.sparse_config
    rng = np.random.default_rng(5)
    s = 96
    pos = jnp.arange(s)
    q = jnp.asarray(rng.normal(size=(s, 2, 2, 16)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(47, 2, 16)), jnp.float32)
    sel = np.asarray(R.selection(q, ck, pos, sc, s // 4))     # [S, KV, P]
    want, scored = rule_count(cfg, np.arange(s)), ms.kernels_scored(
        cfg, np.arange(s))
    np.testing.assert_array_equal(sel.sum(-1), want[:, None].repeat(2, 1))
    assert (scored[:16] == 0).all() and scored[50] == (50 - 3) // 2 + 1


# -- lightning attention ------------------------------------------------------

def _recurrence(q, k, v, g, state):
    """float64, a row at a time, a lane."""
    q, k, v, g = (np.asarray(a, np.float64) for a in (q, k, v, g))
    st = np.array(state, np.float64)
    out = np.zeros(v.shape)
    for t in range(q.shape[0]):
        st = np.exp(g[t])[:, None, None] * st + np.einsum(
            "hk,hv->hkv", k[t], v[t])
        out[t] = np.einsum("hkv,hk->hv", st, q[t])
    return out, st


@pytest.mark.parametrize("s", [7, SUB, SUB + 9])
def test_the_chunk_form_is_the_recurrence_across_boundaries(s):
    rng = np.random.default_rng(s)
    n, h, d = 2, 3, 8
    q, k, v = (rng.normal(size=(n, s, h, d)).astype(np.float32)
               for _ in range(3))
    g = -rng.uniform(0, 1.5, (n, s, h)).astype(np.float32)
    st0 = rng.normal(size=(n, h, d, d)).astype(np.float32)
    live = np.arange(s)[None] < np.array([s, s - 3])[:, None]
    o, st = lightning_chunk(q, k, v, g, st0, 0.5, jnp.asarray(live))
    for lane in range(n):
        m = int(live[lane].sum())
        want, wst = _recurrence(q[lane, :m], k[lane, :m], v[lane, :m],
                                g[lane, :m], st0[lane])
        scale = np.abs(want).max()
        assert np.abs(np.asarray(o[lane, :m]) - 0.5 * want).max() \
            < 1e-5 * scale
        assert np.abs(np.asarray(st[lane]) - wst).max() \
            < 1e-5 * np.abs(wst).max()


def test_the_routes_by_slot_reuse_a_lane_from_nought():
    """By slot: a fresh lane starts from nought whatever its slot held, a
    carried lane from its slot; the one-row route and the decode kernel
    (interpret mode) agree with the chunk form row for row."""
    rng = np.random.default_rng(2)
    n, h, d, m = 2, 2, 128, 4
    states = jnp.asarray(rng.normal(size=(m, h, d, d)), jnp.float32)
    slots = jnp.asarray([3, 1], jnp.int32)
    fresh = jnp.asarray([True, False])
    q, k, v = (jnp.asarray(rng.normal(size=(n, 1, h, d)), jnp.float32)
               for _ in range(3))
    g = jnp.asarray(-rng.uniform(0, 1, (n, 1, h)), jnp.float32)
    o_c, s_c = lightning_attention(q, k, v, g, states, 0.1, None, slots,
                                   fresh)
    want0, _ = _recurrence(q[0], k[0], v[0], g[0], np.zeros((h, d, d)))
    want1, _ = _recurrence(q[1], k[1], v[1], g[1], states[1])
    np.testing.assert_allclose(np.asarray(o_c[0]), 0.1 * want0, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(o_c[1]), 0.1 * want1, rtol=1e-4,
                               atol=1e-3)
    untouched = jnp.asarray([0, 2])
    np.testing.assert_array_equal(np.asarray(s_c[untouched]),
                                  np.asarray(states[untouched]))
    o1, st1 = lightning_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                             jnp.where(fresh[:, None, None, None], 0,
                                       states[slots]), 0.1)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o_c[:, 0]),
                               rtol=1e-5, atol=1e-5)
    dr._FORCE_INTERPRET = True
    try:
        o_k, s_k = lightning_attention(q, k, v, g, states, 0.1, None, slots,
                                       fresh)
    finally:
        dr._FORCE_INTERPRET = False
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_c), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_c), rtol=1e-5,
                               atol=1e-5)


# -- the muP constants and the cache ------------------------------------------

def test_the_depth_factor_is_the_published_layer_counts(built):
    cfg = MiniCPMSalaConfig.from_published(CFG, **W.program_kw(CFG))
    assert cfg.depth_published == 6 == W.depth_published(CFG)
    assert cfg.residual_scale == pytest.approx(1.4 / np.sqrt(6))
    assert cfg.head_divisor == 4.0
    assert cfg.kinds() == MIX[1:5]
    # the decay of held layer 1 is the published layer 2's: l / (N - 1)
    np.testing.assert_allclose(
        cfg.log_decay(1), -(2.0 ** (-2.0 * np.arange(1, 5)))
        * (1 - 2 / 5 + 1e-5), rtol=1e-6)
    np.testing.assert_allclose(cfg.log_decay(1),
                               np.asarray(R.log_decay(CFG, 1)), rtol=1e-6)
    # the reference reads N from reduced too: another N, other logits
    _, w = built
    seq = prompt(20)
    other = dict(CFG, reduced={"num_hidden_layers": {"published": 32,
                                                     "here": 4}})
    assert np.abs(reference_rows(w, seq, [19]) - reference_rows(
        w, seq, [19], cfg=other)).max() > 1e-3


def test_the_layout_and_the_refusals(built):
    model, _ = built
    eng = ServingEngine(model, ragged=True, **ENGINE)
    c = eng.cache
    assert c.mixed and c.indexer_layers == [0, 3]
    assert c.full_layers == [0, 3] and c.state_layers == [1, 2]
    assert c.indexer[0].shape == (120, 2, 2, 16)
    assert c.k_pages[0].shape == (120, 4, 64)
    assert c.lane_state[0][0].shape == (4, 4, 16, 16)
    with pytest.raises(NotImplementedError, match="indexer"):
        ServingEngine(model, ragged=True, prefix_cache=True, **ENGINE)
    with pytest.raises(NotImplementedError, match="int8"):
        ServingEngine(model, ragged=True, cache_dtype="int8", **ENGINE)
    with pytest.raises(NotImplementedError, match="latent"):
        c.export_pages("nobody")
    with pytest.raises(NotImplementedError, match="indexer"):
        c._refuse_mixed("fork")
