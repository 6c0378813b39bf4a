"""``models/sambay.py`` on the serving path, against the plain reference
(``benchmark/harness/reference_sambay.py``) at a small size on the CPU:
6 layers = one pair of each half with the memory and the full layer
between (Mamba, window, Mamba + memory, full, GMU, cross), window 8, page
4, chunk 8, seeded float32 weights. Logits and log-probabilities are
compared, never tokens.

The tolerances. Program and reference both compute in float32 here, in
different orders (the program gathers pages, scans a chunk at a time and
carries its state; the reference runs the whole sequence): they differ
by rounding alone, measured at most 1.3e-5 on logits of magnitude 3
(matrices N(0, 0.09): the published 0.02 scaled to these widths, so that
the recurrence and the scores weigh what they weigh at 2,560), so
``TOL`` = 5e-5 leaves that four times of room. A scan state held in
bfloat16 between steps and a differential attention computed as one
softmax are planted below and have to fail ``TOL`` several times over.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from benchmark.harness import reference_sambay as R
from benchmark.harness import weights_sambay as W
from paddle_tpu.models import SambaYConfig, SambaYForCausalLM
from paddle_tpu.models import sambay as sb
from paddle_tpu.ops.selective_scan import causal_conv_tail, selective_scan
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_cache import LayerCache, PagedKVCache

TOL = 5e-5
CFG = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=6,
           num_attention_heads=8, num_key_value_heads=4, sliding_window=8,
           vocab_size=320, max_position_embeddings=256,
           layer_norm_eps=1e-5, mb_per_layer=2, hidden_act="silu",
           tie_word_embeddings=True, torch_dtype="float32",
           initializer_range=0.09, program={"memory_layer": 2})
ENGINE = dict(page_size=4, num_pages=64, max_batch=4, prefill_chunk=8,
              max_seq_len=64, eos_token_id=None)
PAD = 64


def build(seed=7):
    w = W.make(seed, CFG)
    model = SambaYForCausalLM(SambaYConfig.from_published(
        CFG, dtype="float32", **CFG["program"]))
    params = dict(model.named_parameters())
    names = W.program_names(CFG)
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


def reference_rows(w, seq, positions):
    ids = np.zeros((1, PAD), np.int32)
    ids[0, :len(seq)] = seq
    return np.asarray(R.logits_at(w, CFG, ids, np.asarray(positions),
                                  block=8))


def serve(model, requests, **kw):
    """Run ``requests`` [(prompt, max_new)] to the end; returns
    (engine, {index: [(token, logprob), ...]})."""
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


def worst_error(w, requests, served):
    """Over every served token of every request: the largest gap of its
    reference logit under the reference's best, and the largest |served
    log-probability - the reference's|."""
    gap = err = 0.0
    for i, (p, n) in enumerate(requests):
        toks = np.asarray([t for t, _ in served[i]], np.int32)
        assert len(toks) == n
        ref = reference_rows(w, np.concatenate([p, toks]),
                             len(p) - 1 + np.arange(n))
        lsm = np.asarray(jax.nn.log_softmax(ref, -1))
        rows = np.arange(n)
        gap = max(gap, float(np.max(ref.max(-1) - ref[rows, toks])))
        err = max(err, float(np.max(np.abs(
            np.asarray([lp for _, lp in served[i]]) - lsm[rows, toks]))))
    return gap, err


# -- the model's forward ----------------------------------------------------------

def test_forward_is_the_references(built):
    model, w = built
    ids = prompt(40)[None]
    with P.no_grad():
        got = np.asarray(model(P.to_tensor(ids))._data)[0]
    want = reference_rows(w, ids[0], np.arange(40))
    assert np.abs(want).max() > 0.3          # logits that say something
    assert np.abs(got - want).max() < TOL


def test_the_layout_is_one_pair_of_each_half_around_the_memory(built):
    model, _ = built
    assert [lyr.kind for lyr in model.layers] == [
        "mamba", "window", "mamba", "full", "gmu", "cross"]
    assert [W.kind(CFG, i) for i in range(6)] == [
        lyr.kind for lyr in model.layers]
    pub = SambaYConfig()
    kinds = [pub.kind(i) for i in range(32)]
    assert kinds[:16] == ["mamba", "window"] * 8
    assert kinds[16:18] == ["mamba", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert (pub.head_dim, pub.d_inner, pub.mamba_dt_rank) == (64, 5120, 160)
    assert abs(pub.lam0(17) - (0.8 - 0.6 * np.exp(-5.1))) < 1e-12
    # the head is the embedding: one parameter, one name
    names = [n for n, _ in model.named_parameters()]
    assert "embed_tokens.weight" in names and "lm_head.weight" not in names


# -- through the engine ---------------------------------------------------------------

@pytest.mark.parametrize("plen", [3, 8, 13, 21, 30])
def test_alone_chunked_prefill_then_decode_is_the_references(built, plen):
    """Every logit of every step's sampled row, not only the served
    token's: chunk boundaries fall at different offsets of the window
    (8) and the page (4)."""
    model, w = built
    eng = ServingEngine(model, ragged=True, **ENGINE)
    p = prompt(plen)
    rid = eng.add_request(p, max_new_tokens=10, logprobs=True)
    rows = []
    while not eng.scheduler.all_done():
        eng.step()
        rows.append(np.array(eng._last_logits_probe))
    toks = eng.results()[rid]["tokens"]
    chunks = -(-plen // ENGINE["prefill_chunk"])
    got = np.stack(rows[chunks - 1:chunks - 1 + 10])
    want = reference_rows(w, np.concatenate([p, toks]),
                          plen - 1 + np.arange(10))
    assert np.abs(got - want).max() < TOL
    assert eng.metrics.step_program_classes.value == 2


def test_in_a_crowd_with_requests_starting_and_ending_beside_it(built):
    model, w = built
    requests = [(prompt(n, seed=1), m) for n, m in
                [(26, 12), (5, 3), (17, 9), (9, 14), (31, 4), (12, 7),
                 (3, 11)]]
    eng, served = serve(model, requests, max_batch=3)
    gap, err = worst_error(w, requests, served)
    assert gap < TOL and err < TOL
    m = eng.metrics
    assert m.step_program_classes.value == 2
    assert m.preemptions.value == 0
    assert m.ssm_state_resets.value == len(requests)
    # every prompt token and every fed-back token was scanned by each of
    # the two Mamba layers, once
    fed = sum(len(p) + n - 1 for p, n in requests)
    assert m.ssm_rows_scanned.value == 2 * fed
    assert m.ssm_layer_steps.value == 2 * m.step_dispatches.value


def test_a_window_that_binds_releases_the_pages_behind_it(built):
    model, _ = built
    eng = ServingEngine(model, ragged=True, **ENGINE)
    c = eng.cache
    assert c.window_pages_per_lane == 5            # ceil((8 + 8) / 4) + 1
    rid = eng.add_request(prompt(30), max_new_tokens=30)
    req = eng.request(rid)
    held, full = [], []
    free0 = len(c._wfree)
    while not eng.scheduler.all_done():
        eng.step()
        if c.has_seq(req.seq_id):
            held.append(c.window_pages_held(req.seq_id))
            full.append(c.pages_held(req.seq_id))
    assert max(held) <= c.window_pages_per_lane
    # while the length grows to 60 tokens (15 pages of the full pool) ...
    assert max(full) == 15
    # ... a decoding lane keeps the window's 8 keys: 2 or 3 pages
    assert set(held[-20:]) <= {2, 3}
    assert len(c._wfree) == free0 and len(c._lane_free) == 4
    m = eng.metrics
    assert m.window_pages_held.value / m.window_layer_steps.value <= 5


def test_a_preempted_request_is_recomputed_to_the_same_logits(built):
    model, w = built
    requests = [(prompt(n, seed=2), m) for n, m in
                [(22, 16), (19, 16), (25, 16)]]
    # 17 pages of 4 cannot hold three lanes of up to 41 tokens
    eng, served = serve(model, requests, num_pages=18, watermark_frac=0.0)
    assert eng.metrics.preemptions.value > 0
    assert eng.metrics.ssm_state_resets.value == 3 + \
        eng.metrics.preemptions.value
    gap, err = worst_error(w, requests, served)
    assert gap < TOL and err < TOL
    _, calm = serve(model, requests)
    for i in range(3):
        assert [t for t, _ in served[i]] == [t for t, _ in calm[i]]
        assert np.allclose([lp for _, lp in served[i]],
                           [lp for _, lp in calm[i]], atol=TOL)


def test_a_lane_reused_by_a_new_request_starts_from_a_zero_state(built):
    model, w = built
    requests = [(prompt(19, seed=3), 6), (prompt(11, seed=4), 8),
                (prompt(27, seed=5), 5)]
    eng, served = serve(model, requests, max_batch=1)
    # one lane: every request took the slot the one before it left dirty
    assert eng.cache.max_lanes == 1
    scan, _ = eng.cache.lane_state[0]
    assert float(jnp.abs(scan[1]).max()) > 0
    gap, err = worst_error(w, requests, served)
    assert gap < TOL and err < TOL


# -- planted: what the tolerance has to catch ---------------------------------------

def test_a_bfloat16_scan_state_fails_the_tolerance(built, monkeypatch):
    model, w = built
    real = sb.SambaYDecoderLayer.paged_cache.fget

    def low(self):
        lc = real(self)
        if not lc.state:
            return lc
        return LayerCache(state=tuple(
            (n, s, "bfloat16") for n, s, _ in lc.state))

    monkeypatch.setattr(sb.SambaYDecoderLayer, "paged_cache",
                        property(low))
    requests = [(prompt(40, seed=1), 20)]
    _, served = serve(model, requests)
    gap, err = worst_error(w, requests, served)
    assert err > 4 * TOL


def test_attention_in_one_softmax_fails_the_tolerance(built, monkeypatch):
    model, w = built
    real = sb.diff_attention
    monkeypatch.setattr(sb, "diff_attention", lambda q, k, v, mask, lam,
                        *a: real(q, k, v, mask, 0.0, *a))
    requests = [(prompt(26, seed=1), 12)]
    _, served = serve(model, requests)
    gap, err = worst_error(w, requests, served)
    assert err > 100 * TOL


# -- the scan -----------------------------------------------------------------------------

def _scan_case(s=13, d=24, n=4, lanes=2, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *sh: rng.normal(size=sh).astype(np.float32)   # noqa: E731
    return dict(x=f(lanes, s, d), dt=np.log1p(np.exp(f(lanes, s, d))) * 0.3,
                a=-np.exp(f(n, d) * 0.3), b=f(lanes, s, n),
                c=f(lanes, s, n), d=f(d))


def _by_hand(k, state):
    """The recurrence of the contract, row by row in NumPy float64."""
    x, dt = k["x"].astype(np.float64), k["dt"].astype(np.float64)
    ys = np.zeros_like(x)
    s = state.astype(np.float64).copy()
    for t in range(x.shape[1]):
        s = np.exp(dt[:, t, None, :] * k["a"][None]) * s \
            + (dt[:, t] * x[:, t])[:, None, :] * k["b"][:, t, :, None]
        ys[:, t] = np.sum(s * k["c"][:, t, :, None], 1) + k["d"] * x[:, t]
    return ys, s


@pytest.mark.parametrize("cut", range(1, 13))
def test_the_scan_is_the_recurrence_across_a_chunk_boundary(cut):
    """Rows 0..cut-1 in one call, the rest in the next from the state
    the first left (behind padding rows that change nothing): the
    step-by-step recurrence over all 13, whatever the offset."""
    k = _scan_case()
    state0 = np.random.default_rng(1).normal(size=(2, 4, 24)).astype(
        np.float32)
    want_y, want_s = _by_hand(k, state0)

    def part(lo, hi, state, pad):
        sl = lambda a: np.pad(a[:, lo:hi], [(0, 0), (0, pad)]  # noqa: E731
                              + [(0, 0)] * (a.ndim - 2))
        live = np.arange(hi - lo + pad)[None] < np.full((2, 1), hi - lo)
        y, s = selective_scan(sl(k["x"]), sl(k["dt"]), k["a"], sl(k["b"]),
                              sl(k["c"]), k["d"], state, jnp.asarray(live))
        return np.asarray(y)[:, :hi - lo], s

    y1, s1 = part(0, cut, state0, pad=3)
    y2, s2 = part(cut, 13, s1, pad=0)
    got = np.concatenate([y1, y2], 1)
    assert np.abs(got - want_y).max() < 1e-5 * np.abs(want_y).max()
    assert np.abs(np.asarray(s2) - want_s).max() < 1e-5 * np.abs(
        want_s).max()
    assert s2.dtype == jnp.float32


@pytest.mark.parametrize("cut", [1, 2, 3, 4, 7])
def test_the_convolutions_tail_carries_it_across_a_chunk_boundary(cut):
    rng = np.random.default_rng(2)
    x = rng.normal(size=(2, 9, 6)).astype(np.float32)
    w = rng.normal(size=(4, 6)).astype(np.float32)
    bias = rng.normal(size=6).astype(np.float32)
    pad = np.concatenate([np.zeros((2, 3, 6), np.float32), x], 1)
    want = bias + sum(pad[:, j:j + 9] * w[j] for j in range(4))
    zero = jnp.zeros((2, 3, 6), jnp.float32)
    # the first call is padded by two dead rows the tail must skip
    first = np.pad(x[:, :cut], [(0, 0), (0, 2), (0, 0)])
    y1, tail = causal_conv_tail(first, zero, w, bias,
                                jnp.full((2,), cut, jnp.int32))
    y2, tail2 = causal_conv_tail(x[:, cut:], tail, w, bias)
    got = np.concatenate([np.asarray(y1)[:, :cut], np.asarray(y2)], 1)
    assert np.abs(got - want).max() < 1e-6
    assert np.array_equal(np.asarray(tail2), x[:, -3:])


def test_the_models_mixer_is_the_references_mamba(built):
    """One Mamba layer's mixer in two chunks with a carried state against
    the reference's whole-sequence pass."""
    model, w = built
    mixer, lw = model.layers[0].mixer, w["layers"][0]
    u = np.random.default_rng(5).normal(size=(1, 20, 128)).astype(np.float32)
    want, want_y = R.mamba(jnp.asarray(u[0]),
                           {k: v.astype(jnp.float32) for k, v in lw.items()},
                           W.dims(CFG), "f32")
    tail = jnp.zeros((1, 3, 256), jnp.float32)
    state = jnp.zeros((1, 16, 256), jnp.float32)
    o1, y1, tail, state = mixer.run(jnp.asarray(u[:, :7]), tail, state)
    o2, y2, tail, state = mixer.run(jnp.asarray(u[:, 7:]), tail, state)
    got = np.concatenate([np.asarray(o1), np.asarray(o2)], 1)[0]
    got_y = np.concatenate([np.asarray(y1), np.asarray(y2)], 1)[0]
    assert np.abs(got - np.asarray(want)).max() < 1e-5
    assert np.abs(got_y - np.asarray(want_y)).max() < 1e-5


# -- the cache's make-up ------------------------------------------------------------------

def test_the_cache_takes_its_make_up_from_the_model(built):
    model, _ = built
    eng = ServingEngine(model, ragged=True, **ENGINE)
    c = eng.cache
    assert [lc.pool for lc in c.layout] == [
        None, "window", None, "full", None, None]
    assert c.layout[5].reads == 3 and not c.layout[4].state
    assert (c.full_layers, c.window_layers, c.state_layers) == (
        [3], [1], [0, 2])
    # one layer's K and V a token; the fixed part a lane: two states
    # (float32 [16, 256] + a [3, 256] tail) and 5 window pages
    assert c.bytes_per_token == 2 * 4 * 16 * 4
    assert eng.metrics.cache_bytes_per_token.value == c.bytes_per_token
    assert c.state_bytes_per_lane == 2 * (16 * 256 * 4 + 3 * 256 * 4) \
        + 2 * 5 * 4 * 4 * 16 * 4
    assert eng.metrics.state_bytes_per_lane.value == c.state_bytes_per_lane
    # a pool's entry is a token's keys and values joined: one array
    assert c.w_pages[0].shape == (4 * 5 + 1, 4, 2 * 4 * 16)
    assert c.k_pages[0].shape == (64, 4, 2 * 4 * 16) and not c.v_pages
    assert c.lane_state[0][0].shape == (5, 16, 256)
    assert c.lane_state[0][0].dtype == jnp.float32
    # at the published sizes: 5,120 B a token, and what a lane's states
    # cost (the window pages come on top)
    assert PagedKVCache.page_bytes_per_page(1, 1, 2 * 20 * 64, 16,
                                            "bfloat16", latent=True) \
        // 16 == 5120
    assert 9 * (5120 * 16 * 4 + 3 * 5120 * 2) == 3_225_600


def test_admission_counts_the_fixed_part_in_lanes(built):
    model, _ = built
    eng = ServingEngine(model, ragged=True, **{**ENGINE, "max_batch": 2})
    for n in (9, 9, 9):
        eng.add_request(prompt(n), max_new_tokens=4)
    eng.step()
    assert len(eng.scheduler.live_requests()) == 2
    assert len(eng.scheduler.waiting) == 1
    assert eng.cache.can_hold_lanes(1)   # a slot is taken at the first
    for _ in range(3):                   # chunk: the second request's
        eng.step()                       # comes after the first's two
    assert not eng.cache.can_hold_lanes(1)
    eng.run()
    assert eng.cache.can_hold_lanes(2)
    assert len(eng.results()) == 3


def test_a_llama_and_its_first_layer_still_give_the_uniform_layout():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    llama = LlamaForCausalLM(LlamaConfig.tiny())
    layout = ServingEngine._cache_layout(llama.cfg, llama.llama)
    assert len(layout) == llama.cfg.num_hidden_layers
    assert set(layout) == {LayerCache(pool="full", n_kv_heads=4,
                                      head_dim=16)}
    eng = ServingEngine(llama, ragged=True, page_size=4, num_pages=16,
                        max_batch=2, prefill_chunk=8, max_seq_len=32)
    assert not eng.cache.mixed and eng.cache.extra_operands() == {}
    assert eng.cache.can_hold_lanes(10**6)
    assert eng.metrics.state_bytes_per_lane.value == 0


# -- refused by name --------------------------------------------------------------------

REFUSED = {
    "prefix_cache": (dict(prefix_cache=True), "prefix_cache=True"),
    "speculative_k": (dict(speculative_k=2), "speculative"),
    "tp_degree": (dict(tp_degree=2), "tensor parallelism"),
    "int8_cache": (dict(cache_dtype="int8"), "int8"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_an_engine_option_that_is_not_built_is_refused_by_name(built, what):
    model, _ = built
    kw, match = REFUSED[what]
    with pytest.raises(NotImplementedError, match=match):
        ServingEngine(model, ragged=True, **{**ENGINE, **kw})


def test_a_draft_model_is_refused_by_name(built):
    model, _ = built
    with pytest.raises(NotImplementedError, match="state rolled back"):
        ServingEngine(model, ragged=True, draft_model=model,
                      speculative_k=2, **ENGINE)


def test_forks_handoffs_and_page_shipping_are_refused_by_name(built):
    model, _ = built
    eng = ServingEngine(model, ragged=True, **ENGINE)
    with pytest.raises(NotImplementedError, match="n > 1"):
        eng.add_request(prompt(5), n=2, do_sample=True)
    with pytest.raises(NotImplementedError, match="prefill_only"):
        eng.add_request(prompt(5), prefill_only=True)
    c = eng.cache
    c.alloc_seq("a")
    c.append_slots("a", 6)
    for call, match in [
            (lambda: c.export_pages("a"), "pagewire / disagg"),
            (lambda: c.import_pages("b", {}, [], []), "pagewire / disagg"),
            (lambda: c.attach_tier(object()), "kvtier"),
            (lambda: c.fork("a", "b"), "fork"),
            (lambda: c.free_tail("a", 3), "rolled back")]:
        with pytest.raises(NotImplementedError, match=match):
            call()
    c.free_seq("a")


def test_a_config_whose_equations_are_not_written_is_refused():
    for kw, match in [(dict(tie_word_embeddings=False), "untied head"),
                      (dict(mlp_bias=True), "mlp_bias"),
                      (dict(hidden_act="gelu"), "hidden_act"),
                      (dict(mb_per_layer=4), "mb_per_layer"),
                      (dict(resid_pdrop=0.1), "pdrop")]:
        with pytest.raises(NotImplementedError, match=match):
            SambaYConfig.tiny(**kw)
    with pytest.raises(ValueError, match="memory_layer"):
        SambaYConfig.tiny(memory_layer=3)
    with pytest.raises(ValueError, match="memory_layer"):
        SambaYConfig(num_hidden_layers=6)      # 6 // 2 is odd
