"""``models/kda_moe.py`` (Kimi delta attention beside latent attention,
group-routed experts held in shares) on the serving path, against the
plain reference (``benchmark/harness/reference_kda_moe.py``) at a small
size on the CPU: 4 layers (KDA dense, KDA MoE, MLA MoE, KDA MoE: one
period of 3 and a layer of the next), 4 heads of 16, 16 experts in 4
groups (2 kept, top 4), vocabulary 512, page 4, chunk 24, seeded float32
weights. Logits and log-probabilities are compared, never tokens.

The tolerances. Program and reference both compute in float32 here, in
different orders (the program runs the delta rule in sub-chunks of 16 and
carries its state and the convolution's tail from step to step, gathers
pages, and attends in the absorbed form; the reference runs the whole
sequence a token at a time and attends in the expanded form): they
differ by rounding alone, measured at most 3.4e-6 on logits of magnitude
3 (matrices N(0, 0.09): the published 0.02 scaled to these widths, so
that the state and the scores weigh what they weigh at 2,560), so ``TOL``
= 3e-5 leaves that about nine times of room. A state held in bfloat16
between steps, and a state not carried from one step to the next, are
planted below and have to fail ``TOL`` several times over. The delta
rule's two routes are held to the recurrence in float64 at 1e-5 of the
outputs' scale: float32 rounding of sums over 16 to 40 rows.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from benchmark.harness import reference_kda_moe as R
from benchmark.harness import weights_kda_moe as W
from paddle_tpu.incubate import moe as M
from paddle_tpu.models import KdaMoEConfig, KdaMoEForCausalLM
from paddle_tpu.models import kda_moe as km
from paddle_tpu.models.latent_moe import LatentMoEConfig
from paddle_tpu.ops import delta_rule as dr
from paddle_tpu.ops.delta_rule import SUB, delta_rule
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_cache import LayerCache

TOL = 3e-5
CFG = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
           num_hidden_layers=4, first_k_dense_replace=1, layer_group_size=3,
           moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
           num_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
           num_attention_heads=4, num_key_value_heads=4, head_dim=16,
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           rotary_dim=8, v_head_dim=16, max_position_embeddings=512,
           q_lora_rank=None, rope_theta=6e6, rms_norm_eps=1e-6,
           kda_lower_bound=-5, group_norm_size=1, short_conv_kernel_size=4,
           routed_scaling_factor=2.5, norm_topk_prob=True,
           torch_dtype="float32", initializer_range=0.09)
ENGINE = dict(page_size=4, num_pages=96, max_batch=3, prefill_chunk=24,
              max_seq_len=80, eos_token_id=None)
PAD = 80


def build(cfg=CFG, seed=7):
    w = W.make(seed, cfg)
    total, first, count = W.routed(cfg)
    model = KdaMoEForCausalLM(KdaMoEConfig.from_published(
        cfg, dtype="float32", num_experts=total,
        experts_held=(first, count)))
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


def reference_rows(w, seq, positions, cfg=CFG):
    ids = np.zeros((1, PAD), np.int32)
    ids[0, :len(seq)] = seq
    return np.asarray(R.logits_at(w, cfg, ids, np.asarray(positions),
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


# -- the delta rule -----------------------------------------------------------------------

def _delta_case(s, gate, lanes=2, h=3, d=8, seed=0):
    rng = np.random.default_rng(seed)

    def unit(*shape):
        x = rng.normal(size=shape)
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype(
            np.float32)

    g = {"floor": -5.0 * rng.uniform(0.995, 1.0, (lanes, s, h, d)),
         "near0": -1e-3 * rng.uniform(0.0, 1.0, (lanes, s, h, d))}[gate]
    return dict(q=unit(lanes, s, h, d), k=unit(lanes, s, h, d),
                v=rng.normal(size=(lanes, s, h, d)).astype(np.float32),
                g=g.astype(np.float32),
                beta=rng.uniform(size=(lanes, s, h)).astype(np.float32),
                s0=rng.normal(size=(lanes, h, d, d)).astype(np.float32))


def _recurrence(c, lo, hi, state):
    """The contract's recurrence, row by row in NumPy float64:
    (o [N, hi - lo, H, d] unscaled, state)."""
    st = state.astype(np.float64).copy()
    o = np.zeros(c["v"][:, lo:hi].shape)
    for t in range(lo, hi):
        sp = np.exp(c["g"][:, t])[..., None] * st
        kv = np.einsum("nhk,nhkv->nhv", c["k"][:, t], sp)
        st = sp + np.einsum("nhk,nhv->nhkv", c["k"][:, t],
                            c["beta"][:, t, :, None] * (c["v"][:, t] - kv))
        o[:, t - lo] = np.einsum("nhkv,nhk->nhv", st, c["q"][:, t])
    return o, st


@pytest.mark.parametrize("gate", ["floor", "near0"])
@pytest.mark.parametrize("cut", [1, 15, 16, 17, 33])
def test_the_chunk_form_is_the_recurrence_across_boundaries(gate, cut):
    """Rows 0..cut-1 in one call (padded behind by dead rows that change
    nothing), the rest in the next from the state the first left: the
    recurrence over all 40, whatever the offset against the sub-chunks
    of 16, with the decay pinned at its bound (a sub-chunk's summed
    log-decay near -80) and near nought (the state kept whole)."""
    s = 40
    c = _delta_case(s, gate)
    want_o, want_s = _recurrence(c, 0, s, c["s0"])

    def part(lo, hi, state, pad):
        sl = lambda a: np.pad(a[:, lo:hi], [(0, 0), (0, pad)]  # noqa: E731
                              + [(0, 0)] * (a.ndim - 2))
        live = np.arange(hi - lo + pad)[None] < np.full((2, 1), hi - lo)
        o, st = delta_rule(sl(c["q"]), sl(c["k"]), sl(c["v"]), sl(c["g"]),
                           sl(c["beta"]), state, 1.0, jnp.asarray(live))
        return np.asarray(o)[:, :hi - lo], st

    o1, s1 = part(0, cut, c["s0"], pad=5)
    o2, s2 = part(cut, s, s1, pad=0)
    got = np.concatenate([o1, o2], 1)
    assert np.abs(got - want_o).max() < 1e-5 * np.abs(want_o).max()
    assert np.abs(np.asarray(s2) - want_s).max() < 1e-5 * np.abs(
        want_s).max()
    assert s2.dtype == jnp.float32 and SUB == 16


def test_the_one_row_route_is_the_recurrence():
    c = _delta_case(6, "near0", seed=1)
    want_o, want_s = _recurrence(c, 0, 6, c["s0"])
    st, outs = c["s0"], []
    for t in range(6):
        row = {k: v[:, t:t + 1] for k, v in c.items() if k != "s0"}
        o, st = delta_rule(row["q"], row["k"], row["v"], row["g"],
                           row["beta"], st, 1.0)
        outs.append(np.asarray(o))
    got = np.concatenate(outs, 1)
    assert np.abs(got - want_o).max() < 1e-5 * np.abs(want_o).max()
    assert np.abs(np.asarray(st) - want_s).max() < 1e-5 * np.abs(
        want_s).max()


# -- the router -----------------------------------------------------------------------------

def test_group_limited_routing_is_a_plain_loops():
    rng = np.random.default_rng(3)
    t, h, e, k, ng, kg = 12, 16, 16, 4, 4, 2
    y = rng.normal(size=(t, h)).astype(np.float32)
    wr = rng.normal(size=(h, e)).astype(np.float32)
    b = (0.1 * rng.normal(size=e)).astype(np.float32)
    idx, g = M.dropless_route(jnp.asarray(y), wr, b, k, 2.5, True, ng, kg)
    s = 1.0 / (1.0 + np.exp(-(y.astype(np.float64) @ wr)))
    for i in range(t):
        c = s[i] + b
        size = e // ng
        score = [sum(sorted(c[j * size:(j + 1) * size])[-2:])
                 for j in range(ng)]
        kept = sorted(range(ng), key=lambda j: -score[j])[:kg]
        cand = [x for j in kept for x in range(j * size, (j + 1) * size)]
        chosen = sorted(cand, key=lambda x: -c[x])[:k]
        assert sorted(np.asarray(idx[i]).tolist()) == sorted(chosen)
        want = {x: 2.5 * s[i, x] / sum(s[i, chosen]) for x in chosen}
        for x, gx in zip(np.asarray(idx[i]), np.asarray(g[i])):
            assert abs(gx - want[int(x)]) < 1e-5
    # one group, one kept: the published ungrouped router, op for op
    plain = jax.make_jaxpr(lambda a: M.dropless_route(a, wr, b, k, 2.5))(y)
    ones = jax.make_jaxpr(lambda a: M.dropless_route(a, wr, b, k, 2.5,
                                                     True, 1, 1))(y)
    assert str(plain) == str(ones)


def test_the_four_shares_of_an_expert_layer_add_up_to_the_layer():
    """Each share routes over all 16 experts and computes the part its
    4 give; the shared expert is in every share and counted once."""
    rng = np.random.default_rng(4)
    h, f, e = 64, 32, 16

    def layer(held):
        m = M.DroplessMoE(h, f, e, 4, routed_scaling_factor=2.5,
                          shared_width=f, experts_held=held, n_group=4,
                          topk_group=2)
        return m

    whole = layer(None)
    for p in whole.parameters():
        p._data = jnp.asarray(0.2 * rng.normal(size=p.shape), jnp.float32)
    y = P.to_tensor(rng.normal(size=(2, 5, h)).astype(np.float32))
    shared = np.asarray(whole.shared_experts(y)._data)
    total = np.zeros_like(shared)
    for j in range(4):
        part = layer((4 * j, 4))
        src = dict(whole.named_parameters())
        for name, p in part.named_parameters():
            a = src[name]._data
            p._data = a[4 * j:4 * j + 4] if name.startswith("w_") else a
        total += np.asarray(part(y)._data) - shared
    want = np.asarray(whole(y)._data)
    assert np.abs(total + shared - want).max() < 1e-5 * np.abs(want).max()
    # and the reference's routing, written out, gives the same layer
    ridx, rg, _ = R.route(jnp.asarray(np.asarray(y._data)),
                          src["router.weight"]._data,
                          src["e_score_correction_bias"]._data, top_k=4,
                          scale=2.5, n_group=4, topk_group=2, prec="f32")
    gates = jnp.sum(jax.nn.one_hot(ridx, e) * rg[..., None], -2)
    ref = R.expert_group(jnp.asarray(np.asarray(y._data)), gates,
                         src["w_gate"]._data, src["w_up"]._data,
                         src["w_down"]._data, "f32")
    assert np.abs(np.asarray(ref) + shared - want).max() \
        < 1e-5 * np.abs(want).max()


def test_the_latent_config_takes_a_full_rank_query_and_groups():
    cfg = LatentMoEConfig.tiny(q_lora_rank=None, n_group=4, topk_group=2)
    layer = km.lm.LatentMoEDecoderLayer(cfg, 1)
    assert hasattr(layer.self_attn, "q_proj")
    assert not hasattr(layer.self_attn, "q_a_proj")
    assert layer.mlp.groups == (4, 2)


# -- the model and the engine -------------------------------------------------------

def test_the_layout_and_the_refusals(built):
    model, _ = built
    assert [lyr.kind for lyr in model.layers] == ["kda", "kda", "mla",
                                                  "kda"]
    pub = KdaMoEConfig()
    kinds = [pub.kind(i) for i in range(42)]
    assert kinds.count("mla") == 7 and kinds[5] == "mla"
    lc = model.layers[0].paged_cache
    assert lc.state[0] == (
        "S", (4, 16, 16), "float32")
    assert model.layers[2].paged_cache.pool == "full"
    with pytest.raises(NotImplementedError, match="swiglu limit"):
        KdaMoEConfig(num_hidden_layers=36,
                     expert_swiglu_limit_list=[0] * 35 + [4] * 7)
    KdaMoEConfig(num_hidden_layers=7,
                 expert_swiglu_limit_list=[0] * 35 + [4] * 7)
    with pytest.raises(NotImplementedError, match="kda_lower_bound"):
        KdaMoEConfig(kda_lower_bound=-6)


def test_forward_is_the_references(built):
    model, w = built
    ids = prompt(40)[None]
    with P.no_grad():
        got = np.asarray(model(P.to_tensor(ids))._data)[0]
    want = reference_rows(w, ids[0], np.arange(40))
    assert np.abs(want).max() > 0.3          # logits that say something
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("plen", [5, 24, 41])
def test_alone_chunked_prefill_then_decode_is_the_references(built, plen):
    """Every logit of every step's sampled row: the prompt in chunks of
    24 (sub-chunks of 16 padded), then decode rows through the lane
    state."""
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


def test_in_a_crowd_and_what_the_counters_count(built):
    model, w = built
    requests = [(prompt(n, seed=1), m) for n, m in
                [(37, 9), (5, 12), (20, 7), (50, 6), (3, 10)]]
    eng, served = serve(model, requests)
    gap, err = worst_error(w, requests, served)
    assert gap < TOL and err < TOL
    m = eng.metrics
    assert m.preemptions.value == 0
    # three KDA layers a step; the scan counters count none of them
    assert m.delta_layer_steps.value == 3 * m.step_dispatches.value
    assert m.ssm_layer_steps.value == m.ssm_state_resets.value == 0
    # every prompt token went through the chunk form in each KDA layer
    assert m.delta_rows_chunked.value == 3 * sum(len(p) for p, _ in requests)
    # a lane-step a live lane a layer: prompts' chunks and decode rows
    assert m.delta_lane_steps.value >= 3 * sum(n - 1 for _, n in requests)
    # the expert layers' counts are of the 16 held: all of them here
    assert m.moe_layer_steps.value == 3 * m.step_dispatches.value
    assert m.moe_experts_hit.value <= 16 * m.moe_layer_steps.value


def test_a_preempted_request_is_recomputed_to_the_same_logits(built):
    model, w = built
    requests = [(prompt(n, seed=2), m) for n, m in
                [(22, 16), (19, 16), (25, 16)]]
    # 17 pages of 4 cannot hold three lanes of up to 41 tokens
    eng, served = serve(model, requests, num_pages=18, watermark_frac=0.0)
    assert eng.metrics.preemptions.value > 0
    gap, err = worst_error(w, requests, served)
    assert gap < TOL and err < TOL


def test_the_decode_kernel_serves_the_plain_routes_streams(monkeypatch):
    """At a head width that tiles (2 heads of 128) the decode rows take
    the kernel, run here in interpret mode, which reads and writes each
    lane's state by its slot: the same requests as through the plain
    route give the same tokens, and log-probabilities to rounding."""
    model, _ = build({**CFG, "head_dim": 128, "num_attention_heads": 2,
                      "num_key_value_heads": 2})
    requests = [(prompt(n, seed=3), m) for n, m in
                [(30, 8), (5, 10), (17, 6), (2, 9)]]
    dr.reset_dispatch_stats()
    _, want = serve(model, requests)
    assert dr.dispatch_stats()["kernel"] == 0
    monkeypatch.setattr(dr, "_FORCE_INTERPRET", True)
    _, got = serve(model, requests)
    assert dr.dispatch_stats()["kernel"] > 0
    for i in range(len(requests)):
        assert [t for t, _ in got[i]] == [t for t, _ in want[i]]
        assert max(abs(a - b) for (_, a), (_, b) in zip(got[i], want[i])) \
            < 1e-5


# -- planted: what the tolerance has to catch ---------------------------------------

def test_a_bfloat16_state_fails_the_tolerance(built, monkeypatch):
    model, w = built
    real = km.KdaMoEDecoderLayer.paged_cache.fget

    def low(self):
        lc = real(self)
        if not lc.state:
            return lc
        return LayerCache(state=tuple(
            (n, s, "bfloat16") for n, s, _ in lc.state))

    monkeypatch.setattr(km.KdaMoEDecoderLayer, "paged_cache",
                        property(low))
    requests = [(prompt(40, seed=1), 20)]
    _, served = serve(model, requests)
    gap, err = worst_error(w, requests, served)
    assert err > 4 * TOL


def test_a_state_not_carried_fails_the_tolerance(built, monkeypatch):
    model, w = built
    real = km.KimiDeltaAttention.run

    def forgetful(self, u, tail, state, live=None, n_live=None):
        return real(self, u, tail, jnp.zeros_like(state), live, n_live)

    monkeypatch.setattr(km.KimiDeltaAttention, "run", forgetful)
    requests = [(prompt(40, seed=1), 20)]
    _, served = serve(model, requests)
    gap, err = worst_error(w, requests, served)
    assert err > 100 * TOL
