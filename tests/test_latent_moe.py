"""``models/latent_moe.py`` and ``incubate/moe.py::DroplessMoE`` against
the plain reference (``benchmark/harness/reference_latent_moe.py``) on
seeded weights at a small size in float32: the model's forward, chunked
prefill and decode through the latent page pool of ``ServingEngine``, the
two attention forms, the router, the shares of the expert layer, and what
the engine refuses for a latent pool."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as P
from benchmark.harness import reference_latent_moe as R
from benchmark.harness import weights_latent_moe as W
from paddle_tpu.incubate.moe import (DroplessMoE, dropless_route,
                                     routing_counts)
from paddle_tpu.models import LatentMoEConfig, LatentMoEForCausalLM
from paddle_tpu.models import latent_moe as lm
from paddle_tpu.serving import ServingEngine

CFG = dict(vocab_size=320, hidden_size=128, intermediate_size=256,
           moe_intermediate_size=64, num_hidden_layers=3,
           num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           n_routed_experts=16, num_experts_per_tok=4, n_shared_experts=1,
           first_k_dense_replace=1, routed_scaling_factor=2.5,
           norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=32e6,
           max_position_embeddings=256, torch_dtype="float32",
           initializer_range=0.02)
ENGINE = dict(page_size=4, num_pages=128, max_batch=4, prefill_chunk=8,
              max_seq_len=64)
F32 = jnp.float32


def place(model, w, names):
    params = dict(model.named_parameters())
    assert set(names.values()) == set(params)
    for path, name in names.items():
        p = params[name]
        p._data = W.get(w, path)
        if hasattr(p, "_lazy_init"):
            del p._lazy_init
    for lyr in model.sublayers(include_self=True):
        lyr.__dict__["_has_lazy_params"] = False


@pytest.fixture(scope="module")
def built():
    w = W.make(11, CFG)
    with P.LazyGuard():
        model = LatentMoEForCausalLM(LatentMoEConfig.from_published(CFG))
    place(model, w, W.program_names(CFG))
    model.eval()
    return model, w


def test_forward_agrees_with_the_reference(built):
    model, w = built
    ids = np.random.default_rng(5).integers(0, 320, (1, 64)).astype(np.int32)
    fwd = P.jit.to_static(lambda t: model(t))
    got = np.asarray(fwd(P.to_tensor(ids))._data)[0]
    want = np.asarray(R.logits_at(w, CFG, ids, np.arange(64), block=16,
                                  group_size=4))
    assert want.std() > 0.1
    assert np.abs(got - want).max() < 2e-5
    # experts in groups equal experts at once
    once = np.asarray(R.logits_at(w, CFG, ids, np.arange(64), block=64,
                                  group_size=16))
    assert np.abs(once - want).max() < 1e-5


def serve(model, prompts, max_new, **kw):
    events = {}

    def on_event(ev):
        if ev["type"] == "token":
            events.setdefault(ev["req_id"], []).append(
                (ev["token"], ev.get("logprob")))

    eng = ServingEngine(model, on_event=on_event, eos_token_id=None,
                        **{**ENGINE, **kw})
    rids = [eng.add_request(p, max_new_tokens=max_new, logprobs=True)
            for p in prompts]
    eng.run()
    return eng, [events[r] for r in rids]


@pytest.fixture(scope="module")
def served(built):
    model, _ = built
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 320, n).astype(np.int32)
               for n in (19, 7, 30, 12, 9)]     # 30 = four chunks of 8
    eng, out = serve(model, prompts, 10)
    return eng, prompts, out


def test_chunked_prefill_and_decode_through_the_latent_pool(built, served):
    _, w = built
    eng, prompts, out = served
    assert eng.metrics.step_program_classes.value == 2
    for prompt, evs in zip(prompts, out):
        toks = np.asarray([t for t, _ in evs])
        lps = np.asarray([lp for _, lp in evs], np.float32)
        assert len(toks) == 10
        ids = np.zeros((1, 64), np.int32)
        ids[0, :len(prompt) + 10] = np.concatenate([prompt, toks])
        pos = len(prompt) - 1 + np.arange(10)
        ref = np.asarray(R.logits_at(w, CFG, ids, pos, block=16))
        pick = ref[np.arange(10), toks]
        assert (ref.max(-1) - pick).max() < 1e-5      # the greedy token
        lsm = np.asarray(jax.nn.log_softmax(ref, -1))[np.arange(10), toks]
        assert np.abs(lps - lsm).max() < 2e-5


def test_the_cache_holds_one_latent_entry_a_token_a_layer(served):
    eng, prompts, _ = served
    cache = eng.cache
    assert cache.latent and cache.v_pages == []
    assert [tuple(p.shape) for p in cache.k_pages] == [(128, 4, 32 + 8)] * 3
    assert cache.bytes_per_token == (32 + 8) * 4 * 3
    assert eng.metrics.cache_bytes_per_token.value == cache.bytes_per_token
    by_head = cache.page_bytes_per_page(3, 4, 24, 4, "float32")
    assert cache.bytes_total == 128 * 4 * 480 < 128 * by_head


def test_routing_is_counted_on_the_device(served):
    eng, prompts, _ = served
    m = eng.metrics
    # every prompt token and every decoded token but a request's last
    # passes 2 expert layers and meets 4 experts in each
    tokens = sum(len(p) for p in prompts) + 5 * 9
    assert m.moe_assignments.value == tokens * 4 * 2
    assert m.moe_layer_steps.value == 2 * m.step_dispatches.value
    assert 0 < m.moe_experts_hit.value <= 16 * m.moe_layer_steps.value
    assert m.moe_expert_load_max.value >= m.moe_layer_steps.value
    rec = [r for r in eng.trace.flight.dump() if r["kind"] == "ragged_step"]
    assert rec and all(0 < r["experts_hit"] <= 32 for r in rec)


def test_a_request_served_alone_gets_the_same_stream(built, served):
    """No schedule in a token: the crowd of five (four chunks of 8 for
    the longest, decode lanes beside them) gives each request what it
    gets alone, its prompt one chunk."""
    model, _ = built
    _, prompts, out = served
    for p, b in zip(prompts[:2], out[:2]):
        _, (a,) = serve(model, [p], 10, max_batch=1,
                        prefill_chunk=len(p))
        assert [t for t, _ in a] == [t for t, _ in b]
        assert np.allclose([lp for _, lp in a], [lp for _, lp in b],
                           atol=1e-5)


def test_absorbed_attention_equals_expanded(built):
    model, _ = built
    at = model.layers[1].self_attn
    cfg = model.cfg
    rng = np.random.default_rng(7)
    y = jnp.asarray(rng.normal(size=(1, 24, 128)), F32)
    want = np.asarray(at(P.to_tensor(y))._data)[0]
    # the same 24 tokens through a pool of 8 pages of 4, written in order
    pool = jnp.zeros((8, 4, cfg.latent_dim), F32)
    slots = jnp.arange(24, dtype=jnp.int32) + 4          # pages 1..6
    pt = jnp.broadcast_to(jnp.arange(1, 9, dtype=jnp.int32) % 8, (24, 8))
    pos = jnp.arange(24, dtype=jnp.int32)
    got, pool = at.paged_forward(P.to_tensor(y), pos[None], pool, slots,
                                 pt, pos + 1)
    assert np.abs(np.asarray(got._data)[0] - want).max() < 2e-6
    assert float(jnp.abs(pool[0]).max()) == 0.0          # scratch untouched
    assert float(jnp.abs(pool[1:7]).min()) > 0.0


def test_interleaved_rope_is_the_references():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 9, 3, 8)), F32)
    pos = jnp.broadcast_to(jnp.arange(9), (2, 9))
    got = lm.rope_interleaved(x, pos[..., None], 32e6)
    assert np.abs(np.asarray(got - R.rope_interleaved(x, 32e6))).max() < 1e-6
    # pairs (2i, 2i+1): a rotation keeps each pair's norm, and position 0
    # is the identity
    pair = lambda a: np.asarray(a).reshape(2, 9, 3, 4, 2)    # noqa: E731
    assert np.allclose(np.linalg.norm(pair(got), axis=-1),
                       np.linalg.norm(pair(x), axis=-1), atol=1e-5)
    assert np.allclose(np.asarray(got)[:, 0], np.asarray(x)[:, 0])


def test_the_bias_moves_the_choice_and_not_the_weights():
    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.normal(size=(32, 128)), F32)
    wr = jnp.asarray(0.02 * rng.normal(size=(128, 16)), F32)
    zero = jnp.zeros(16, F32)
    idx0, g0 = dropless_route(y, wr, zero, 4, 2.5)
    assert np.allclose(np.asarray(g0).sum(-1), 2.5, atol=1e-5)
    bias = zero.at[5].set(10.0)              # expert 5 chosen by everyone
    idx1, g1 = dropless_route(y, wr, bias, 4, 2.5)
    assert (np.asarray(idx1) == 5).any(-1).all()
    assert not (np.asarray(idx0) == 5).any(-1).all()
    assert np.allclose(np.asarray(g1).sum(-1), 2.5, atol=1e-5)
    # the weight of expert 5 is its own score's share, not the bias's
    s = np.asarray(jax.nn.sigmoid(y @ wr))
    pick = np.take_along_axis(s, np.asarray(idx1), -1)
    want = 2.5 * pick / pick.sum(-1, keepdims=True)
    assert np.abs(np.asarray(g1) - want).max() < 1e-5
    ref_idx, ref_g = R.route(y, wr, bias, top_k=4, scale=2.5, norm=True,
                             prec="f32")
    assert (np.asarray(ref_idx) == np.asarray(idx1)).all()
    assert np.abs(np.asarray(ref_g) - np.asarray(g1)).max() < 1e-6


def moe_share(lw, first, count, shared, top_k=4):
    layer = DroplessMoE(128, 64, 16, top_k, routed_scaling_factor=2.5,
                        shared_width=64 if shared else 0,
                        experts_held=(first, count))
    sl = slice(first, first + count)
    layer.router.weight._data = lw["router"]
    layer.e_score_correction_bias._data = lw["router_bias"]
    layer.w_gate._data = lw["w_gate"][sl]
    layer.w_up._data = lw["w_up"][sl]
    layer.w_down._data = lw["w_down"][sl]
    if shared:
        for n in ("gate", "up", "down"):
            getattr(layer.shared_experts, f"{n}_proj").weight._data = \
                lw[f"shared_{n}"]
    return layer


def reference_layer(y, lw, top_k=4):
    """E_shared(y) + sum_i g_i E_i(y) by the plain reference, uncut."""
    idx, g = R.route(y, lw["router"], lw["router_bias"], top_k=top_k,
                     scale=2.5, norm=True, prec="f32")
    gates = jnp.sum(jax.nn.one_hot(idx, 16, dtype=F32) * g[..., None], -2)
    return R.swiglu(y, lw["shared_gate"], lw["shared_up"],
                    lw["shared_down"], "f32") + R.expert_group(
        y, gates, lw["w_gate"], lw["w_up"], lw["w_down"], "f32")


def test_the_shares_add_up_to_the_uncut_layer(built):
    _, w = built
    lw = w["layers"][1]
    y = jnp.asarray(np.random.default_rng(4).normal(size=(1, 40, 128)), F32)
    want = np.asarray(reference_layer(y, lw))
    parts = [np.asarray(moe_share(lw, 4 * i, 4, shared=(i == 0))(
        P.to_tensor(y))._data) for i in range(4)]
    assert all(np.abs(p).max() > 1e-3 for p in parts)
    assert np.abs(sum(parts) - want).max() < 2e-6
    whole = np.asarray(moe_share(lw, 0, 16, True)(P.to_tensor(y))._data)
    assert np.abs(whole - want).max() < 2e-6
    # a share's counts are of its own experts only
    idx, _ = dropless_route(y[0], lw["router"], lw["router_bias"], 4, 2.5)
    counts = [np.asarray(routing_counts(idx, 4 * i, 4)) for i in range(4)]
    assert sum(c[0] for c in counts) == 40 * 4
    valid = jnp.arange(40) < 30
    assert int(routing_counts(idx, 0, 16, valid)[0]) == 30 * 4


def test_no_token_is_dropped_when_all_choose_one_expert(built):
    _, w = built
    lw = dict(w["layers"][2])
    lw["router_bias"] = jnp.zeros(16, F32).at[3].set(10.0)
    y = jnp.asarray(np.random.default_rng(6).normal(size=(1, 64, 128)), F32)
    layer = moe_share(lw, 0, 16, True, top_k=1)
    out, counts = layer.forward_counted(P.to_tensor(y))
    assert np.asarray(counts).tolist() == [64, 1, 64, 1]
    want = np.asarray(reference_layer(y, lw, top_k=1))
    assert np.abs(np.asarray(out._data) - want).max() < 2e-6
    # every one of the 64 tokens got expert 3's answer at weight 2.5
    only = 2.5 * np.asarray(R.swiglu(y, lw["w_gate"][3], lw["w_up"][3],
                                     lw["w_down"][3], "f32"))
    shared = np.asarray(R.swiglu(y, lw["shared_gate"], lw["shared_up"],
                                 lw["shared_down"], "f32"))
    assert np.abs(np.asarray(out._data) - shared - only).max() < 2e-6


@pytest.mark.parametrize("kw, names", [
    (dict(cache_dtype="int8"), "int8 latent cache"),
    (dict(tp_degree=2), "tensor parallelism"),
    (dict(draft_model="self"), "draft model"),
    (dict(host_pool="pool"), "kvtier"),
])
def test_what_the_latent_pool_does_not_build_is_refused(built, kw, names):
    model, _ = built
    if kw.get("draft_model") == "self":
        kw = dict(draft_model=model)
    if kw.get("host_pool") == "pool":
        from paddle_tpu.serving.kvtier import HostPagePool
        kw = dict(host_pool=HostPagePool(1 << 20),
                  prefix_cache=True)
    with pytest.raises(NotImplementedError, match=names):
        ServingEngine(model, **{**ENGINE, **kw})


def test_page_shipping_is_refused_by_name(served):
    eng, prompts, _ = served
    for call in (lambda: eng.cache.export_pages(0),
                 lambda: eng.cache.import_pages(99, {}, [], []),
                 lambda: eng.cache.export_prefix_pages(prompts[0]),
                 lambda: eng.cache.import_prefix_pages({}, [], []),
                 lambda: eng.export_prefix(prompts[0])):
        with pytest.raises(NotImplementedError, match="pagewire / disagg"):
            call()


def test_a_config_whose_equations_are_not_written_is_refused():
    with pytest.raises(NotImplementedError, match="n_group"):
        LatentMoEConfig.tiny(n_group=8, topk_group=4)
    with pytest.raises(NotImplementedError, match="scoring_func"):
        LatentMoEConfig.tiny(scoring_func="softmax")
    with pytest.raises(ValueError, match="experts_held"):
        DroplessMoE(128, 64, 16, 4, experts_held=(12, 8))


def test_the_validation_message_and_the_llama_geometry():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    with pytest.raises(TypeError, match="paged_forward"):
        ServingEngine(object())
    llama = LlamaForCausalLM(LlamaConfig.tiny())
    assert ServingEngine._cache_geometry(llama.cfg, llama.llama) == (
        4, 16, None)
