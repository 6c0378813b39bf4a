"""CLIP family parity vs the `transformers` torch oracle (weight
transplant — same strategy as tests/test_models_vit_t5.py)."""
import numpy as np
import pytest

import paddle_tpu as P

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

# cert marker (ADVICE.md #3): under PADDLE_TPU_CERT_RUN=1 the conftest
# makes these oracle deps mandatory (missing -> run FAILS, not skips)
pytestmark = pytest.mark.certification


def _t(a):
    return P.to_tensor(np.asarray(a.detach().numpy()))


def _set(p, a):
    p.set_value(_t(a))


def _tiny_hf():
    from transformers import CLIPConfig as HFConfig, CLIPModel
    cfg = HFConfig(
        text_config=dict(vocab_size=99, hidden_size=64,
                         intermediate_size=128, num_hidden_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=24, eos_token_id=98,
                         pad_token_id=0, bos_token_id=97),
        vision_config=dict(hidden_size=64, intermediate_size=128,
                           num_hidden_layers=2, num_attention_heads=4,
                           image_size=32, patch_size=8),
        projection_dim=32)
    torch.manual_seed(3)
    return CLIPModel(cfg).eval()


def _copy_layer(oo, ho):
    at = ho.self_attn
    _set(oo.self_attn.q.weight, at.q_proj.weight.T)
    _set(oo.self_attn.q.bias, at.q_proj.bias)
    _set(oo.self_attn.k.weight, at.k_proj.weight.T)
    _set(oo.self_attn.k.bias, at.k_proj.bias)
    _set(oo.self_attn.v.weight, at.v_proj.weight.T)
    _set(oo.self_attn.v.bias, at.v_proj.bias)
    _set(oo.self_attn.o.weight, at.out_proj.weight.T)
    _set(oo.self_attn.o.bias, at.out_proj.bias)
    _set(oo.layer_norm1.weight, ho.layer_norm1.weight)
    _set(oo.layer_norm1.bias, ho.layer_norm1.bias)
    _set(oo.layer_norm2.weight, ho.layer_norm2.weight)
    _set(oo.layer_norm2.bias, ho.layer_norm2.bias)
    _set(oo.fc1.weight, ho.mlp.fc1.weight.T)
    _set(oo.fc1.bias, ho.mlp.fc1.bias)
    _set(oo.fc2.weight, ho.mlp.fc2.weight.T)
    _set(oo.fc2.bias, ho.mlp.fc2.bias)


def _transplant(hf):
    from paddle_tpu.models.clip import CLIPConfig, CLIPModel
    ours = CLIPModel(CLIPConfig.tiny())
    ours.eval()
    v_o, v_h = ours.vision_model, hf.vision_model
    v_o.class_embedding.set_value(_t(v_h.embeddings.class_embedding))
    _set(v_o.patch_embedding.weight,
         v_h.embeddings.patch_embedding.weight)
    _set(v_o.position_embedding.weight,
         v_h.embeddings.position_embedding.weight)
    _set(v_o.pre_layernorm.weight, v_h.pre_layrnorm.weight)
    _set(v_o.pre_layernorm.bias, v_h.pre_layrnorm.bias)
    for oo, ho in zip(v_o.layers, v_h.encoder.layers):
        _copy_layer(oo, ho)
    _set(v_o.post_layernorm.weight, v_h.post_layernorm.weight)
    _set(v_o.post_layernorm.bias, v_h.post_layernorm.bias)

    t_o, t_h = ours.text_model, hf.text_model
    _set(t_o.token_embedding.weight,
         t_h.embeddings.token_embedding.weight)
    _set(t_o.position_embedding.weight,
         t_h.embeddings.position_embedding.weight)
    for oo, ho in zip(t_o.layers, t_h.encoder.layers):
        _copy_layer(oo, ho)
    _set(t_o.final_layer_norm.weight, t_h.final_layer_norm.weight)
    _set(t_o.final_layer_norm.bias, t_h.final_layer_norm.bias)

    _set(ours.visual_projection.weight, hf.visual_projection.weight.T)
    _set(ours.text_projection.weight, hf.text_projection.weight.T)
    ours.logit_scale.set_value(_t(hf.logit_scale.reshape(1)))
    return ours


def _batch(rng, b=3):
    px = rng.standard_normal((b, 3, 32, 32)).astype(np.float32)
    ids = np.concatenate(
        [np.full((b, 1), 97), rng.integers(1, 97, (b, 8)),
         np.full((b, 1), 98), np.zeros((b, 2))], axis=1).astype(np.int64)
    return px, ids


class TestCLIPParity:
    @pytest.fixture(scope="class")
    def pair(self):
        hf = _tiny_hf()
        return hf, _transplant(hf)

    def test_image_features_match_oracle(self, pair):
        hf, ours = pair
        px, _ = _batch(np.random.default_rng(0))
        with torch.no_grad():
            ref = hf.get_image_features(torch.tensor(px)).numpy()
        got = np.asarray(ours.get_image_features(P.to_tensor(px))._data)
        np.testing.assert_allclose(got, ref, atol=2e-4, rtol=1e-3)

    def test_text_features_match_oracle(self, pair):
        hf, ours = pair
        _, ids = _batch(np.random.default_rng(1))
        with torch.no_grad():
            ref = hf.get_text_features(torch.tensor(ids)).numpy()
        got = np.asarray(ours.get_text_features(
            P.to_tensor(ids.astype(np.int32)))._data)
        np.testing.assert_allclose(got, ref, atol=2e-4, rtol=1e-3)

    def test_similarity_logits_match_oracle(self, pair):
        hf, ours = pair
        px, ids = _batch(np.random.default_rng(2))
        with torch.no_grad():
            out = hf(input_ids=torch.tensor(ids),
                     pixel_values=torch.tensor(px))
            ref_i = out.logits_per_image.numpy()
            ref_t = out.logits_per_text.numpy()
        li, lt = ours(P.to_tensor(ids.astype(np.int32)),
                      P.to_tensor(px))
        np.testing.assert_allclose(np.asarray(li._data), ref_i,
                                   atol=3e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(lt._data), ref_t,
                                   atol=3e-4, rtol=1e-3)

    def test_contrastive_training_decreases_loss(self):
        # fresh model: training must not mutate the class-scoped
        # transplanted fixture the parity tests compare to the oracle
        from paddle_tpu.models.clip import (CLIPConfig, CLIPModel,
                                            clip_loss)
        from paddle_tpu.optimizer import AdamW
        ours = CLIPModel(CLIPConfig.tiny())
        ours.train()
        opt = AdamW(learning_rate=1e-3, parameters=ours.parameters())
        rng = np.random.default_rng(3)
        px, ids = _batch(rng, b=4)
        pxt = P.to_tensor(px)
        idt = P.to_tensor(ids.astype(np.int32))
        # the subject is the family, not the eager path: the loss is one
        # traced program (`to_static`) and backward() differentiates that
        # one program — not one XLA compile an op
        loss_of = P.jit.to_static(
            lambda idt, pxt: clip_loss(ours(idt, pxt)[1]))
        losses = []
        for _ in range(8):
            loss = loss_of(idt, pxt)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.8, losses
        ours.eval()


class TestCLIPGlobalLoss:
    """Global-batch contrastive loss on the virtual device mesh: value
    and GRADIENT parity vs the single-process full-batch oracle. The
    gradient check is the load-bearing part — it proves the gather's
    backward psum_scatters cross-rank cotangents (rank s's loss depends
    on rank r's features) instead of slicing them away."""

    def test_matches_full_batch_oracle(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as Pspec
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed._axis import axis_env
        from paddle_tpu.models.clip import clip_global_loss

        rng = np.random.default_rng(7)
        n_dev, b_local, d = 4, 2, 8
        img = jnp.asarray(rng.standard_normal(
            (n_dev * b_local, d)).astype(np.float32))
        txt = jnp.asarray(rng.standard_normal(
            (n_dev * b_local, d)).astype(np.float32))
        scale = jnp.asarray([0.7], np.float32)

        def oracle(i, t, s):
            loss = clip_global_loss(P.Tensor(i), P.Tensor(t),
                                    P.Tensor(s), group=None)
            return loss._data.reshape(())

        ref, ref_vjp = jax.vjp(oracle, img, txt, scale)
        gi_ref, gt_ref, gs_ref = ref_vjp(jnp.ones(()))

        mesh = Mesh(np.array(jax.devices()[:n_dev]), ("dp",))
        g = dist.new_group(list(range(n_dev)), axis_name="dp")

        def body(il, tl):
            def f(i, t, s):
                loss = clip_global_loss(P.Tensor(i), P.Tensor(t),
                                        P.Tensor(s), group=g)
                return jax.lax.pmean(loss._data.reshape(()), "dp")
            val, vjp = jax.vjp(f, il, tl, scale)
            gi, gt, gs = vjp(jnp.ones(()))
            return val[None], gi, gt, gs[None]

        # jitted: the subject is the collective's value and vjp, and a
        # bare shard_map compiles its body one op at a time (~240 programs)
        fm = jax.jit(jax.shard_map(body, mesh=mesh,
                                   in_specs=(Pspec("dp"), Pspec("dp")),
                                   out_specs=(Pspec("dp"), Pspec("dp"),
                                              Pspec("dp"), Pspec("dp"))))
        with axis_env("dp"):
            vals, gi, gt, gs = fm(img, txt)
        # every rank's pmean equals the global loss
        np.testing.assert_allclose(np.asarray(vals),
                                   np.full(n_dev, float(ref)), rtol=1e-5)
        # vjp of the pmean'd loss wrt the local shard == oracle grad
        # rows for that shard (cross-rank terms included)
        np.testing.assert_allclose(np.asarray(gi), np.asarray(gi_ref),
                                   atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(gt), np.asarray(gt_ref),
                                   atol=1e-5, rtol=1e-4)
        # logit_scale is a replicated capture: shard_map psums its
        # cotangent, so EVERY rank holds the full global grad
        np.testing.assert_allclose(np.asarray(gs).ravel(),
                                   np.full(n_dev,
                                           float(np.asarray(gs_ref)[0])),
                                   atol=1e-5, rtol=1e-4)
