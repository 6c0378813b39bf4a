"""ConvNeXt family parity vs the `transformers` torch oracle (weight
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
    from transformers import ConvNextConfig, ConvNextModel
    cfg = ConvNextConfig(num_channels=3, patch_size=4,
                         hidden_sizes=[16, 32, 64, 96],
                         depths=[2, 2, 2, 2], image_size=32,
                         drop_path_rate=0.0)
    torch.manual_seed(5)
    return ConvNextModel(cfg).eval()


def _transplant(hf):
    from paddle_tpu.vision.models.convnext import (ConvNeXt,
                                                   ConvNeXtConfig)
    ours = ConvNeXt(ConvNeXtConfig.tiny(num_classes=0))
    ours.eval()
    _set(ours.patch_embed.weight, hf.embeddings.patch_embeddings.weight)
    _set(ours.patch_embed.bias, hf.embeddings.patch_embeddings.bias)
    _set(ours.embed_norm.norm.weight, hf.embeddings.layernorm.weight)
    _set(ours.embed_norm.norm.bias, hf.embeddings.layernorm.bias)
    for i, hs in enumerate(hf.encoder.stages):
        if i > 0:
            ds = hs.downsampling_layer
            _set(ours.down_norms[i - 1].norm.weight, ds[0].weight)
            _set(ours.down_norms[i - 1].norm.bias, ds[0].bias)
            _set(ours.down_convs[i - 1].weight, ds[1].weight)
            _set(ours.down_convs[i - 1].bias, ds[1].bias)
        for hb, ob in zip(hs.layers, ours.stages[i]):
            _set(ob.dwconv.weight, hb.dwconv.weight)
            _set(ob.dwconv.bias, hb.dwconv.bias)
            _set(ob.layernorm.weight, hb.layernorm.weight)
            _set(ob.layernorm.bias, hb.layernorm.bias)
            _set(ob.pwconv1.weight, hb.pwconv1.weight.T)
            _set(ob.pwconv1.bias, hb.pwconv1.bias)
            _set(ob.pwconv2.weight, hb.pwconv2.weight.T)
            _set(ob.pwconv2.bias, hb.pwconv2.bias)
            _set(ob.layer_scale_parameter, hb.layer_scale_parameter)
    _set(ours.norm.weight, hf.layernorm.weight)
    _set(ours.norm.bias, hf.layernorm.bias)
    return ours


class TestConvNeXtParity:
    @pytest.fixture(scope="class")
    def pair(self):
        hf = _tiny_hf()
        return hf, _transplant(hf)

    def test_pooled_features_match_oracle(self, pair):
        hf, ours = pair
        x = np.random.default_rng(0).standard_normal(
            (2, 3, 32, 32)).astype(np.float32)
        with torch.no_grad():
            ref = hf(torch.tensor(x)).pooler_output.numpy()
        got = np.asarray(ours.forward_features(P.to_tensor(x))._data)
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, atol=2e-4, rtol=1e-3)

    def test_trains_and_layer_scale_learns(self):
        from paddle_tpu.vision.models.convnext import (ConvNeXt,
                                                       ConvNeXtConfig)
        from paddle_tpu.optimizer import AdamW
        import paddle_tpu.nn.functional as F
        m = ConvNeXt(ConvNeXtConfig.tiny())
        m.train()
        scale = m.stages[0][0].layer_scale_parameter
        before = np.asarray(scale._data).copy()
        opt = AdamW(learning_rate=2e-3, parameters=m.parameters())
        rng = np.random.default_rng(1)
        x = P.to_tensor(rng.standard_normal((4, 3, 32, 32))
                        .astype(np.float32))
        y = P.to_tensor(rng.integers(0, 10, (4,)).astype(np.int64))
        # the subject is the family, not the eager path: the loss is one
        # traced program (`to_static`) and backward() differentiates that
        # one program — not one XLA compile an op
        loss_of = P.jit.to_static(lambda x, y: F.cross_entropy(m(x), y))
        losses = []
        for _ in range(6):
            loss = loss_of(x, y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.9, losses
        assert np.abs(np.asarray(scale._data) - before).max() > 1e-7

    def test_builders(self):
        from paddle_tpu.vision.models import convnext_tiny
        # described, not initialised (LazyGuard): the asserts read shapes
        # and structure; the same classes' initialisers run in this file's
        # tiny-config tests, not again at 28-86 M parameters
        with P.LazyGuard():
            m = convnext_tiny(num_classes=7)
        assert m.head.weight.shape[1] == 7
        assert len(m.stages) == 4
